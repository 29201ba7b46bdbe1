"""95th percentile of the same samples as ``placement_p50_ms``."""

import numpy as np


def read(o):
    lat = o.run.latencies_ms
    if o.run.mode != "serve" or lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 95))
