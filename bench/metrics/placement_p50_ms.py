"""Median, over every task due in the window, of due time to placement
visible in the simulator's tables (host clock, wall-clock open loop)."""

import numpy as np


def read(o):
    lat = o.run.latencies_ms
    if o.run.mode != "serve" or lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 50))
