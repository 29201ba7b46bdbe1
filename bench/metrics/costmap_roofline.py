"""Roofline share (%) of the ``costmap_pallas`` kernel: its least time
(memory-bound: bytes over HBM bandwidth) over its time in the device
trace. One call per solver round, on the round's task bucket."""

import kernel_cost
from metrics import _kernels


def read(o):
    return _kernels.roofline(
        o, "costmap_pallas", kernel_cost.costmap_bytes, kernel_cost.costmap_flops,
        per_iteration=False,
    )
