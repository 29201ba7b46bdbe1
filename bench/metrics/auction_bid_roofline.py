"""Roofline share (%) of the ``bid_top2_pallas`` kernel: least time
(memory-bound) over its time in the device trace. One call per auction
iteration, on the round's task bucket."""

import kernel_cost
from metrics import _kernels


def read(o):
    return _kernels.roofline(
        o, "bid_top2_pallas", kernel_cost.bid_bytes, kernel_cost.bid_flops,
        per_iteration=True,
    )
