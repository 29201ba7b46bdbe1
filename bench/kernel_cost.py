"""Operations and bytes of the scheduler's two Pallas kernels, from their
call shapes. Both are memory-bound: each reads a (T, M) float32 matrix
once and does a few vector operations per element, far below the chip's
peak operations per byte, so their roofline time is bytes over the HBM
bandwidth.
"""

from __future__ import annotations

import json
import os

F32 = 4
I32 = 4


def costmap_bytes(n_tasks: int, n_machines: int) -> int:
    """``costmap_pallas(perf_idx (T,), latency (T, M)) -> cost (T, M)``:
    read the latency matrix and the model ids, write the int32 costs."""
    return n_tasks * n_machines * (F32 + I32) + n_tasks * I32


def costmap_flops(n_tasks: int, n_machines: int) -> int:
    """Per element: quantise (divide, round, multiply, clip: 4), a cubic by
    Horner (6), threshold select and clip (3), cost (divide, round,
    multiply: 3)."""
    return 16 * n_tasks * n_machines


def bid_bytes(n_tasks: int, n_machines: int) -> int:
    """``bid_top2_pallas(values (T, M), price1 (M,), price2 (M,))``: read the
    value matrix and both price vectors, write index, best and second."""
    return n_tasks * n_machines * F32 + 2 * n_machines * F32 + 3 * n_tasks * F32


def bid_flops(n_tasks: int, n_machines: int) -> int:
    """Per element: subtract the price, running max, arg-max bookkeeping and
    the runner-up (about 6 vector operations)."""
    return 6 * n_tasks * n_machines


def roofline_s(bytes_: float, flops: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take and which
    peak bounds it."""
    t_mem = bytes_ / float(peak["hbm_bytes_per_s"])
    t_op = flops / float(peak["bf16_flops_per_s"])
    return (t_mem, "memory") if t_mem >= t_op else (t_op, "compute")


def load_peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json "
            f"(known: {sorted(table)}); add its published row with a source"
        )
    return table[device_kind]
