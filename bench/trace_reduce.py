"""Reduce a JAX profiler trace of the measured window to device metrics.

Input: the ``.xplane.pb`` the profiler writes. `load_events` turns it
into plain event lists, which `reduce` reads (and which a test can build
by hand):

- ``device``: one list per device plane (``/device:TPU:n``), the events
  of its XLA op line: ``(start_ns, dur_ns, label)``, where ``label`` is
  the op's HLO text (``%costmap_pallas.2 = s32[...] custom-call(...)``),
  so a Pallas kernel is found by the name it was given;
- ``host``: the harness's own ``bench.*`` annotations on the host plane,
  ``(start_ns, dur_ns, name)``.

`reduce` computes, over the window (the ``bench.window`` annotation):

- busy seconds: the union of the device's op intervals, averaged over the
  devices; the idle share is 1 - busy / window;
- time and calls by kernel name (the op's name without its instance
  number);
- the device ops that took most time, by op name;
- idle time, divided among the ``bench.*`` annotations that overlap each
  gap (what the host was doing then), summed by name.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[float, float, str]  # (start_ns, dur_ns, label)

OP_LINES = ("XLA Ops",)


# Ops that enclose other ops' events on the same line (a loop, a branch):
# left out of the top-ops list, whose entries would otherwise overlap.
CONTAINERS = ("while", "conditional", "call")


def op_base(op: str) -> str:
    """``%costmap_pallas.2`` -> ``costmap_pallas``: the op's name without
    the HLO ``%`` and instance number (a kernel's name as it was given)."""
    name = op.lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def load_events(path: str) -> dict:
    """``{"device": [[Event, ...] per device], "host": [Event, ...]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: List[List[Event]] = []
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs: List[Event] = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    strs = [str(v) for _k, v in e.stats if isinstance(v, str)]
                    evs.append((float(e.start_ns), float(e.duration_ns),
                                " ".join([e.name] + strs)))
            device.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((float(e.start_ns), float(e.duration_ns), e.name))
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for s, d, _ in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


class _HostIndex:
    """The annotations inside the window (siblings, non-overlapping),
    looked up by time."""

    def __init__(self, host: Sequence[Event]):
        self.spans = sorted(host)
        self.starts = [s for s, _, _ in self.spans]

    def split(self, a: float, b: float) -> Dict[str, float]:
        """How ``[a, b)`` divides among the annotations it overlaps; the
        rest is ``host:unannotated``."""
        out: Dict[str, float] = {}
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        covered = 0.0
        while i < len(self.spans) and self.spans[i][0] < b:
            s, d, name = self.spans[i]
            part = min(b, s + d) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        if b - a - covered > 0:
            out["host:unannotated"] = out.get("host:unannotated", 0.0) + (b - a - covered)
        return out


def reduce(
    events: dict, kernels: Sequence[str] = (), top: int = 10
) -> Optional[dict]:
    """Device metrics of the window, or None when no device op ran."""
    host = list(events["host"])
    windows = [h for h in host if h[2] == "bench.window"]
    devices = [d for d in events["device"] if d]
    if not windows or not devices:
        return None
    w0, wd, _ = windows[0]
    w1 = w0 + wd
    inner = _HostIndex([h for h in host if h[2] != "bench.window"])
    busy_ns = 0.0
    gaps: Dict[str, float] = {}
    kernel_ns = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    ops: Dict[str, float] = {}
    for dev in devices:
        busy = union(_clip(dev, w0, w1))
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                for name, part in inner.split(a, b).items():
                    gaps[name] = gaps.get(name, 0.0) + part
        for s, d, label in dev:
            if s < w0 or s >= w1:
                continue
            op = label.split(" ", 1)[0]
            base = op_base(op)
            if base not in CONTAINERS:
                ops[op] = ops.get(op, 0.0) + d
            if base in kernel_ns:
                kernel_ns[base] += d
                kernel_calls[base] += 1
    n = len(devices)
    busy_s = busy_ns / n / 1e9
    window_s = wd / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n for k, v in kernel_calls.items()},
        "device_ops": [
            [k, v / n / 1e9] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v / n / 1e9] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
