"""Host-round part: the ``sim.build_state`` spans (the round's task
arrays and its (jobs, machines) latency rows), per round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.host_round(o, "sim.build_state")
