"""Host-round part: the ``sim.apply`` spans (placements and migrations
written to the simulator's tables), per round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.host_round(o, "sim.apply")
