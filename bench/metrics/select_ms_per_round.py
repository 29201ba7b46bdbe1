"""Host-round part: the ``sim.select`` spans (the ready prefix of the
pending queue and the migration movers), per round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.host_round(o, "sim.select")
