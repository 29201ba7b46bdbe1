"""Host-round part: the ``round_program.stack`` spans (the round padded
into its (tasks, jobs, machines) bucket), per round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.host_round(o, "round_program.stack")
