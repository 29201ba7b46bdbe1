"""Solver part: the ``round_program.dispatch`` spans (the jitted window
program called until it returns), per solver round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.solver(o, "round_program.dispatch")
