"""Solver part: the ``round_program.sync`` spans (the auction iteration
counts brought back: the device's run plus the wait for it), per solver
round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.solver(o, "round_program.sync")
