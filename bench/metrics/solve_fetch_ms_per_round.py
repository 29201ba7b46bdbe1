"""Solver part: the ``round_program.fetch`` spans (assignments and costs
brought back, convergence checked), per solver round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.solver(o, "round_program.fetch")
