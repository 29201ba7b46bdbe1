"""Solver part: the ``round_program.upload`` spans (the window's arrays
and the policy scalars shipped to the device and waited for), per solver
round (program spans)."""

from metrics import _parts


def read(o):
    return _parts.solver(o, "round_program.upload")
