"""A stand-in reference for the benchmark's own tests.

It declares the ``controller`` kind and both optional blocks, so that a
configuration naming it under ``reference`` runs through the whole
harness: the configuration and, for a what-if round, its lanes reach
``place``, and controller rounds are sampled and compared. Its answer is
a placeholder, not a reference: it places no pending task and keeps every
mover where it runs, so the comparison finds every task the program
placed or moved.
"""

import numpy as np

from reference import Cluster, Policy, RoundInputs  # noqa: F401  (the module's interface)

KINDS = ("place", "controller")
BLOCKS = ("plane", "scheduler")


def place(inp, cluster, policy, series, plane_seed, precision="float32",
          costs=None, config=None, kind="place", lanes=None):
    if config is None or "plane" not in config:
        raise ValueError("the stub reference expects the run's configuration")
    if kind not in KINDS:
        raise ValueError(f"the stub reference solves no {kind!r} round")
    if kind != "place" and not lanes:
        raise ValueError(f"a {kind!r} round reaches the reference without its lanes")
    return np.full(len(inp.task_job), -1, np.int64)
