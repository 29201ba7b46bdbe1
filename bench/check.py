"""The comparison that decides ``correct``.

After the window has closed, a sample of its solver rounds, drawn from the
seed, is re-solved by the configuration's plain reference (the module its
``reference`` key names, ``reference.py`` by default), and each task's
machine in the simulator's tables right after its round is compared with
what the reference's placement puts there:

- a pending task: the reference's machine, or still unplaced (-1) where
  the reference leaves it unscheduled;
- a running task of a migration round: the reference's machine, or its
  current one where the reference keeps it in place.

So the comparison covers the cost build (Eqs. 6-10 through the costmap
kernel), the auction (through the bid kernel) and the apply into the
tables, at the timed sizes. It is exact: the limit on mismatched tasks is
0. The sample always holds the round with the most tasks and, where the
window has any, ``min_migration`` migration rounds, the largest first.

A reference module declares ``KINDS``, the kinds of logged round it
solves, and ``BLOCKS``, the optional configuration blocks (``plane``,
``scheduler``) it implements. Only rounds of its kinds are sampled, and a
configuration with a block it does not implement is refused: it must
never be compared against rows the reference cannot compute. Besides, it
provides ``Cluster``, ``Policy``, ``RoundInputs`` and ``place``, which
receives the configuration and, for a what-if round, its lanes.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from typing import List, Optional, Sequence

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
BLOCKS = ("plane", "scheduler")  # a configuration's optional blocks


def load_reference(config: dict):
    """The reference module ``config`` names, loaded by path, once it is
    shown to implement every optional block the configuration has."""
    rel = config.get("reference", "reference.py")
    path = os.path.normpath(os.path.join(BENCH_DIR, rel))
    if not path.startswith(BENCH_DIR + os.sep):
        raise ValueError(f"config 'reference' {rel!r} lies outside bench/")
    if not path.endswith(".py") or not os.path.isfile(path):
        raise FileNotFoundError(f"config 'reference' {rel!r}: no module file bench/{rel}")
    name = "bench_reference_" + rel[:-3].replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # its dataclasses look their module up there
    spec.loader.exec_module(mod)
    for block in BLOCKS:
        if block in config and block not in mod.BLOCKS:
            raise ValueError(
                f"the config has a {block!r} block, which its reference bench/{rel} "
                f"does not implement (it declares {tuple(mod.BLOCKS)}); name one that "
                f"does under the config's 'reference' key"
            )
    return mod


def sample_rounds(
    rounds: Sequence[dict], seed: int, n: int, min_migration: int = 0,
    kinds: Optional[Sequence[str]] = None,
) -> List[int]:
    """Indices of the rounds to compare, drawn from ``seed`` among the
    rounds of ``kinds`` (default: all)."""
    if kinds is not None:
        idx = [i for i, r in enumerate(rounds) if r["kind"] in kinds]
        return [idx[i] for i in sample_rounds([rounds[i] for i in idx], seed, n, min_migration)]
    if not rounds:
        return []
    rng = np.random.default_rng((int(seed), 0xC4EC))
    sizes = np.asarray([len(r["task_job"]) for r in rounds])
    chosen = [int(np.argmax(sizes))]
    mig = [i for i, r in enumerate(rounds) if r["migration"]]
    if mig and min_migration:
        by_size = sorted(mig, key=lambda i: -sizes[i])
        picks = [by_size[0]] + list(rng.permutation(by_size[1:])[: min_migration - 1])
        chosen += [int(i) for i in picks if i not in chosen]
    rest = [i for i in rng.permutation(len(rounds)) if int(i) not in chosen]
    chosen += [int(i) for i in rest[: max(0, n - len(chosen))]]
    return sorted(chosen)


def inputs_of(ref, r: dict):
    return ref.RoundInputs(
        t=r["t"], task_job=r["task_job"], perf_idx=r["perf_idx"],
        root_machine=r["root_machine"], wait_s=r["wait_s"], run_s=r["run_s"],
        cur_machine=r["cur_machine"], free_slots=r["free_slots"],
    )


def expected_after(r: dict, cols: np.ndarray, n_machines: int) -> np.ndarray:
    """Each task's machine after the round, had it placed ``cols``."""
    n_ready = len(r["ready_ids"])
    on_machine = (cols >= 0) & (cols < n_machines)
    exp = np.where(on_machine, cols, -1)
    movers = slice(n_ready, None)
    exp[movers] = np.where(on_machine[movers], cols[movers], r["cur_machine"][movers])
    return exp


def compare(
    ref, rounds: Sequence[dict], picks: Sequence[int], cluster, policy,
    series: np.ndarray, plane_seed: int, precision: str = "float32",
    against: str = "tables", stated: str = "float32", config: Optional[dict] = None,
) -> dict:
    """Mismatched tasks between the reference module ``ref`` (in
    ``precision``) and the tables after each picked round
    (``against="tables"``), or the reference in the configuration's
    ``stated`` precision (``against="stated"``: the control)."""
    mismatched = tasks = mig = 0
    worst_round = -1
    kinds: dict = {}
    for i in picks:
        r = rounds[i]
        inp = inputs_of(ref, r)
        kind = r["kind"]
        extra = {} if kind == "place" else {"kind": kind, "lanes": r["lanes"]}

        def solve(prec):
            return ref.place(inp, cluster, policy, series, plane_seed, prec,
                             config=config, **extra)

        got = expected_after(r, solve(precision), cluster.n_machines)
        if against == "tables":
            want = r["after"]
        else:
            want = expected_after(r, solve(stated), cluster.n_machines)
        bad = int((got != want).sum())
        if bad and worst_round < 0:
            worst_round = i
        mismatched += bad
        tasks += len(got)
        mig += bool(r["migration"])
        kinds[kind] = kinds.get(kind, 0) + 1
    return {
        "rounds": len(picks),
        "migration_rounds": mig,
        "kinds": kinds,
        "tasks": tasks,
        "mismatched_tasks": mismatched,
        "first_bad_round": worst_round,
    }
