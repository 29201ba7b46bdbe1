"""The comparison that decides ``correct``.

After the window has closed, a sample of its solver rounds, drawn from the
seed, is re-solved by the plain reference (`reference.py`), and each
task's machine in the simulator's tables right after its round is
compared with what the reference's placement puts there:

- a pending task: the reference's machine, or still unplaced (-1) where
  the reference leaves it unscheduled;
- a running task of a migration round: the reference's machine, or its
  current one where the reference keeps it in place.

So the comparison covers the cost build (Eqs. 6-10 through the costmap
kernel), the auction (through the bid kernel) and the apply into the
tables, at the timed sizes. It is exact: the limit on mismatched tasks is
0. The sample always holds the round with the most tasks and, where the
window has any, ``min_migration`` migration rounds, the largest first.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

import reference as ref


def sample_rounds(
    rounds: Sequence[dict], seed: int, n: int, min_migration: int = 0
) -> List[int]:
    """Indices of the rounds to compare, drawn from ``seed``."""
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


def inputs_of(r: dict) -> ref.RoundInputs:
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
    rounds: Sequence[dict], picks: Sequence[int], cluster: ref.Cluster,
    policy: ref.Policy, series: np.ndarray, plane_seed: int,
    precision: str = "float32", against: str = "tables", stated: str = "float32",
) -> dict:
    """Mismatched tasks between the reference (in ``precision``) and the
    tables after each picked round (``against="tables"``), or the
    reference in the configuration's ``stated`` precision
    (``against="stated"``: the control)."""
    mismatched = tasks = mig = 0
    worst_round = -1
    for i in picks:
        r = rounds[i]
        inp = inputs_of(r)
        cols = ref.place(inp, cluster, policy, series, plane_seed, precision)
        got = expected_after(r, cols, cluster.n_machines)
        if against == "tables":
            want = r["after"]
        else:
            base = ref.place(inp, cluster, policy, series, plane_seed, stated)
            want = expected_after(r, base, cluster.n_machines)
        bad = int((got != want).sum())
        if bad and worst_round < 0:
            worst_round = i
        mismatched += bad
        tasks += len(got)
        mig += bool(r["migration"])
    return {
        "rounds": len(picks),
        "migration_rounds": mig,
        "tasks": tasks,
        "mismatched_tasks": mismatched,
        "first_bad_round": worst_round,
    }
