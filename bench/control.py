"""Readings behind the limit of ``correct``, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed it sets the cell up and runs a window at the cell's own load
and sizes, then compares the same seed-drawn sample of rounds twice:

- the program (the tables after each round) against the float32
  reference: the lower reading, 0 on a sound run;
- the control: the reference computed in bfloat16, the precision below the
  one the configuration states, put in the program's place, against the
  float32 reference: the upper reading, which the limit (0 mismatched
  tasks) has to fail.

One JSON line per seed on standard output. The seeds share one warmed
backend (its programs are keyed by bucket and its windows are exogenous,
so no seed's rounds can see another's). The benchmark's own runs do not
run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    a = p.parse_args(argv)
    try:
        bench_run._imports()
        import harness

        cell = harness.Cell.load(bench_run.ROOT, a.workload)
        jax = bench_run.configure_jax()
        dev = bench_run.device_info(jax, cell.chips, require_tpu=True)
    except bench_run.Refused as e:
        bench_run.say(f"refused: {e}")
        return 3
    compiles = harness.CompileCounter()
    shared = []  # one backend, warmed once, serves every seed's windows

    def backend(b):
        if not shared:
            shared.append(b.sim.backend)
        return shared[0]

    for seed in a.seeds:
        bench, run, setup_s = bench_run.measure(
            cell, seed, a.seconds, False, compiles, backend, t_start=time.perf_counter()
        )
        t0 = time.perf_counter()
        prog = bench_run.compare(bench, seed)
        t1 = time.perf_counter()
        ctrl = bench_run.compare(bench, seed, precision="bfloat16", against="stated")
        print(json.dumps({
            "workload": a.workload, "seed": seed, "device": dev["kind"],
            "rounds": prog["rounds"], "migration_rounds": prog["migration_rounds"],
            "tasks": prog["tasks"],
            "program_mismatched_tasks": prog["mismatched_tasks"],
            "control_mismatched_tasks": ctrl["mismatched_tasks"],
            "unplaced_tasks": run.failed, "compiles_in_window": run.compiles_in_window,
            "reference_s": t1 - t0, "setup_s": setup_s,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
