"""The control: the reference computed in bfloat16 (the precision below the
float32 the configuration states), put in the program's place, fails the
limit of 0 mismatched tasks, while the program passes it. Tiny cell on
the CPU; ``bench/control.py`` reads the same at the cells' own sizes on
the chip."""

import harness
import run as bench_run


def test_control_fails_where_program_passes(tiny_cell):
    cell = tiny_cell("replay", check_rounds=8, check_min_rounds=4)
    bench_run._imports()
    bench_run.configure_jax()
    bench, run, _ = bench_run.measure(cell, 4242424242, 2.0, False, harness.CompileCounter())
    prog = bench_run.compare(bench, 4242424242)
    ctrl = bench_run.compare(bench, 4242424242, precision="bfloat16", against="stated")
    assert prog["rounds"] >= 4 and prog["migration_rounds"] >= 1
    assert prog["mismatched_tasks"] == 0
    assert ctrl["mismatched_tasks"] > 0
