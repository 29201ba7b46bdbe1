"""The chip smoke run's phases, rehearsed on the CPU at a tiny size with
the Pallas kernels in interpret mode, plus its refusals and the compile
cache helper it calls."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py")
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = dict(n_machines=96, machines_per_rack=8, racks_per_pod=4, slots_per_machine=4)


def test_device_phase_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="needs a TPU"):
        chip_smoke.phase_device()


def test_kernel_phase_exact_on_grid():
    out = chip_smoke.phase_kernels(bid_shape=(16, 300), interpret=True)
    # 101 grid points, 100 half-steps and their 200 neighbours, 3 outside.
    assert out["latencies"] == 404
    assert out["costmap_cells"] == 8 * 404
    assert out["bid_rows"] == {"seeded": 16, "ties": 16}


def test_replay_and_parity_phases():
    info, sim, records = chip_smoke.phase_replay(
        TINY, trace_s=3600, replay_s=240, record_rounds=24, interpret=True
    )
    assert info["rounds"] >= 20 and info["tasks_placed"] > 0
    assert info["auction_iterations"] > 0
    # Interpret mode lowers the kernels to plain HLO: no TPU custom calls.
    assert info["kernel_calls"] == {"costmap": False, "auction_bid": False}
    par = chip_smoke.phase_parity(records, sim)
    assert par["rounds"] == 24 and par["migration_rounds"] >= 1
    assert par["mismatches"] == 0


def test_parity_phase_needs_a_migration_round():
    _info, sim, records = chip_smoke.phase_replay(
        TINY, trace_s=3600, replay_s=5, record_rounds=4, interpret=True
    )
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_parity(records, sim, min_rounds=1)


def test_serving_phase():
    out = chip_smoke.phase_serving(
        TINY, rate_jobs_s=1.0, horizon_s=20, batch_tasks=32,
        record_rounds=4, interpret=True,
    )
    assert out["jit_compiles_post_warmup"] == 0.0
    assert out["replay_mismatches"] == 0
    assert out["tasks_placed"] > 0


def _run_script(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_script_refuses_cpu():
    out = _run_script(REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_alone_refuses(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert runtime.enable_compilation_cache() == str(tmp_path)
    # JAX reads the variable itself; no other path is configured in code.
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compilation_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compilation_cache() == path


# --------------------------------------------------------------------- #
# One process per chip; failed benchmark modules fail the run.


def test_sweep_pool_refuses_while_chip_is_held(monkeypatch):
    from repro.core.sweep import SweepSpec, run_sweep

    monkeypatch.setattr(runtime, "held_accelerator", lambda: "tpu")
    spec = SweepSpec(
        n_machines=16, machines_per_rack=8, racks_per_pod=2, duration_s=30,
        policies=("random", "load_spreading"), seeds=(0,),
        scenarios=("baseline",),
    )
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        run_sweep(spec, workers=2)


def test_trace_scale_child_refuses_while_chip_is_held(monkeypatch):
    from benchmarks import trace_scale

    monkeypatch.setattr(runtime, "held_accelerator", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        trace_scale._run_child({})


def test_no_chip_held_on_the_cpu():
    assert runtime.held_accelerator() is None
    runtime.require_chip_free("a test")


def test_benchmark_harness_reports_failed_modules(capsys):
    from types import SimpleNamespace

    from benchmarks import run as bench_run

    ok = SimpleNamespace(run=lambda: [("row", 1.0, "x")])
    bad = SimpleNamespace(run=lambda: 1 / 0)
    assert bench_run.run_modules([("ok", ok), ("bad", bad)]) == ["bad"]
    out = capsys.readouterr().out
    assert "row,1.0,x" in out
    assert "bad_ERROR,0,ZeroDivisionError" in out
