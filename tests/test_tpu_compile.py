"""Compile the scheduler's main-path programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
each program for one chip of a described ``v5e:2x2`` topology, and the test
checks that the Pallas kernels survived as TPU custom calls — what the
chip's compiler refuses (tiling, VMEM, memory) fails here at no chip time.
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import policy, topology
from repro.core.round_program import RoundProgram
from repro.kernels.auction_bid import kernel as bid_kernel
from repro.kernels.costmap import kernel as cm_kernel

PAPER_TOPO = topology.Topology(
    n_machines=12_500, machines_per_rack=48, racks_per_pod=16, slots_per_machine=8
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # can never be read back here; keep the cache out of it.
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(text):
    return [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


SHAPES = [(8, 64), (8, 12_500), (1024, 12_500)]


@pytest.mark.parametrize("T,M", SHAPES)
def test_costmap_kernel_compiles(one_chip, T, M):
    compiled = cm_kernel.costmap_pallas.lower(
        _sds((T,), jnp.int32, one_chip), _sds((T, M), jnp.float32, one_chip)
    ).compile()
    assert _custom_calls(compiled.as_text())


@pytest.mark.parametrize("T,M", SHAPES)
def test_bid_kernel_compiles(one_chip, T, M):
    compiled = bid_kernel.bid_top2_pallas.lower(
        _sds((T, M), jnp.float32, one_chip),
        _sds((M,), jnp.float32, one_chip),
        _sds((M,), jnp.float32, one_chip),
    ).compile()
    assert _custom_calls(compiled.as_text())


def _program():
    return RoundProgram(
        PAPER_TOPO,
        policy.PolicyParams(preemption=True),
        n_pad_tasks=8,
        n_pad_jobs=8,
        use_pallas=True,
    )


def _assert_both_kernels(text):
    calls = _custom_calls(text)
    assert any("costmap_pallas" in line for line in calls)
    assert any("bid_top2_pallas" in line for line in calls)


def test_window_program_compiles_with_both_kernels(one_chip):
    compiled = _program().lower_window(one_chip).compile()
    _assert_both_kernels(compiled.as_text())


def test_whatif_program_compiles_with_both_kernels(one_chip):
    compiled = _program().lower_whatif(4, one_chip).compile()
    _assert_both_kernels(compiled.as_text())
