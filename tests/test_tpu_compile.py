"""Compiles the planner's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
chip that is described and not attached. That refuses what interpret mode
and the CPU never check (block shapes against the (8, 128) tiling, unaligned
lane slices, VMEM and HBM budgets). Shapes are those ``chip_smoke.py`` drives
on the chip. The topology is described inside a fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.fleet import _kmeans_labels
from repro.core.sweep import SweepEngine
from repro.kernels.minplus import minplus_pallas_batch, tpu_tuned_bt

# the cross-silo cell: T = 16 000 buckets to 16 384, U_i < 1024
SILO_B, SILO_N, SILO_T, SILO_W = 8, 32, 16384, 1024
# the cross-device monotone cell
MONO_B, MONO_N, MONO_W = 4, 4096, 64
# the cross-device uplink cell: the DP over 2 130-3 550 phones, T' up to 57k
UPLINK_B, UPLINK_N, UPLINK_T, UPLINK_W = 8, 4096, 65536, 64
FLEET_N = 16384
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but can
    # never be read back without one; keep them out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("chips",), axis_types=(AxisType.Auto,))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(one_chip, B, Tp, W):
    bt = tpu_tuned_bt(Tp, W)
    return minplus_pallas_batch.lower(
        _spec((B, Tp), jnp.float32, one_chip),
        _spec((B, W), jnp.float32, one_chip),
        BT=bt,
        interpret=False,
    ).compile()


@pytest.mark.parametrize("B", [1, SILO_B])
def test_minplus_kernel_compiles_at_cross_silo_shape(one_chip, B):
    compiled = _compile_kernel(one_chip, B, SILO_T + 1, SILO_W)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "B,Tp,W",
    [(1, UPLINK_T + 1, UPLINK_W), (SILO_B, SILO_T + 1, SILO_W)],
    ids=["uplink", "cross-silo"],
)
def test_band_bounded_kernel_compiles(one_chip, B, Tp, W):
    # the band bound is a scalar-prefetch operand and the band loop's trip
    # count depends on it
    compiled = minplus_pallas_batch.lower(
        _spec((B, Tp), jnp.float32, one_chip),
        _spec((B, W), jnp.float32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        BT=tpu_tuned_bt(Tp, W),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_minplus_kernel_compiles_at_longest_resident_row(one_chip):
    # the longest row tpu_tuned_bt admits, found against its own VMEM model
    lo, hi = SILO_T, 1 << 26
    while hi - lo > 1:
        mid = (lo + hi) // 2
        try:
            tpu_tuned_bt(mid, SILO_W)
            lo = mid
        except ValueError:
            hi = mid
    with pytest.raises(ValueError, match="VMEM"):
        tpu_tuned_bt(hi, SILO_W)
    compiled = _compile_kernel(one_chip, 1, lo, SILO_W)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_engine_program_compiles_with_pallas_tpu(one_chip):
    engine = SweepEngine(backend="pallas_tpu")
    run = engine._build(("dp", SILO_B, SILO_N, SILO_T, SILO_W))
    compiled = run.lower(
        _spec((SILO_B, SILO_N, SILO_W), jnp.float32, one_chip),
        _spec((SILO_B,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert engine.cache_stats()["compiles"] == 1


def test_uplink_dp_program_keeps_an_int8_argmin_matrix_within_hbm(one_chip):
    # the largest executable the uplink cell warms: B = 8 at its top bucket
    engine = SweepEngine(backend="pallas_tpu")
    run = engine._build(("dp", UPLINK_B, UPLINK_N, UPLINK_T, UPLINK_W))
    compiled = run.lower(
        _spec((UPLINK_B, UPLINK_N, UPLINK_W), jnp.float32, one_chip),
        _spec((UPLINK_B,), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert f"s8[{UPLINK_N},{UPLINK_B},{UPLINK_T + 1}]" in text
    assert f"s32[{UPLINK_N},{UPLINK_B},{UPLINK_T + 1}]" not in text
    slab = UPLINK_N * UPLINK_B * (UPLINK_T + 1)  # bytes at int8
    mem = compiled.memory_analysis()
    assert slab <= mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_sharded_engine_programs_compile_on_four_chips(four_chips):
    # chip_smoke.py --chips 4: B = 32 at the cross-silo widths, the batch
    # axis over the 2x2 host
    B = 32
    engine = SweepEngine(backend="pallas_tpu", mesh=four_chips)
    run = engine._build(("dp", B, SILO_N, SILO_T, SILO_W))
    rows = NamedSharding(four_chips, PartitionSpec("chips"))
    compiled = run.lower(
        _spec((B, SILO_N, SILO_W), jnp.float32, rows),
        _spec((B,), jnp.int32, rows),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_marginal_select_compiles_at_cross_device_bucket(one_chip):
    engine = SweepEngine(backend="pallas_tpu")
    run = engine._build(("marginal", MONO_B, MONO_N, MONO_W))
    compiled = run.lower(
        _spec((MONO_B, MONO_N, MONO_W), jnp.float32, one_chip),
        _spec((MONO_B, MONO_N), jnp.int32, one_chip),
        _spec((MONO_B,), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_fleet_kmeans_compiles_at_fleet_size(one_chip):
    k = 128  # ceil(sqrt(16 384)), the auto cluster count
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = _kmeans_labels.lower(
        _spec((FLEET_N, 10), jnp.float32, one_chip),
        _spec(key.shape, key.dtype, one_chip),
        k=k,
        iters=16,
    ).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < total < V5E_HBM_BYTES
