"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed here and compiles for a described chip,
so what it refuses (tiling, fast-memory limits, a table that does not
fit, a kernel that cannot be partitioned) is found without chip time.
Nothing runs: these say nothing about results or speed.

The topology is described inside a module fixture and only there: only
one process may load the TPU library at a time, and every xdist worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributedratelimiting.redis_tpu.ops import fp_directory as F
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.ops.pallas_kernels import (
    sweep_expired_pallas,
)

N = 10_000_000  # BASELINE's active keys; chip_smoke.py's tables below
SMOKE_SLOTS = 1 << 24
SMOKE_FP_SLOTS = 1 << 26


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def bucket_state(n, sharding):
    return K.BucketState(sds((n,), jnp.float32, sharding),
                         sds((n,), jnp.int32, sharding),
                         sds((n,), jnp.bool_, sharding))


def scalars(sharding):
    return sds((), jnp.float32, sharding), sds((), jnp.float32, sharding)


def hbm_fits(compiled, limit=16 * 2**30):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < limit, mem
    return mem


def test_acquire_scan_packed24(one_chip):
    k, b = 32, 8192
    compiled = K.acquire_scan_packed24.lower(
        bucket_state(N, one_chip), sds((k, b, 3), jnp.uint8, one_chip),
        sds((k,), jnp.int32, one_chip), *scalars(one_chip)).compile()
    hbm_fits(compiled)


@pytest.mark.parametrize("n", [N, SMOKE_SLOTS])
def test_acquire_batch_packed(one_chip, n):
    compiled = K.acquire_batch_packed.lower(
        bucket_state(n, one_chip), sds((4, 4096), jnp.int32, one_chip),
        *scalars(one_chip)).compile()
    hbm_fits(compiled)


@pytest.mark.parametrize("kernel", [K.acquire_scan_fused_bits,
                                    K.acquire_scan_fused_packed])
def test_bulk_scan_at_smoke_size(one_chip, kernel):
    """The bulk-load kernels chip_smoke.py runs: K=32 batches of 4096."""
    compiled = kernel.lower(
        bucket_state(SMOKE_SLOTS, one_chip),
        sds((32, 4096, 5), jnp.uint8, one_chip),
        sds((32,), jnp.int32, one_chip), *scalars(one_chip)).compile()
    hbm_fits(compiled)


@pytest.mark.parametrize("n", [N, SMOKE_FP_SLOTS])
def test_fp_directory_resolve_insert(one_chip, n):
    b = 4096
    compiled = F.fp_acquire_batch.lower(
        sds((n, 2), jnp.uint32, one_chip), bucket_state(n, one_chip),
        sds((b, 2), jnp.uint32, one_chip), sds((b,), jnp.int32, one_chip),
        sds((b,), jnp.bool_, one_chip), sds((), jnp.int32, one_chip),
        *scalars(one_chip)).compile()
    hbm_fits(compiled)


def test_fp_bulk_scan_at_smoke_size(one_chip):
    compiled = F.fp_acquire_scan_fused_bits.lower(
        sds((SMOKE_FP_SLOTS, 2), jnp.uint32, one_chip),
        bucket_state(SMOKE_FP_SLOTS, one_chip),
        sds((8, 4096, 3), jnp.uint32, one_chip),
        sds((8,), jnp.int32, one_chip), *scalars(one_chip)).compile()
    hbm_fits(compiled)


@pytest.mark.parametrize("n", [N, SMOKE_SLOTS])
def test_sweep_expired_pallas_is_a_tpu_kernel(one_chip, n):
    compiled = sweep_expired_pallas.lower(
        sds((n,), jnp.float32, one_chip), sds((n,), jnp.int32, one_chip),
        sds((n,), jnp.int8, one_chip), sds((), jnp.int32, one_chip),
        *scalars(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    hbm_fits(compiled)


def test_two_level_scan_step_on_four_chips(topo):
    from jax.sharding import Mesh

    from distributedratelimiting.redis_tpu.parallel.mesh import SHARD_AXIS
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        GlobalCounter,
        make_two_level_scan_step,
    )

    mesh = Mesh(topo.devices[:4], (SHARD_AXIS,))
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    batch = NamedSharding(mesh, P(SHARD_AXIS, None, None))
    rep = NamedSharding(mesh, P())
    k, b, n = 32, 2048, 4 * (1 << 22)
    step = make_two_level_scan_step(mesh)
    compiled = step.lower(
        bucket_state(n, rows),
        sds((4, k, b), jnp.int32, batch), sds((4, k, b), jnp.int32, batch),
        sds((4, k, b), jnp.bool_, batch), sds((k,), jnp.int32, rep),
        sds((), jnp.float32, rep), sds((), jnp.float32, rep),
        GlobalCounter(sds((), jnp.float32, rep), sds((), jnp.float32, rep),
                      sds((), jnp.int32, rep), sds((), jnp.bool_, rep)),
        sds((), jnp.float32, rep)).compile()
    assert "all-reduce" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = n * 9 + 4 * k * b * (4 + 4 + 1)
    assert whole / 4 * 0.9 < per_device < whole / 4 * 1.1, per_device


def test_fp_two_level_scan_step_on_four_chips(topo):
    """The mesh fingerprint-directory step chip_smoke.py --chips 4 runs."""
    from jax.sharding import Mesh

    from distributedratelimiting.redis_tpu.parallel.fp_sharded import (
        make_sharded_fp_scan_step,
    )
    from distributedratelimiting.redis_tpu.parallel.mesh import SHARD_AXIS
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        GlobalCounter,
    )

    mesh = Mesh(topo.devices[:4], (SHARD_AXIS,))
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    k, b, n = 8, 512, 4 * (1 << 24)
    compiled = make_sharded_fp_scan_step(mesh).lower(
        sds((n, 2), jnp.uint32, NamedSharding(mesh, P(SHARD_AXIS, None))),
        bucket_state(n, rows),
        sds((4, k, b, 3), jnp.uint32,
            NamedSharding(mesh, P(SHARD_AXIS, None, None, None))),
        sds((k,), jnp.int32, rep), *scalars(rep),
        GlobalCounter(sds((), jnp.float32, rep), sds((), jnp.float32, rep),
                      sds((), jnp.int32, rep), sds((), jnp.bool_, rep)),
        sds((), jnp.float32, rep)).compile()
    assert "all-reduce" in compiled.as_text()
    hbm_fits(compiled)
