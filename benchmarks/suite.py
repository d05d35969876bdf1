"""The five BASELINE benchmark configurations (BASELINE.md "configs").

1. ``single_bucket_cpu``      — TestApp-style single token bucket, pure-CPU
                                store, one op per call (the Redis-class
                                baseline the reference's exact limiter is
                                architecturally bound to).
2. ``partitioned_10k_uniform``— PartitionedRateLimiter over strings, 10K
                                keys uniform, end-to-end asyncio micro-batch
                                path against the device store.
3. ``approximate_1m_zipf``    — 1M keys with Zipf(1.1) hot-key skew: the
                                device scan kernel with in-batch duplicate
                                serialization ON (hot keys collide inside
                                every batch), plus the approximate
                                limiter's local hot-path decision rate (its
                                decisions never leave the host — that IS
                                the algorithm, SURVEY.md invariant 6).
4. ``sliding_window_10m_bursty`` — 10M-slot sliding-window table, bursty
                                Poisson batch occupancy, scanned dispatch.
5. ``two_level_mesh``         — key-sharded two-level step (acquire + psum
                                global tier) over a mesh of ALL visible
                                devices (8 virtual CPU devices in tests,
                                real chips under TPU).

Every config prints ONE JSON line:
``{"config": ..., "metric": ..., "value": ..., "unit": ...}`` plus
config-specific extras. Sizes shrink under ``--smoke`` so the full suite
exercises identical code paths in seconds (tests/test_benchmarks.py).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time


def _zipf_slots(rng, n_slots: int, shape, a: float = 1.1):
    """Zipf(a) ranks mapped onto the slot space: rank r → slot r-1, tail
    clipped into the table. Hot slots repeat heavily inside each batch."""
    z = rng.zipf(a, shape)
    return ((z - 1) % n_slots).astype("int32")


def bench_single_bucket_cpu(smoke: bool = False) -> dict:
    """Config 1 — the reference's deployment class: one bucket, one store
    op per acquire, no batching (TestApp/Program.cs:8-22 semantics)."""
    from distributedratelimiting.redis_tpu.models.options import (
        TokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.models.token_bucket import (
        TokenBucketRateLimiter,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    n = 2_000 if smoke else 200_000
    lim = TokenBucketRateLimiter(
        TokenBucketOptions(token_limit=1 << 30, tokens_per_period=1 << 30,
                           instance_name="cfg1"),
        InProcessBucketStore(),
    )
    for _ in range(100):  # warm dict/code paths
        lim.acquire(1)
    t0 = time.perf_counter()
    for _ in range(n):
        lim.acquire(1)
    dt = time.perf_counter() - t0
    return {
        "config": "single_bucket_cpu",
        "metric": "decisions_per_sec",
        "value": round(n / dt),
        "unit": "decisions/s",
        "store": "in-process (Redis-class, one op per call)",
    }


def bench_partitioned_10k_uniform(smoke: bool = False) -> dict:
    """Config 2 — 10K keys uniform through the full asyncio micro-batched
    serving path (closed-loop worker pool)."""
    from distributedratelimiting.redis_tpu.models.options import (
        TokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.models.partitioned import (
        PartitionedRateLimiter,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n_keys = 256 if smoke else 10_000
    workers = 256 if smoke else 8192
    reqs_per_worker = 2 if smoke else 4

    async def main():
        store = DeviceBucketStore(
            n_slots=1 << (10 if smoke else 15), max_batch=4096,
            max_delay_s=300e-6, max_inflight=16,
        )
        lim = PartitionedRateLimiter(
            TokenBucketOptions(token_limit=1 << 30,
                               tokens_per_period=1 << 30,
                               instance_name="cfg2"),
            store,
        )
        lat: list[float] = []

        async def worker(w):
            for j in range(reqs_per_worker):
                t0 = time.perf_counter()
                await lim.acquire_async(f"user{(w * 31 + j) % n_keys}", 1)
                lat.append(time.perf_counter() - t0)

        await asyncio.gather(*(worker(w) for w in range(min(workers, 512))))
        lat.clear()
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(w) for w in range(workers)))
        dt = time.perf_counter() - t0
        throughput = len(lat) / dt
        lat.sort()
        p99 = lat[int(len(lat) * 0.99)]
        await store.aclose()
        return throughput, p99

    throughput, p99 = asyncio.run(main())
    return {
        "config": "partitioned_10k_uniform",
        "metric": "decisions_per_sec",
        "value": round(throughput),
        "unit": "decisions/s",
        "n_keys": n_keys,
        "p99_ms": round(p99 * 1e3, 3),
    }


def bench_approximate_1m_zipf(smoke: bool = False) -> dict:
    """Config 3 — Zipf(1.1) hot-key skew at 1M keys. Two measurements:
    the device scan kernel with duplicate serialization on (hot keys
    collide inside every batch), and the approximate limiter's local
    decision rate (its hot path never touches the store — invariant 6)."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from distributedratelimiting.redis_tpu.models.approximate import (
        ApproximateTokenBucketRateLimiter,
    )
    from distributedratelimiting.redis_tpu.models.options import (
        ApproximateTokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.ops import kernels as K
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    n_slots = 1 << (12 if smoke else 20)
    batch = 512 if smoke else 8192
    scan_k = 4 if smoke else 16
    iters = 2 if smoke else 30
    rng = np.random.default_rng(3)

    state = K.init_bucket_state(n_slots)
    cap = jnp.float32(1e9)
    rate = jnp.float32(1.0)

    def stage(i):
        # Host-side numpy staging: the timed loop pays the host→device
        # transfer per dispatch, as production serving does.
        slots = _zipf_slots(rng, n_slots, (scan_k, batch))
        rows = K.pack_rows(slots, np.ones((scan_k, batch), np.uint8))
        nows = np.arange(scan_k, dtype=np.int32) + 1 + i * scan_k
        return rows, nows

    def dispatch(state, arrays):
        rows, nows = arrays
        return K.acquire_scan(state, jnp.asarray(rows), jnp.asarray(nows),
                              cap, rate)

    staged = [stage(i) for i in range(4)]
    state, out = dispatch(state, staged[0])
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(iters):
        state, out = dispatch(state, staged[i % 4])
    jax.block_until_ready(out)
    device_rate = iters * scan_k * batch / (time.perf_counter() - t0)

    # Local hot path: pure in-memory decisions (the approximate design's
    # point — zero store round-trips between syncs).
    lim = ApproximateTokenBucketRateLimiter(
        ApproximateTokenBucketOptions(token_limit=1 << 30,
                                      tokens_per_period=1 << 30,
                                      instance_name="cfg3"),
        InProcessBucketStore(),
    )
    n_local = 2_000 if smoke else 300_000
    for _ in range(100):
        lim.acquire(1)
    t0 = time.perf_counter()
    for _ in range(n_local):
        lim.acquire(1)
    local_rate = n_local / (time.perf_counter() - t0)

    # Vectorized local bulk admission: one numpy pass decides a whole
    # batch against the same availability formula.
    n_bulk = 10_000 if smoke else 2_000_000
    ones = np.ones(n_bulk, np.int64)
    lim.acquire_many(ones[:100])
    t0 = time.perf_counter()
    lim.acquire_many(ones)
    local_bulk_rate = n_bulk / (time.perf_counter() - t0)

    return {
        "config": "approximate_1m_zipf",
        "metric": "device_decisions_per_sec",
        "value": round(device_rate),
        "unit": "decisions/s",
        "n_keys": n_slots,
        "zipf_a": 1.1,
        "duplicate_serialization": True,
        "local_hot_path_decisions_per_sec": round(local_rate),
        "local_bulk_decisions_per_sec": round(local_bulk_rate),
    }


def bench_sliding_window_10m_bursty(smoke: bool = False) -> dict:
    """Config 4 — sliding-window counters at 10M keys under bursty Poisson
    arrivals: per-scanned-batch occupancy ~ Poisson alternating between a
    high and a low rate (bursts), invalid rows masked."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from distributedratelimiting.redis_tpu.ops import kernels as K

    n_slots = 4096 if smoke else 10_000_000
    batch = 512 if smoke else 8192
    scan_k = 4 if smoke else 16
    iters = 2 if smoke else 30
    rng = np.random.default_rng(4)

    state = K.init_window_state(n_slots)
    limit = jnp.float32(100.0)
    window = jnp.int32(1024)  # 1s of ticks

    def stage(i):
        slots = rng.integers(0, n_slots, (scan_k, batch)).astype(np.int32)
        # Bursty: batch occupancy ~ Poisson(0.9·B) in bursts, Poisson(0.2·B)
        # between bursts — arrival gaps become padding rows (slot = -1) in
        # the fixed-shape row layout.
        lam = batch * (0.9 if (i % 4) < 2 else 0.2)
        occ = np.minimum(rng.poisson(lam, scan_k), batch)
        slots[np.arange(batch)[None, :] >= occ[:, None]] = -1
        rows = K.pack_rows(slots, np.ones((scan_k, batch), np.uint8))
        nows = np.arange(scan_k, dtype=np.int32) * 37 + 1 + i * scan_k * 37
        return (rows, nows), int(occ.sum())

    def dispatch(state, arrays):
        rows, nows = arrays  # np staged; transfer paid in-loop
        return K.window_acquire_scan(state, jnp.asarray(rows),
                                     jnp.asarray(nows), limit, window)

    staged = [stage(i) for i in range(4)]
    (arrays, _) = staged[0]
    state, out = dispatch(state, arrays)
    jax.block_until_ready(out)
    decided = 0
    t0 = time.perf_counter()
    for i in range(iters):
        arrays, occ = staged[i % 4]
        state, out = dispatch(state, arrays)
        decided += occ
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    # The same workload against the MESH store: keyed sliding windows
    # sharded over every visible device (ShardedWindowStore — the serving
    # path MeshBucketStore.window_acquire rides), end-to-end with string
    # keys, routing, and per-shard directories.
    from distributedratelimiting.redis_tpu.parallel.mesh import create_mesh
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        ShardedWindowStore,
    )

    mesh = create_mesh(len(jax.devices()))
    ws = ShardedWindowStore(
        mesh, limit=100.0, window_sec=1.0,
        per_shard_slots=1 << (10 if smoke else 17))
    pool = [f"wkey{i}" for i in range(2_000 if smoke else 500_000)]
    n_bulk = 1 << (10 if smoke else 17)
    calls = [[pool[j] for j in rng.integers(0, len(pool), n_bulk)]
             for _ in range(3)]
    ones = [1] * n_bulk
    ws.acquire_many_blocking(calls[0], ones, with_remaining=False)  # warm
    t0 = time.perf_counter()
    served = 0
    for c in calls:
        served += len(ws.acquire_many_blocking(c, ones,
                                               with_remaining=False))
    mesh_rate = served / (time.perf_counter() - t0)

    return {
        "config": "sliding_window_10m_bursty",
        "metric": "decisions_per_sec",
        "value": round(decided / dt),
        "unit": "decisions/s",
        "n_keys": n_slots,
        "arrivals": "poisson bursts (0.9B/0.2B alternating)",
        "mesh_window_serving_decisions_per_sec": round(mesh_rate),
        "mesh_window_devices": mesh.devices.size,
    }


def bench_two_level_mesh(smoke: bool = False) -> dict:
    """Config 5 — the fused two-level step (sharded acquire + psum global
    tier) over a mesh of every visible device."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedratelimiting.redis_tpu.ops import kernels as K
    from distributedratelimiting.redis_tpu.parallel.mesh import (
        SHARD_AXIS,
        create_mesh,
    )
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        init_global_counter,
        make_two_level_scan_step,
    )

    n_dev = len(jax.devices())
    mesh = create_mesh(n_dev)
    per_shard = 1 << (10 if smoke else 20)  # ≈ 10M total keys at 8 chips full
    b_local = 256 if smoke else 8192
    scan_k = 2 if smoke else 16
    iters = 4 if smoke else 40
    rng = np.random.default_rng(5)

    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    state = K.BucketState(
        tokens=jax.device_put(jnp.zeros((n_dev * per_shard,), jnp.float32), sharding),
        last_ts=jax.device_put(jnp.zeros((n_dev * per_shard,), jnp.int32), sharding),
        exists=jax.device_put(jnp.zeros((n_dev * per_shard,), bool), sharding),
    )
    gcounter = jax.device_put(init_global_counter(), NamedSharding(mesh, P()))
    step = make_two_level_scan_step(mesh, handle_duplicates=False)

    def stage():
        # numpy staging — the timed loop pays the host→device transfers.
        slots = rng.integers(0, per_shard,
                             (n_dev, scan_k, b_local)).astype(np.int32)
        counts = np.ones((n_dev, scan_k, b_local), np.int32)
        valid = np.ones((n_dev, scan_k, b_local), bool)
        return slots, counts, valid

    staged = [stage() for _ in range(4)]
    cap = jnp.float32(1e9)
    rate = jnp.float32(1.0)
    decay = jnp.float32(1.0)

    def dispatch(state, gcounter, arrays, base):
        slots, counts, valid = arrays
        nows = np.arange(scan_k, dtype=np.int32) + base
        return step(state, jnp.asarray(slots), jnp.asarray(counts),
                    jnp.asarray(valid), jnp.asarray(nows), cap, rate,
                    gcounter, decay)

    state, granted, _, gcounter = dispatch(state, gcounter, staged[0], 1)
    jax.block_until_ready(granted)
    t0 = time.perf_counter()
    for i in range(iters):
        state, granted, _, gcounter = dispatch(
            state, gcounter, staged[i % 4], (i + 1) * scan_k + 1)
    jax.block_until_ready(granted)
    dt = time.perf_counter() - t0

    # End-to-end bulk SERVING path on the same mesh: string keys through
    # ShardedDeviceStore.acquire_many_blocking (vectorized routing +
    # per-shard native resolve + scanned two-level launches + readback).
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        ShardedDeviceStore,
    )

    store = ShardedDeviceStore(
        mesh, capacity=1e9, fill_rate_per_sec=1.0,
        per_shard_slots=1 << (10 if smoke else 17))
    pool = [f"user{i}" for i in range(2_000 if smoke else 500_000)]
    n_bulk = 1 << (10 if smoke else 17)
    calls = [[pool[j] for j in rng.integers(0, len(pool), n_bulk)]
             for _ in range(3)]
    ones = [1] * n_bulk
    store.acquire_many_blocking(calls[0], ones, with_remaining=False)  # warm
    t0 = time.perf_counter()
    served = 0
    for c in calls:
        served += len(store.acquire_many_blocking(c, ones,
                                                  with_remaining=False))
    bulk_rate = served / (time.perf_counter() - t0)

    return {
        "config": "two_level_mesh",
        "metric": "aggregate_decisions_per_sec",
        "value": round(iters * n_dev * scan_k * b_local / dt),
        "unit": "decisions/s",
        "n_devices": n_dev,
        "scan_depth": scan_k,
        "n_keys": n_dev * per_shard,
        "global_score_after": float(np.asarray(gcounter.value)),
        "bulk_serving_decisions_per_sec": round(bulk_rate),
    }


def bench_psum_cadence(smoke: bool = False) -> dict:
    """Ablation (SURVEY.md §7 "Two-level sync cadence"): per-BATCH psum
    (one collective per scanned batch — the fused two-level step) vs
    per-LAUNCH psum (one collective after K batches — the reference's
    per-period sync posture). Grant decisions are identical; the trade is
    collective count vs global-counter staleness (bounded by one launch's
    wall time, ≙ the reference's staleness ≤ ReplenishmentPeriod)."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedratelimiting.redis_tpu.ops import kernels as K
    from distributedratelimiting.redis_tpu.parallel.mesh import (
        SHARD_AXIS,
        create_mesh,
    )
    from distributedratelimiting.redis_tpu.parallel.sharded_store import (
        init_global_counter,
        make_two_level_scan_step,
        make_two_level_scan_step_deferred,
    )

    n_dev = len(jax.devices())
    mesh = create_mesh(n_dev)
    per_shard = 1 << (10 if smoke else 18)
    b_local = 256 if smoke else 8192
    scan_k = 4 if smoke else 16
    iters = 4 if smoke else 40
    rng = np.random.default_rng(6)
    sharding = NamedSharding(mesh, P(SHARD_AXIS))

    def fresh():
        state = K.BucketState(
            tokens=jax.device_put(
                jnp.zeros((n_dev * per_shard,), jnp.float32), sharding),
            last_ts=jax.device_put(
                jnp.zeros((n_dev * per_shard,), jnp.int32), sharding),
            exists=jax.device_put(
                jnp.zeros((n_dev * per_shard,), bool), sharding),
        )
        return state, jax.device_put(init_global_counter(),
                                     NamedSharding(mesh, P()))

    staged = [
        (rng.integers(0, per_shard,
                      (n_dev, scan_k, b_local)).astype(np.int32),
         np.ones((n_dev, scan_k, b_local), np.int32),
         np.ones((n_dev, scan_k, b_local), bool))
        for _ in range(4)
    ]
    cap, rate, decay = jnp.float32(1e9), jnp.float32(1.0), jnp.float32(1.0)

    out = {"config": "psum_cadence", "metric": "aggregate_decisions_per_sec",
           "unit": "decisions/s", "n_devices": n_dev, "scan_depth": scan_k}
    grants, gvals = {}, {}
    for name, factory in (
        ("per_batch", make_two_level_scan_step),
        ("per_launch", make_two_level_scan_step_deferred),
    ):
        step = factory(mesh, handle_duplicates=False)
        state, g = fresh()

        def dispatch(state, g, arrays, base):
            slots, counts, valid = arrays
            nows = np.arange(scan_k, dtype=np.int32) + base
            return step(state, jnp.asarray(slots), jnp.asarray(counts),
                        jnp.asarray(valid), jnp.asarray(nows), cap, rate,
                        g, decay)

        state, granted, _, g = dispatch(state, g, staged[0], 1)
        jax.block_until_ready(granted)
        grants[name] = np.asarray(granted).copy()
        t0 = time.perf_counter()
        for i in range(iters):
            state, granted, _, g = dispatch(
                state, g, staged[i % 4], (i + 1) * scan_k + 1)
        jax.block_until_ready(granted)
        dt = time.perf_counter() - t0
        out[f"{name}_decisions_per_sec"] = round(
            iters * n_dev * scan_k * b_local / dt)
        gvals[name] = float(np.asarray(g.value))
    # Decisions are cadence-independent (the acquire path reads no global
    # state inside a launch); counters differ only by decay granularity.
    assert np.array_equal(grants["per_batch"], grants["per_launch"])
    out["value"] = out["per_batch_decisions_per_sec"]
    out["global_counter_per_batch"] = gvals["per_batch"]
    out["global_counter_per_launch"] = gvals["per_launch"]
    return out


def bench_cluster_bulk(smoke: bool = False) -> dict:
    """Cluster scale-out: bulk decisions through N shared-nothing store
    servers over localhost TCP, keys crc32-routed client-side
    (`ClusterBucketStore`) — per-node sub-batches fan out concurrently,
    so the aggregate rides N servers' pipelines."""
    import numpy as np

    from distributedratelimiting.redis_tpu.runtime.cluster import (
        ClusterBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n_nodes = 2 if smoke else 3
    n = 1 << (10 if smoke else 16)
    calls = 2 if smoke else 4

    async def main():
        backings = [DeviceBucketStore(n_slots=1 << (10 if smoke else 18),
                                      max_batch=4096)
                    for _ in range(n_nodes)]
        servers = []
        for b in backings:
            srv = BucketStoreServer(b)
            await srv.start()
            servers.append(srv)
        store = ClusterBucketStore(
            addresses=[(s.host, s.port) for s in servers])
        rng = np.random.default_rng(5)
        pool = [f"user{i}" for i in range(200_000)]
        batches = [[pool[j] for j in rng.integers(0, len(pool), n)]
                   for _ in range(calls)]
        counts = [1] * n
        # Warm the exact shapes (connect + compile on every node).
        await asyncio.gather(*(store.acquire_many(
            b, counts, 1e7, 1e7, with_remaining=False) for b in batches))
        t0 = time.perf_counter()
        await asyncio.gather(*(store.acquire_many(
            b, counts, 1e7, 1e7, with_remaining=False) for b in batches))
        dt = time.perf_counter() - t0
        rate = calls * n / dt
        await store.aclose()
        for s in servers:
            await s.aclose()
        for b in backings:
            await b.aclose()
        return rate

    rate = asyncio.run(main())
    return {
        "config": "cluster_bulk",
        "metric": "decisions_per_sec",
        "value": round(rate),
        "unit": "decisions/s",
        "n_nodes": n_nodes,
        "keys_per_call": n,
    }


def bench_fp_directory(smoke: bool = False) -> dict:
    """Device-resident directory vs host directory: the same bulk
    workload through `FingerprintBucketStore` (in-kernel probe/insert on
    fingerprints; host duty = one hashing pass) and `DeviceBucketStore`
    (native host directory + packed slot operands). Reports both so the
    trade (operand bytes vs host work — docs/DESIGN.md §5b) stays
    measured, not asserted."""
    import numpy as np

    from distributedratelimiting.redis_tpu.runtime.fp_store import (
        FingerprintBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n = 1 << (10 if smoke else 17)
    n_slots = 1 << (12 if smoke else 21)
    calls = 2 if smoke else 4

    def run_store(store) -> float:
        rng = np.random.default_rng(9)
        pool = [f"user{i}" for i in range(500_000)]
        batches = [[pool[j] for j in rng.integers(0, len(pool), n)]
                   for _ in range(calls)]
        counts = [1] * n
        for b in batches:  # warm: insert pass + compile at exact shapes
            store.acquire_many_blocking(b, counts, 1e7, 1e7,
                                        with_remaining=False)
        t0 = time.perf_counter()
        for b in batches:
            store.acquire_many_blocking(b, counts, 1e7, 1e7,
                                        with_remaining=False)
        return calls * n / (time.perf_counter() - t0)

    fp_store = FingerprintBucketStore(n_slots=n_slots)
    fp_rate = run_store(fp_store)
    asyncio.run(fp_store.aclose())
    host_store = DeviceBucketStore(n_slots=n_slots)
    host_rate = run_store(host_store)
    asyncio.run(host_store.aclose())
    return {
        "config": "fp_directory",
        "metric": "decisions_per_sec",
        "value": round(fp_rate),
        "unit": "decisions/s",
        "host_directory_decisions_per_sec": round(host_rate),
        "keys_per_call": n,
        "n_slots": n_slots,
    }


def bench_fp_mesh(smoke: bool = False) -> dict:
    """Mesh-sharded fingerprint tier: bulk decisions through
    `ShardedFpDeviceStore` over every visible device — in-kernel
    probe/insert per shard, fingerprint-as-route, psum global tier.
    The fp analogue of `two_level_mesh`."""
    import numpy as np

    from distributedratelimiting.redis_tpu.parallel.fp_sharded import (
        ShardedFpDeviceStore,
    )
    from distributedratelimiting.redis_tpu.parallel.mesh import create_mesh

    import jax

    mesh = create_mesh(len(jax.devices()))
    n = 1 << (10 if smoke else 16)
    calls = 2 if smoke else 4
    # Provision by TOTAL slot budget (2^19 ≈ 3.6× the ~145K unique keys
    # the workload draws), not per-shard: the r05 TPU run of the old
    # per-shard=2^16 config on a 1-device mesh left the table 8× under
    # water — permanent window pressure, sweep+grow cycles inside the
    # timed loop.
    total_slots = 1 << (11 if smoke else 19)
    store = ShardedFpDeviceStore(
        mesh, capacity=1e9, fill_rate_per_sec=1.0,
        per_shard_slots=max(256, total_slots // mesh.devices.size),
        batch=128 if smoke else 2048)
    rng = np.random.default_rng(13)
    pool = [f"user{i}" for i in range(200_000)]
    batches = [[pool[j] for j in rng.integers(0, len(pool), n)]
               for _ in range(calls)]
    counts = [1] * n
    for b in batches:  # warm: inserts + compile at exact shapes
        store.acquire_many_blocking(b, counts, with_remaining=False)
    t0 = time.perf_counter()
    for b in batches:
        store.acquire_many_blocking(b, counts, with_remaining=False)
    rate = calls * n / (time.perf_counter() - t0)
    return {
        "config": "fp_mesh",
        "metric": "decisions_per_sec",
        "value": round(rate),
        "unit": "decisions/s",
        "n_devices": mesh.devices.size,
        "keys_per_call": n,
        "global_score": store.global_score,
    }


CONFIGS = {
    "single_bucket_cpu": bench_single_bucket_cpu,
    "partitioned_10k_uniform": bench_partitioned_10k_uniform,
    "approximate_1m_zipf": bench_approximate_1m_zipf,
    "sliding_window_10m_bursty": bench_sliding_window_10m_bursty,
    "two_level_mesh": bench_two_level_mesh,
    "psum_cadence": bench_psum_cadence,
    "cluster_bulk": bench_cluster_bulk,
    "fp_directory": bench_fp_directory,
    "fp_mesh": bench_fp_mesh,
}


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("configs", nargs="*",
                        help=f"subset of configs to run (default: all); "
                             f"choices: {', '.join(CONFIGS)}")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny sizes — exercise code paths, not perf")
    args = parser.parse_args(argv)
    unknown = [c for c in args.configs if c not in CONFIGS]
    if unknown:
        parser.error(f"unknown config(s): {', '.join(unknown)}")
    names = args.configs or list(CONFIGS)
    for name in names:
        result = CONFIGS[name](smoke=args.smoke)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
