#!/usr/bin/env python3
"""Bring-up proof: the served rate-limiting path on a TPU at 10M keys.

    python3 chip_smoke.py             # one chip, every single-chip phase
    python3 chip_smoke.py --chips 4   # the key-sharded mesh phase only

One process owns the chip. Every phase drives the entry points a user
calls and compares the grants with ``InProcessBucketStore``, the serial
reference of ``tests/test_differential.py``, on a ``ManualClock`` and
traffic made from ``--seed``. Any mismatch or error exits non-zero. The
per-phase lines are facts of this run (no speed claims); the last line
is exactly ``{"ok": true, "device": {...}}``, printed only when every
phase passed on a TPU.

Bulk calls hold one count per key per call: the device serves mixed
same-key counts inside one call conservatively (``BucketStore.
acquire_many``), where the serial reference would be exact; with one
count per key the two agree by construction.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

#: Active keys held on the device (BASELINE.json's metric: 10M keys).
N_KEYS = 10_000_000
#: Table slots: 10M keys at 60% occupancy, below the 75% pregrow mark,
#: and a whole number of the Pallas sweep's 32K-slot tiles.
N_SLOTS = 1 << 24
#: Per-shard slots on the four-chip mesh (2.5M keys a shard, 60%).
MESH_PER_SHARD = 1 << 22
#: The fingerprint directory places a key in the first free cell of a
#: 16-cell window and denies the key when the window is full. Simulated
#: on the host, windows fill for 0.19% of inserts at 60% occupancy,
#: 3e-5 at 40% and 1.6e-6 at 30%, so its tables are sized to 15%.
FP_SLOTS = 1 << 26
MESH_FP_PER_SHARD = 1 << 24
LOAD_CHUNK = 1 << 17          # keys per acquire_many_blocking call
SUBSET = 4096                 # keys under the exactness trace
TRACE_BATCHES = 16            # x SUBSET requests = 64K requests
# Bucket config: 10 permits, refill 4/s. 4/1024 per tick is exact in
# float32, so device and reference balances agree bit for bit.
CAP = 10.0
RATE = 4.0
INSTANCE = "api"


def key_names(n: int) -> list[str]:
    return [f"{INSTANCE}:user{i}" for i in range(n)]


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileTimer:
    """Seconds XLA spends compiling (jax's own backend-compile duration
    events), kept apart from load time."""

    def __init__(self) -> None:
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def device_memory() -> list[dict]:
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


# -- phases (sizes are arguments: tests/test_chip_smoke.py runs them tiny) --

def phase_load(name, store, keys, timer, chunk=LOAD_CHUNK) -> None:
    """Every key's first acquire (1 permit of a full bucket) is granted."""
    c0, t0 = timer.total, time.perf_counter()
    granted = 0
    for pos in range(0, len(keys), chunk):
        part = keys[pos:pos + chunk]
        res = store.acquire_many_blocking(part, np.ones(len(part), np.int64),
                                          CAP, RATE, with_remaining=False)
        granted += int(res.granted.sum())
    wall = time.perf_counter() - t0
    compile_s = timer.total - c0
    check(granted == len(keys),
          f"{name}: {len(keys) - granted} first acquires denied")
    emit(f"{name}_load", ok=True, keys=len(keys), granted=granted,
         chunk=chunk)
    emit(f"{name}_load_seconds", load_s=wall - compile_s,
         compile_s=compile_s, wall_s=wall)


def make_reference(clock):
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    return InProcessBucketStore(clock=clock)


def trace_batches(rng, subset: list[str], batches: int):
    """Seeded bulk trace over ``subset``: even batches draw keys uniformly
    (many in-batch duplicates), odd ones a permutation with 1/16 repeats
    (few). Counts are 1-3, one per key per batch; the clock advances
    0-0.25 s (whole ticks) before each batch."""
    n = len(subset)
    for b in range(batches):
        if b % 2 == 0:
            idx = rng.integers(0, n, n)
        else:
            idx = rng.permutation(n)
            rep = rng.random(n) < 1 / 16
            idx[rep] = rng.integers(0, n, int(rep.sum()))
        per_key = rng.integers(1, 4, n)
        yield (int(rng.integers(0, 257)), [subset[i] for i in idx],
               per_key[idx])


def phase_exact(name, store, clock, ref, ref_clock, keys, rng,
                subset_n=SUBSET, batches=TRACE_BATCHES,
                after_batch=None) -> list[str]:
    """Seeded trace on a subset of the loaded table; grants must equal the
    serial reference element for element. Returns the subset."""
    pick = np.sort(rng.choice(len(keys), subset_n, replace=False))
    subset = [keys[i] for i in pick]
    # The reference learns the subset's history: the load's first acquire.
    ref.acquire_many_blocking(subset, np.ones(subset_n, np.int64), CAP, RATE)
    n_req = n_granted = 0
    for dt, ks, counts in trace_batches(rng, subset, batches):
        clock.advance_ticks(dt)
        ref_clock.advance_ticks(dt)
        got = store.acquire_many_blocking(ks, counts, CAP, RATE)
        want = ref.acquire_many_blocking(ks, counts, CAP, RATE)
        bad = np.nonzero(got.granted != want.granted)[0]
        check(len(bad) == 0,
              f"{name}: {len(bad)} grants differ from the reference, first "
              f"at row {bad[:1].tolist()} of a {len(ks)}-row batch")
        if after_batch is not None:
            after_batch(counts, want)
        n_req += len(ks)
        n_granted += int(want.granted.sum())
    check(0 < n_granted < n_req, f"{name}: trace never denied or granted")
    emit(f"{name}_exact", ok=True, requests=n_req, granted=n_granted,
         denied=n_req - n_granted, mismatches=0)
    return subset


async def phase_microbatch(store, ref, subset, rng, n=512) -> None:
    """README quick start: PartitionedRateLimiter.acquire_async over the
    micro-batched store, a few hundred acquires in flight at once."""
    from distributedratelimiting.redis_tpu.models.options import (
        TokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.models.partitioned import (
        PartitionedRateLimiter,
    )

    opts = TokenBucketOptions(token_limit=int(CAP),
                              tokens_per_period=int(RATE),
                              replenishment_period_s=1.0,
                              instance_name=INSTANCE)
    dev = PartitionedRateLimiter(opts, store)
    want_lim = PartitionedRateLimiter(opts, ref)
    # 64 hot users, ~8 acquires each, all in flight together.
    users = [subset[i].split(":", 1)[1]
             for i in rng.choice(len(subset), 64, replace=False)]
    asks = [users[i] for i in rng.integers(0, len(users), n)]
    leases = await asyncio.gather(*(dev.acquire_async(u) for u in asks))
    got = np.array([le.is_acquired for le in leases])
    want = np.array([want_lim.acquire(u).is_acquired for u in asks])
    bad = int((got != want).sum())
    check(bad == 0, f"microbatch: {bad} of {n} grants differ from the "
          f"reference ({int(got.sum())} vs {int(want.sum())} granted)")
    emit("microbatch", ok=True, acquires=n, users=len(users),
         granted=int(got.sum()), flushes=store.metrics.launches)


async def phase_served(store, ref, subset, rng, n_scalar=512) -> None:
    """BucketStoreServer with the native front end, a RemoteBucketStore
    client on the same loop: scalar ACQUIREs, then one OP_ACQUIRE_MANY."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )

    async with BucketStoreServer(store, native_frontend=True) as srv:
        check(srv._native is not None, "served: native front end not up")
        client = RemoteBucketStore(address=(srv.host, srv.port),
                                   coalesce_requests=False)
        try:
            mism = granted = 0
            for i in range(n_scalar):
                key = subset[int(rng.integers(0, len(subset)))]
                count = int(rng.integers(1, 4))
                got = await client.acquire(key, count, CAP, RATE)
                want = ref.acquire_blocking(key, count, CAP, RATE)
                mism += got.granted != want.granted
                granted += want.granted
            check(mism == 0, f"served: {mism} scalar replies differ")
            idx = rng.integers(0, len(subset), len(subset))
            keys = [subset[i] for i in idx]
            counts = rng.integers(1, 4, len(subset))[idx]
            got = await client.acquire_many(keys, counts, CAP, RATE)
            want = ref.acquire_many_blocking(keys, counts, CAP, RATE)
            bad = int((got.granted != want.granted).sum())
            check(bad == 0, f"served: {bad} bulk-frame rows differ")
        finally:
            await client.aclose()
    emit("served", ok=True, scalar_acquires=n_scalar, scalar_granted=granted,
         bulk_rows=len(keys), bulk_granted=int(want.granted.sum()),
         native_frontend=True)


def phase_sweep(store, clock, ref, ref_clock, n_keys, subset) -> None:
    """One TTL sweep of the whole table through the compiled Pallas
    kernel; it must free exactly the slots the reference says expired."""
    from distributedratelimiting.redis_tpu.ops import bucket_math as bm
    from distributedratelimiting.redis_tpu.ops.pallas_kernels import (
        sweep_expired_pallas,
    )

    check(store.use_pallas_sweep, "sweep: the store does not use Pallas")
    clock.advance_ticks(bm.TICKS_PER_SECOND)
    ref_clock.advance_ticks(bm.TICKS_PER_SECOND)
    now = clock.now_ticks()
    rate = RATE / bm.TICKS_PER_SECOND

    def expired(tokens: float, ts: int) -> bool:
        ttl = min(max(int(np.ceil((CAP - tokens) / rate)), bm.MIN_TTL_TICKS),
                  bm.MAX_TTL_TICKS)
        return now - ts >= ttl

    # Keys outside the subset still hold the load's (CAP - 1, tick 0).
    expect = (n_keys - len(subset)) * expired(CAP - 1, 0)
    expect += sum(expired(*ref._buckets[(k, CAP, RATE)]) for k in subset)
    before, sweeps = store.metrics.slots_evicted, store.metrics.sweeps
    t0 = time.perf_counter()
    store.sweep_all()
    wall = time.perf_counter() - t0
    freed = store.metrics.slots_evicted - before
    check(store.metrics.sweeps > sweeps, "sweep: no sweep ran")
    check(sweep_expired_pallas._cache_size() > 0,
          "sweep: the Pallas kernel was never compiled")
    check(freed == expect, f"sweep: freed {freed} slots, reference says "
          f"{expect}")
    # A swept key comes back as a full bucket, as the reference has it.
    ones = np.ones(len(subset), np.int64)
    got = store.acquire_many_blocking(subset, ones, CAP, RATE)
    want = ref.acquire_many_blocking(subset, ones, CAP, RATE)
    check(np.array_equal(got.granted, want.granted),
          "sweep: grants after the sweep differ from the reference")
    emit("sweep", ok=True, slots=store._tables[(CAP, RATE)].n_slots,
         freed=freed, interpret=store.pallas_interpret, wall_s=wall)


def run_one_chip(seed: int, n_keys: int = N_KEYS, n_slots: int = N_SLOTS,
                 fp_slots: int = FP_SLOTS, subset_n: int = SUBSET,
                 batches: int = TRACE_BATCHES, chunk: int = LOAD_CHUNK,
                 **store_kw) -> None:
    asyncio.run(_one_chip(seed, n_keys, n_slots, fp_slots, subset_n,
                          batches, chunk, store_kw))


async def _one_chip(seed, n_keys, n_slots, fp_slots, subset_n, batches,
                    chunk, store_kw) -> None:
    # One event loop for every phase: the micro-batchers bind to it.
    from distributedratelimiting.redis_tpu.runtime.clock import ManualClock
    from distributedratelimiting.redis_tpu.runtime.fp_store import (
        FingerprintBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    timer = CompileTimer()
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    keys = key_names(n_keys)
    emit("keys", n=n_keys, build_s=time.perf_counter() - t0)

    clock, ref_clock = ManualClock(), ManualClock()
    store = DeviceBucketStore(n_slots=n_slots, clock=clock, **store_kw)
    ref = make_reference(ref_clock)
    phase_load("host", store, keys, timer, chunk)
    subset = phase_exact("host", store, clock, ref, ref_clock, keys, rng,
                         subset_n, batches)
    await phase_microbatch(store, ref, subset, rng)
    await phase_served(store, ref, subset, rng)
    phase_sweep(store, clock, ref, ref_clock, n_keys, subset)
    emit("host_memory", devices=device_memory())
    await store.aclose()
    del store

    clock, ref_clock = ManualClock(), ManualClock()
    fp = FingerprintBucketStore(n_slots=fp_slots, clock=clock, **store_kw)
    phase_load("fp", fp, keys, timer, chunk)
    check(fp.metrics.fp_unresolved == 0, "fp: window pressure during load")
    phase_exact("fp", fp, clock, make_reference(ref_clock), ref_clock, keys,
                rng, subset_n, batches)
    emit("fp_memory", devices=device_memory())
    await fp.aclose()


def run_mesh(seed: int, n_chips: int = 4, n_keys: int = N_KEYS,
             per_shard: int = MESH_PER_SHARD,
             fp_per_shard: int = MESH_FP_PER_SHARD, subset_n: int = SUBSET,
             batches: int = TRACE_BATCHES, chunk: int = LOAD_CHUNK) -> None:
    """Key-sharded two-level store over ``n_chips`` devices, both
    directory homes: grants equal the reference, the psum global tier
    equals the permits consumed across all shards, and every shard of
    the state sits on its own device."""
    import jax

    from distributedratelimiting.redis_tpu.parallel.mesh import create_mesh
    from distributedratelimiting.redis_tpu.parallel.mesh_store import (
        MeshBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.clock import ManualClock

    timer = CompileTimer()
    rng = np.random.default_rng(seed)
    keys = key_names(n_keys)
    mesh = create_mesh(n_chips)
    for home, slots in (("host", per_shard), ("fp", fp_per_shard)):
        clock, ref_clock = ManualClock(), ManualClock()
        store = MeshBucketStore(mesh, per_shard_slots=slots, clock=clock,
                                directory=home)
        name = f"mesh_{home}"
        phase_load(name, store, keys, timer, chunk)
        tier = store._sharded(CAP, RATE)
        check(tier.global_score == float(n_keys),
              f"{name}: global counter {tier.global_score} after the load, "
              f"{n_keys} permits consumed")
        # The global tier decays linearly between launches and adds each
        # launch's psum'd consumption: replay it. (The host home decays
        # at the fill rate; the fp home at its own decay rate, 0 unless
        # set.)
        decay = np.float32(getattr(tier, "decay_per_tick",
                                   tier.rate_per_tick))
        g = {"value": np.float32(n_keys), "ts": clock.now_ticks()}

        def follow(counts, want, g=g, clock=clock, decay=decay):
            now = clock.now_ticks()
            g["value"] = np.float32(max(
                np.float32(0), g["value"]
                - np.float32(now - g["ts"]) * decay))
            g["value"] += np.float32((counts * want.granted).sum())
            g["ts"] = now

        phase_exact(name, store, clock, make_reference(ref_clock), ref_clock,
                    keys, rng, subset_n, batches, after_batch=follow)
        ulp = float(np.spacing(np.float32(max(g["value"], 1.0))))
        check(abs(tier.global_score - float(g["value"])) <= 8 * ulp,
              f"{name}: global counter {tier.global_score}, replay "
              f"{float(g['value'])}")
        placed = {}
        for field in tier.state._fields:
            arr = getattr(tier.state, field)
            placed[field] = sorted({s.device.id
                                    for s in arr.addressable_shards})
            check(len(placed[field]) == n_chips,
                  f"{name}: {field} spans devices {placed[field]}")
        emit(f"{name}_placement", ok=True, shards=placed,
             global_counter=tier.global_score, devices=device_memory())
        asyncio.run(store.aclose())
        del store, tier
        jax.clear_caches()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip mesh phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    print(json.dumps({"phase": "device", "platform": platform,
                      "kind": devices[0].device_kind, "count": len(devices),
                      "jax": jax.__version__}), flush=True)
    if platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips}, {len(devices)} present",
              file=sys.stderr)
        return 2

    from distributedratelimiting.redis_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributedratelimiting.redis_tpu.utils.native import (
        load_directory_lib,
        load_frontend_lib,
    )

    cache = enable_compile_cache()
    # Warm or cold: entries already in the persistent compile cache.
    emit("compile_cache", dir=cache, entries_at_start=len(
        os.listdir(cache)) if cache and os.path.isdir(cache) else 0)
    check(load_directory_lib() is not None, "native directory did not load")
    check(load_frontend_lib() is not None, "native front end did not load")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_mesh(args.seed)
    else:
        run_one_chip(args.seed)
    emit("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
