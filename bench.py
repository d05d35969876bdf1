"""Benchmark: permit decisions/sec/chip at 10M active keys.

North star (BASELINE.json): >= 50M permit decisions/sec aggregate on a
v5e-8 with p99 acquire < 2ms, i.e. >= 6.25M decisions/sec/chip.
``vs_baseline`` is measured throughput / 6.25M (the per-chip north-star
share — the reference itself publishes no numbers, BASELINE.md).

Emission contract: the bench prints the FULL result JSON after *every*
completed section with ``"partial": true`` — a reader of the tail parses
the LAST JSON line, so a run cut mid-way still leaves every finished
metric on record. The final line has ``"partial": false``. A global
wall-clock budget (``BENCH_BUDGET_S``, default 1200s) bounds the whole
run: when it runs out, remaining sections are marked ``skipped_budget``.
The device is checked in this process before anything else: no TPU is
an error (exit 2), and a section that raises makes the run exit 1.
Sections that need no chip (the CPU stand-ins below) run in children
started with ``JAX_PLATFORMS=cpu``: the chip belongs to this process.
Restructuring the sections into cells is ROADMAP item A1.

Method (headline): steady-state device throughput of the batched
refill-and-decrement kernel over a 10M-slot HBM table — batches of 8K
random keys, SCAN_K batches pipelined per dispatch via lax.scan (each batch
keeps its own ``now`` operand), donated state buffers, host->device
transfer of fresh request arrays included in the timed loop, in-batch
duplicate serialization ON (exact invariant-3 semantics).

The pipeline is transfer-bound, not compute-bound (the kernel runs at
~3.3B decisions/s on resident operands; transfers overlap across queued
dispatches, with a sharp sustained-rate cliff above ~1MB per dispatch),
so the headline path uses the 3-bytes-per-decision operand layout
(``acquire_scan_packed24``: 24-bit packed slot ids, unit permits). The
5-bytes-per-decision mixed-count path (``acquire_scan_compact``) is
reported as a secondary metric.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading
import time

import numpy as np


N_SLOTS = 10_000_000
BATCH = 8192
SCAN_K = 32           # 768KB/dispatch packed24, amortizing dispatch
                      # overhead (the best K on the chip is not measured)
ITERS = 100           # timed dispatches of SCAN_K batches each
COMPACT_SCAN_K = 16   # 5B/decision fused path: 640KB/dispatch
CAPACITY = 100.0
RATE_PER_SEC = 50.0
NORTH_STAR_PER_CHIP = 50e6 / 8
TIMED_WINDOWS = 3     # best-of-N for every throughput metric; the link
                      # probe below records the host→device rate beside
                      # them (the spread on the chip is not measured)


def bench_kernel_throughput(jnp, K, clock):
    """Headline: 24-bit-packed scanned kernel path at 10M keys."""
    import jax

    rate_per_tick = jnp.float32(RATE_PER_SEC / 1024.0)
    cap = jnp.float32(CAPACITY)
    state = K.init_bucket_state(N_SLOTS)
    rng = np.random.default_rng(0)

    staged = [
        K.pack_slots24(rng.integers(0, N_SLOTS, (SCAN_K, BATCH)))
        for _ in range(4)
    ]

    def dispatch(state, packed):
        base = clock.now_ticks()
        nows = np.arange(SCAN_K, dtype=np.int32) + base
        return K.acquire_scan_packed24(
            state, jnp.asarray(packed), jnp.asarray(nows), cap,
            rate_per_tick,
        )

    # Warmup: compile + touch every page of the donated buffers.
    state, granted, _ = dispatch(state, staged[0])
    jax.block_until_ready(granted)

    # Best-of-N timed windows (TIMED_WINDOWS).
    best = 0.0
    for _ in range(TIMED_WINDOWS):
        t0 = time.perf_counter()
        for i in range(ITERS):
            state, granted, _ = dispatch(state, staged[i % len(staged)])
        jax.block_until_ready(granted)
        dt = time.perf_counter() - t0
        best = max(best, ITERS * SCAN_K * BATCH / dt)
    return best, state


def bench_link_probe(jnp):
    """Raw host→device upload rate of one 768KB buffer, recorded next to
    the throughput numbers so a slow host link is distinguishable from a
    code regression."""
    import jax

    x = np.ones((768 * 1024,), np.uint8)
    jax.block_until_ready(jax.device_put(x))
    best = 0.0
    for _ in range(TIMED_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(jax.device_put(x))
        best = max(best, 10 * x.nbytes / (time.perf_counter() - t0))
    return best / 1e6


def bench_rtt_probe(jnp):
    """Round-trip control for the latency-bound metrics: (a)
    ``link_rtt_ms`` — median round trip of a tiny up+down transfer, the
    per-flush floor every e2e latency number rides on; (b)
    ``link_pipeline_overlap_x`` — wall time of 8 serial result fetches
    over one overlapped ``device_get`` of 8. Recorded beside every run's
    numbers so a latency slide is attributable at a glance."""
    import jax

    x = np.ones((8,), np.uint8)
    jax.block_until_ready(jax.device_put(x))  # warm the path
    rtts = []
    for _ in range(15):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(x))  # one up + one down = one RTT
        rtts.append(time.perf_counter() - t0)
    rtts.sort()
    rtt_ms = rtts[len(rtts) // 2] * 1e3

    def fresh():
        # jax arrays cache their host copy after the first fetch — every
        # timed fetch needs arrays that have never come back.
        arrs = [jax.device_put(np.full((4096,), i, np.uint8))
                for i in range(8)]
        jax.block_until_ready(arrs)
        return arrs

    best_serial = float("inf")
    best_overlap = float("inf")
    for _ in range(3):
        arrs = fresh()
        t0 = time.perf_counter()
        for a in arrs:
            np.asarray(a)
        best_serial = min(best_serial, time.perf_counter() - t0)
        arrs = fresh()
        t0 = time.perf_counter()
        jax.device_get(arrs)  # one call: fetches overlap
        best_overlap = min(best_overlap, time.perf_counter() - t0)
    overlap_x = best_serial / best_overlap if best_overlap > 0 else 1.0
    return rtt_ms, overlap_x


def bench_compact_throughput(jnp, K, clock, state):
    """Secondary: mixed-count 5-bytes/decision path, fused into ONE
    operand per dispatch (``pack_compact5`` +
    ``acquire_scan_compact_fused``)."""
    import jax

    rate_per_tick = jnp.float32(RATE_PER_SEC / 1024.0)
    cap = jnp.float32(CAPACITY)
    rng = np.random.default_rng(1)
    sk = COMPACT_SCAN_K
    staged = [
        K.pack_compact5(rng.integers(0, N_SLOTS, (sk, BATCH)).astype(np.int32),
                        np.ones((sk, BATCH), np.uint8))
        for _ in range(4)
    ]

    def dispatch(state, fused):
        nows = np.arange(sk, dtype=np.int32) + clock.now_ticks()
        return K.acquire_scan_compact_fused(
            state, jnp.asarray(fused), jnp.asarray(nows), cap, rate_per_tick,
        )

    state, granted, _ = dispatch(state, staged[0])
    jax.block_until_ready(granted)
    iters = 60
    best = 0.0
    for _ in range(TIMED_WINDOWS):
        t0 = time.perf_counter()
        for i in range(iters):
            state, granted, _ = dispatch(state, staged[i % 4])
        jax.block_until_ready(granted)
        best = max(best, iters * sk * BATCH / (time.perf_counter() - t0))
    return best, state


def bench_single_batch(jnp, K, clock, state):
    """Secondary: one-batch-per-dispatch rate (latency-oriented path)."""
    import jax

    rate_per_tick = jnp.float32(RATE_PER_SEC / 1024.0)
    cap = jnp.float32(CAPACITY)
    rng = np.random.default_rng(1)
    slots = [jnp.asarray(rng.integers(0, N_SLOTS, BATCH), np.int32)
             for _ in range(4)]
    counts = jnp.ones((BATCH,), jnp.int32)
    valid = jnp.ones((BATCH,), bool)

    state, granted, _ = K.acquire_batch(
        state, slots[0], counts, valid, jnp.int32(clock.now_ticks()),
        cap, rate_per_tick, handle_duplicates=False)
    jax.block_until_ready(granted)
    iters = 100
    best = 0.0
    for _ in range(TIMED_WINDOWS):
        t0 = time.perf_counter()
        for i in range(iters):
            state, granted, _ = K.acquire_batch(
                state, slots[i % 4], counts, valid,
                jnp.int32(clock.now_ticks()), cap, rate_per_tick,
                handle_duplicates=False)
        jax.block_until_ready(granted)
        best = max(best, iters * BATCH / (time.perf_counter() - t0))
    return best


async def bench_e2e_bulk(store_mod, limiter_mod, options_mod):
    """End-to-end BULK serving path: ``acquire_many`` arrays through the
    partitioned limiter — key→slot resolve + packing + scanned dispatch +
    single-fetch readback all included; several calls overlap in flight.
    Returns (verdict-only decisions/s, with-remaining decisions/s)."""
    store = store_mod.DeviceBucketStore(n_slots=1 << 21, max_batch=8192)
    lim = limiter_mod.PartitionedRateLimiter(
        options_mod.TokenBucketOptions(
            token_limit=10_000_000, tokens_per_period=10_000_000,
            instance_name="bulk"), store)
    n = 1 << 17
    rng = np.random.default_rng(2)
    pool = [f"user{i}" for i in range(1_000_000)]
    calls = [[pool[j] for j in rng.integers(0, len(pool), n)]
             for _ in range(8)]

    async def run_round(with_remaining):
        await lim.acquire_many(calls[0], with_remaining=with_remaining)  # warm
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *(lim.acquire_many(c, with_remaining=with_remaining)
              for c in calls))
        dt = time.perf_counter() - t0
        return sum(len(r) for r in results) / dt

    verdict_only = max([await run_round(False) for _ in range(2)])
    with_remaining = await run_round(True)
    await store.aclose()
    return verdict_only, with_remaining


async def bench_fp_bulk():
    """Device-resident-directory bulk path: the same whole-array workload
    through `FingerprintBucketStore` — key→slot probe/insert happens
    IN-KERNEL on 64-bit fingerprints; the host's per-call duty is one
    native hashing pass (no host directory). Reported beside the
    host-directory bulk number so the operand-bytes vs host-work trade
    (docs/DESIGN.md §5b) is tracked per round on the real chip."""
    from distributedratelimiting.redis_tpu.runtime.fp_store import (
        FingerprintBucketStore,
    )

    store = FingerprintBucketStore(n_slots=1 << 21, max_batch=8192)
    n = 1 << 17
    rng = np.random.default_rng(3)
    pool = [f"user{i}" for i in range(1_000_000)]
    calls = [[pool[j] for j in rng.integers(0, len(pool), n)]
             for _ in range(4)]
    counts = [1] * n

    async def run_round():
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *(store.acquire_many(c, counts, 10_000_000.0, 10_000_000.0,
                                 with_remaining=False) for c in calls))
        dt = time.perf_counter() - t0
        return sum(len(r) for r in results) / dt

    await run_round()  # warm: insert pass + compile at the exact shapes
    rate = max([await run_round() for _ in range(2)])
    await store.aclose()
    return rate


async def bench_e2e_remote_bulk(store_mod):
    """End-to-end REMOTE bulk path: acquire_many through a real localhost
    socket — wire encode + chunking + server decode + scanned device
    dispatch + bulk reply — the reference's actual topology (every decision
    crosses a wire there, one RTT each; here one RTT carries ~80K)."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )

    backing = store_mod.DeviceBucketStore(n_slots=1 << 21, max_batch=8192)
    async with BucketStoreServer(backing) as srv:
        store = RemoteBucketStore(address=(srv.host, srv.port))
        try:
            n = 1 << 17
            rng = np.random.default_rng(3)
            pool = [f"user{i}" for i in range(1_000_000)]
            calls = [[pool[j] for j in rng.integers(0, len(pool), n)]
                     for _ in range(4)]
            counts = [1] * n

            async def run_round():
                t0 = time.perf_counter()
                results = await asyncio.gather(
                    *(store.acquire_many(c, counts, 10_000_000.0,
                                         10_000_000.0,
                                         with_remaining=False)
                      for c in calls))
                dt = time.perf_counter() - t0
                return sum(len(r) for r in results) / dt

            await run_round()  # warm: connect + compile + first chunks
            rate = max([await run_round() for _ in range(2)])
        finally:
            await store.aclose()
    await backing.aclose()
    return rate


async def bench_e2e_async_nproc(store_mod, n_clients: int = 4):
    """N-process per-request scaling: one server process owns the device;
    ``n_clients`` separate client processes drive the per-request
    ``acquire`` contract over TCP concurrently. The per-PROCESS async
    ceiling is Python task scheduling (~14µs/request on a builder's CPU
    host); this shows how the serving story scales past it:
    client-side Python multiplies out across processes, all coalescing
    into the one store's micro-batches."""
    import os
    import subprocess
    import sys

    backing = store_mod.DeviceBucketStore(
        n_slots=1 << 17, max_batch=4096, max_delay_s=300e-6, max_inflight=16)
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"  # clients never touch the device
    async with BucketStoreServer(backing, host="127.0.0.1") as srv:
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--nproc-client", srv.host, str(srv.port), str(i)],
                stdout=subprocess.PIPE, text=True, env=env)
            for i in range(n_clients)
        ]

        def harvest(p):
            try:
                out, _ = p.communicate(timeout=300)
                return json.loads(out.strip().splitlines()[-1])["rate"]
            except Exception:  # a dead/hung client degrades the aggregate,
                p.kill()      # never the whole bench run
                return 0.0

        rates = await asyncio.gather(
            *(asyncio.to_thread(harvest, p) for p in procs))
    await backing.aclose()
    return sum(rates), [r for r in rates if r]


def _nproc_client(host: str, port: str, wid: str) -> None:
    """One client process of the N-process scaling bench: closed-loop
    per-request acquires over a RemoteBucketStore."""
    import faulthandler

    # A stalled client gets killed by the parent's harvest timeout and
    # silently reads as rate 0 — dump where it actually was first.
    faulthandler.dump_traceback_later(240, exit=True)
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    async def run() -> None:
        # Per-request framing: closed-loop workers' requests then merge
        # across ALL clients in the SERVER's micro-batcher (one device
        # dispatch per round). Client-side coalescing would make each
        # client's flush its own bulk dispatch — N clients ⇒ N sequential
        # device round-trips per closed-loop round.
        store = RemoteBucketStore(address=(host, int(port)),
                                  coalesce_requests=False)

        async def worker(w: int, reqs: int) -> None:
            for j in range(reqs):
                await store.acquire(f"u{wid}-{w}-{j % 1000}", 1,
                                    10_000_000.0, 10_000_000.0)

        await asyncio.gather(*(worker(w, 30) for w in range(32)))  # warm
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(w, 150) for w in range(64)))
        rate = 64 * 150 / (time.perf_counter() - t0)
        await store.aclose()
        print(json.dumps({"rate": rate}))

    asyncio.run(run())


def bench_pallas_sweep(store_mod):
    """The COMPILED (non-interpret) Pallas streaming sweep on this
    platform: trigger a sweep over reclaimable slots and check it freed
    them (a Pallas failure raises: there is no fallback)."""
    from distributedratelimiting.redis_tpu.runtime.clock import ManualClock

    clock = ManualClock()
    store = store_mod.DeviceBucketStore(n_slots=1024, clock=clock)
    for i in range(64):
        store.acquire_blocking(f"sweep{i}", 1, 10.0, 10.0)
    clock.advance_seconds(5.0)  # everything refills → TTL-expired
    store.sweep_all()
    return bool(store.use_pallas_sweep and store.metrics.slots_evicted >= 64)


async def bench_e2e_async(store_mod, limiter_mod, options_mod):
    """End-to-end asyncio path: micro-batched partitioned limiter driven by
    a closed-loop worker pool deep enough to keep several flush readbacks in
    flight (readbacks overlap across flushes). Returns (decisions/s, p99
    seconds)."""
    store = store_mod.DeviceBucketStore(
        n_slots=1 << 17, max_batch=4096, max_delay_s=300e-6, max_inflight=16)
    lim = limiter_mod.PartitionedRateLimiter(
        options_mod.TokenBucketOptions(
            token_limit=10_000_000, tokens_per_period=10_000_000,
            instance_name="bench"), store)
    lat: list[float] = []
    workers = 16384
    reqs_per_worker = 3

    async def worker(w):
        for j in range(reqs_per_worker):
            t0 = time.perf_counter()
            await lim.acquire_async(f"user{(w * 7 + j) % 10000}", 1)
            lat.append(time.perf_counter() - t0)

    # Warm the kernel (one compile per table) at full depth.
    await asyncio.gather(*(worker(w) for w in range(2048)))
    lat.clear()

    t0 = time.perf_counter()
    await asyncio.gather(*(worker(w) for w in range(workers)))
    dt = time.perf_counter() - t0
    throughput = len(lat) / dt

    # Low-load latency probe: p99 without saturation queueing — at this
    # depth each request's latency ≈ flush deadline + one device round
    # trip.
    # ≥10K samples so the p99 rests on ~100 observations, not 2.
    lat.clear()

    async def low_load_worker(w):
        for j in range(160):
            t0 = time.perf_counter()
            await lim.acquire_async(f"user{(w * 7 + j) % 10000}", 1)
            lat.append(time.perf_counter() - t0)

    await asyncio.gather(*(low_load_worker(w) for w in range(64)))
    lat.sort()
    p99_low = lat[int(len(lat) * 0.99)]
    await store.aclose()
    return throughput, p99_low


async def bench_serving_p99(store_mod, on_d64=None):
    """SERVER-side p99: request-arrival → result-ready on a
    BucketStoreServer fronting the device store — ≥10K samples from the
    server's own histogram (utils/metrics.LatencyHistogram) at a bounded
    closed-loop depth (64 in flight) so the number is steady-state serving
    latency, not open-loop queueing blowup; then a short depth-4 window
    (640 samples — low-confidence by design, the sample count is emitted
    with it) to separate link RTT from queueing. ``on_d64`` fires with
    the depth-64 numbers as soon as they exist, so a failure during the
    extra window cannot discard the headline measurement."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )

    backing = store_mod.DeviceBucketStore(
        n_slots=1 << 17, max_batch=4096, max_delay_s=300e-6, max_inflight=16)
    async with BucketStoreServer(backing) as srv:
        # Per-request framing so every request is its own latency sample
        # (client coalescing would make samples = flushes).
        store = RemoteBucketStore(address=(srv.host, srv.port),
                                  coalesce_requests=False)
        try:
            async def worker(w, reqs):
                for j in range(reqs):
                    await store.acquire(f"user{(w * 11 + j) % 10000}", 1,
                                        10_000_000.0, 10_000_000.0)

            # Warm (compile + connect), then reset the histogram so the
            # p99 reflects steady state, not the first compile.
            await asyncio.gather(*(worker(w, 10) for w in range(64)))
            srv.serving_latency.reset()
            await asyncio.gather(*(worker(w, 160) for w in range(64)))
            stats = await store.stats()
            if on_d64 is not None:
                on_d64(stats["serving_p99_ms"], stats["serving_p50_ms"],
                       stats["serving_samples"])
            # Low-depth window too: depth 4 reads as ~one flush round
            # trip and separates link latency from queueing.
            srv.serving_latency.reset()
            await asyncio.gather(*(worker(w, 160) for w in range(4)))
            stats4 = await store.stats()
        finally:
            await store.aclose()
    await backing.aclose()
    return (stats["serving_p99_ms"], stats["serving_p50_ms"],
            stats["serving_samples"], stats4["serving_p99_ms"],
            stats4["serving_p50_ms"], stats4["serving_samples"])


def bench_serving_p99_cpu(timeout_s: float = 600.0,
                          backing: str = "device",
                          native: bool = False,
                          tier0: bool = False) -> dict | None:
    """Co-located-device stand-in for the <2ms serving north star, now a
    TWO-process rig: the server child owns the store +
    kernel on its own core; a separate load child drives closed-loop
    per-request traffic at depths 4/16/64. The p99 is the SERVER's own
    arrival→ready histogram over a post-warmup window (stats reset flag),
    so client-side Python scheduling no longer pollutes the number the
    way the old single-process probe did. Returns the per-depth dict, or
    None if either child failed.

    ``backing="instant"`` swaps the XLA-CPU device store for
    ``InProcessBucketStore`` — a pure-Python kernel that answers in
    microseconds. The serving p99 against it is the FRAMEWORK's own
    overhead (wire + asyncio + per-request handling) with the kernel
    removed; (device-backed p99 − instant p99) isolates what the
    stand-in's XLA-CPU flush contributes, the part a co-located TPU
    replaces (its cost on the chip is not measured)."""
    import concurrent.futures
    import subprocess


    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    deadline = time.monotonic() + timeout_s
    server_argv = [sys.executable, os.path.abspath(__file__),
                   "--serving-server-child", backing]
    if native:
        server_argv.append("native")
    if tier0:
        server_argv.append("tier0")
    server = subprocess.Popen(
        server_argv,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    # No `with` around the executor: its shutdown joins the reader thread,
    # which only returns at EOF — a child that never prints would turn the
    # timeout below into a circular wait. The finally's kill/close EOFs
    # the pipe, so the parked thread always unblocks before process exit.
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        line = pool.submit(server.stdout.readline).result(
            timeout=min(120.0, timeout_s))
        addr = json.loads(line)
        load_flag = ("--native-load-child" if native
                     else "--serving-load-child")
        load_argv = [sys.executable, os.path.abspath(__file__),
                     load_flag, addr["host"], str(addr["port"])]
        if tier0:
            load_argv.append("hot")  # hot-key workload: tier-0's case
        load = subprocess.run(
            load_argv,
            env=env, capture_output=True, text=True,
            timeout=max(deadline - time.monotonic(), 30.0))
        if load.returncode != 0:
            return None
        return json.loads(load.stdout.strip().splitlines()[-1])
    except Exception:  # child hung/died: skip the co-located stand-in
        return None
    finally:
        try:
            server.stdin.close()  # the server child parks on stdin EOF
            server.wait(timeout=10)
        except Exception:
            server.kill()
        pool.shutdown(wait=False)


def _serving_server_child(backing_kind: str = "device",
                          native: bool = False,
                          tier0: bool = False,
                          shards: int = 1,
                          pin: bool = False,
                          uring: str | None = None) -> None:
    """Server half of the co-located stand-in: owns the (CPU-platform)
    device store and its kernel — or, for ``backing_kind="instant"``, the
    pure-Python ``InProcessBucketStore`` whose microsecond kernel makes
    the serving histogram a pure framework-overhead measurement. With
    ``native=True`` the sockets are served by the C++ front-end
    (native/frontend.cc) — epoll by default, or the io_uring data plane
    when ``uring`` is ``"on"``/``"sqpoll"`` (round 16). Parks until the
    parent closes stdin, then prints ONE more JSON line — the transport
    counters (fe_uring_counts: data-plane syscalls, ring enters, SQEs,
    fallbacks) and this process's rusage CPU-seconds — so the rig can
    charge syscalls/frame and cycles/row to the server, not the client."""
    if pin:
        # CPU discipline for the pinned multi-shard rig: the C shard
        # threads get CPUs 0..N-1 EXCLUSIVELY (fe_start_sharded pins
        # them there); every Python thread of this process — the
        # asyncio loop that serves residue frames and runs the tier-0
        # sync pump, and the per-shard pump threads — is herded onto
        # the next few CPUs so neither the shards nor the load child
        # can starve the reconciliation loop (a sync pump starved past
        # max_stale_s fails SAFE — stale replicas stop deciding — but
        # the resulting all-residue storm is exactly the regime the
        # sweep must not measure by accident).
        nproc = os.cpu_count() or 1
        herd = set(range(shards, min(shards + 4, nproc))) or {0}
        try:
            os.sched_setaffinity(0, herd)
        except OSError:
            pass
    from distributedratelimiting.redis_tpu.runtime import store as store_mod
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )

    async def run() -> None:
        if backing_kind == "instant":
            backing = store_mod.InProcessBucketStore()
        else:
            backing = store_mod.DeviceBucketStore(
                n_slots=1 << 17, max_batch=4096, max_delay_s=300e-6,
                max_inflight=16)
        native_tier0 = False
        if tier0:
            from distributedratelimiting.redis_tpu.runtime.native_frontend import (
                Tier0Config,
            )

            # Tight sync cadence: the bench window is seconds long and the
            # hit-rate/overadmit gauges should reflect settled envelopes.
            # The pinned multi-shard rig also raises max_budget: at
            # node-level demand (~2M permits/s/key) the 1M default gives
            # each key <1s of envelope headroom, so any sync-round
            # hiccup longer than that tips the whole keyspace into the
            # all-residue regime (fail-safe but Python-speed — see
            # docs/OPERATIONS.md §12). 16M ≈ 10s of headroom.
            native_tier0 = (Tier0Config(sync_interval_s=0.01,
                                        max_budget=float(1 << 24))
                            if pin else
                            Tier0Config(sync_interval_s=0.01))
        async with BucketStoreServer(backing,
                                     native_frontend=native,
                                     native_tier0=native_tier0,
                                     native_shards=shards,
                                     native_pin_shards=pin,
                                     native_uring=uring) as srv:
            print(json.dumps({"host": srv.host, "port": srv.port}),
                  flush=True)
            await asyncio.get_running_loop().run_in_executor(
                None, sys.stdin.read)
            # Shutdown report, read by _shard_rig AFTER it closes our
            # stdin: transport counters must be sampled while the
            # front-end is still up (the handle dies with the context
            # manager), and rusage here charges the server process only.
            import resource

            ru = resource.getrusage(resource.RUSAGE_SELF)
            tail: dict = {"server_cpu_s": round(ru.ru_utime
                                                + ru.ru_stime, 4)}
            if native and srv._native is not None:
                ts = srv._native.transport_stats()
                if ts is not None:
                    tail["transport"] = ts
                # ε-consumption counters (round 18): cumulative tier-0
                # grant tokens + the per-slice split (fe_t0_eps) so the
                # recapture lanes can price local-admission drift per
                # shard slice beside the transport economics.
                t0 = srv._native.tier0_stats()
                if t0:
                    tail["t0_grant_tokens"] = t0.get("grant_tokens",
                                                     0.0)
                    tail["t0_overadmit_total"] = t0.get(
                        "overadmit_total", 0.0)
                eps = srv._native.t0_eps_tokens()
                if eps:
                    tail["t0_eps_tokens"] = eps
            print(json.dumps(tail), flush=True)
        await backing.aclose()

    asyncio.run(run())


def _native_load_child(host: str, port: str,
                       workload: str = "uniform") -> None:
    """Load half of the native-front-end rig: the C closed-loop load
    generator (native_frontend.native_loadgen) at a depth sweep, with the
    server's own C-side histogram sampled per window — both directions of
    the ceiling (req/s and p99) come from native measurement, so Python
    client scheduling bounds neither. ``workload="hot"`` collapses the
    keyspace to one key per connection — the tier-0 admission cache's
    target shape — and reports the server's tier-0 gauges beside the
    rates."""
    from distributedratelimiting.redis_tpu.runtime.native_frontend import (
        native_loadgen,
    )
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    keyspace = 1 if workload == "hot" else 1000

    async def run() -> None:
        store = RemoteBucketStore(address=(host, int(port)),
                                  coalesce_requests=False)
        out: dict = {}
        # Warm: connects, compiles nothing (instant backing), seeds keys
        # (and, for the hot workload, installs the tier-0 replicas).
        await asyncio.to_thread(native_loadgen, host, int(port),
                                conns=4, depth=16, reqs_per_conn=2000,
                                keyspace=keyspace)
        for depth in (4, 16, 64, 256):
            await store.stats(reset=True)
            replies, _, elapsed = await asyncio.to_thread(
                native_loadgen, host, int(port), conns=4, depth=depth,
                reqs_per_conn=50000, keyspace=keyspace)
            stats = await store.stats()
            out[f"d{depth}"] = {
                "rate": replies / elapsed,
                "p50_ms": stats["serving_p50_ms"],
                "p99_ms": stats["serving_p99_ms"],
                "samples": stats["serving_samples"],
            }
        stats = await store.stats()
        if "tier0" in stats:
            out["tier0"] = stats["tier0"]
        await store.aclose()
        print(json.dumps(out), flush=True)

    asyncio.run(run())


def _bulk_load_child(host: str, port: str, workload: str = "hot") -> None:
    """Load half of the native-BULK rig: closed-loop ACQUIRE_MANY frames
    (4096 rows each) from a few concurrent submitters. The Python client
    cost is per-frame, amortized over 4096 rows, so it bounds nothing —
    the server's bulk lane is the measured ceiling. ``workload="hot"``
    draws from 64 keys at high capacity (every row tier-0-hostable: the
    native lane's target shape); ``"cold"`` draws from 100K keys (all
    residue — the zero-copy handoff itself)."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    keyspace = 64 if workload == "hot" else 100_000
    n = 4096
    capacity, fill = 1e8, 1e8

    async def run() -> None:
        store = RemoteBucketStore(address=(host, int(port)))
        keys = [f"b{i % keyspace}" for i in range(n)]
        counts = [1] * n
        rows = 0

        async def worker(reps: int) -> None:
            nonlocal rows
            for _ in range(reps):
                res = await store.acquire_many(keys, counts, capacity,
                                               fill)
                rows += len(res.granted)

        # Warm: connects, seeds keys, installs tier-0 replicas (the
        # first frames are all-residue by construction).
        await asyncio.gather(*(worker(4) for _ in range(4)))
        pre = await store.stats(reset=True)
        rows = 0
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(25) for _ in range(4)))
        dt = time.perf_counter() - t0
        stats = await store.stats()
        out = {
            "rows_per_s": rows / dt,
            "rows": rows,
            "elapsed_s": dt,
            "p50_ms": stats["serving_p50_ms"],
            "p99_ms": stats["serving_p99_ms"],
            "samples": stats["serving_samples"],
        }
        if "tier0" in stats:
            out["tier0_hit_rate"] = stats["tier0"]["hit_rate"]
            if "tier0" in pre:
                # Measured-window hit rate: the warm frames' deliberate
                # all-residue installs must not dilute the steady-state
                # figure the acceptance bound names.
                d = {k: stats["tier0"][k] - pre["tier0"][k]
                     for k in ("hits", "local_denies", "misses")}
                eligible = sum(d.values())
                if eligible:
                    out["window_tier0_hit_rate"] = (
                        (d["hits"] + d["local_denies"]) / eligible)
        if "native_bulk" in stats:
            out["native_bulk"] = stats["native_bulk"]
        await store.aclose()
        print(json.dumps(out), flush=True)

    asyncio.run(run())


def _bulk_rig(server_args: "list[str]", load_args: "list[str]",
              timeout_s: float) -> dict | None:
    """One two-process bulk measurement: a --serving-server-child with
    ``server_args`` and a --bulk-load-child with ``load_args`` (the
    bench_serving_p99_cpu child discipline — a wedged store op costs the
    section, not the runner). Returns the load child's JSON, or None."""
    import concurrent.futures
    import subprocess


    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    deadline = time.monotonic() + timeout_s
    server = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--serving-server-child", *server_args],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        line = pool.submit(server.stdout.readline).result(
            timeout=min(120.0, timeout_s))
        addr = json.loads(line)
        load = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--bulk-load-child", addr["host"], str(addr["port"]),
             *load_args],
            env=env, capture_output=True, text=True,
            timeout=max(deadline - time.monotonic(), 30.0))
        if load.returncode != 0:
            return None
        return json.loads(load.stdout.strip().splitlines()[-1])
    except Exception:
        return None
    finally:
        try:
            server.stdin.close()
            server.wait(timeout=10)
        except Exception:
            server.kill()
        pool.shutdown(wait=False)


def bench_native_bulk(timeout_s: float = 420.0) -> dict | None:
    """``serving_native_bulk`` section: the native bulk lane measured
    against the asyncio bulk path (instant backing, hot keyspace — the
    ≥2×-per-core acceptance arm at high tier-0 hit rate) AND against a
    device-class backing (XLA-CPU stand-in: multi-ms flush — the regime
    the 2 ms p99 north star fears; the real-device number stays owed in
    benchmarks/recapture.py's ledger until a healthy TPU window)."""
    budget = max(timeout_s / 4.0, 60.0)
    native = _bulk_rig(["instant", "native", "tier0"], ["hot"], budget)
    asy = _bulk_rig(["instant"], ["hot"], budget)
    device = _bulk_rig(["device", "native", "tier0"], ["hot"], budget)
    # Cold arm: 100K-key uniform draws, tier-0 off — every row is
    # residue, so this is the zero-copy handoff itself against the
    # multi-ms flush (the no-shield worst case of the regime).
    device_cold = _bulk_rig(["device", "native"], ["cold"], budget)
    if native is None or asy is None:
        return None
    out = {"native": native, "asyncio": asy}
    if device is not None:
        out["device"] = device
    if device_cold is not None:
        out["device_cold"] = device_cold
    return out


def _serving_load_child(host: str, port: str) -> None:
    """Load half: closed-loop per-request acquires at a depth sweep; each
    depth's window is warm → stats(reset) → ≥10K measured samples →
    stats. Reports the server-side serving histogram AND the store's
    flush histogram (dispatch+kernel+readback) so serving p99 decomposes
    into device-side floor vs framework queueing."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    async def run() -> None:
        store = RemoteBucketStore(address=(host, int(port)),
                                  coalesce_requests=False)
        out: dict = {}

        async def worker(w: int, reqs: int) -> None:
            for j in range(reqs):
                await store.acquire(f"user{(w * 11 + j) % 10000}", 1,
                                    10_000_000.0, 10_000_000.0)

        for depth in (4, 16, 64):
            await asyncio.gather(*(worker(w, 40) for w in range(depth)))
            await store.stats(reset=True)
            reqs = max(10240 // depth, 160)
            await asyncio.gather(*(worker(w, reqs) for w in range(depth)))
            stats = await store.stats()
            flush = stats.get("store", {})
            out[f"d{depth}"] = {
                "p99_ms": stats["serving_p99_ms"],
                "p50_ms": stats["serving_p50_ms"],
                "samples": stats["serving_samples"],
                "flush_p99_ms": flush.get("flush_p99_ms"),
                "flush_p50_ms": flush.get("flush_p50_ms"),
            }
        await store.aclose()
        print(json.dumps(out), flush=True)

    asyncio.run(run())


def _shard_load_child(host: str, port: str, shards: str) -> None:
    """Load half of the multi-shard rig: 3 loadgen threads per shard,
    each a C closed-loop bulk client (fe_lg_bulk — frames built and
    replies counted in C, so the client bounds nothing) pinned AWAY
    from the shard CPUs (the server child pins shard i to CPU i; an
    unpinned client thread scheduled onto a shard CPU steals exactly
    the core the measurement is charging). The kernel's SO_REUSEPORT
    hash spreads each thread's 4 connections across shards. Reports
    the aggregate rows/s over the threads' own windows plus the
    server's merged and per-shard gauges, and the total frames/rows
    this child pushed (warm included) so the rig can divide the
    server's lifetime syscall counter by a lifetime denominator.
    ``DRL_BENCH_SHARD_FRAMES`` / ``DRL_BENCH_SHARD_ROWS`` shrink the
    per-thread workload for small hosts (defaults 400 / 4096)."""
    import threading

    from distributedratelimiting.redis_tpu.runtime.native_frontend import (
        native_bulk_loadgen,
    )
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    n_shards = int(shards)
    nproc = os.cpu_count() or 1
    # Mirror of the server child's CPU discipline: shards own CPUs
    # 0..N-1, the server's Python threads own the next 4 — clients take
    # what's left (everything, when the box is too small to carve).
    first = min(n_shards + 4, max(nproc - 2, 0))
    client_cpus = (set(range(first, nproc))
                   if first < nproc else set(range(nproc)))
    n_threads = max(6, 4 * n_shards)
    frames_hot = int(os.environ.get("DRL_BENCH_SHARD_FRAMES", "400"))
    rows_hot = int(os.environ.get("DRL_BENCH_SHARD_ROWS", "4096"))

    def one(out: list, warm: bool) -> None:
        try:
            os.sched_setaffinity(0, client_cpus)
        except OSError:
            pass  # restricted cpuset: measure unpinned
        frames, rows, granted, el = native_bulk_loadgen(
            host, int(port), conns=4, depth=2 if warm else 8,
            frames_per_conn=10 if warm else frames_hot,
            rows_per_frame=1024 if warm else rows_hot, keyspace=64)
        out.append((frames, rows, granted, el))

    async def run() -> None:
        store = RemoteBucketStore(address=(host, int(port)))
        # Warm: connects + installs the 64 hot keys' tier-0 replicas.
        rows_out: list = []
        th = [threading.Thread(target=one, args=(rows_out, True))
              for _ in range(4)]
        for t in th:
            t.start()
        for t in th:
            t.join()
        frames_sent = sum(f for f, _r, _g, _el in rows_out)
        rows_sent = sum(r for _f, r, _g, _el in rows_out)
        await store.stats(reset=True)
        best = 0.0
        for _ in range(3):
            rows_out = []
            th = [threading.Thread(target=one, args=(rows_out, False))
                  for _ in range(n_threads)]
            for t in th:
                t.start()
            for t in th:
                t.join()
            best = max(best, sum(r / el for _f, r, _g, el in rows_out))
            frames_sent += sum(f for f, _r, _g, _el in rows_out)
            rows_sent += sum(r for _f, r, _g, _el in rows_out)
        stats = await store.stats()
        out = {
            "rows_per_s": best,
            "shards": n_shards,
            "load_threads": n_threads,
            "frames_sent": frames_sent,
            "rows_sent": rows_sent,
            "p50_ms": stats["serving_p50_ms"],
            "p99_ms": stats["serving_p99_ms"],
        }
        nb = stats.get("native_bulk")
        if nb:
            out["rows_local_frac"] = (nb["rows_local"]
                                      / max(nb["rows"], 1))
        if "tier0" in stats:
            out["tier0_hit_rate"] = stats["tier0"]["hit_rate"]
        per = stats.get("shards")
        if per:
            out["per_shard_rows"] = [r["native_bulk"]["rows"]
                                     for r in per]
        await store.aclose()
        print(json.dumps(out), flush=True)

    asyncio.run(run())


def _shard_rig(shards: int, timeout_s: float,
               uring: str | None = None) -> dict | None:
    """One multi-shard measurement: an instant-backed native server
    child with ``shards`` pinned shards (tier-0 armed), driven by a
    --shard-load-child (the bench_serving_p99_cpu child discipline).
    ``uring`` picks the transport arm ("on"/"sqpoll"; None = epoll).
    After the load finishes the server's stdin is closed and its
    shutdown line (transport counters + rusage) is folded into the
    load child's result."""
    import concurrent.futures
    import subprocess


    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    deadline = time.monotonic() + timeout_s
    argv = [sys.executable, os.path.abspath(__file__),
            "--serving-server-child", "instant", "native", "tier0",
            f"shards={shards}", "pin"]
    if uring is not None:
        argv.append(f"uring={uring}")
    server = subprocess.Popen(
        argv,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        line = pool.submit(server.stdout.readline).result(
            timeout=min(120.0, timeout_s))
        addr = json.loads(line)
        load = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--shard-load-child", addr["host"], str(addr["port"]),
             str(shards)],
            env=env, capture_output=True, text=True,
            timeout=max(deadline - time.monotonic(), 30.0))
        if load.returncode != 0:
            return None
        res = json.loads(load.stdout.strip().splitlines()[-1])
        server.stdin.close()
        tail = pool.submit(server.stdout.readline).result(timeout=30.0)
        if tail.strip():
            res.update(json.loads(tail))
        return res
    except Exception:
        return None
    finally:
        try:
            if not server.stdin.closed:
                server.stdin.close()
            server.wait(timeout=10)
        except Exception:
            server.kill()
        pool.shutdown(wait=False)


def bench_native_shards(timeout_s: float = 600.0) -> dict | None:
    """``serving_native_shards`` section: the multi-shard front-end's
    node-level scaling curve (round 11). One native server per point,
    shards ∈ {1, 2, 4, 8} pinned to CPUs 0..N-1, instant backing,
    tier-0 armed, hot 64-key ACQUIRE_MANY workload from the C bulk
    loadgen — rows/s through ONE port as a function of shard count.
    The acceptance bound is s4 ≥ 3.5× s1 on the same machine; the
    device-backed arm stays owed in benchmarks/recapture.py
    (native_fe_shard_sweep) until a TPU window."""
    out: dict = {}
    budget = max(timeout_s / 4.5, 60.0)
    for s in (1, 2, 4, 8):
        res = _shard_rig(s, budget)
        if res is None:
            if s == 1:
                return None  # nothing to normalize against
            continue
        out[f"s{s}"] = res
    if "s1" in out and "s4" in out:
        out["speedup_4v1"] = (out["s4"]["rows_per_s"]
                              / out["s1"]["rows_per_s"])
    if "s1" in out and "s8" in out:
        out["speedup_8v1"] = (out["s8"]["rows_per_s"]
                              / out["s1"]["rows_per_s"])
    return out


def _nominal_mhz() -> float:
    """Nominal clock for the cycles/row stand-in: first ``cpu MHz``
    row of /proc/cpuinfo, 2 GHz when the field is absent (ARM,
    containers that mask cpuinfo). A stand-in, not a cycle counter —
    the column is only compared across arms on the SAME host."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("cpu mhz"):
                    return float(line.split(":", 1)[1])
    except (OSError, ValueError, IndexError):
        pass
    return 2000.0


def bench_native_uring(timeout_s: float = 540.0) -> dict | None:
    """``serving_native_uring`` section: transport economics of the
    multi-shard front-end (round 16). The serving_native_shards rig,
    run once per transport arm — epoll, io_uring, io_uring+SQPOLL —
    at 1/4/8 shards. Two headline columns per arm:

    - syscalls/frame — the server's own data-plane syscall counter
      (every accept/recv/send/epoll_wait/io_uring_enter is counted in
      C at the call site, both transports) divided by the frames the
      load child pushed over the server's lifetime. This is the number
      the io_uring rebuild exists to shrink: one ring enter drains and
      submits for every ready connection, where epoll pays a recv and
      a send per connection per burst, and SQPOLL retires the submit
      enter too.
    - cycles/row — server-process rusage CPU-seconds x nominal MHz
      divided by rows pushed: a CPU stand-in, not a hardware cycle
      counter.

    A uring arm whose shards fell back to epoll (old kernel, seccomp)
    is reported with ``fell_back: true`` instead of being passed off
    as ring numbers; kernels with no io_uring at all run the epoll arm
    only and say so in ``probe``."""
    from distributedratelimiting.redis_tpu.runtime.native_frontend import (
        uring_probe,
    )

    ok, reason = uring_probe()
    mhz = _nominal_mhz()
    out: dict = {"uring_available": ok, "probe": reason,
                 "nominal_mhz": round(mhz, 1)}
    arms = [("epoll", None)]
    if ok:
        arms += [("uring", "on"), ("sqpoll", "sqpoll")]
    points = [(name, uring, s) for name, uring in arms for s in (1, 4, 8)]
    budget = max(timeout_s / (len(points) + 1.0), 40.0)
    got_any = False
    for name, uring, s in points:
        res = _shard_rig(s, budget, uring=uring)
        if res is None:
            continue
        row: dict = {"rows_per_s": res["rows_per_s"],
                     "p50_ms": res["p50_ms"], "p99_ms": res["p99_ms"]}
        tr = res.get("transport")
        if tr is not None:
            row["uring_shards"] = tr["uring_shards"]
            row["fallbacks"] = tr["fallbacks"]
            if uring is not None and tr["uring_shards"] < s:
                row["fell_back"] = True  # loud: NOT ring numbers
            frames = res.get("frames_sent")
            if frames:
                row["syscalls_per_frame"] = round(
                    tr["io_syscalls"] / frames, 3)
        cpu_s = res.get("server_cpu_s")
        rows_sent = res.get("rows_sent")
        if cpu_s and rows_sent:
            row["cycles_per_row"] = round(
                cpu_s * mhz * 1e6 / rows_sent, 1)
        out[f"{name}_s{s}"] = row
        got_any = True
    return out if got_any else None


def bench_metrics_overhead() -> tuple[float, float, float, int,
                                      float, float]:
    """``serving_metrics_overhead`` section: the observability plane's
    whole-cost audit. Same closed-loop per-request rig (asyncio server,
    instant in-process backing so the kernel contributes nothing) run
    twice — plane ENABLED (heavy-hitter sketch fed per request, stage
    stamps, flight recorder armed, /metrics listener up and scraped
    mid-run) vs ``observability=False``. The documented contract is
    <3% throughput cost with the plane on; exposition itself is
    pull-only, so the scrape rides the measured window to keep the
    audit honest.

    A third arm audits DISTRIBUTED TRACING at the production default
    (head sampling 1%): tracing toggles on the process-global tracer
    around ABBA window blocks on the plane-enabled rig, so the delta
    isolates the tracing hooks (coin flips, context captures, span
    machinery on the sampled 1%) under the same <3% contract.

    Returns (on_rate, off_rate, overhead_pct — the median of paired
    per-window deltas, scrape_bytes, tracing_on_rate,
    tracing_overhead_pct)."""
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )
    from distributedratelimiting.redis_tpu.utils import tracing

    async def main() -> tuple[float, float, float, int, float, float]:
        async def make(observability: bool):
            srv = BucketStoreServer(
                InProcessBucketStore(), observability=observability,
                metrics_port=0 if observability else None)
            await srv.start()
            store = RemoteBucketStore(address=(srv.host, srv.port),
                                      coalesce_requests=False)
            return srv, store

        async def window(store, depth: int = 32, reqs: int = 150) -> float:
            async def worker(w: int) -> None:
                for j in range(reqs):
                    await store.acquire(f"user{(w * 13 + j) % 512}", 1,
                                        1e7, 1e7)

            t0 = time.perf_counter()
            await asyncio.gather(*(worker(w) for w in range(depth)))
            return depth * reqs / (time.perf_counter() - t0)

        srv_on, store_on = await make(True)
        srv_off, store_off = await make(False)
        try:
            # Warm both rigs, then measure ABBA-ordered window blocks
            # (on,off,off,on) and take the median per-block delta. The
            # shared-core scheduler drifts on multi-second scales, which
            # defeated every simpler estimator tried here (sequential
            # single-shot: -3%..+49% "overhead"; interleaved best-of-3:
            # ±5% A/A floor; strict on-first pairs: alternation bias —
            # a same-period slow phase lands on one side every time).
            # ABBA cancels linear drift inside each block by symmetry.
            await window(store_on, depth=16, reqs=40)
            await window(store_off, depth=16, reqs=40)
            blocks = []
            for _ in range(4):
                a1 = await window(store_on)
                b1 = await window(store_off)
                b2 = await window(store_off)
                a2 = await window(store_on)
                blocks.append(((a1 + a2) / 2, (b1 + b2) / 2))
            on_rate = max(a for a, _ in blocks)
            off_rate = max(b for _, b in blocks)
            deltas = sorted((b - a) / b for a, b in blocks)
            median_delta = deltas[len(deltas) // 2]
            # One mid-run scrape proves the plane was live and bills the
            # exposition to the enabled side.
            reader, writer = await asyncio.open_connection(
                srv_on.host, srv_on.metrics_port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: bench\r\n\r\n")
            await writer.drain()
            data = await reader.read()
            writer.close()
            # Tracing arm: same ABBA discipline, toggling the global
            # tracer around window blocks on the SAME enabled rig (both
            # rigs share the process-global tracer, so a two-rig pairing
            # would contaminate the control side).
            tblocks = []
            try:
                for _ in range(4):
                    tracing.configure(enabled=True, sample_rate=0.01,
                                      keep_rate=0.1)
                    a1 = await window(store_on)
                    tracing.configure(enabled=False)
                    b1 = await window(store_on)
                    b2 = await window(store_on)
                    tracing.configure(enabled=True, sample_rate=0.01,
                                      keep_rate=0.1)
                    a2 = await window(store_on)
                    tracing.configure(enabled=False)
                    tblocks.append(((a1 + a2) / 2, (b1 + b2) / 2))
            finally:
                tracing.configure(enabled=False)
                tracing.get_tracer().reset()
            trace_rate = max(a for a, _ in tblocks)
            tdeltas = sorted((b - a) / b for a, b in tblocks)
            trace_pct = tdeltas[len(tdeltas) // 2] * 100.0
            return (on_rate, off_rate, median_delta * 100.0, len(data),
                    trace_rate, trace_pct)
        finally:
            await store_on.aclose()
            await store_off.aclose()
            await srv_on.aclose()
            await srv_off.aclose()

    return asyncio.run(main())


def bench_audit_overhead() -> tuple[float, float, float, int]:
    """``audit_overhead`` section: the conservation audit plane's
    steady-state serving cost (runtime/audit.py). Two otherwise
    identical closed-loop rigs — the ε-ledger + burn-rate watchdog
    ticking at 10x the production cadence (tick_s=0.05 vs the 0.5
    default, so the measured number upper-bounds the deployed cost) vs
    the ``audit=False`` ablation — under the same ABBA window-block
    discipline as ``serving_metrics_overhead``. The hot-path cost is
    two float adds per scalar grant; everything else rides the
    background tick. Contract: <3%.

    Returns (on_rate, off_rate, overhead_pct, audit_ticks — the
    enabled rig's tick count, proving the plane was live inside the
    measured windows)."""
    from distributedratelimiting.redis_tpu.runtime.audit import (
        AuditConfig,
    )
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    async def main() -> tuple[float, float, float, int]:
        async def make(audit):
            srv = BucketStoreServer(InProcessBucketStore(), audit=audit)
            await srv.start()
            store = RemoteBucketStore(address=(srv.host, srv.port),
                                      coalesce_requests=False)
            return srv, store

        async def window(store, depth: int = 32, reqs: int = 150) -> float:
            async def worker(w: int) -> None:
                for j in range(reqs):
                    await store.acquire(f"user{(w * 13 + j) % 512}", 1,
                                        1e7, 1e7)

            t0 = time.perf_counter()
            await asyncio.gather(*(worker(w) for w in range(depth)))
            return depth * reqs / (time.perf_counter() - t0)

        srv_on, store_on = await make(AuditConfig(tick_s=0.05))
        srv_off, store_off = await make(False)
        try:
            await window(store_on, depth=16, reqs=40)
            await window(store_off, depth=16, reqs=40)
            blocks = []
            for _ in range(4):
                a1 = await window(store_on)
                b1 = await window(store_off)
                b2 = await window(store_off)
                a2 = await window(store_on)
                blocks.append(((a1 + a2) / 2, (b1 + b2) / 2))
            on_rate = max(a for a, _ in blocks)
            off_rate = max(b for _, b in blocks)
            deltas = sorted((b - a) / b for a, b in blocks)
            ticks = srv_on.auditor.ticks
            return (on_rate, off_rate,
                    deltas[len(deltas) // 2] * 100.0, ticks)
        finally:
            await store_on.aclose()
            await store_off.aclose()
            await srv_on.aclose()
            await srv_off.aclose()

    return asyncio.run(main())


def bench_e2e_async_nproc_cpu(timeout_s: float = 600.0) -> tuple[float, int]:
    """Run the N-process scaling bench with a CPU-platform server child.

    The metric is per-request PYTHON/SOCKET scaling across processes —
    the device is explicitly not the bound. The chip belongs to this
    process, so the server child runs on the CPU platform, exactly like
    the serving-p99 co-located stand-in.
    """
    import os
    import subprocess
    import sys


    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--nproc-child"],
            env=env, capture_output=True, timeout=timeout_s, text=True)
        if proc.returncode != 0:
            return 0.0, 0
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        return out["rate"], out["clients"]
    except Exception:
        return 0.0, 0


def _nproc_child() -> None:
    from distributedratelimiting.redis_tpu.runtime import store as store_mod

    rate, rates = asyncio.run(bench_e2e_async_nproc(store_mod))
    print(json.dumps({"rate": rate, "clients": len(rates)}))


# --------------------------------------------------------------------------
# Orchestration: incremental and budget-bounded. No TPU is an error; a
# section that raises makes the run exit non-zero.
# --------------------------------------------------------------------------

_T0 = time.monotonic()
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1200"))

RESULT: dict = {
    "metric": "permit_decisions_per_sec_per_chip",
    "value": None,
    "unit": "decisions/s",
    "vs_baseline": None,
    "platform": None,
    "device_kind": None,
    "device_count": None,
    "n_keys": N_SLOTS,
    "batch": BATCH,
    "scan_depth": SCAN_K,
    "link_upload_mb_per_s": None,
    # Round-trip control beside the latency-bound metrics: the host
    # link's round-trip floor and fetch-pipelining factor recorded with
    # every run (e2e_async_link_rtt_ms is the copy taken when that
    # section ran).
    "link_rtt_ms": None,
    "link_pipeline_overlap_x": None,
    "e2e_async_link_rtt_ms": None,
    "compact_path_decisions_per_sec": None,
    "single_batch_decisions_per_sec": None,
    "e2e_bulk_decisions_per_sec": None,
    "e2e_bulk_with_remaining_decisions_per_sec": None,
    "e2e_fp_bulk_decisions_per_sec": None,
    "e2e_remote_bulk_decisions_per_sec": None,
    "e2e_async_decisions_per_sec": None,
    "e2e_async_nproc_decisions_per_sec": None,
    "e2e_async_nproc_clients": None,
    "e2e_p99_low_load_ms": None,
    "serving_p99_ms": None,
    "serving_p50_ms": None,
    "serving_p99_samples": None,
    "serving_p99_d4_ms": None,
    "serving_p50_d4_ms": None,
    "serving_p99_d4_samples": None,
    # Co-located-device stand-in (two CPU-platform children, server and
    # load on separate cores): the framework's own serving overhead, the
    # number the <2ms north star bounds. Headline keys are the depth-64
    # window; d4/d16 plus the flush histogram (device dispatch + kernel +
    # readback) give the queueing-vs-kernel decomposition.
    "serving_p99_colocated_ms": None,
    "serving_p50_colocated_ms": None,
    "serving_p99_colocated_d4_ms": None,
    "serving_p99_colocated_d16_ms": None,
    "flush_p99_colocated_ms": None,
    "flush_p50_colocated_ms": None,
    # Same rig with InProcessBucketStore (pure-Python microsecond
    # kernel): serving latency with the kernel term removed — the
    # framework-overhead floor of the decomposition; see
    # bench_serving_p99_cpu(backing="instant").
    "serving_p99_instant_ms": None,
    "serving_p50_instant_ms": None,
    "serving_p99_instant_d4_ms": None,
    "serving_p99_instant_d16_ms": None,
    # Native C++ front-end (native/frontend.cc) over the instant backing,
    # driven by the C load generator: the per-request serving ceiling
    # with per-request Python removed from BOTH ends — the number that
    # supersedes the ~13K req/s/core asyncio wire ceiling.
    "serving_native_req_per_s_d64": None,
    "serving_native_req_per_s_d256": None,
    "serving_native_p50_d16_ms": None,
    "serving_native_p99_d16_ms": None,
    "serving_native_p99_d64_ms": None,
    # Tier-0 admission cache over the same rig, hot-key workload (one key
    # per loadgen connection): decisions answered inside the C epoll loop
    # from the per-key replica table, reconciled by the async debit pump.
    # The ratio vs serving_native_req_per_s_d256 is the tentpole's win;
    # hit_rate and the overadmit gauges audit the epsilon contract.
    "serving_native_tier0_req_per_s_d64": None,
    "serving_native_tier0_req_per_s_d256": None,
    "serving_native_tier0_p99_d64_ms": None,
    "serving_native_tier0_hit_rate": None,
    "serving_native_tier0_overadmit_total": None,
    "serving_native_tier0_overadmit_max": None,
    "serving_native_tier0_speedup_vs_off": None,
    # Native bulk lane (round 8): ACQUIRE_MANY rows/s through the C
    # lane (hot keyspace, tier-0 per-row decisions) vs the asyncio bulk
    # path on the same instant backing — the ≥2×-per-core acceptance
    # ratio — plus the same rig against a device-class (multi-ms flush)
    # backing, the regime the 2 ms p99 north star fears. The real-device
    # number stays owed in benchmarks/recapture.py until a TPU window.
    "serving_native_bulk_rows_per_s": None,
    "serving_native_bulk_p99_ms": None,
    "serving_native_bulk_tier0_hit_rate": None,
    "serving_native_bulk_asyncio_rows_per_s": None,
    "serving_native_bulk_speedup_vs_asyncio": None,
    "serving_native_bulk_device_rows_per_s": None,
    "serving_native_bulk_device_p99_ms": None,
    "serving_native_bulk_device_cold_rows_per_s": None,
    "serving_native_bulk_device_cold_p99_ms": None,
    # Multi-shard native front-end (round 11): bulk rows/s through ONE
    # port as a function of SO_REUSEPORT epoll shard count (pinned,
    # instant backing, hot keyspace, tier-0 armed — the node-level
    # scaling curve the 50M/s aggregate model multiplies). Acceptance:
    # s4 >= 3.5x s1 on the same machine.
    "serving_native_shards_rows_per_s_s1": None,
    "serving_native_shards_rows_per_s_s2": None,
    "serving_native_shards_rows_per_s_s4": None,
    "serving_native_shards_rows_per_s_s8": None,
    "serving_native_shards_speedup_4v1": None,
    "serving_native_shards_speedup_8v1": None,
    "serving_native_shards_p99_s4_ms": None,
    "serving_native_shards_local_frac_s4": None,
    # io_uring data plane (round 16): the same pinned shard rig per
    # transport arm — epoll vs uring vs uring+SQPOLL — with the
    # server's C-side data-plane syscall counter divided by frames
    # pushed, and rusage-derived cycles/row. Acceptance: syscalls/frame
    # on the ring ≤ 1/10 of epoll's at the multi-connection point.
    "serving_native_uring_available": None,
    "serving_native_uring_syscalls_per_frame_epoll_s4": None,
    "serving_native_uring_syscalls_per_frame_uring_s4": None,
    "serving_native_uring_syscalls_per_frame_sqpoll_s4": None,
    "serving_native_uring_syscall_reduction_s4": None,
    "serving_native_uring_rows_per_s_uring_s4": None,
    "serving_native_uring_p99_uring_s4_ms": None,
    # Observability-plane cost audit: closed-loop per-request rate with
    # the plane (heavy hitters + flight recorder + /metrics listener +
    # stage stamps) enabled vs observability=False. Contract: <3%.
    "serving_metrics_on_req_per_s": None,
    "serving_metrics_off_req_per_s": None,
    "serving_metrics_overhead_pct": None,
    "serving_metrics_scrape_bytes": None,
    # Distributed-tracing arm of the same audit: head-sampled (1%)
    # tracing toggled on the plane-enabled rig; same <3% contract.
    "serving_tracing_on_req_per_s": None,
    "serving_tracing_overhead_pct": None,
    # Conservation audit plane arm (runtime/audit.py): ε-ledger +
    # watchdog ticking at 10x production cadence vs audit=False; same
    # ABBA estimator, same <3% contract. audit_ticks proves liveness.
    "serving_audit_on_req_per_s": None,
    "serving_audit_off_req_per_s": None,
    "serving_audit_overhead_pct": None,
    "serving_audit_ticks": None,
    "pallas_sweep_ok": None,
    "budget_s": BUDGET_S,
    "elapsed_s": 0.0,
    "section_status": {},
    "partial": True,
}


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _emit() -> None:
    """Print the full result JSON as one line; the driver's tail capture
    parses the LAST line, so every call supersedes the previous one."""
    RESULT["elapsed_s"] = round(time.monotonic() - _T0, 1)
    print(json.dumps(RESULT), flush=True)


def _section(name: str, fn):
    """Run one bench section in this process and record its status:
    "ok" | "skipped_budget" | "error: ...". Always emits the partial JSON
    before returning; ``main`` exits non-zero if any section failed."""
    if _remaining() < 20.0:
        RESULT["section_status"][name] = "skipped_budget"
        _emit()
        return "skipped_budget", None
    try:
        value = fn()
    except Exception as e:  # noqa: BLE001 — recorded, and fails the run
        import traceback

        err = f"{type(e).__name__}: {e}"
        traceback.print_exc()
        print(f"bench: section {name} failed: {err}", file=sys.stderr,
              flush=True)
        RESULT["section_status"][name] = f"error: {err[:200]}"
        _emit()
        return "error", None
    RESULT["section_status"][name] = "ok"
    _emit()
    return "ok", value


def _run_device_sections() -> None:
    """Run every device-touching section in order, sharing kernel state."""
    import jax.numpy as jnp

    from distributedratelimiting.redis_tpu.models import partitioned
    from distributedratelimiting.redis_tpu.models import options as options_mod
    from distributedratelimiting.redis_tpu.ops import kernels as K
    from distributedratelimiting.redis_tpu.runtime import store as store_mod
    from distributedratelimiting.redis_tpu.runtime.clock import MonotonicClock

    clock = MonotonicClock()
    ctx: dict = {}

    def run(name, fn, keys):
        status, value = _section(name, fn)
        if status == "ok" and keys:
            vals = value if isinstance(value, tuple) else (value,)
            for k, v in zip(keys, vals):
                RESULT[k] = v
            _emit()  # _section's emit predates the stores: re-emit so the
            # tail never shows this section "ok" with its metrics null

    def sec_link():
        return round(bench_link_probe(jnp), 1)

    def sec_rtt():
        rtt_ms, overlap_x = bench_rtt_probe(jnp)
        return round(rtt_ms, 2), round(overlap_x, 2)

    def sec_headline():
        rate, state = bench_kernel_throughput(jnp, K, clock)
        ctx["state"] = state
        RESULT["vs_baseline"] = round(rate / NORTH_STAR_PER_CHIP, 3)
        return round(rate)

    def sec_compact():
        rate, state = bench_compact_throughput(jnp, K, clock, ctx["state"])
        ctx["state"] = state
        return round(rate)

    def sec_single():
        rate = bench_single_batch(jnp, K, clock, ctx["state"])
        del ctx["state"]  # free the 10M-slot table before serving stores
        return round(rate)

    def sec_bulk():
        a, b = asyncio.run(bench_e2e_bulk(store_mod, partitioned,
                                          options_mod))
        return round(a), round(b)

    def sec_fp_bulk():
        return round(asyncio.run(bench_fp_bulk()))

    def sec_remote_bulk():
        return round(asyncio.run(bench_e2e_remote_bulk(store_mod)))

    def sec_e2e_async():
        rate, p99 = asyncio.run(
            bench_e2e_async(store_mod, partitioned, options_mod))
        # Stamp the RTT the link showed THIS run next to the numbers it
        # bounds (the link swings between sections, but the same-run
        # probe is the control the round-over-round comparison needs).
        RESULT["e2e_async_link_rtt_ms"] = RESULT["link_rtt_ms"]
        return round(rate), round(p99 * 1e3, 3)

    def sec_serving_p99():
        def on_d64(p99, p50, n):
            # Land the headline numbers the moment they exist: a wedge
            # during the extra depth-4 window must not discard them.
            RESULT["serving_p99_ms"] = round(p99, 3)
            RESULT["serving_p50_ms"] = round(p50, 3)
            RESULT["serving_p99_samples"] = n
            _emit()

        p99, p50, n, p99_d4, p50_d4, n4 = asyncio.run(
            bench_serving_p99(store_mod, on_d64=on_d64))
        return (round(p99, 3), round(p50, 3), n,
                round(p99_d4, 3), round(p50_d4, 3), n4)

    def sec_pallas():
        return bench_pallas_sweep(store_mod)

    run("link_probe", sec_link, ["link_upload_mb_per_s"])
    run("link_rtt_probe", sec_rtt,
        ["link_rtt_ms", "link_pipeline_overlap_x"])
    run("headline", sec_headline, ["value"])
    run("compact", sec_compact, ["compact_path_decisions_per_sec"])
    run("single_batch", sec_single, ["single_batch_decisions_per_sec"])
    run("e2e_bulk", sec_bulk, ["e2e_bulk_decisions_per_sec",
                               "e2e_bulk_with_remaining_decisions_per_sec"])
    run("fp_bulk", sec_fp_bulk, ["e2e_fp_bulk_decisions_per_sec"])
    run("remote_bulk", sec_remote_bulk,
        ["e2e_remote_bulk_decisions_per_sec"])
    run("e2e_async", sec_e2e_async,
        ["e2e_async_decisions_per_sec", "e2e_p99_low_load_ms"])
    run("serving_p99", sec_serving_p99,
        ["serving_p99_ms", "serving_p50_ms", "serving_p99_samples",
         "serving_p99_d4_ms", "serving_p50_d4_ms",
         "serving_p99_d4_samples"])
    run("pallas_sweep", sec_pallas, ["pallas_sweep_ok"])


def main() -> int:
    import jax

    from distributedratelimiting.redis_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    _emit()  # first line lands before any device or child work
    devices = jax.devices()
    RESULT["platform"] = devices[0].platform
    RESULT["device_kind"] = devices[0].device_kind
    RESULT["device_count"] = len(devices)
    _emit()
    if RESULT["platform"] != "tpu":
        print(f"bench: no TPU (jax found {RESULT['platform']!r}); a "
              "measurement path does not fall back to the CPU",
              file=sys.stderr, flush=True)
        return 2
    enable_compile_cache()
    _run_device_sections()

    def sec_nproc():
        rate, clients = bench_e2e_async_nproc_cpu(
            timeout_s=min(600.0, max(_remaining(), 30.0)))
        if clients == 0:  # child died/timed out: a failed section must
            # never read as a measured rate of 0 (evidence fidelity)
            raise RuntimeError("nproc CPU child failed or timed out")
        return rate, clients

    status, value = _section("nproc", sec_nproc)
    if status == "ok":
        RESULT["e2e_async_nproc_decisions_per_sec"] = round(value[0])
        RESULT["e2e_async_nproc_clients"] = value[1]
        _emit()

    def sec_serving_cpu():
        out = bench_serving_p99_cpu(
            timeout_s=min(600.0, max(_remaining(), 30.0)))
        if out is None:
            raise RuntimeError("serving-p99 CPU children failed or timed out")
        return out

    status, value = _section("serving_p99_colocated", sec_serving_cpu)
    if status == "ok" and value is not None:
        d64, d16, d4 = value["d64"], value["d16"], value["d4"]
        RESULT["serving_p99_colocated_ms"] = round(d64["p99_ms"], 3)
        RESULT["serving_p50_colocated_ms"] = round(d64["p50_ms"], 3)
        RESULT["serving_p99_colocated_d4_ms"] = round(d4["p99_ms"], 3)
        RESULT["serving_p99_colocated_d16_ms"] = round(d16["p99_ms"], 3)
        if d64.get("flush_p99_ms") is not None:
            RESULT["flush_p99_colocated_ms"] = round(d64["flush_p99_ms"], 3)
            RESULT["flush_p50_colocated_ms"] = round(d64["flush_p50_ms"], 3)
        _emit()

    def sec_serving_instant():
        out = bench_serving_p99_cpu(
            timeout_s=min(300.0, max(_remaining(), 30.0)),
            backing="instant")
        if out is None:
            raise RuntimeError("instant-serving children failed/timed out")
        return out

    status, value = _section("serving_p99_instant", sec_serving_instant)
    if status == "ok" and value is not None:
        d64, d16, d4 = value["d64"], value["d16"], value["d4"]
        RESULT["serving_p99_instant_ms"] = round(d64["p99_ms"], 3)
        RESULT["serving_p50_instant_ms"] = round(d64["p50_ms"], 3)
        RESULT["serving_p99_instant_d4_ms"] = round(d4["p99_ms"], 3)
        RESULT["serving_p99_instant_d16_ms"] = round(d16["p99_ms"], 3)
        _emit()

    def sec_serving_native():
        out = bench_serving_p99_cpu(
            timeout_s=min(300.0, max(_remaining(), 30.0)),
            backing="instant", native=True)
        if out is None:
            raise RuntimeError("native-frontend children failed/timed out")
        return out

    status, value = _section("serving_native", sec_serving_native)
    if status == "ok" and value is not None:
        RESULT["serving_native_req_per_s_d64"] = round(value["d64"]["rate"])
        RESULT["serving_native_req_per_s_d256"] = round(
            value["d256"]["rate"])
        RESULT["serving_native_p50_d16_ms"] = round(
            value["d16"]["p50_ms"], 3)
        RESULT["serving_native_p99_d16_ms"] = round(
            value["d16"]["p99_ms"], 3)
        RESULT["serving_native_p99_d64_ms"] = round(
            value["d64"]["p99_ms"], 3)
        _emit()

    def sec_serving_native_tier0():
        out = bench_serving_p99_cpu(
            timeout_s=min(300.0, max(_remaining(), 30.0)),
            backing="instant", native=True, tier0=True)
        if out is None:
            raise RuntimeError("tier0-frontend children failed/timed out")
        return out

    status, value = _section("serving_native_tier0",
                             sec_serving_native_tier0)
    if status == "ok" and value is not None:
        RESULT["serving_native_tier0_req_per_s_d64"] = round(
            value["d64"]["rate"])
        RESULT["serving_native_tier0_req_per_s_d256"] = round(
            value["d256"]["rate"])
        RESULT["serving_native_tier0_p99_d64_ms"] = round(
            value["d64"]["p99_ms"], 3)
        t0 = value.get("tier0") or {}
        if t0:
            RESULT["serving_native_tier0_hit_rate"] = round(
                t0.get("hit_rate", 0.0), 4)
            RESULT["serving_native_tier0_overadmit_total"] = t0.get(
                "overadmit_total")
            RESULT["serving_native_tier0_overadmit_max"] = t0.get(
                "overadmit_max")
        off = RESULT["serving_native_req_per_s_d256"]
        if off:
            RESULT["serving_native_tier0_speedup_vs_off"] = round(
                value["d256"]["rate"] / off, 2)
        _emit()

    def sec_serving_native_bulk():
        out = bench_native_bulk(timeout_s=min(420.0,
                                              max(_remaining(), 30.0)))
        if out is None:
            raise RuntimeError("native-bulk children failed or timed out")
        return out

    status, value = _section("serving_native_bulk",
                             sec_serving_native_bulk)
    if status == "ok" and value is not None:
        nat, asy = value["native"], value["asyncio"]
        RESULT["serving_native_bulk_rows_per_s"] = round(nat["rows_per_s"])
        RESULT["serving_native_bulk_p99_ms"] = round(nat["p99_ms"], 3)
        hit = nat.get("window_tier0_hit_rate", nat.get("tier0_hit_rate"))
        if hit is not None:
            RESULT["serving_native_bulk_tier0_hit_rate"] = round(hit, 4)
        RESULT["serving_native_bulk_asyncio_rows_per_s"] = round(
            asy["rows_per_s"])
        if asy["rows_per_s"]:
            RESULT["serving_native_bulk_speedup_vs_asyncio"] = round(
                nat["rows_per_s"] / asy["rows_per_s"], 2)
        for arm, key in (("device", "serving_native_bulk_device"),
                         ("device_cold",
                          "serving_native_bulk_device_cold")):
            dev = value.get(arm)
            if dev is not None:
                RESULT[f"{key}_rows_per_s"] = round(dev["rows_per_s"])
                RESULT[f"{key}_p99_ms"] = round(dev["p99_ms"], 3)
        _emit()

    def sec_native_shards():
        out = bench_native_shards(timeout_s=min(600.0,
                                                max(_remaining(), 60.0)))
        if out is None:
            raise RuntimeError("shard-sweep children failed or timed out")
        return out

    status, value = _section("serving_native_shards", sec_native_shards)
    if status == "ok" and value is not None:
        for s_n in (1, 2, 4, 8):
            arm = value.get(f"s{s_n}")
            if arm is not None:
                RESULT[f"serving_native_shards_rows_per_s_s{s_n}"] = \
                    round(arm["rows_per_s"])
        if value.get("speedup_4v1") is not None:
            RESULT["serving_native_shards_speedup_4v1"] = round(
                value["speedup_4v1"], 2)
        if value.get("speedup_8v1") is not None:
            RESULT["serving_native_shards_speedup_8v1"] = round(
                value["speedup_8v1"], 2)
        s4 = value.get("s4")
        if s4 is not None:
            RESULT["serving_native_shards_p99_s4_ms"] = round(
                s4["p99_ms"], 3)
            if "rows_local_frac" in s4:
                RESULT["serving_native_shards_local_frac_s4"] = round(
                    s4["rows_local_frac"], 4)
        _emit()

    def sec_native_uring():
        out = bench_native_uring(timeout_s=min(540.0,
                                               max(_remaining(), 60.0)))
        if out is None:
            raise RuntimeError("uring-sweep children failed or timed out")
        return out

    status, value = _section("serving_native_uring", sec_native_uring)
    if status == "ok" and value is not None:
        RESULT["serving_native_uring_available"] = value.get(
            "uring_available")
        spf = {}
        for arm in ("epoll", "uring", "sqpoll"):
            row = value.get(f"{arm}_s4")
            if row is None or row.get("fell_back"):
                continue
            if "syscalls_per_frame" in row:
                spf[arm] = row["syscalls_per_frame"]
                RESULT[f"serving_native_uring_syscalls_per_frame"
                       f"_{arm}_s4"] = row["syscalls_per_frame"]
        if "epoll" in spf and ("sqpoll" in spf or "uring" in spf):
            ring = spf.get("sqpoll", spf.get("uring"))
            if ring:
                RESULT["serving_native_uring_syscall_reduction_s4"] = \
                    round(spf["epoll"] / ring, 2)
        u4 = value.get("uring_s4")
        if u4 is not None and not u4.get("fell_back"):
            RESULT["serving_native_uring_rows_per_s_uring_s4"] = round(
                u4["rows_per_s"])
            RESULT["serving_native_uring_p99_uring_s4_ms"] = round(
                u4["p99_ms"], 3)
        _emit()

    def sec_metrics_overhead():
        (on_rate, off_rate, pct, scraped,
         trace_rate, trace_pct) = bench_metrics_overhead()
        return (round(on_rate), round(off_rate), round(pct, 2), scraped,
                round(trace_rate), round(trace_pct, 2))

    status, value = _section("serving_metrics_overhead",
                             sec_metrics_overhead)
    if status == "ok" and value is not None:
        (RESULT["serving_metrics_on_req_per_s"],
         RESULT["serving_metrics_off_req_per_s"],
         RESULT["serving_metrics_overhead_pct"],
         RESULT["serving_metrics_scrape_bytes"],
         RESULT["serving_tracing_on_req_per_s"],
         RESULT["serving_tracing_overhead_pct"]) = value
        _emit()

    def sec_audit_overhead():
        on_rate, off_rate, pct, ticks = bench_audit_overhead()
        return round(on_rate), round(off_rate), round(pct, 2), ticks

    status, value = _section("audit_overhead", sec_audit_overhead)
    if status == "ok" and value is not None:
        (RESULT["serving_audit_on_req_per_s"],
         RESULT["serving_audit_off_req_per_s"],
         RESULT["serving_audit_overhead_pct"],
         RESULT["serving_audit_ticks"]) = value
        _emit()

    RESULT["partial"] = False
    _emit()
    failed = [k for k, v in RESULT["section_status"].items()
              if v.startswith("error")]
    return 1 if failed else 0


if __name__ == "__main__":
    if "--serving-server-child" in sys.argv:
        i = sys.argv.index("--serving-server-child")
        kind = sys.argv[i + 1] if len(sys.argv) > i + 1 else "device"
        rest = sys.argv[i + 2:]
        shards = 1
        uring = None
        for arg in rest:
            if arg.startswith("shards="):
                shards = int(arg.split("=", 1)[1])
            elif arg.startswith("uring="):
                uring = arg.split("=", 1)[1]
        _serving_server_child(kind, native="native" in rest,
                              tier0="tier0" in rest, shards=shards,
                              pin="pin" in rest, uring=uring)
        sys.exit(0)
    if "--shard-load-child" in sys.argv:
        i = sys.argv.index("--shard-load-child")
        _shard_load_child(sys.argv[i + 1], sys.argv[i + 2],
                          sys.argv[i + 3])
        sys.exit(0)
    if "--native-load-child" in sys.argv:
        i = sys.argv.index("--native-load-child")
        workload = (sys.argv[i + 3]
                    if len(sys.argv) > i + 3 else "uniform")
        _native_load_child(sys.argv[i + 1], sys.argv[i + 2], workload)
        sys.exit(0)
    if "--serving-load-child" in sys.argv:
        i = sys.argv.index("--serving-load-child")
        _serving_load_child(sys.argv[i + 1], sys.argv[i + 2])
        sys.exit(0)
    if "--bulk-load-child" in sys.argv:
        i = sys.argv.index("--bulk-load-child")
        workload = sys.argv[i + 3] if len(sys.argv) > i + 3 else "hot"
        _bulk_load_child(sys.argv[i + 1], sys.argv[i + 2], workload)
        sys.exit(0)
    if "--nproc-child" in sys.argv:
        _nproc_child()
        sys.exit(0)
    if "--nproc-client" in sys.argv:
        i = sys.argv.index("--nproc-client")
        _nproc_client(sys.argv[i + 1], sys.argv[i + 2], sys.argv[i + 3])
        sys.exit(0)
    sys.exit(main())
