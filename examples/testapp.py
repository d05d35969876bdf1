"""TestApp — the manual console harness, completed (SURVEY.md §2 #12, §4).

The reference's ``TestApp/Program.cs`` had two intended test styles, both
commented out; this harness makes both real:

- ``single``       — the single-process smoke (``:8-22``): an approximate
                     limiter with the reference's exact config (100 ms
                     period, 1 token/period, limit 100, queue 100,
                     ``:13-16``), spun in a loop printing the
                     ``ToString()``-style dump (``:31``, ``:510-513``).
- ``server``/``worker`` — the multi-instance topology the Orleans harness
                     gestured at (``:37-104``): N worker *processes* on
                     localhost, ids from argv, all sharing one store
                     server (the Redis stand-in).
- ``convergence``  — orchestrates server + N workers and checks the
                     property the approximate algorithm exists to provide:
                     aggregate admitted throughput converges to
                     ≤ token_limit regardless of instance count (SURVEY.md
                     §4 implication (c)).

Usage::

    python examples/testapp.py single --seconds 3
    python examples/testapp.py convergence --instances 4 --seconds 8
    # or by hand, Orleans-style (one command per terminal):
    python -m distributedratelimiting.redis_tpu.runtime.server --port 6380 --backend inprocess
    python examples/testapp.py worker --port 6380 --id 0 --seconds 10
    python examples/testapp.py worker --port 6380 --id 1 --seconds 10
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

REPO_ROOT = __file__.rsplit("/", 2)[0]

# The reference TestApp's limiter config (TestApp/Program.cs:13-16) scaled
# to a visible rate: period 100 ms, tokens_per_period 1 ⇒ 10 tokens/s,
# burst capacity (token_limit) 100, queue 100.
PERIOD_S = 0.1
TOKENS_PER_PERIOD = 1
TOKEN_LIMIT = 100
QUEUE_LIMIT = 100


def _options():
    from distributedratelimiting.redis_tpu.models.options import (
        ApproximateTokenBucketOptions,
    )

    return ApproximateTokenBucketOptions(
        token_limit=TOKEN_LIMIT,
        tokens_per_period=TOKENS_PER_PERIOD,
        replenishment_period_s=PERIOD_S,
        queue_limit=QUEUE_LIMIT,
        instance_name="testapp",
    )


async def _drive(limiter, seconds: float,
                 print_dumps: bool) -> tuple[int, int, int]:
    """5 concurrent worker tasks acquiring as fast as leases come — the
    Orleans harness's worker-pool shape (TestApp/Program.cs:69-73,81-103).

    Returns ``(granted, denied, granted_late)`` where ``granted_late``
    counts grants in the second half of the window — past the startup
    transient (each fresh instance admits its full local burst before the
    first syncs propagate; convergence is a steady-state property)."""
    granted = denied = granted_late = 0
    deadline = time.monotonic() + seconds
    halfway = deadline - seconds / 2

    async def worker():
        nonlocal granted, denied, granted_late
        while time.monotonic() < deadline:
            lease = await limiter.acquire_async(1)
            if lease.is_acquired:
                granted += 1
                if time.monotonic() >= halfway:
                    granted_late += 1
                await asyncio.sleep(0.001)  # hold, then "release" (consumed)
            else:
                denied += 1
                retry = lease.retry_after or 0.01
                await asyncio.sleep(min(retry, 0.1))

    async def dumper():
        while time.monotonic() < deadline:
            await asyncio.sleep(1.0)
            print(limiter, flush=True)  # ≙ Console.WriteLine(limiter) :31

    tasks = [asyncio.create_task(worker()) for _ in range(5)]
    if print_dumps:
        tasks.append(asyncio.create_task(dumper()))
    results = await asyncio.gather(*tasks, return_exceptions=True)
    # A crashed worker must fail the harness loudly — swallowing it would
    # let a convergence run "pass" having served zero traffic.
    errors = [r for r in results if isinstance(r, BaseException)]
    if errors:
        raise errors[0]
    return granted, denied, granted_late


def cmd_single(args) -> int:
    """Single-process smoke against an in-process store (``:8-22``)."""
    from distributedratelimiting.redis_tpu.models.approximate import (
        ApproximateTokenBucketRateLimiter,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    async def main():
        limiter = ApproximateTokenBucketRateLimiter(
            _options(), InProcessBucketStore())
        granted, denied, _ = await _drive(limiter, args.seconds,
                                          print_dumps=True)
        print(json.dumps({"granted": granted, "denied": denied,
                          **limiter.stats()}), flush=True)
        await limiter.aclose()

    asyncio.run(main())
    return 0


def cmd_worker(args) -> int:
    """One limiter instance (≙ one silo) against a shared store server."""
    from distributedratelimiting.redis_tpu.models.approximate import (
        ApproximateTokenBucketRateLimiter,
    )
    from distributedratelimiting.redis_tpu.runtime.remote import (
        RemoteBucketStore,
    )

    async def main():
        store = RemoteBucketStore(address=("127.0.0.1", args.port))
        limiter = ApproximateTokenBucketRateLimiter(_options(), store)
        granted, denied, granted_late = await _drive(limiter, args.seconds,
                                                     print_dumps=args.verbose)
        print(json.dumps({
            "worker_id": args.id, "granted": granted, "denied": denied,
            "granted_late": granted_late,
            "instance_count_estimate": limiter.stats()["instance_count_estimate"],
        }), flush=True)
        await limiter.aclose()
        await store.aclose()

    asyncio.run(main())
    return 0


def cmd_convergence(args) -> int:
    """Spawn 1 store server + N worker processes; assert aggregate admitted
    throughput ≤ token_limit + fill·T (+ one period of staleness per
    instance) — the multi-client convergence property."""
    import socket

    with socket.socket() as s:  # free localhost port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    server = subprocess.Popen(
        [sys.executable, "-m",
         "distributedratelimiting.redis_tpu.runtime.server",
         "--port", str(port), "--backend", args.backend],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
    )
    try:
        assert server.stdout is not None
        line = server.stdout.readline()  # wait for "listening" banner
        if "listening" not in line:
            raise RuntimeError(f"server failed to start: {line!r}")
        workers = [
            subprocess.Popen(
                [sys.executable, __file__, "worker", "--port", str(port),
                 "--id", str(i), "--seconds", str(args.seconds)],
                cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
                # Workers reach the store over TCP and need no chip.
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            for i in range(args.instances)
        ]
        reports = []
        for w in workers:
            out, _ = w.communicate(timeout=args.seconds + 60)
            for ln in out.splitlines():
                if ln.startswith("{"):
                    reports.append(json.loads(ln))
    finally:
        server.terminate()
        server.wait(timeout=10)

    if len(reports) != args.instances:
        raise RuntimeError(
            f"only {len(reports)}/{args.instances} workers reported — a "
            "worker died before printing its summary"
        )
    total_granted = sum(r["granted"] for r in reports)
    total_late = sum(r["granted_late"] for r in reports)
    # Steady-state admission bound, checked on the second half of the run
    # (the first half absorbs the startup transient: each fresh instance
    # admits its full local burst before syncs propagate). Aggregate
    # admitted rate must settle to ~fill_rate, over-admitting by at most
    # one replenishment period of staleness per instance — the reference's
    # documented bound (SURVEY.md invariant 6) — plus margin for the
    # instance-count EWMA still converging.
    fill_rate = TOKENS_PER_PERIOD / PERIOD_S
    half = args.seconds / 2
    bound = 2.0 * (fill_rate * half
                   + args.instances * fill_rate * PERIOD_S * 2)
    summary = {
        "instances": args.instances,
        "seconds": args.seconds,
        "total_granted": total_granted,
        "steady_state_granted": total_late,
        "steady_state_bound": round(bound, 1),
        "converged": total_late <= bound,
        "per_worker": reports,
    }
    print(json.dumps(summary), flush=True)
    return 0 if summary["converged"] else 1


def cmd_bulk(args) -> int:
    """Bulk serving demo: whole key arrays through PartitionedRateLimiter
    against the device store (the batching the reference's README promised
    and never built), plus the keyed window façade — one await per call,
    no per-request futures."""
    import numpy as np

    from distributedratelimiting.redis_tpu.models.options import (
        SlidingWindowOptions,
        TokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.models.partitioned import (
        PartitionedRateLimiter,
    )
    from distributedratelimiting.redis_tpu.models.partitioned_window import (
        PartitionedWindowRateLimiter,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    async def main():
        store = DeviceBucketStore(n_slots=1 << max(10, args.keys.bit_length()))
        buckets = PartitionedRateLimiter(
            TokenBucketOptions(token_limit=100, tokens_per_period=50,
                               instance_name="bulkdemo"), store)
        windows = PartitionedWindowRateLimiter(
            SlidingWindowOptions(permit_limit=100, window_s=1.0,
                                 instance_name="bulkwin"), store)
        rng = np.random.default_rng(0)
        users = [f"user{i}" for i in rng.integers(0, args.keys, args.n)]
        # Warm: first calls pay kernel compilation, not serving cost.
        await buckets.acquire_many(users[:256], 0, with_remaining=False)
        await windows.acquire_many(users[:256], 0, with_remaining=False)
        t0 = time.perf_counter()
        res = await buckets.acquire_many(users, 1, with_remaining=False)
        bucket_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        wres = await windows.acquire_many(users, 1, with_remaining=False)
        window_dt = time.perf_counter() - t0
        print(json.dumps({
            "requests": args.n,
            "distinct_keys": args.keys,
            "bucket_granted": int(res.granted_count),
            "bucket_decisions_per_sec": round(args.n / bucket_dt),
            "window_granted": int(wres.granted_count),
            "window_decisions_per_sec": round(args.n / window_dt),
        }), flush=True)
        await store.aclose()

    asyncio.run(main())
    return 0


def cmd_cluster(args) -> int:
    """Cluster demo: N store servers in this process (shared-nothing, each
    its own store), one ClusterBucketStore routing keys across them,
    bulk + single-key traffic, then one node killed to show per-node
    degraded mode (deny policy)."""
    from distributedratelimiting.redis_tpu.runtime.cluster import (
        ClusterBucketStore,
    )
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        InProcessBucketStore,
    )

    async def main():
        servers = []
        for _ in range(args.nodes):
            srv = BucketStoreServer(InProcessBucketStore(),
                                    native_frontend=args.native_frontend)
            await srv.start()
            servers.append(srv)
        store = ClusterBucketStore(
            addresses=[(s.host, s.port) for s in servers],
            partial_failures="deny", request_timeout_s=3.0)
        keys = [f"user{i}" for i in range(args.n)]
        res = await store.acquire_many(keys, [1] * args.n, 100.0, 50.0)
        # The placement map is the routing truth (no modulus): epoch 0
        # routes exactly like the legacy crc32 % N, and a resharded
        # cluster's spread follows the map automatically.
        spread = [0] * args.nodes
        for k in keys:
            spread[store.node_index_of(k)] += 1
        stats = await store.stats()
        await servers[0].aclose()  # kill node 0 → its keys deny, rest serve
        res2 = await store.acquire_many(keys, [1] * args.n, 100.0, 50.0)
        live = sum(1 for i, k in enumerate(keys)
                   if store.node_index_of(k) != 0 and res2.granted[i])
        print(json.dumps({
            "nodes": args.nodes,
            "key_spread": spread,
            "granted_all_nodes_up": int(res.granted_count),
            "per_node_requests_served": [
                s["requests_served"] for s in stats["nodes"]],
            "after_node0_killed": {
                "granted": int(res2.granted_count),
                "live_node_grants": live,
                "node0_keys_denied": spread[0],
            },
        }, ), flush=True)
        await store.aclose()
        for s in servers[1:]:
            await s.aclose()

    asyncio.run(main())
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("single", help="single-process smoke")
    p.add_argument("--seconds", type=float, default=3.0)
    p.set_defaults(fn=cmd_single)

    p = sub.add_parser("worker", help="one limiter instance vs shared server")
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--id", type=int, default=0)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--verbose", action="store_true")
    p.set_defaults(fn=cmd_worker)

    p = sub.add_parser("convergence", help="server + N workers, check bound")
    p.add_argument("--instances", type=int, default=4)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--backend", choices=("inprocess", "device"),
                   default="inprocess",
                   help="store behind the server: device = the TPU/"
                   "device-resident DeviceBucketStore (the production "
                   "topology: N processes → TCP → device store)")
    p.set_defaults(fn=cmd_convergence)

    p = sub.add_parser("bulk", help="whole-array bulk serving demo "
                       "(buckets + keyed windows on the device store)")
    p.add_argument("--n", type=int, default=100_000,
                   help="requests per bulk call")
    p.add_argument("--keys", type=int, default=50_000,
                   help="distinct key pool size")
    p.set_defaults(fn=cmd_bulk)

    p = sub.add_parser("cluster", help="N shared-nothing store servers + "
                       "client-side key routing; kills a node to show "
                       "per-node degraded mode")
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument("--native-frontend", action="store_true",
                   help="serve each node's sockets from the C++ epoll "
                   "front-end (native/frontend.cc)")
    p.add_argument("--n", type=int, default=1000,
                   help="keys in the bulk call")
    p.set_defaults(fn=cmd_cluster)

    args = parser.parse_args(argv)
    from distributedratelimiting.redis_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    sys.exit(main())
