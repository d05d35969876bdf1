"""Aggregate scale-out curve: N store servers × M bulk-client processes.

The 50M/s north star (BASELINE.json) is an *aggregate serving* target —
kernel-path numbers don't speak to it. This harness measures the only
aggregate the environment can produce: N shared-nothing
``BucketStoreServer`` processes on this box, M client processes each
bulk-driving a ``ClusterBucketStore`` (client-side placement-map
routing — epoch-0 maps route exactly like crc32 % N — with per-node
sub-batches fanned out concurrently; the same composition the
reference would reach with N Redis nodes and cluster-aware clients,
``RedisRateLimiting.Redis/README.md``'s horizontal-scale story).

Run: ``python -m benchmarks.scaleout [--nodes 1,2,4,8] [--clients 2]
[--seconds 6] [--backing cpu|device]``
Prints one JSON line per node count; the parent measures aggregate
decisions/s across all client processes against wall clock.

Topology is configurable (the harness only):

- ``--hosts a:6380,b:6380`` drives EXTERNAL, already-running store
  servers (one JSONL record for the whole list) instead of spawning
  localhost children — the real multi-host measurement.
- ``--config topo.json`` reads the same knobs from a file
  (``{"nodes": [...], "clients": N, "seconds": S, "backing": ...,
  "hosts": [...], "cores": N}``); CLI flags override file values.
- ``--cores`` records the core count the operator ACTUALLY gave the rig
  (taskset/cgroup), for the interpretation contract below; it defaults
  to ``os.cpu_count()``.

Interpretation contract: when
every server and client timeshares one CPU, the curve measures
*composition overhead* (does adding nodes cost throughput?), not
parallel speedup — the per-node ceiling × N model only applies when
each node owns its own core/chip. The harness therefore records
``nproc`` and ``cores`` so the reader can tell which regime a record
came from.
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import json
import os
import subprocess
import sys
import time

# Child roles ---------------------------------------------------------------


def _server_child(shards: int = 0) -> None:
    """One store-server process: CPU-platform device store (the serving
    stand-in) or the real device, prints its address, parks on stdin.
    ``shards > 0`` serves through the native multi-shard front-end
    (round 11): N SO_REUSEPORT epoll shards + tier-0 per node, so the
    scale-out curve can compose node counts from NODE-level (not
    core-level) serving rates."""
    from distributedratelimiting.redis_tpu.runtime.server import (
        BucketStoreServer,
    )
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    async def run() -> None:
        backing = DeviceBucketStore(n_slots=1 << 18, max_batch=4096)
        kwargs = {}
        if shards > 0:
            kwargs = {"native_frontend": True, "native_tier0": True,
                      "native_shards": shards}
        async with BucketStoreServer(backing, **kwargs) as srv:
            print(json.dumps({"host": srv.host, "port": srv.port}),
                  flush=True)
            await asyncio.get_running_loop().run_in_executor(
                None, sys.stdin.read)
        await backing.aclose()

    asyncio.run(run())


def _client_child(addrs_json: str, seconds: str) -> None:
    """One bulk-client process: closed-loop ``acquire_many`` against the
    whole cluster for the given duration; prints its decision count."""
    import numpy as np

    from distributedratelimiting.redis_tpu.runtime.cluster import (
        ClusterBucketStore,
    )

    addrs = [tuple(a) for a in json.loads(addrs_json)]
    dur = float(seconds)
    n = 1 << 16
    rng = np.random.default_rng(os.getpid())
    pool = [f"user{i}" for i in range(200_000)]
    batches = [[pool[j] for j in rng.integers(0, len(pool), n)]
               for _ in range(4)]
    counts = [1] * n

    async def run() -> None:
        # Generous request timeout: at N=8 on a single-core box the warm
        # call rides an 8-process XLA-CPU compile stampede and can exceed
        # the default 30 s (observed) without anything being wrong.
        store = ClusterBucketStore(addresses=addrs,
                                   request_timeout_s=180.0)
        # Warm every node connection + kernel shape.
        await store.acquire_many(batches[0], counts, 1e7, 1e7,
                                 with_remaining=False)
        done = 0
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < dur:
            await store.acquire_many(batches[i % len(batches)], counts,
                                     1e7, 1e7, with_remaining=False)
            done += n
            i += 1
        dt = time.perf_counter() - t0
        await store.aclose()
        print(json.dumps({"decisions": done, "dt": dt}), flush=True)

    asyncio.run(run())


# Parent orchestration ------------------------------------------------------


def _measure(n_nodes: int, n_clients: int, seconds: float,
             backing: str, hosts: "list[list] | None" = None,
             cores: int | None = None, fe_shards: int = 0) -> dict:
    me = os.path.abspath(__file__)
    # Children run this file by path, outside the package: put the repo
    # root on their import path.
    root = os.path.dirname(os.path.dirname(me))
    env = os.environ.copy()
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    # Clients never need the chip; servers take it only with
    # ``--backing device`` (one chip per process: one device server).
    cpu_env = dict(env, JAX_PLATFORMS="cpu")
    if backing == "cpu":
        env = cpu_env
    elif n_nodes > 1 and not hosts:
        raise SystemExit("--backing device runs one server per chip: "
                         "use --nodes 1 or --hosts")
    # External topology: the operator's already-running servers replace
    # the spawned localhost children; everything else is identical.
    servers = [] if hosts else [subprocess.Popen(
        [sys.executable, me, "--server-child", str(fe_shards)], env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        for _ in range(n_nodes)]
    pool = concurrent.futures.ThreadPoolExecutor(1)
    try:
        if hosts:
            addrs = [[h, int(p)] for h, p in
                     (a if isinstance(a, (list, tuple))
                      else a.rsplit(":", 1) for a in hosts)]
            n_nodes = len(addrs)
        else:
            addrs = []
            for s in servers:
                # Pooled readline with a timeout: a server child that
                # dies or hangs in start-up never prints its address.
                line = pool.submit(s.stdout.readline).result(timeout=180.0)
                a = json.loads(line)
                addrs.append([a["host"], a["port"]])
        addrs_json = json.dumps(addrs)
        t0 = time.perf_counter()
        clients = [subprocess.Popen(
            [sys.executable, me, "--client-child", addrs_json,
             str(seconds)], env=cpu_env, stdout=subprocess.PIPE, text=True)
            for _ in range(n_clients)]
        outs = []
        try:
            for c in clients:
                out, _ = c.communicate(timeout=seconds * 8 + 240)
                outs.append(json.loads(out.strip().splitlines()[-1]))
        finally:
            for c in clients:  # a timed-out/garbled client must not keep
                if c.poll() is None:  # spinning against dying servers
                    c.kill()
        wall = time.perf_counter() - t0
        per_client = [o["decisions"] / o["dt"] for o in outs]
        return {
            "config": "scaleout",
            "n_nodes": n_nodes,
            "n_clients": n_clients,
            "fe_shards": fe_shards or None,
            "backing": backing if not hosts else "external",
            # Clients start together and run identical closed-loop
            # windows, so the aggregate is the sum of per-client rates
            # over their own measured windows (parent wall clock would
            # fold one-time compile/warmup into the denominator).
            "aggregate_decisions_per_sec": round(sum(per_client)),
            "per_client_decisions_per_sec": [round(r) for r in per_client],
            "wall_incl_warm_s": round(wall, 1),
            "nproc": os.cpu_count(),
            "cores": cores if cores is not None else os.cpu_count(),
            "hosts": [f"{h}:{p}" for h, p in addrs] if hosts else None,
        }
    finally:
        for s in servers:
            try:
                s.stdin.close()
                s.wait(timeout=10)
            except Exception:
                s.kill()
        pool.shutdown(wait=False)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--nodes", default=None,
                   help="comma-separated node counts to spawn locally "
                   "(default 1,2,4,8; ignored when --hosts is given)")
    p.add_argument("--clients", type=int, default=None)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--backing", choices=("cpu", "device"), default=None)
    p.add_argument("--hosts", default=None,
                   help="comma-separated host:port of EXTERNAL servers "
                   "to drive instead of spawning localhost children")
    p.add_argument("--cores", type=int, default=None,
                   help="core count the rig actually owns (recorded in "
                   "the JSONL; default os.cpu_count())")
    p.add_argument("--shards", type=int, default=None,
                   help="serve each spawned node through the native "
                   "multi-shard front-end with this many SO_REUSEPORT "
                   "epoll shards (0/absent = the asyncio server): the "
                   "node-level arm of the aggregate model — rows/s per "
                   "NODE x node count, not per core")
    p.add_argument("--config", default=None,
                   help="JSON file supplying the same knobs (nodes, "
                   "clients, seconds, backing, hosts, cores, shards); "
                   "CLI flags override it")
    args = p.parse_args(argv)
    cfg: dict = {}
    if args.config:
        with open(args.config, encoding="utf-8") as f:
            cfg = json.load(f)
    nodes = (args.nodes.split(",") if args.nodes
             else cfg.get("nodes", [1, 2, 4, 8]))
    clients = args.clients if args.clients is not None else cfg.get(
        "clients", 2)
    seconds = args.seconds if args.seconds is not None else cfg.get(
        "seconds", 6.0)
    backing = args.backing or cfg.get("backing", "cpu")
    hosts = (args.hosts.split(",") if args.hosts
             else cfg.get("hosts") or None)
    cores = args.cores if args.cores is not None else cfg.get("cores")
    fe_shards = (args.shards if args.shards is not None
                 else int(cfg.get("shards", 0) or 0))
    if hosts:
        print(json.dumps(_measure(len(hosts), clients, seconds, backing,
                                  hosts=hosts, cores=cores)), flush=True)
        return 0
    for n in [int(x) for x in nodes]:
        print(json.dumps(_measure(n, clients, seconds, backing,
                                  cores=cores, fe_shards=fe_shards)),
              flush=True)
    return 0


if __name__ == "__main__":
    if "--server-child" in sys.argv:
        i = sys.argv.index("--server-child")
        shards = (int(sys.argv[i + 1])
                  if len(sys.argv) > i + 1 else 0)
        _server_child(shards)
        sys.exit(0)
    if "--client-child" in sys.argv:
        i = sys.argv.index("--client-child")
        _client_child(sys.argv[i + 1], sys.argv[i + 2])
        sys.exit(0)
    sys.exit(main())
