"""Operational wire ops: SAVE (≙ BGSAVE), STATS, and the active sweeper."""

import asyncio
import json

import pytest

from distributedratelimiting.redis_tpu.runtime import wire
from distributedratelimiting.redis_tpu.runtime.checkpoint import load_snapshot
from distributedratelimiting.redis_tpu.runtime.clock import ManualClock
from distributedratelimiting.redis_tpu.runtime.remote import RemoteBucketStore
from distributedratelimiting.redis_tpu.runtime.server import BucketStoreServer
from distributedratelimiting.redis_tpu.runtime.store import (
    DeviceBucketStore,
    InProcessBucketStore,
)


def run(coro):
    return asyncio.run(coro)


class TestSaveOp:
    def test_save_writes_restorable_checkpoint(self, tmp_path):
        path = str(tmp_path / "dump.bin")

        async def main():
            clock = ManualClock()
            backing = InProcessBucketStore(clock=clock)
            async with BucketStoreServer(backing, snapshot_path=path) as srv:
                client = RemoteBucketStore(address=(srv.host, srv.port))
                try:
                    await client.acquire("k", 4, 10.0, 1.0)
                    await client.save()
                finally:
                    await client.aclose()
            restored = InProcessBucketStore(clock=clock)
            load_snapshot(restored, path)
            assert restored.acquire_blocking("k", 6, 10.0, 1.0).granted
            assert not restored.acquire_blocking("k", 1, 10.0, 1.0).granted

        run(main())

    def test_save_without_path_is_remote_error(self):
        async def main():
            async with BucketStoreServer(InProcessBucketStore()) as srv:
                client = RemoteBucketStore(address=(srv.host, srv.port))
                try:
                    with pytest.raises(wire.RemoteStoreError,
                                       match="snapshot-path"):
                        await client.save()
                    # The connection survives the failed SAVE.
                    await client.ping()
                finally:
                    await client.aclose()

        run(main())

    def test_server_cli_restores_snapshot_at_startup(self, tmp_path):
        # The main() path: --snapshot-path pointing at an existing file
        # restores before serving (tested via the module-level pieces the
        # CLI wires: save to file, fresh store, load).
        from distributedratelimiting.redis_tpu.runtime.checkpoint import (
            save_snapshot,
        )

        path = str(tmp_path / "dump.bin")
        clock = ManualClock()
        s = InProcessBucketStore(clock=clock)
        s.acquire_blocking("x", 9, 10.0, 1.0)
        save_snapshot(s, path)
        s2 = InProcessBucketStore(clock=clock)
        load_snapshot(s2, path)
        assert not s2.acquire_blocking("x", 5, 10.0, 1.0).granted


class TestMeshBackendCLI:
    def test_server_cli_serves_mesh_backend(self):
        """`--backend mesh` from the console: the pod-slice deployment
        unit (a TCP server fronting every visible chip) must be
        launchable without code — here against the virtual 8-device CPU
        mesh, exercising buckets, windows, and the bulk op end to end."""
        import os
        import re
        import subprocess
        import sys

        from distributedratelimiting.redis_tpu.utils.cpu_bootstrap import (
            XLA_DEVICE_COUNT_FLAG,
        )

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"{XLA_DEVICE_COUNT_FLAG}=8")
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m",
             "distributedratelimiting.redis_tpu.runtime.server",
             "--backend", "mesh", "--port", "0", "--slots", "64"],
            cwd=repo, env=env, stdout=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            m = re.search(r"listening on (\S+):(\d+)", line)
            assert m, line
            host, port = m.group(1), int(m.group(2))

            async def drive():
                client = RemoteBucketStore(address=(host, port))
                try:
                    assert (await client.acquire("k", 1, 5.0, 1.0)).granted
                    assert (await client.window_acquire(
                        "w", 2, 3.0, 1.0)).granted
                    res = await client.acquire_many(
                        [f"b{i}" for i in range(32)], [1] * 32, 5.0, 1.0)
                    assert res.granted.all()
                    wres = await client.window_acquire_many(
                        [f"wb{i}" for i in range(32)], [1] * 32, 5.0, 1.0)
                    assert wres.granted.all()
                    stats = await client.stats()
                    assert any(k.startswith("bucket[")
                               for k in stats["store"]["tiers"])
                finally:
                    await client.aclose()

            run(drive())
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_server_cli_fp_directory(self):
        """`--directory fp` from the console: the device-resident
        fingerprint directory deployable without code — buckets and keyed
        windows decided straight from fingerprints over TCP."""
        import os
        import re
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m",
             "distributedratelimiting.redis_tpu.runtime.server",
             "--directory", "fp", "--port", "0", "--slots", "256"],
            cwd=repo, env=env, stdout=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            m = re.search(r"listening on (\S+):(\d+)", line)
            assert m, line
            host, port = m.group(1), int(m.group(2))

            async def drive():
                client = RemoteBucketStore(address=(host, port))
                try:
                    got = [(await client.acquire("k", 1, 3.0, 0.0)).granted
                           for _ in range(5)]
                    assert got == [True] * 3 + [False] * 2
                    assert (await client.window_acquire(
                        "w", 2, 3.0, 10.0)).granted
                    res = await client.acquire_many(
                        [f"b{i}" for i in range(32)], [1] * 32, 5.0, 1.0)
                    assert res.granted.all()
                finally:
                    await client.aclose()

            run(drive())
        finally:
            proc.terminate()
            proc.wait(timeout=10)


class TestStatsOp:
    def test_stats_reports_server_and_store_metrics(self):
        async def main():
            store = DeviceBucketStore(n_slots=64, counter_slots=8,
                                      clock=ManualClock(), max_batch=64)
            async with BucketStoreServer(store) as srv:
                client = RemoteBucketStore(address=(srv.host, srv.port))
                try:
                    await client.acquire("a", 1, 10.0, 1.0)
                    stats = await client.stats()
                finally:
                    await client.aclose()
            assert stats["requests_served"] >= 1
            assert stats["connections_served"] == 1
            assert stats["store"]["launches"] >= 1
            json.dumps(stats)  # round-trippable

        run(main())

    def test_stats_reset_flag_opens_fresh_window(self):
        """``stats(reset=True)`` must zero the serving-latency AND flush
        histograms IN PLACE (the MicroBatcher holds its reference) after
        snapshotting — the measurement-window contract the serving-p99
        rig relies on. A plain ``stats()`` must not reset."""

        async def main():
            store = DeviceBucketStore(n_slots=64, counter_slots=8,
                                      clock=ManualClock(), max_batch=64)
            async with BucketStoreServer(store) as srv:
                client = RemoteBucketStore(address=(srv.host, srv.port),
                                           coalesce_requests=False)
                try:
                    for i in range(8):
                        await client.acquire(f"w{i}", 1, 10.0, 1.0)
                    s1 = await client.stats()        # plain: no reset
                    s2 = await client.stats(reset=True)
                    s3 = await client.stats()
                    await client.acquire("post", 1, 10.0, 1.0)
                    s4 = await client.stats()
                finally:
                    await client.aclose()
            assert s1["serving_samples"] >= 8
            assert s2["serving_samples"] >= s1["serving_samples"]  # pre-reset snap
            assert s2["store"]["flush_samples"] >= 1
            # Post-reset: only the stats ops themselves have landed.
            assert s3["serving_samples"] <= 2
            assert s3["store"]["flush_samples"] == 0
            # New samples land in the SAME (in-place-reset) histograms.
            assert s4["serving_samples"] > s3["serving_samples"]
            assert s4["store"]["flush_samples"] >= 1

        run(main())


class TestActiveSweeper:
    def test_sweep_all_evicts_expired_buckets(self):
        clock = ManualClock()
        store = DeviceBucketStore(n_slots=64, counter_slots=8, clock=clock,
                                  max_batch=64)
        store.acquire_blocking("gone", 1, 10.0, 1.0)
        table = store._table(10.0, 1.0)
        assert table.dir.lookup("gone") is not None
        # Past time-to-full TTL (deficit 1 token @ 1/s → ceil + clamp ≥ 1s).
        clock.advance_seconds(5.0)
        store.sweep_all()
        assert table.dir.lookup("gone") is None
        assert store.metrics.slots_evicted >= 1

    def test_background_sweeper_runs_and_stops(self):
        async def main():
            clock = ManualClock()
            store = DeviceBucketStore(n_slots=64, counter_slots=8,
                                      clock=clock, max_batch=64)
            store.acquire_blocking("k", 1, 10.0, 1.0)
            clock.advance_seconds(5.0)
            store.start_sweeper(period_s=0.02)
            store.start_sweeper(period_s=0.02)  # idempotent
            for _ in range(100):
                await asyncio.sleep(0.02)
                if store.metrics.sweeps > 0:
                    break
            assert store.metrics.sweeps > 0
            await store.aclose()
            assert store._sweeper_task is None

        run(main())


class TestSaveCoalescing:
    def test_concurrent_saves_share_one_pull(self, tmp_path):
        path = str(tmp_path / "dump.bin")
        pulls = []

        class CountingStore(InProcessBucketStore):
            def snapshot(self):
                pulls.append(1)
                import time

                time.sleep(0.05)  # keep the save in flight
                return super().snapshot()

        async def main():
            backing = CountingStore()
            backing.acquire_blocking("k", 1, 10.0, 1.0)
            async with BucketStoreServer(backing, snapshot_path=path) as srv:
                client = RemoteBucketStore(address=(srv.host, srv.port))
                try:
                    await asyncio.gather(*(client.save() for _ in range(6)))
                finally:
                    await client.aclose()

        run(main())
        # 6 concurrent requests coalesce onto in-flight saves — far fewer
        # full-state pulls than requests (1-2 depending on arrival timing).
        assert 1 <= len(pulls) <= 2, pulls


class TestSweeperResilience:
    def test_sweeper_survives_failing_sweep(self):
        async def main():
            clock = ManualClock()
            store = DeviceBucketStore(n_slots=64, counter_slots=8,
                                      clock=clock, max_batch=64)
            calls = []
            original = store.sweep_all

            def flaky():
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError("transient device error")
                original()

            store.sweep_all = flaky
            store.start_sweeper(period_s=0.02)
            for _ in range(200):
                await asyncio.sleep(0.02)
                if len(calls) >= 2:
                    break
            assert len(calls) >= 2  # kept running after the failure
            await store.aclose()  # and aclose survives a failed task

        run(main())
