"""Store tests: device vs in-process semantic equivalence, slot lifecycle,
snapshot/restore, counter sync."""

import asyncio

import numpy as np
import pytest

from distributedratelimiting.redis_tpu.ops.bucket_math import TICKS_PER_SECOND
from distributedratelimiting.redis_tpu.runtime.clock import ManualClock
from distributedratelimiting.redis_tpu.runtime.store import (
    DeviceBucketStore,
    InProcessBucketStore,
)


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def clock():
    return ManualClock()


def device_store(clock, **kw):
    kw.setdefault("n_slots", 64)
    kw.setdefault("counter_slots", 16)
    kw.setdefault("max_delay_s", 0.001)
    return DeviceBucketStore(clock=clock, **kw)


class TestSemanticEquivalence:
    def test_random_ops_agree(self, clock, rng):
        """The TPU store and the serial in-process store must make identical
        decisions on an identical op stream (deterministic manual clock)."""
        dev = device_store(clock)
        ref = InProcessBucketStore(clock=clock)
        cap, rate = 20.0, 8.0

        async def main():
            for _ in range(120):
                clock.advance_ticks(int(rng.integers(0, TICKS_PER_SECOND)))
                key = f"k{rng.integers(0, 10)}"
                count = int(rng.integers(0, 6))
                got = dev.acquire_blocking(key, count, cap, rate)
                want = ref.acquire_blocking(key, count, cap, rate)
                assert got.granted == want.granted, (key, count)
                assert abs(got.remaining - want.remaining) < 1e-2

        run(main())

    def test_batched_async_agrees_with_serial(self, clock, rng):
        dev = device_store(clock)
        ref = InProcessBucketStore(clock=clock)
        cap, rate = 10.0, 2.0

        async def main():
            for round_ in range(10):
                clock.advance_ticks(TICKS_PER_SECOND // 2)
                keys = [f"k{i}" for i in range(8)]
                counts = [int(rng.integers(1, 4)) for _ in keys]
                got = await asyncio.gather(*(
                    dev.acquire(k, c, cap, rate) for k, c in zip(keys, counts)
                ))
                want = [ref.acquire_blocking(k, c, cap, rate)
                        for k, c in zip(keys, counts)]
                for g, w in zip(got, want):
                    assert g.granted == w.granted

        run(main())


class TestSlotLifecycle:
    def test_grow_on_exhaustion(self, clock):
        dev = device_store(clock, n_slots=4)

        async def main():
            # 10 distinct always-draining keys in a 4-slot table: the table
            # must grow (sweep can't reclaim — all buckets stay non-full).
            for i in range(10):
                res = dev.acquire_blocking(f"k{i}", 5, 10.0, 1.0)
                assert res.granted

        run(main())
        table = next(iter(dev._tables.values()))
        assert table.n_slots >= 10
        assert len(table.dir) == 10

    def test_sweep_reclaims_idle_slots(self, clock):
        dev = device_store(clock, n_slots=4)

        async def main():
            for i in range(4):
                dev.acquire_blocking(f"k{i}", 1, 10.0, 10.0)
            # After 2s the buckets are full again (rate 10/s, deficit 1) →
            # sweep frees them instead of growing.
            clock.advance_seconds(2.0)
            dev.acquire_blocking("fresh", 1, 10.0, 10.0)

        run(main())
        table = next(iter(dev._tables.values()))
        assert table.n_slots == 4  # no growth: sweep reclaimed
        assert table.dir.lookup("fresh") is not None

    def test_distinct_configs_get_distinct_tables(self, clock):
        dev = device_store(clock)
        dev.acquire_blocking("k", 1, 10.0, 1.0)
        dev.acquire_blocking("k", 1, 20.0, 1.0)
        assert len(dev._tables) == 2


class TestCounterSync:
    def test_sync_decay_and_instance_estimate(self, clock):
        dev = device_store(clock)

        async def main():
            clock.advance_seconds(1.0)
            res = await dev.sync_counter("bucket", 30.0, 10.0)
            assert res.global_score == 30.0
            clock.advance_seconds(2.0)
            res = await dev.sync_counter("bucket", 5.0, 10.0)
            # 30 decayed by 2s*10/s → 10, +5 = 15.
            assert abs(res.global_score - 15.0) < 1e-3

        run(main())

    def test_matches_inprocess(self, clock, rng):
        dev = device_store(clock)
        ref = InProcessBucketStore(clock=clock)

        async def main():
            for _ in range(20):
                clock.advance_ticks(int(rng.integers(1, 2 * TICKS_PER_SECOND)))
                count = float(rng.integers(0, 20))
                got = await dev.sync_counter("b", count, 5.0)
                want = await ref.sync_counter("b", count, 5.0)
                assert abs(got.global_score - want.global_score) < 1e-2
                assert abs(got.period_ewma_ticks - want.period_ewma_ticks) < 1.0

        run(main())


class TestWindow:
    def test_window_matches_inprocess(self, clock, rng):
        dev = device_store(clock)
        ref = InProcessBucketStore(clock=clock)

        async def main():
            for _ in range(60):
                clock.advance_ticks(int(rng.integers(0, 3 * TICKS_PER_SECOND)))
                key = f"k{rng.integers(0, 4)}"
                count = int(rng.integers(1, 5))
                got = dev.window_acquire_blocking(key, count, 10.0, 5.0)
                want = ref.window_acquire_blocking(key, count, 10.0, 5.0)
                assert got.granted == want.granted, (key, count)

        run(main())


class TestSnapshotRestore:
    def test_roundtrip(self, clock):
        dev = device_store(clock)

        async def main():
            dev.acquire_blocking("a", 3, 10.0, 1.0)
            dev.acquire_blocking("b", 7, 10.0, 1.0)
            await dev.sync_counter("g", 12.0, 1.0)
            snap = dev.snapshot()

            dev2 = device_store(clock)
            dev2.restore(snap)
            # Same immediate decision surface after restore.
            r1 = dev.acquire_blocking("a", 7, 10.0, 1.0)
            r2 = dev2.acquire_blocking("a", 7, 10.0, 1.0)
            assert r1.granted == r2.granted == True  # noqa: E712  (7 left)
            r1 = dev.acquire_blocking("b", 7, 10.0, 1.0)
            r2 = dev2.acquire_blocking("b", 7, 10.0, 1.0)
            assert r1.granted == r2.granted == False  # noqa: E712

        run(main())


class TestEpochRebase:
    def test_rebase_preserves_decisions(self):
        clock = ManualClock(start_ticks=2**30 - 10)
        dev = device_store(clock)

        async def main():
            dev.acquire_blocking("k", 10, 10.0, 1.0)  # drain at t≈2^30
            clock.advance_seconds(5.0)  # crosses the rebase threshold
            # Rebase happens inside now_ticks_checked; elapsed must still be
            # ~5s → 5 tokens refilled.
            res = dev.acquire_blocking("k", 5, 10.0, 1.0)
            assert res.granted
            res = dev.acquire_blocking("k", 1, 10.0, 1.0)
            assert not res.granted
            assert clock.now_ticks() < 2**30  # clock was rebased

        run(main())


class TestRestoreAcrossProcesses:
    def test_restore_realigns_clock_epoch(self):
        """Regression: restoring into a fresh process (new clock epoch) must
        preserve elapsed-since-touch, not clamp it to zero."""
        old_clock = ManualClock(start_ticks=1000 * TICKS_PER_SECOND)  # old uptime
        old = device_store(old_clock)

        async def main():
            old.acquire_blocking("k", 10, 10.0, 1.0)  # drain at old-t
            snap = old.snapshot()

            new_clock = ManualClock(start_ticks=0)  # fresh process
            new = device_store(new_clock)
            new.restore(snap)
            new_clock.advance_seconds(5.0)
            # 5s elapsed since the drain → 5 tokens, despite epoch change.
            assert new.acquire_blocking("k", 5, 10.0, 1.0).granted
            assert not new.acquire_blocking("k", 1, 10.0, 1.0).granted

        run(main())

    def test_restore_includes_window_tables(self):
        clock = ManualClock()
        dev = device_store(clock)

        async def main():
            dev.window_acquire_blocking("w", 8, 10.0, 5.0)
            snap = dev.snapshot()
            dev2 = device_store(ManualClock())
            dev2.restore(snap)
            # Restored window still remembers 8 of 10 consumed.
            assert not dev2.window_acquire_blocking("w", 5, 10.0, 5.0).granted
            assert dev2.window_acquire_blocking("w", 2, 10.0, 5.0).granted

        run(main())


class TestSweepPinning:
    def test_midbatch_sweep_cannot_steal_batch_slot(self):
        """Regression: a sweep triggered by slot exhaustion mid-batch must
        not free a slot already resolved for an earlier request in the same
        batch."""
        clock = ManualClock()
        dev = device_store(clock, n_slots=2)

        async def main():
            dev.acquire_blocking("a", 1, 10.0, 10.0)
            dev.acquire_blocking("b", 1, 10.0, 10.0)
            # Both buckets refill to full within 1s → sweepable.
            clock.advance_seconds(5.0)
            # Batch touches existing "a" AND new "c": allocating "c" sweeps;
            # "a"'s pinned slot must survive in the directory.
            res = await asyncio.gather(
                dev.acquire(("a"), 10, 10.0, 10.0),
                dev.acquire(("c"), 10, 10.0, 10.0),
            )
            assert all(r.granted for r in res)
            table = next(iter(dev._tables.values()))
            assert table.dir.lookup("a") is not None
            assert table.dir.lookup("a") != table.dir.lookup("c")
            # And "a" was actually drained — no cross-contamination (same
            # tick, so no refill yet).
            assert not dev.acquire_blocking("a", 1, 10.0, 10.0).granted

        run(main())


class TestBulkAcquire:
    """acquire_many: one call decides a whole key array, semantics
    identical to issuing the requests in order (duplicates serialize)."""

    def test_bulk_agrees_with_sequential_inprocess_reference(self, clock, rng):
        """Exact parity on duplicate-free calls (duplicates across calls
        and across time are fine — only in-call duplicates are decided
        conservatively, covered by the next test)."""
        dev = device_store(clock, max_batch=8)  # force multi-chunk dispatch
        ref = InProcessBucketStore(clock=clock)
        cap, rate = 10.0, 4.0
        for _ in range(4):
            perm = rng.permutation(24)
            keys = [f"k{i}" for i in perm]
            counts = [int(rng.integers(0, 4)) for _ in range(24)]
            bulk = dev.acquire_many_blocking(keys, counts, cap, rate)
            seq = [ref.acquire_blocking(k, c, cap, rate)
                   for k, c in zip(keys, counts)]
            assert [bool(g) for g in bulk.granted] == [r.granted for r in seq]
            np.testing.assert_allclose(
                bulk.remaining, [r.remaining for r in seq], atol=1e-4)
            clock.advance_seconds(0.5)

    def test_bulk_duplicates_conservative_never_over_admit(self, clock, rng):
        """In-call duplicates: total granted permits per key never exceed
        what the bucket held (the invariant); denials may be conservative
        relative to a serial replay (the documented trade)."""
        dev = device_store(clock, max_batch=8)
        cap, rate = 10.0, 0.0  # no refill: clean conservation accounting
        keys = [f"k{rng.integers(4)}" for _ in range(60)]
        counts = [int(rng.integers(0, 5)) for _ in range(60)]
        bulk = dev.acquire_many_blocking(keys, counts, cap, rate)
        spent: dict[str, int] = {}
        for k, c, g in zip(keys, counts, bulk.granted):
            if g:
                spent[k] = spent.get(k, 0) + c
        assert all(v <= cap for v in spent.values()), spent

    def test_bulk_async_single_await(self, clock):
        dev = device_store(clock, max_batch=8)

        async def main():
            res = await dev.acquire_many(
                [f"a{i}" for i in range(20)], [1] * 20, 5.0, 1.0)
            assert len(res) == 20
            # cap 5: every fresh key grants once... all distinct keys here.
            assert res.granted_count == 20
            # Same key 8 times, cap 5 -> exactly 5 grants in-order.
            res2 = await dev.acquire_many(["hot"] * 8, [1] * 8, 5.0, 1.0)
            assert [bool(g) for g in res2.granted] == [True] * 5 + [False] * 3
            await dev.aclose()

        run(main())

    def test_bulk_oversized_counts_fall_back_to_split_layout(self, clock):
        dev = device_store(clock, max_batch=8)
        res = dev.acquire_many_blocking(
            ["big", "big", "small"], [300, 300, 1], 500.0, 1.0)
        assert [bool(g) for g in res.granted] == [True, False, True]

    @pytest.mark.parametrize("fixed", [False, True])
    def test_window_bulk_oversized_counts(self, clock, fixed):
        """Counts over a byte take the scan kernel's 8-byte rows on the
        window path too (coalescing off, so the scanned plan runs)."""
        dev = device_store(clock, max_batch=8, coalesce_duplicates=False)
        res = dev.window_acquire_many_blocking(
            ["big", "big", "small"], [300, 300, 1], 500.0, 1.0, fixed=fixed)
        assert dev.metrics.bulk_plans_scanned == 1
        assert [bool(g) for g in res.granted] == [True, False, True]
        np.testing.assert_array_equal(res.remaining, [200.0, 200.0, 499.0])

    def test_bulk_result_indexing_and_iter(self, clock):
        dev = device_store(clock)
        res = dev.acquire_many_blocking(["x", "y"], [1, 9], 5.0, 1.0)
        assert res[0].granted and not res[1].granted
        as_list = list(res)
        assert as_list[0].granted and not as_list[1].granted
        assert len(res) == 2 and res.granted_count == 1

    def test_bulk_empty_call(self, clock):
        dev = device_store(clock)
        res = dev.acquire_many_blocking([], [], 5.0, 1.0)
        assert len(res) == 0 and res.granted_count == 0

    def test_bulk_zipf_duplicates_coalesce_into_grouped_rows(self, clock,
                                                             rng):
        """Heavy duplication routes the bulk call through the grouped
        kernel: launch rows ≈ distinct (key, count) groups, duplicates
        recorded in rows_coalesced, and decisions identical to the scan
        path's conservative serialization."""
        dev = device_store(clock, max_batch=64)
        cap, rate = 10.0, 0.0
        keys = [f"hot{rng.zipf(1.2) % 8}" for _ in range(400)]
        counts = [1] * 400
        res = dev.acquire_many_blocking(keys, counts, cap, rate)
        assert dev.metrics.rows_coalesced >= 400 - 8 * 2
        # Per key: exactly cap grants, on the FIRST occurrences.
        seen: dict[str, int] = {}
        for k, g in zip(keys, res.granted):
            before = seen.get(k, 0)
            assert bool(g) == (before < cap), (k, before)
            seen[k] = before + 1

        # Remaining view matches the per-row reconstruction.
        dev2 = device_store(clock, max_batch=64, coalesce_duplicates=False)
        res2 = dev2.acquire_many_blocking(keys, counts, cap, rate)
        np.testing.assert_array_equal(res.granted, res2.granted)
        np.testing.assert_allclose(res.remaining, res2.remaining, atol=1e-4)

    def test_bulk_mixed_counts_per_key_fall_back_to_scan(self, clock):
        dev = device_store(clock, max_batch=8)
        # "m" has mixed counts in one call → whole call on the scan path,
        # exact cumulative prefixes.
        res = dev.acquire_many_blocking(
            ["m", "m", "m", "n", "n"], [3, 1, 2, 2, 2], 5.0, 0.0)
        assert [bool(g) for g in res.granted] == [True, True, False,
                                                  True, True]

    def test_bulk_grouped_zero_count_probes(self, clock):
        dev = device_store(clock, max_batch=8)
        res = dev.acquire_many_blocking(
            ["p", "p", "p", "p"], [0, 0, 0, 0], 3.0, 0.0)
        assert res.granted.all()
        # Probes consumed nothing.
        assert dev.acquire_many_blocking(["p"], [3], 3.0, 0.0).granted[0]

    def test_window_bulk_zipf_coalesces_and_agrees(self, clock, rng):
        """window_acquire_many rides the same grouped coalescing as the
        bucket bulk path (one launch row per (key, count) group), with
        decisions identical to the per-row scan path."""
        dev = device_store(clock, max_batch=64)
        keys = [f"hw{rng.zipf(1.2) % 6}" for _ in range(300)]
        res = dev.window_acquire_many_blocking(keys, [1] * 300, 4.0, 1.0)
        assert dev.metrics.rows_coalesced >= 300 - 6 * 2
        seen: dict[str, int] = {}
        for k, g in zip(keys, res.granted):
            before = seen.get(k, 0)
            assert bool(g) == (before < 4), (k, before)
            seen[k] = before + 1
        dev2 = device_store(clock, max_batch=64, coalesce_duplicates=False)
        res2 = dev2.window_acquire_many_blocking(keys, [1] * 300, 4.0, 1.0)
        np.testing.assert_array_equal(res.granted, res2.granted)
        np.testing.assert_allclose(res.remaining, res2.remaining, atol=1e-4)

    def test_window_bulk_fixed_agrees_with_sequential(self, clock, rng):
        dev = device_store(clock, max_batch=8)
        ref = InProcessBucketStore(clock=clock)
        for _ in range(3):
            keys = [f"fw{i}" for i in rng.choice(12, size=8, replace=False)]
            counts = [int(c) for c in rng.integers(0, 3, size=8)]
            got = dev.window_acquire_many_blocking(keys, counts, 5.0, 1.0,
                                                   fixed=True)
            want = [ref.fixed_window_acquire_blocking(k, c, 5.0, 1.0)
                    for k, c in zip(keys, counts)]
            assert [bool(g) for g in got.granted] == [w.granted
                                                      for w in want]
            clock.advance_seconds(0.4)

    def test_bulk_default_path_on_inprocess_and_remote_parity(self, clock):
        ref = InProcessBucketStore(clock=clock)
        res = ref.acquire_many_blocking(["a"] * 7, [1] * 7, 5.0, 1.0)
        assert [bool(g) for g in res.granted] == [True] * 5 + [False] * 2

        async def main():
            ref2 = InProcessBucketStore(clock=clock)
            res2 = await ref2.acquire_many(["b"] * 7, [1] * 7, 5.0, 1.0)
            assert res2.granted_count == 5

        run(main())


def _bulk_plan_frame(case: str, rng) -> tuple[list[str], list[int]]:
    """One bulk call's keys and counts. Every key's balance (6) is a
    multiple of each count it asks for, and a key's denials come only
    after its balance is spent, so the serial reference and the bulk
    path's conservative in-call serialization agree row for row."""
    if case == "one-row":
        return ["solo"], [2]
    if case == "uniform":
        keys = [f"u{i}" for i in rng.permutation(48)]
        return keys, [int(c) for c in rng.integers(0, 7, 48)]
    # Eight hot keys, eight rows each, interleaved: 8 groups of 64 rows.
    per_key = {f"h{i}": [1 + i % 3] * 8 for i in range(8)}
    if case == "mixed":
        per_key["h0"] = [1, 2, 3, 1, 1, 1, 1, 1]
    order = rng.permutation([k for k in per_key for _ in range(8)])
    seen = {k: 0 for k in per_key}
    keys, counts = [], []
    for k in order:
        keys.append(str(k))
        counts.append(per_key[k][seen[k]])
        seen[k] += 1
    return keys, counts


class TestBulkPlan:
    """A bulk call resolves its keys through the host directory once, in
    one store-lock section, and picks the grouped or scanned plan from
    those slots."""

    @pytest.mark.parametrize("case, plan", [
        ("uniform", "scanned"), ("duplicated", "grouped"),
        ("one-row", "scanned"), ("mixed", "scanned")])
    @pytest.mark.parametrize("kind", ["bucket", "window"])
    def test_one_resolve_per_call_and_serial_decisions(self, clock, rng,
                                                       kind, case, plan):
        dev = device_store(clock, n_slots=128, max_batch=64)
        ref = InProcessBucketStore(clock=clock)
        if kind == "bucket":
            table = dev._table(6.0, 0.0)
            call = lambda k, c: dev.acquire_many(k, c, 6.0, 0.0)
            serial = lambda k, c: ref.acquire_blocking(k, c, 6.0, 0.0)
        else:
            table = dev._wtable(6.0, 10.0)
            call = lambda k, c: dev.window_acquire_many(k, c, 6.0, 10.0)
            serial = lambda k, c: ref.window_acquire_blocking(k, c, 6.0,
                                                              10.0)
        resolves = []
        resolve_batch = table.dir.resolve_batch

        def counted(keys):
            resolves.append(len(keys))
            return resolve_batch(keys)

        table.dir.resolve_batch = counted
        keys, counts = _bulk_plan_frame(case, rng)

        async def main():
            res = await call(keys, counts)
            await dev.aclose()
            return res

        res = run(main())
        assert resolves == [len(keys)]
        want = [serial(k, c) for k, c in zip(keys, counts)]
        assert [bool(g) for g in res.granted] == [w.granted for w in want]
        assert res.remaining.tolist() == [w.remaining for w in want]
        snap = dev.metrics.snapshot()
        assert (snap["bulk_plans_grouped"], snap["bulk_plans_scanned"]) == \
            ((1, 0) if plan == "grouped" else (0, 1))
        assert dev.metrics.rows_coalesced == (
            len(keys) - 8 if plan == "grouped" else 0)


class TestBulkLimiter:
    def test_partitioned_acquire_many(self, clock):
        from distributedratelimiting.redis_tpu.models.options import (
            TokenBucketOptions,
        )
        from distributedratelimiting.redis_tpu.models.partitioned import (
            PartitionedRateLimiter,
        )

        dev = device_store(clock, max_batch=8)
        lim = PartitionedRateLimiter(
            TokenBucketOptions(token_limit=5, tokens_per_period=1,
                               instance_name="bulk"), dev)

        async def main():
            res = await lim.acquire_many([f"u{i % 10}" for i in range(50)])
            assert len(res) == 50
            # 10 partitions x cap 5 = 50 grants possible; 5 requests each.
            assert res.granted_count == 50
            res2 = await lim.acquire_many(["u0"] * 3)
            assert res2.granted_count == 0  # u0 drained
            assert lim.metrics.decisions == 53
            return True

        assert run(main())

    def test_partitioned_bulk_per_resource_permits_validated(self, clock):
        from distributedratelimiting.redis_tpu.models.options import (
            TokenBucketOptions,
        )
        from distributedratelimiting.redis_tpu.models.partitioned import (
            PartitionedRateLimiter,
        )

        dev = device_store(clock)
        lim = PartitionedRateLimiter(
            TokenBucketOptions(token_limit=5, tokens_per_period=1,
                               instance_name="bulk2"), dev)
        with pytest.raises(ValueError):
            lim.acquire_many_blocking(["a", "b"], [1, 99])  # over limit
        with pytest.raises(ValueError):
            lim.acquire_many_blocking(["a", "b"], [1])  # length mismatch
        res = lim.acquire_many_blocking(["a", "b"], [2, 9 - 5])
        assert res.granted_count == 2


class TestBulkVerdictOnly:
    def test_bits_path_matches_full_path(self, clock, rng):
        dev = device_store(clock, max_batch=8)
        dev2 = device_store(ManualClock(), max_batch=8)
        keys = [f"k{rng.integers(12)}" for _ in range(64)]
        full = dev.acquire_many_blocking(keys, [1] * 64, 5.0, 1.0)
        bits = dev2.acquire_many_blocking(keys, [1] * 64, 5.0, 1.0,
                                          with_remaining=False)
        assert bits.remaining is None
        assert [bool(g) for g in bits.granted] == \
               [bool(g) for g in full.granted]
        assert bits[0].remaining == 0.0  # indexing still works


def test_partitioned_bulk_zero_permit_probe_always_granted():
    """Bulk keeps the single-request contract: permits=0 is granted
    unconditionally, even riding beside a denied same-key request."""
    from distributedratelimiting.redis_tpu.models.options import (
        TokenBucketOptions,
    )
    from distributedratelimiting.redis_tpu.models.partitioned import (
        PartitionedRateLimiter,
    )

    clock = ManualClock()
    dev = device_store(clock)
    lim = PartitionedRateLimiter(
        TokenBucketOptions(token_limit=5, tokens_per_period=1,
                           instance_name="zp"), dev)
    lim.acquire("k", 2)  # bucket at 3
    res = lim.acquire_many_blocking(["k", "k"], [5, 0])
    assert not res[0].granted         # 5 > 3
    assert res[1].granted             # probe: unconditional, as in acquire()
    assert lim.acquire("k", 0).is_acquired


class TestFlushCoalescing:
    """Same-key requests in one flush collapse to one launch row
    (grouped kernel), verdicts identical to per-row serialization."""

    def test_hot_key_one_row_first_n_granted(self, clock):
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)

        async def main():
            results = await asyncio.gather(
                *(dev.acquire("hot", 1, 5.0, 1.0) for _ in range(32)))
            grants = [r.granted for r in results]
            assert grants == [True] * 5 + [False] * 27
            # 32 requests rode as ONE launch row.
            assert dev.metrics.rows_coalesced == 31
            assert dev.metrics.rows_valid == 1
            await dev.aclose()

        run(main())

    def test_mixed_hot_and_cold_keys(self, clock):
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)

        async def main():
            reqs = [("hot", 1)] * 10 + [("cold1", 2), ("cold2", 2)] \
                + [("hot", 1)] * 10
            results = await asyncio.gather(
                *(dev.acquire(k, c, 5.0, 1.0) for k, c in reqs))
            hot = [r.granted for i, r in enumerate(results)
                   if reqs[i][0] == "hot"]
            assert sum(hot) == 5 and hot == [True] * 5 + [False] * 15
            assert all(r.granted for i, r in enumerate(results)
                       if reqs[i][0] != "hot")
            # 22 requests -> 3 rows (hot group + 2 singles).
            assert dev.metrics.rows_coalesced == 19
            await dev.aclose()

        run(main())

    def test_mixed_counts_same_key_stay_exact(self, clock):
        """A key with differing counts in one flush falls back to exact
        per-row cumulative prefixes: 3+1+1 at cap 5 -> all granted, then
        denial."""
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)

        async def main():
            counts = [3, 1, 1, 2]
            results = await asyncio.gather(
                *(dev.acquire("mk", c, 5.0, 1.0) for c in counts))
            assert [r.granted for r in results] == [True, True, True, False]
            await dev.aclose()

        run(main())

    def test_zero_count_probe_groups(self, clock):
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)

        async def main():
            # Probes beside real requests: granted while balance covers the
            # earlier (conservative) demand.
            results = await asyncio.gather(
                dev.acquire("p", 2, 5.0, 1.0),
                dev.acquire("p", 0, 5.0, 1.0),
                dev.acquire("p", 0, 5.0, 1.0),
            )
            assert [r.granted for r in results] == [True, True, True]
            await dev.aclose()

        run(main())

    def test_coalesced_agrees_with_serial_inprocess(self, clock, rng):
        """Differential: duplicate-heavy async traffic vs the serial
        reference, uniform counts per key (the coalesced regime)."""
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)
        ref = InProcessBucketStore(clock=clock)
        cap, rate = 12.0, 3.0

        async def main():
            for round_ in range(6):
                clock.advance_seconds(1.0)
                keys = [f"k{rng.integers(3)}" for _ in range(24)]
                got = await asyncio.gather(
                    *(dev.acquire(k, 1, cap, rate) for k in keys))
                want = [ref.acquire_blocking(k, 1, cap, rate) for k in keys]
                # Per-key grant totals must match (arrival order inside one
                # flush is the gather order — same as the serial replay).
                for key in set(keys):
                    got_n = sum(g.granted for g, kk in zip(got, keys)
                                if kk == key)
                    want_n = sum(w.granted for w, kk in zip(want, keys)
                                 if kk == key)
                    assert got_n == want_n, (round_, key)
            await dev.aclose()

        run(main())

    def test_window_table_hot_key_coalesces(self, clock):
        """Window limiters share the coalescing machinery: a hot key is one
        launch row, first-n-granted semantics."""
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)

        async def main():
            results = await asyncio.gather(
                *(dev.window_acquire("hot", 1, 5.0, 10.0) for _ in range(20)))
            grants = [r.granted for r in results]
            assert grants == [True] * 5 + [False] * 15
            assert dev.metrics.rows_coalesced == 19
            # Serial reference agreement on a fresh store.
            ref = InProcessBucketStore(clock=clock)
            want = [ref.window_acquire_blocking("hot", 1, 5.0, 10.0)
                    for _ in range(20)]
            assert grants == [w.granted for w in want]
            await dev.aclose()

        run(main())

    def test_ablation_toggle_off_uses_per_row_path(self, clock, rng):
        """coalesce_duplicates=False re-enables the per-row host-prefix
        flush; decisions agree with the serial reference the same way."""
        dev = device_store(clock, max_batch=64, max_delay_s=0.005,
                           coalesce_duplicates=False)
        ref = InProcessBucketStore(clock=clock)

        async def main():
            keys = [f"k{rng.integers(3)}" for _ in range(24)]
            got = await asyncio.gather(
                *(dev.acquire(k, 1, 12.0, 3.0) for k in keys))
            want = [ref.acquire_blocking(k, 1, 12.0, 3.0) for k in keys]
            for key in set(keys):
                assert (sum(g.granted for g, kk in zip(got, keys) if kk == key)
                        == sum(w.granted for w, kk in zip(want, keys)
                               if kk == key))
            assert dev.metrics.rows_coalesced == 0
            await dev.aclose()

        run(main())

    def test_coalesced_remaining_matches_per_row_view(self, clock):
        """Each member's remaining is its exact per-row conservative view,
        not the group-wide post-consumption value."""
        dev = device_store(clock, max_batch=64, max_delay_s=0.005)
        off = device_store(ManualClock(), max_batch=64, max_delay_s=0.005,
                           coalesce_duplicates=False)

        async def main():
            got = await asyncio.gather(
                *(dev.acquire("h", 1, 5.0, 1.0) for _ in range(8)))
            want = await asyncio.gather(
                *(off.acquire("h", 1, 5.0, 1.0) for _ in range(8)))
            assert [(r.granted, r.remaining) for r in got] == \
                   [(r.granted, r.remaining) for r in want]
            # First grant sees 4 left, not the group's post-consumption 0.
            assert got[0] == (True, 4.0)
            await dev.aclose()
            await off.aclose()

        run(main())


class TestLaunchWidths:
    """A bucket-table launch is padded to the smallest of its widths (16,
    ``max_batch``) that holds its rows; the decisions do not depend on the
    width, and every width is compiled with the table."""

    @pytest.mark.parametrize("n, width", [(1, 16), (16, 16), (17, 4096),
                                          (256, 4096), (257, 4096)])
    def test_bulk_call_pads_to_the_smallest_width(self, clock, rng, n,
                                                  width):
        dev = device_store(clock, n_slots=1024)
        ref = InProcessBucketStore(clock=clock)
        keys = [f"k{i}" for i in rng.permutation(n)]
        counts = [int(c) for c in rng.integers(0, 4, n)]
        before = dev.metrics.rows_processed
        for _ in range(3):  # spend, then spend what is left
            bulk = dev.acquire_many_blocking(keys, counts, 5.0, 4.0)
            seq = [ref.acquire_blocking(k, c, 5.0, 4.0)
                   for k, c in zip(keys, counts)]
            assert [bool(g) for g in bulk.granted] == [r.granted for r in seq]
            np.testing.assert_allclose(
                bulk.remaining, [r.remaining for r in seq], atol=1e-4)
            clock.advance_seconds(0.25)
        assert dev.metrics.rows_processed - before == 3 * width

    def test_flushes_pad_to_16(self, clock):
        """One-row flushes and a coalesced hot key (one grouped row)."""
        dev = device_store(clock, max_delay_s=0.005)

        async def main():
            assert dev.acquire_blocking("a", 1, 5.0, 1.0).granted
            assert dev.metrics.rows_processed == 16
            results = await asyncio.gather(
                *(dev.acquire("hot", 1, 3.0, 1.0) for _ in range(8)))
            assert [r.granted for r in results] == [True] * 3 + [False] * 5
            assert dev.metrics.rows_processed == 16 * dev.metrics.launches
            await dev.aclose()

        run(main())

    def test_no_width_compiles_while_serving(self, clock):
        """Once a table exists, flushes and bulk calls of 1, 17 and 257
        rows, with and without remainders and with in-batch duplicates,
        compile nothing: each width was compiled with the table."""
        import jax

        dev = device_store(clock, n_slots=2048, max_delay_s=0.005)
        dev.acquire_blocking("first", 1, 5.0, 1.0)  # builds the table
        compiles = []

        def on_event(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        async def main():
            for n in (1, 17, 257):
                keys = [f"n{n}-{i}" for i in range(n)]
                await asyncio.gather(*(dev.acquire(k, 1, 5.0, 1.0)
                                       for k in keys))
                await asyncio.gather(*(dev.acquire(keys[0], 1, 5.0, 1.0)
                                       for _ in range(n)))
                for with_remaining in (True, False):
                    await dev.acquire_many(keys, [1] * n, 5.0, 1.0,
                                           with_remaining=with_remaining)
                await dev.acquire_many(keys[:1] * n, [1] * n, 5.0, 1.0)
            await dev.aclose()

        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            run(main())
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert dev.metrics.launches > 12
        assert compiles == []

    def test_pregrow_warms_the_served_bulk_kernel(self, clock):
        """After the pregrow warm at the doubled size and the grow, a
        32-chunk bulk call in either verdict form finds its scan kernel
        compiled."""
        from distributedratelimiting.redis_tpu.ops import kernels as K

        dev = device_store(clock, max_batch=16, coalesce_duplicates=False)
        table = dev._table(5.0, 1.0)
        table._warm_for_size(2 * table.n_slots)
        table._grow()
        entries = K.acquire_scan._cache_size()
        keys = [f"k{i % 40}" for i in range(32 * 16)]
        for with_remaining in (True, False):
            dev.acquire_many_blocking(keys, [1] * len(keys), 5.0, 1.0,
                                      with_remaining=with_remaining)
        assert dev.metrics.launches == 2
        assert K.acquire_scan._cache_size() == entries


class TestOneCrossingPerLaunch:
    """A served decision launch hands its jitted kernel host (numpy)
    operands, which jit copies up inside the call: no ``jnp.asarray`` or
    ``jax.device_put`` before it, and no new signature after the table's
    warm-up."""

    N = 4096

    def _keys(self, tag=""):
        return [f"k{tag}{i}" for i in range(self.N)]

    @pytest.mark.parametrize("with_remaining", [True, False])
    def test_bulk_call_sends_no_separate_transfer(self, clock, transfers,
                                                  with_remaining):
        dev = device_store(clock, n_slots=2**14)
        keys = self._keys()

        async def main():
            await dev.acquire_many(keys, [1] * self.N, 5.0, 1.0,
                                   with_remaining=with_remaining)
            launches = dev.metrics.launches
            transfers.clear()
            res = await dev.acquire_many(keys, [1] * self.N, 5.0, 1.0,
                                         with_remaining=with_remaining)
            assert transfers == []
            assert res.granted.all()
            assert dev.metrics.launches == launches + 1
            await dev.aclose()

        run(main())
        assert dev.metrics.dispatch_transfers == 0

    def test_scalar_flush_sends_no_separate_transfer(self, clock, transfers):
        """A flush of distinct keys (one row each) and one of a hot key
        (the grouped kernel)."""
        dev = device_store(clock, n_slots=1024, max_delay_s=0.005)

        async def main():
            await dev.acquire("warm", 1, 5.0, 1.0)
            launches = dev.metrics.launches
            transfers.clear()
            distinct = await asyncio.gather(
                *(dev.acquire(f"k{i}", 1, 5.0, 1.0) for i in range(8)))
            hot = await asyncio.gather(
                *(dev.acquire("hot", 1, 3.0, 1.0) for _ in range(8)))
            assert transfers == []
            assert all(r.granted for r in distinct)
            assert [r.granted for r in hot] == [True] * 3 + [False] * 5
            assert dev.metrics.launches > launches
            assert dev.metrics.rows_coalesced > 0
            await dev.aclose()

        run(main())
        assert dev.metrics.dispatch_transfers == 0

    def test_first_served_calls_hit_the_warmed_signatures(self, clock):
        """After the tables are built, a 4,096-key bulk call in both
        verdict forms and 16-row flushes (per-row and grouped) of the
        bucket table, and a grouped flush of the window table, add no
        entry to any kernel's dispatch cache (a table size of its own, so
        no other test's calls fill the entries first)."""
        from distributedratelimiting.redis_tpu.ops import kernels as K

        dev = device_store(clock, n_slots=12_345, max_delay_s=0.005)
        dev._table(5.0, 1.0)
        dev._wtable(4.0, 1.0)
        kernels = (K.acquire_scan, K.acquire_batch_packed,
                   K.acquire_batch_packed_grouped,
                   K.window_acquire_batch_packed_grouped)
        entries = [k._cache_size() for k in kernels]
        keys = self._keys()

        async def main():
            for with_remaining in (True, False):
                await dev.acquire_many(keys, [1] * self.N, 5.0, 1.0,
                                       with_remaining=with_remaining)
            await asyncio.gather(
                *(dev.acquire(f"s{i}", 1, 5.0, 1.0) for i in range(8)))
            await asyncio.gather(
                *(dev.acquire("hot", 1, 5.0, 1.0) for _ in range(8)))
            await asyncio.gather(
                *(dev.window_acquire("w", 1, 4.0, 1.0) for _ in range(8)))
            await dev.aclose()

        run(main())
        assert dev.metrics.rows_processed == 2 * self.N + 2 * 16 + 4096
        assert [k._cache_size() for k in kernels] == entries

    def test_numpy_operands_decide_like_device_operands(self, clock):
        """Seeded bulk calls with duplicate keys, mixed counts, counts
        above 255 (the 8-byte rows) and zero-permit probes, through both
        plans: the same grants and remainders as a table that sends each
        operand up with ``jnp.asarray`` before the call."""
        import jax.numpy as jnp

        from distributedratelimiting.redis_tpu.runtime import store as S

        class AsarrayTable(S._DeviceTable):
            def _launch_scan_chunk(self, rows, nows, verdict_only):
                return super()._launch_scan_chunk(
                    jnp.asarray(rows), jnp.asarray(nows), verdict_only)

            def _launch_grouped(self, packed):
                return super()._launch_grouped(jnp.asarray(packed))

        class AsarrayStore(DeviceBucketStore):
            _TABLE_CLS = AsarrayTable

        dev = device_store(clock, n_slots=1024)
        ref = AsarrayStore(clock=clock, n_slots=1024, counter_slots=16)
        g = np.random.default_rng(29)
        plans = []
        for call in range(6):
            n = int(g.integers(200, 600))
            keys = [f"k{i}" for i in g.integers(0, 120, n)]
            if call % 2:   # one count per key: the grouped plan
                counts = [int(c) for c in g.integers(0, 40, 120)]
                counts = [counts[int(k[1:])] for k in keys]
            else:          # mixed counts, some above 255, zero probes
                counts = [int(c) for c in g.choice([0, 1, 2, 7, 300], n)]
            for with_remaining in (True, False):
                before = dev.metrics.bulk_plans_grouped
                got = dev.acquire_many_blocking(
                    keys, counts, 400.0, 50.0, with_remaining=with_remaining)
                want = ref.acquire_many_blocking(
                    keys, counts, 400.0, 50.0, with_remaining=with_remaining)
                plans.append(dev.metrics.bulk_plans_grouped - before)
                np.testing.assert_array_equal(got.granted, want.granted)
                if with_remaining:
                    np.testing.assert_array_equal(got.remaining,
                                                  want.remaining)
                assert got.granted.any() and not got.granted.all()
            clock.advance_seconds(0.5)
        assert 0 < sum(plans) < len(plans)
        assert dev.metrics.dispatch_transfers == 0


def test_record_launch_counts_operands_already_on_the_device():
    import jax.numpy as jnp

    from distributedratelimiting.redis_tpu.utils.metrics import StoreMetrics

    m = StoreMetrics()
    m.record_launch(16, 3, np.zeros(4, np.int32), np.int32(7))
    m.record_launch(16, 3, jnp.zeros(4, jnp.int32), np.zeros(1, np.int32))
    assert (m.launches, m.dispatch_transfers) == (2, 1)
    assert m.snapshot()["dispatch_transfers"] == 1
