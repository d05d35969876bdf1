"""Device-resident fingerprint directory: kernels + store.

The probe/insert/TTL design and its disclosed trade-offs live in
``ops/fp_directory.py``; the store integration in ``runtime/fp_store.py``.
Differential anchor: `FingerprintBucketStore` must decide exactly like
`InProcessBucketStore` under a shared manual clock."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from distributedratelimiting.redis_tpu.ops import fp_directory as F
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.runtime.clock import ManualClock
from distributedratelimiting.redis_tpu.runtime.fp_store import (
    FingerprintBucketStore,
    fingerprints,
)
from distributedratelimiting.redis_tpu.runtime.store import (
    DeviceBucketStore,
    InProcessBucketStore,
)


def run(coro):
    return asyncio.run(coro)


class TestFingerprints:
    def test_native_and_python_agree(self):
        # The pure-Python FNV fallback must match the native pass
        # bit-for-bit — fingerprints persist in tables and checkpoints.
        from distributedratelimiting.redis_tpu.runtime import fp_store

        keys = ["a", "user:42", "ключ-🔑", "", "x" * 300]
        got = fingerprints(keys)
        for i, k in enumerate(keys):
            h = fp_store._fp64_py(k)
            assert got[i, 0] == h & 0xFFFFFFFF
            assert got[i, 1] == h >> 32

    def test_never_empty_sentinel(self):
        fps = fingerprints([f"k{i}" for i in range(1000)])
        assert ((fps != 0).any(axis=1)).all()


def _resolve(fp, keys, valid=None, probe_window=8, rounds=4):
    k = jnp.asarray(fingerprints(keys))
    v = jnp.ones((len(keys),), bool) if valid is None else jnp.asarray(valid)
    return F.fp_resolve_core(fp, k, v, probe_window=probe_window,
                             rounds=rounds)


class TestResolveKernel:
    def test_insert_then_hit_same_slot(self):
        fp = F.init_fp_table(64)
        out1 = _resolve(fp, ["alpha", "beta", "gamma"])
        assert np.asarray(out1.resolved).all()
        out2 = _resolve(out1.fp, ["gamma", "alpha", "beta"])
        s1 = np.asarray(out1.slots)
        s2 = np.asarray(out2.slots)
        assert s2[0] == s1[2] and s2[1] == s1[0] and s2[2] == s1[1]

    def test_distinct_keys_distinct_slots(self):
        fp = F.init_fp_table(256)
        keys = [f"k{i}" for i in range(100)]
        out = _resolve(fp, keys)
        slots = np.asarray(out.slots)
        assert np.asarray(out.resolved).all()
        assert len(np.unique(slots)) == 100

    def test_in_batch_duplicates_share_slot(self):
        fp = F.init_fp_table(64)
        out = _resolve(fp, ["dup", "x", "dup", "dup"])
        slots = np.asarray(out.slots)
        assert slots[0] == slots[2] == slots[3] != slots[1]

    def test_padding_rows_do_not_insert(self):
        fp = F.init_fp_table(64)
        out = _resolve(fp, ["a", "b"], valid=np.array([True, False]))
        assert int((np.asarray(out.fp) != 0).any(-1).sum()) == 1
        assert np.asarray(out.slots)[1] == -1

    def test_window_pressure_reports_unresolved(self):
        # 4-slot table, window 4: the 5th distinct key cannot be placed.
        fp = F.init_fp_table(4)
        out = _resolve(fp, [f"k{i}" for i in range(6)], probe_window=4)
        res = np.asarray(out.resolved)
        assert res.sum() == 4
        assert (np.asarray(out.slots)[~res] == -1).all()

    def test_sweep_frees_cells_for_reuse(self):
        fp = F.init_fp_table(4)
        state = K.init_bucket_state(4)
        out = _resolve(fp, [f"k{i}" for i in range(4)], probe_window=4)
        # Touch the buckets so exists=True and TTL applies.
        state, _, _ = K.acquire_core(
            state, out.slots, jnp.ones((4,), jnp.int32),
            jnp.ones((4,), bool), jnp.int32(0), jnp.float32(5.0),
            jnp.float32(1.0 / 1024.0))
        far = 10_000_000  # way past time-to-full TTL
        fp2, state2, n_freed = F.fp_sweep_expired(
            out.fp, state, jnp.int32(far), jnp.float32(5.0),
            jnp.float32(1.0 / 1024.0))
        assert int(n_freed) == 4
        out2 = _resolve(fp2, ["fresh1", "fresh2"], probe_window=4)
        assert np.asarray(out2.resolved).all()

    def test_peek_does_not_insert(self):
        fp = F.init_fp_table(64)
        state = K.init_bucket_state(64)
        k = jnp.asarray(fingerprints(["ghost"]))
        est = F.fp_peek_batch(fp, state, k, jnp.ones((1,), bool),
                              jnp.int32(0), jnp.float32(7.0),
                              jnp.float32(0.0), probe_window=8)
        assert float(np.asarray(est)[0]) == 7.0  # full bucket on miss
        assert int((np.asarray(fp) != 0).any(-1).sum()) == 0

    def test_migrate_preserves_state(self):
        fp = F.init_fp_table(8)
        state = K.init_bucket_state(8)
        keys = [f"k{i}" for i in range(6)]
        out = _resolve(fp, keys, probe_window=8)
        tokens = jnp.asarray(np.arange(8, dtype=np.float32))
        state = K.BucketState(tokens, state.last_ts,
                              jnp.ones((8,), bool))
        new_fp = F.init_fp_table(16)
        new_state = K.init_bucket_state(16)
        kpair = out.fp[np.asarray(out.slots)]
        new_fp, new_state, placed = F.fp_migrate_chunk(
            new_fp, new_state, kpair, tokens[out.slots],
            state.last_ts[out.slots], state.exists[out.slots],
            jnp.ones((6,), bool), probe_window=8)
        assert np.asarray(placed).all()
        re = _resolve(new_fp, keys, probe_window=8)
        old_tokens = np.asarray(tokens)[np.asarray(out.slots)]
        new_tokens = np.asarray(new_state.tokens)[np.asarray(re.slots)]
        np.testing.assert_allclose(new_tokens, old_tokens)


class TestTableInvariants:
    def test_churn_preserves_uniqueness_and_findability(self):
        # Property check over random insert/expire/re-insert churn: every
        # live fingerprint occupies exactly ONE cell (duplicate cells
        # would let one key's consumption split across buckets), and
        # every live key resolves to its cell within the probe window
        # (full-window scans make TTL clears safe — this is the claim).
        rng = np.random.default_rng(17)
        clock = ManualClock()
        store = FingerprintBucketStore(n_slots=256, clock=clock,
                                       probe_window=8)
        table = store._table(5.0, 1.0)
        pool = [f"c{i}" for i in range(120)]

        async def churn():
            for cycle in range(6):
                batch = [pool[j] for j in rng.integers(0, len(pool), 80)]
                await store.acquire_many(batch, [1] * 80, 5.0, 1.0)
                clock.advance_seconds(rng.choice([0.5, 2.0, 3600.0]))
                store.sweep_all()
            fp = np.asarray(table.fp)
            live = fp[(fp != 0).any(-1)]
            # Uniqueness: no fingerprint occupies two cells.
            packed = live[:, 0].astype(np.uint64) << 32 | live[:, 1]
            assert len(np.unique(packed)) == len(packed)
            # Findability: re-resolving every live fingerprint hits
            # (insert-free peek must see full table coverage).
            from distributedratelimiting.redis_tpu.ops import (
                fp_directory as F,
            )
            import jax.numpy as jnp

            out = F.fp_resolve_core(
                jnp.asarray(fp), jnp.asarray(live),
                jnp.ones((len(live),), bool),
                probe_window=table.probe_window, rounds=1)
            assert np.asarray(out.resolved).all()
            slots = np.asarray(out.slots)
            assert len(np.unique(slots)) == len(slots)
            await store.aclose()

        run(churn())


class TestFingerprintStore:
    def test_capacity_enforced_async_path(self):
        async def main():
            store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            got = [(await store.acquire("k", 1, 3.0, 1.0)).granted
                   for _ in range(5)]
            assert got == [True] * 3 + [False] * 2
            await store.aclose()

        run(main())

    def test_refill_over_time(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock)
            for _ in range(3):
                assert (await store.acquire("k", 1, 3.0, 1.0)).granted
            assert not (await store.acquire("k", 1, 3.0, 1.0)).granted
            clock.advance_seconds(2.0)
            assert (await store.acquire("k", 2, 3.0, 1.0)).granted
            await store.aclose()

        run(main())

    def test_bulk_matches_host_directory_store(self):
        # Same kernel core, different directory: the fingerprint store
        # must decide bit-identically to the host-directory device store
        # (including the documented CONSERVATIVE in-batch duplicate rule,
        # which an exact serial oracle intentionally differs from).
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=1024, clock=clock)
            oracle = DeviceBucketStore(n_slots=1024, clock=clock)
            rng = np.random.default_rng(7)
            keys = [f"k{i}" for i in rng.integers(0, 40, 300)]
            counts = rng.integers(0, 4, 300).tolist()
            got = await store.acquire_many(keys, counts, 5.0, 1.0)
            want = await oracle.acquire_many(keys, counts, 5.0, 1.0)
            np.testing.assert_array_equal(got.granted, want.granted)
            np.testing.assert_allclose(got.remaining, want.remaining,
                                       atol=1e-4)
            await store.aclose()
            await oracle.aclose()

        run(main())

    def test_bulk_verdict_only_matches_host_directory_store(self):
        # The with_remaining=False path ships bit-packed verdicts (the
        # u8[K, 2, B//8] bit-planes) — its grants must equal both the
        # host-directory store's and its own with_remaining=True path
        # (same kernel, different result encoding).
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=1024, clock=clock)
            full = FingerprintBucketStore(n_slots=1024, clock=clock)
            oracle = DeviceBucketStore(n_slots=1024, clock=clock)
            rng = np.random.default_rng(11)
            keys = [f"k{i}" for i in rng.integers(0, 40, 300)]
            counts = rng.integers(0, 4, 300).tolist()
            got = await store.acquire_many(keys, counts, 5.0, 1.0,
                                           with_remaining=False)
            ref = await full.acquire_many(keys, counts, 5.0, 1.0)
            want = await oracle.acquire_many(keys, counts, 5.0, 1.0,
                                             with_remaining=False)
            assert got.remaining is None
            np.testing.assert_array_equal(got.granted, want.granted)
            np.testing.assert_array_equal(got.granted, ref.granted)
            await store.aclose()
            await full.aclose()
            await oracle.aclose()

        run(main())

    def test_bulk_negative_count_stays_valid_row(self):
        # pack_fp12 clamps counts on BOTH sides: a negative ask must stay
        # a valid row (kernel grants count<=0 like every other path), not
        # wrap into uint32 sign-bit range and read as a padding row.
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock)
            res = await store.acquire_many(["neg", "pos"], [-1, 1],
                                           5.0, 0.0)
            assert bool(res.granted[0]) and bool(res.granted[1])
            # The clamped ask consumed nothing: the bucket is still full,
            # so a full-capacity ask grants and the next one is denied —
            # i.e. the row resolved into a real bucket, not padding.
            res2 = await store.acquire_many(["neg", "neg"], [5, 1],
                                            5.0, 0.0,
                                            with_remaining=False)
            assert bool(res2.granted[0]) and not bool(res2.granted[1])
            await store.aclose()

        run(main())

    def test_bulk_verdict_only_odd_max_batch(self):
        # max_batch not divisible by 8 cannot use bit-planes; the path
        # must fall back to the f32 fused result instead of crashing
        # (classic-store guard parity, store.py bits path).
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=1024, max_batch=60,
                                           clock=clock)
            oracle = DeviceBucketStore(n_slots=1024, clock=clock)
            rng = np.random.default_rng(13)
            # Distinct keys: the in-batch duplicate-serialization rule is
            # batch-boundary-dependent, and max_batch=60 chunks batches
            # differently from the oracle's default — duplicates would
            # legitimately diverge.
            keys = [f"k{i}" for i in range(150)]
            counts = rng.integers(0, 4, 150).tolist()
            got = await store.acquire_many(keys, counts, 5.0, 1.0,
                                           with_remaining=False)
            want = await oracle.acquire_many(keys, counts, 5.0, 1.0,
                                             with_remaining=False)
            assert got.remaining is None
            np.testing.assert_array_equal(got.granted, want.granted)
            await store.aclose()
            await oracle.aclose()

        run(main())

    def test_bulk_distinct_keys_match_exact_oracle(self):
        # With no in-call duplicates the decisions are exact — the serial
        # InProcess oracle applies directly.
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=1024, clock=clock)
            oracle = InProcessBucketStore(clock=clock)
            rng = np.random.default_rng(11)
            keys = [f"k{i}" for i in range(300)]
            counts = rng.integers(0, 7, 300).tolist()
            got = await store.acquire_many(keys, counts, 5.0, 1.0)
            want = await oracle.acquire_many(keys, counts, 5.0, 1.0)
            np.testing.assert_array_equal(got.granted, want.granted)
            np.testing.assert_allclose(got.remaining, want.remaining,
                                       atol=1e-4)
            await store.aclose()

        run(main())

    def test_bulk_duplicate_serialization(self):
        async def main():
            store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            res = await store.acquire_many(["hot"] * 8, [1] * 8, 5.0, 0.0)
            assert list(res.granted) == [True] * 5 + [False] * 3
            await store.aclose()

        run(main())

    def test_peek_and_blocking(self):
        store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
        assert store.peek_blocking("fresh", 9.0, 1.0) == 9.0
        r = store.acquire_blocking("fresh", 4, 9.0, 1.0)
        assert r.granted and r.remaining == pytest.approx(5.0)
        assert store.peek_blocking("fresh", 9.0, 1.0) == 5.0
        run(store.aclose())

    def test_pressure_grows_table_and_keeps_state(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=64, clock=clock,
                                           probe_window=8)
            table = store._table(5.0, 0.0)
            # Consume 2 of 5 on a marker key, then slam enough distinct
            # keys to exceed the probe windows → pressure → grow.
            assert (await store.acquire("marker", 2, 5.0, 0.0)).granted
            keys = [f"f{i}" for i in range(200)]
            res = await store.acquire_many(keys, [1] * 200, 5.0, 0.0)
            assert store.metrics.fp_unresolved > 0
            assert table.n_slots >= 128  # at least one doubling
            # Marker's consumption survived the device-side rehash.
            assert store.peek_blocking("marker", 5.0, 0.0) == 3.0
            # Deny-and-heal converges: each pressured call sweeps/grows,
            # so within a few retries every key is placeable and grants.
            for _ in range(3):
                res = await store.acquire_many(keys, [1] * 200, 5.0, 0.0)
                if res.granted.all():
                    break
            assert res.granted.all()
            assert table.n_slots >= 256
            await store.aclose()

        run(main())

    def test_snapshot_restore_roundtrip(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock)
            for i in range(10):
                await store.acquire(f"k{i}", 2, 5.0, 1.0)
            snap = store.snapshot()
            fresh = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            fresh.restore(snap)
            res = await fresh.acquire_many(
                [f"k{i}" for i in range(10)], [4] * 10, 5.0, 1.0)
            assert not res.granted.any()  # 3 left of 5 per key
            await store.aclose()
            await fresh.aclose()

        run(main())

    def test_restore_replaces_legacy_wrapping_placement(self):
        # Pre-v2 snapshots placed entries at base = mix(fp) % n (wrapping
        # window). Restoring one must RE-PLACE entries through the
        # migrate kernel, not install the table verbatim — under today's
        # non-wrapping base = mix(fp) % (n - L + 1) most legacy positions
        # are invisible to the probe, and their state would silently
        # reset.
        async def main():
            from distributedratelimiting.redis_tpu.runtime.fp_store import (
                fingerprints,
            )

            n = 256
            keys = [f"legacy{i}" for i in range(20)]
            fps = fingerprints(keys)
            h = (fps[:, 0] * np.uint32(0x9E3779B1)) ^ fps[:, 1]
            base_old = h % np.uint32(n)
            fp_tab = np.zeros((n, 2), np.uint32)
            tokens = np.zeros((n,), np.float32)
            last_ts = np.zeros((n,), np.int32)
            exists = np.zeros((n,), bool)
            for i, b in enumerate(base_old):
                assert not fp_tab[b].any(), "test keys must not collide"
                fp_tab[b] = fps[i]       # sparse table: old code placed
                tokens[b] = float(i)     # each key at its window's base
                exists[b] = True
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=n, clock=clock)
            store.acquire_blocking("warm", 1, 100.0, 0.0)  # make the table
            snap = store.snapshot()
            key0 = next(iter(snap["tables"]))
            legacy = {"fp": fp_tab, "probe_window": 16,  # no "placement":
                      "tokens": tokens, "last_ts": last_ts,  # a v1 form
                      "exists": exists}
            snap["tables"] = {key0: legacy}
            store.restore(snap)
            for i, k in enumerate(keys):
                got = store.peek_blocking(k, 100.0, 0.0)
                assert got == float(int(i)), (k, got, i)
            await store.aclose()

        run(main())

    def test_restore_adopts_snapshot_probe_window(self):
        # A key placed deep in a 16-cell window must stay visible after
        # restoring into a store configured with a narrower window — the
        # snapshot's geometry wins (else deep entries are orphaned and
        # their consumption forgotten).
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock,
                                           probe_window=16)
            for i in range(40):
                await store.acquire(f"k{i}", 2, 5.0, 0.0)
            snap = store.snapshot()
            narrow = FingerprintBucketStore(n_slots=256, clock=ManualClock(),
                                            probe_window=4)
            narrow.restore(snap)
            assert narrow._table(5.0, 0.0).probe_window == 16
            res = await narrow.acquire_many(
                [f"k{i}" for i in range(40)], [4] * 40, 5.0, 0.0)
            assert not res.granted.any()  # consumption all remembered
            await store.aclose()
            await narrow.aclose()

        run(main())

    def test_cross_type_restore_rejected(self):
        async def main():
            host_store = DeviceBucketStore(n_slots=256, clock=ManualClock())
            await host_store.acquire("k", 1, 5.0, 1.0)
            snap = host_store.snapshot()
            fp_store = FingerprintBucketStore(n_slots=256,
                                              clock=ManualClock())
            with pytest.raises(ValueError, match="host key directory"):
                fp_store.restore(snap)
            fp_store2 = FingerprintBucketStore(n_slots=256,
                                               clock=ManualClock())
            await fp_store2.acquire("k", 1, 5.0, 1.0)
            snap2 = fp_store2.snapshot()
            host_store2 = DeviceBucketStore(n_slots=256, clock=ManualClock())
            with pytest.raises(ValueError, match="fingerprint"):
                host_store2.restore(snap2)
            for s in (host_store, fp_store, fp_store2, host_store2):
                await s.aclose()

        run(main())

    def test_aux_tiers_inherited(self):
        async def main():
            store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            # Windows, counters, semaphores ride the parent store.
            assert (await store.window_acquire("w", 1, 3.0, 10.0)).granted
            r = await store.sync_counter("c", 5.0, 0.0)
            assert r.global_score == pytest.approx(5.0)
            assert (await store.concurrency_acquire("s", 1, 2)).granted
            await store.concurrency_release("s", 1)
            await store.aclose()

        run(main())

    def test_window_tier_uses_fingerprint_directory(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock)
            # Sliding window limit 3 per 10s.
            got = [(await store.window_acquire("w", 1, 3.0, 10.0)).granted
                   for _ in range(5)]
            assert got == [True] * 3 + [False] * 2
            # Window table really is fingerprint-backed (no host dir).
            wt = store._wtable(3.0, 10.0)
            assert not hasattr(wt, "dir")
            assert int((np.asarray(wt.fp) != 0).any(-1).sum()) == 1
            # New window ⇒ interpolated estimate decays.
            clock.advance_seconds(15.0)
            assert (await store.window_acquire("w", 1, 3.0, 10.0)).granted
            await store.aclose()

        run(main())

    def test_window_bulk_matches_host_directory_store(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=1024, clock=clock)
            oracle = DeviceBucketStore(n_slots=1024, clock=clock)
            rng = np.random.default_rng(5)
            keys = [f"w{i}" for i in rng.integers(0, 50, 300)]
            counts = rng.integers(0, 3, 300).tolist()
            for fixed in (False, True):
                got = await store.window_acquire_many(
                    keys, counts, 4.0, 10.0, fixed=fixed)
                want = await oracle.window_acquire_many(
                    keys, counts, 4.0, 10.0, fixed=fixed)
                np.testing.assert_array_equal(got.granted, want.granted)
                np.testing.assert_allclose(got.remaining, want.remaining,
                                           atol=1e-4)
            await store.aclose()
            await oracle.aclose()

        run(main())

    def test_window_bulk_verdict_only_matches_full_path(self):
        # The window-family bit-plane path (with_remaining=False through
        # fp_window_acquire_scan_fused_bits) must grant identically to
        # the f32 fused path and the host-directory oracle, for both
        # sliding and fixed windows.
        async def main():
            clock = ManualClock()
            rng = np.random.default_rng(17)
            keys = [f"w{i}" for i in rng.integers(0, 50, 300)]
            counts = rng.integers(0, 3, 300).tolist()
            for fixed in (False, True):
                store = FingerprintBucketStore(n_slots=1024, clock=clock)
                full = FingerprintBucketStore(n_slots=1024, clock=clock)
                oracle = DeviceBucketStore(n_slots=1024, clock=clock)
                got = await store.window_acquire_many(
                    keys, counts, 4.0, 10.0, fixed=fixed,
                    with_remaining=False)
                ref = await full.window_acquire_many(
                    keys, counts, 4.0, 10.0, fixed=fixed)
                want = await oracle.window_acquire_many(
                    keys, counts, 4.0, 10.0, fixed=fixed,
                    with_remaining=False)
                assert got.remaining is None
                np.testing.assert_array_equal(got.granted, ref.granted)
                np.testing.assert_array_equal(got.granted, want.granted)
                await store.aclose()
                await full.aclose()
                await oracle.aclose()

        run(main())

    def test_window_growth_preserves_state(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=64, clock=clock,
                                           probe_window=8)
            wt = store._wtable(5.0, 60.0)
            # Consume 4 of 5 on a marker key, then flood distinct keys.
            r = await store.window_acquire_many(["wm"], [4], 5.0, 60.0)
            assert r.granted.all()
            keys = [f"wf{i}" for i in range(200)]
            for _ in range(4):
                res = await store.window_acquire_many(
                    keys, [1] * 200, 5.0, 60.0)
                if res.granted.all():
                    break
            assert res.granted.all()
            assert wt.n_slots >= 256
            # Marker's 4-of-5 consumption survived the window rehash.
            r2 = await store.window_acquire_many(["wm"], [2], 5.0, 60.0)
            assert not r2.granted.any()
            await store.aclose()

        run(main())

    def test_window_snapshot_roundtrip_and_cross_type(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=256, clock=clock)
            await store.window_acquire("w", 3, 5.0, 60.0)
            snap = store.snapshot()
            fresh = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            fresh.restore(snap)
            r = await fresh.window_acquire("w", 3, 5.0, 60.0)
            assert not r.granted  # 3 of 5 already consumed pre-snapshot
            host = DeviceBucketStore(n_slots=256, clock=ManualClock())
            with pytest.raises(ValueError, match="fingerprint"):
                host.restore(snap)
            await store.aclose()
            await fresh.aclose()
            await host.aclose()

        run(main())

    def test_concurrent_mixed_traffic_with_growth(self):
        # Race posture: async micro-batched acquires + blocking bulk calls
        # from threads + growth pressure, all against one table. The
        # donated-buffer discipline (launches under store._lock) must hold:
        # no "Array has been deleted", no lost state, aggregate
        # conservation (a cap-K key never grants more than K + refill).
        import threading

        async def main():
            store = FingerprintBucketStore(n_slots=128, clock=ManualClock(),
                                           probe_window=8)
            errors = []

            def bulk_worker(w):
                try:
                    keys = [f"b{w}-{i}" for i in range(150)]
                    for _ in range(3):
                        store.acquire_many_blocking(keys, [1] * 150, 5.0, 0.0)
                except Exception as exc:  # pragma: no cover
                    errors.append(exc)

            threads = [threading.Thread(target=bulk_worker, args=(w,))
                       for w in range(3)]
            for t in threads:
                t.start()
            granted_hot = 0
            for _ in range(40):
                r = await store.acquire("hot", 1, 10.0, 0.0)
                granted_hot += int(r.granted)
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            assert granted_hot == 10  # cap-10, zero refill: exactly 10
            # Table grew under the 450-distinct-key pressure and survived.
            assert store._table(5.0, 0.0).n_slots > 128
            await store.aclose()

        run(main())

    def test_limiter_integration(self):
        from distributedratelimiting.redis_tpu.models.options import (
            TokenBucketOptions,
        )
        from distributedratelimiting.redis_tpu.models.token_bucket import (
            TokenBucketRateLimiter,
        )

        async def main():
            store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            limiter = TokenBucketRateLimiter(
                TokenBucketOptions(token_limit=3, tokens_per_period=1,
                                   instance_name="api"), store)
            got = [(await limiter.acquire_async(1)).is_acquired
                   for _ in range(5)]
            assert got == [True] * 3 + [False] * 2
            await store.aclose()

        run(main())


def _blob(keys):
    """The native bulk lane's key form (``wire.KeyBlob``) of ``keys``."""
    from distributedratelimiting.redis_tpu.runtime.wire import KeyBlob

    raw = [k.encode("utf-8", "surrogateescape") for k in keys]
    offsets = np.zeros(len(raw) + 1, np.int64)
    offsets[1:] = np.cumsum([len(r) for r in raw])
    return KeyBlob(b"".join(raw), offsets)


class TestServedStages:
    """The served path's host stages, one sample per store call, as the
    host-directory tables record them (runtime/store.py)."""

    def test_bulk_call_records_one_sample_per_stage(self):
        async def main():
            store = FingerprintBucketStore(n_slots=1024, clock=ManualClock())
            m = store.metrics
            keys = [f"k{i}" for i in range(100)]
            for i in range(3):
                res = await store.acquire_many(
                    _blob(keys) if i % 2 else keys, [1] * 100, 5.0, 1.0,
                    with_remaining=bool(i % 2))
                assert res.granted.all()
                assert m.store_host.total == m.store_readback.total == i + 1
            assert m.store_host.sum_s > 0.0 and m.store_readback.sum_s > 0.0
            await store.aclose()

        run(main())

    def test_flush_records_one_sample_per_stage(self):
        async def main():
            store = FingerprintBucketStore(n_slots=256, clock=ManualClock())
            m = store.metrics
            for i in range(3):
                assert (await store.acquire(f"k{i}", 1, 3.0, 1.0)).granted
                assert m.store_host.total == m.store_readback.total == i + 1
            assert m.flush_latency.total == 3
            await store.aclose()

        run(main())

    def test_untraced_store_opens_no_span(self, monkeypatch):
        """Without a profiler trace the served calls construct no trace
        annotation: each span is the shared no-op after one flag read."""
        from jax.profiler import TraceAnnotation

        from distributedratelimiting.redis_tpu.utils import tracing

        opened = []

        class Recording(TraceAnnotation):
            def __init__(self, name, **kw):
                opened.append(name)
                super().__init__(name, **kw)

        monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", Recording)

        async def main():
            store = FingerprintBucketStore(n_slots=1024, clock=ManualClock())
            keys = [f"k{i}" for i in range(64)]
            await store.acquire_many(_blob(keys), [1] * 64, 5.0, 1.0)
            await store.acquire("one", 1, 5.0, 1.0)
            assert store.metrics.store_host.total == 2
            await store.aclose()

        run(main())
        assert opened == []


class TestServedKeyForm:
    """The key form the native bulk lane hands the store, and the key
    space the benchmark's ``token-bucket-10m-fp`` configuration serves."""

    def test_keyblob_fingerprints_match_list_and_python(self):
        from distributedratelimiting.redis_tpu.runtime import fp_store

        keys = ["a", "user:42", "ключ-🔑", "", "x" * 300,
                "api:user00000000", "api:user09999999"]
        want = fingerprints(keys)
        np.testing.assert_array_equal(fingerprints(_blob(keys)), want)
        for i, k in enumerate(keys):
            h = fp_store._fp64_py(k)
            assert (int(want[i, 0]), int(want[i, 1])) == (h & 0xFFFFFFFF,
                                                          h >> 32)

    def test_keyblob_bulk_decides_like_in_process_store(self):
        async def main():
            clock = ManualClock()
            store = FingerprintBucketStore(n_slots=4096, clock=clock)
            oracle = InProcessBucketStore(clock=clock)
            rng = np.random.default_rng(11)
            for _ in range(6):
                idx = rng.choice(500, 256, replace=False)
                keys = [f"api:user{i:08d}" for i in idx]
                counts = rng.integers(0, 4, 256).tolist()
                got = await store.acquire_many(_blob(keys), counts, 5.0, 2.0)
                want = await oracle.acquire_many(keys, counts, 5.0, 2.0)
                np.testing.assert_array_equal(got.granted, want.granted)
                np.testing.assert_allclose(got.remaining, want.remaining,
                                           atol=1e-4)
                clock.advance_seconds(0.7)
            assert store.metrics.fp_unresolved == 0
            await store.aclose()
            await oracle.aclose()

        run(main())

    def test_benchmark_key_space_fingerprints_are_distinct(self):
        """Every key of ``token-bucket-10m-fp`` has its own 64-bit
        fingerprint, so two keys never share a bucket there and the
        store's answers are exactly the token bucket's: the precondition
        that configuration states. FNV-1a-64 in numpy over the fixed-width
        names, checked against ``_fp64_py`` and the native pass."""
        import json
        import pathlib

        from distributedratelimiting.redis_tpu.runtime import fp_store

        cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                          / "perfbench" / "configs"
                          / "token-bucket-10m-fp.json").read_text())
        spec = cfg["keys"]
        n, digits = int(spec["count"]), int(spec["digits"])
        prefix = spec["prefix"].encode()
        idx = np.arange(n, dtype=np.uint64)
        h = np.full(n, fp_store._FNV_OFFSET, np.uint64)
        prime = np.uint64(fp_store._FNV_PRIME)
        for byte in prefix:
            h ^= np.uint64(byte)
            h *= prime
        for d in range(digits - 1, -1, -1):
            h ^= (idx // np.uint64(10 ** d)) % np.uint64(10) + np.uint64(48)
            h *= prime
        h[h == 0] = np.uint64(fp_store._FNV_OFFSET)
        sample = [0, 1, 12_345, n // 2, n - 1]
        names = [f"{spec['prefix']}{i:0{digits}d}" for i in sample]
        native = fingerprints(names)
        for j, (i, name) in enumerate(zip(sample, names)):
            assert int(h[i]) == fp_store._fp64_py(name)
            assert int(h[i]) == int(native[j, 0]) | int(native[j, 1]) << 32
        assert len(np.unique(h)) == n


class TestOneCrossingPerLaunch:
    """The fingerprint store's decision launches hand their kernels host
    (numpy) operands: jit copies them up inside the call, with no
    ``jnp.asarray`` or ``jax.device_put`` before it."""

    @pytest.mark.parametrize("with_remaining", [True, False])
    def test_bulk_call_sends_no_separate_transfer(self, transfers,
                                                  with_remaining):
        store = FingerprintBucketStore(n_slots=2**15, clock=ManualClock())
        keys = [f"k{i}" for i in range(4096)]

        async def main():
            await store.acquire_many(keys, [1] * 4096, 5.0, 1.0,
                                     with_remaining=with_remaining)
            launches = store.metrics.launches
            transfers.clear()
            res = await store.acquire_many(keys, [1] * 4096, 5.0, 1.0,
                                           with_remaining=with_remaining)
            assert transfers == []
            assert res.granted.all()
            assert store.metrics.launches == launches + 1
            await store.aclose()

        run(main())
        assert store.metrics.fp_unresolved == 0
        assert store.metrics.dispatch_transfers == 0

    def test_scalar_flush_sends_no_separate_transfer(self, transfers):
        store = FingerprintBucketStore(n_slots=1024, clock=ManualClock(),
                                       max_delay_s=0.005)

        async def main():
            await store.acquire("warm", 1, 5.0, 1.0)
            launches = store.metrics.launches
            transfers.clear()
            got = await asyncio.gather(
                *(store.acquire("hot", 1, 3.0, 1.0) for _ in range(8)))
            assert transfers == []
            assert [r.granted for r in got] == [True] * 3 + [False] * 5
            assert store.metrics.launches > launches
            await store.aclose()

        run(main())
        assert store.metrics.dispatch_transfers == 0

    def test_numpy_operands_decide_like_device_operands(self):
        """Seeded bulk calls with duplicate keys, mixed counts, counts
        above 255 and zero-permit probes: the same grants and remainders
        as a table that sends each operand up with ``jnp.asarray``."""
        from distributedratelimiting.redis_tpu.runtime import fp_store

        class AsarrayTable(fp_store._FpTable):
            def _call_scan_fused(self, fused, nows):
                return super()._call_scan_fused(jnp.asarray(fused),
                                                jnp.asarray(nows))

            def _call_scan_fused_bits(self, fused, nows):
                return super()._call_scan_fused_bits(jnp.asarray(fused),
                                                     jnp.asarray(nows))

        class AsarrayStore(FingerprintBucketStore):
            _TABLE_CLS = AsarrayTable

        clock = ManualClock()
        store = FingerprintBucketStore(n_slots=1024, clock=clock)
        ref = AsarrayStore(n_slots=1024, clock=clock)
        g = np.random.default_rng(29)
        for _ in range(4):
            n = int(g.integers(200, 600))
            keys = [f"k{i}" for i in g.integers(0, 120, n)]
            counts = [int(c) for c in g.choice([0, 1, 2, 7, 300], n)]
            for with_remaining in (True, False):
                got = store.acquire_many_blocking(
                    keys, counts, 400.0, 50.0, with_remaining=with_remaining)
                want = ref.acquire_many_blocking(
                    keys, counts, 400.0, 50.0, with_remaining=with_remaining)
                np.testing.assert_array_equal(got.granted, want.granted)
                if with_remaining:
                    np.testing.assert_array_equal(got.remaining,
                                                  want.remaining)
                assert got.granted.any() and not got.granted.all()
            clock.advance_seconds(0.5)
        assert store.metrics.fp_unresolved == 0
        assert store.metrics.dispatch_transfers == 0
