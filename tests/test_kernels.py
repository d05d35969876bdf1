"""L1 kernel tests: the jitted batch kernels vs a serial Python simulator.

The simulator replays the reference's Lua semantics one request at a time
(``RedisTokenBucketRateLimiter.cs:176-239``); the batch kernel must agree on
every grant/state when batches are duplicate-free, and must never over-admit
when they are not (conservative in-batch serialization).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedratelimiting.redis_tpu.ops import bucket_math as bm
from distributedratelimiting.redis_tpu.ops import kernels as K

TPS = bm.TICKS_PER_SECOND


class SerialBucketSim:
    """Pure-Python serial replay of the exact-bucket Lua kernel."""

    def __init__(self, n, capacity, fill_rate_per_tick):
        self.tokens = np.zeros(n)
        self.last_ts = np.zeros(n, np.int64)
        self.exists = np.zeros(n, bool)
        self.cap = capacity
        self.rate = fill_rate_per_tick

    def acquire(self, slot, count, now):
        if not self.exists[slot]:
            refilled = self.cap
        else:
            delta = max(0, now - self.last_ts[slot])
            refilled = min(self.cap, self.tokens[slot] + delta * self.rate)
        granted = refilled >= count
        self.tokens[slot] = refilled - (count if granted else 0)
        self.last_ts[slot] = now
        self.exists[slot] = True
        return granted


def run_batch(state, slots, counts, now, cap, rate, handle_duplicates=True):
    b = len(slots)
    return K.acquire_batch(
        state,
        jnp.asarray(slots, jnp.int32),
        jnp.asarray(counts, jnp.int32),
        jnp.ones((b,), bool),
        jnp.asarray(now, jnp.int32),
        jnp.float32(cap),
        jnp.float32(rate),
        handle_duplicates=handle_duplicates,
    )


class TestAcquireBatch:
    def test_matches_serial_sim_unique_slots(self, rng):
        n, cap, rate = 64, 20.0, 4.0 / TPS
        state = K.init_bucket_state(n)
        sim = SerialBucketSim(n, cap, rate)
        now = 0
        for _ in range(30):
            now += int(rng.integers(0, TPS))
            batch = rng.choice(n, size=16, replace=False)
            counts = rng.integers(0, 8, size=16)
            state, granted, remaining = run_batch(state, batch, counts, now, cap, rate)
            granted = np.asarray(granted)
            for s, c, g in zip(batch, counts, granted):
                assert sim.acquire(s, c, now) == g, (s, c, now)
            np.testing.assert_allclose(
                np.asarray(state.tokens)[batch], sim.tokens[batch], atol=1e-2
            )

    def test_duplicates_never_over_admit(self, rng):
        # Many requests to few slots in one batch: total granted per slot
        # must fit within that slot's refilled balance (invariant 3 at batch
        # granularity), regardless of grant pattern.
        n, cap, rate = 8, 10.0, 0.0
        for trial in range(10):
            state = K.init_bucket_state(n)
            slots = rng.integers(0, n, size=64)
            counts = rng.integers(1, 6, size=64)
            state, granted, _ = run_batch(state, slots, counts, 1, cap, rate)
            granted = np.asarray(granted)
            for s in range(n):
                m = slots == s
                assert counts[m][granted[m]].sum() <= cap

    def test_duplicates_serialize_in_batch_order(self):
        # capacity 10, zero rate: requests [6, 6, 3] to one slot →
        # serial order grants 6, denies 6, conservative prefix denies 3 too
        # (prefix counts the denied 6) — allowed to under-admit, never over.
        state = K.init_bucket_state(4)
        state, granted, _ = run_batch(state, [2, 2, 2], [6, 6, 3], 1, 10.0, 0.0)
        g = list(np.asarray(granted))
        assert g[0] is np.True_
        assert g[1] is np.False_
        assert float(state.tokens[2]) == 4.0

    def test_padding_rows_untouched(self):
        state = K.init_bucket_state(4)
        b = 4
        state, granted, remaining = K.acquire_batch(
            state,
            jnp.asarray([1, -1, 2, -1], jnp.int32),
            jnp.asarray([3, 5, 2, 7], jnp.int32),
            jnp.asarray([True, False, True, False]),
            jnp.int32(10),
            jnp.float32(10.0),
            jnp.float32(0.0),
        )
        assert list(np.asarray(granted)) == [True, False, True, False]
        assert not bool(state.exists[0]) and not bool(state.exists[3])
        assert bool(state.exists[1]) and bool(state.exists[2])

    def test_fast_path_no_duplicates_flag(self, rng):
        n, cap, rate = 32, 15.0, 2.0 / TPS
        s1 = K.init_bucket_state(n)
        s2 = K.init_bucket_state(n)
        slots = rng.choice(n, size=8, replace=False)
        counts = rng.integers(0, 6, size=8)
        s1, g1, r1 = run_batch(s1, slots, counts, 100, cap, rate, True)
        s2, g2, r2 = run_batch(s2, slots, counts, 100, cap, rate, False)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_allclose(np.asarray(s1.tokens), np.asarray(s2.tokens))


class TestSyncBatch:
    def test_decay_add_and_ewma(self):
        state = K.init_counter_state(4)
        decay = 2.0 / TPS
        # First sync at t=TPS: init-on-miss, v = count, p = now (epoch delta).
        state, v, p = K.sync_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([6.0], jnp.float32),
            jnp.asarray([True]), jnp.int32(TPS), jnp.float32(decay),
        )
        assert float(v[0]) == 6.0
        assert float(p[0]) == TPS
        # Second sync 2 s later: v = max(0, 6 - 4) + 5 = 7.
        state, v, p = K.sync_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([5.0], jnp.float32),
            jnp.asarray([True]), jnp.int32(3 * TPS), jnp.float32(decay),
        )
        assert np.isclose(float(v[0]), 7.0)
        assert np.isclose(float(p[0]), 0.8 * TPS + 0.2 * 2 * TPS)
        assert float(state.value[1]) == float(v[0])

    def test_independent_counters(self):
        state = K.init_counter_state(8)
        state, v, _ = K.sync_batch(
            state, jnp.asarray([0, 5], jnp.int32),
            jnp.asarray([3.0, 9.0], jnp.float32),
            jnp.asarray([True, True]), jnp.int32(10), jnp.float32(0.0),
        )
        assert list(np.asarray(v)) == [3.0, 9.0]
        assert float(state.value[5]) == 9.0


class TestWindowAcquireBatch:
    W = 10 * TPS

    def test_grant_then_deny_at_limit(self):
        state = K.init_window_state(4)
        state, g, r = K.window_acquire_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([8], jnp.int32),
            jnp.asarray([True]), jnp.int32(1), jnp.float32(10.0),
            jnp.int32(self.W),
        )
        assert bool(g[0])
        state, g, r = K.window_acquire_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([5], jnp.int32),
            jnp.asarray([True]), jnp.int32(2), jnp.float32(10.0),
            jnp.int32(self.W),
        )
        assert not bool(g[0])

    def test_window_rolloff_readmits(self):
        state = K.init_window_state(4)
        state, g, _ = K.window_acquire_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([10], jnp.int32),
            jnp.asarray([True]), jnp.int32(1), jnp.float32(10.0),
            jnp.int32(self.W),
        )
        assert bool(g[0])
        # Two full windows later the old consumption is gone entirely.
        state, g, _ = K.window_acquire_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([10], jnp.int32),
            jnp.asarray([True]), jnp.int32(2 * self.W + 1), jnp.float32(10.0),
            jnp.int32(self.W),
        )
        assert bool(g[0])


class TestSweep:
    def test_evicts_idle_full_buckets_only(self):
        cap, rate = 10.0, 1.0 / TPS  # time-to-full from empty = 10 s
        state = K.init_bucket_state(4)
        # Slot 1 drained at t=0; slot 2 untouched (doesn't exist).
        state, g, _ = run_batch(state, [1], [10], 0, cap, rate)
        assert bool(g[0])
        # 5 s later: not yet refillable to full → kept.
        state, freed = K.sweep_expired(
            state, jnp.int32(5 * TPS), jnp.float32(cap), jnp.float32(rate)
        )
        assert not bool(freed[1]) and bool(state.exists[1])
        # 10 s later: bucket would be full → evicted.
        state, freed = K.sweep_expired(
            state, jnp.int32(10 * TPS), jnp.float32(cap), jnp.float32(rate)
        )
        assert bool(freed[1]) and not bool(state.exists[1])
        assert not bool(freed[2])

    def test_evicted_slot_reinitializes_full(self):
        cap, rate = 10.0, 1.0 / TPS
        state = K.init_bucket_state(4)
        state, _, _ = run_batch(state, [1], [10], 0, cap, rate)
        state, _ = K.sweep_expired(
            state, jnp.int32(20 * TPS), jnp.float32(cap), jnp.float32(rate)
        )
        # Init-on-miss semantics: next touch sees a full bucket.
        state, g, _ = run_batch(state, [1], [10], 20 * TPS + 1, cap, rate)
        assert bool(g[0])


class TestPeek:
    def test_readonly_estimate(self):
        cap, rate = 10.0, 2.0 / TPS
        state = K.init_bucket_state(4)
        state, _, _ = run_batch(state, [1], [8], 0, cap, rate)
        est = K.peek_batch(
            state, jnp.asarray([1, 2], jnp.int32), jnp.asarray([True, True]),
            jnp.int32(2 * TPS), jnp.float32(cap), jnp.float32(rate),
        )
        assert float(est[0]) == 6.0   # 2 + 2*2
        assert float(est[1]) == 10.0  # missing key reads full
        # State unchanged by peek.
        assert float(state.tokens[1]) == 2.0


class TestSlotValidation:
    def test_out_of_range_slot_is_denied_not_phantom_granted(self):
        state = K.init_bucket_state(4)
        state, granted, _ = K.acquire_batch(
            state,
            jnp.asarray([7, 1], jnp.int32),  # 7 out of range for N=4
            jnp.asarray([1, 1], jnp.int32),
            jnp.asarray([True, True]),
            jnp.int32(0), jnp.float32(10.0), jnp.float32(0.0),
        )
        assert list(np.asarray(granted)) == [False, True]
        assert not bool(state.exists[3])  # no wrap/clamp write


class TestAuxSweepsAndRebase:
    def test_counter_sweep_86400s_ttl(self):
        state = K.init_counter_state(4)
        state, _, _ = K.sync_batch(
            state, jnp.asarray([2], jnp.int32), jnp.asarray([5.0], jnp.float32),
            jnp.asarray([True]), jnp.int32(0), jnp.float32(0.0),
        )
        state, freed = K.sweep_counters(state, jnp.int32(bm.GLOBAL_COUNTER_TTL_TICKS))
        assert bool(freed[2]) and not bool(state.exists[2])

    def test_window_sweep_two_idle_windows(self):
        W = 10 * TPS
        state = K.init_window_state(4)
        state, g, _ = K.window_acquire_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([1], jnp.int32),
            jnp.asarray([True]), jnp.int32(1), jnp.float32(10.0), jnp.int32(W),
        )
        state, freed = K.sweep_windows(state, jnp.int32(W + 1), jnp.int32(W))
        assert not bool(freed[1])
        state, freed = K.sweep_windows(state, jnp.int32(2 * W + 1), jnp.int32(W))
        assert bool(freed[1]) and not bool(state.exists[1])

    def test_epoch_rebase_preserves_elapsed(self):
        cap, rate = 10.0, 1.0 / TPS
        state = K.init_bucket_state(4)
        state, _, _ = run_batch(state, [1], [10], 5 * TPS, cap, rate)
        # Rebase both the table and the caller's clock by 4 s.
        state = K.rebase_bucket_epoch(state, jnp.int32(4 * TPS))
        # 3 s of refill measured in the new epoch: now = (5-4)+3 = 4 s.
        est = K.peek_batch(
            state, jnp.asarray([1], jnp.int32), jnp.asarray([True]),
            jnp.int32(4 * TPS), jnp.float32(cap), jnp.float32(rate),
        )
        assert float(est[0]) == 3.0


def _scan_out(out):
    """``(granted, remaining)`` of a scan kernel's ``f32[K, 2, B]``."""
    out = np.asarray(out)
    return out[:, 0] > 0.5, out[:, 1]


def _bits(granted):
    return np.packbits(granted, axis=-1, bitorder="little")


def _rows8(slots, counts):
    """The 8-byte row layout of the same rows, whatever the counts."""
    return np.concatenate(
        [K.pack_rows(slots, np.zeros_like(counts, np.uint8))[..., :4],
         np.asarray(counts).astype("<i4")[..., None].view(np.uint8)],
        axis=-1)


class TestAcquireScanCompact:
    def test_matches_sequential_batches(self):
        rng = np.random.default_rng(7)
        n, b, k = 128, 32, 4
        slots = rng.integers(0, n, (k, b)).astype(np.int32)
        slots[0, :3] = 5  # in-batch duplicates
        counts = rng.integers(1, 4, (k, b)).astype(np.uint8)
        nows = np.arange(1, k + 1, dtype=np.int32) * 10

        s1 = K.init_bucket_state(n)
        s1, out = K.acquire_scan(
            s1, jnp.asarray(K.pack_rows(slots, counts)), jnp.asarray(nows),
            jnp.float32(6.0), jnp.float32(0.5))
        granted, remaining = _scan_out(out)

        s2 = K.init_bucket_state(n)
        for i in range(k):
            s2, g2, r2 = K.acquire_batch(
                s2, jnp.asarray(slots[i]), jnp.asarray(counts[i], jnp.int32),
                jnp.ones((b,), bool), jnp.int32(nows[i]), jnp.float32(6.0),
                jnp.float32(0.5))
            np.testing.assert_array_equal(granted[i], np.asarray(g2))
            np.testing.assert_allclose(remaining[i], np.asarray(r2),
                                       rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s1.tokens),
                                   np.asarray(s2.tokens), rtol=1e-6)

    def test_negative_slot_is_padding(self):
        s = K.init_bucket_state(16)
        slots = np.array([[0, -1, 3]], np.int32)
        counts = np.ones((1, 3), np.uint8)
        s, out = K.acquire_scan(
            s, jnp.asarray(K.pack_rows(slots, counts)),
            jnp.asarray([1], np.int32), jnp.float32(5.0), jnp.float32(0.1))
        assert list(_scan_out(out)[0][0]) == [True, False, True]
        # Padding touched nothing: only slots 0 and 3 exist.
        assert list(np.nonzero(np.asarray(s.exists))[0]) == [0, 3]


class TestAcquireScanCompactFused:
    def test_matches_unfused(self):
        """5-byte and 8-byte rows of the same requests decide
        identically."""
        rng = np.random.default_rng(13)
        n, b, k = 70_000, 32, 4  # n > 2**16: exercises three slot bytes
        slots = rng.integers(0, n, (k, b)).astype(np.int32)
        slots[0, :3] = 5          # duplicates
        slots[1, :2] = -1         # padding rows
        counts = rng.integers(0, 255, (k, b)).astype(np.uint8)
        nows = np.arange(1, k + 1, dtype=np.int32) * 10

        rows5 = K.pack_rows(slots, counts)
        rows8 = _rows8(slots, counts)
        assert rows5.shape[-1] == 5 and rows8.shape[-1] == 8
        s1 = K.init_bucket_state(n)
        s1, out1 = K.acquire_scan(
            s1, jnp.asarray(rows5), jnp.asarray(nows),
            jnp.float32(200.0), jnp.float32(0.5))
        s2 = K.init_bucket_state(n)
        s2, out2 = K.acquire_scan(
            s2, jnp.asarray(rows8), jnp.asarray(nows),
            jnp.float32(200.0), jnp.float32(0.5))
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        for a, b_ in zip(s1, s2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_window_fused_bits_matches_packed(self):
        """The window verdict-only bit-packed form (window bulk calls with
        with_remaining=False) agrees bit for bit with the full result,
        for both window families."""
        rng = np.random.default_rng(19)
        n, b, k = 300, 64, 3
        slots = rng.integers(0, n, (k, b)).astype(np.int32)
        slots[1, :5] = -1
        counts = rng.integers(0, 3, (k, b)).astype(np.uint8)
        nows = np.arange(1, k + 1, dtype=np.int32) * 400
        rows = jnp.asarray(K.pack_rows(slots, counts))
        for interpolate in (True, False):
            s1 = K.init_window_state(n)
            s1, out = K.window_acquire_scan(
                s1, rows, jnp.asarray(nows), jnp.float32(3.0),
                jnp.int32(1024), interpolate=interpolate)
            s2 = K.init_window_state(n)
            s2, bits = K.window_acquire_scan(
                s2, rows, jnp.asarray(nows), jnp.float32(3.0),
                jnp.int32(1024), interpolate=interpolate, verdict_only=True)
            np.testing.assert_array_equal(np.asarray(bits),
                                          _bits(_scan_out(out)[0]))
            np.testing.assert_allclose(np.asarray(s1.curr_count),
                                       np.asarray(s2.curr_count), rtol=1e-6)

    def test_fused_bits_matches_compact_bits(self):
        """The bucket verdict-only form agrees with the full result."""
        rng = np.random.default_rng(17)
        n, b, k = 500, 64, 3
        slots = rng.integers(0, n, (k, b)).astype(np.int32)
        slots[2, :8] = -1
        counts = rng.integers(1, 4, (k, b)).astype(np.uint8)
        nows = np.arange(1, k + 1, dtype=np.int32)
        rows = jnp.asarray(K.pack_rows(slots, counts))
        s1 = K.init_bucket_state(n)
        s1, out = K.acquire_scan(s1, rows, jnp.asarray(nows),
                                 jnp.float32(4.0), jnp.float32(0.1))
        s2 = K.init_bucket_state(n)
        s2, bits = K.acquire_scan(s2, rows, jnp.asarray(nows),
                                  jnp.float32(4.0), jnp.float32(0.1),
                                  verdict_only=True)
        np.testing.assert_array_equal(np.asarray(bits),
                                      _bits(_scan_out(out)[0]))
        np.testing.assert_allclose(np.asarray(s1.tokens),
                                   np.asarray(s2.tokens), rtol=1e-6)

    def test_pack_compact5_layout(self):
        slots = np.array([0, 1, 255, 65536, (1 << 24) + 7, -1], np.int32)
        counts = np.array([1, 2, 3, 4, 5, 0], np.uint8)
        fused = K.pack_rows(slots, counts)
        assert fused.shape == (6, 5)
        # LE i32 reassembly from bytes 0-3, count in byte 4.
        back = fused[:, :4].copy().view("<i4").reshape(-1)
        np.testing.assert_array_equal(back, slots)
        np.testing.assert_array_equal(fused[:, 4], counts)
        # A count over a byte widens every row to an LE i32 count.
        wide = np.array([1, 2, 256, 70_000, 2**31 - 1, 0], np.int64)
        rows8 = K.pack_rows(slots, wide)
        assert rows8.shape == (6, 8)
        np.testing.assert_array_equal(
            rows8[:, :4].copy().view("<i4").reshape(-1), slots)
        np.testing.assert_array_equal(
            rows8[:, 4:].copy().view("<i4").reshape(-1), wide)


class TestWindowAcquireScanCompact:
    def test_matches_sequential_window_batches(self):
        rng = np.random.default_rng(13)
        n, b, k = 64, 16, 3
        slots = rng.integers(0, n, (k, b)).astype(np.int32)
        slots[0, 5:] = -1  # bursty: padding tail rows
        counts = rng.integers(1, 3, (k, b)).astype(np.uint8)
        nows = np.array([10, 40, 90], np.int32)

        s1 = K.init_window_state(n)
        s1, out = K.window_acquire_scan(
            s1, jnp.asarray(K.pack_rows(slots, counts)), jnp.asarray(nows),
            jnp.float32(4.0), jnp.int32(32))
        granted, _ = _scan_out(out)

        s2 = K.init_window_state(n)
        for i in range(k):
            s2, g2, _ = K.window_acquire_batch(
                s2, jnp.asarray(slots[i]), jnp.asarray(counts[i], jnp.int32),
                jnp.asarray(slots[i] >= 0), jnp.int32(nows[i]),
                jnp.float32(4.0), jnp.int32(32))
            np.testing.assert_array_equal(granted[i], np.asarray(g2))
        np.testing.assert_allclose(np.asarray(s1.curr_count),
                                   np.asarray(s2.curr_count), rtol=1e-6)


@pytest.mark.parametrize("verdict_only", [False, True])
@pytest.mark.parametrize("width", [5, 8])
@pytest.mark.parametrize("kind", ["bucket", "sliding", "fixed"])
def test_scan_matches_sequential_batches(kind, width, verdict_only):
    """Each scan kernel, in each row width and verdict form, decides K
    batches as K sequential batch calls do: grants, remaining and the
    table, with in-batch duplicates, padding rows and zero-permit
    probes."""
    rng = np.random.default_rng(29)
    n, b, k = 70_000, 32, 4
    slots = rng.integers(0, n, (k, b)).astype(np.int32)
    slots[:, :6] = rng.integers(0, 4, (k, 6))  # duplicates, across batches
    slots[1, 10:14] = -1
    counts = rng.integers(0, 120, (k, b))
    if width == 8:
        counts[0, 0], counts[2, 3] = 300, 70_000
    rows = K.pack_rows(slots, counts)
    assert rows.shape[-1] == width
    nows = np.array([100, 100, 700, 5_000], np.int32)
    if kind == "bucket":
        state = K.init_bucket_state(n)
        params = (jnp.float32(150.0), jnp.float32(0.25))
        scan, batch, static = K.acquire_scan, K.acquire_batch, {}
    else:
        state = K.init_window_state(n)
        params = (jnp.float32(150.0), jnp.int32(1024))
        scan, batch = K.window_acquire_scan, K.window_acquire_batch
        static = {"interpolate": kind == "sliding"}

    got_state, out = scan(jax.tree.map(jnp.copy, state), jnp.asarray(rows),
                          jnp.asarray(nows), *params,
                          verdict_only=verdict_only, **static)
    want_g, want_r = [], []
    for i in range(k):
        state, g, r = batch(state, jnp.asarray(slots[i]),
                            jnp.asarray(counts[i], jnp.int32),
                            jnp.asarray(slots[i] >= 0), jnp.int32(nows[i]),
                            *params, **static)
        want_g.append(np.asarray(g))
        want_r.append(np.asarray(r))
    want_g = np.stack(want_g)
    assert want_g.any() and not want_g[slots >= 0].all()
    if verdict_only:
        np.testing.assert_array_equal(np.asarray(out), _bits(want_g))
    else:
        granted, remaining = _scan_out(out)
        np.testing.assert_array_equal(granted, want_g)
        np.testing.assert_allclose(remaining, np.stack(want_r), rtol=1e-6)
    for a, b_ in zip(got_state, state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-6)


# -- write-back: one unique write per slot vs the four-scatter form -------
#
# The oracle below is the decision launch as it stood before the write-back
# landed each slot once: set ``refilled`` on every row's slot, scatter-add
# ``-consumed`` per row, then set ``last_ts`` and ``exists``. The kernels
# must leave the same table and return the same outputs, bit for bit.

CAP, RATE = 5.0, 10.0 / TPS  # 10/1024 per tick: every refill exact in f32
WIDTH = 64
# A 64-slot table takes the write-back's sorted-index form, a 2^20-slot
# one (over K.SORTED_WRITE_SLOTS_PER_ROW slots per row) the unsorted form.
TABLES = [64, 1 << 20]


def _oracle_core(state, slots, counts, valid, now, prefix=None):
    size = state.tokens.shape[0]
    valid = valid & (slots >= 0) & (slots < size)
    gs = jnp.where(valid, slots, 0)
    counts_f = jnp.asarray(counts, jnp.float32)
    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs],
                                 state.exists[gs], now, CAP, RATE)
    if prefix is None:
        prefix = bm.duplicate_prefix(slots, counts, valid)
    prefix = jnp.asarray(prefix, jnp.float32)
    granted = valid & (refilled >= prefix + counts_f)
    consumed = jnp.where(granted, counts_f, 0.0)
    remaining = jnp.where(valid, jnp.maximum(refilled - prefix - consumed,
                                             0.0), 0.0)
    return _four_scatters(state, slots, valid, refilled, consumed, now), \
        granted, remaining


def _four_scatters(state, slots, valid, refilled, consumed, now):
    ss = jnp.where(valid, slots, state.tokens.shape[0])
    tokens = state.tokens.at[ss].set(refilled, mode="drop")
    tokens = tokens.at[ss].add(-consumed, mode="drop")
    last_ts = state.last_ts.at[ss].set(jnp.asarray(now, jnp.int32),
                                       mode="drop")
    exists = state.exists.at[ss].set(True, mode="drop")
    return K.BucketState(tokens, last_ts, exists)


@jax.jit
def _oracle_packed(state, packed):
    st, g, r = _oracle_core(state, packed[0], packed[1], packed[0] >= 0,
                            packed[2, 0], packed[3])
    return st, jnp.stack([g.astype(jnp.float32), r])


@jax.jit
def _oracle_grouped(state, packed):
    slots, now, n = packed[0], packed[2, 0], packed[4]
    size = state.tokens.shape[0]
    valid = (slots >= 0) & (slots < size)
    gs = jnp.where(valid, slots, 0)
    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs],
                                 state.exists[gs], now, CAP, RATE)
    c = packed[1].astype(jnp.float32)
    avail = refilled - packed[3].astype(jnp.float32)
    n_granted = jnp.where(
        c > 0, jnp.clip(jnp.floor(avail / jnp.maximum(c, 1.0)), 0.0,
                        n.astype(jnp.float32)),
        jnp.where(avail >= 0, n.astype(jnp.float32), 0.0))
    n_granted = jnp.where(valid, n_granted, 0.0)
    consumed = n_granted * c
    remaining = jnp.where(valid, jnp.maximum(avail - consumed, 0.0), 0.0)
    st = _four_scatters(state, slots, valid, refilled, consumed, now)
    return st, jnp.stack([n_granted, remaining])


@jax.jit
def _oracle_scan(state, slots_k, counts_k, nows_k):
    def body(st, xs):
        slots, counts, now = xs
        st, g, r = _oracle_core(st, slots, counts.astype(jnp.int32),
                                slots >= 0, now)
        return st, jnp.stack([g.astype(jnp.float32), r])
    return jax.lax.scan(body, state, (slots_k, counts_k, nows_k))


def _seed_state(n):
    """Slots 0-39 hold balances (whole, halves, quarters, empty) stamped at
    assorted ticks; slots from 40 on were never touched (``exists``
    false)."""
    tokens = np.zeros(n, np.float32)
    tokens[:40] = np.resize([5.0, 0.0, 2.5, 1.0, 3.75, 0.25, 4.0, 2.0], 40)
    last_ts = np.zeros(n, np.int32)
    last_ts[:40] = np.resize([900, 1000, 950, 1000, 700], 40)
    exists = np.zeros(n, bool)
    exists[:40] = True
    return tokens, last_ts, exists


def _state(arrays):
    return K.BucketState(*(jnp.array(a, copy=True) for a in arrays))


# Each scenario: launches of (now, [(slot, count), ...]).
WRITE_BACK_SCENARIOS = {
    "dup_equal_counts": [(1000, [(3, 1), (3, 1), (3, 1), (3, 1), (5, 2),
                                 (5, 2), (6, 1)])],
    "dup_mixed_counts": [(1000, [(3, 2), (7, 1), (3, 1), (3, 3), (7, 4),
                                 (3, 1)])],
    "denied_then_smaller": [(1000, [(2, 3), (2, 1), (12, 9), (12, 2),
                                    (12, 1)])],
    "zero_permit_probes": [(1000, [(9, 0), (9, 2), (9, 0), (1, 0), (1, 1),
                                   (1, 0), (44, 0)])],
    "new_keys": [(1000, [(41, 1), (42, 5), (43, 2), (43, 2), (43, 2),
                         (45, 0), (46, 6)])],
    "padding_only": [(1000, []), (1001, [])],
    "relaunch_same_tick": [(1000, [(3, 2), (41, 3), (4, 1)]),
                           (1000, [(3, 2), (41, 3), (4, 1), (3, 1)]),
                           (1000, [(41, 1), (41, 1)])],
}


def _layout(rows, where):
    """Row positions in the WIDTH-row operand: packed at the front, or
    ending at the last row (every live-width cut is then the full one)."""
    if where == "front":
        return list(range(len(rows)))
    return list(range(WIDTH - len(rows), WIDTH))


def _packed_rows(now, rows, where):
    packed = np.full((4, WIDTH), -1, np.int32)
    packed[1:] = 0
    packed[2] = now
    demand: dict[int, int] = {}
    for pos, (slot, count) in zip(_layout(rows, where), rows):
        packed[:, pos] = (slot, count, now, demand.get(slot, 0))
        demand[slot] = demand.get(slot, 0) + count
    return packed


def _grouped_rows(now, rows, where):
    """The store's coalescing: one (slot, count, n) row per key when its
    counts agree, else one row per request with its exact prefix."""
    by_slot: dict[int, list[int]] = {}
    for slot, count in rows:
        by_slot.setdefault(slot, []).append(count)
    groups = []
    for slot, counts in by_slot.items():
        if len(set(counts)) == 1:
            groups.append((slot, counts[0], 0, len(counts)))
        else:
            pref = 0
            for c in counts:
                groups.append((slot, c, pref, 1))
                pref += c
    packed = np.full((5, WIDTH), -1, np.int32)
    packed[1:] = 0
    packed[2] = now
    for pos, g in zip(_layout(groups, where), groups):
        packed[[0, 1, 3, 4], pos] = g
    return packed


def _assert_same(new, ref):
    """Bit-for-bit equality of states and outputs (floats compared as their
    32-bit patterns, so -0.0 and 0.0 differ)."""
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(ref)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            a, b = a.view(np.uint32), b.view(np.uint32)
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n", TABLES)
@pytest.mark.parametrize("where", ["front", "tail"])
@pytest.mark.parametrize("scenario", sorted(WRITE_BACK_SCENARIOS))
class TestWriteBackMatchesFourScatter:
    def test_acquire_batch_packed(self, scenario, where, n):
        new, ref = _state(_seed_state(n)), _state(_seed_state(n))
        for now, rows in WRITE_BACK_SCENARIOS[scenario]:
            packed = jnp.asarray(_packed_rows(now, rows, where))
            new, out = K.acquire_batch_packed(new, packed, jnp.float32(CAP),
                                              jnp.float32(RATE))
            ref, out_ref = _oracle_packed(ref, packed)
            _assert_same((new, out), (ref, out_ref))

    def test_acquire_batch_packed_grouped(self, scenario, where, n):
        new, ref = _state(_seed_state(n)), _state(_seed_state(n))
        for now, rows in WRITE_BACK_SCENARIOS[scenario]:
            packed = jnp.asarray(_grouped_rows(now, rows, where))
            new, out = K.acquire_batch_packed_grouped(
                new, packed, jnp.float32(CAP), jnp.float32(RATE))
            ref, out_ref = _oracle_grouped(ref, packed)
            _assert_same((new, out), (ref, out_ref))

    @pytest.mark.parametrize("bits", [False, True])
    def test_acquire_scan_fused(self, scenario, where, n, bits):
        """All of a scenario's launches in ONE scanned call (K launches)."""
        launches = WRITE_BACK_SCENARIOS[scenario]
        slots = np.full((len(launches), WIDTH), -1, np.int32)
        counts = np.zeros((len(launches), WIDTH), np.uint8)
        for k, (_, rows) in enumerate(launches):
            for pos, (slot, count) in zip(_layout(rows, where), rows):
                slots[k, pos], counts[k, pos] = slot, count
        nows = np.array([now for now, _ in launches], np.int32)
        new, out = K.acquire_scan(
            _state(_seed_state(n)), jnp.asarray(K.pack_rows(slots, counts)),
            jnp.asarray(nows), jnp.float32(CAP), jnp.float32(RATE),
            verdict_only=bits)
        ref, out_ref = _oracle_scan(_state(_seed_state(n)), jnp.asarray(slots),
                                    jnp.asarray(counts), jnp.asarray(nows))
        if bits:
            granted = np.asarray(out_ref)[:, 0] > 0.5
            out_ref = np.packbits(granted, axis=-1, bitorder="little")
        _assert_same((new, out), (ref, out_ref))
