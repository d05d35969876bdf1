"""L0 — pure token-bucket math, time always an explicit operand.

These are the deterministic cores of the reference's two Lua kernels,
re-derived as vectorized jax-numpy functions over structure-of-arrays state:

- :func:`refill_and_decrement` ≙ the exact-bucket Lua script
  (``TokenBucket/RedisTokenBucketRateLimiter.cs:176-239``): lazy refill from
  elapsed store time, clock-regression clamp, refill clamp to
  ``[0, capacity]``, all-or-nothing grant, init-on-miss to a full bucket.
- :func:`decay_and_add` ≙ the approximate-bucket sync script
  (``ApproximateTokenBucket/RedisApproximateTokenBucketRateLimiter.cs:216-271``):
  decaying global consumption counter plus an EWMA of the inter-sync
  interval, from which callers derive a membership-free instance-count
  estimate.
- :func:`sliding_window_estimate` — the sliding-window counter variant
  (a BASELINE.json config; absent from the reference, which only sketched
  it in dead code).

Representation choices (TPU-first, see SURVEY.md §7 "Numerics"):

- **Time** is an ``int32`` tick count, ``TICKS_PER_SECOND = 1024`` (a power
  of two so second↔tick conversions are exact in float32). A batch kernel
  receives ONE scalar ``now`` — every key in the batch observes the same
  clock, the consistency property the reference got from Redis ``TIME``
  (``RedisTokenBucketRateLimiter.cs:202-203``). Clients never supply time
  (invariant 1, SURVEY.md §2).
- **Tokens** are ``float32``. Grant comparison is ``tokens >= count`` with
  no epsilon: float rounding can only under-admit, never over-admit, which
  is the safe direction for a rate limiter. The reference's accidental
  Lua-number truncation semantics (SURVEY.md invariant 10) are replaced by
  explicit ``floor`` at the observation boundary only.

Everything here is shape-polymorphic and dtype-stable so it can be jitted,
vmapped, and shard_mapped without retracing per config: capacities and rates
arrive as (broadcastable) array operands, not Python constants baked into
the trace — unlike the reference, which re-generates and re-compiles the Lua
script text per limiter instance
(``RedisTokenBucketRateLimiter.cs:184-185``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# One tick = 1/1024 s. Power of two → exact in float32, and a full int32 range
# covers ~24 days of uptime, far beyond any flush interval. Idle slots are
# reclaimed by TTL eviction long before tick wraparound can matter; see
# DeviceBucketStore.sweep().
TICKS_PER_SECOND = 1024

# Lua kernel TTL clamp: max(1s, min(1yr, time-to-full-refill))
# (RedisTokenBucketRateLimiter.cs:234-235).
MIN_TTL_TICKS = TICKS_PER_SECOND  # 1 second
MAX_TTL_TICKS = 365 * 24 * 3600 * TICKS_PER_SECOND  # 1 year (clamped to int32 below)
_INT32_MAX = 2**31 - 1

# The approximate global counter's fixed TTL: 86400 s
# (RedisApproximateTokenBucketRateLimiter.cs:268).
GLOBAL_COUNTER_TTL_TICKS = 86400 * TICKS_PER_SECOND

# EWMA smoothing of the inter-sync interval: new_p = 0.8*prev + 0.2*delta
# (RedisApproximateTokenBucketRateLimiter.cs:260-262).
PERIOD_EWMA_ALPHA = 0.2


def seconds_to_ticks(seconds: float) -> int:
    """Host-side convenience: convert seconds to integer ticks (floor)."""
    return int(seconds * TICKS_PER_SECOND)


def ticks_to_seconds(ticks) -> float:
    return ticks / TICKS_PER_SECOND


def elapsed_ticks(now, last_ts):
    """Elapsed store time with the clock-regression clamp.

    ``max(0, now - last)`` — after a store failover the new authority's clock
    may be behind; negative elapsed must not mint or destroy tokens
    (``RedisTokenBucketRateLimiter.cs:218``; invariant 1).
    """
    return jnp.maximum(0, now - last_ts).astype(jnp.int32)


def refill(tokens, last_ts, now, capacity, fill_rate_per_tick):
    """Lazy refill: tokens materialize arithmetically from elapsed time.

    ``min(capacity, tokens + elapsed * rate)`` — the upper clamp bounds what
    a forward clock jump can grant to one full bucket
    (``RedisTokenBucketRateLimiter.cs:221`` and comment ``:179-180``;
    invariants 1-2). No background replenishment ever touches per-key state,
    which is what makes 10M idle keys free.
    """
    delta = elapsed_ticks(now, last_ts).astype(jnp.float32)
    return jnp.minimum(
        jnp.asarray(capacity, jnp.float32),
        tokens + delta * jnp.asarray(fill_rate_per_tick, jnp.float32),
    )


def refill_or_init(tokens, last_ts, exists, now, capacity, fill_rate_per_tick):
    """Refill where the slot exists; init-on-miss to a FULL bucket elsewhere
    (``RedisTokenBucketRateLimiter.cs:210-215``) — shared by the decision
    kernels and the read-only peek path."""
    return jnp.where(
        exists,
        refill(tokens, last_ts, now, capacity, fill_rate_per_tick),
        jnp.asarray(capacity, jnp.float32) + jnp.zeros_like(tokens),
    )


def decay_core(value, period_ewma, last_ts, exists, now, decay_rate_per_tick):
    """Decay-without-add core shared by :func:`decay_and_add` and the batched
    sync kernel (which needs the decayed value separately so consumption can
    be applied via scatter-add). Returns ``(decayed, new_period)``."""
    # Init-on-miss must not read a stale/garbage timestamp: a fresh counter's
    # "previous touch" is the store epoch (tick 0).
    delta = elapsed_ticks(now, jnp.where(exists, last_ts, 0)).astype(jnp.float32)
    decayed = jnp.where(
        exists,
        jnp.maximum(
            0.0, value - delta * jnp.asarray(decay_rate_per_tick, jnp.float32)
        ),
        0.0,
    )
    new_period = jnp.where(
        exists,
        (1.0 - PERIOD_EWMA_ALPHA) * period_ewma + PERIOD_EWMA_ALPHA * delta,
        delta,
    )
    return decayed, new_period


def refill_and_decrement(tokens, last_ts, exists, now, counts, capacity,
                         fill_rate_per_tick):
    """The exact-bucket kernel core: one atomic refill-then-grant step.

    Mirrors the Lua program at ``RedisTokenBucketRateLimiter.cs:176-239``:

    - ``exists == False`` ⇒ init-on-miss to a full bucket (``:210-215``) —
      a wiped store self-heals to "full" rather than "empty".
    - refill with regression clamp + capacity clamp (``:218,:221``);
    - all-or-nothing grant: ``count`` permits are consumed iff
      ``refilled >= count`` (``:224-227``; invariant 4). ``count == 0`` is a
      probe: it "succeeds" trivially and consumes nothing — callers decide
      probe semantics at the API layer.

    Args:
      tokens:  f32[...] current token balances (garbage where ``~exists``).
      last_ts: i32[...] last-touch store ticks (garbage where ``~exists``).
      exists:  bool[...] slot-occupancy mask.
      now:     i32 scalar — THE batch timestamp (store is time authority).
      counts:  i32/f32[...] requested permits per key, >= 0.
      capacity, fill_rate_per_tick: broadcastable f32 bucket parameters.

    Returns:
      ``(new_tokens, new_last_ts, granted)`` — post-decision state and a
      bool grant mask. State for every touched key advances its timestamp to
      ``now`` whether or not the grant succeeded (the refill was applied).
    """
    counts = jnp.asarray(counts, jnp.float32)
    refilled = refill_or_init(tokens, last_ts, exists, now, capacity,
                              fill_rate_per_tick)
    granted = refilled >= counts
    new_tokens = refilled - jnp.where(granted, counts, 0.0)
    new_last_ts = jnp.broadcast_to(jnp.asarray(now, jnp.int32), new_tokens.shape)
    return new_tokens, new_last_ts, granted


def time_to_full_ttl(tokens, capacity, fill_rate_per_tick):
    """Per-key state TTL: time until the bucket would be full again.

    ``clamp(ceil((capacity - tokens) / rate), 1s, 1yr)`` — once a bucket has
    sat untouched long enough to be full, its state is indistinguishable from
    init-on-miss, so it can be evicted (``RedisTokenBucketRateLimiter.cs:234-235``;
    invariant 5). Returns i32 ticks.
    """
    rate = jnp.maximum(jnp.asarray(fill_rate_per_tick, jnp.float32), 1e-30)
    deficit = jnp.maximum(jnp.asarray(capacity, jnp.float32) - tokens, 0.0)
    ttl = jnp.ceil(deficit / rate)
    ttl = jnp.clip(ttl, MIN_TTL_TICKS, min(MAX_TTL_TICKS, _INT32_MAX))
    return ttl.astype(jnp.int32)


def decay_and_add(value, period_ewma, last_ts, exists, now, local_counts,
                  decay_rate_per_tick):
    """The approximate-bucket sync kernel core: decaying consumption counter.

    The global bucket is *inverted* relative to the exact one: it tracks a
    decaying **throttle score** (consumption), not a token balance
    (``RedisApproximateTokenBucketRateLimiter.cs:216-271``):

      ``new_v = max(0, v - delta * decay_rate) + local_counts``   (``:258``)
      ``new_p = 0.8 * p + 0.2 * delta``                           (``:260-262``)

    ``new_p`` is the EWMA of the observed inter-sync interval for THIS
    counter across ALL client instances: with k clients each syncing every
    replenishment period, syncs arrive k times per period, so
    ``period / new_p ≈ k`` — the membership-free instance-count estimate
    (``:443``; invariant 6, SURVEY.md §5.3d).

    Init-on-miss: a fresh counter starts at ``v = local_counts`` with
    ``p = delta`` undefined — we seed the EWMA with the replenishment-period
    hint via the caller passing ``period_ewma`` prefilled, or simply with
    ``delta=0`` contribution (matching the Lua script, which initializes
    ``p`` to the first observed delta).

    Returns ``(new_value, new_period_ewma, new_last_ts)``.
    """
    local_counts = jnp.asarray(local_counts, jnp.float32)
    decayed, new_period = decay_core(
        value, period_ewma, last_ts, exists, now, decay_rate_per_tick
    )
    new_value = decayed + local_counts
    new_last_ts = jnp.broadcast_to(jnp.asarray(now, jnp.int32), new_value.shape)
    return new_value, new_period, new_last_ts


def instance_count_estimate(replenishment_period_ticks, period_ewma):
    """``max(1, round(period / observed_sync_interval))``.

    (``RedisApproximateTokenBucketRateLimiter.cs:443``.) Elasticity is
    automatic: clients joining or leaving reshapes the estimate within
    ~O(period) with no membership protocol.
    """
    p = jnp.maximum(jnp.asarray(period_ewma, jnp.float32), 1.0)
    est = jnp.round(jnp.asarray(replenishment_period_ticks, jnp.float32) / p)
    return jnp.maximum(1.0, est).astype(jnp.int32)


def available_tokens(token_limit, global_score, instance_count, local_score):
    """The approximate limiter's local availability formula.

    ``max(0, ceil((token_limit - global_score) / instance_count) - local_score)``
    (``RedisApproximateTokenBucketRateLimiter.cs:37``) — each client
    self-limits to its estimated fair share of the global remainder, minus
    what it has already consumed locally since the last sync.
    """
    share = jnp.ceil(
        (jnp.asarray(token_limit, jnp.float32) - global_score)
        / jnp.maximum(jnp.asarray(instance_count, jnp.float32), 1.0)
    )
    avail = share - local_score
    return jnp.maximum(0.0, avail)


def retry_after_ticks(deficit, fill_rate_per_tick):
    """Time until ``deficit`` more tokens exist: ``deficit / fill_rate``.

    The reference computes ``deficit * FillRatePerSecond``
    (``RedisApproximateTokenBucketRateLimiter.cs:393-394``) which is
    dimensionally inverted — a known defect (SURVEY.md §2) we deliberately
    correct rather than replicate.
    """
    rate = jnp.maximum(jnp.asarray(fill_rate_per_tick, jnp.float32), 1e-30)
    return jnp.ceil(jnp.asarray(deficit, jnp.float32) / rate).astype(jnp.int32)


def sliding_window_advance(prev_count, curr_count, window_idx, exists, now,
                           window_ticks):
    """Advance a two-bucket sliding-window counter to the window containing ``now``.

    State per key: counts for the current and previous fixed windows plus the
    integer index of the current window. On advance by one window, current
    rolls into previous; on advance by 2+, both zero. Init-on-miss zeros.

    Returns ``(prev_count', curr_count', window_idx')``.
    """
    idx_now = (jnp.asarray(now, jnp.int32) // jnp.asarray(window_ticks, jnp.int32))
    idx_now = jnp.broadcast_to(idx_now, jnp.shape(window_idx)).astype(jnp.int32)
    # Clock-regression clamp: never move the window backwards.
    idx_now = jnp.maximum(idx_now, jnp.where(exists, window_idx, idx_now))
    steps = idx_now - jnp.where(exists, window_idx, idx_now)
    same = steps == 0
    one = steps == 1
    prev_new = jnp.where(same, prev_count, jnp.where(one, curr_count, 0.0))
    curr_new = jnp.where(same, curr_count, 0.0)
    prev_new = jnp.where(exists, prev_new, 0.0)
    curr_new = jnp.where(exists, curr_new, 0.0)
    return prev_new, curr_new, idx_now


def sliding_window_estimate(prev_count, curr_count, window_idx, now, window_ticks):
    """Weighted sliding-window estimate of consumption in the trailing window.

    ``curr + prev * (1 - frac_elapsed_of_current_window)`` — the standard
    interpolation (Cloudflare-style) giving a smooth approximation of a true
    sliding log at two counters per key.
    """
    # Compute the small in-window remainder in int32 FIRST: casting absolute
    # ticks to f32 loses precision past 2^24 ticks (~4.5 h uptime) and the
    # cancellation error would let the estimate over-admit.
    rem = jnp.asarray(now, jnp.int32) - window_idx * jnp.asarray(window_ticks, jnp.int32)
    frac = rem.astype(jnp.float32) / jnp.asarray(window_ticks, jnp.float32)
    frac = jnp.clip(frac, 0.0, 1.0)
    return curr_count + prev_count * (1.0 - frac)


def sliding_window_acquire(prev_count, curr_count, window_idx, exists, now,
                           counts, limit, window_ticks):
    """Atomic advance + estimate + all-or-nothing grant for the window variant.

    Grant iff ``estimate + count <= limit``; on grant the current-window
    counter absorbs ``count``. Same shape contract as
    :func:`refill_and_decrement`.

    Returns ``(prev', curr', idx', granted)``.
    """
    counts = jnp.asarray(counts, jnp.float32)
    prev_new, curr_new, idx_new = sliding_window_advance(
        prev_count, curr_count, window_idx, exists, now, window_ticks
    )
    est = sliding_window_estimate(prev_new, curr_new, idx_new, now, window_ticks)
    granted = est + counts <= jnp.asarray(limit, jnp.float32)
    curr_new = curr_new + jnp.where(granted, counts, 0.0)
    return prev_new, curr_new, idx_new, granted


def segment_starts(sorted_keys):
    """Bool mask of the first row of each run of equal sorted keys."""
    return jnp.concatenate([jnp.ones((1,), bool),
                            sorted_keys[1:] != sorted_keys[:-1]])


def segment_cumsum(seg_start, values):
    """Inclusive per-segment running sum of ``values`` (in segment order).

    Sums reset at each segment boundary, so accumulation (and float32
    rounding) stays per-key — a whole-batch cumsum would lose integer
    precision past 2^24 total demand and could over-admit duplicates.
    """
    def seg_combine(a, b):
        a_flag, a_val = a
        b_flag, b_val = b
        return a_flag | b_flag, jnp.where(b_flag, b_val, a_val + b_val)

    _, inc = jax.lax.associative_scan(seg_combine, (seg_start, values))
    return inc


def duplicate_prefix(slots, counts, valid):
    """Per-request prefix of earlier same-slot demand within one batch.

    ``prefix[i] = sum_{j < i, slots[j] == slots[i], valid[j]} counts[j]``.

    Used to serialize duplicate keys inside one batch conservatively: request
    ``i`` is granted only if the refilled balance covers ``prefix[i] +
    counts[i]``. Counting *all* earlier same-slot demand (granted or not) can
    only under-admit relative to true serial order — never over-admit —
    preserving atomicity (invariant 3) at batch granularity. The serving
    flush path additionally coalesces same-key requests gathered into one
    flush into grouped rows (``store._DeviceTable._flush`` →
    ``kernels.acquire_batch_packed_grouped``), so hot keys occupy one row
    instead of many and this in-kernel sort only serves paths that ship no
    host prefix (SURVEY.md §7 "Hard parts").

    Implemented as a stable sort by slot + segmented exclusive prefix sum —
    O(B log B) with O(B) memory traffic, cheap enough that the dup-safe
    kernel variant is simply always used (no per-flush host dup detection,
    no second compiled variant).
    """
    slots = jnp.asarray(slots)
    counts_f = jnp.asarray(counts, jnp.float32) * jnp.asarray(valid, jnp.float32)
    # Stable sort groups equal slots while preserving original request order
    # within each group, so an in-segment exclusive prefix is exactly the
    # "earlier same-slot demand" sum.
    order = jnp.argsort(slots, stable=True)
    c_sorted = counts_f[order]
    prefix_sorted = (segment_cumsum(segment_starts(slots[order]), c_sorted)
                     - c_sorted)
    return jnp.zeros_like(counts_f).at[order].set(prefix_sorted)
