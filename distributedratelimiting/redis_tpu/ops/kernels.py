"""L1 — jitted batch kernels over the keyed state table.

Each function here is the moral equivalent of one prepared Lua script in the
reference (``LuaScript.Prepare`` at ``RedisTokenBucketRateLimiter.cs:45``):
traced and compiled once, then invoked per micro-batch. Differences, by
design (TPU-first, SURVEY.md §7):

- One launch serves a whole batch of keys (the reference paid one network
  RTT per key per acquire, ``RedisTokenBucketRateLimiter.cs:63``).
- Bucket parameters (capacity, fill rate) are *operands*, not constants
  baked into compiled text, so one compilation serves every limiter config.
- State buffers are donated: steady-state operation re-uses the same HBM
  allocation, no copies of the (potentially multi-GB) table per launch.
- Atomicity (invariant 3) holds at batch granularity: XLA executes the
  whole gather → decide → scatter program as one serialized step over the
  state arrays, exactly as Redis serialized Lua scripts. Duplicate keys
  within one batch are serialized conservatively by the rule of
  :func:`~.bucket_math.duplicate_prefix` (never over-admit): the token
  bucket kernels sort each launch's rows by slot once, decide in that
  order and write each touched slot back once.

State layout is structure-of-arrays in HBM — ``tokens: f32[N]``,
``last_ts: i32[N]``, ``exists: bool[N]`` — 9 bytes/key, so 10M keys ≈ 90 MB,
comfortably resident on one chip and shardable along N over a mesh.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributedratelimiting.redis_tpu.ops import bucket_math as bm

__all__ = [
    "BucketState",
    "CounterState",
    "WindowState",
    "init_bucket_state",
    "init_counter_state",
    "init_window_state",
    "acquire_core",
    "acquire_batch",
    "acquire_batch_packed",
    "acquire_batch_packed_grouped",
    "acquire_scan",
    "pack_rows",
    "acquire_hierarchical_packed",
    "debit_batch_packed",
    "sync_batch",
    "sync_batch_packed",
    "SemaState",
    "init_sema_state",
    "sema_batch_packed",
    "sweep_semas",
    "rebase_sema_epoch",
    "window_acquire_batch",
    "window_acquire_batch_packed",
    "window_acquire_batch_packed_grouped",
    "window_acquire_scan",
    "sweep_expired",
    "sweep_counters",
    "sweep_windows",
    "rebase_bucket_epoch",
    "rebase_counter_epoch",
    "rebase_window_epoch",
    "peek_batch",
    "peek_batch_packed",
]


class BucketState(NamedTuple):
    """SoA token-bucket table ≙ the Redis hash ``{v, t}`` per key
    (``RedisTokenBucketRateLimiter.cs:210-230``), plus an occupancy mask
    standing in for Redis key existence."""

    tokens: jax.Array   # f32[N]
    last_ts: jax.Array  # i32[N]
    exists: jax.Array   # bool[N]


class CounterState(NamedTuple):
    """SoA decaying-counter table ≙ the Redis hash ``{v, p, t}``
    (``RedisApproximateTokenBucketRateLimiter.cs:265-268``)."""

    value: jax.Array    # f32[N] decaying throttle score
    period: jax.Array   # f32[N] EWMA of inter-sync interval (ticks)
    last_ts: jax.Array  # i32[N]
    exists: jax.Array   # bool[N]


class WindowState(NamedTuple):
    """SoA two-bucket sliding-window table (BASELINE config 4)."""

    prev_count: jax.Array  # f32[N]
    curr_count: jax.Array  # f32[N]
    window_idx: jax.Array  # i32[N]
    exists: jax.Array      # bool[N]


def init_bucket_state(n: int) -> BucketState:
    return BucketState(
        tokens=jnp.zeros((n,), jnp.float32),
        last_ts=jnp.zeros((n,), jnp.int32),
        exists=jnp.zeros((n,), bool),
    )


def init_counter_state(n: int) -> CounterState:
    return CounterState(
        value=jnp.zeros((n,), jnp.float32),
        period=jnp.zeros((n,), jnp.float32),
        last_ts=jnp.zeros((n,), jnp.int32),
        exists=jnp.zeros((n,), bool),
    )


def init_window_state(n: int) -> WindowState:
    return WindowState(
        prev_count=jnp.zeros((n,), jnp.float32),
        curr_count=jnp.zeros((n,), jnp.float32),
        window_idx=jnp.zeros((n,), jnp.int32),
        exists=jnp.zeros((n,), bool),
    )


def _valid_slots(slots, valid, size):
    """A row is live only if marked valid AND its slot is in range — an
    out-of-range slot with ``valid=True`` (e.g. a stale directory entry) must
    become a denied padding row, not a phantom grant against row 0/N-1."""
    return valid & (slots >= 0) & (slots < size)


def _gather_slots(slots, valid):
    """Clamp invalid/padding rows to slot 0 for the gather; their results are
    masked out and their scatters dropped."""
    return jnp.where(valid, slots, 0)


def _scatter_slots(slots, valid, size):
    """Padding rows map past the end of the table ⇒ dropped by
    ``mode='drop'`` scatters. (Negative indices would *wrap*, not drop.)"""
    return jnp.where(valid, slots, size)


#: A scatter whose indices are unique and sorted streams the whole table
#: once, whatever its row count (~12 ps a slot on a TPU v5e: 50, 200 and
#: 800 us at 2^22, 2^24 and 2^26 slots); an unsorted one pays per update
#: row, real or dropped (~92 ns a row at each of those sizes). They cross
#: near 7,900 slots a row, so the write-back sorts its indices when the
#: table holds fewer than this many slots per row of the launch. At 2^20
#: slots the two forms cost the same (PERF.md §5).
SORTED_WRITE_SLOTS_PER_ROW = 8192


def _write_back(state: BucketState, keys, seg_start, refilled, consumed,
               any_fresh, now):
    """Land one launch's decisions in the table, each touched slot ONCE:
    ``tokens = refilled − Σ consumed`` over the slot's rows in the launch,
    ``last_ts = now``, ``exists = True``.

    The rows come in slot order (:func:`_decide_in_slot_order`): ``keys``
    are their slots, invalid rows keyed ``size`` (past the table) and last,
    ``seg_start`` marks each run of one slot. The last row of a valid run
    carries the slot's write; every other row, padding included, goes to
    an index of its own past the table and is dropped. The indices are
    unique, so XLA issues the scatters without ordering same-address
    updates. Same-slot rows share one refilled value (same gathered state,
    same ``now``), and with integer counts and balances below 2^24 both
    ``Σ consumed`` and the subtraction are exact: the result equals the
    former four scatters (set refilled, add −consumed per row, set
    ``last_ts``, set ``exists``) bit for bit. ``exists`` is written only
    when ``any_fresh`` (some row found its slot empty); elsewhere every
    touched slot already holds ``True``.
    """
    size = state.tokens.shape[0]
    b = keys.shape[0]
    if size + b > jnp.iinfo(jnp.int32).max:
        raise ValueError(f"table of {size} slots leaves no int32 index "
                         f"past its end for {b} rows")
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    vals = refilled - bm.segment_cumsum(seg_start, consumed)
    idx = jnp.where(seg_end & (keys < size), keys,
                    size + jnp.arange(b, dtype=jnp.int32))
    in_order = size < SORTED_WRITE_SLOTS_PER_ROW * b
    if in_order:
        idx, vals = jax.lax.sort((idx, vals), num_keys=1)

    def put(arr, v, in_order=in_order):
        return arr.at[idx].set(v, mode="drop", unique_indices=True,
                               indices_are_sorted=in_order)

    tokens = put(state.tokens, vals)
    last_ts = put(state.last_ts, jnp.asarray(now, jnp.int32))
    # The bool table's write takes the unsorted form: its sorted form
    # costs seconds of TPU compile per kernel, for a write that only new
    # keys need.
    exists = jax.lax.cond(any_fresh, lambda e: put(e, True, False),
                          lambda e: e, state.exists)
    return BucketState(tokens, last_ts, exists)


def _decide_in_slot_order(state: BucketState, slots, valid, now, capacity,
                          fill_rate_per_tick, rows, decide):
    """One decision launch, worked in slot order.

    Sorts the rows by slot (stable: same-slot rows keep request order;
    invalid rows sort last), carrying ``rows`` along as sort operands,
    gathers each row's slot state and calls ``decide(refilled, valid,
    seg_start, *rows)``, which returns ``(out_a, out_b, consumed)`` per
    row. The state lands through :func:`_write_back`; the two outputs go
    back to request order through a second sort."""
    size = state.tokens.shape[0]
    iota = jnp.arange(slots.shape[0], dtype=jnp.int32)
    keys, order, *rows = jax.lax.sort(
        (jnp.where(valid, slots, size), iota, *rows), num_keys=1,
        is_stable=True)
    valid = keys < size
    seg_start = bm.segment_starts(keys)
    gs = _gather_slots(keys, valid)
    ex_old = state.exists[gs]
    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs], ex_old,
                                 now, capacity, fill_rate_per_tick)
    out_a, out_b, consumed = decide(refilled, valid, seg_start, *rows)
    state = _write_back(state, keys, seg_start, refilled, consumed,
                       jnp.any(valid & ~ex_old), now)
    _, out_a, out_b = jax.lax.sort((order, out_a, out_b), num_keys=1)
    return state, out_a, out_b


def acquire_core(state: BucketState, slots, counts, valid, now, capacity,
                 fill_rate_per_tick, *, handle_duplicates: bool = True,
                 prefix=None):
    """Traceable core of :func:`acquire_batch` — also the per-shard block
    body under ``shard_map`` (where ``state`` is one shard's slice and
    ``slots`` are shard-local ids). See :func:`acquire_batch` for the full
    contract. ``prefix`` (f32[B]) overrides the in-kernel same-slot demand
    computation when the caller already knows it (the host batcher computes
    it exactly during batch assembly)."""
    valid = _valid_slots(slots, valid, state.tokens.shape[0])
    rows = (jnp.asarray(counts, jnp.float32),)
    if prefix is not None:
        rows += (jnp.asarray(prefix, jnp.float32),)

    def decide(refilled, valid, seg_start, counts_f, prefix=None):
        if prefix is None and handle_duplicates:
            # Earlier same-slot demand, granted or not: the segmented
            # exclusive sum of bucket_math.duplicate_prefix, in slot order.
            demand = counts_f * valid
            prefix = bm.segment_cumsum(seg_start, demand) - demand
        elif prefix is None:
            prefix = jnp.zeros_like(counts_f)
        granted = valid & (refilled >= prefix + counts_f)
        consumed = jnp.where(granted, counts_f, 0.0)
        remaining = jnp.where(
            valid, jnp.maximum(refilled - prefix - consumed, 0.0), 0.0)
        return granted, remaining, consumed

    return _decide_in_slot_order(state, slots, valid, now, capacity,
                                 fill_rate_per_tick, rows, decide)


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_batch(state: BucketState, slots, counts, valid, now, capacity,
                  fill_rate_per_tick, *, handle_duplicates: bool = True):
    """Atomic batched refill-and-decrement — the exact-bucket Lua kernel
    (``RedisTokenBucketRateLimiter.cs:176-239``) over a micro-batch.

    Args:
      state: donated ``BucketState`` (buffers re-used in place).
      slots: i32[B] table indices (-1 or any out-of-range ⇒ padding row).
      counts: i32[B] requested permits (>= 0; 0 behaves as a probe).
      valid: bool[B] real-request mask.
      now: i32 scalar batch timestamp (host is time authority, invariant 1).
      capacity, fill_rate_per_tick: f32 scalars (operands, not constants).
      handle_duplicates: statically enables the same-slot serialization
        pass (sort-based, O(B log B)). On by default; False exists for
        ablation and for callers that guarantee duplicate-free batches.

    Returns:
      ``(new_state, granted bool[B], remaining f32[B])`` where ``remaining``
      is each request's post-decision view of its bucket (conservative under
      in-batch duplication) — the analogue of the script's ``new_v`` reply
      (``:238``).
    """
    return acquire_core(state, slots, counts, valid, now, capacity,
                        fill_rate_per_tick, handle_duplicates=handle_duplicates)


def _unpack_requests(packed):
    """Split the single packed i32[4, B] flush operand: row 0 = slots
    (negative ⇒ padding), row 1 = counts, row 2 = broadcast batch timestamp,
    row 3 = host-computed same-slot demand prefix. One packed array = ONE
    host→device transfer per flush (the cost of a transfer on the chip is
    not measured)."""
    slots = packed[0]
    counts = packed[1]
    now = packed[2, 0]
    prefix = packed[3]
    valid = slots >= 0
    return slots, counts, valid, now, prefix


@partial(jax.jit, donate_argnums=0)
def acquire_batch_packed(state: BucketState, packed, capacity,
                         fill_rate_per_tick):
    """:func:`acquire_batch` with single-transfer operands and a single
    packed result: ``packed`` as in :func:`_unpack_requests`; ``capacity`` /
    ``fill_rate_per_tick`` are device-resident per-table constants (no
    per-flush scalar uploads). Returns ``(new_state, out f32[2, B])`` where
    ``out[0] = granted`` (0/1) and ``out[1] = remaining`` — one device→host
    transfer resolves the whole flush."""
    slots, counts, valid, now, prefix = _unpack_requests(packed)
    new_state, granted, remaining = acquire_core(
        state, slots, counts, valid, now, capacity, fill_rate_per_tick,
        prefix=prefix,
    )
    out = jnp.stack([granted.astype(jnp.float32), remaining])
    return new_state, out


@partial(jax.jit, donate_argnums=0)
def acquire_batch_packed_grouped(state: BucketState, packed, capacity,
                                 fill_rate_per_tick):
    """Coalesced-duplicates flush kernel: one row per ``(key, count)``
    GROUP instead of one row per request (SURVEY.md §7 "Hard parts" —
    Zipf hot keys hammering one slot must not eat the whole batch).

    ``packed i32[5, B]``: row 0 slots (-1 ⇒ padding), row 1 per-request
    count ``c``, row 2 broadcast batch timestamp, row 3 host-computed
    same-slot demand prefix (earlier groups' total integer demand), row 4
    group size ``n`` (number of identical requests).

    Grant rule — exactly the per-row conservative serialization, closed
    over ``n`` identical requests: the first ``n_granted = clamp(floor(
    (refilled − prefix) / c), 0, n)`` members are granted (``c == 0``
    probe groups grant all ``n``, consuming nothing). Consumption is
    ``n_granted · c``, so a group decision is bit-identical to ``n``
    per-row decisions with cumulative prefixes.

    Returns ``(new_state, out f32[2, B])``: ``out[0] = n_granted`` per
    group, ``out[1] = post-consumption remaining`` (every member's view).
    """
    slots = packed[0]
    valid = _valid_slots(slots, slots >= 0, state.tokens.shape[0])
    rows = (jnp.asarray(packed[1], jnp.float32),   # count per request
            jnp.asarray(packed[3], jnp.float32),   # demand prefix
            jnp.asarray(packed[4], jnp.float32))   # group size

    def decide(refilled, valid, seg_start, c, prefix, n):
        avail = refilled - prefix
        n_granted = jnp.where(
            c > 0,
            jnp.clip(jnp.floor(avail / jnp.maximum(c, 1.0)), 0.0, n),
            # c == 0 probe group: granted iff the balance covers the
            # prefix — the same `refilled >= prefix + 0` rule as the
            # per-row kernel.
            jnp.where(avail >= 0, n, 0.0),
        )
        n_granted = jnp.where(valid, n_granted, 0.0)
        consumed = n_granted * c
        remaining = jnp.where(valid, jnp.maximum(avail - consumed, 0.0), 0.0)
        return n_granted, remaining, consumed

    state, n_granted, remaining = _decide_in_slot_order(
        state, slots, valid, packed[2, 0], capacity, fill_rate_per_tick,
        rows, decide)
    return state, jnp.stack([n_granted, remaining])


def pack_rows(slots, counts):
    """Host-side packing of a scanned launch's rows into ONE operand.

    i32 slot ids (-1 = padding) and their counts become little-endian
    ``u8[..., 5]`` (bytes 0-3 the slot, byte 4 the count) when every
    count fits a byte, else ``u8[..., 8]`` (bytes 4-7 the count as an
    i32). A ``uint8`` ``counts`` array fits without a scan; wider counts
    must fit an i32. Validity travels in the slot's sign, so no mask
    array goes up."""
    import numpy as np

    slots = np.asarray(slots, np.int32)
    counts = np.asarray(counts)
    narrow = counts.dtype == np.uint8 or not counts.size or (
        int(counts.min()) >= 0 and int(counts.max()) <= 0xFF)
    out = np.empty((*slots.shape, 5 if narrow else 8), np.uint8)
    out[..., :4] = slots.astype("<i4").view(np.uint8).reshape(
        *slots.shape, 4)
    if narrow:
        out[..., 4] = counts
    else:
        out[..., 4:] = counts.astype("<i4").view(np.uint8).reshape(
            *slots.shape, 4)
    return out


def _le_i32(p, at):
    """The LE i32 in bytes ``at``..``at + 3`` of int32-widened rows (the
    sign bit lands through ``<< 24``, so -1 padding comes back exactly)."""
    return (p[..., at] | (p[..., at + 1] << 8) | (p[..., at + 2] << 16)
            | (p[..., at + 3] << 24))


def _unpack_rows(rows):
    """Device-side unpack of :func:`pack_rows`: ``(slots, counts)`` as
    i32, the count's width read from the static trailing dimension."""
    p = rows.astype(jnp.int32)
    counts = p[..., 4] if rows.shape[-1] == 5 else _le_i32(p, 4)
    return _le_i32(p, 0), counts


def _scan_rows(decide, state, rows, nows_k, verdict_only):
    """The one scan driver: K batches of :func:`pack_rows` rows decided in
    ONE launch via ``lax.scan``, each with its own ``now`` (the
    one-timestamp-per-batch time authority), exactly as K sequential
    batch calls. ``decide(st, slots, counts, valid, now)`` is a batch
    core. Output: ``f32[K, 2, B]`` (row 0 grants as 0/1, row 1
    remaining), or with ``verdict_only`` the grants bit-packed into
    ``u8[K, B//8]``, little-endian bit order (``B % 8 == 0``; host side
    ``np.unpackbits(..., bitorder="little")``)."""
    slots_k, counts_k = _unpack_rows(rows)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = decide(st, slots, counts, slots >= 0, now)
        if verdict_only:
            return st, (granted.reshape(-1, 8).astype(jnp.uint8)
                        << jnp.arange(8, dtype=jnp.uint8)).sum(
                            axis=1, dtype=jnp.uint8)
        return st, jnp.stack([granted.astype(jnp.float32), remaining])

    return jax.lax.scan(body, state, (slots_k, counts_k, nows_k))


@partial(jax.jit, donate_argnums=0, static_argnames=("verdict_only",))
def acquire_scan(state: BucketState, rows, nows_k, capacity,
                 fill_rate_per_tick, *, verdict_only: bool = False):
    """The bulk path's token-bucket kernel: K batches of :func:`pack_rows`
    rows (``u8[K, B, 5 | 8]``, ``nows_k: i32[K]``) decided in one launch,
    one operand up and one result down (:func:`_scan_rows`).

    Returns ``(new_state, out)``: ``out`` is ``f32[K, 2, B]``, or
    ``u8[K, B//8]`` grant bits with ``verdict_only``."""
    decide = partial(acquire_core, capacity=capacity,
                     fill_rate_per_tick=fill_rate_per_tick)
    return _scan_rows(decide, state, rows, nows_k, verdict_only)


@partial(jax.jit, donate_argnums=0,
         static_argnames=("interpolate", "verdict_only"))
def window_acquire_scan(state: WindowState, rows, nows_k, limit,
                        window_ticks, *, interpolate: bool = True,
                        verdict_only: bool = False):
    """The window analogue of :func:`acquire_scan`: the same operand and
    results. ``interpolate=False`` gives fixed-window semantics."""
    decide = partial(_window_acquire_core, limit=limit,
                     window_ticks=window_ticks, interpolate=interpolate)
    return _scan_rows(decide, state, rows, nows_k, verdict_only)



@partial(jax.jit, donate_argnums=0)
def sync_batch(state: CounterState, slots, local_counts, valid, now,
               decay_rate_per_tick):
    """Batched decaying-counter sync — the approximate-bucket Lua kernel
    (``RedisApproximateTokenBucketRateLimiter.cs:216-271``) over a batch of
    global counters.

    One row per counter per flush (the host aggregates each limiter's local
    score before syncing, so duplicate slots do not occur in practice; if
    they do, decayed-value writes coincide and count adds accumulate, which
    over-counts only the EWMA, never the score).

    Returns ``(new_state, global_scores f32[B], period_ewmas f32[B])`` — the
    script's ``{new_v, new_p}`` reply (``:270``).
    """
    return _sync_core(state, slots, local_counts, valid, now,
                      decay_rate_per_tick)


def _sync_core(state: CounterState, slots, local_counts, valid, now,
               decay_rate_per_tick):
    valid = _valid_slots(slots, valid, state.value.shape[0])
    gs = _gather_slots(slots, valid)
    v_old = state.value[gs]
    p_old = state.period[gs]
    ts_old = state.last_ts[gs]
    ex_old = state.exists[gs]

    counts_f = jnp.asarray(local_counts, jnp.float32)
    decayed, new_period = bm.decay_core(
        v_old, p_old, ts_old, ex_old, now, decay_rate_per_tick
    )
    new_value = decayed + counts_f

    ss = _scatter_slots(slots, valid, state.value.shape[0])
    value_arr = state.value.at[ss].set(decayed, mode="drop")
    value_arr = value_arr.at[ss].add(counts_f * valid, mode="drop")
    period_arr = state.period.at[ss].set(new_period, mode="drop")
    ts_arr = state.last_ts.at[ss].set(jnp.asarray(now, jnp.int32), mode="drop")
    ex_arr = state.exists.at[ss].set(True, mode="drop")

    return CounterState(value_arr, period_arr, ts_arr, ex_arr), new_value, new_period


@partial(jax.jit, donate_argnums=0,
         static_argnames=("handle_duplicates", "interpolate"))
def window_acquire_batch(state: WindowState, slots, counts, valid, now, limit,
                         window_ticks, *, handle_duplicates: bool = True,
                         interpolate: bool = True):
    """Batched sliding-window acquire (BASELINE config 4).

    Same contract as :func:`acquire_batch`; grant iff the interpolated
    trailing-window estimate plus this request stays within ``limit``
    (``interpolate=False`` = fixed-window: current-window count only).
    """
    return _window_acquire_core(state, slots, counts, valid, now, limit,
                                window_ticks,
                                handle_duplicates=handle_duplicates,
                                interpolate=interpolate)


def _window_acquire_core(state: WindowState, slots, counts, valid, now, limit,
                         window_ticks, *, handle_duplicates: bool = True,
                         prefix=None, interpolate: bool = True):
    """``interpolate=True`` → sliding window (trailing-window estimate);
    ``False`` → fixed window (current-window count only — the
    ``FixedWindowRateLimiter`` family member's semantics). Same state,
    advance, atomicity, and sweep machinery either way."""
    valid = _valid_slots(slots, valid, state.prev_count.shape[0])
    gs = _gather_slots(slots, valid)
    prev_old = state.prev_count[gs]
    curr_old = state.curr_count[gs]
    idx_old = state.window_idx[gs]
    ex_old = state.exists[gs]

    counts_f = jnp.asarray(counts, jnp.float32)
    prev_new, curr_new, idx_new = bm.sliding_window_advance(
        prev_old, curr_old, idx_old, ex_old, now, window_ticks
    )
    if interpolate:
        est = bm.sliding_window_estimate(prev_new, curr_new, idx_new, now,
                                         window_ticks)
    else:
        est = curr_new

    if prefix is None and handle_duplicates:
        prefix = bm.duplicate_prefix(slots, counts, valid)
    elif prefix is None:
        prefix = jnp.zeros_like(counts_f)
    else:
        prefix = jnp.asarray(prefix, jnp.float32)

    granted = valid & (est + prefix + counts_f <= jnp.asarray(limit, jnp.float32))
    consumed = jnp.where(granted, counts_f, 0.0)
    remaining = jnp.where(
        valid,
        jnp.maximum(jnp.asarray(limit, jnp.float32) - est - prefix - consumed, 0.0),
        0.0,
    )

    ss = _scatter_slots(slots, valid, state.prev_count.shape[0])
    prev_arr = state.prev_count.at[ss].set(prev_new, mode="drop")
    curr_arr = state.curr_count.at[ss].set(curr_new, mode="drop")
    curr_arr = curr_arr.at[ss].add(consumed, mode="drop")
    idx_arr = state.window_idx.at[ss].set(idx_new, mode="drop")
    ex_arr = state.exists.at[ss].set(True, mode="drop")

    return WindowState(prev_arr, curr_arr, idx_arr, ex_arr), granted, remaining


class SemaState(NamedTuple):
    """SoA concurrency-semaphore table: ``active`` = permits currently
    held per key. No reference analogue (the reference implements only
    token buckets); this backs the ``ConcurrencyLimiter`` member of the
    ``System.Threading.RateLimiting`` family, whose leases RETURN permits
    on dispose."""

    active: jax.Array   # i32[N] held permits
    last_ts: jax.Array  # i32[N] last touch (for idle-slot sweeps)
    exists: jax.Array   # bool[N]


def init_sema_state(n: int) -> SemaState:
    return SemaState(
        active=jnp.zeros((n,), jnp.int32),
        last_ts=jnp.zeros((n,), jnp.int32),
        exists=jnp.zeros((n,), bool),
    )


@partial(jax.jit, donate_argnums=0)
def sema_batch_packed(state: SemaState, packed):
    """Atomic batched semaphore update. ``packed: i32[4, B]`` — row 0
    slots (-1 padding), row 1 signed deltas (+n acquire / -n release),
    row 2 per-row permit limits, row 3 the batch timestamp.

    Acquire (+n) grants iff ``active + same-slot-earlier-demand + n <=
    limit`` — all-or-nothing, duplicates serialized conservatively like
    the token-bucket kernels (invariant 3 at batch granularity). Release
    (-n) always applies, clamped at 0 (over-release is a caller bug the
    store must survive, not amplify). Init-on-miss: a slot with
    ``exists=False`` starts at 0 held.

    Returns ``(new_state, out f32[2, B])``: row 0 ok (0/1 — releases are
    always 1), row 1 post-op active count as seen by that row — computed
    from the same serialization prefix that admitted the row, so
    duplicate acquire rows read their own serialized value, not the
    post-batch total.

    Caller contract: a batch must not mix releases with other rows of
    the SAME slot — the state write clamps the slot's NET delta at zero,
    which would let an over-release swallow a granted acquire's permit
    (`DeviceBucketStore.concurrency_acquire_many` routes such rows
    through sequential single-op dispatches instead).
    """
    slots = packed[0]
    deltas = packed[1]
    limits = packed[2]
    now = packed[3, 0]
    valid = _valid_slots(slots, slots >= 0, state.active.shape[0])
    gs = _gather_slots(slots, valid)
    active_old = jnp.where(state.exists[gs], state.active[gs], 0)

    # Serialize same-slot rows: earlier acquires reserve, earlier releases
    # free. Net prefix = sum of earlier applied deltas, conservatively
    # approximated by granting against (active + prefix of earlier GRANTS).
    # Two-pass exact serialization would need a scan over the batch; the
    # conservative form never over-admits: treat all earlier acquires in
    # the batch as granted, ignore earlier releases for admission.
    acq = jnp.maximum(deltas, 0)
    prefix = bm.duplicate_prefix(slots, acq, valid)

    is_release = deltas < 0
    # f32 comparison (exact to 2^24 — far above any real permit limit)
    # avoids int32 overflow when a batch's worth of acquires sums large.
    fits = (active_old.astype(jnp.float32) + prefix.astype(jnp.float32)
            + acq.astype(jnp.float32)) <= limits.astype(jnp.float32)
    ok = valid & (is_release | fits)
    applied = jnp.where(ok, deltas, 0)

    ss = _scatter_slots(slots, valid, state.active.shape[0])
    active_arr = state.active.at[ss].set(active_old, mode="drop")
    active_arr = active_arr.at[ss].add(applied, mode="drop")
    active_arr = jnp.maximum(active_arr, 0)
    # delta == 0 is a read-only probe: it must not allocate the slot or
    # refresh its TTL (a monitoring poll would otherwise keep dead slots
    # alive past the sweep forever).
    touch = _scatter_slots(slots, valid & (deltas != 0),
                           state.active.shape[0])
    ts_arr = state.last_ts.at[touch].set(jnp.asarray(now, jnp.int32),
                                         mode="drop")
    ex_arr = state.exists.at[touch].set(True, mode="drop")

    # Per-row post-op view: active + earlier same-slot APPLIED deltas +
    # this row's applied delta, clamped like the state itself. Admission
    # used the conservative demand prefix above (earlier acquires count
    # whether granted or not — no scan needed, never over-admits), but
    # the REPORTED count sums only what actually landed, so a denied
    # row can never read an impossible held value above the limit. For
    # a single row per slot this equals the slot's new value.
    applied_prefix = bm.duplicate_prefix(slots, applied, valid)
    after = jnp.maximum(
        active_old.astype(jnp.float32) + applied_prefix.astype(jnp.float32)
        + applied.astype(jnp.float32), 0.0)
    out = jnp.stack([
        ok.astype(jnp.float32),
        jnp.where(valid, after, 0.0),
    ])
    return SemaState(active_arr, ts_arr, ex_arr), out


@partial(jax.jit, donate_argnums=0)
def sweep_semas(state: SemaState, now):
    """Reclaim idle semaphore slots: zero held permits AND untouched past
    the global-counter TTL (86400 s). A slot with permits still held is
    never swept — leaked permits are an operator problem (`active` reset
    requires an explicit release), not something expiry may silently
    forgive."""
    expired = state.exists & (state.active <= 0) & (
        bm.elapsed_ticks(now, state.last_ts) >= bm.GLOBAL_COUNTER_TTL_TICKS
    )
    return SemaState(
        state.active, state.last_ts, state.exists & ~expired
    ), expired


@partial(jax.jit, donate_argnums=0)
def rebase_sema_epoch(state: SemaState, offset_ticks):
    return SemaState(
        state.active,
        jnp.maximum(state.last_ts - offset_ticks, 0),
        state.exists,
    )


@partial(jax.jit, donate_argnums=(0, 1))
def acquire_hierarchical_packed(child_state: BucketState,
                                parent_state: BucketState, packed,
                                child_capacity, child_rate_per_tick,
                                parent_capacity, parent_rate_per_tick):
    """Fused two-level (tenant → key) weighted-cost admission — the
    token-denominated plane's kernel (runtime/admission.py, DESIGN.md
    §15): ONE launch gathers the child key row AND the parent tenant
    row, refills both, and grants iff BOTH levels admit, with
    both-or-neither state change (the "parent refund on child deny"
    contract, closed algebraically: neither side is debited unless the
    row is granted, and every touched slot still advances its refill
    timestamp exactly like a denied flat acquire would).

    ``packed i32[4, B]``: row 0 child slots (-1 ⇒ padding), row 1
    token costs, row 2 broadcast batch timestamp, row 3 parent slots.
    The two states are distinct tables (the store rejects identical
    child/parent configs — one donated buffer cannot be donated
    twice).

    Duplicate serialization is conservative on BOTH axes, mirroring
    the flat bulk paths' documented posture: the child prefix counts
    ALL earlier same-key demand; the parent prefix counts earlier
    same-tenant demand that the child level admitted (a
    child-admitted-but-parent-denied row still reserves ahead on its
    tenant within the batch). Exact on serial stores and whenever the
    in-call demand fits — the same latitude ``acquire_many``
    documents.

    Returns ``(child_state', parent_state', out f32[2, B])`` with
    ``out[0] = granted`` (0/1) and ``out[1] = min(child_remaining,
    parent_remaining)`` — each row's post-decision view of its binding
    constraint."""
    cslots = packed[0]
    counts = packed[1]
    now = packed[2, 0]
    pslots = packed[3]
    c_size = child_state.tokens.shape[0]
    p_size = parent_state.tokens.shape[0]
    valid = (_valid_slots(cslots, cslots >= 0, c_size)
             & _valid_slots(pslots, pslots >= 0, p_size))
    counts_f = jnp.asarray(counts, jnp.float32)

    cgs = _gather_slots(cslots, valid)
    pgs = _gather_slots(pslots, valid)
    c_ref = bm.refill_or_init(child_state.tokens[cgs],
                              child_state.last_ts[cgs],
                              child_state.exists[cgs], now,
                              child_capacity, child_rate_per_tick)
    p_ref = bm.refill_or_init(parent_state.tokens[pgs],
                              parent_state.last_ts[pgs],
                              parent_state.exists[pgs], now,
                              parent_capacity, parent_rate_per_tick)

    c_prefix = bm.duplicate_prefix(cslots, counts, valid)
    child_ok = valid & (c_ref >= c_prefix + counts_f)
    # Parent axis: only child-admitted demand reserves ahead (a row the
    # child already denied cannot double-charge its tenant's headroom).
    p_demand = jnp.where(child_ok, counts_f, 0.0)
    p_prefix = bm.duplicate_prefix(pslots, p_demand, valid)
    granted = child_ok & (p_ref >= p_prefix + counts_f)

    consumed = jnp.where(granted, counts_f, 0.0)
    c_rem = jnp.where(valid,
                      jnp.maximum(c_ref - c_prefix - consumed, 0.0), 0.0)
    p_rem = jnp.where(valid,
                      jnp.maximum(p_ref - p_prefix - consumed, 0.0), 0.0)
    remaining = jnp.minimum(c_rem, p_rem)

    css = _scatter_slots(cslots, valid, c_size)
    new_c_tokens = child_state.tokens.at[css].set(c_ref, mode="drop")
    new_c_tokens = new_c_tokens.at[css].add(-consumed, mode="drop")
    new_c_ts = child_state.last_ts.at[css].set(
        jnp.asarray(now, jnp.int32), mode="drop")
    new_c_exists = child_state.exists.at[css].set(True, mode="drop")

    pss = _scatter_slots(pslots, valid, p_size)
    new_p_tokens = parent_state.tokens.at[pss].set(p_ref, mode="drop")
    new_p_tokens = new_p_tokens.at[pss].add(-consumed, mode="drop")
    new_p_ts = parent_state.last_ts.at[pss].set(
        jnp.asarray(now, jnp.int32), mode="drop")
    new_p_exists = parent_state.exists.at[pss].set(True, mode="drop")

    out = jnp.stack([granted.astype(jnp.float32), remaining])
    return (BucketState(new_c_tokens, new_c_ts, new_c_exists),
            BucketState(new_p_tokens, new_p_ts, new_p_exists), out)


@partial(jax.jit, donate_argnums=0)
def debit_batch_packed(state: BucketState, packed, capacity,
                       fill_rate_per_tick):
    """Saturating bulk debit — the tier-0 replica reconciliation kernel.

    The native front-end's tier-0 cache admits permits locally and drains
    the accumulated counts here in one launch: refill exactly like
    :func:`acquire_batch_packed`, then subtract each row's drained amount
    clamped at zero. This is :func:`sync_batch`'s decaying-counter
    semantic mirrored onto the bucket table (``score == capacity −
    tokens``: the counter's decay-then-add is the bucket's
    refill-then-subtract, both saturating), which keeps ONE authority —
    the same table the exact fall-through path decides against — so
    tier-0 and per-request decisions reconcile without double-accounting.

    ``packed i32[3, B]``: row 0 slots (-1 ⇒ padding), row 1 the float32
    drained amounts bitcast to int32 (exact, like the counter-sync
    operand), row 2 the batch timestamp (store-stamped time,
    invariant 1). Duplicate slots are serialized conservatively via the
    demand prefix (callers pre-aggregate per key, so duplicates only
    arise from misuse and can at worst under-debit, never corrupt).

    Returns ``(new_state, out f32[2, B])``: row 0 the post-debit balance
    (each row's serialized view), row 1 the clamped shortfall — the part
    of the drained amount that found no tokens, i.e. the observed
    over-admission the sync pump surfaces as a gauge.
    """
    slots = packed[0]
    amounts = jax.lax.bitcast_convert_type(packed[1], jnp.float32)
    now = packed[2, 0]
    size = state.tokens.shape[0]
    valid = _valid_slots(slots, slots >= 0, size)
    gs = _gather_slots(slots, valid)
    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs],
                                 state.exists[gs], now, capacity,
                                 fill_rate_per_tick)
    prefix = bm.duplicate_prefix(slots, amounts, valid)
    avail = jnp.maximum(refilled - prefix, 0.0)
    applied = jnp.where(valid, jnp.minimum(amounts, avail), 0.0)
    shortfall = jnp.where(valid, amounts - applied, 0.0)
    remaining = jnp.where(valid, avail - applied, 0.0)

    ss = _scatter_slots(slots, valid, size)
    new_tokens = state.tokens.at[ss].set(refilled, mode="drop")
    new_tokens = new_tokens.at[ss].add(-applied, mode="drop")
    new_last_ts = state.last_ts.at[ss].set(jnp.asarray(now, jnp.int32),
                                           mode="drop")
    new_exists = state.exists.at[ss].set(True, mode="drop")
    out = jnp.stack([remaining, shortfall])
    return BucketState(new_tokens, new_last_ts, new_exists), out


@partial(jax.jit, donate_argnums=0)
def sync_batch_packed(state: CounterState, packed, decay_rate_per_tick):
    """:func:`sync_batch` with single-transfer operands/results. The
    counter-sync operand is i32[3, B] (unlike the acquire kernels' i32[4, B]
    — there is no duplicate-prefix row here): row 0 slots, row 1 the
    float32 local counts bitcast to int32 (exact — no quantization), row 2
    the timestamp. The reply is ``f32[2, B]`` = (global scores, period
    EWMAs), the Lua ``{new_v, new_p}`` pair in one readback."""
    slots = packed[0]
    local_counts = jax.lax.bitcast_convert_type(packed[1], jnp.float32)
    now = packed[2, 0]
    valid = slots >= 0
    new_state, scores, periods = _sync_core(
        state, slots, local_counts, valid, now, decay_rate_per_tick
    )
    return new_state, jnp.stack([scores, periods])


@partial(jax.jit, donate_argnums=0, static_argnames=("interpolate",))
def window_acquire_batch_packed(state: WindowState, packed, limit,
                                window_ticks, *, interpolate: bool = True):
    """:func:`window_acquire_batch` with the single-transfer operand/result
    convention of :func:`acquire_batch_packed`."""
    slots, counts, valid, now, prefix = _unpack_requests(packed)
    new_state, granted, remaining = _window_acquire_core(
        state, slots, counts, valid, now, limit, window_ticks,
        prefix=prefix, interpolate=interpolate,
    )
    out = jnp.stack([granted.astype(jnp.float32), remaining])
    return new_state, out


@partial(jax.jit, donate_argnums=0, static_argnames=("interpolate",))
def window_acquire_batch_packed_grouped(state: WindowState, packed, limit,
                                        window_ticks, *,
                                        interpolate: bool = True):
    """Coalesced-duplicates window flush — the window-table analogue of
    :func:`acquire_batch_packed_grouped` (same ``packed i32[5, B]``
    layout). Grant rule per group: ``n_granted = clamp(floor((limit −
    est − prefix) / c), 0, n)`` (``c == 0`` probes grant all ``n`` iff the
    window estimate plus prefix still fits the limit), bit-identical to
    ``n`` per-row decisions with cumulative prefixes.

    Returns ``(new_state, out f32[2, B])``: ``out[0] = n_granted``,
    ``out[1] = post-consumption remaining``.
    """
    slots = packed[0]
    counts = packed[1]
    now = packed[2, 0]
    prefix = jnp.asarray(packed[3], jnp.float32)
    n_reqs = packed[4]
    size = state.prev_count.shape[0]
    valid = _valid_slots(slots, slots >= 0, size)
    gs = _gather_slots(slots, valid)

    prev_new, curr_new, idx_new = bm.sliding_window_advance(
        state.prev_count[gs], state.curr_count[gs], state.window_idx[gs],
        state.exists[gs], now, window_ticks,
    )
    if interpolate:
        est = bm.sliding_window_estimate(prev_new, curr_new, idx_new, now,
                                         window_ticks)
    else:
        est = curr_new

    c = jnp.asarray(counts, jnp.float32)
    n = jnp.asarray(n_reqs, jnp.float32)
    avail = jnp.asarray(limit, jnp.float32) - est - prefix
    n_granted = jnp.where(
        c > 0,
        jnp.clip(jnp.floor(avail / jnp.maximum(c, 1.0)), 0.0, n),
        jnp.where(avail >= 0, n, 0.0),
    )
    n_granted = jnp.where(valid, n_granted, 0.0)
    consumed = n_granted * c
    remaining = jnp.where(valid, jnp.maximum(avail - consumed, 0.0), 0.0)

    ss = _scatter_slots(slots, valid, size)
    prev_arr = state.prev_count.at[ss].set(prev_new, mode="drop")
    curr_arr = state.curr_count.at[ss].set(curr_new, mode="drop")
    curr_arr = curr_arr.at[ss].add(consumed, mode="drop")
    idx_arr = state.window_idx.at[ss].set(idx_new, mode="drop")
    ex_arr = state.exists.at[ss].set(True, mode="drop")
    out = jnp.stack([n_granted, remaining])
    return WindowState(prev_arr, curr_arr, idx_arr, ex_arr), out


@partial(jax.jit, donate_argnums=0)
def sweep_expired(state: BucketState, now, capacity, fill_rate_per_tick):
    """TTL eviction pass — invariant 5 (state self-expiry, bounded memory).

    A slot whose bucket has been idle past its time-to-full-refill TTL
    (clamped ``[1s, 1yr]``, ``RedisTokenBucketRateLimiter.cs:234-235``) is
    indistinguishable from init-on-miss, so `exists` is simply cleared. One
    vectorized pass over the whole table; the host runs it on a slow cadence
    (it also bounds int32 tick staleness far below wraparound).

    Returns ``(new_state, freed bool[N])`` — `freed` lets the host directory
    reclaim slot ids.
    """
    ttl = bm.time_to_full_ttl(state.tokens, capacity, fill_rate_per_tick)
    expired = state.exists & (bm.elapsed_ticks(now, state.last_ts) >= ttl)
    new_exists = state.exists & ~expired
    return BucketState(state.tokens, state.last_ts, new_exists), expired


@jax.jit
def peek_batch(state: BucketState, slots, valid, now, capacity,
               fill_rate_per_tick):
    """Read-only availability estimate (``GetAvailablePermits`` support,
    invariant 7) — refill math applied without writing state back."""
    valid = _valid_slots(slots, valid, state.tokens.shape[0])
    gs = _gather_slots(slots, valid)
    refilled = bm.refill_or_init(
        state.tokens[gs], state.last_ts[gs], state.exists[gs], now, capacity,
        fill_rate_per_tick,
    )
    return jnp.where(valid, jnp.floor(refilled), 0.0)


@jax.jit
def peek_batch_packed(state: BucketState, packed, capacity,
                      fill_rate_per_tick):
    """:func:`peek_batch` with the packed operand convention (rows 1/3 of
    ``packed`` are ignored — peeks carry no counts)."""
    slots, _, valid, now, _ = _unpack_requests(packed)
    valid = _valid_slots(slots, valid, state.tokens.shape[0])
    gs = _gather_slots(slots, valid)
    refilled = bm.refill_or_init(
        state.tokens[gs], state.last_ts[gs], state.exists[gs], now, capacity,
        fill_rate_per_tick,
    )
    return jnp.where(valid, jnp.floor(refilled), 0.0)


@partial(jax.jit, donate_argnums=0)
def sweep_counters(state: CounterState, now):
    """TTL eviction for the decaying-counter table: fixed 86400 s TTL, the
    reference's ``EXPIRE`` on the global counter hash
    (``RedisApproximateTokenBucketRateLimiter.cs:268``)."""
    expired = state.exists & (
        bm.elapsed_ticks(now, state.last_ts) >= bm.GLOBAL_COUNTER_TTL_TICKS
    )
    return CounterState(
        state.value, state.period, state.last_ts, state.exists & ~expired
    ), expired


@partial(jax.jit, donate_argnums=0)
def sweep_windows(state: WindowState, now, window_ticks):
    """TTL eviction for the sliding-window table: a slot idle for two full
    windows carries no information (both counters would roll to zero)."""
    idx_now = jnp.asarray(now, jnp.int32) // jnp.asarray(window_ticks, jnp.int32)
    expired = state.exists & (idx_now - state.window_idx >= 2)
    return WindowState(
        state.prev_count, state.curr_count, state.window_idx,
        state.exists & ~expired,
    ), expired


@partial(jax.jit, donate_argnums=0)
def rebase_bucket_epoch(state: BucketState, offset_ticks):
    """Shift every timestamp back by ``offset_ticks`` — the host calls this
    (and rebases its clock epoch identically) before int32 tick time can
    overflow (~24 days of uptime at 1024 ticks/s). Elapsed values are
    invariant under the joint shift."""
    new_ts = jnp.where(
        state.exists,
        jnp.maximum(state.last_ts - jnp.asarray(offset_ticks, jnp.int32), 0),
        state.last_ts,
    )
    return BucketState(state.tokens, new_ts, state.exists)


@partial(jax.jit, donate_argnums=0)
def rebase_counter_epoch(state: CounterState, offset_ticks):
    new_ts = jnp.where(
        state.exists,
        jnp.maximum(state.last_ts - jnp.asarray(offset_ticks, jnp.int32), 0),
        state.last_ts,
    )
    return CounterState(state.value, state.period, new_ts, state.exists)


@partial(jax.jit, donate_argnums=0)
def rebase_window_epoch(state: WindowState, offset_windows):
    """Epoch rebase for window tables: indices shift by whole windows
    (``offset_windows = offset_ticks // window_ticks``, host-computed). The
    sub-window phase remainder introduces at most one window of boundary
    skew, once per rebase (~6 days) — without this the advance clamp would
    pin old indices forever and freeze those keys."""
    new_idx = jnp.where(
        state.exists,
        jnp.maximum(state.window_idx - jnp.asarray(offset_windows, jnp.int32), 0),
        state.window_idx,
    )
    return WindowState(state.prev_count, state.curr_count, new_idx, state.exists)
