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
  within one batch are serialized conservatively via
  :func:`~.bucket_math.duplicate_prefix` (never over-admit) — a sort-based
  O(B log B) pass cheap enough to run unconditionally.

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
    "acquire_scan_compact",
    "acquire_scan_compact_packed",
    "acquire_scan_compact_bits",
    "acquire_scan_packed24",
    "pack_slots24",
    "SLOT24_PAD",
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
    "window_acquire_scan_compact",
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
    gs = _gather_slots(slots, valid)
    t_old = state.tokens[gs]
    ts_old = state.last_ts[gs]
    ex_old = state.exists[gs]

    counts_f = jnp.asarray(counts, jnp.float32)
    refilled = bm.refill_or_init(t_old, ts_old, ex_old, now, capacity,
                                 fill_rate_per_tick)

    if prefix is None and handle_duplicates:
        prefix = bm.duplicate_prefix(slots, counts, valid)
    elif prefix is None:
        prefix = jnp.zeros_like(counts_f)
    else:
        prefix = jnp.asarray(prefix, jnp.float32)

    granted = valid & (refilled >= prefix + counts_f)
    consumed = jnp.where(granted, counts_f, 0.0)
    remaining = jnp.where(valid, jnp.maximum(refilled - prefix - consumed, 0.0), 0.0)

    ss = _scatter_slots(slots, valid, state.tokens.shape[0])
    # Duplicates all write the identical refilled value (same now, same old
    # state), then consumption accumulates via scatter-add.
    new_tokens = state.tokens.at[ss].set(refilled, mode="drop")
    new_tokens = new_tokens.at[ss].add(-consumed, mode="drop")
    new_last_ts = state.last_ts.at[ss].set(
        jnp.asarray(now, jnp.int32), mode="drop"
    )
    new_exists = state.exists.at[ss].set(True, mode="drop")

    return BucketState(new_tokens, new_last_ts, new_exists), granted, remaining


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
    counts = packed[1]
    now = packed[2, 0]
    prefix = jnp.asarray(packed[3], jnp.float32)
    n_reqs = packed[4]
    size = state.tokens.shape[0]
    valid = _valid_slots(slots, slots >= 0, size)
    gs = _gather_slots(slots, valid)

    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs],
                                 state.exists[gs], now, capacity,
                                 fill_rate_per_tick)
    c = jnp.asarray(counts, jnp.float32)
    n = jnp.asarray(n_reqs, jnp.float32)
    avail = refilled - prefix
    n_granted = jnp.where(
        c > 0,
        jnp.clip(jnp.floor(avail / jnp.maximum(c, 1.0)), 0.0, n),
        # c == 0 probe group: granted iff the balance covers the prefix —
        # the same `refilled >= prefix + 0` rule as the per-row kernel.
        jnp.where(avail >= 0, n, 0.0),
    )
    n_granted = jnp.where(valid, n_granted, 0.0)
    consumed = n_granted * c
    remaining = jnp.where(valid, jnp.maximum(avail - consumed, 0.0), 0.0)

    ss = _scatter_slots(slots, valid, size)
    new_tokens = state.tokens.at[ss].set(refilled, mode="drop")
    new_tokens = new_tokens.at[ss].add(-consumed, mode="drop")
    new_last_ts = state.last_ts.at[ss].set(jnp.asarray(now, jnp.int32),
                                           mode="drop")
    new_exists = state.exists.at[ss].set(True, mode="drop")
    out = jnp.stack([n_granted, remaining])
    return BucketState(new_tokens, new_last_ts, new_exists), out


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan(state: BucketState, slots_k, counts_k, valid_k, nows_k,
                 capacity, fill_rate_per_tick, *,
                 handle_duplicates: bool = True):
    """Pipelined dispatch: K micro-batches decided in ONE kernel launch via
    ``lax.scan`` — amortizes launch overhead when the host has several
    flushes queued. Semantics are identical to K sequential
    :func:`acquire_batch` calls: each scanned batch keeps its own ``now``
    operand (``nows_k[k]``), preserving the one-timestamp-per-batch
    time-authority property.

    Shapes: ``slots_k/counts_k/valid_k: [K, B]``, ``nows_k: i32[K]``.
    Returns ``(new_state, granted [K, B], remaining [K, B])``.
    """

    def body(st, xs):
        slots, counts, valid, now = xs
        st, granted, remaining = acquire_core(
            st, slots, counts, valid, now, capacity, fill_rate_per_tick,
            handle_duplicates=handle_duplicates,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, counts_k, valid_k, nows_k)
    )
    return state, granted, remaining


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_compact(state: BucketState, slots_k, counts_k, nows_k,
                         capacity, fill_rate_per_tick, *,
                         handle_duplicates: bool = True):
    """Transfer-minimal scanned dispatch for the throughput path.

    Designed for a host→device *transfer*-bound pipeline (not measured on
    the chip), where sustained throughput ≈ link bandwidth / bytes-per-
    decision. This variant ships 5 bytes/decision (i32 slot + u8 count;
    validity is ``slots >= 0``, so no mask array travels) versus 9-16 for
    the split/packed layouts. The in-kernel duplicate sort is kept ON by
    default: it preserves invariant 3 exactly.

    ``counts_k: u8[K, B]`` caps per-request permits at 255 on this path;
    larger requests belong on the packed serving path
    (:func:`acquire_batch_packed`, i32 counts).

    Shapes: ``slots_k: i32[K, B]``, ``counts_k: u8[K, B]``,
    ``nows_k: i32[K]``. Returns ``(new_state, granted bool[K, B],
    remaining f32[K, B])``.
    """

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = acquire_core(
            st, slots, counts.astype(jnp.int32), slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, counts_k, nows_k)
    )
    return state, granted, remaining


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_compact_packed(state: BucketState, slots_k, counts_k,
                                nows_k, capacity, fill_rate_per_tick, *,
                                handle_duplicates: bool = True):
    """:func:`acquire_scan_compact` with a SINGLE packed result array.

    The bulk serving path resolves a whole call with ONE device→host
    fetch: ``out f32[K, 2, B]`` stacks ``granted``
    (0/1, row 0) and ``remaining`` (row 1) per scanned batch. Same
    decision semantics as the unpacked variant.

    Returns ``(new_state, out f32[K, 2, B])``.
    """

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = acquire_core(
            st, slots, counts.astype(jnp.int32), slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        return st, jnp.stack([granted.astype(jnp.float32), remaining])

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_compact_bits(state: BucketState, slots_k, counts_k,
                              nows_k, capacity, fill_rate_per_tick, *,
                              handle_duplicates: bool = True):
    """Verdict-only scanned dispatch: grants return BIT-PACKED.

    For bulk callers that don't need per-request ``remaining`` (admission
    gates), the result shrinks from 8 bytes/decision to 1 *bit*/decision —
    ``out u8[K, B//8]``, little-endian bit order (host side:
    ``np.unpackbits(..., bitorder="little")``).
    Requires ``B % 8 == 0`` (every batch size here is a power of two).

    Returns ``(new_state, grant_bits u8[K, B//8])``.
    """

    def body(st, xs):
        slots, counts, now = xs
        st, granted, _ = acquire_core(
            st, slots, counts.astype(jnp.int32), slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        bits = (granted.reshape(-1, 8).astype(jnp.uint8)
                << jnp.arange(8, dtype=jnp.uint8)).sum(
                    axis=1, dtype=jnp.uint8)
        return st, bits

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


def _unpack_compact5(fused):
    """Device-side unpack of the :func:`pack_compact5` layout: LE i32 slot
    from bytes 0-3 (int32 bit-ops land -1 padding exactly via the sign bit
    in ``<<24``), count from byte 4."""
    p = fused.astype(jnp.int32)
    slots_k = (p[..., 0] | (p[..., 1] << 8) | (p[..., 2] << 16)
               | (p[..., 3] << 24))
    return slots_k, p[..., 4]


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_fused_bits(state: BucketState, fused, nows_k, capacity,
                            fill_rate_per_tick, *,
                            handle_duplicates: bool = True):
    """The bulk serving path's minimum-transfer dispatch: ONE fused
    operand up (:func:`pack_compact5`, 5 bytes/decision), ONE bit-packed
    result down (1 bit/decision): one transfer each way per dispatch.

    Returns ``(new_state, grant_bits u8[K, B//8])`` (little-endian bit
    order, ``B % 8 == 0``)."""
    slots_k, counts_k = _unpack_compact5(fused)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, _ = acquire_core(
            st, slots, counts, slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        bits = (granted.reshape(-1, 8).astype(jnp.uint8)
                << jnp.arange(8, dtype=jnp.uint8)).sum(
                    axis=1, dtype=jnp.uint8)
        return st, bits

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_fused_packed(state: BucketState, fused, nows_k, capacity,
                              fill_rate_per_tick, *,
                              handle_duplicates: bool = True):
    """Fused-input variant of :func:`acquire_scan_compact_packed`: one
    operand up, one ``f32[K, 2, B]`` result down (row 0 grants, row 1
    remaining)."""
    slots_k, counts_k = _unpack_compact5(fused)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = acquire_core(
            st, slots, counts, slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        return st, jnp.stack([granted.astype(jnp.float32), remaining])

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


#: Padding sentinel for the 24-bit packed slot layout (all-ones 24 bits).
SLOT24_PAD = (1 << 24) - 1


def pack_compact5(slots, counts):
    """Host-side packing for :func:`acquire_scan_compact_fused`: i32 slot
    ids (-1 = padding) + u8 counts → little-endian u8[..., 5] (bytes 0-3
    the slot, byte 4 the count). One array per dispatch: the 5-byte
    layout travels as ONE operand (the per-transfer cost on the chip is
    not measured)."""
    import numpy as np

    slots = np.asarray(slots, np.int32)
    out = np.empty((*slots.shape, 5), np.uint8)
    out[..., :4] = slots.astype("<i4").view(np.uint8).reshape(
        *slots.shape, 4)
    out[..., 4] = counts
    return out


def pack_slots24(slots):
    """Host-side packing for :func:`acquire_scan_packed24`: i32 slot ids
    (or ``SLOT24_PAD`` for padding rows) → little-endian u8[..., 3].
    Vectorized numpy; ~0.8ms for a [32, 8192] stage — off the device
    critical path (staging overlaps dispatches)."""
    import numpy as np

    slots = np.asarray(slots)
    if slots.size and (slots.min() < 0 or slots.max() > SLOT24_PAD):
        # Out-of-range ids would silently truncate to SOME in-range slot —
        # debiting an unrelated key's bucket. Fail at pack time instead.
        raise ValueError(
            f"slot ids must be within [0, {SLOT24_PAD}] (SLOT24_PAD = "
            "padding); use acquire_scan_compact for larger tables"
        )
    out = np.empty((*slots.shape, 3), np.uint8)
    out[..., 0] = slots & 0xFF
    out[..., 1] = (slots >> 8) & 0xFF
    out[..., 2] = (slots >> 16) & 0xFF
    return out


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_compact_fused(state: BucketState, fused, nows_k, capacity,
                               fill_rate_per_tick, *,
                               handle_duplicates: bool = True):
    """:func:`acquire_scan_compact` with slots + counts fused into ONE
    operand array: ``fused u8[K, B, 5]`` from :func:`pack_compact5`.
    Decision semantics identical; transfer count per dispatch drops from
    two arrays to one. Padding rows carry slot -1 (all-ones bytes 0-3).

    Returns ``(new_state, granted bool[K, B], remaining f32[K, B])``.
    """
    slots_k, counts_k = _unpack_compact5(fused)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = acquire_core(
            st, slots, counts, slots >= 0, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, counts_k, nows_k)
    )
    return state, granted, remaining


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def acquire_scan_packed24(state: BucketState, packed, nows_k, capacity,
                          fill_rate_per_tick, *,
                          handle_duplicates: bool = True):
    """Minimum-transfer scanned dispatch: 3 bytes per decision.

    For a host→device transfer-bound pipeline (not measured on the
    chip), the headline throughput path packs each unit-permit request
    into a 24-bit slot id:
    ``packed: u8[K, B, 3]`` little-endian, :data:`SLOT24_PAD` = padding.
    Requires ``n_slots < 2**24 - 1`` (16.7M keys/table — the 10M-key
    BASELINE target fits; larger tables use :func:`acquire_scan_compact`).

    Every request asks exactly 1 permit — the canonical rate-limit
    request. Mixed-count batches belong on the compact or packed paths.
    Duplicate serialization stays ON by default: invariant 3 holds
    exactly.

    Returns ``(new_state, granted bool[K, B], remaining f32[K, B])``.
    """
    p = packed.astype(jnp.int32)
    slots_k = p[..., 0] | (p[..., 1] << 8) | (p[..., 2] << 16)

    def body(st, xs):
        slots, now = xs
        valid = slots != SLOT24_PAD
        st, granted, remaining = acquire_core(
            st, slots, jnp.ones_like(slots), valid, now, capacity,
            fill_rate_per_tick, handle_duplicates=handle_duplicates,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, nows_k)
    )
    return state, granted, remaining


@partial(jax.jit, donate_argnums=0,
         static_argnames=("handle_duplicates", "interpolate"))
def window_acquire_scan(state: WindowState, slots_k, counts_k, valid_k,
                        nows_k, limit, window_ticks, *,
                        handle_duplicates: bool = True,
                        interpolate: bool = True):
    """Pipelined sliding-window dispatch: K micro-batches in ONE launch via
    ``lax.scan`` — the window analogue of :func:`acquire_scan`, with the
    same per-batch ``now`` time-authority property. ``interpolate=False``
    gives fixed-window semantics."""

    def body(st, xs):
        slots, counts, valid, now = xs
        st, granted, remaining = _window_acquire_core(
            st, slots, counts, valid, now, limit, window_ticks,
            handle_duplicates=handle_duplicates, interpolate=interpolate,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, counts_k, valid_k, nows_k)
    )
    return state, granted, remaining


@partial(jax.jit, donate_argnums=0,
         static_argnames=("handle_duplicates", "interpolate"))
def window_acquire_scan_fused_bits(state: WindowState, fused, nows_k,
                                   limit, window_ticks, *,
                                   handle_duplicates: bool = True,
                                   interpolate: bool = True):
    """Verdict-only fused window dispatch: 1 bit/decision down (the window
    analogue of :func:`acquire_scan_fused_bits`; ``B % 8 == 0``)."""
    slots_k, counts_k = _unpack_compact5(fused)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, _ = _window_acquire_core(
            st, slots, counts, slots >= 0, now, limit, window_ticks,
            handle_duplicates=handle_duplicates, interpolate=interpolate,
        )
        bits = (granted.reshape(-1, 8).astype(jnp.uint8)
                << jnp.arange(8, dtype=jnp.uint8)).sum(
                    axis=1, dtype=jnp.uint8)
        return st, bits

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


@partial(jax.jit, donate_argnums=0,
         static_argnames=("handle_duplicates", "interpolate"))
def window_acquire_scan_fused_packed(state: WindowState, fused, nows_k,
                                     limit, window_ticks, *,
                                     handle_duplicates: bool = True,
                                     interpolate: bool = True):
    """The window bulk path's minimum-transfer dispatch: ONE fused operand
    up (:func:`pack_compact5`), ONE ``f32[K, 2, B]`` result down (row 0
    grants, row 1 remaining) — the window analogue of
    :func:`acquire_scan_fused_packed`. ``interpolate=False`` = fixed
    windows."""
    slots_k, counts_k = _unpack_compact5(fused)

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = _window_acquire_core(
            st, slots, counts, slots >= 0, now, limit, window_ticks,
            handle_duplicates=handle_duplicates, interpolate=interpolate,
        )
        return st, jnp.stack([granted.astype(jnp.float32), remaining])

    state, out = jax.lax.scan(body, state, (slots_k, counts_k, nows_k))
    return state, out


@partial(jax.jit, donate_argnums=0, static_argnames=("handle_duplicates",))
def window_acquire_scan_compact(state: WindowState, slots_k, counts_k,
                                nows_k, limit, window_ticks, *,
                                handle_duplicates: bool = True):
    """Transfer-minimal scanned sliding-window dispatch — the window
    analogue of :func:`acquire_scan_compact`: 5 bytes/decision (i32 slot +
    u8 count), validity implied by slot sign, per-batch ``now`` operands."""

    def body(st, xs):
        slots, counts, now = xs
        st, granted, remaining = _window_acquire_core(
            st, slots, counts.astype(jnp.int32), slots >= 0, now, limit,
            window_ticks, handle_duplicates=handle_duplicates,
        )
        return st, (granted, remaining)

    state, (granted, remaining) = jax.lax.scan(
        body, state, (slots_k, counts_k, nows_k)
    )
    return state, granted, remaining


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
