"""Device-resident key directory: probe/insert on fingerprints, in-kernel.

The classic store keeps key→slot routing on the host (`runtime/directory.py`
+ ``native/directory.cc``) and ships resolved slot ids to the device. This
module moves the directory INTO device memory — the "device-side
hashing/eviction/TTL without host round-trips per key" hard part called out
in SURVEY.md §7: the host's entire per-batch duty shrinks to one hashing
pass (``dir_fp64_pylist`` — 64-bit FNV-1a fingerprints), and the kernel
itself finds-or-claims each key's slot against a fingerprint table in HBM,
fused with the refill-and-decrement decision.

Design (all shapes static, XLA-friendly — no data-dependent control flow):

- **Table**: ``fp: u32[N, 2]`` — (lo, hi) halves of each slot's key
  fingerprint; ``(0, 0)`` means EMPTY (the host hasher never emits it).
  Bucket state stays the ordinary :class:`~.kernels.BucketState`; a freshly
  claimed slot keeps ``exists=False`` so the decision kernel's init-on-miss
  (invariant: ``RedisTokenBucketRateLimiter.cs:210-215``) initializes the
  bucket — insert only writes the fingerprint.
- **Probe**: each request scans a fixed window of ``L`` cells starting at
  ``mix(fp) % N`` (one ``[B, L, 2]`` gather). Full-window scans make
  deletion trivially safe: clearing a cell cannot hide a key placed later
  in the window, because lookups never early-stop at an empty cell (the
  tombstone problem of classic linear probing does not arise).
- **Insert**: unresolved requests claim their window's first empty cell by
  scattering their fingerprint ROW (``[B, 2]`` into ``[N, 2]`` — one
  scatter, so a contested cell ends up with exactly one winner's coherent
  pair) and re-gathering to see who won. Losers retry next round against
  the updated occupancy; duplicates of the same new key pick the same cell
  and all "win" (identical fingerprint). ``R`` rounds bound the retries;
  requests still unresolved after ``R`` (pathological window pressure)
  come back with slot ``-1`` — the caller denies and reports, and the
  host can grow/sweep before the next batch.
- **Sweep**: expired buckets (same TTL rule as :func:`~.kernels
  .sweep_expired`) get BOTH ``exists`` and their fingerprint cleared — the
  table self-expires with zero host bookkeeping (no free-lists).

Collision disclosure: two distinct keys share a bucket iff their 64-bit
fingerprints collide (probability ≈ n²/2⁶⁵ — about 3·10⁻⁶ at 10M keys);
the classic host directory compares full key bytes and has no such case.
The trade is explicit: this path removes the host table (RAM, insert cost,
growth machinery) and its per-batch resolve from the serving path.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributedratelimiting.redis_tpu.ops import bucket_math as bm
from distributedratelimiting.redis_tpu.ops import kernels as K

__all__ = [
    "init_fp_table",
    "fp_resolve_core",
    "fp_acquire_batch",
    "fp_acquire_scan_fused",
    "fp_acquire_scan_fused_bits",
    "pack_fp12",
    "fp_debit_batch",
    "fp_peek_batch",
    "fp_migrate_chunk",
    "fp_sweep_expired",
    "fp_window_acquire_batch",
    "fp_window_acquire_scan_fused",
    "fp_window_acquire_scan_fused_bits",
    "fp_migrate_window_chunk",
    "fp_sweep_windows",
    "FpResolveOut",
]

#: Golden-ratio multiplier for the lo/hi mix → base probe index. Plain
#: int, NOT a jnp scalar: a module-level jnp constant would initialize
#: the backend at import time, taking the chip in every process that
#: imports the package (one process per chip).
_MIX = 0x9E3779B1

#: Placement-mapping version carried in checkpoints: v2 = non-wrapping
#: windows (base = h % (n - L + 1)); v1/absent = the old
#: wrapping h % n. Restores re-place entries from a different placement
#: through the migrate kernel instead of installing tables verbatim.
PLACEMENT_VERSION = 2


def init_fp_table(n: int) -> jax.Array:
    """Empty fingerprint table: ``u32[n, 2]`` of zeros."""
    return jnp.zeros((n, 2), jnp.uint32)


class FpResolveOut(NamedTuple):
    fp: jax.Array        # u32[N, 2] — table after inserts
    slots: jax.Array     # i32[B] — resolved slot per request, -1 unresolved
    resolved: jax.Array  # bool[B] — False only under window pressure


def _base_index(kpair, n: int, probe_window: int):
    # np.uint32, not a bare int (jit would parse it int32 → overflow) and
    # not jnp.uint32 at module scope (import-time backend init, above).
    # Bases land in [0, n - L]: the probe window NEVER wraps, so every
    # window is L consecutive cells. The last L-1 cells are reachable only
    # as window tails, a negligible uniformity trade.
    h = kpair[:, 0] * np.uint32(_MIX) ^ kpair[:, 1]
    return (h % jnp.uint32(n - probe_window + 1)).astype(jnp.int32)


def _window_cells(fp, base, probe_window: int):
    """Gather each request's contiguous probe window, ``[B, L, 2]``
    starting at row ``base``, as one gather of ``B·L`` single rows. A
    slice-gather of ``(L, 2)`` windows reads the same cells, but XLA's
    TPU backend lowers it to a loop over the ``B`` rows (~5.6 ms for a
    4,096-row launch at 2^26 slots on a v5e, PERF.md §5)."""
    widx = base[:, None] + jnp.arange(probe_window, dtype=jnp.int32)[None, :]
    return fp.at[widx].get(mode="promise_in_bounds")


def fp_resolve_core(fp, kpair, valid, *, probe_window: int,
                    rounds: int) -> FpResolveOut:
    """Find-or-claim a slot for each fingerprint (traceable core).

    Args:
      fp: ``u32[N, 2]`` table.
      kpair: ``u32[B, 2]`` request fingerprints (never ``(0, 0)``).
      valid: ``bool[B]`` — padding rows neither match nor insert.
      probe_window: cells scanned per request (static).
      rounds: insert retry rounds (static; ≥1).
    """
    n = fp.shape[0]
    b = kpair.shape[0]
    # Static (trace-time) guard: the non-wrapping placement needs at
    # least one full window; smaller tables would wrap the uint32
    # modulus in _base_index into garbage bases silently.
    assert n >= probe_window, (
        f"fp table of {n} slots is smaller than probe_window "
        f"{probe_window}")
    rows = jnp.arange(b, dtype=jnp.int32)
    base = _base_index(kpair, n, probe_window)
    # [B, L] candidate cells (contiguous, non-wrapping window).
    widx = base[:, None] + jnp.arange(probe_window, dtype=jnp.int32)[None, :]

    slots = jnp.full((b,), -1, jnp.int32)
    resolved = ~valid  # padding rows are "done" (slot stays -1)

    def probe(fp, slots, resolved):
        """Match pass: find each unresolved request's cell if present."""
        cells = _window_cells(fp, base, probe_window)   # [B, L, 2]
        occ = (cells != 0).any(-1)              # [B, L]
        match = (occ
                 & (cells[..., 0] == kpair[:, None, 0])
                 & (cells[..., 1] == kpair[:, None, 1]))
        hit = match.any(1) & ~resolved
        hpos = jnp.argmax(match, axis=1).astype(jnp.int32)
        slots = jnp.where(hit, widx[rows, hpos], slots)
        return slots, resolved | hit, occ

    # Steady-state fast path: one pure gather resolves every present key.
    # The insert machinery (scatter + verify re-gather, the expensive part
    # of this kernel) runs ONLY while some request is still unresolved —
    # a `while_loop` whose condition reduces on device, so a warm serving
    # batch costs one probe gather and zero insert rounds.
    slots, resolved, _ = probe(fp, slots, resolved)

    def round_needed(carry):
        _, _, resolved, r = carry
        return (r < rounds) & ~resolved.all()

    # Per-KEY free-cell preference: contenders sharing a window spread
    # across its free cells instead of all fighting for argmax(free) (one
    # winner per round — pathological when n is close to L and every base
    # collapses to the same window). Derived from the fingerprint, not
    # the row, so in-batch duplicates of one new key still pick the SAME
    # cell and all win its insert (docstring contract).
    pref = ((kpair[:, 0] ^ (kpair[:, 1] * np.uint32(0x85EBCA6B)))
            % jnp.uint32(probe_window)).astype(jnp.int32)
    lane = jnp.arange(probe_window, dtype=jnp.int32)[None, :]
    rot_idx = (pref[:, None] + lane) % probe_window  # [B, L]

    def insert_round(carry):
        fp, slots, resolved, r = carry
        slots, resolved, occ = probe(fp, slots, resolved)
        free = ~occ
        has_free = free.any(1)
        need = ~resolved & has_free
        free_rot = jnp.take_along_axis(free, rot_idx, axis=1)
        first = jnp.argmax(free_rot, axis=1).astype(jnp.int32)
        tpos = jnp.take_along_axis(rot_idx, first[:, None], axis=1)[:, 0]
        target = jnp.where(need, widx[rows, tpos], n)  # n ⇒ dropped
        # One scatter of whole (lo, hi) ROWS: a contested cell gets one
        # winner's coherent pair (two per-half scatters could interleave
        # different writers into a fingerprint that belongs to no key).
        fp = fp.at[target].set(kpair, mode="drop")
        got = fp[jnp.where(need, target, 0)]
        won = need & (got == kpair).all(-1)
        slots = jnp.where(won, target, slots)
        resolved = resolved | won
        return fp, slots, resolved, r + 1

    fp, slots, resolved, _ = jax.lax.while_loop(
        round_needed, insert_round,
        (fp, slots, resolved, jnp.int32(0)))
    return FpResolveOut(fp, slots, resolved)


def _fp_acquire_core(fp, state, kpair, counts, valid, now, capacity,
                     fill_rate_per_tick, *, probe_window: int, rounds: int,
                     handle_duplicates: bool):
    out = fp_resolve_core(fp, kpair, valid, probe_window=probe_window,
                          rounds=rounds)
    live = valid & out.resolved
    state, granted, remaining = K.acquire_core(
        state, out.slots, counts, live, now, capacity, fill_rate_per_tick,
        handle_duplicates=handle_duplicates)
    return out.fp, state, granted, remaining, out.resolved


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates"))
def fp_acquire_batch(fp, state: K.BucketState, kpair, counts, valid, now,
                     capacity, fill_rate_per_tick, *, probe_window: int = 16,
                     rounds: int = 4, handle_duplicates: bool = True):
    """Fused directory-resolve + refill-and-decrement: ONE kernel launch
    decides a batch straight from key fingerprints — the whole Lua-script
    role (``RedisTokenBucketRateLimiter.cs:176-239``) including the key
    lookup Redis does in its hash table before the script body runs.

    Returns ``(fp, state, granted, remaining, resolved)``; unresolved rows
    (window pressure, see module docstring) are denied with
    ``remaining = 0`` and reported so the host can sweep/grow.
    """
    return _fp_acquire_core(fp, state, kpair, counts, valid, now, capacity,
                            fill_rate_per_tick, probe_window=probe_window,
                            rounds=rounds,
                            handle_duplicates=handle_duplicates)


def pack_fp12(fps: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Host-side packing for the fused fp dispatches: ``u32[B, 3]`` =
    (lo, hi, count), padding rows marked by count ``0xFFFFFFFF``. ONE
    operand array per dispatch instead of three (kpair/counts/valid), as
    :func:`~.kernels.pack_rows` does. 12 bytes/decision.

    ``fps`` is ``u32[B, 2]`` (padding rows arbitrary), ``counts`` is the
    valid prefix's counts — rows past ``len(counts)`` become padding.
    """
    b = fps.shape[0]
    fused = np.empty((b, 3), np.uint32)
    fused[:, :2] = fps
    fused[:, 2] = np.uint32(0xFFFFFFFF)
    n = len(counts)
    # Clamp BOTH sides: a negative count must stay a valid row (it grants,
    # like every other path's kernel does for count ≤ 0), not wrap into
    # the uint32 sign-bit range and get silently reclassified as padding.
    fused[:n, 2] = np.clip(counts, 0, 2**31 - 1).astype(np.uint32)
    return fused


def _unpack_fp12(fused):
    """Device-side unpack of :func:`pack_fp12`: the count column read as
    i32 makes padding exactly ``-1`` via the sign bit."""
    kpair = fused[..., :2]
    counts = fused[..., 2].astype(jnp.int32)
    valid = counts >= 0
    return kpair, jnp.maximum(counts, 0), valid


def _bitpack2(granted, resolved):
    """Pack two bool[B] planes into ``u8[2, B//8]`` (little-endian bit
    order, host side ``np.unpackbits(..., bitorder="little")``): plane 0
    grants, plane 1 resolve status — ONE device→host fetch carries both
    verdict and window-pressure report at 2 bits/decision."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    g = (granted.reshape(-1, 8).astype(jnp.uint8) << shifts).sum(
        axis=1, dtype=jnp.uint8)
    r = (resolved.reshape(-1, 8).astype(jnp.uint8) << shifts).sum(
        axis=1, dtype=jnp.uint8)
    return jnp.stack([g, r])


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates"))
def fp_acquire_scan_fused_bits(fp, state: K.BucketState, fused_k, nows_k,
                               capacity, fill_rate_per_tick, *,
                               probe_window: int = 16, rounds: int = 4,
                               handle_duplicates: bool = True):
    """Minimum-transfer fp bulk dispatch: ONE fused operand up
    (:func:`pack_fp12`), ONE bit-packed result down — the fp analogue of
    :func:`~.kernels.acquire_scan` with ``verdict_only``: the
    verdict-only path ships granted+resolved as two bit-planes in a
    single ``u8[K, 2, B//8]`` array (one fetch per dispatch).

    Returns ``(fp, state, bits u8[K, 2, B//8])``; ``B % 8 == 0``.
    """

    def body(carry, xs):
        fp, st = carry
        fused, now = xs
        kpair, counts, valid = _unpack_fp12(fused)
        fp, st, granted, _, res = _fp_acquire_core(
            fp, st, kpair, counts, valid, now, capacity,
            fill_rate_per_tick, probe_window=probe_window, rounds=rounds,
            handle_duplicates=handle_duplicates)
        return (fp, st), _bitpack2(granted, res)

    (fp, state), bits = jax.lax.scan(body, (fp, state), (fused_k, nows_k))
    return fp, state, bits


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates"))
def fp_acquire_scan_fused(fp, state: K.BucketState, fused_k, nows_k,
                          capacity, fill_rate_per_tick, *,
                          probe_window: int = 16, rounds: int = 4,
                          handle_duplicates: bool = True):
    """Fused-operand fp bulk dispatch WITH per-request remaining: ONE
    operand up, ONE ``f32[K, 2, B]`` result down — row 0 encodes
    ``granted + 2·resolved`` (both recovered exactly from the small
    integer), row 1 is remaining. One fetch replaces three.

    Returns ``(fp, state, out f32[K, 2, B])``.
    """

    def body(carry, xs):
        fp, st = carry
        fused, now = xs
        kpair, counts, valid = _unpack_fp12(fused)
        fp, st, granted, remaining, res = _fp_acquire_core(
            fp, st, kpair, counts, valid, now, capacity,
            fill_rate_per_tick, probe_window=probe_window, rounds=rounds,
            handle_duplicates=handle_duplicates)
        code = granted.astype(jnp.float32) + 2.0 * res.astype(jnp.float32)
        return (fp, st), jnp.stack([code, remaining])

    (fp, state), out = jax.lax.scan(body, (fp, state), (fused_k, nows_k))
    return fp, state, out


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds"))
def fp_debit_batch(fp, state: K.BucketState, kpair, amounts, valid, now,
                   capacity, fill_rate_per_tick, *,
                   probe_window: int = 16, rounds: int = 4):
    """Saturating bulk debit with in-kernel slot resolution — the
    fingerprint edition of :func:`~.kernels.debit_batch_packed`, and
    the lane the hierarchical deny-refund (``debit_many`` with a
    negative amount, runtime/store.py) rides on the fp store. The debit
    algebra is byte-for-byte the packed kernel's: refill-or-init, then
    subtract clamped at zero (a NEGATIVE amount credits back; the next
    refill's capacity clamp bounds any overshoot — refunds can only
    under-credit, the safe direction), duplicate fingerprints
    serialized via the demand prefix.

    Resolution inserts on miss (a debit of an absent key initializes
    it at capacity and debits from there — the host-dict
    ``InProcessBucketStore.debit_many`` semantics); rows still
    unresolved after ``rounds`` (window pressure) apply nothing and
    report their full amount as shortfall.

    Returns ``(fp, state, out f32[2, B])``: row 0 the post-debit
    balance, row 1 the clamped shortfall.
    """
    out = fp_resolve_core(fp, kpair, valid, probe_window=probe_window,
                          rounds=rounds)
    live = valid & out.resolved
    amounts = jnp.asarray(amounts, jnp.float32)
    size = state.tokens.shape[0]
    gs = jnp.where(live, out.slots, 0)
    refilled = bm.refill_or_init(state.tokens[gs], state.last_ts[gs],
                                 state.exists[gs], now, capacity,
                                 fill_rate_per_tick)
    prefix = bm.duplicate_prefix(out.slots, amounts, live)
    avail = jnp.maximum(refilled - prefix, 0.0)
    applied = jnp.where(live, jnp.minimum(amounts, avail), 0.0)
    # Unresolved-but-valid rows (window pressure) applied nothing: a
    # positive debit reports its full amount as shortfall; a refund
    # reports zero (shortfall means "tokens the debit did not find",
    # a refund has none — it just went un-credited, the safe side).
    shortfall = jnp.where(live, amounts - applied,
                          jnp.where(valid, jnp.maximum(amounts, 0.0),
                                    0.0))
    remaining = jnp.where(live, avail - applied, 0.0)
    ss = jnp.where(live, out.slots, size)  # size ⇒ scatter-dropped
    new_tokens = state.tokens.at[ss].set(refilled, mode="drop")
    new_tokens = new_tokens.at[ss].add(-applied, mode="drop")
    new_last_ts = state.last_ts.at[ss].set(
        jnp.asarray(now, jnp.int32), mode="drop")
    new_exists = state.exists.at[ss].set(True, mode="drop")
    return (out.fp,
            K.BucketState(new_tokens, new_last_ts, new_exists),
            jnp.stack([remaining, shortfall]))


@partial(jax.jit, static_argnames=("probe_window",))
def fp_peek_batch(fp, state: K.BucketState, kpair, valid, now, capacity,
                  fill_rate_per_tick, *, probe_window: int = 16):
    """Read-only availability estimate straight from fingerprints
    (``GetAvailablePermits``): lookup WITHOUT insert — peeking at an
    unseen key must not claim a slot — and missing keys report a full
    bucket (init-on-miss semantics read-only)."""
    n = fp.shape[0]
    b = kpair.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    base = _base_index(kpair, n, probe_window)
    widx = base[:, None] + jnp.arange(probe_window, dtype=jnp.int32)[None, :]
    cells = _window_cells(fp, base, probe_window)
    occ = (cells != 0).any(-1)
    match = (occ
             & (cells[..., 0] == kpair[:, None, 0])
             & (cells[..., 1] == kpair[:, None, 1]))
    hit = match.any(1)
    slots = jnp.where(hit, widx[rows, jnp.argmax(match, 1)], 0)
    refilled = bm.refill_or_init(
        state.tokens[slots], state.last_ts[slots], state.exists[slots] & hit,
        now, capacity, fill_rate_per_tick)
    return jnp.where(valid, jnp.floor(refilled), 0.0)


def _fp_migrate_core(fp, state, kpair, cols, valid, *, probe_window: int,
                     rounds: int):
    """Claim slots for old-table entries in the new table and scatter
    their per-slot state columns across (traceable core — also the
    per-shard block body of the mesh migrate step). Returns the per-entry
    ``placed`` mask: under heavy in-chunk window contention (tiny or
    crowded tables) the bounded insert rounds can leave entries unplaced,
    and the host retries exactly those in another pass — each pass places
    at least one contender per contested cell, so retries terminate."""
    out = fp_resolve_core(fp, kpair, valid, probe_window=probe_window,
                          rounds=rounds)
    live = valid & out.resolved
    ss = jnp.where(live, out.slots, fp.shape[0])  # n ⇒ dropped
    new_state = type(state)(*(
        getattr(state, f).at[ss].set(c, mode="drop")
        for f, c in zip(state._fields, cols)))
    return out.fp, new_state, live


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds"))
def fp_migrate_chunk(fp, state: K.BucketState, kpair, tokens, last_ts,
                     exists, valid, *, probe_window: int = 16,
                     rounds: int = 4):
    """Growth/rehash step, on-device: claim slots for a chunk of OLD-table
    entries in the new (larger) table, then scatter their bucket state to
    the claimed slots. The host's whole role in a grow is reading the old
    fingerprints back, chunking, and retrying unplaced entries —
    placement and state movement never leave the device. Returns
    ``(fp, state, placed bool[B])``."""
    return _fp_migrate_core(fp, state, kpair, (tokens, last_ts, exists),
                            valid, probe_window=probe_window, rounds=rounds)


def _fp_window_core(fp, state, kpair, counts, valid, now, limit,
                    window_ticks, *, probe_window: int, rounds: int,
                    handle_duplicates: bool, interpolate: bool):
    out = fp_resolve_core(fp, kpair, valid, probe_window=probe_window,
                          rounds=rounds)
    live = valid & out.resolved
    state, granted, remaining = K._window_acquire_core(
        state, out.slots, counts, live, now, limit, window_ticks,
        handle_duplicates=handle_duplicates, interpolate=interpolate)
    return out.fp, state, granted, remaining, out.resolved


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates",
                          "interpolate"))
def fp_window_acquire_batch(fp, state: K.WindowState, kpair, counts, valid,
                            now, limit, window_ticks, *,
                            probe_window: int = 16, rounds: int = 4,
                            handle_duplicates: bool = True,
                            interpolate: bool = True):
    """Fused resolve + sliding/fixed-window decision — the window-family
    analogue of :func:`fp_acquire_batch` (``interpolate=False`` = fixed
    window). Same insert/claim discipline; a freshly claimed slot's
    window state initializes via the core's init-on-miss."""
    return _fp_window_core(fp, state, kpair, counts, valid, now, limit,
                           window_ticks, probe_window=probe_window,
                           rounds=rounds,
                           handle_duplicates=handle_duplicates,
                           interpolate=interpolate)


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates",
                          "interpolate"))
def fp_window_acquire_scan_fused_bits(fp, state: K.WindowState, fused_k,
                                      nows_k, limit, window_ticks, *,
                                      probe_window: int = 16,
                                      rounds: int = 4,
                                      handle_duplicates: bool = True,
                                      interpolate: bool = True):
    """Window-family analogue of :func:`fp_acquire_scan_fused_bits`:
    one :func:`pack_fp12` operand up, ``u8[K, 2, B//8]`` bit-planes down
    (granted, resolved)."""

    def body(carry, xs):
        fp, st = carry
        fused, now = xs
        kpair, counts, valid = _unpack_fp12(fused)
        fp, st, granted, _, res = _fp_window_core(
            fp, st, kpair, counts, valid, now, limit, window_ticks,
            probe_window=probe_window, rounds=rounds,
            handle_duplicates=handle_duplicates, interpolate=interpolate)
        return (fp, st), _bitpack2(granted, res)

    (fp, state), bits = jax.lax.scan(body, (fp, state), (fused_k, nows_k))
    return fp, state, bits


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds", "handle_duplicates",
                          "interpolate"))
def fp_window_acquire_scan_fused(fp, state: K.WindowState, fused_k, nows_k,
                                 limit, window_ticks, *,
                                 probe_window: int = 16, rounds: int = 4,
                                 handle_duplicates: bool = True,
                                 interpolate: bool = True):
    """Window-family analogue of :func:`fp_acquire_scan_fused`: one
    operand up, one ``f32[K, 2, B]`` result down (row 0 =
    ``granted + 2·resolved``, row 1 = remaining)."""

    def body(carry, xs):
        fp, st = carry
        fused, now = xs
        kpair, counts, valid = _unpack_fp12(fused)
        fp, st, granted, remaining, res = _fp_window_core(
            fp, st, kpair, counts, valid, now, limit, window_ticks,
            probe_window=probe_window, rounds=rounds,
            handle_duplicates=handle_duplicates, interpolate=interpolate)
        code = granted.astype(jnp.float32) + 2.0 * res.astype(jnp.float32)
        return (fp, st), jnp.stack([code, remaining])

    (fp, state), out = jax.lax.scan(body, (fp, state), (fused_k, nows_k))
    return fp, state, out


@partial(jax.jit, donate_argnums=(0, 1),
         static_argnames=("probe_window", "rounds"))
def fp_migrate_window_chunk(fp, state: K.WindowState, kpair, prev_count,
                            curr_count, window_idx, exists, valid, *,
                            probe_window: int = 16, rounds: int = 4):
    """Window-table growth step (the :func:`fp_migrate_chunk` analogue):
    claim slots in the new table, scatter the four window-state arrays
    across. Returns ``(fp, state, placed bool[B])``."""
    return _fp_migrate_core(
        fp, state, kpair, (prev_count, curr_count, window_idx, exists),
        valid, probe_window=probe_window, rounds=rounds)


@partial(jax.jit, donate_argnums=(0, 1))
def fp_sweep_windows(fp, state: K.WindowState, now, window_ticks):
    """Window-table TTL eviction with fingerprint clearing: a slot idle
    two full windows carries no information (:func:`~.kernels
    .sweep_windows`); its cell becomes claimable immediately."""
    idx_now = (jnp.asarray(now, jnp.int32)
               // jnp.asarray(window_ticks, jnp.int32))
    expired = state.exists & (idx_now - state.window_idx >= 2)
    fp = jnp.where(expired[:, None], jnp.uint32(0), fp)
    new_state = K.WindowState(state.prev_count, state.curr_count,
                              state.window_idx, state.exists & ~expired)
    return fp, new_state, expired.sum(dtype=jnp.int32)


@partial(jax.jit, donate_argnums=(0, 1))
def fp_sweep_expired(fp, state: K.BucketState, now, capacity,
                     fill_rate_per_tick):
    """TTL eviction with zero host bookkeeping: clear ``exists`` AND the
    fingerprint of every expired slot (same TTL rule as
    :func:`~.kernels.sweep_expired`, invariant 5). Freed cells become
    claimable immediately; full-window probing makes the clear safe for
    every other key (module docstring). Returns ``(fp, state, n_freed)``
    — a scalar readback, not an N-byte mask."""
    ttl = bm.time_to_full_ttl(state.tokens, capacity, fill_rate_per_tick)
    expired = state.exists & (bm.elapsed_ticks(now, state.last_ts) >= ttl)
    new_exists = state.exists & ~expired
    fp = jnp.where(expired[:, None], jnp.uint32(0), fp)
    return (fp, K.BucketState(state.tokens, state.last_ts, new_exists),
            expired.sum(dtype=jnp.int32))
