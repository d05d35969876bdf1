"""Key-sharded bucket state over a device mesh + the two-level psum step.

This is the scale-out tier (SURVEY.md §5.7-5.8, §7 L4): the
``(key → {tokens, last_ts})`` table becomes 1-D arrays sharded along the
key axis of a ``Mesh``; key→shard routing is a stable hash on the host;
per-key independence means the hot acquire path needs **zero cross-chip
communication** — each shard decides its own keys' requests in its own
HBM. The only collective is the approximate algorithm's global tier: one
``lax.psum`` of per-chip consumed counts per sync (replacing the
reference's per-period Redis round-trip,
``RedisApproximateTokenBucketRateLimiter.cs:439``), so the ICI cost is one
scalar all-reduce per period, not per request.

``make_two_level_step`` builds the flagship fused step — sharded batched
acquire + psum + decaying replicated global counter — which is also the
framework's ``dryrun_multichip`` / bench entry (BASELINE config 5).
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distributedratelimiting.redis_tpu.ops import bucket_math as bm
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.parallel.mesh import (
    SHARD_AXIS,
    init_sharded,
)
from distributedratelimiting.redis_tpu.runtime.clock import Clock, MonotonicClock
from distributedratelimiting.redis_tpu.runtime.directory import make_directory
from distributedratelimiting.redis_tpu.runtime.store import (
    AcquireResult,
    BulkAcquireResult,
    _pad_size,
    _REBASE_MARGIN_TICKS,
    _REBASE_THRESHOLD_TICKS,
    _shift_ts,
)
from distributedratelimiting.redis_tpu.utils.metrics import StoreMetrics
from distributedratelimiting.redis_tpu.utils.native import load_directory_lib

__all__ = [
    "GlobalCounter",
    "make_sharded_acquire_step",
    "make_two_level_step",
    "make_two_level_scan_step",
    "make_two_level_scan_step_deferred",
    "make_sharded_window_scan_step",
    "ShardedDeviceStore",
    "ShardedWindowStore",
    "shard_of_key",
    "route_keys",
]


class GlobalCounter(NamedTuple):
    """Replicated decaying global counter (one logical limiter's shared
    tier): scalar ``{v, p, t}`` hash, same as the reference's global bucket
    (``RedisApproximateTokenBucketRateLimiter.cs:265-268``)."""

    value: jax.Array    # f32[] decaying throttle score
    period: jax.Array   # f32[] EWMA of inter-sync interval (ticks)
    last_ts: jax.Array  # i32[]
    exists: jax.Array   # bool[]


def global_tier_update(g: GlobalCounter, total, now,
                       decay_rate) -> GlobalCounter:
    """ONE recurrence of the two-level global tier (SURVEY.md invariant
    6): decay the replicated counter to ``now``, add the psum'd
    consumption, refresh the period EWMA. The single definition keeps
    every step variant (per-batch, per-launch, fingerprint) bit-identical
    by construction."""
    decayed, new_period = bm.decay_core(
        g.value, g.period, g.last_ts, g.exists, now, decay_rate)
    return GlobalCounter(value=decayed + total, period=new_period,
                         last_ts=jnp.asarray(now, jnp.int32),
                         exists=jnp.asarray(True))


def init_global_counter() -> GlobalCounter:
    return GlobalCounter(
        value=jnp.float32(0), period=jnp.float32(0),
        last_ts=jnp.int32(0), exists=jnp.asarray(False),
    )


@jax.jit
def _peek_gather(state: K.BucketState, idx):
    """Read one slot's ``(tokens, last_ts, exists)`` as one f32[3] — a
    single dispatch + readback regardless of the index's value. The i32
    timestamp travels bitcast (exact); the host views it back."""
    return jnp.stack([
        state.tokens[idx],
        jax.lax.bitcast_convert_type(state.last_ts[idx], jnp.float32),
        state.exists[idx].astype(jnp.float32),
    ])


def shard_of_key(key: str, n_shards: int) -> int:
    """Stable key→shard routing (host side). crc32 so every client process
    on every host routes identically — the distributed directory needs no
    coordination."""
    return zlib.crc32(key.encode("utf-8", "surrogateescape")) % n_shards


def route_keys(keys: "Sequence[str] | list[str]", n_shards: int) -> np.ndarray:
    """Vectorized :func:`shard_of_key` over a batch: one native C call for
    the whole batch when the directory library is built (the same zero-copy
    list[str] path the key directory uses), a Python crc32 loop otherwise.
    Both agree bit-for-bit with ``zlib.crc32(key) % n_shards``."""
    import ctypes

    n = len(keys)
    lib = load_directory_lib()
    blob = getattr(keys, "blob", None)
    if lib is not None and blob is not None:
        # wire.KeyBlob fast path: crc32-route straight off the frame's
        # key bytes (no Python strings — the mesh serving lane's half of
        # the zero-copy bulk path).
        out = np.empty(n, np.int32)
        lib.dir_route_batch(
            blob,
            keys.offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, n_shards,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out
    if lib is not None and lib.has_pylist:
        if not isinstance(keys, list):
            keys = list(keys)
        out = np.empty(n, np.int32)
        if lib.dir_route_pylist(
                keys, n_shards,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))) == 0:
            return out
    return np.fromiter(
        (zlib.crc32(k.encode("utf-8", "surrogateescape")) % n_shards
         for k in keys),
                       np.int32, n)


def make_sharded_acquire_step(mesh, *, handle_duplicates: bool = True):
    """Jitted sharded acquire: state sharded along keys, batch laid out as
    ``[n_shards, B_local]`` with shard-LOCAL slot ids. No collectives —
    each shard serves its keys independently.
    """
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    batch_spec = P(SHARD_AXIS, None)

    def block(state, slots, counts, valid, now, capacity, rate):
        # Block sees its own [per_shard] slice and [1, B] batch rows.
        new_state, granted, remaining = K.acquire_core(
            state, slots[0], counts[0], valid[0], now, capacity, rate,
            handle_duplicates=handle_duplicates,
        )
        return new_state, granted[None], remaining[None]

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(state_specs, batch_spec, batch_spec, batch_spec, P(), P(), P()),
        out_specs=(state_specs, batch_spec, batch_spec),
    )
    return jax.jit(mapped, donate_argnums=0)


def make_two_level_step(mesh, *, handle_duplicates: bool = True):
    """The flagship fused multi-chip step (BASELINE config 5):

    1. sharded batched acquire over the key-sharded table (no comm);
    2. per-chip consumed = Σ granted counts;
    3. ``lax.psum`` over ICI → total consumed this step;
    4. replicated global counter decays and absorbs the total
       (``new_v = max(0, v − Δt·decay) + Σ``, the sync-script recurrence).

    Returns ``(new_state, granted, remaining, new_global, global_score)``.
    In production the global tier runs once per replenishment period; fusing
    it here costs one scalar psum and gives the dry-run/bench a single step
    exercising sharding + collective together.
    """
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    gspecs = GlobalCounter(P(), P(), P(), P())
    batch_spec = P(SHARD_AXIS, None)

    def block(state, slots, counts, valid, now, capacity, rate,
              gcounter, decay_rate):
        new_state, granted, remaining = K.acquire_core(
            state, slots[0], counts[0], valid[0], now, capacity, rate,
            handle_duplicates=handle_duplicates,
        )
        consumed = jnp.sum(
            jnp.asarray(counts[0], jnp.float32) * granted
        )
        total = jax.lax.psum(consumed, SHARD_AXIS)  # the only collective
        new_g = global_tier_update(gcounter, total, now, decay_rate)
        return new_state, granted[None], remaining[None], new_g

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(state_specs, batch_spec, batch_spec, batch_spec,
                  P(), P(), P(), gspecs, P()),
        out_specs=(state_specs, batch_spec, batch_spec, gspecs),
    )
    return jax.jit(mapped, donate_argnums=(0, 7))


def make_two_level_scan_step(mesh, *, handle_duplicates: bool = True):
    """Scanned variant of :func:`make_two_level_step`: K micro-batches per
    launch (``lax.scan`` inside each shard's block), one psum + global-
    counter decay per scanned batch. Amortizes per-dispatch host overhead
    the same way :func:`~.ops.kernels.acquire_scan_compact` does on one
    chip — the sharded path is dispatch-bound at small per-step work, so
    scanning multiplies multi-chip throughput without touching semantics
    (each batch keeps its own ``now``; the global counter sees batches in
    order).

    Batch layout: ``slots_k/counts_k/valid_k: [n_shards, K, B_local]``
    (sharded on axis 0), ``nows_k: i32[K]`` replicated. Returns
    ``(new_state, granted [n_shards, K, B], remaining likewise,
    new_gcounter, )``.
    """
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    gspecs = GlobalCounter(P(), P(), P(), P())
    batch_spec = P(SHARD_AXIS, None, None)

    def block(state, slots, counts, valid, nows, capacity, rate,
              gcounter, decay_rate):
        def body(carry, xs):
            st, g = carry
            sl, ct, va, now = xs
            st, granted, remaining = K.acquire_core(
                st, sl, ct, va, now, capacity, rate,
                handle_duplicates=handle_duplicates,
            )
            consumed = jnp.sum(jnp.asarray(ct, jnp.float32) * granted)
            total = jax.lax.psum(consumed, SHARD_AXIS)
            g = global_tier_update(g, total, now, decay_rate)
            return (st, g), (granted, remaining)

        # Blocks see [1, K, B] slices; scan over K.
        (state, gcounter), (granted, remaining) = jax.lax.scan(
            body, (state, gcounter),
            (slots[0], counts[0], valid[0], nows),
        )
        return state, granted[None], remaining[None], gcounter

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(state_specs, batch_spec, batch_spec, batch_spec,
                  P(), P(), P(), gspecs, P()),
        out_specs=(state_specs, batch_spec, batch_spec, gspecs),
    )
    return jax.jit(mapped, donate_argnums=(0, 7))


def make_two_level_scan_step_deferred(mesh, *, handle_duplicates: bool = True):
    """Cadence ablation counterpart of :func:`make_two_level_scan_step`:
    the K scanned batches run with NO collectives (acquire only,
    accumulating each chip's consumed count); ONE psum + ONE global-counter
    decay-and-add runs after the scan — i.e. per-LAUNCH sync instead of
    per-batch, the analogue of the reference's per-``ReplenishmentPeriod``
    sync against per-request (SURVEY.md §7 "Two-level sync cadence").

    Grant decisions are bit-identical to the per-batch variant — the
    acquire path never reads the global counter inside a launch (fair-share
    feedback happens between launches, in the approximate limiter). What
    changes is (a) collective count: 1/launch vs K/launch, and (b) the
    returned counter's decay granularity: one ``Δt·decay`` step at the last
    batch's ``now`` instead of K steps — staleness bounded by one launch's
    time span, exactly the reference's staleness ≤ period bound with
    "period" = launch cadence. Its throughput effect on chips is not
    measured.
    """
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    gspecs = GlobalCounter(P(), P(), P(), P())
    batch_spec = P(SHARD_AXIS, None, None)

    def block(state, slots, counts, valid, nows, capacity, rate,
              gcounter, decay_rate):
        def body(carry, xs):
            st, consumed_acc = carry
            sl, ct, va, now = xs
            st, granted, remaining = K.acquire_core(
                st, sl, ct, va, now, capacity, rate,
                handle_duplicates=handle_duplicates,
            )
            consumed = jnp.sum(jnp.asarray(ct, jnp.float32) * granted)
            return (st, consumed_acc + consumed), (granted, remaining)

        # The accumulator is per-shard ("varying" over the mesh axis inside
        # shard_map); the initial zero must be cast to match.
        zero = jax.lax.pcast(jnp.zeros((), jnp.float32), (SHARD_AXIS,),
                             to="varying")
        (state, consumed_total), (granted, remaining) = jax.lax.scan(
            body, (state, zero),
            (slots[0], counts[0], valid[0], nows),
        )
        total = jax.lax.psum(consumed_total, SHARD_AXIS)  # ONE per launch
        gcounter = global_tier_update(gcounter, total, nows[-1], decay_rate)
        return state, granted[None], remaining[None], gcounter

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(state_specs, batch_spec, batch_spec, batch_spec,
                  P(), P(), P(), gspecs, P()),
        out_specs=(state_specs, batch_spec, batch_spec, gspecs),
    )
    return jax.jit(mapped, donate_argnums=(0, 7))


class _ShardedKeyedTable:
    """Shared host runtime for key-sharded device tables (buckets and
    windows): per-shard native key directories, one vectorized crc32
    routing call per batch, sweep/grow reclaim with cross-shard pinning,
    and per-shard doubling growth. Subclasses provide the device pieces:

    - ``_widen_state(old, new)`` — re-lay the sharded state arrays at the
      doubled per-shard width;
    - ``_device_sweep()`` — run the table's TTL sweep kernel against the
      current clock and return the freed-mask as a host bool array.

    Requires attributes: ``n_shards``, ``per_shard``, ``dirs``, ``_lock``,
    ``metrics``.
    """

    #: Max scanned batches per fused dispatch / per-shard row width of one
    #: scanned batch (bounds the jit cache to power-of-two K variants —
    #: see DeviceBucketStore._BULK_MAX_K).
    _BULK_MAX_K = 32
    _BULK_B = 2048

    # -- hooks -------------------------------------------------------------
    def _widen_state(self, old: int, new: int) -> None:
        raise NotImplementedError

    def _device_sweep(self) -> np.ndarray:
        raise NotImplementedError

    def force_rebase(self, offset: int) -> None:
        """Shift the table's stored time state by ``-offset`` ticks WITHOUT
        touching the clock (the composing store's coordinated-rebase
        hook)."""
        raise NotImplementedError

    # -- shared machinery --------------------------------------------------
    def now_ticks_checked(self) -> int:
        """Store clock read with int32-overflow protection: rebase the
        table and the clock together before ~24 days of tick time can
        overflow (composing stores disable this via
        ``rebase_threshold_ticks`` and coordinate one rebase across every
        table sharing the clock)."""
        now = self.clock.now_ticks()
        if now >= self._rebase_threshold:
            with self._lock:
                now = self.clock.now_ticks()
                if now >= self._rebase_threshold:
                    offset = now - _REBASE_MARGIN_TICKS
                    self.force_rebase(offset)
                    self.clock.rebase(offset)  # type: ignore[attr-defined]
                    now = self.clock.now_ticks()
        return now

    def _bulk_decide(self, keys: Sequence[str], counts: Sequence[int],
                     with_remaining: bool, launch_chunk) -> BulkAcquireResult:
        """Shared whole-array bulk path: vectorized key→(shard, local)
        resolve, ``[n_shards, K, B]`` chunk layout, readback fan-out, and
        the zero-permit probe override. ``launch_chunk(slots, counts,
        valid, nows)`` runs the table's scanned step and returns the
        ``(granted, remaining)`` device arrays."""
        n = len(keys)
        counts_np = np.asarray(counts, np.int64)
        granted_out = np.empty(n, bool)
        rem_out = np.empty(n, np.float32) if with_remaining else None
        if n == 0:
            return BulkAcquireResult(granted_out, rem_out)
        with self._lock:
            shards, locs = self._resolve_batch(keys)  # KeyBlob-aware
            jpos, shard_counts = self._group_by_shard(shards)
            max_rows = int(shard_counts.max(initial=1))
            b = _pad_size(min(max_rows, self._BULK_B), floor=8)
            pos = 0
            while pos < max_rows:
                rows = -(-(max_rows - pos) // b)  # ceil
                k = 1
                while k < rows and k < self._BULK_MAX_K:
                    k *= 2
                take_rows = k * b
                sel = (jpos >= pos) & (jpos < pos + take_rows)
                rel = (jpos[sel] - pos).astype(np.int64)
                s_sel = shards[sel]
                slots_chunk = np.full((self.n_shards, k, b), -1, np.int32)
                counts_chunk = np.zeros((self.n_shards, k, b), np.int32)
                valid_chunk = np.zeros((self.n_shards, k, b), bool)
                slots_chunk[s_sel, rel // b, rel % b] = locs[sel]
                counts_chunk[s_sel, rel // b, rel % b] = counts_np[sel]
                valid_chunk[s_sel, rel // b, rel % b] = True
                nows = np.full((k,), self.now_ticks_checked(), np.int32)
                granted, remaining = launch_chunk(
                    jnp.asarray(slots_chunk), jnp.asarray(counts_chunk),
                    jnp.asarray(valid_chunk), jnp.asarray(nows))
                g_np = np.asarray(granted)
                granted_out[sel] = g_np[s_sel, rel // b, rel % b] > 0.5
                if rem_out is not None:
                    r_np = np.asarray(remaining)
                    rem_out[sel] = r_np[s_sel, rel // b, rel % b]
                self.metrics.record_launch(self.n_shards * take_rows,
                                           int(sel.sum()))
                pos += take_rows
        if (counts_np == 0).any():
            # Zero-permit probes are granted unconditionally on every
            # single-request path; the bulk path's conservative in-batch
            # prefix could deny one riding beside denied same-key demand.
            granted_out[counts_np == 0] = True
        return BulkAcquireResult(granted_out, rem_out)

    @property
    def directory(self) -> dict[str, tuple[int, int]]:
        """Merged ``key → (shard, local slot)`` view (diagnostics/tests;
        the serving path never materializes this)."""
        return {
            key: (shard, local)
            for shard, d in enumerate(self.dirs)
            for key, local in d.to_dict().items()
        }

    def _resolve_batch(self, keys: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized key→(shard, local) resolution for a whole batch: one
        native routing call + one directory batch-resolve per touched shard
        (the mesh analogue of the single-chip one-C-call-per-flush resolve).
        On free-list exhaustion: sweep (pinning this batch's already-
        resolved slots), then grow every shard, re-resolving until all keys
        land — the single-chip reclaim discipline (store.py
        ``_resolve_with_reclaim``), with growth keeping the geometry
        homogeneous across shards."""
        fused = self._resolve_batch_fused(keys)
        if fused is not None:
            return fused
        if not isinstance(keys, list):
            keys = list(keys)  # split path indexes str refs via numpy
        shards = route_keys(keys, self.n_shards)
        locs = np.empty(len(keys), np.int32)
        # Object-array gather: numpy fancy indexing moves the str refs at
        # C speed — a Python `[keys[i] for i in …]` loop here was the
        # resolve path's dominant cost (measured 4x of everything else).
        keys_arr = np.asarray(keys, dtype=object)
        # (shard, locals) already resolved for THIS batch, across every
        # shard processed so far — a sweep triggered by a later shard's
        # exhaustion must not reclaim an earlier shard's TTL-expired slot
        # that this batch is about to dispatch to (the mid-batch
        # cross-contamination hazard). Kept as shard-tagged arrays and
        # materialized into a flat-id set ONLY when a sweep actually runs
        # (the rare path): growth mid-loop re-lays the flat index space,
        # and per-key Python tuple building is hot-path cost.
        done: list[tuple[int, np.ndarray]] = []
        # One stable argsort groups every shard's requests (8 per-shard
        # boolean scans + gathers cost ~2x this on large batches).
        order = np.argsort(shards, kind="stable")
        sorted_keys = keys_arr[order]
        sorted_shards = shards[order]
        bounds = np.searchsorted(sorted_shards,
                                 np.arange(self.n_shards + 1))
        for shard in range(self.n_shards):
            lo, hi = int(bounds[shard]), int(bounds[shard + 1])
            if lo == hi:
                continue
            idx = order[lo:hi]
            sub = sorted_keys[lo:hi].tolist()
            d = self.dirs[shard]
            slots = d.resolve_batch(sub)
            while (slots < 0).any():
                pinned = {
                    int(sh) * self.per_shard + int(loc)
                    for sh, arr in done for loc in arr
                }
                pinned.update(int(shard) * self.per_shard + int(s)
                              for s in slots[slots >= 0])
                self._sweep_locked(pinned)
                if d.free_count * 16 <= self.per_shard:
                    # Sweep-first hysteresis: a trickle of reclaimed slots
                    # on a near-full table would re-sweep on every batch —
                    # grow instead (all shards, keeping geometry uniform).
                    self._grow()
                slots = d.resolve_batch(sub)
            locs[idx] = slots
            done.append((int(shard), slots))
        return shards, locs

    def _resolve_batch_fused(self, keys: list[str]):
        """One C call routes AND resolves the whole batch (crc32 → shard →
        that shard's open-addressing probe, allocating on miss) — the mesh
        analogue of the single-chip one-call resolve, available when every
        per-shard directory is native. Returns ``None`` to fall back to
        the split route/group/resolve path (pure-Python directories, or a
        non-str key)."""
        import ctypes

        from distributedratelimiting.redis_tpu.runtime.directory import (
            NativeKeyDirectory,
        )

        # Capability is invariant after construction (dirs are created in
        # __init__ and reloaded in place by restore) — cache the verdict
        # so the hot path pays zero re-checks.
        fused_ok = getattr(self, "_fused_ok", None)
        if fused_ok is None:
            lib = load_directory_lib()
            # Blob inputs need only the plain C ABI; the pylist branch
            # additionally needs the CPython-API build (has_pylist).
            fused_ok = self._fused_ok = bool(
                lib is not None
                and all(isinstance(d, NativeKeyDirectory)
                        for d in self.dirs))
        if not fused_ok:
            return None
        lib = load_directory_lib()
        blob = getattr(keys, "blob", None)
        if blob is None:
            if not lib.has_pylist:
                return None  # split path handles the encode fallback
            if not isinstance(keys, list):
                keys = list(keys)
        n = len(keys)
        shards = np.empty(n, np.int32)
        locs = np.empty(n, np.int32)
        sh_ptr = shards.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        lo_ptr = locs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

        def call() -> int:
            # Handles re-read per call: restore()'s directory load swaps
            # the underlying native handle.
            handles = (ctypes.c_void_p * self.n_shards)(
                *(d._h for d in self.dirs))
            if blob is not None:
                # wire.KeyBlob zero-copy lane: route + probe straight off
                # the frame's key bytes (no Python strings).
                return int(lib.dir_resolve_sharded_batch(
                    blob,
                    keys.offsets.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int64)),
                    n, handles, self.n_shards, sh_ptr, lo_ptr))
            return int(lib.dir_resolve_sharded_pylist(
                keys, handles, self.n_shards, sh_ptr, lo_ptr))

        unresolved = call()
        if unresolved < 0:  # non-str key: let the split path raise naturally
            return None
        while unresolved > 0:
            ok = locs >= 0
            pinned = set((shards[ok].astype(np.int64) * self.per_shard
                          + locs[ok]).tolist())
            self._sweep_locked(pinned)
            dry = np.unique(shards[~ok])
            if any(self.dirs[s].free_count * 16 <= self.per_shard
                   for s in dry):
                # Sweep-first hysteresis (see the split path).
                self._grow()
            unresolved = call()  # already-resolved keys are idempotent
        return shards, locs

    def _grow(self) -> None:
        """Double every shard's slot capacity in place. The sharded layout
        is contiguous per shard, so growth re-lays the flat arrays as
        ``[n_shards, per_shard]`` blocks padded to twice the width — one
        host round-trip, amortized O(log growth) times over a store's life
        (the single-chip table's doubling discipline, store.py ``_grow``).
        Kernels recompile at the new shape on next launch."""
        old, new = self.per_shard, self.per_shard * 2
        self._widen_state(old, new)
        for d in self.dirs:
            d.add_slots(old, new)
        self.per_shard = new
        self.metrics.pregrows += 1

    def _widen_host(self, arr, old: int, new: int) -> np.ndarray:
        host = np.asarray(arr).reshape(self.n_shards, old)
        out = np.zeros((self.n_shards, new), host.dtype)
        out[:, :old] = host
        return out.reshape(-1)

    def _group_by_shard(self, shards: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Per-request row position within its shard's queue (stable in
        request order — duplicate keys keep arrival order for the kernel's
        prefix serialization) plus the per-shard load histogram."""
        n = len(shards)
        shard_counts = np.bincount(shards, minlength=self.n_shards)
        starts = np.zeros(self.n_shards + 1, np.int64)
        np.cumsum(shard_counts, out=starts[1:])
        order = np.argsort(shards, kind="stable")
        jpos = np.empty(n, np.int64)
        jpos[order] = np.arange(n) - starts[shards[order]]
        return jpos, shard_counts

    def sweep(self) -> int:
        """TTL eviction across all shards (elementwise → partitioned by XLA
        along the existing sharding, no resharding)."""
        with self._lock:
            return self._sweep_locked(None)

    def _sweep_locked(self, pinned: set[int] | None) -> int:
        """``pinned`` flat slot ids — slots already resolved for an
        in-flight batch — are exempt from reclamation (same mid-batch
        cross-contamination hazard as the single-chip store's sweep)."""
        freed_np = self._device_sweep()
        n_freed = 0
        if freed_np.any():
            dead = np.nonzero(freed_np)[0].astype(np.int64)
            if pinned:
                dead = dead[~np.isin(dead, np.fromiter(pinned, np.int64,
                                                       len(pinned)))]
            dead_shards = dead // self.per_shard
            dead_locals = (dead % self.per_shard).astype(np.int32)
            for shard in np.unique(dead_shards):
                n_freed += self.dirs[shard].remove_slots(
                    dead_locals[dead_shards == shard])
        self.metrics.sweeps += 1
        self.metrics.slots_evicted += n_freed
        return n_freed


class ShardedDeviceStore(_ShardedKeyedTable):
    """Host runtime for one key-sharded, homogeneous-config bucket table.

    Mirrors ``_DeviceTable``'s role in the single-chip store, scaled over a
    mesh: host directory maps key → (shard, local slot); requests are
    grouped by shard, padded to a common per-shard width, and decided in
    one launch of the sharded step. The global tier (two-level) is fused
    into the same launch.
    """

    def __init__(self, mesh, capacity: float, fill_rate_per_sec: float,
                 *, per_shard_slots: int = 2**14,
                 clock: Clock | None = None,
                 handle_duplicates: bool = True,
                 sync_cadence: str = "batch",
                 rebase_threshold_ticks: int = _REBASE_THRESHOLD_TICKS) -> None:
        if sync_cadence not in ("batch", "launch"):
            raise ValueError("sync_cadence must be 'batch' or 'launch'")
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.per_shard = per_shard_slots
        self.capacity = float(capacity)
        self.fill_rate_per_sec = float(fill_rate_per_sec)
        self.rate_per_tick = fill_rate_per_sec / bm.TICKS_PER_SECOND
        self.clock = clock or MonotonicClock()
        self.metrics = StoreMetrics()
        # See DeviceBucketStore: a composing store coordinates rebases.
        self._rebase_threshold = rebase_threshold_ticks

        n_total = self.n_shards * per_shard_slots
        sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self.state = init_sharded(K.init_bucket_state, n_total, sharding)
        self.gcounter = jax.device_put(
            init_global_counter(), NamedSharding(mesh, P())
        )
        self._step = make_two_level_step(mesh,
                                         handle_duplicates=handle_duplicates)
        # Global-tier sync cadence (its throughput effect on chips is not
        # measured): "batch" = one psum per scanned batch (K collectives/launch,
        # counter staleness ≤ one batch); "launch" = consumed counts
        # accumulate in-scan and ONE psum lands after it (staleness ≤ one
        # launch's time span — the reference's staleness ≤ period bound
        # with "period" = launch cadence). Grant decisions are
        # bit-identical either way; only the counter's decay granularity
        # and the collective count change.
        self.sync_cadence = sync_cadence
        scan_factory = (make_two_level_scan_step_deferred
                        if sync_cadence == "launch"
                        else make_two_level_scan_step)
        self._scan_step = scan_factory(
            mesh, handle_duplicates=handle_duplicates)
        # One key→local-slot directory per shard (C++ batch-resolve when
        # buildable — runtime/directory.py); routing key→shard is crc32.
        self.dirs = [make_directory(per_shard_slots)
                     for _ in range(self.n_shards)]
        import threading

        self._lock = threading.RLock()

    # -- _ShardedKeyedTable hooks ------------------------------------------
    def _widen_state(self, old: int, new: int) -> None:
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self.state = K.BucketState(
            tokens=jax.device_put(
                self._widen_host(self.state.tokens, old, new), sharding),
            last_ts=jax.device_put(
                self._widen_host(self.state.last_ts, old, new), sharding),
            exists=jax.device_put(
                self._widen_host(self.state.exists, old, new), sharding),
        )

    def _device_sweep(self) -> np.ndarray:
        now = self.now_ticks_checked()
        self.state, freed = K.sweep_expired(
            self.state, jnp.int32(now), jnp.float32(self.capacity),
            jnp.float32(self.rate_per_tick),
        )
        return np.asarray(freed)

    def force_rebase(self, offset: int) -> None:
        """Shift table + global-counter timestamps without touching the
        clock (the composing store's coordinated-rebase hook — see
        ``DeviceBucketStore.force_rebase``)."""
        with self._lock:
            self.state = K.rebase_bucket_epoch(self.state, jnp.int32(offset))
            self.gcounter = GlobalCounter(
                value=self.gcounter.value,
                period=self.gcounter.period,
                last_ts=jnp.maximum(
                    self.gcounter.last_ts - jnp.int32(offset), 0),
                exists=self.gcounter.exists,
            )

    def peek_blocking(self, key: str) -> float:
        """Read-only availability estimate: never allocates a slot or
        writes device state (the ``GetAvailablePermits`` contract)."""
        with self._lock:
            shard = shard_of_key(key, self.n_shards)
            local = self.dirs[shard].lookup(key)
            if local is None:
                return float(np.floor(self.capacity))
            idx = shard * self.per_shard + local
            now = self.now_ticks_checked()
            # One jitted gather with the index as an OPERAND (a Python-int
            # subscript would bake the index into the computation — one
            # compile per distinct slot) and one packed readback.
            out = np.asarray(_peek_gather(self.state, jnp.int32(idx)))
        tokens = float(out[0])
        ts = int(np.float32(out[1]).view(np.int32))
        exists = bool(out[2])
        if not exists:
            return float(np.floor(self.capacity))
        refilled = min(self.capacity,
                       tokens + max(0, now - ts) * self.rate_per_tick)
        return float(np.floor(refilled))

    # -- decisions ---------------------------------------------------------
    def acquire_batch_blocking(
        self, requests: Sequence[tuple[str, int]],
        decay_rate_per_sec: float | None = None,
    ) -> list[AcquireResult]:
        """Decide a batch of ``(key, count)`` requests in one fused launch.
        Returns results in request order."""
        decay = (decay_rate_per_sec if decay_rate_per_sec is not None
                 else self.fill_rate_per_sec) / bm.TICKS_PER_SECOND
        with self._lock:
            return self._acquire_locked(requests, decay)

    def _acquire_locked(self, requests, decay) -> list[AcquireResult]:
        n = len(requests)
        keys = [k for k, _ in requests]
        counts = np.fromiter((c for _, c in requests), np.int64, n)
        shards, locs = self._resolve_batch(keys)
        jpos, shard_counts = self._group_by_shard(shards)
        b_local = _pad_size(int(shard_counts.max(initial=1)), floor=8)
        slots_np = np.full((self.n_shards, b_local), -1, np.int32)
        counts_np = np.zeros((self.n_shards, b_local), np.int32)
        valid_np = np.zeros((self.n_shards, b_local), bool)
        slots_np[shards, jpos] = locs
        counts_np[shards, jpos] = counts
        valid_np[shards, jpos] = True
        now = self.now_ticks_checked()
        self.state, granted, remaining, self.gcounter = self._step(
            self.state,
            jnp.asarray(slots_np), jnp.asarray(counts_np), jnp.asarray(valid_np),
            jnp.int32(now), jnp.float32(self.capacity),
            jnp.float32(self.rate_per_tick), self.gcounter, jnp.float32(decay),
        )
        g_np = np.asarray(granted)[shards, jpos]
        r_np = np.asarray(remaining)[shards, jpos]
        self.metrics.record_launch(self.n_shards * b_local, n)
        return [AcquireResult(bool(g), float(r)) for g, r in zip(g_np, r_np)]

    # -- bulk decisions (the mesh serving surface for acquire_many) --------
    def acquire_many_blocking(
        self, keys: Sequence[str], counts: Sequence[int], *,
        with_remaining: bool = True,
        decay_rate_per_sec: float | None = None,
    ) -> BulkAcquireResult:
        """Whole-array bulk acquire over the mesh: the shared
        ``_bulk_decide`` chunking (``[n_shards, K, B]`` layout) over the
        scanned two-level step — sharded acquire + one psum per scanned
        batch. Each dispatch decides up to ``n_shards × K × B`` requests
        in one fused launch."""
        decay = (decay_rate_per_sec if decay_rate_per_sec is not None
                 else self.fill_rate_per_sec) / bm.TICKS_PER_SECOND
        cap = jnp.float32(self.capacity)
        rate = jnp.float32(self.rate_per_tick)
        decay_dev = jnp.float32(decay)

        def launch_chunk(slots, counts_dev, valid, nows):
            self.state, granted, remaining, self.gcounter = self._scan_step(
                self.state, slots, counts_dev, valid, nows, cap, rate,
                self.gcounter, decay_dev,
            )
            return granted, remaining

        return self._bulk_decide(keys, counts, with_remaining, launch_chunk)

    @property
    def global_score(self) -> float:
        return float(np.asarray(self.gcounter.value))

    # -- checkpoint (SURVEY.md §5.4, parity with DeviceBucketStore) --------
    def snapshot(self) -> dict:
        """Pull the sharded state to host for a planned-restart checkpoint.
        Restorable into a store with the same mesh size and per-shard
        capacity; timestamps re-align via the captured ``now_ticks``."""
        with self._lock:
            return {
                "now_ticks": self.clock.now_ticks(),
                "n_shards": self.n_shards,
                "per_shard": self.per_shard,
                "capacity": self.capacity,
                "fill_rate_per_sec": self.fill_rate_per_sec,
                "directories": [d.to_dict() for d in self.dirs],
                "tokens": np.asarray(self.state.tokens),
                "last_ts": np.asarray(self.state.last_ts),
                "exists": np.asarray(self.state.exists),
                "gcounter": {
                    "value": np.asarray(self.gcounter.value),
                    "period": np.asarray(self.gcounter.period),
                    "last_ts": np.asarray(self.gcounter.last_ts),
                    "exists": np.asarray(self.gcounter.exists),
                },
            }

    def restore(self, snap: dict) -> None:
        with self._lock:
            if snap["n_shards"] != self.n_shards:
                # Re-sharding a snapshot is real key-redistribution work;
                # adopting a different per-shard WIDTH is not — the state
                # arrays and directories below are rebuilt wholesale from
                # the snapshot, so a store that grew (per-shard doubling)
                # before checkpointing restores into a fresh store fine.
                raise ValueError(
                    f"snapshot geometry {snap['n_shards']}x{snap['per_shard']}"
                    f" != store geometry {self.n_shards}x{self.per_shard} "
                    "(shard count must match)"
                )
            self.per_shard = int(snap["per_shard"])
            if (snap["capacity"] != self.capacity
                    or snap["fill_rate_per_sec"] != self.fill_rate_per_sec):
                # Token balances are only meaningful under the config they
                # accrued under (the single-chip store gets this for free —
                # its tables are keyed by (cap, rate)).
                raise ValueError(
                    f"snapshot config (cap={snap['capacity']}, "
                    f"rate={snap['fill_rate_per_sec']}) != store config "
                    f"(cap={self.capacity}, rate={self.fill_rate_per_sec})"
                )
            shift = int(self.clock.now_ticks()) - int(snap["now_ticks"])
            sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
            self.state = K.BucketState(
                tokens=jax.device_put(jnp.asarray(snap["tokens"]), sharding),
                last_ts=jax.device_put(
                    jnp.asarray(_shift_ts(snap["last_ts"], shift)), sharding),
                exists=jax.device_put(jnp.asarray(snap["exists"]), sharding),
            )
            g = snap["gcounter"]
            g_ts = int(_shift_ts(g["last_ts"], shift))
            self.gcounter = jax.device_put(
                GlobalCounter(
                    value=jnp.asarray(g["value"], jnp.float32),
                    period=jnp.asarray(g["period"], jnp.float32),
                    last_ts=jnp.int32(g_ts),
                    exists=jnp.asarray(bool(g["exists"])),
                ),
                NamedSharding(self.mesh, P()),
            )
            for d, mapping in zip(self.dirs, snap["directories"]):
                d.load(mapping, self.per_shard)


def make_sharded_window_scan_step(mesh, *, interpolate: bool = True,
                                  handle_duplicates: bool = True):
    """Scanned key-sharded window step: K micro-batches per launch inside
    each shard's block (the window analogue of
    :func:`make_two_level_scan_step`, minus the global tier — windows have
    no cross-key state, so the hot path needs ZERO collectives).
    ``interpolate=False`` gives fixed-window semantics over the same state.

    Batch layout: ``slots_k/counts_k/valid_k: [n_shards, K, B_local]``
    (sharded on axis 0, shard-LOCAL slot ids), ``nows_k: i32[K]``
    replicated. Returns ``(new_state, granted, remaining)``.
    """
    state_specs = K.WindowState(P(SHARD_AXIS), P(SHARD_AXIS),
                                P(SHARD_AXIS), P(SHARD_AXIS))
    batch_spec = P(SHARD_AXIS, None, None)

    def block(state, slots, counts, valid, nows, limit, window_ticks):
        def body(st, xs):
            sl, ct, va, now = xs
            st, granted, remaining = K._window_acquire_core(
                st, sl, ct, va, now, limit, window_ticks,
                handle_duplicates=handle_duplicates,
                interpolate=interpolate,
            )
            return st, (granted, remaining)

        state, (granted, remaining) = jax.lax.scan(
            body, state, (slots[0], counts[0], valid[0], nows),
        )
        return state, granted[None], remaining[None]

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(state_specs, batch_spec, batch_spec, batch_spec,
                  P(), P(), P()),
        out_specs=(state_specs, batch_spec, batch_spec),
    )
    return jax.jit(mapped, donate_argnums=0)


class ShardedWindowStore(_ShardedKeyedTable):
    """Key-sharded sliding/fixed-window table over a mesh — BASELINE
    config 4 at mesh scale. Mirrors :class:`ShardedDeviceStore`'s host
    runtime (same directories, routing, growth, sweeps) over
    ``WindowState`` with the scanned window step; one homogeneous
    ``(limit, window, fixed?)`` config per instance, matching the
    single-chip ``_DeviceWindowTable``."""

    def __init__(self, mesh, limit: float, window_sec: float, *,
                 fixed: bool = False, per_shard_slots: int = 2**14,
                 clock: Clock | None = None,
                 handle_duplicates: bool = True,
                 rebase_threshold_ticks: int = _REBASE_THRESHOLD_TICKS) -> None:
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.per_shard = per_shard_slots
        self.limit = float(limit)
        self.window_ticks = int(window_sec * bm.TICKS_PER_SECOND)
        self.fixed = fixed
        self.clock = clock or MonotonicClock()
        self.metrics = StoreMetrics()
        # See ShardedDeviceStore: a composing store coordinates rebases.
        self._rebase_threshold = rebase_threshold_ticks
        n_total = self.n_shards * per_shard_slots
        sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self.state = init_sharded(K.init_window_state, n_total, sharding)
        self._scan_step = make_sharded_window_scan_step(
            mesh, interpolate=not fixed,
            handle_duplicates=handle_duplicates)
        self.dirs = [make_directory(per_shard_slots)
                     for _ in range(self.n_shards)]
        import threading

        self._lock = threading.RLock()

    # -- _ShardedKeyedTable hooks ------------------------------------------
    def _widen_state(self, old: int, new: int) -> None:
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self.state = K.WindowState(
            prev_count=jax.device_put(
                self._widen_host(self.state.prev_count, old, new), sharding),
            curr_count=jax.device_put(
                self._widen_host(self.state.curr_count, old, new), sharding),
            window_idx=jax.device_put(
                self._widen_host(self.state.window_idx, old, new), sharding),
            exists=jax.device_put(
                self._widen_host(self.state.exists, old, new), sharding),
        )

    def _device_sweep(self) -> np.ndarray:
        self.state, freed = K.sweep_windows(
            self.state, jnp.int32(self.now_ticks_checked()),
            jnp.int32(self.window_ticks),
        )
        return np.asarray(freed)

    def force_rebase(self, offset_ticks: int) -> None:
        """Window tables rebase by whole windows (see
        ``kernels.rebase_window_epoch``) — called by the composing store's
        coordinated rebase, or by ``now_ticks_checked`` standalone."""
        with self._lock:
            self.state = K.rebase_window_epoch(
                self.state, jnp.int32(offset_ticks // self.window_ticks))

    # -- decisions ---------------------------------------------------------
    def acquire_many_blocking(
        self, keys: Sequence[str], counts: Sequence[int], *,
        with_remaining: bool = True,
    ) -> BulkAcquireResult:
        """Whole-array bulk window acquire over the mesh — the shared
        ``_bulk_decide`` chunking over the scanned window step."""
        limit_dev = jnp.float32(self.limit)
        window_dev = jnp.int32(self.window_ticks)

        def launch_chunk(slots, counts_dev, valid, nows):
            self.state, granted, remaining = self._scan_step(
                self.state, slots, counts_dev, valid, nows,
                limit_dev, window_dev,
            )
            return granted, remaining

        return self._bulk_decide(keys, counts, with_remaining, launch_chunk)

    def acquire_batch_blocking(
        self, requests: Sequence[tuple[str, int]],
    ) -> list[AcquireResult]:
        res = self.acquire_many_blocking(
            [k for k, _ in requests], [c for _, c in requests])
        return list(res)

    # -- checkpoint --------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "now_ticks": self.clock.now_ticks(),
                "n_shards": self.n_shards,
                "per_shard": self.per_shard,
                "limit": self.limit,
                "window_ticks": self.window_ticks,
                "fixed": self.fixed,
                "directories": [d.to_dict() for d in self.dirs],
                "prev_count": np.asarray(self.state.prev_count),
                "curr_count": np.asarray(self.state.curr_count),
                "window_idx": np.asarray(self.state.window_idx),
                "exists": np.asarray(self.state.exists),
            }

    def restore(self, snap: dict) -> None:
        with self._lock:
            if snap["n_shards"] != self.n_shards:
                raise ValueError(
                    f"snapshot geometry {snap['n_shards']}x"
                    f"{snap['per_shard']} != store geometry "
                    f"{self.n_shards}x{self.per_shard} (shard count must "
                    "match)")
            if (snap["limit"] != self.limit
                    or snap["window_ticks"] != self.window_ticks
                    or snap["fixed"] != self.fixed):
                raise ValueError("snapshot config != store config")
            self.per_shard = int(snap["per_shard"])
            # Window indices re-align by whole windows, with the SAME
            # signed clamp as the single-chip restore (_shift_ts): a large
            # negative shift must leave stale indices negative — i.e.
            # long-expired — not clip them to "current window", which
            # would enforce stale counts against fresh requests.
            shift_w = ((int(self.clock.now_ticks()) - int(snap["now_ticks"]))
                       // self.window_ticks)
            idx = _shift_ts(snap["window_idx"], shift_w)
            sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
            self.state = K.WindowState(
                prev_count=jax.device_put(
                    jnp.asarray(snap["prev_count"]), sharding),
                curr_count=jax.device_put(
                    jnp.asarray(snap["curr_count"]), sharding),
                window_idx=jax.device_put(jnp.asarray(idx), sharding),
                exists=jax.device_put(jnp.asarray(snap["exists"]), sharding),
            )
            for d, mapping in zip(self.dirs, snap["directories"]):
                d.load(mapping, self.per_shard)
