"""Mesh-sharded device-resident directory: fingerprints over ICI.

Combines the two flagship designs: the key-sharded mesh tables of
:mod:`~.sharded_store` (keys never interact ⇒ zero hot-path collectives;
the only collective is the two-level global tier's psum — SURVEY.md §5.7/8)
with the device-resident fingerprint directory of
:mod:`~..ops.fp_directory` (in-kernel probe/insert; the host's per-batch
duty is one hashing pass).

Routing falls out for free: the fingerprint IS the route. Shard =
``fp_lo % n_shards`` — no second hash, no crc32 pass; every host routes
identically because every host hashes identically. Each shard holds an
independent fingerprint table + state slice in its own HBM and probes
shard-locally; TTL sweeps stay elementwise (the single-chip sweep
kernels applied to sharded arrays preserve the sharding with no
collectives), and growth is a per-shard device rehash (the route is
resize-invariant). Both table families ship: token buckets
(:class:`ShardedFpDeviceStore`, with the psum global tier) and
sliding/fixed windows (:class:`ShardedFpWindowStore`, collective-free).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distributedratelimiting.redis_tpu.ops import bucket_math as bm
from distributedratelimiting.redis_tpu.ops import fp_directory as F
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.parallel.mesh import (
    SHARD_AXIS,
    init_sharded,
)
from distributedratelimiting.redis_tpu.parallel.sharded_store import (
    GlobalCounter,
    global_tier_update,
    init_global_counter,
)
from distributedratelimiting.redis_tpu.runtime.clock import Clock, MonotonicClock
from distributedratelimiting.redis_tpu.utils.metrics import StoreMetrics
from distributedratelimiting.redis_tpu.runtime.store import (
    BulkAcquireResult,
    _grant_zero_probes,
    _rate_per_tick,
    _REBASE_MARGIN_TICKS,
    _REBASE_THRESHOLD_TICKS,
)

__all__ = ["make_sharded_fp_scan_step",
           "make_sharded_fp_window_scan_step",
           "make_sharded_fp_migrate_step",
           "ShardedFpDeviceStore", "ShardedFpWindowStore"]


def make_sharded_fp_migrate_step(mesh, state_cls=None, *,
                                 probe_window: int = 16,
                                 rounds: int = 4):
    """Jitted per-shard rehash chunk for mesh growth: each shard claims
    slots for a chunk of ITS old entries in its doubled slice and
    scatters the per-slot state columns across — no collectives (shard =
    ``fp_lo % n_shards`` is invariant under resize, so entries never move
    between shards; only within their shard's table). ``state_cls`` picks
    the table family (:class:`~..ops.kernels.BucketState` default, or
    ``WindowState``)."""
    state_cls = state_cls or K.BucketState
    nf = len(state_cls._fields)
    fp_spec = P(SHARD_AXIS, None)
    state_specs = state_cls(*([P(SHARD_AXIS)] * nf))
    kpair_spec = P(SHARD_AXIS, None, None)
    col_spec = P(SHARD_AXIS, None)

    def block(fp, state, kpair, *rest):
        cols, valid = rest[:-1], rest[-1]
        fp, state, placed = F._fp_migrate_core(
            fp, state, kpair[0], tuple(c[0] for c in cols), valid[0],
            probe_window=probe_window, rounds=rounds)
        return fp, state, placed[None]

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(fp_spec, state_specs, kpair_spec)
        + (col_spec,) * (nf + 1),
        out_specs=(fp_spec, state_specs, P(SHARD_AXIS)),
    )
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_sharded_fp_window_scan_step(mesh, *, probe_window: int = 16,
                                     rounds: int = 4,
                                     handle_duplicates: bool = True,
                                     interpolate: bool = True):
    """Window-family analogue of :func:`make_sharded_fp_scan_step` —
    fused in-shard probe/insert + sliding/fixed-window decision, no
    collectives at all (windows have no cross-key state; the global tier
    is the approximate BUCKET algorithm's). ``interpolate=False`` =
    fixed-window semantics. Same one-operand/one-result transfer shape:
    takes ``fused_k u32[n_shards, K, B, 3]``, returns
    ``(fp, state, out f32[n_shards, K, 2, B])``."""
    fp_spec = P(SHARD_AXIS, None)
    state_specs = K.WindowState(P(SHARD_AXIS), P(SHARD_AXIS),
                                P(SHARD_AXIS), P(SHARD_AXIS))
    fused_spec = P(SHARD_AXIS, None, None, None)
    out_spec = P(SHARD_AXIS, None, None, None)

    def block(fp, state, fused, nows, limit, window_ticks):
        def body(carry, xs):
            f, st = carry
            fu, now = xs
            kp, ct, va = F._unpack_fp12(fu)
            f, st, granted, remaining, resolved = F._fp_window_core(
                f, st, kp, ct, va, now, limit, window_ticks,
                probe_window=probe_window, rounds=rounds,
                handle_duplicates=handle_duplicates,
                interpolate=interpolate)
            code = (granted.astype(jnp.float32)
                    + 2.0 * resolved.astype(jnp.float32))
            return (f, st), jnp.stack([code, remaining])

        (fp, state), out = jax.lax.scan(
            body, (fp, state), (fused[0], nows))
        return (fp, state, out[None])

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(fp_spec, state_specs, fused_spec, P(), P(), P()),
        out_specs=(fp_spec, state_specs, out_spec),
    )
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_sharded_fp_scan_step(mesh, *, probe_window: int = 16,
                              rounds: int = 4,
                              handle_duplicates: bool = True,
                              sync_cadence: str = "batch"):
    """Jitted sharded fused resolve+acquire with the psum global tier.

    Layout: ``fp u32[N, 2]`` and bucket state sharded along keys
    (``P(SHARD_AXIS)``); batch ``fused_k u32[n_shards, K, B, 3]`` (the
    :func:`~..ops.fp_directory.pack_fp12` layout — ONE operand array per
    launch, shard-LOCAL fingerprints) sharded on axis 0; ``nows_k
    i32[K]`` replicated. Each scanned batch runs probe/insert + decision
    in-shard; the scalar psum feeding the replicated decaying global
    counter runs per scanned batch (``sync_cadence="batch"``) or once
    per launch over the accumulated consumed count (``"launch"`` — same
    deployable cadence trade as
    :func:`~.sharded_store.make_two_level_scan_step_deferred`; grants are
    bit-identical, counter staleness ≤ one launch's span).

    Returns ``(fp, state, out f32[n_shards, K, 2, B], gcounter)`` — the
    result rides ONE array per launch: row 0 encodes
    ``granted + 2·resolved`` exactly, row 1 is remaining.
    """
    if sync_cadence not in ("batch", "launch"):
        raise ValueError("sync_cadence must be 'batch' or 'launch'")
    fp_spec = P(SHARD_AXIS, None)
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    gspecs = GlobalCounter(P(), P(), P(), P())
    fused_spec = P(SHARD_AXIS, None, None, None)
    out_spec = P(SHARD_AXIS, None, None, None)
    deferred = sync_cadence == "launch"

    def block(fp, state, fused, nows, capacity, rate, gcounter, decay_rate):
        def body(carry, xs):
            f, st, g, consumed_acc = carry
            fu, now = xs
            kp, ct, va = F._unpack_fp12(fu)
            f, st, granted, remaining, resolved = F._fp_acquire_core(
                f, st, kp, ct, va, now, capacity, rate,
                probe_window=probe_window, rounds=rounds,
                handle_duplicates=handle_duplicates)
            consumed = jnp.sum(jnp.asarray(ct, jnp.float32) * granted)
            if deferred:
                consumed_acc = consumed_acc + consumed
            else:
                total = jax.lax.psum(consumed, SHARD_AXIS)
                g = global_tier_update(g, total, now, decay_rate)
            code = (granted.astype(jnp.float32)
                    + 2.0 * resolved.astype(jnp.float32))
            return (f, st, g, consumed_acc), jnp.stack([code, remaining])

        # The accumulator is per-shard ("varying" over the mesh axis inside
        # shard_map); the initial zero must be cast to match.
        zero = jax.lax.pcast(jnp.zeros((), jnp.float32), (SHARD_AXIS,),
                             to="varying")
        ((fp, state, gcounter, consumed_total), out) = jax.lax.scan(
            body, (fp, state, gcounter, zero), (fused[0], nows))
        if deferred:
            total = jax.lax.psum(consumed_total, SHARD_AXIS)  # ONE/launch
            gcounter = global_tier_update(gcounter, total, nows[-1],
                                          decay_rate)
        return (fp, state, out[None], gcounter)

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(fp_spec, state_specs, fused_spec, P(), P(), P(), gspecs,
                  P()),
        out_specs=(fp_spec, state_specs, out_spec, gspecs),
    )
    return jax.jit(mapped, donate_argnums=(0, 1, 6))


class ShardedFpDeviceStore:
    """Serving wrapper: bulk decisions against mesh-sharded fingerprint
    tables. One homogeneous config per instance (like
    :class:`~.sharded_store.ShardedDeviceStore`); the bulk path hashes
    once, routes by ``fp_lo % n_shards`` (vectorized numpy — the
    fingerprint is the route), groups order-stably per shard, and decides
    the whole call in scanned fused launches.

    Window pressure (a request whose shard-local probe window can't place
    it) denies the row, counts it in ``fp_unresolved``, and heals:
    sweep, then — if the sweep freed (almost) nothing — an all-shard
    doubling via the device-side per-shard rehash
    (:func:`make_sharded_fp_migrate_step`; entries never cross shards
    because the route ``fp_lo % n_shards`` is resize-invariant). Denied
    requests are not retried in-call (deny-and-heal, as on the single
    chip); the caller's next attempt lands in the relieved table. Set
    ``auto_grow=False`` to presize instead.
    """

    _BULK_MAX_K = 8

    def __init__(self, mesh, *, capacity: float, fill_rate_per_sec: float,
                 per_shard_slots: int = 1 << 16, batch: int = 512,
                 probe_window: int = 16, rounds: int = 4,
                 decay_rate_per_sec: float = 0.0,
                 clock: Clock | None = None,
                 auto_grow: bool = True,
                 sync_cadence: str = "batch",
                 rebase_threshold_ticks: int = _REBASE_THRESHOLD_TICKS
                 ) -> None:
        import threading

        if sync_cadence not in ("batch", "launch"):
            raise ValueError("sync_cadence must be 'batch' or 'launch'")
        # Global-tier psum cadence; irrelevant to the window subclass
        # (its step has no global tier) but accepted uniformly so the
        # mesh store can pass one config to every sharded tier.
        self.sync_cadence = sync_cadence
        self.mesh = mesh
        # Donated-state launches must serialize (the codebase-wide rule:
        # a second launch while one is in flight would reuse a deleted
        # buffer); sweeps and rebases take the same lock.
        self._lock = threading.RLock()
        self._rebase_threshold = rebase_threshold_ticks
        self.n_shards = mesh.devices.size
        if per_shard_slots < probe_window:
            # Same contract as _FpTable: the non-wrapping placement
            # (n - L + 1 modulus) is undefined below one window per
            # shard, and would silently wrap to garbage bases.
            raise ValueError(
                f"per_shard_slots ({per_shard_slots}) must be >= "
                f"probe_window ({probe_window})")
        self.capacity = float(capacity)
        self.rate_per_tick = _rate_per_tick(fill_rate_per_sec)
        self.decay_per_tick = _rate_per_tick(decay_rate_per_sec)
        self.per_shard_slots = per_shard_slots
        self.batch = batch
        self.probe_window = probe_window
        self.rounds = rounds
        self.clock = clock or MonotonicClock()
        self.auto_grow = auto_grow
        self.metrics = StoreMetrics()
        self.fp_unresolved = 0
        self.grows = 0
        self._peek_step = None

        fp_shard = NamedSharding(mesh, P(SHARD_AXIS, None))
        n = per_shard_slots * self.n_shards
        self.fp = init_sharded(F.init_fp_table, n, fp_shard)
        self.state = self._fresh_sharded_state(n)
        self.gcounter = jax.device_put(
            init_global_counter(), NamedSharding(mesh, P()))
        self._step = self._make_step()

    # -- table-family hooks (the window subclass swaps these) --------------
    def _init_state_host(self, n: int):
        return K.init_bucket_state(n)

    def _fresh_sharded_state(self, n: int):
        return init_sharded(self._init_state_host, n,
                            NamedSharding(self.mesh, P(SHARD_AXIS)))

    def _make_step(self):
        return make_sharded_fp_scan_step(
            self.mesh, probe_window=self.probe_window, rounds=self.rounds,
            sync_cadence=self.sync_cadence)

    def _launch(self, fused, nows):
        """One scanned fused dispatch (caller holds the lock); updates
        the table in place, returns the ``f32[S, K, 2, B]`` result
        handle (code row = granted + 2·resolved, row 1 = remaining)."""
        self.fp, self.state, out, self.gcounter = self._step(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            jnp.float32(self.capacity), jnp.float32(self.rate_per_tick),
            self.gcounter, jnp.float32(self.decay_per_tick))
        return out

    @property
    def global_score(self) -> float:
        return float(np.asarray(self.gcounter.value))

    def now_ticks_checked(self) -> int:
        """Clock read with int32-overflow protection (the codebase-wide
        rule: rebase table + clock together before ~24 days of tick time
        can overflow the i32 ``now`` operand)."""
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

    def force_rebase(self, offset: int) -> None:
        """Shift bucket + global-counter timestamps without touching the
        clock (fingerprints carry no time state)."""
        with self._lock:
            self.state = K.rebase_bucket_epoch(self.state, jnp.int32(offset))
            self.gcounter = GlobalCounter(
                value=self.gcounter.value, period=self.gcounter.period,
                last_ts=jnp.maximum(
                    self.gcounter.last_ts - jnp.int32(offset), 0),
                exists=self.gcounter.exists)

    def acquire_many_blocking(self, keys: Sequence[str],
                              counts: Sequence[int], *,
                              with_remaining: bool = True
                              ) -> BulkAcquireResult:
        from distributedratelimiting.redis_tpu.runtime.fp_store import (
            fingerprints,
        )

        n = len(keys)
        if n == 0:
            return BulkAcquireResult(
                np.zeros(0, bool),
                np.zeros(0, np.float32) if with_remaining else None)
        counts_np = np.asarray(counts, np.int64)
        fps = fingerprints(keys)  # KeyBlob-aware
        routes = fps[:, 0] % np.uint32(self.n_shards)
        order = np.argsort(routes, kind="stable")  # per-shard arrival order
        bounds = np.searchsorted(routes[order], np.arange(self.n_shards + 1))
        per_shard = np.diff(bounds)
        rows = int(per_shard.max())

        granted = np.zeros(n, bool)
        remaining = np.zeros(n, np.float32) if with_remaining else None
        b = self.batch
        pos = 0  # row offset within each shard's group, advanced per launch
        self._lock.acquire()  # donated-state launches serialize
        try:
            # Sampled under the lock: a concurrent epoch rebase must not
            # pair a pre-rebase `now` with post-rebase state.
            now = self.now_ticks_checked()
            call_pressure = 0
            # Per-DEVICE operand budget (each shard's slice rides its own
            # host→device link): scan depth shrinks before one device's
            # slice crosses the ~768KB-1MB transfer collapse — the
            # single-chip fp store's _BULK_BYTE_BUDGET discipline.
            max_k = self._BULK_MAX_K
            while max_k > 1 and max_k * b * 12 > 640 * 1024:
                max_k //= 2
            while pos < rows:
                k = 1
                need_rows = -(-(rows - pos) // b)
                while k < need_rows and k < max_k:
                    k *= 2
                take = k * b
                # ONE fused operand per launch (pack_fp12 layout: lo, hi,
                # count; 0xFFFFFFFF count ⇒ padding) and ONE result array
                # back — transfer-count discipline, same as the
                # single-chip fp bulk path.
                fused = np.zeros((self.n_shards, k * b, 3), np.uint32)
                fused[:, :, 2] = np.uint32(0xFFFFFFFF)
                sel = []  # (shard, local slice, global order slice)
                n_valid = 0
                for s in range(self.n_shards):
                    lo = bounds[s] + pos
                    hi = min(bounds[s + 1], lo + take)
                    m = max(0, hi - lo)
                    if m == 0:
                        continue
                    idx = order[lo:hi]
                    fused[s, :m] = F.pack_fp12(fps[idx], counts_np[idx])
                    n_valid += m
                    sel.append((s, m, idx))
                nows = np.full((k,), now, np.int32)
                out_d = self._launch(
                    fused.reshape(self.n_shards, k, b, 3), nows)
                self.metrics.record_launch(self.n_shards * k * b, n_valid)
                out_np = np.asarray(out_d)  # [S, K, 2, B]
                code = out_np[:, :, 0, :].reshape(
                    self.n_shards, -1).astype(np.int32)
                r_np = out_np[:, :, 1, :].reshape(self.n_shards, -1)
                for s, m, idx in sel:
                    granted[idx] = (code[s, :m] & 1).astype(bool)
                    if remaining is not None:
                        remaining[idx] = r_np[s, :m]
                    call_pressure += int((~((code[s, :m] & 2) > 0)).sum())
                pos += take
            self.fp_unresolved += call_pressure
            self.metrics.fp_unresolved += call_pressure
            if call_pressure and self.auto_grow:
                # Deny-and-heal (single-chip discipline, both clauses —
                # see _FpTable._relieve_pressure): sweep, then grow when
                # the sweep freed (almost) nothing OR the table is past
                # the growth threshold (live keys can saturate a probe
                # window at modest load factors).
                n_total = self.per_shard_slots * self.n_shards
                freed = self._sweep_locked()
                if (freed < max(1, n_total // 16)
                        or self._occupancy() >= 0.7 * n_total):
                    self._grow_locked()
        finally:
            self._lock.release()
        _grant_zero_probes(granted, counts_np)
        return BulkAcquireResult(granted, remaining)

    def _occupancy(self) -> int:
        # Caller holds the lock (donated buffers).
        return int(np.asarray((np.asarray(self.fp) != 0).any(-1).sum()))

    def _grow_locked(self) -> None:
        """All-shard doubling via the device-side per-shard rehash: each
        shard's entries re-place within the shard's doubled slice (the
        route is resize-invariant, so nothing crosses shards)."""
        old_fp = np.asarray(self.fp).reshape(self.n_shards, -1, 2)
        olds = [np.asarray(a).reshape(self.n_shards, -1)
                for a in self.state]
        self._rehash_locked(old_fp, olds, old_fp.shape[1] * 2)
        self.grows += 1
        self.metrics.pregrows += 1

    def _rehash_locked(self, old_fp: np.ndarray, olds: list,
                       per_start: int,
                       probe_window: int | None = None) -> None:
        """Re-place every shard's live entries into fresh sharded tables
        (``old_fp`` is ``[S, per_old, 2]``, ``olds`` state columns in
        field order, same shape) — the shared driver behind growth and
        legacy-snapshot adoption. Caller holds the lock; nothing mutates
        until placement succeeds. ``probe_window`` lets snapshot adoption
        place under the snapshot's geometry before the caller commits it.

        A shard entry whose whole window fills with other entries is
        unplaceable at a given size — a density accident; double and
        retry (load halves per attempt, so this converges), with a cap
        so a pathological set still fails loudly. Same discipline as
        _FpTable._rehash."""
        pw = self.probe_window if probe_window is None else probe_window
        entries = [np.nonzero((old_fp[s] != 0).any(-1))[0]
                   for s in range(self.n_shards)]
        migrate = make_sharded_fp_migrate_step(
            self.mesh, type(self.state), probe_window=pw,
            rounds=self.rounds)
        b = self.batch
        per_new = per_start  # committed only after the rehash
        leftover = 0
        for _attempt in range(4):
            n = per_new * self.n_shards
            fp_shard = NamedSharding(self.mesh, P(SHARD_AXIS, None))
            fp = init_sharded(F.init_fp_table, n, fp_shard)
            state = self._fresh_sharded_state(n)
            pending = entries
            stuck = False
            # Unplaced entries (bounded insert rounds under in-chunk
            # window contention) retry in later passes; zero-progress ⇒
            # some window is genuinely full at this size.
            while any(len(p) for p in pending):
                next_pending = [[] for _ in range(self.n_shards)]
                rows = max(len(p) for p in pending)
                pos = 0
                while pos < rows:
                    kpair = np.zeros((self.n_shards, b, 2), np.uint32)
                    cols = [np.zeros((self.n_shards, b), a.dtype)
                            for a in olds]
                    valid = np.zeros((self.n_shards, b), bool)
                    chunk_idx = [None] * self.n_shards
                    for s in range(self.n_shards):
                        idx = pending[s][pos:pos + b]
                        m = len(idx)
                        if m == 0:
                            continue
                        chunk_idx[s] = idx
                        kpair[s, :m] = old_fp[s][idx]
                        for c, a in zip(cols, olds):
                            c[s, :m] = a[s][idx]
                        valid[s, :m] = True
                    fp, state, placed = migrate(
                        fp, state, jnp.asarray(kpair),
                        *(jnp.asarray(c) for c in cols), jnp.asarray(valid))
                    placed_np = np.asarray(placed).reshape(self.n_shards, -1)
                    for s in range(self.n_shards):
                        idx = chunk_idx[s]
                        if idx is None:
                            continue
                        miss = ~placed_np[s, :len(idx)]
                        if miss.any():
                            next_pending[s].append(idx[miss])
                    pos += b
                new_pending = [
                    np.concatenate(p) if p else np.zeros((0,), np.int64)
                    for p in next_pending]
                if (sum(len(p) for p in new_pending)
                        >= sum(len(p) for p in pending)):
                    stuck = True
                    leftover = sum(len(p) for p in new_pending)
                    break
                pending = new_pending
            if not stuck:
                self.fp, self.state = fp, state
                self.per_shard_slots = per_new
                return
            per_new *= 2
        raise RuntimeError(
            f"sharded fingerprint rehash cannot place {leftover} entries "
            f"even at {per_new // 2} slots/shard")

    def sweep(self) -> int:
        """Elementwise TTL sweep across every shard — the single-chip
        kernel applied to the sharded arrays (sharding is preserved, no
        collectives). Returns slots freed."""
        with self._lock:
            return self._sweep_locked()

    def _sweep_locked(self) -> int:
        # `now` FIRST: now_ticks_checked can fire an epoch rebase that
        # donates-and-replaces self.state — arguments already evaluated
        # would then reference deleted (or stale pre-rebase) buffers.
        now = self.now_ticks_checked()
        self.fp, self.state, n_freed = F.fp_sweep_expired(
            self.fp, self.state, jnp.int32(now),
            jnp.float32(self.capacity), jnp.float32(self.rate_per_tick))
        freed = int(np.asarray(n_freed))
        self.metrics.sweeps += 1
        self.metrics.slots_evicted += freed
        return freed

    # -- per-request flush surface (the mesh front-end's batcher) ----------
    def acquire_batch_blocking(
            self, requests: "Sequence[tuple[str, int]]"
    ) -> "list[AcquireResult]":
        """Decide a batch of ``(key, count)`` requests in one bulk call;
        results in request order (same in-call duplicate conservatism as
        :meth:`acquire_many_blocking`)."""
        return list(self.acquire_many_blocking(
            [k for k, _ in requests], [c for _, c in requests]))

    def peek_blocking(self, key: str) -> float:
        """Read-only availability estimate — shard-local lookup WITHOUT
        insert (peeking at an unseen key must not claim a slot)."""
        from distributedratelimiting.redis_tpu.runtime.fp_store import (
            fingerprints,
        )

        if self._peek_step is None:
            self._peek_step = _make_sharded_fp_peek_step(
                self.mesh, probe_window=self.probe_window)
        fp1 = fingerprints([key])[0]
        shard = int(fp1[0] % np.uint32(self.n_shards))
        kpair = np.zeros((self.n_shards, 8, 2), np.uint32)
        valid = np.zeros((self.n_shards, 8), bool)
        kpair[shard, 0] = fp1
        valid[shard, 0] = True
        with self._lock:
            now = self.now_ticks_checked()
            est = self._peek_step(
                self.fp, self.state, jnp.asarray(kpair),
                jnp.asarray(valid), jnp.int32(now),
                jnp.float32(self.capacity), jnp.float32(self.rate_per_tick))
        return float(np.asarray(est)[shard, 0])

    # -- checkpoint --------------------------------------------------------
    def _config_snap(self) -> dict:
        return {"capacity": self.capacity,
                "rate_per_tick": self.rate_per_tick}

    def _check_config_snap(self, snap: dict) -> None:
        want = self._config_snap()
        got = {k: snap.get(k) for k in want}
        if got != want:
            raise ValueError(
                f"snapshot config {got} != store config {want} — a "
                "fingerprint snapshot restores only into a same-config "
                "store")

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "now_ticks": self.clock.now_ticks(),
                "n_shards": self.n_shards,
                "per_shard": self.per_shard_slots,
                "probe_window": self.probe_window,
                "placement": F.PLACEMENT_VERSION,
                "fp": np.asarray(self.fp),
                "gcounter": {
                    "value": float(np.asarray(self.gcounter.value)),
                    "period": float(np.asarray(self.gcounter.period)),
                    "last_ts": int(np.asarray(self.gcounter.last_ts)),
                    "exists": bool(np.asarray(self.gcounter.exists)),
                },
            }
            snap.update(self._config_snap())
            for f in type(self.state)._fields:
                snap[f] = np.asarray(getattr(self.state, f))
            return snap

    def restore(self, snap: dict) -> None:
        from distributedratelimiting.redis_tpu.runtime.store import _shift_ts

        with self._lock:
            if "fp" not in snap:
                raise ValueError(
                    "snapshot's tables use the host key directory — "
                    "restore into the host-directory sharded store")
            if snap["n_shards"] != self.n_shards:
                raise ValueError(
                    f"snapshot shard count {snap['n_shards']} != "
                    f"store {self.n_shards} (fingerprints route by "
                    "fp % n_shards — re-sharding is key redistribution)")
            self._check_config_snap(snap)
            shift = int(self.clock.now_ticks()) - int(snap["now_ticks"])
            new_pw = int(snap.get("probe_window", self.probe_window))
            cls = type(self.state)
            raw_cols = []
            for f in cls._fields:
                a = snap[f]
                if f == "last_ts":
                    a = _shift_ts(a, shift)
                elif f == "window_idx":
                    a = _shift_ts(a, shift // self.window_ticks)
                raw_cols.append(np.asarray(a))
            # Install the tables FIRST — the legacy re-place below can
            # raise, and config committed before a failed install would
            # leave a half-restored store whose probe geometry no longer
            # matches its live tables.
            if snap.get("placement") != F.PLACEMENT_VERSION:
                # Pre-v2 snapshots placed entries with the wrapping h % n
                # window; verbatim install under the non-wrapping
                # placement would orphan nearly every key. Re-place
                # through the migrate kernel (shard routing is
                # placement-invariant, so entries stay in their shards).
                self._rehash_locked(
                    np.asarray(snap["fp"]).reshape(self.n_shards, -1, 2),
                    [c.reshape(self.n_shards, -1) for c in raw_cols],
                    int(snap["per_shard"]), probe_window=new_pw)
            else:
                fp_shard = NamedSharding(self.mesh, P(SHARD_AXIS, None))
                shard = NamedSharding(self.mesh, P(SHARD_AXIS))
                self.fp = jax.device_put(jnp.asarray(snap["fp"]), fp_shard)
                self.state = cls(*(jax.device_put(jnp.asarray(a), shard)
                                   for a in raw_cols))
                self.per_shard_slots = int(snap["per_shard"])
            if new_pw != self.probe_window:
                # The jitted steps bake probe_window in at construction;
                # entries placed deep in a wider window would be
                # invisible to a narrower scan.
                self.probe_window = new_pw
                self._step = self._make_step()
                self._peek_step = None
            g = snap.get("gcounter")
            if g is not None:
                self.gcounter = jax.device_put(GlobalCounter(
                    value=jnp.float32(g["value"]),
                    period=jnp.float32(g["period"]),
                    last_ts=jnp.int32(max(0, g["last_ts"] + shift)),
                    exists=jnp.asarray(g["exists"])),
                    NamedSharding(self.mesh, P()))


def _make_sharded_fp_peek_step(mesh, *, probe_window: int):
    """Shard-local read-only lookup: the key's fingerprint sits in ITS
    shard's batch row; every shard probes its own slice (wrong-shard rows
    are invalid ⇒ 0)."""
    fp_spec = P(SHARD_AXIS, None)
    state_specs = K.BucketState(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    kpair_spec = P(SHARD_AXIS, None, None)
    row_spec = P(SHARD_AXIS, None)

    def block(fp, state, kpair, valid, now, capacity, rate):
        est = F.fp_peek_batch(fp, state, kpair[0], valid[0], now, capacity,
                              rate, probe_window=probe_window)
        return est[None]

    mapped = shard_map(
        block, mesh=mesh,
        in_specs=(fp_spec, state_specs, kpair_spec, row_spec, P(), P(), P()),
        out_specs=row_spec,
    )
    return jax.jit(mapped)


class ShardedFpWindowStore(ShardedFpDeviceStore):
    """Sliding/fixed-window tables with the device-resident directory
    over a mesh — the window member of the fp family's matrix (single
    chip × mesh, buckets × windows). No collectives at all: windows have
    no cross-key state, and the global tier belongs to the approximate
    bucket algorithm. Everything else (route-by-fingerprint bulk path,
    pressure heal, per-shard rehash growth, epoch rebase) is inherited.
    """

    def __init__(self, mesh, *, limit: float, window_sec: float,
                 fixed: bool = False, **kw) -> None:
        self.limit = float(limit)
        self.window_ticks = int(
            window_sec * bm.TICKS_PER_SECOND)
        self.fixed = fixed
        # capacity/fill-rate are bucket-family operands; unused here (the
        # base stores them, the window step never reads them).
        super().__init__(mesh, capacity=limit, fill_rate_per_sec=0.0, **kw)

    def _init_state_host(self, n: int):
        return K.init_window_state(n)

    def _make_step(self):
        return make_sharded_fp_window_scan_step(
            self.mesh, probe_window=self.probe_window, rounds=self.rounds,
            interpolate=not self.fixed)

    def _launch(self, fused, nows):
        self.fp, self.state, out = self._step(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            jnp.float32(self.limit), jnp.int32(self.window_ticks))
        return out

    def peek_blocking(self, key: str) -> float:
        raise NotImplementedError(
            "window tables expose no peek (matching the single-chip "
            "window tiers)")

    def _config_snap(self) -> dict:
        return {"limit": self.limit, "window_ticks": self.window_ticks,
                "fixed": self.fixed}

    def _sweep_locked(self) -> int:
        now = self.now_ticks_checked()  # before the args (rebase hazard)
        self.fp, self.state, n_freed = F.fp_sweep_windows(
            self.fp, self.state, jnp.int32(now),
            jnp.int32(self.window_ticks))
        freed = int(np.asarray(n_freed))
        self.metrics.sweeps += 1
        self.metrics.slots_evicted += freed
        return freed

    def force_rebase(self, offset: int) -> None:
        with self._lock:
            self.state = K.rebase_window_epoch(
                self.state, jnp.int32(offset // self.window_ticks))
