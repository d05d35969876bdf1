"""Device-directory store: key→slot lives in HBM, not on the host.

:class:`FingerprintBucketStore` is a :class:`~.store.DeviceBucketStore`
whose token-bucket tier swaps the host-side key directory
(``runtime/directory.py`` + ``native/directory.cc``) for the
device-resident fingerprint table of :mod:`~..ops.fp_directory`. Per
batch, the host's only duty is one 64-bit hashing pass over the keys
(``dir_fp64_pylist``); the kernel finds-or-claims each key's slot and
decides it in the SAME launch. What this buys over the host directory:

- no host table at all for buckets — no arena RAM at 10M keys, no
  GIL-held insert pass, no host free-list bookkeeping on sweeps (TTL
  eviction clears fingerprints on device, `fp_sweep_expired`);
- growth is a device-side rehash (``fp_migrate_chunk``): the host reads
  fingerprints back and chunks, placement + state movement stay on
  device.

The trade (made explicit, not hidden): requests ship 8-byte fingerprints
instead of packed slot ids, so per-decision transfer is larger than the
packed24 host-directory path, while this store saves host CPU and
memory (which side wins on the chip is not measured) (the SURVEY.md §7 "device-side
hashing/eviction/TTL" regime). Fingerprint collisions (two keys sharing a
bucket) occur with probability ≈ n²/2⁶⁵ — about 3·10⁻⁶ at 10M keys —
versus never for the byte-comparing host directory; see
``ops/fp_directory.py`` for the full disclosure.

The keyed hot tiers — token buckets AND sliding/fixed windows (the two
10M-key table families, BASELINE configs 3-4) — both run on the
device-resident directory (:class:`_FpTable` / :class:`_FpWindowTable`).
The remaining aux tiers (decaying counters, concurrency semaphores) are
inherited with the host directory: their key cardinality is per-limiter,
not per-end-user, so a host table of a few dozen entries is the right
tool there.
"""

from __future__ import annotations

import asyncio
import ctypes
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributedratelimiting.redis_tpu.ops import fp_directory as F
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.runtime.batcher import MicroBatcher
from distributedratelimiting.redis_tpu.runtime.store import (
    AcquireResult,
    BulkAcquireResult,
    DeviceBucketStore,
    _AcquireReq,
    _grant_zero_probes,
    _pad_size,
    _rate_per_tick,
    _shift_ts,
)
from distributedratelimiting.redis_tpu.utils.native import load_directory_lib

__all__ = ["FingerprintBucketStore", "fingerprints"]

_FNV_OFFSET = 1469598103934665603
_FNV_PRIME = 1099511628211
_PLACEMENT_VERSION = F.PLACEMENT_VERSION


def _fp64_py(key: str) -> int:
    """Pure-Python FNV-1a 64 — must stay bit-identical to the native
    ``dir_fp64_pylist`` (fingerprints live in device tables and
    checkpoints; every process must hash keys the same way)."""
    h = _FNV_OFFSET
    for byte in key.encode("utf-8", "surrogateescape"):
        h = ((h ^ byte) * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h or _FNV_OFFSET


def fingerprints(keys: Sequence[str]) -> np.ndarray:
    """Hash a key batch to ``u32[n, 2]`` (lo, hi) fingerprints — one
    native C pass when the directory library is built, the identical
    pure-Python FNV elsewhere. Never returns the all-zero EMPTY
    sentinel."""
    n = len(keys)
    out = np.empty((n, 2), np.uint32)
    lib = load_directory_lib()
    blob = getattr(keys, "blob", None)
    if lib is not None and blob is not None and n:
        # wire.KeyBlob zero-copy lane: hash straight off the frame bytes.
        lib.dir_fp64_batch(
            blob,
            keys.offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
        return out
    if lib is not None and getattr(lib, "has_pylist", False) and n:
        ks = keys if isinstance(keys, list) else list(keys)
        if lib.dir_fp64_pylist(
                ks, out.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint32))) == 0:
            return out
    for i, k in enumerate(keys):
        h = _fp64_py(k)
        out[i, 0] = h & 0xFFFFFFFF
        out[i, 1] = h >> 32
    return out


class _FpTable:
    """One homogeneous-config bucket table with a device-resident
    directory. External interface mirrors ``store._DeviceTable`` (the
    parent store's methods are reused wholesale); internally every launch
    carries fingerprints and the probe/insert happens in-kernel."""

    #: Scan depth cap for bulk dispatches (mirrors _PackedLaunchMixin).
    _BULK_MAX_K = 16
    #: Per-dispatch operand byte budget, the classic store's 640KB
    #: discipline at the fused layout's 12 B/decision (whether a transfer
    #: cliff exists on the chip's host link is not measured).
    _BULK_BYTE_BUDGET = 640 * 1024
    #: Grow when (occupied / n_slots) crosses this after window pressure.
    _GROW_AT = 0.7

    #: Dirty accounting for incremental checkpoints (store.py
    #: ``enable_dirty_tracking``): slot placement happens in-kernel here
    #: — the host never sees which slot a row landed in — so the gauge
    #: counts dispatched rows instead, a documented UPPER bound on dirty
    #: slots (duplicates re-count). ``None`` until armed; the v4 delta
    #: itself is a structural diff over the slot arrays, exact either
    #: way (runtime/checkpoint.py).
    dirty_rows: "int | None" = None

    def __init__(self, store: "FingerprintBucketStore", capacity: float,
                 fill_rate_per_sec: float, n_slots: int) -> None:
        if n_slots < store.probe_window:
            # n - L + 1 must stay positive: the non-wrapping window
            # placement (_base_index) is undefined below one window.
            raise ValueError(
                f"n_slots ({n_slots}) must be >= probe_window "
                f"({store.probe_window})")
        self.store = store
        self.capacity = float(capacity)
        self.fill_rate_per_sec = float(fill_rate_per_sec)
        self.rate_per_tick = _rate_per_tick(fill_rate_per_sec)
        self.n_slots = n_slots
        self.fp = F.init_fp_table(n_slots)
        self.state = K.init_bucket_state(n_slots)
        self.cap_dev = jnp.float32(self.capacity)
        self.rate_dev = jnp.float32(self.rate_per_tick)
        self.probe_window = store.probe_window
        self.rounds = store.insert_rounds
        self.batcher: MicroBatcher[_AcquireReq, AcquireResult] = MicroBatcher(
            self._flush,
            max_batch=store.max_batch,
            max_delay_s=store.max_delay_s,
            max_inflight=store.max_inflight,
            flush_latency=store.metrics.flush_latency,
            queue_latency=store.metrics.queue_latency,
            flush_observer=store._flush_observer,
        )

    # -- kernel bindings (the window subclass swaps these) ------------------
    def _call_batch(self, kpair, counts, valid, now):
        """Run one fused resolve+decide batch, updating the table in
        place; returns ``(granted, remaining, resolved)`` device handles.
        Caller holds the store lock (donated buffers)."""
        self.fp, self.state, granted, remaining, resolved = (
            F.fp_acquire_batch(
                self.fp, self.state, jnp.asarray(kpair),
                jnp.asarray(counts), jnp.asarray(valid), jnp.int32(now),
                self.cap_dev, self.rate_dev,
                probe_window=self.probe_window, rounds=self.rounds))
        return granted, remaining, resolved

    def _call_scan_fused(self, fused, nows):
        """Minimum-transfer bulk dispatch (with remaining): one
        :func:`~.ops.fp_directory.pack_fp12` operand up, one
        ``f32[K, 2, B]`` result down. Caller holds the store lock."""
        self.fp, self.state, out = F.fp_acquire_scan_fused(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            self.cap_dev, self.rate_dev,
            probe_window=self.probe_window, rounds=self.rounds)
        return out

    def _call_scan_fused_bits(self, fused, nows):
        """Verdict-only bulk dispatch: one operand up, granted+resolved
        bit-planes down (2 bits/decision). Caller holds the store lock."""
        self.fp, self.state, bits = F.fp_acquire_scan_fused_bits(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            self.cap_dev, self.rate_dev,
            probe_window=self.probe_window, rounds=self.rounds)
        return bits

    # -- launches (donated state: dispatch under the store lock) -----------
    def _launch_batch(self, kpair: np.ndarray, counts: np.ndarray,
                      valid: np.ndarray):
        """One fused resolve+acquire dispatch; returns device handles."""
        store = self.store
        with store._lock:
            now = store.now_ticks_checked()
            out = self._call_batch(kpair, counts, valid, now)
            n_valid = int(valid.sum())
            if self.dirty_rows is not None:
                self.dirty_rows += n_valid
            store.metrics.record_launch(len(valid), n_valid)
        return out

    def _postprocess(self, granted_np, remaining_np, resolved_np,
                     counts_np, m: int):
        """Shared readback fixups: zero-permit probes always grant
        (``_grant_probes`` contract) and window-pressure rows are counted
        + relieved."""
        granted = granted_np[:m].copy()
        _grant_zero_probes(granted, counts_np[:m])
        pressure = int((~resolved_np[:m]).sum())
        if pressure:
            self.store.metrics.fp_unresolved += pressure
            self._relieve_pressure()
        return granted, remaining_np[:m], resolved_np[:m]

    async def _flush(self, reqs: Sequence[_AcquireReq]) -> list[AcquireResult]:
        n = len(reqs)
        b = _pad_size(n)
        kpair = np.zeros((b, 2), np.uint32)
        kpair[:n] = fingerprints([r.key for r in reqs])
        counts = np.zeros((b,), np.int32)
        counts[:n] = [min(r.count, 2**31 - 1) for r in reqs]
        valid = np.zeros((b,), bool)
        valid[:n] = True
        granted_d, remaining_d, resolved_d = self._launch_batch(
            kpair, counts, valid)
        loop = asyncio.get_running_loop()
        g, r, res = await loop.run_in_executor(
            None, lambda: (np.asarray(granted_d), np.asarray(remaining_d),
                           np.asarray(resolved_d)))
        g, r, _ = self._postprocess(g, r, res, counts, n)
        return [AcquireResult(bool(g[i]), float(r[i])) for i in range(n)]

    def acquire_blocking(self, key: str, count: int) -> AcquireResult:
        b = 64
        kpair = np.zeros((b, 2), np.uint32)
        kpair[0] = fingerprints([key])[0]
        counts = np.zeros((b,), np.int32)
        counts[0] = min(count, 2**31 - 1)
        valid = np.zeros((b,), bool)
        valid[0] = True
        granted_d, remaining_d, resolved_d = self._launch_batch(
            kpair, counts, valid)
        g, r, _ = self._postprocess(
            np.asarray(granted_d), np.asarray(remaining_d),
            np.asarray(resolved_d), counts, 1)
        return AcquireResult(bool(g[0]), float(r[0]))

    # -- bulk --------------------------------------------------------------
    def _bulk_dispatch(self, keys: Sequence[str], counts_np: np.ndarray,
                       with_remaining: bool = True):
        """Chunked scan dispatches over the whole key array; returns
        ``[(result handle, take), ...]`` with no readback — each dispatch
        ships ONE fused operand array and fetches ONE result array
        (bit-planes on the verdict-only path), keeping the transfer count
        per call minimal (its share on the chip is not measured)."""
        n = len(keys)
        fps = fingerprints(keys)  # KeyBlob-aware
        b = self.store.max_batch
        outs = []
        store = self.store
        pos = 0
        with store.profiler.span("acquire_many_fp", n), store._lock:
            now = store.now_ticks_checked()
            if self.dirty_rows is not None:
                self.dirty_rows += n
            max_k = self._BULK_MAX_K
            while max_k > 1 and max_k * b * 12 > self._BULK_BYTE_BUDGET:
                max_k //= 2
            while pos < n:
                rows = -(-(n - pos) // b)
                k = 1
                while k < rows and k < max_k:
                    k *= 2
                take = min(k * b, n - pos)
                kp = np.zeros((k * b, 2), np.uint32)
                kp[:take] = fps[pos:pos + take]
                fused = F.pack_fp12(kp, counts_np[pos:pos + take])
                nows = np.full((k,), now, np.int32)
                # Bit-planes need B % 8 == 0 (same guard as the classic
                # store's bits path, store.py); otherwise ship the f32
                # fused result and let the gather ignore its remaining row.
                call = (self._call_scan_fused_bits
                        if not with_remaining and b % 8 == 0
                        else self._call_scan_fused)
                outs.append((call(fused.reshape(k, b, 3), nows), take))
                store.metrics.record_launch(k * b, take)
                pos += take
        return outs

    def _gather_bulk(self, outs, counts_np: np.ndarray,
                     with_remaining: bool) -> BulkAcquireResult:
        n = len(counts_np)
        granted = np.empty((n,), bool)
        remaining = np.empty((n,), np.float32) if with_remaining else None
        pressure = 0
        pos = 0
        # One device_get over every dispatch's handle: lets the runtime
        # overlap the fetches instead of paying one link RTT per dispatch
        # sequentially (multi-dispatch calls on a ~70 ms-RTT day).
        arrs = jax.device_get([h for h, _ in outs])
        for arr, (_, take) in zip(arrs, outs):
            if arr.dtype == np.uint8:  # u8[K, 2, B//8] bit-planes
                granted[pos:pos + take] = np.unpackbits(
                    arr[:, 0, :].reshape(-1),
                    bitorder="little").astype(bool)[:take]
                res = np.unpackbits(
                    arr[:, 1, :].reshape(-1),
                    bitorder="little").astype(bool)[:take]
            else:                    # f32[K, 2, B]: code row + remaining
                code = arr[:, 0, :].reshape(-1)[:take].astype(np.int32)
                granted[pos:pos + take] = (code & 1).astype(bool)
                res = (code & 2) > 0
                if remaining is not None:
                    remaining[pos:pos + take] = arr[:, 1, :].reshape(
                        -1)[:take]
            pressure += int((~res).sum())
            pos += take
        _grant_zero_probes(granted, counts_np)
        if pressure:
            self.store.metrics.fp_unresolved += pressure
            self._relieve_pressure()
        return BulkAcquireResult(granted, remaining)

    def acquire_many_blocking(self, keys: Sequence[str],
                              counts: Sequence[int], *,
                              with_remaining: bool = True
                              ) -> BulkAcquireResult:
        counts_np = np.asarray(counts, np.int64)
        outs = self._bulk_dispatch(keys, counts_np,
                                   with_remaining=with_remaining)
        return self._gather_bulk(outs, counts_np, with_remaining)

    async def acquire_many(self, keys: Sequence[str],
                           counts: Sequence[int], *,
                           with_remaining: bool = True) -> BulkAcquireResult:
        counts_np = np.asarray(counts, np.int64)
        outs = self._bulk_dispatch(keys, counts_np,
                                   with_remaining=with_remaining)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._gather_bulk(outs, counts_np, with_remaining))

    def _debit_launch(self, keys: Sequence[str],
                      amounts: Sequence[float]):
        """One saturating-debit launch with in-kernel slot resolution
        (``fp_debit_batch``) — the lane ``DeviceBucketStore.debit_many``
        dispatches through. On this store it carries BOTH the tier-0
        reconciliation shape and the hierarchical deny-refund's
        NEGATIVE-amount credit (the PR-9 base-compose fallback: fp
        tables skip the fused hierarchical kernel, so a child deny
        refunds the parent here). Mirrors the host-directory
        ``_DeviceTable._debit_launch`` contract — returns the packed
        ``f32[2, B]`` (post-debit balance, clamped shortfall)."""
        store = self.store
        n = len(keys)
        with store.profiler.span("debit_batch", n), store._lock:
            b = _pad_size(n, floor=64)
            kpair = np.zeros((b, 2), np.uint32)
            kpair[:n] = fingerprints(list(keys))
            amts = np.zeros((b,), np.float32)
            amts[:n] = np.asarray(amounts, np.float32)
            valid = np.zeros((b,), bool)
            valid[:n] = True
            now = store.now_ticks_checked()
            self.fp, self.state, out = F.fp_debit_batch(
                self.fp, self.state, jnp.asarray(kpair),
                jnp.asarray(amts), jnp.asarray(valid), jnp.int32(now),
                self.cap_dev, self.rate_dev,
                probe_window=self.probe_window, rounds=self.rounds)
            if self.dirty_rows is not None:
                self.dirty_rows += n
            store.metrics.record_launch(b, n)
            return out

    # -- reads -------------------------------------------------------------
    def peek_blocking(self, key: str) -> float:
        b = 64
        kpair = np.zeros((b, 2), np.uint32)
        kpair[0] = fingerprints([key])[0]
        valid = np.zeros((b,), bool)
        valid[0] = True
        with self.store._lock:
            est = F.fp_peek_batch(
                self.fp, self.state, jnp.asarray(kpair), jnp.asarray(valid),
                jnp.int32(self.store.now_ticks_checked()), self.cap_dev,
                self.rate_dev, probe_window=self.probe_window)
        return float(np.asarray(est)[0])

    # -- maintenance -------------------------------------------------------
    def _occupancy(self) -> int:
        # Under the store lock: concurrent launches donate self.fp, and a
        # readback racing a donation dies with "Array has been deleted".
        with self.store._lock:
            return int(np.asarray((np.asarray(self.fp) != 0).any(-1).sum()))

    def _relieve_pressure(self) -> None:
        """Window pressure response: sweep expired slots; grow when the
        table is past the growth threshold OR the sweep freed (almost)
        nothing — with only live keys, one full probe window can fill at
        modest load factors, and without the freed-nothing clause the key
        hashing there would be denied forever while paying a full-table
        sweep per attempt. The denied requests are NOT retried here —
        deny-and-heal keeps the launch path deterministic; the caller's
        next attempt lands in the relieved table."""
        with self.store._lock:
            before = self.store.metrics.slots_evicted
            self._sweep()
            freed = self.store.metrics.slots_evicted - before
            if (freed < max(1, self.n_slots // 16)
                    or self._occupancy() >= self._GROW_AT * self.n_slots):
                self._grow()

    def _sweep(self, pinned=None) -> None:
        store = self.store
        with store.profiler.span("sweep_fp", self.n_slots), store._lock:
            now = store.now_ticks_checked()
            self.fp, self.state, n_freed = F.fp_sweep_expired(
                self.fp, self.state, jnp.int32(now), self.cap_dev,
                self.rate_dev)
            store.metrics.sweeps += 1
            store.metrics.slots_evicted += int(np.asarray(n_freed))

    # Growth hooks (the window subclass swaps these three):
    def _init_fresh(self, n: int):
        return F.init_fp_table(n), K.init_bucket_state(n)

    def _migrate_kernel(self):
        return F.fp_migrate_chunk

    def _grow(self) -> None:
        """Double the table with a device-side rehash: read old
        fingerprints back, then per chunk claim slots in the new table and
        scatter the old per-slot state across (the migrate kernel) — the
        host never computes a placement."""
        store = self.store
        with store._lock:
            self._rehash(np.asarray(self.fp),
                         [np.asarray(a) for a in self.state],
                         self.n_slots * 2)
            store.metrics.pregrows += 1

    def _rehash(self, old_fp: np.ndarray, olds: list, start_n: int,
                probe_window: int | None = None) -> None:
        """Re-place every live entry into a fresh table via the migrate
        kernel, doubling and retrying when placement gets stuck — the
        shared driver behind growth AND legacy-snapshot adoption (caller
        holds the store lock; ``olds`` are state columns in field order).
        Mutates nothing until placement succeeds, so a raise leaves the
        table exactly as it was. ``probe_window`` lets snapshot adoption
        place under the snapshot's geometry before committing it.

        An entry whose whole window fills with OTHER entries is
        unplaceable at a given size — a density accident, not a bug
        (observed at ~0.8 load). Doubling always converges (load halves
        per attempt); the attempt cap makes a pathological hash set fail
        loudly instead of allocating forever."""
        pw = self.probe_window if probe_window is None else probe_window
        entries = np.nonzero((old_fp != 0).any(-1))[0]
        migrate = self._migrate_kernel()
        b = self.store.max_batch
        new_n = start_n
        leftover = 0
        for _attempt in range(4):
            fp, state = self._init_fresh(new_n)
            pending = entries
            stuck = False
            # Entries a pass can't place (bounded insert rounds under
            # in-chunk window contention) retry in later passes; each
            # pass places ≥1 contender per contested cell, so a pass
            # with zero progress means some window is genuinely full.
            while len(pending):
                next_pending = []
                for pos in range(0, len(pending), b):
                    idx = pending[pos:pos + b]
                    m = len(idx)
                    kpair = np.zeros((b, 2), np.uint32)
                    kpair[:m] = old_fp[idx]
                    cols = []
                    for arr in olds:
                        col = np.zeros((b,), arr.dtype)
                        col[:m] = arr[idx]
                        cols.append(col)
                    valid = np.zeros((b,), bool)
                    valid[:m] = True
                    fp, state, placed = migrate(
                        fp, state, jnp.asarray(kpair),
                        *(jnp.asarray(c) for c in cols),
                        jnp.asarray(valid),
                        probe_window=pw,
                        rounds=self.rounds)
                    miss = ~np.asarray(placed)[:m]
                    if miss.any():
                        next_pending.append(idx[miss])
                if not next_pending:
                    break
                next_pending = np.concatenate(next_pending)
                if len(next_pending) >= len(pending):
                    stuck, leftover = True, len(next_pending)
                    break
                pending = next_pending
            if not stuck:
                self.fp, self.state, self.n_slots = fp, state, new_n
                self.probe_window = pw
                return
            new_n *= 2
        raise RuntimeError(
            f"fingerprint rehash cannot place {leftover} entries even "
            f"at {new_n // 2} slots")

    def rebase(self, offset: int) -> None:
        self.state = K.rebase_bucket_epoch(self.state, jnp.int32(offset))

    # -- checkpoint form ---------------------------------------------------
    def to_snap(self) -> dict:
        return {
            "fp": np.asarray(self.fp),
            "probe_window": self.probe_window,
            "placement": _PLACEMENT_VERSION,
            "tokens": np.asarray(self.state.tokens),
            "last_ts": np.asarray(self.state.last_ts),
            "exists": np.asarray(self.state.exists),
        }

    def load_snap(self, data: dict, shift: int) -> None:
        if "fp" not in data:
            raise ValueError(
                "checkpoint's bucket tables use the host key directory — "
                "restore into a DeviceBucketStore")
        # Adopt the snapshot's probe window along with its size: a key
        # placed at offset 12 of a 16-cell window is invisible to an
        # 8-cell scan — restoring into a narrower window would silently
        # orphan such entries (and later duplicate their fingerprints).
        pw = int(data.get("probe_window", self.probe_window))
        cols = [np.asarray(data["tokens"]),
                np.asarray(_shift_ts(data["last_ts"], shift)),
                np.asarray(data["exists"])]
        if data.get("placement") != _PLACEMENT_VERSION:
            # Pre-v2 snapshots placed entries with a WRAPPING h % n
            # window; installing them verbatim under today's non-wrapping
            # placement would silently orphan nearly every key. Re-place
            # everything through the migrate kernel instead — it commits
            # table AND probe_window only on success, so a failed restore
            # leaves this table fully intact.
            self._rehash(np.asarray(data["fp"]), cols, len(cols[0]),
                         probe_window=pw)
            return
        self.probe_window = pw
        self.n_slots = len(data["tokens"])
        self.fp = jnp.asarray(data["fp"])
        self.state = K.BucketState(
            tokens=jnp.asarray(cols[0]),
            last_ts=jnp.asarray(cols[1]),
            exists=jnp.asarray(cols[2]),
        )


class _FpWindowTable(_FpTable):
    """Sliding/fixed-window table with the device-resident directory —
    the window-family counterpart of :class:`_FpTable` (shares its flush,
    bulk, pressure, and lock machinery; swaps the kernel bindings, sweep
    rule, growth migrate, and checkpoint form)."""

    def __init__(self, store: "FingerprintBucketStore", limit: float,
                 window_ticks: int, n_slots: int, *,
                 fixed: bool = False) -> None:
        if n_slots < store.probe_window:
            raise ValueError(
                f"n_slots ({n_slots}) must be >= probe_window "
                f"({store.probe_window})")
        self.store = store
        self.limit = float(limit)
        self.window_ticks = int(window_ticks)
        self.fixed = fixed
        self.n_slots = n_slots
        self.fp = F.init_fp_table(n_slots)
        self.state = K.init_window_state(n_slots)
        self.limit_dev = jnp.float32(self.limit)
        self.window_dev = jnp.int32(self.window_ticks)
        self.probe_window = store.probe_window
        self.rounds = store.insert_rounds
        self.batcher: MicroBatcher[_AcquireReq, AcquireResult] = MicroBatcher(
            self._flush,
            max_batch=store.max_batch,
            max_delay_s=store.max_delay_s,
            max_inflight=store.max_inflight,
            flush_latency=store.metrics.flush_latency,
            queue_latency=store.metrics.queue_latency,
            flush_observer=store._flush_observer,
        )

    def _call_batch(self, kpair, counts, valid, now):
        self.fp, self.state, granted, remaining, resolved = (
            F.fp_window_acquire_batch(
                self.fp, self.state, jnp.asarray(kpair),
                jnp.asarray(counts), jnp.asarray(valid), jnp.int32(now),
                self.limit_dev, self.window_dev,
                probe_window=self.probe_window, rounds=self.rounds,
                interpolate=not self.fixed))
        return granted, remaining, resolved

    def _call_scan_fused(self, fused, nows):
        self.fp, self.state, out = F.fp_window_acquire_scan_fused(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            self.limit_dev, self.window_dev,
            probe_window=self.probe_window, rounds=self.rounds,
            interpolate=not self.fixed)
        return out

    def _call_scan_fused_bits(self, fused, nows):
        self.fp, self.state, bits = F.fp_window_acquire_scan_fused_bits(
            self.fp, self.state, jnp.asarray(fused), jnp.asarray(nows),
            self.limit_dev, self.window_dev,
            probe_window=self.probe_window, rounds=self.rounds,
            interpolate=not self.fixed)
        return bits

    def peek_blocking(self, key: str) -> float:
        raise NotImplementedError(
            "window tables expose no peek (matching _DeviceWindowTable)")

    def _sweep(self, pinned=None) -> None:
        store = self.store
        with store.profiler.span("sweep_fp_windows", self.n_slots), \
                store._lock:
            now = store.now_ticks_checked()
            self.fp, self.state, n_freed = F.fp_sweep_windows(
                self.fp, self.state, jnp.int32(now), self.window_dev)
            store.metrics.sweeps += 1
            store.metrics.slots_evicted += int(np.asarray(n_freed))

    def _init_fresh(self, n: int):
        return F.init_fp_table(n), K.init_window_state(n)

    def _migrate_kernel(self):
        return F.fp_migrate_window_chunk

    def rebase(self, offset_ticks: int) -> None:
        self.state = K.rebase_window_epoch(
            self.state, jnp.int32(offset_ticks // self.window_ticks))

    def to_snap(self) -> dict:
        return {
            "fp": np.asarray(self.fp),
            "probe_window": self.probe_window,
            "placement": _PLACEMENT_VERSION,
            "prev_count": np.asarray(self.state.prev_count),
            "curr_count": np.asarray(self.state.curr_count),
            "window_idx": np.asarray(self.state.window_idx),
            "exists": np.asarray(self.state.exists),
        }

    def load_snap(self, data: dict, shift: int) -> None:
        if "fp" not in data:
            raise ValueError(
                "checkpoint's window tables use the host key directory — "
                "restore into a DeviceBucketStore")
        pw = int(data.get("probe_window", self.probe_window))
        cols = [np.asarray(data["prev_count"]),
                np.asarray(data["curr_count"]),
                np.asarray(_shift_ts(data["window_idx"],
                                     shift // self.window_ticks)),
                np.asarray(data["exists"])]
        if data.get("placement") != _PLACEMENT_VERSION:
            # Pre-v2 wrapping placement: re-place via the migrate kernel
            # (see _FpTable.load_snap — commit-on-success).
            self._rehash(np.asarray(data["fp"]), cols, len(cols[0]),
                         probe_window=pw)
            return
        self.probe_window = pw
        self.n_slots = len(data["prev_count"])
        self.fp = jnp.asarray(data["fp"])
        self.state = K.WindowState(
            prev_count=jnp.asarray(cols[0]),
            curr_count=jnp.asarray(cols[1]),
            window_idx=jnp.asarray(cols[2]),
            exists=jnp.asarray(cols[3]),
        )


class FingerprintBucketStore(DeviceBucketStore):
    """``DeviceBucketStore`` with the bucket tier's key directory moved
    into device memory (module docstring). Drop-in: same ``BucketStore``
    surface, same limiter compatibility, checkpoints interchange only
    with other fingerprint stores (the snapshot carries fingerprints, not
    key strings — keys are not recoverable from a fingerprint table)."""

    def __init__(self, *, probe_window: int = 16, insert_rounds: int = 4,
                 **kwargs) -> None:
        super().__init__(**kwargs)
        self.probe_window = probe_window
        self.insert_rounds = insert_rounds

    _TABLE_CLS = _FpTable
    _WTABLE_CLS = _FpWindowTable
