"""The bucket store — where rate-limit state lives and decisions execute.

``BucketStore`` is the framework's storage seam, playing the role the
``IDatabase`` + ``ConnectionMultiplexerFactory`` pair played in the
reference (``…Options.cs:75`` — the injection point SURVEY.md §4 calls out
as "the seam designed for exactly this — preserve an equivalent seam"):

- :class:`DeviceBucketStore` — the TPU store. Per-key bucket state lives in
  HBM as SoA arrays; ``acquire`` calls are micro-batched into one kernel
  launch (≙ one Lua ``EVALSHA``, but for thousands of keys at once); the
  store's clock stamps every launch (store-as-time-authority, invariant 1).
- :class:`InProcessBucketStore` — a pure-Python store with identical
  semantics: the test fake (≙ a fake ``ConnectionMultiplexer``) and the
  single-node CPU baseline for BASELINE config 1.

Organization of the device store: one *table* per bucket configuration
``(capacity, fill_rate)`` — matching the reference, where one limiter (or
one partitioned limiter's whole key space) shares a single config
(``RedisTokenBucketRateLimiterOptions``), so tables are homogeneous and the
kernels take config as two scalar operands. Tables grow by doubling and
reclaim slots with TTL sweeps (invariant 5). Decaying global counters (the
approximate algorithm's shared tier) live in one store-wide table with a
*per-row* decay-rate operand, since each approximate limiter may have its
own rate.
"""

from __future__ import annotations

import abc
import asyncio
import threading
import time
from typing import Callable, Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributedratelimiting.redis_tpu.ops import bucket_math as bm
from distributedratelimiting.redis_tpu.ops import kernels as K
from distributedratelimiting.redis_tpu.utils import log
from distributedratelimiting.redis_tpu.runtime.batcher import MicroBatcher
from distributedratelimiting.redis_tpu.runtime.clock import Clock, MonotonicClock
from distributedratelimiting.redis_tpu.runtime.directory import make_directory
from distributedratelimiting.redis_tpu.utils.metrics import StoreMetrics
from distributedratelimiting.redis_tpu.utils.tracing import (
    Profiler,
    ProfilingSession,
    annotate,
)

__all__ = [
    "AcquireResult",
    "BulkAcquireResult",
    "SyncResult",
    "BucketStore",
    "DeviceBucketStore",
    "InProcessBucketStore",
    "check_hierarchical_args",
]


def check_hierarchical_args(count: int, tenant_capacity: float,
                            tenant_fill_rate_per_sec: float,
                            capacity: float,
                            fill_rate_per_sec: float) -> None:
    """Shared validation for every hierarchical lane (in-process,
    device, remote client, server dispatch — one rule, zero drift):
    costs must be non-negative (a negative 'cost' would MINT tokens
    through the refund algebra), and the tenant and key configs must
    differ — identical configs would alias parent and child into one
    table (the fused kernel donates each state buffer once), and a
    tenant budget equal to the per-key config is a flat limiter
    spelled twice, not a hierarchy."""
    if count < 0:
        raise ValueError("hierarchical acquire cost must be >= 0")
    if (float(tenant_capacity), float(tenant_fill_rate_per_sec)) == \
            (float(capacity), float(fill_rate_per_sec)):
        raise ValueError(
            "hierarchical acquire requires distinct tenant and key "
            "configs (identical (capacity, fill_rate) would alias the "
            "two levels into one table)")

# Host tick value at which the store rebases its epoch (≪ int32 max), and
# how much history the new epoch keeps. Margin 2^29 (~6 days): timestamps
# within the last ~6 days survive the shift exactly; older ones clamp to the
# new epoch, which can only under-refill (safe) and only matters for buckets
# whose time-to-full exceeds ~6 days.
_REBASE_THRESHOLD_TICKS = 2**30
_REBASE_MARGIN_TICKS = 2**29


def _shift_ts(ts, shift: int):
    """Re-align stored tick timestamps to a new clock epoch: widen to
    int64, shift, and saturate back into int32 range (shared by every
    snapshot-restore path — single-chip and sharded)."""
    shifted = np.asarray(ts).astype(np.int64) + shift
    return np.clip(shifted, -(2**31) + 1, 2**31 - 1).astype(np.int32)


class AcquireResult(NamedTuple):
    granted: bool
    remaining: float  # post-decision token estimate (≙ Lua reply new_v)


class BulkAcquireResult:
    """Vectorized decision results: numpy arrays, not per-request objects.

    The bulk serving path exists because building one Python object (and
    resolving one future) per decision caps a process near ~50K decisions/s
    regardless of device speed; callers that hold many keys' requests get
    the verdicts as two arrays and index only what they need."""

    __slots__ = ("granted", "remaining")

    def __init__(self, granted: np.ndarray,
                 remaining: np.ndarray | None) -> None:
        self.granted = granted        # bool[n]
        # f32[n]; None when the caller opted out (``with_remaining=False``,
        # the verdict-only fast path — fetches 1 bit/decision).
        self.remaining = remaining

    def __len__(self) -> int:
        return len(self.granted)

    def __getitem__(self, i: int) -> AcquireResult:
        r = 0.0 if self.remaining is None else float(self.remaining[i])
        return AcquireResult(bool(self.granted[i]), r)

    def __iter__(self):
        for i in range(len(self.granted)):
            yield self[i]

    @property
    def granted_count(self) -> int:
        return int(np.count_nonzero(self.granted))


class SyncResult(NamedTuple):
    global_score: float
    period_ewma_ticks: float


class _AcquireReq(NamedTuple):
    key: str
    count: int


class BucketStore(abc.ABC):
    """Abstract store: token buckets + decaying counters + sliding windows.

    All rate arguments are per-second; conversion to per-tick happens at the
    store boundary so callers never see ticks except in ``SyncResult``.
    """

    clock: Clock

    @abc.abstractmethod
    async def connect(self) -> None:
        """Idempotent lazy init (≙ ``ConnectAsync``,
        ``RedisTokenBucketRateLimiter.cs:111-151``)."""

    # -- exact token bucket ------------------------------------------------
    @abc.abstractmethod
    async def acquire(self, key: str, count: int, capacity: float,
                      fill_rate_per_sec: float) -> AcquireResult: ...

    @abc.abstractmethod
    def acquire_blocking(self, key: str, count: int, capacity: float,
                         fill_rate_per_sec: float) -> AcquireResult:
        """Synchronous single-request path (the reference's sync ``Acquire``
        silently always failed — a surprise SURVEY.md §2 tells us not to
        replicate; here it is a real, blocking decision)."""

    @abc.abstractmethod
    def peek_blocking(self, key: str, capacity: float,
                      fill_rate_per_sec: float) -> float:
        """Read-only availability estimate (``GetAvailablePermits``)."""

    def acquire_submitter(self, capacity: float, fill_rate_per_sec: float):
        """Per-request hot-path factory: returns an async ``(key, count) →
        AcquireResult`` bound to one bucket config, with per-call routing
        (config→table lookup, connect check) hoisted out of the loop.
        Limiters cache one per config — at ~20µs/decision budgets the
        hoisted work is a measurable share. Default: a thin binding over
        :meth:`acquire`; :class:`DeviceBucketStore` overrides with a
        direct micro-batcher binding."""
        async def submit(key: str, count: int) -> AcquireResult:
            return await self.acquire(key, count, capacity,
                                      fill_rate_per_sec)

        return submit

    # -- bulk token bucket (one call, many keys) ---------------------------
    async def acquire_many(self, keys: Sequence[str], counts: Sequence[int],
                           capacity: float, fill_rate_per_sec: float, *,
                           with_remaining: bool = True) -> "BulkAcquireResult":
        """Vectorized acquire: decide ``len(keys)`` requests in one call —
        one await resolves them all (no per-request future). Duplicate keys
        serialize in request order; on batched device stores the in-batch
        serialization is *conservative* (an earlier same-key request's
        demand reserves ahead of later ones even if it is denied — the same
        property as the micro-batched serving path; over-admission is
        impossible, and the decisions are exact whenever in-call duplicates
        are all granted or keys are distinct). ``with_remaining=False``
        lets a verdict-only caller skip the per-request remaining estimates
        (the device store then fetches 1 bit per decision). Default
        implementation: a pipelined gather over the per-key path;
        :class:`DeviceBucketStore` overrides with scanned whole-array
        kernel launches."""
        results = await asyncio.gather(
            *(self.acquire(k, int(c), capacity, fill_rate_per_sec)
              for k, c in zip(keys, counts)))
        return BulkAcquireResult(
            np.fromiter((r.granted for r in results), bool, len(results)),
            np.fromiter((r.remaining for r in results), np.float32,
                        len(results)) if with_remaining else None)

    def acquire_many_blocking(self, keys: Sequence[str],
                              counts: Sequence[int], capacity: float,
                              fill_rate_per_sec: float, *,
                              with_remaining: bool = True) -> "BulkAcquireResult":
        results = [self.acquire_blocking(k, int(c), capacity,
                                         fill_rate_per_sec)
                   for k, c in zip(keys, counts)]
        return BulkAcquireResult(
            np.fromiter((r.granted for r in results), bool, len(results)),
            np.fromiter((r.remaining for r in results), np.float32,
                        len(results)) if with_remaining else None)

    # -- bulk windows (one call, many keys) --------------------------------
    async def window_acquire_many(self, keys: Sequence[str],
                                  counts: Sequence[int], limit: float,
                                  window_sec: float, *, fixed: bool = False,
                                  with_remaining: bool = True
                                  ) -> "BulkAcquireResult":
        """Vectorized window acquire (sliding by default, ``fixed=True``
        for fixed windows) — the window analogue of :meth:`acquire_many`,
        with the same in-call duplicate conservatism and probe semantics.
        Default: pipelined gather over the per-key path; device stores
        override with scanned whole-array launches."""
        op = (self.fixed_window_acquire if fixed else self.window_acquire)
        results = await asyncio.gather(
            *(op(k, int(c), limit, window_sec)
              for k, c in zip(keys, counts)))
        return BulkAcquireResult(
            np.fromiter((r.granted for r in results), bool, len(results)),
            np.fromiter((r.remaining for r in results), np.float32,
                        len(results)) if with_remaining else None)

    def window_acquire_many_blocking(self, keys: Sequence[str],
                                     counts: Sequence[int], limit: float,
                                     window_sec: float, *,
                                     fixed: bool = False,
                                     with_remaining: bool = True
                                     ) -> "BulkAcquireResult":
        op = (self.fixed_window_acquire_blocking if fixed
              else self.window_acquire_blocking)
        results = [op(k, int(c), limit, window_sec)
                   for k, c in zip(keys, counts)]
        return BulkAcquireResult(
            np.fromiter((r.granted for r in results), bool, len(results)),
            np.fromiter((r.remaining for r in results), np.float32,
                        len(results)) if with_remaining else None)

    # -- hierarchical tenant → key admission (runtime/admission.py) --------
    async def acquire_hierarchical(self, tenant: str, key: str, count: int,
                                   tenant_capacity: float,
                                   tenant_fill_rate_per_sec: float,
                                   capacity: float,
                                   fill_rate_per_sec: float, *,
                                   priority: int = 0) -> AcquireResult:
        """Two-level weighted-cost admission: grant iff BOTH the child
        key's ``(capacity, fill_rate)`` bucket and the parent tenant's
        ``(tenant_capacity, tenant_fill_rate)`` bucket admit ``count``
        tokens, with both-or-neither state change (parent refund on
        child deny — DESIGN.md §15). ``remaining`` is the binding
        constraint's post-decision view: ``min(child, parent)``.
        ``priority`` (admission.PRIORITY_*) never changes a
        healthy-path decision; wire stores stamp it on the frame so
        envelope serving (drain windows, parked handoffs) can honor
        the shed order.

        Default: sequential parent-then-child compose with a
        saturating refund of the parent on child deny (via
        ``debit_many`` with a negative amount, where the store has
        one; stores without a reconciliation lane skip the refund —
        under-admission only, never over). Exact single-step
        implementations: :class:`InProcessBucketStore` (serial core)
        and :class:`DeviceBucketStore` (the fused
        ``acquire_hierarchical_packed`` kernel);
        ``RemoteBucketStore`` ships the whole decision as one
        ``OP_ACQUIRE_H`` frame."""
        check_hierarchical_args(count, tenant_capacity,
                                tenant_fill_rate_per_sec, capacity,
                                fill_rate_per_sec)
        parent = await self.acquire(tenant, count, tenant_capacity,
                                    tenant_fill_rate_per_sec)
        if not parent.granted:
            return AcquireResult(False, parent.remaining)
        child = await self.acquire(key, count, capacity,
                                   fill_rate_per_sec)
        if child.granted:
            return AcquireResult(True, min(child.remaining,
                                           parent.remaining))
        if count > 0 and type(self).debit_many is not BucketStore.debit_many:
            # Refund the parent debit through the saturating debit lane
            # (a negative amount credits back; the next refill's
            # capacity clamp bounds any transient overshoot, so the
            # refund can only under-credit — the safe direction).
            await self.debit_many([tenant], [-float(count)],
                                  tenant_capacity,
                                  tenant_fill_rate_per_sec)
        return AcquireResult(False, child.remaining)

    def acquire_hierarchical_blocking(self, tenant: str, key: str,
                                      count: int,
                                      tenant_capacity: float,
                                      tenant_fill_rate_per_sec: float,
                                      capacity: float,
                                      fill_rate_per_sec: float, *,
                                      priority: int = 0) -> AcquireResult:
        """Blocking compose (overridden with exact single-step
        implementations by the serial/device/remote stores). The base
        compose has no blocking refund lane: a child deny leaves the
        parent debited — under-admission only, documented."""
        check_hierarchical_args(count, tenant_capacity,
                                tenant_fill_rate_per_sec, capacity,
                                fill_rate_per_sec)
        parent = self.acquire_blocking(tenant, count, tenant_capacity,
                                       tenant_fill_rate_per_sec)
        if not parent.granted:
            return AcquireResult(False, parent.remaining)
        child = self.acquire_blocking(key, count, capacity,
                                      fill_rate_per_sec)
        if child.granted:
            return AcquireResult(True, min(child.remaining,
                                           parent.remaining))
        return AcquireResult(False, child.remaining)

    async def acquire_hierarchical_many(self, tenants: Sequence[str],
                                        keys: Sequence[str],
                                        counts: Sequence[int],
                                        tenant_capacity: float,
                                        tenant_fill_rate_per_sec: float,
                                        capacity: float,
                                        fill_rate_per_sec: float, *,
                                        with_remaining: bool = True,
                                        priority: int = 0
                                        ) -> "BulkAcquireResult":
        """Vectorized hierarchical admission — row ``i`` decides
        ``counts[i]`` tokens for ``(tenants[i], keys[i])``. Same-key /
        same-tenant rows serialize in request order; on batched device
        stores the serialization is conservative on BOTH axes (the
        fused kernel's documented posture). Default: sequential loop
        over :meth:`acquire_hierarchical`."""
        n = len(keys)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        for i in range(n):
            r = await self.acquire_hierarchical(
                tenants[i], keys[i], int(counts[i]), tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                priority=priority)
            granted[i] = r.granted
            if remaining is not None:
                remaining[i] = r.remaining
        return BulkAcquireResult(granted, remaining)

    def acquire_hierarchical_many_blocking(self, tenants: Sequence[str],
                                           keys: Sequence[str],
                                           counts: Sequence[int],
                                           tenant_capacity: float,
                                           tenant_fill_rate_per_sec: float,
                                           capacity: float,
                                           fill_rate_per_sec: float, *,
                                           with_remaining: bool = True,
                                           priority: int = 0
                                           ) -> "BulkAcquireResult":
        n = len(keys)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        for i in range(n):
            r = self.acquire_hierarchical_blocking(
                tenants[i], keys[i], int(counts[i]), tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                priority=priority)
            granted[i] = r.granted
            if remaining is not None:
                remaining[i] = r.remaining
        return BulkAcquireResult(granted, remaining)

    # -- estimate-reserve-settle (runtime/reservations.py) -----------------
    def reservation_ledger(self, **kwargs):
        """Get-or-create this store's :class:`~.reservations.
        ReservationLedger` — ONE ledger per store, shared by every
        consumer (the server's OP_RESERVE/OP_SETTLE dispatch, the
        migration import lane, in-process cluster nodes), so a
        reservation imported by a MIGRATE_PUSH is visible to the next
        settle. ``kwargs`` configure the ledger on FIRST creation only
        (the server wires flight recorder / velocity / liveconfig in
        before serving); later callers get the existing instance."""
        led = getattr(self, "_reservations", None)
        if led is None:
            from distributedratelimiting.redis_tpu.runtime.reservations import (
                ReservationLedger,
            )

            led = self._reservations = ReservationLedger(self, **kwargs)
        return led

    # -- global quota federation (runtime/federation.py) -------------------
    def federation_ledger(self, **kwargs):
        """Get-or-create this store's :class:`~.federation.
        FederationLedger` — ONE ledger per store (the
        ``reservation_ledger`` pattern), shared by the server's
        OP_FED_* dispatch and the checkpoint attachment lane
        (runtime/checkpoint.py snapshots/restores its lease state
        beside the bucket tables, so a home crash/restart resumes
        every lease). ``kwargs`` configure the ledger on FIRST
        creation only."""
        led = getattr(self, "_federation", None)
        if led is None:
            from distributedratelimiting.redis_tpu.runtime.federation import (
                FederationLedger,
            )

            led = self._federation = FederationLedger(self, **kwargs)
        return led

    async def reserve(self, rid: str, tenant: str, key: str,
                      estimate: "float | None",
                      tenant_capacity: float,
                      tenant_fill_rate_per_sec: float,
                      capacity: float, fill_rate_per_sec: float, *,
                      priority: int = 0,
                      ttl_s: "float | None" = None,
                      attempt: int = 0,
                      deadline_s: "float | None" = None):
        """Admit an ESTIMATED cost against the tenant → key budgets and
        hold a TTL'd reservation (:mod:`~.reservations` — the streaming
        lane for costs unknown until generation ends). Default: the
        store-attached ledger; ``RemoteBucketStore`` overrides with one
        ``OP_RESERVE`` frame so the ledger lives server-side.
        ``attempt``/``deadline_s`` feed the goodput plane — retry
        fingerprinting and settle-vs-deadline accounting
        (docs/DESIGN.md §24)."""
        return await self.reservation_ledger().reserve(
            rid, tenant, key, estimate, tenant_capacity,
            tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
            priority=priority, ttl_s=ttl_s, attempt=attempt,
            deadline_s=deadline_s)

    async def settle(self, rid: str, tenant: str, actual: float):
        """Reconcile a reservation's actual cost: refund over-estimates
        through the saturating negative-debit lane, carry
        under-estimates as per-tenant debt (idempotent by rid — see
        :meth:`~.reservations.ReservationLedger.settle`)."""
        return await self.reservation_ledger().settle(rid, tenant,
                                                      actual)

    # -- decaying global counter (approximate algorithm's shared tier) -----
    @abc.abstractmethod
    async def sync_counter(self, key: str, local_count: float,
                           decay_rate_per_sec: float) -> SyncResult: ...

    @abc.abstractmethod
    def sync_counter_blocking(self, key: str, local_count: float,
                              decay_rate_per_sec: float) -> SyncResult: ...

    async def sync_counters_many(self, keys: Sequence[str],
                                 local_counts: Sequence[float],
                                 decay_rate_per_sec: float
                                 ) -> tuple[np.ndarray, np.ndarray]:
        """Bulk decaying-counter sync — the entry point for EXTERNAL
        replica fleets (a host process running many approximate-limiter
        replicas, or an edge tier reconciling a whole key table) to drain
        their accumulated local counts in one call instead of one
        :meth:`sync_counter` round trip per key. Returns ``(global_scores
        f64[n], period_ewmas f64[n])`` row-for-row with ``keys``.
        Default: a sequential loop (same-key rows keep request order);
        :class:`DeviceBucketStore` overrides with ONE ``sync_batch``
        launch for the whole fleet."""
        scores = np.empty(len(keys), np.float64)
        periods = np.empty(len(keys), np.float64)
        for i, (k, c) in enumerate(zip(keys, local_counts)):
            res = await self.sync_counter(k, float(c), decay_rate_per_sec)
            scores[i] = res.global_score
            periods[i] = res.period_ewma_ticks
        return scores, periods

    async def debit_many(self, keys: Sequence[str],
                         amounts: Sequence[float], capacity: float,
                         fill_rate_per_sec: float
                         ) -> tuple[np.ndarray, np.ndarray]:
        """Saturating bulk debit — the reconciliation half of the native
        front-end's tier-0 admission cache: drain permits the edge
        already granted locally out of the authoritative bucket table
        (refill, then subtract clamped at zero). Returns ``(remaining
        f64[n], shortfall f64[n])``: the post-debit balance per key and
        the part of each drained amount that found no tokens (the
        observed over-admission). Callers pre-aggregate per key. Not
        every store hosts tier-0 replicas; the front-end feature-detects
        this method and disables tier-0 when it is absent."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support tier-0 replica "
            "reconciliation (debit_many)")

    # -- sliding window ----------------------------------------------------
    @abc.abstractmethod
    async def window_acquire(self, key: str, count: int, limit: float,
                             window_sec: float) -> AcquireResult: ...

    @abc.abstractmethod
    def window_acquire_blocking(self, key: str, count: int, limit: float,
                                window_sec: float) -> AcquireResult: ...

    # -- fixed window (current-window count only, no interpolation) --------
    @abc.abstractmethod
    async def fixed_window_acquire(self, key: str, count: int, limit: float,
                                   window_sec: float) -> AcquireResult: ...

    @abc.abstractmethod
    def fixed_window_acquire_blocking(self, key: str, count: int,
                                      limit: float,
                                      window_sec: float) -> AcquireResult: ...

    # -- concurrency semaphore (held permits, returned on lease dispose) ---
    @abc.abstractmethod
    async def concurrency_acquire(self, key: str, count: int,
                                  limit: int) -> AcquireResult:
        """Atomically add ``count`` held permits iff ``active + count <=
        limit``. ``remaining`` in the result is the post-op active count."""

    @abc.abstractmethod
    def concurrency_acquire_blocking(self, key: str, count: int,
                                     limit: int) -> AcquireResult: ...

    @abc.abstractmethod
    async def concurrency_release(self, key: str, count: int) -> None:
        """Return ``count`` held permits (clamped at zero held)."""

    @abc.abstractmethod
    def concurrency_release_blocking(self, key: str, count: int) -> None: ...

    async def concurrency_acquire_many(self, keys: Sequence[str],
                                       deltas: Sequence[int],
                                       limit: "int | Sequence[int]"
                                       ) -> "BulkAcquireResult":
        """Vectorized semaphore ops: decide ``len(keys)`` signed deltas in
        one call — +n acquires (all-or-nothing against the row's limit),
        -n releases (always succeed, clamped at zero held), 0 probes.
        ``limit`` is a scalar or one per row (the native front-end sends
        a whole micro-batch as ONE call with per-row limits so same-key
        acquires and releases keep arrival order). Same-key rows
        serialize in request order; duplicate-acquire admission may be
        *conservative* on batched stores — an earlier same-key acquire's
        demand reserves ahead of later rows even if it is denied — and
        exact on serial stores, the same latitude :meth:`acquire_many`
        documents for buckets. Result rows: ``granted`` (releases always
        True), ``remaining`` = post-op active count from the row's own
        serialized view (0.0 for releases, matching the scalar wire
        reply). Default: in-order loop over the per-key path;
        :class:`DeviceBucketStore` overrides with packed kernel
        dispatches."""
        n = len(keys)
        limits = self._sema_limits(limit, n)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32)
        for i, (k, d) in enumerate(zip(keys, deltas)):
            d = int(d)
            if d >= 0:
                r = await self.concurrency_acquire(k, d, int(limits[i]))
                granted[i] = r.granted
                remaining[i] = r.remaining
            else:
                await self.concurrency_release(k, -d)
                granted[i] = True
                remaining[i] = 0.0
        return BulkAcquireResult(granted, remaining)

    @staticmethod
    def _sema_limits(limit, n: int) -> np.ndarray:
        """Broadcast a scalar-or-per-row ``limit`` to ``i64[n]``."""
        arr = np.asarray(limit, np.int64)
        if arr.ndim == 0:
            return np.full(n, int(arr), np.int64)
        if arr.shape != (n,):
            raise ValueError(
                f"limit must be a scalar or one per row: got shape "
                f"{arr.shape} for {n} rows")
        return arr

    # -- lifecycle / ops ---------------------------------------------------
    @abc.abstractmethod
    async def aclose(self) -> None: ...

    @abc.abstractmethod
    def snapshot(self) -> dict:
        """Host-side checkpoint of all live state (SURVEY.md §5.4: planned
        restarts snapshot ``(keys, tokens, ts)``; crash recovery simply
        accepts init-on-miss)."""

    @abc.abstractmethod
    def restore(self, snap: dict) -> None: ...

    def export_entries(self, keep) -> dict:
        """Normalized per-key state for the keys ``keep`` selects — the
        live-migration handoff unit (:mod:`~.placement`). Default:
        filter this store's :meth:`snapshot` through the schema-aware
        extractor (host-dict and device slot-array schemas both
        understood). The matching import runs through
        :func:`placement.import_entries`, whose generic lane replays
        buckets via the saturating ``debit_many`` kernel."""
        from distributedratelimiting.redis_tpu.runtime import placement

        return placement.extract_entries(self.snapshot(), keep)


def start_periodic_sweeper(sweep_all: Callable[[], None],
                           period_s: float) -> "asyncio.Task":
    """Shared active-expiry loop (DeviceBucketStore + MeshBucketStore):
    runs ``sweep_all`` off-loop every ``period_s``; a transient device
    error must not silently end active expiry for the store's lifetime —
    log and retry next period (degraded-mode posture, invariant 9)."""

    async def loop() -> None:
        while True:
            await asyncio.sleep(period_s)
            try:
                # Device passes block; keep the event loop responsive.
                await asyncio.to_thread(sweep_all)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                log.error_evaluating_kernel(exc)

    return asyncio.get_running_loop().create_task(loop())


def _rate_per_tick(rate_per_sec: float) -> float:
    return rate_per_sec / bm.TICKS_PER_SECOND


def _grant_zero_probes(granted: np.ndarray, counts_np: np.ndarray) -> None:
    """The zero-permit-probe contract in one place (shared by the
    host-directory mixin and the fingerprint store): probes always grant
    — the kernel's conservative in-batch prefix could deny one riding
    beside denied same-key demand."""
    if (counts_np == 0).any():
        granted[counts_np == 0] = True


def _pad_size(n: int, floor: int = 64) -> int:
    """Pad batches to a power of two ≥ ``floor`` so the jit cache stays
    small (one compilation per size bucket, not per batch length)."""
    size = floor
    while size < n:
        size *= 2
    return size


async def _readback(store, fetch: Callable[[], object]):
    """Run ``fetch`` (device→host transfer and unpack) on the loop's
    executor under the ``drl/store.readback`` span; records ``store``'s
    ``store_readback`` stage from the submit to the host arrays in hand
    on the executor thread (the loop's wake-up after it is not counted).
    Shared by the host-directory tables and the fingerprint store."""
    def job():
        with annotate("drl/store.readback"):
            out = fetch()
        return out, time.perf_counter()

    t0 = time.perf_counter()
    out, t1 = await asyncio.get_running_loop().run_in_executor(None, job)
    store.metrics.store_readback.record(t1 - t0)
    return out


def _duplicate_prefix_host(slots: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Exact per-request prefix of earlier same-slot demand, computed on the
    host in int64 (vectorized stable-sort + segmented cumsum, ~30µs at
    B=4096). Shipping it with the batch lets the device kernel skip its
    in-kernel sort — the decision is then pure gather/refill/compare/scatter."""
    order = np.argsort(slots, kind="stable")
    s_sorted = slots[order]
    c_sorted = counts[order].astype(np.int64)
    csum = np.cumsum(c_sorted)
    seg_start = np.r_[True, s_sorted[1:] != s_sorted[:-1]]
    base = np.maximum.accumulate(np.where(seg_start, csum - c_sorted, 0))
    prefix = np.empty_like(csum)
    prefix[order] = csum - c_sorted - base
    return prefix


def _build_packed(reqs: Sequence[_AcquireReq], slots: Sequence[int], b: int,
                  now: int) -> np.ndarray:
    """ONE padded i32[4, b] operand per launch — row 0 slots (-1 = padding),
    row 1 counts, row 2 the batch timestamp, row 3 the host-computed
    same-slot demand prefix. The flush hot path ships exactly one
    host→device array and reads back exactly one result array (what a
    transfer costs on the chip is not measured)."""
    packed = np.full((4, b), -1, np.int32)
    packed[1] = 0
    packed[3] = 0
    n = len(reqs)
    packed[0, :n] = slots
    packed[1, :n] = [r.count for r in reqs]
    packed[2] = now
    if n != len(set(slots)):
        packed[3, :n] = np.minimum(
            _duplicate_prefix_host(packed[0, :n], packed[1, :n]), 2**31 - 1
        )
    return packed


def _resolve_with_reclaim(directory, keys: list[str], sweep, grow, *,
                          min_free: int = 0) -> np.ndarray:
    """Batch key→slot resolution with the shared reclaim discipline: on
    free-list exhaustion mid-batch, sweep expired slots (pinning the ones
    already resolved for this batch), grow if still dry, re-resolve —
    already-allocated keys are idempotent lookups, and each dry iteration
    doubles capacity, so the loop terminates.

    ``min_free`` adds sweep-first *hysteresis*: when a sweep reclaims only
    a trickle (≤ ``min_free`` slots), the table grows anyway — otherwise a
    near-full table of live keys re-runs a full sweep on nearly every
    allocation (each freeing a slot or two), a throughput cliff worse than
    one doubling."""
    slots = directory.resolve_batch(keys)
    while (slots < 0).any():
        pinned = {int(s) for s in slots[slots >= 0]}
        sweep(pinned)
        if directory.free_count <= min_free:
            grow()
        slots = directory.resolve_batch(keys)
    return slots


class _PackedLaunchMixin:
    """Shared flush machinery for tables whose ``_launch`` returns the
    packed ``f32[2, B]`` result (row 0 grants, row 1 remaining): readback
    convention plus cross-submit same-key coalescing. Duplicate keys in
    one flush collapse to one launch row per ``(key, count)`` group via
    the table's ``_launch_grouped`` (the Zipf hot-key win — a hot key no
    longer eats the batch), verdicts fanned back out in arrival order.
    Decision semantics are bit-identical to the per-row conservative
    serialization (``bucket_math.duplicate_prefix``); keys whose in-flush
    counts are mixed fall back to per-row entries with exact cumulative
    prefixes."""

    #: Dirty-slot accounting for incremental checkpoints
    #: (runtime/checkpoint.py v4; docs/OPERATIONS.md §10): ``None`` —
    #: the default, zero hot-path cost — until the owning store's
    #: ``enable_dirty_tracking()`` arms it with a set. Every slot a
    #: launch resolves lands here (slightly over-inclusive: a denied
    #: request still refreshes its slot's refill timestamp, so resolved
    #: ≈ written), giving OP_STATS the dirty/total ratio that predicts
    #: the next delta's size. The delta itself is computed by structural
    #: diff, not this set — forgetting a site here can never corrupt a
    #: checkpoint, only misreport the gauge.
    dirty_slots: "set[int] | None" = None

    #: Operand widths below ``max_batch`` that a launch carrying fewer rows
    #: is padded to instead: the smallest that holds its rows. A decision
    #: launch's table scatters pay for every row, padding included
    #: (PERF.md §5), so a one-row launch in a 16-row operand costs a
    #: fraction of one padded to 4,096. Each width is one more compiled
    #: variant of every served kernel, warmed with the table. Empty:
    #: every launch is ``max_batch`` wide.
    LAUNCH_WIDTHS: tuple[int, ...] = ()

    def _launch_width(self, rows: int) -> int:
        b = self.store.max_batch
        return next((w for w in self.LAUNCH_WIDTHS if rows <= w < b), b)

    def _launch_widths(self) -> list[int]:
        """Every width a launch of this table can take (what to warm)."""
        return sorted({self._launch_width(w) for w in self.LAUNCH_WIDTHS}
                      | {self.store.max_batch})

    async def _flush(self, reqs: Sequence[_AcquireReq]) -> list[AcquireResult]:
        groups = (self._coalesce(reqs)
                  if self.store.coalesce_duplicates else None)
        # Block for device results on an executor thread so the event loop
        # keeps accumulating the next flush; readbacks of distinct flushes
        # overlap (see MicroBatcher). One packed array = one transfer.
        t0 = time.perf_counter()
        out = (self._launch(reqs) if groups is None
               else self._dispatch_grouped(groups))
        self.store.metrics.store_host.record(time.perf_counter() - t0)
        out_np = await _readback(self.store, lambda: np.asarray(out))
        if groups is None:
            return [
                AcquireResult(bool(out_np[0, i] > 0.5), float(out_np[1, i]))
                for i in range(len(reqs))
            ]
        results: list[AcquireResult | None] = [None] * len(reqs)
        for g, (_, count, _, members, _) in enumerate(groups):
            n_granted = int(out_np[0, g])
            # Reconstruct each member's exact per-row remaining view from
            # the group result: avail = post-consumption remaining +
            # consumed (clamping matches the per-row kernel's, since a
            # negative avail yields 0 either way).
            avail = float(out_np[1, g]) + n_granted * count
            for j, idx in enumerate(members):
                granted = j < n_granted
                results[idx] = AcquireResult(
                    granted,
                    max(avail - j * count - (count if granted else 0), 0.0))
        return results  # type: ignore[return-value]

    @staticmethod
    def _coalesce(reqs: Sequence[_AcquireReq]):
        """Group requests for the grouped kernels; ``None`` when there are
        no duplicates (the classic single-row-per-request path is used)."""
        by_key: dict[str, list[int]] = {}
        for i, r in enumerate(reqs):
            by_key.setdefault(r.key, []).append(i)
        if len(by_key) == len(reqs):
            return None
        # (key, count, n, member_indices, prefix)
        groups: list[tuple[str, int, int, list[int], int]] = []
        for key, members in by_key.items():
            counts = {reqs[i].count for i in members}
            if len(counts) == 1:
                groups.append((key, counts.pop(), len(members), members, 0))
            else:
                # Mixed counts for one key: per-request rows with exact
                # cumulative prefixes (identical to the per-row kernel).
                pref = 0
                for i in members:
                    # Saturate like _build_packed does — a huge cumulative
                    # prefix must under-admit, not overflow the i32 operand.
                    groups.append((key, reqs[i].count, 1, [i],
                                   min(pref, 2**31 - 1)))
                    pref += reqs[i].count
        return groups

    def _dispatch_grouped(self, groups):
        """Pack groups into the shared i32[5, B] operand and hand it to
        the table's grouped kernel (``_launch_grouped``)."""
        with self.store.profiler.span("acquire_batch_grouped",
                                      len(groups)), self.store._lock:
            slots = self.resolve_slots([g[0] for g in groups])
            b = self._launch_width(len(groups))
            with annotate("drl/store.dispatch"):
                now = self.store.now_ticks_checked()
                packed = np.full((5, b), -1, np.int32)
                packed[1] = 0
                packed[3] = 0
                packed[4] = 0
                n = len(groups)
                packed[0, :n] = slots
                packed[1, :n] = [g[1] for g in groups]
                packed[2] = now
                packed[3, :n] = [g[4] for g in groups]
                packed[4, :n] = [g[2] for g in groups]
                out = self._launch_grouped(packed)
            n_reqs = sum(g[2] for g in groups)
            self.store.metrics.record_launch(b, n, packed)
            self.store.metrics.rows_coalesced += n_reqs - n
            return out

    def _warm_grouped(self) -> None:
        """Compile the grouped kernel at table construction (all-padding
        operand, state values untouched). Lazily compiling it on the first
        duplicate-containing flush would land a ~1s TPU compile inside the
        store lock at an unpredictable point mid-serving."""
        packed = np.full((5, self.store.max_batch), -1, np.int32)
        packed[1:] = 0
        jax.block_until_ready(self._launch_grouped(packed))

    # -- growth de-cliffing -------------------------------------------------
    def _maybe_pregrow(self) -> None:
        """When the table crosses 75% occupancy, pre-compile the serving
        kernels for the doubled size on a background thread — OUTSIDE the
        store lock — so the eventual ``_grow`` swap finds them in the jit
        cache instead of stalling the serving path for the recompile
        (~1 s/size on TPU; see DESIGN.md "Table growth")."""
        target = self.n_slots * 2
        if (self._pregrow_target < target
                and self.dir.free_count * 4 < self.n_slots):
            self._pregrow_target = target
            t = threading.Thread(
                target=self._pregrow_worker, args=(target,),
                name="table-pregrow", daemon=True,
            )
            # Tracked so aclose() can join: a daemon thread mid-XLA-compile
            # at interpreter teardown aborts the process ("FATAL: exception
            # not rethrown" out of the runtime's thread machinery).
            self.store._bg_threads.add(t)
            t.start()

    def _pregrow_worker(self, n_slots: int) -> None:
        try:
            with self.store.profiler.span("pregrow_warm", n_slots):
                self._warm_for_size(n_slots)
            self.store.metrics.pregrows += 1
        except Exception as exc:  # a failed warm only costs the old cliff
            log.error_evaluating_kernel(exc)

    def acquire_blocking(self, key: str, count: int) -> AcquireResult:
        out_np = np.asarray(self._launch([_AcquireReq(key, count)]))
        return AcquireResult(bool(out_np[0, 0] > 0.5), float(out_np[1, 0]))


    # -- shared bulk machinery (acquire_many over any packed table) --------
    #: Max scanned batches per bulk dispatch: 32 × 4096 ≈ 640KB of compact
    #: operands, amortizing dispatch overhead (the best K on the chip is
    #: not measured). K is chosen per call from {1, 2, 4, …, 32}, so the
    #: jit cache holds at most 6 bulk variants per table.
    _BULK_MAX_K = 32
    #: Profiler span name for a bulk call's store-lock section, in both
    #: plans (per table family): the plan is chosen inside it, from the
    #: resolved slots.
    _BULK_SPAN = "acquire_many"

    def _launch_many(self, slots: np.ndarray, counts_np: np.ndarray,
                     with_remaining: bool) -> list[tuple]:
        """Dispatch a whole resolved slot array as scanned kernel launches
        (the caller holds the store lock); returns per-dispatch device
        handles (no readback — callers overlap it). The chunking/padding
        discipline is shared; each chunk goes up as ONE
        :func:`~.ops.kernels.pack_rows` operand to the table family's scan
        kernel (``_scan``): 5 bytes a decision where the counts fit a
        byte, else 8."""
        n = len(slots)
        b = self.store.max_batch
        outs: list[tuple] = []
        compact = n > 0 and int(counts_np.max(initial=0)) <= 0xFF
        with annotate("drl/store.dispatch"):
            now = self.store.now_ticks_checked()
            pos = 0
            while pos < n:
                rows = -(-(n - pos) // b)  # ceil
                k = 1
                while k < rows and k < self._BULK_MAX_K:
                    k *= 2
                # Oversized counts keep the full width: their 8-byte
                # kernel compiles on first use, not with the table.
                w = (self._launch_width(n - pos)
                     if rows == 1 and compact else b)
                take = min(k * w, n - pos)
                s = np.full((k * w,), -1, np.int32)
                s[:take] = slots[pos:pos + take]
                c = np.zeros((k * w,), np.uint8 if compact else np.int32)
                c[:take] = np.minimum(counts_np[pos:pos + take], 2**31 - 1)
                rows = K.pack_rows(s.reshape(k, w), c.reshape(k, w))
                nows = np.full((k,), now, np.int32)
                out = self._launch_scan_chunk(
                    rows, nows, not with_remaining and w % 8 == 0)
                outs.append((out, take))
                self.store.metrics.record_launch(k * w, take, rows, nows)
                pos += take
        return outs

    def _launch_scan_chunk(self, rows: np.ndarray, nows: np.ndarray,
                           verdict_only: bool):
        """One chunk's scanned dispatch — returns a device handle whose
        layout ``_gather_bulk`` understands (u8 bit-packed grants or
        ``f32[K, 2, B]``). The operands stay numpy: jit copies them up
        inside the call, the launch's one host→device crossing."""
        self.state, out = self._scan(self.state, rows, nows, verdict_only)
        return out

    def _scan(self, state, rows, nows, verdict_only: bool):
        """The table family's scan kernel on ``state``: returns
        ``(new_state, out)``."""
        raise NotImplementedError

    @staticmethod
    def _gather_bulk(outs: list[tuple], n: int,
                     with_remaining: bool = True) -> BulkAcquireResult:
        granted = np.empty((n,), bool)
        remaining = np.empty((n,), np.float32) if with_remaining else None
        pos = 0
        # ONE device→host fetch per dispatch, and ONE device_get across
        # dispatches so those fetches overlap instead of serializing a
        # round trip each.
        arrs = jax.device_get([h for h, _ in outs])
        for out_np, (_, take) in zip(arrs, outs):
            if out_np.dtype == np.uint8:       # bit-packed grants
                bits = np.unpackbits(out_np.reshape(-1), bitorder="little")
                granted[pos:pos + take] = bits[:take].astype(bool)
            else:                              # f32[K, 2, B]
                granted[pos:pos + take] = (
                    out_np[:, 0, :].reshape(-1)[:take] > 0.5)
                if remaining is not None:
                    remaining[pos:pos + take] = (
                        out_np[:, 1, :].reshape(-1)[:take])
            pos += take
        return BulkAcquireResult(granted, remaining)

    @staticmethod
    def _grant_probes(res: BulkAcquireResult,
                      counts_np: np.ndarray) -> BulkAcquireResult:
        """Zero-permit probes are granted unconditionally on every
        single-request path (the kernel's ``new_v >= 0`` is always true);
        the bulk path's conservative in-batch prefix could deny a probe
        riding beside denied same-key demand — override here so direct
        store callers see one contract (not just limiters that patch up)."""
        _grant_zero_probes(res.granted, counts_np)
        return res

    @staticmethod
    def _bulk_groups(slots: np.ndarray, counts_np: np.ndarray):
        """Slot-grouped view of a bulk call for duplicate coalescing, or
        ``None`` when it wouldn't pay. The decision counts distinct slots
        with one sort and one compare; only a call that groups pays for
        the stable argsort + segment boundaries (request order is
        preserved within each slot's segment, which is what makes group
        decisions bit-identical to the per-row conservative
        serialization). Declines when <25% of rows would be saved, or
        when any key's counts are mixed (the scan path's exact prefixes
        handle that rare shape)."""
        n = len(slots)
        if n < 2:
            return None
        s_sorted = np.sort(slots)  # what the stable argsort sorts to
        seg_start = np.r_[True, s_sorted[1:] != s_sorted[:-1]]
        if int(np.count_nonzero(seg_start)) * 4 > n * 3:
            return None
        order = np.argsort(slots, kind="stable")
        starts = np.nonzero(seg_start)[0]
        lengths = np.diff(np.r_[starts, n])
        c_sorted = counts_np[order]
        first_c = c_sorted[starts]
        if not np.array_equal(c_sorted, np.repeat(first_c, lengths)):
            return None
        seg_id = np.cumsum(seg_start) - 1
        rank = np.arange(n) - starts[seg_id]
        return order, seg_id, rank, starts, lengths, first_c

    def _launch_many_grouped(self, slots: np.ndarray, groups,
                             with_remaining: bool):
        """Coalesced bulk dispatch of resolved slots (the caller holds the
        store lock): one launch row per ``(key, count)`` group via the
        grouped flush kernel — under Zipf hot keys the transferred bytes
        (the bulk path's real cost) shrink by the duplicate fraction.
        ``groups`` is :meth:`_bulk_groups`' view. Returns a readback
        closure."""
        n = len(slots)
        order, seg_id, rank, starts, lengths, first_c = groups
        gslots = slots[order][starts]
        gcounts = np.minimum(first_c, 2**31 - 1).astype(np.int32)
        b = self.store.max_batch
        outs: list[tuple] = []
        with annotate("drl/store.dispatch"):
            now = self.store.now_ticks_checked()
            for pos in range(0, len(gslots), b):
                m = min(b, len(gslots) - pos)
                w = self._launch_width(m)
                packed = np.full((5, w), -1, np.int32)
                packed[1] = 0
                packed[3] = 0  # one group per slot per call ⇒ prefix 0
                packed[4] = 0
                packed[0, :m] = gslots[pos:pos + m]
                packed[1, :m] = gcounts[pos:pos + m]
                packed[2] = now
                packed[4, :m] = np.minimum(lengths[pos:pos + m], 2**31 - 1)
                out = self._launch_grouped(packed)
                outs.append((out, m))
                self.store.metrics.record_launch(w, m, packed)
        self.store.metrics.rows_coalesced += n - len(gslots)

        def gather() -> BulkAcquireResult:
            n_g = np.empty(len(gslots), np.float32)
            rem_g = np.empty(len(gslots), np.float32)
            pos = 0
            for out, m in outs:
                out_np = np.asarray(out)  # one fetch per dispatch
                n_g[pos:pos + m] = out_np[0, :m]
                rem_g[pos:pos + m] = out_np[1, :m]
                pos += m
            granted_sorted = rank < n_g[seg_id]
            granted = np.empty(n, bool)
            granted[order] = granted_sorted
            remaining = None
            if with_remaining:
                c = first_c[seg_id].astype(np.float32)
                # Each member's per-row remaining view, reconstructed from
                # the group result exactly as the flush path does
                # (_PackedLaunchMixin._flush).
                avail = rem_g[seg_id] + n_g[seg_id] * c
                rem_sorted = np.maximum(
                    avail - rank * c - np.where(granted_sorted, c, 0.0), 0.0)
                remaining = np.empty(n, np.float32)
                remaining[order] = rem_sorted.astype(np.float32)
            return BulkAcquireResult(granted, remaining)

        return gather

    def _bulk_plan(self, keys: Sequence[str], counts_np: np.ndarray,
                   with_remaining: bool):
        """Resolve the call's keys once, choose the grouped or scanned plan
        from those slots, and dispatch it — one store-lock section;
        returns the readback closure (callers run it inline or on an
        executor)."""
        n = len(keys)
        metrics = self.store.metrics
        with self.store.profiler.span(self._BULK_SPAN, n), self.store._lock:
            # keys may be a wire.KeyBlob: the native directory resolves
            # straight from the frame's byte blob (zero Python strings).
            slots = self.resolve_slots(keys)
            groups = (self._bulk_groups(slots, counts_np)
                      if self.store.coalesce_duplicates else None)
            if groups is not None:
                metrics.bulk_plans_grouped += 1
                return self._launch_many_grouped(slots, groups,
                                                 with_remaining)
            metrics.bulk_plans_scanned += 1
            outs = self._launch_many(slots, counts_np, with_remaining)
        return lambda: self._gather_bulk(outs, n, with_remaining)

    def acquire_many_blocking(self, keys: Sequence[str],
                              counts: Sequence[int], *,
                              with_remaining: bool = True) -> BulkAcquireResult:
        counts_np = np.asarray(counts, np.int64)
        gather = self._bulk_plan(keys, counts_np, with_remaining)
        with annotate("drl/store.readback"):
            res = gather()
        return self._grant_probes(res, counts_np)

    async def acquire_many(self, keys: Sequence[str],
                           counts: Sequence[int], *,
                           with_remaining: bool = True) -> BulkAcquireResult:
        counts_np = np.asarray(counts, np.int64)
        t0 = time.perf_counter()
        gather = self._bulk_plan(keys, counts_np, with_remaining)
        self.store.metrics.store_host.record(time.perf_counter() - t0)
        # ONE await resolves the whole call; the readback runs off-loop so
        # the event loop keeps serving (and other bulk calls' dispatches
        # overlap this one's transfer).
        res = await _readback(self.store, gather)
        return self._grant_probes(res, counts_np)


def _arm_dirty(table) -> None:
    """Arm one table's dirty accounting (idempotent). Classic tables
    track the exact host-resolved slot set (``dirty_slots``);
    fingerprint tables — whose slot placement happens in-kernel, never
    on host — count dispatched rows instead (``dirty_rows``, a
    documented upper bound: duplicates re-count)."""
    if hasattr(table, "dirty_slots"):
        if table.dirty_slots is None:
            table.dirty_slots = set()
    elif hasattr(table, "dirty_rows") and table.dirty_rows is None:
        table.dirty_rows = 0


def _dirty_clear(table) -> None:
    if getattr(table, "dirty_slots", None) is not None:
        table.dirty_slots.clear()
    elif getattr(table, "dirty_rows", None) is not None:
        table.dirty_rows = 0


def _dirty_count(table) -> int:
    if getattr(table, "dirty_slots", None) is not None:
        return len(table.dirty_slots)
    return int(getattr(table, "dirty_rows", None) or 0)


class _DeviceTable(_PackedLaunchMixin):
    """One homogeneous-config bucket table: device arrays + host directory."""

    LAUNCH_WIDTHS = (16,)

    def __init__(self, store: "DeviceBucketStore", capacity: float,
                 fill_rate_per_sec: float, n_slots: int) -> None:
        self.store = store
        self.capacity = float(capacity)
        self.fill_rate_per_sec = float(fill_rate_per_sec)
        self.rate_per_tick = _rate_per_tick(fill_rate_per_sec)
        self.state = K.init_bucket_state(n_slots)
        self.n_slots = n_slots
        # Host key→slot routing: C++ batch-resolve when buildable, Python
        # fallback otherwise (runtime/directory.py — identical semantics).
        self.dir = make_directory(n_slots)
        # Device-resident config constants: uploaded once, never per flush.
        self.cap_dev = jnp.float32(self.capacity)
        self.rate_dev = jnp.float32(self.rate_per_tick)
        self.batcher: MicroBatcher[_AcquireReq, AcquireResult] = MicroBatcher(
            self._flush,
            max_batch=store.max_batch,
            max_delay_s=store.max_delay_s,
            max_inflight=store.max_inflight,
            flush_latency=store.metrics.flush_latency,
            queue_latency=store.metrics.queue_latency,
            flush_observer=store._flush_observer,
        )
        self._pregrow_target = 0
        self.state = self._warm_launches(self.state)

    # -- slot management ---------------------------------------------------
    def resolve_slots(self, keys: list[str]) -> np.ndarray:
        """Batch key→slot resolution (the host hot path — one native call)."""
        with annotate("drl/store.resolve"):
            slots = _resolve_with_reclaim(self.dir, keys, self._sweep,
                                          self._grow,
                                          min_free=self.n_slots // 16)
        if self.dirty_slots is not None:
            self.dirty_slots.update(slots.tolist())
        self._maybe_pregrow()
        return slots

    def _warm_launches(self, state: K.BucketState) -> K.BucketState:
        """Run every served decision kernel once at every launch width, on
        all-padding operands (state values untouched): the flush's per-row
        and grouped kernels, and a one-chunk bulk call with and without
        remainders. A shape first met mid-serving would land a ~1 s TPU
        compile inside the store lock, at whatever moment a flush or bulk
        call first outgrew the narrow width."""
        cap, rate = self.cap_dev, self.rate_dev
        for w in self._launch_widths():
            packed = np.full((5, w), -1, np.int32)
            packed[1:] = 0
            state, out = K.acquire_batch_packed(state, packed[:4], cap, rate)
            if self.store.coalesce_duplicates:
                state, out = K.acquire_batch_packed_grouped(
                    state, packed, cap, rate)
            state, out = self._warm_scan(state, 1, w)
        jax.block_until_ready(out)
        return state

    def _warm_scan(self, state: K.BucketState, k: int, w: int):
        """Run the bulk scan kernel once on ``k × w`` padding rows, in
        both verdict forms (bits only where ``w % 8 == 0``)."""
        rows = K.pack_rows(np.full((k, w), -1, np.int32),
                           np.zeros((k, w), np.uint8))
        nows = np.zeros((k,), np.int32)
        for verdict_only in (False, True) if w % 8 == 0 else (False,):
            state, out = self._scan(state, rows, nows, verdict_only)
        return state, out

    def _warm_for_size(self, n_slots: int) -> None:
        """One dummy pass of every serving+sweep kernel at ``n_slots`` —
        populates the jit cache for the post-grow shapes. Includes the
        bulk scan kernel at K=``_BULK_MAX_K`` in both verdict forms (the
        large-``acquire_many`` shape) so the first post-grow bulk call
        doesn't hit the ~1s recompile cliff the pregrow machinery exists
        to remove; smaller tail-K chunks may still compile lazily
        (cheaper, and off the common path). The dummy state is freed
        eagerly at the end — the warm runs concurrently with the live
        table, so holding it would keep transient device memory at ~3×
        through the 75%-occupancy window."""
        state = self._warm_launches(K.init_bucket_state(n_slots))
        state, _ = K.sweep_expired(state, jnp.int32(0), self.cap_dev,
                                   self.rate_dev)
        state, out = self._warm_scan(state, self._BULK_MAX_K,
                                     self.store.max_batch)
        jax.block_until_ready(out)
        for arr in state:
            arr.delete()

    def _sweep(self, pinned: set[int] | None = None) -> None:
        """Reclaim slots whose buckets have sat full-refilled past TTL
        (invariant 5). One vectorized pass; freed ids return to the pool.

        On TPU the pass runs as the fused Pallas streaming kernel, whose
        per-tile expired counts let a no-op sweep finish after a ~100-byte
        readback instead of fetching the full bool mask (N bytes — 10 MB at
        10M slots). Elsewhere it runs the XLA kernel, or the Pallas kernel
        in Pallas's interpreter when ``use_pallas_sweep=True`` is asked
        for (tests). A Pallas failure raises: there is no quiet fallback.

        ``pinned`` slots (already resolved for the in-flight batch) are
        exempt — a sweep triggered mid-batch must not free-and-reallocate a
        slot an earlier request in the same batch is about to touch, which
        would cross-contaminate two keys' buckets."""
        with self.store.profiler.span("sweep", self.n_slots):
            self._sweep_locked(pinned)

    def _sweep_locked(self, pinned: set[int] | None = None) -> None:
        now = self.store.clock.now_ticks()
        if self.store.use_pallas_sweep:
            from distributedratelimiting.redis_tpu.ops.pallas_kernels import (
                sweep_expired_pallas,
            )

            new_exists, mask, counts = sweep_expired_pallas(
                self.state.tokens, self.state.last_ts,
                self.state.exists.astype(jnp.int8), jnp.int32(now),
                jnp.float32(self.capacity), jnp.float32(self.rate_per_tick),
                interpret=self.store.pallas_interpret,
            )
            self.state = K.BucketState(
                self.state.tokens, self.state.last_ts,
                new_exists.astype(bool),
            )
            if int(np.asarray(counts).sum()) == 0:
                self.store.metrics.sweeps += 1
                return
            freed_np = np.asarray(mask).astype(bool)
        else:
            self.state, freed = K.sweep_expired(
                self.state, jnp.int32(now), jnp.float32(self.capacity),
                jnp.float32(self.rate_per_tick),
            )
            freed_np = np.asarray(freed)
        if freed_np.any():
            dead = np.nonzero(freed_np)[0].astype(np.int32)
            if pinned:
                dead = dead[~np.isin(dead, np.fromiter(pinned, np.int32,
                                                       len(pinned)))]
            self.store.metrics.slots_evicted += self.dir.remove_slots(dead)
        self.store.metrics.sweeps += 1

    def _grow(self) -> None:
        """Double the table. Amortized; recompiles kernels for the new N."""
        old_n = self.n_slots
        new_n = old_n * 2
        self.state = K.BucketState(
            tokens=jnp.concatenate([self.state.tokens, jnp.zeros((old_n,), jnp.float32)]),
            last_ts=jnp.concatenate([self.state.last_ts, jnp.zeros((old_n,), jnp.int32)]),
            exists=jnp.concatenate([self.state.exists, jnp.zeros((old_n,), bool)]),
        )
        self.dir.add_slots(old_n, new_n)
        self.n_slots = new_n

    # -- decision paths ----------------------------------------------------
    def _launch_grouped(self, packed):
        self.state, out = K.acquire_batch_packed_grouped(
            self.state, packed, self.cap_dev, self.rate_dev,
        )
        return out

    def _launch(self, reqs: Sequence[_AcquireReq]):
        """Build padded arrays and dispatch one acquire kernel launch.

        The whole read-modify-write of the donated ``self.state`` runs under
        the store lock: the blocking path may be called from arbitrary
        threads while the event loop flushes batches, and two concurrent
        donating kernel calls on the same buffers would race (one side
        would operate on a deleted/donated array)."""
        with self.store.profiler.span("acquire_batch", len(reqs)), \
                self.store._lock:
            slots = self.resolve_slots([r.key for r in reqs])
            # Fixed pad widths (LAUNCH_WIDTHS, max_batch), each compiled
            # with the table, never one per batch length (~1 s per size
            # on TPU, fatal for serving-path p99).
            b = self._launch_width(len(reqs))
            now = self.store.now_ticks_checked()
            packed = _build_packed(reqs, slots, b, now)
            self.state, out = K.acquire_batch_packed(
                self.state, packed, self.cap_dev, self.rate_dev,
            )
            self.store.metrics.record_launch(b, len(reqs), packed)
            return out

    # -- bulk decision path (chunk loop shared via _PackedLaunchMixin) -----
    def _scan(self, state, rows, nows, verdict_only: bool):
        return K.acquire_scan(state, rows, nows, self.cap_dev, self.rate_dev,
                              verdict_only=verdict_only)

    def _debit_launch(self, keys: Sequence[str], amounts: Sequence[float]):
        """One saturating-debit launch (tier-0 reconciliation): refill,
        subtract the drained local grants clamped at zero, return the
        packed ``f32[2, B]`` result (post-debit balance, shortfall).
        Same single-transfer/locking discipline as ``_launch``."""
        n = len(keys)
        with self.store.profiler.span("debit_batch", n), self.store._lock:
            slots = self.resolve_slots(list(keys))
            b = _pad_size(n, floor=64)
            now = self.store.now_ticks_checked()
            packed = np.full((3, b), -1, np.int32)
            packed[1] = 0
            packed[0, :n] = slots
            # Float amounts travel bitcast in the int32 row (exact) —
            # the counter-sync operand convention.
            packed[1, :n] = np.asarray(amounts, np.float32).view(np.int32)
            packed[2] = now
            self.state, out = K.debit_batch_packed(
                self.state, jnp.asarray(packed), self.cap_dev, self.rate_dev,
            )
            self.store.metrics.record_launch(b, n)
            return out

    def peek_blocking(self, key: str) -> float:
        with self.store._lock:
            slot = self.dir.lookup(key)
            if slot is None:
                return float(np.floor(self.capacity))
            b = _pad_size(1)
            packed = _build_packed([_AcquireReq(key, 0)], [slot], b,
                                   self.store.now_ticks_checked())
            est = K.peek_batch_packed(
                self.state, jnp.asarray(packed), self.cap_dev, self.rate_dev,
            )
        return float(np.asarray(est)[0])

    def rebase(self, offset: int) -> None:
        self.state = K.rebase_bucket_epoch(self.state, jnp.int32(offset))

    # -- checkpoint form (swapped wholesale by _FpTable) -------------------
    def to_snap(self) -> dict:
        return {
            "directory": self.dir.to_dict(),
            "tokens": np.asarray(self.state.tokens),
            "last_ts": np.asarray(self.state.last_ts),
            "exists": np.asarray(self.state.exists),
        }

    def load_snap(self, data: dict, shift: int) -> None:
        if "directory" not in data:
            raise ValueError(
                "checkpoint's bucket tables use the device-resident "
                "fingerprint directory — restore into a "
                "FingerprintBucketStore")
        # Adopt the snapshot's size: tables grow independently by
        # doubling at runtime, so a post-growth checkpoint has no
        # reason to match a fresh store's default size — a restore
        # that raised here would crash-loop exactly the planned
        # restart it exists for.
        self.n_slots = len(data["tokens"])
        self.state = K.BucketState(
            tokens=jnp.asarray(data["tokens"]),
            last_ts=jnp.asarray(_shift_ts(data["last_ts"], shift)),
            exists=jnp.asarray(data["exists"]),
        )
        self.dir.load(data["directory"], self.n_slots)


class _DeviceWindowTable(_PackedLaunchMixin):
    """One homogeneous-config window table (sliding by default;
    ``fixed=True`` disables the trailing-window interpolation — the
    fixed-window limiter's semantics — over the same state/sweeps)."""

    def __init__(self, store: "DeviceBucketStore", limit: float,
                 window_ticks: int, n_slots: int, *,
                 fixed: bool = False) -> None:
        self.store = store
        self.limit = float(limit)
        self.fixed = fixed
        self.window_ticks = int(window_ticks)
        self.state = K.init_window_state(n_slots)
        self.n_slots = n_slots
        self.dir = make_directory(n_slots)
        self.limit_dev = jnp.float32(self.limit)
        self.window_dev = jnp.int32(self.window_ticks)
        self.batcher: MicroBatcher[_AcquireReq, AcquireResult] = MicroBatcher(
            self._flush,
            max_batch=store.max_batch,
            max_delay_s=store.max_delay_s,
            max_inflight=store.max_inflight,
            flush_latency=store.metrics.flush_latency,
            queue_latency=store.metrics.queue_latency,
            flush_observer=store._flush_observer,
        )
        self._pregrow_target = 0
        if store.coalesce_duplicates:
            self._warm_grouped()

    def resolve_slots(self, keys: list[str]) -> np.ndarray:
        with annotate("drl/store.resolve"):
            slots = _resolve_with_reclaim(self.dir, keys, self._sweep,
                                          self._grow,
                                          min_free=self.n_slots // 16)
        if self.dirty_slots is not None:
            self.dirty_slots.update(slots.tolist())
        self._maybe_pregrow()
        return slots

    def _warm_for_size(self, n_slots: int) -> None:
        b = self.store.max_batch
        state = K.init_window_state(n_slots)
        packed = np.full((4, b), -1, np.int32)
        packed[1:] = 0
        state, out = K.window_acquire_batch_packed(
            state, packed, self.limit_dev, self.window_dev,
            interpolate=not self.fixed)
        state, _ = K.sweep_windows(state, jnp.int32(0), self.window_dev)
        if self.store.coalesce_duplicates:
            packed5 = np.full((5, b), -1, np.int32)
            packed5[1:] = 0
            state, out = K.window_acquire_batch_packed_grouped(
                state, packed5, self.limit_dev, self.window_dev,
                interpolate=not self.fixed)
        jax.block_until_ready(out)

    def _sweep(self, pinned: set[int] | None = None) -> None:
        with self.store.profiler.span("sweep_windows", self.n_slots):
            self._sweep_locked(pinned)

    def _sweep_locked(self, pinned: set[int] | None = None) -> None:
        now = self.store.clock.now_ticks()
        self.state, freed = K.sweep_windows(
            self.state, jnp.int32(now), jnp.int32(self.window_ticks)
        )
        freed_np = np.asarray(freed)
        if freed_np.any():
            dead = np.nonzero(freed_np)[0].astype(np.int32)
            if pinned:
                dead = dead[~np.isin(dead, np.fromiter(pinned, np.int32,
                                                       len(pinned)))]
            self.store.metrics.slots_evicted += self.dir.remove_slots(dead)
        self.store.metrics.sweeps += 1

    def rebase(self, offset_ticks: int) -> None:
        self.state = K.rebase_window_epoch(
            self.state, jnp.int32(offset_ticks // self.window_ticks)
        )

    # -- checkpoint form (swapped wholesale by _FpWindowTable) -------------
    def to_snap(self) -> dict:
        return {
            "directory": self.dir.to_dict(),
            "prev_count": np.asarray(self.state.prev_count),
            "curr_count": np.asarray(self.state.curr_count),
            "window_idx": np.asarray(self.state.window_idx),
            "exists": np.asarray(self.state.exists),
        }

    def load_snap(self, data: dict, shift: int) -> None:
        if "directory" not in data:
            raise ValueError(
                "checkpoint's window tables use the device-resident "
                "fingerprint directory — restore into a "
                "FingerprintBucketStore")
        self.n_slots = len(data["prev_count"])
        self.state = K.WindowState(
            prev_count=jnp.asarray(data["prev_count"]),
            curr_count=jnp.asarray(data["curr_count"]),
            window_idx=jnp.asarray(
                _shift_ts(data["window_idx"], shift // self.window_ticks)),
            exists=jnp.asarray(data["exists"]),
        )
        self.dir.load(data["directory"], self.n_slots)

    def _grow(self) -> None:
        old_n = self.n_slots
        self.state = K.WindowState(
            prev_count=jnp.concatenate([self.state.prev_count, jnp.zeros((old_n,), jnp.float32)]),
            curr_count=jnp.concatenate([self.state.curr_count, jnp.zeros((old_n,), jnp.float32)]),
            window_idx=jnp.concatenate([self.state.window_idx, jnp.zeros((old_n,), jnp.int32)]),
            exists=jnp.concatenate([self.state.exists, jnp.zeros((old_n,), bool)]),
        )
        self.dir.add_slots(old_n, old_n * 2)
        self.n_slots = old_n * 2

    def _launch_grouped(self, packed):
        self.state, out = K.window_acquire_batch_packed_grouped(
            self.state, packed, self.limit_dev, self.window_dev,
            interpolate=not self.fixed,
        )
        return out

    def _launch(self, reqs: Sequence[_AcquireReq]):
        # Same dispatch discipline as _DeviceTable.
        with self.store.profiler.span("window_acquire_batch", len(reqs)), \
                self.store._lock:
            slots = self.resolve_slots([r.key for r in reqs])
            b = self.store.max_batch  # fixed pad ⇒ one compiled kernel
            packed = _build_packed(reqs, slots, b,
                                   self.store.now_ticks_checked())
            self.state, out = K.window_acquire_batch_packed(
                self.state, packed, self.limit_dev, self.window_dev,
                interpolate=not self.fixed,
            )
            self.store.metrics.record_launch(b, len(reqs), packed)
            return out

    # -- bulk path (chunk loop shared via _PackedLaunchMixin) --------------
    _BULK_SPAN = "window_acquire_many"

    def _scan(self, state, rows, nows, verdict_only: bool):
        return K.window_acquire_scan(
            state, rows, nows, self.limit_dev, self.window_dev,
            interpolate=not self.fixed, verdict_only=verdict_only)


class DeviceBucketStore(BucketStore):
    """TPU-resident store: HBM tables + micro-batched kernel launches."""

    def __init__(
        self,
        *,
        n_slots: int = 2**17,
        counter_slots: int = 2**14,
        clock: Clock | None = None,
        max_batch: int = 4096,
        max_delay_s: float = 200e-6,
        max_inflight: int = 8,
        use_pallas_sweep: bool | None = None,
        coalesce_duplicates: bool = True,
        profiling_session: Callable[[], ProfilingSession | None] | None = None,
        rebase_threshold_ticks: int = _REBASE_THRESHOLD_TICKS,
    ) -> None:
        self.clock = clock or MonotonicClock()
        # ≙ Func<ProfilingSession> registered with the connection on connect
        # (TryRegisterProfiler, RedisTokenBucketRateLimiter.cs:166-174);
        # here the "commands" profiled are kernel dispatches.
        self.profiler = Profiler(profiling_session)
        on_tpu = jax.default_backend() == "tpu"
        # The TTL sweep's kernel: Pallas on the chip, always; off the chip
        # XLA, or Pallas's interpreter when a test asks for it.
        self.use_pallas_sweep = on_tpu if use_pallas_sweep is None \
            else use_pallas_sweep
        self.pallas_interpret = not on_tpu
        # Flush-level same-key coalescing (False = ablation/debug: every
        # request is its own launch row, in-kernel prefix serialization).
        self.coalesce_duplicates = coalesce_duplicates
        self.n_slots_default = n_slots
        self.counter_slots = counter_slots
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.max_inflight = max_inflight
        self.metrics = StoreMetrics()
        self._tables: dict[tuple[float, float], _DeviceTable] = {}
        self._wtables: dict[tuple[float, int], _DeviceWindowTable] = {}
        self._counters = K.init_counter_state(counter_slots)
        self._counter_dir = make_directory(counter_slots)
        self._semas = K.init_sema_state(counter_slots)
        self._sema_dir = make_directory(counter_slots)
        self._decay_rate_dev: dict[float, jax.Array] = {}
        self._lock = threading.RLock()  # directory/slot allocation guard
        # A composing store (MeshBucketStore) sets this effectively
        # infinite and coordinates one rebase across every table sharing
        # the clock — independent rebases would strand sibling stores'
        # timestamps in the old epoch.
        self._rebase_threshold = rebase_threshold_ticks
        self._connected = False
        self._connect_gate = asyncio.Lock()
        self._sweeper_task: asyncio.Task | None = None
        # Live background pregrow-warm threads (see _maybe_pregrow);
        # joined on aclose so process exit never tears XLA down under a
        # mid-compile thread.
        self._bg_threads: set[threading.Thread] = set()
        # Dirty-slot accounting (incremental checkpoints; see
        # enable_dirty_tracking) — off by default, zero serving cost.
        self._dirty_tracking = False

    # -- connection lifecycle (lazy, idempotent) ---------------------------
    async def connect(self) -> None:
        if self._connected:
            return
        async with self._connect_gate:  # ≙ SemaphoreSlim(1,1) double-check
            if self._connected:
                return
            # Touch the device so real connection errors surface here, not
            # on the first hot-path acquire (mirrors lazy ConnectAsync).
            jax.block_until_ready(jnp.zeros((8,)))
            self._connected = True

    def _flush_observer(self, n: int, wall_s: float,
                        error: str | None,
                        trace_id: str | None = None) -> None:
        """Per-flush flight-recorder feed (MicroBatcher ``flush_observer``).
        One attribute check per flush when no recorder is attached; a
        flush FAILURE is the store's degraded-mode entry, so it also
        fires a rate-limited auto-dump — the outage window's lead-in
        frames land on disk while they still exist. ``trace_id`` (the
        flush's elected trace, when any member was sampled) stamps the
        frame so a flight dump cross-references its exported trace."""
        rec = self.metrics.flight_recorder
        if rec is None:
            return
        rec.record("flush", n=n, wall_ms=round(wall_s * 1e3, 3),
                   error=error, trace_id=trace_id)
        if error is not None:
            rec.auto_dump("flush_error", {"error": error,
                                          "trace_id": trace_id})

    def now_ticks_checked(self) -> int:
        """Read the store clock; rebase every table's epoch before int32
        tick time can overflow (~24 days of uptime)."""
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
        """Shift every table's stored timestamps by ``-offset`` WITHOUT
        touching the clock — the coordinated-rebase hook for composing
        stores (the caller rebases the shared clock exactly once after
        every participating store has shifted)."""
        with self._lock:
            for t in self._tables.values():
                t.rebase(offset)
            for wt in self._wtables.values():
                wt.rebase(offset)
            self._counters = K.rebase_counter_epoch(
                self._counters, jnp.int32(offset)
            )
            self._semas = K.rebase_sema_epoch(
                self._semas, jnp.int32(offset)
            )

    # -- table routing -----------------------------------------------------
    # Subclasses swap the constructed table classes (the fingerprint store
    # substitutes its device-directory tables) without copying the keying
    # or locking below.
    _TABLE_CLS: type = None  # type: ignore[assignment]  # set after class
    _WTABLE_CLS: type = None  # type: ignore[assignment]

    def _table(self, capacity: float, fill_rate_per_sec: float) -> "_DeviceTable":
        key = (float(capacity), float(fill_rate_per_sec))
        with self._lock:
            table = self._tables.get(key)
            if table is None:
                table = self._TABLE_CLS(self, capacity, fill_rate_per_sec,
                                        self.n_slots_default)
                if self._dirty_tracking:
                    _arm_dirty(table)
                self._tables[key] = table
            return table

    def _wtable(self, limit: float, window_sec: float,
                fixed: bool = False) -> "_DeviceWindowTable":
        wt = int(window_sec * bm.TICKS_PER_SECOND)
        key = (float(limit), wt, fixed)
        with self._lock:
            table = self._wtables.get(key)
            if table is None:
                table = self._WTABLE_CLS(self, limit, wt,
                                           self.n_slots_default, fixed=fixed)
                if self._dirty_tracking:
                    _arm_dirty(table)
                self._wtables[key] = table
            return table

    # -- exact bucket ------------------------------------------------------
    async def acquire(self, key: str, count: int, capacity: float,
                      fill_rate_per_sec: float) -> AcquireResult:
        await self.connect()
        table = self._table(capacity, fill_rate_per_sec)
        return await table.batcher.submit(_AcquireReq(key, count))

    def acquire_submitter(self, capacity: float, fill_rate_per_sec: float):
        """Hot-path binding: resolve the table ONCE; each call is then one
        ``MicroBatcher.submit`` — no connect check, no config→table lock,
        no arg re-validation per request."""
        submit = self._table(capacity, fill_rate_per_sec).batcher.submit

        async def fast(key: str, count: int) -> AcquireResult:
            return await submit(_AcquireReq(key, count))

        return fast

    def acquire_blocking(self, key: str, count: int, capacity: float,
                         fill_rate_per_sec: float) -> AcquireResult:
        return self._table(capacity, fill_rate_per_sec).acquire_blocking(key, count)

    async def acquire_many(self, keys: Sequence[str], counts: Sequence[int],
                           capacity: float, fill_rate_per_sec: float, *,
                           with_remaining: bool = True) -> BulkAcquireResult:
        """Bulk path: the whole array rides scanned kernel launches — no
        per-request futures, one await per call (the batching the
        reference's README promised but never built, ``README.md:7``)."""
        await self.connect()
        table = self._table(capacity, fill_rate_per_sec)
        return await table.acquire_many(keys, counts,
                                        with_remaining=with_remaining)

    def acquire_many_blocking(self, keys: Sequence[str],
                              counts: Sequence[int], capacity: float,
                              fill_rate_per_sec: float, *,
                              with_remaining: bool = True) -> BulkAcquireResult:
        return self._table(capacity, fill_rate_per_sec).acquire_many_blocking(
            keys, counts, with_remaining=with_remaining)

    def peek_blocking(self, key: str, capacity: float,
                      fill_rate_per_sec: float) -> float:
        return self._table(capacity, fill_rate_per_sec).peek_blocking(key)

    async def window_acquire_many(self, keys: Sequence[str],
                                  counts: Sequence[int], limit: float,
                                  window_sec: float, *, fixed: bool = False,
                                  with_remaining: bool = True
                                  ) -> BulkAcquireResult:
        await self.connect()
        table = self._wtable(limit, window_sec, fixed)
        return await table.acquire_many(keys, counts,
                                        with_remaining=with_remaining)

    def window_acquire_many_blocking(self, keys: Sequence[str],
                                     counts: Sequence[int], limit: float,
                                     window_sec: float, *,
                                     fixed: bool = False,
                                     with_remaining: bool = True
                                     ) -> BulkAcquireResult:
        return self._wtable(limit, window_sec, fixed).acquire_many_blocking(
            keys, counts, with_remaining=with_remaining)

    # -- decaying counter --------------------------------------------------
    def _counter_slot(self, key: str) -> int:
        with self._lock:
            return int(_resolve_with_reclaim(
                self._counter_dir, [key],
                lambda pinned: self._sweep_counters(),
                self._grow_counters,
            )[0])

    def _sweep_counters(self) -> None:
        with self.profiler.span("sweep_counters",
                                self._counters.value.shape[0]):
            self._sweep_counters_locked()

    def _sweep_counters_locked(self) -> None:
        self._counters, freed = K.sweep_counters(
            self._counters, jnp.int32(self.clock.now_ticks())
        )
        freed_np = np.asarray(freed)
        if freed_np.any():
            dead = np.nonzero(freed_np)[0].astype(np.int32)
            self.metrics.slots_evicted += self._counter_dir.remove_slots(dead)
        self.metrics.sweeps += 1

    def _grow_counters(self) -> None:
        old_n = self._counters.value.shape[0]
        self._counters = K.CounterState(
            value=jnp.concatenate([self._counters.value, jnp.zeros((old_n,), jnp.float32)]),
            period=jnp.concatenate([self._counters.period, jnp.zeros((old_n,), jnp.float32)]),
            last_ts=jnp.concatenate([self._counters.last_ts, jnp.zeros((old_n,), jnp.int32)]),
            exists=jnp.concatenate([self._counters.exists, jnp.zeros((old_n,), bool)]),
        )
        self._counter_dir.add_slots(old_n, old_n * 2)

    def _sync_dispatch(self, key: str, local_count: float,
                       decay_rate_per_sec: float):
        return self._sync_dispatch_many([key], [local_count],
                                        decay_rate_per_sec)

    def _sync_dispatch_many(self, keys: Sequence[str],
                            local_counts: Sequence[float],
                            decay_rate_per_sec: float):
        """ONE ``sync_batch`` launch for a whole fleet of counters — the
        device half of :meth:`sync_counters_many` (and, with one row, of
        the classic per-limiter :meth:`sync_counter`)."""
        n = len(keys)
        with self._lock:
            slots = _resolve_with_reclaim(
                self._counter_dir, list(keys),
                lambda pinned: self._sweep_counters(),
                self._grow_counters,
            )
        with self.profiler.span("sync_counter", n), self._lock:
            b = _pad_size(n, floor=8)
            packed = np.full((3, b), -1, np.int32)
            packed[1] = 0
            packed[0, :n] = slots
            # Float local counts travel bitcast in the int32 row (exact).
            packed[1, :n] = np.asarray(local_counts,
                                       np.float32).view(np.int32)
            packed[2] = self.now_ticks_checked()
            rate = self._decay_rate_dev.get(decay_rate_per_sec)
            if rate is None:
                rate = jnp.float32(_rate_per_tick(decay_rate_per_sec))
                self._decay_rate_dev[decay_rate_per_sec] = rate
            self._counters, out = K.sync_batch_packed(
                self._counters, jnp.asarray(packed), rate,
            )
            return out

    async def sync_counter(self, key: str, local_count: float,
                           decay_rate_per_sec: float) -> SyncResult:
        """One decaying-counter sync (≙ the approximate limiter's periodic
        ``ScriptEvaluateAsync(_syncScript)``,
        ``RedisApproximateTokenBucketRateLimiter.cs:439``)."""
        await self.connect()
        out = self._sync_dispatch(key, local_count, decay_rate_per_sec)
        loop = asyncio.get_running_loop()
        out_np = await loop.run_in_executor(None, lambda: np.asarray(out))
        return SyncResult(float(out_np[0, 0]), float(out_np[1, 0]))

    def sync_counter_blocking(self, key: str, local_count: float,
                              decay_rate_per_sec: float) -> SyncResult:
        """Synchronous sync path for loop-less callers (the approximate
        limiter's inline refresh when only the sync API is used)."""
        out_np = np.asarray(self._sync_dispatch(key, local_count,
                                                decay_rate_per_sec))
        return SyncResult(float(out_np[0, 0]), float(out_np[1, 0]))

    async def sync_counters_many(self, keys: Sequence[str],
                                 local_counts: Sequence[float],
                                 decay_rate_per_sec: float
                                 ) -> tuple[np.ndarray, np.ndarray]:
        """Bulk replica sync: the whole fleet's local counts land in ONE
        ``sync_batch`` launch against the counter table (duplicate keys'
        adds accumulate — pre-aggregate per key for exact EWMAs, see
        :func:`~.ops.kernels.sync_batch`)."""
        await self.connect()
        n = len(keys)
        out = self._sync_dispatch_many(keys, local_counts,
                                       decay_rate_per_sec)
        loop = asyncio.get_running_loop()
        out_np = await loop.run_in_executor(None, lambda: np.asarray(out))
        return (out_np[0, :n].astype(np.float64),
                out_np[1, :n].astype(np.float64))

    async def debit_many(self, keys: Sequence[str],
                         amounts: Sequence[float], capacity: float,
                         fill_rate_per_sec: float
                         ) -> tuple[np.ndarray, np.ndarray]:
        """Tier-0 reconciliation against the authoritative bucket table:
        one saturating-debit launch per (capacity, rate) table (see
        :func:`~.ops.kernels.debit_batch_packed`)."""
        await self.connect()
        n = len(keys)
        table = self._table(capacity, fill_rate_per_sec)
        out = table._debit_launch(keys, amounts)
        loop = asyncio.get_running_loop()
        out_np = await loop.run_in_executor(None, lambda: np.asarray(out))
        return (out_np[0, :n].astype(np.float64),
                out_np[1, :n].astype(np.float64))

    # -- hierarchical tenant → key admission (fused kernel) ----------------
    def _hier_dispatch(self, tenants: Sequence[str], keys: Sequence[str],
                       counts_np: np.ndarray, tcap: float, trate: float,
                       cap: float, rate: float) -> list[tuple]:
        """Dispatch hierarchical rows as fused two-table launches: the
        child key table and the parent tenant table decide together in
        ONE kernel (grant iff both levels admit — the decision itself
        is the reconciliation, no refund traffic exists). Returns
        per-chunk device handles (no readback; callers overlap it)."""
        check_hierarchical_args(int(counts_np.min(initial=0)), tcap,
                                trate, cap, rate)
        n = len(keys)
        ctable = self._table(cap, rate)
        ptable = self._table(tcap, trate)
        outs: list[tuple] = []
        with self.profiler.span("acquire_hierarchical", n), self._lock:
            cslots = ctable.resolve_slots(list(keys))
            pslots = ptable.resolve_slots(list(tenants))
            now = self.now_ticks_checked()
            b = self.max_batch
            pos = 0
            while pos < n:
                take = min(b, n - pos)
                packed = np.full((4, b), -1, np.int32)
                packed[1] = 0
                packed[0, :take] = cslots[pos:pos + take]
                packed[1, :take] = np.minimum(counts_np[pos:pos + take],
                                              2**31 - 1)
                packed[2] = now
                packed[3, :take] = pslots[pos:pos + take]
                ctable.state, ptable.state, out = \
                    K.acquire_hierarchical_packed(
                        ctable.state, ptable.state, jnp.asarray(packed),
                        ctable.cap_dev, ctable.rate_dev,
                        ptable.cap_dev, ptable.rate_dev)
                outs.append((out, take))
                self.metrics.record_launch(b, take)
                pos += take
        return outs

    @staticmethod
    def _hier_gather(outs: list[tuple], n: int,
                     with_remaining: bool) -> BulkAcquireResult:
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        pos = 0
        arrs = jax.device_get([h for h, _ in outs])
        for out_np, (_, take) in zip(arrs, outs):
            granted[pos:pos + take] = out_np[0, :take] > 0.5
            if remaining is not None:
                remaining[pos:pos + take] = out_np[1, :take]
            pos += take
        return BulkAcquireResult(granted, remaining)

    def _hier_fused_supported(self) -> bool:
        """The fused lane needs host-resolved slots; fingerprint tables
        place in-kernel, so the fp store keeps the base compose (exact
        per call, parent refund through its ``debit_many``)."""
        return getattr(self._TABLE_CLS, "resolve_slots", None) is not None

    async def acquire_hierarchical(self, tenant, key, count,
                                   tenant_capacity,
                                   tenant_fill_rate_per_sec, capacity,
                                   fill_rate_per_sec, *, priority=0):
        await self.connect()
        if not self._hier_fused_supported():
            return await super().acquire_hierarchical(
                tenant, key, count, tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                priority=priority)
        res = await self.acquire_hierarchical_many(
            [tenant], [key], [int(count)], tenant_capacity,
            tenant_fill_rate_per_sec, capacity, fill_rate_per_sec)
        return res[0]

    def acquire_hierarchical_blocking(self, tenant, key, count,
                                      tenant_capacity,
                                      tenant_fill_rate_per_sec, capacity,
                                      fill_rate_per_sec, *, priority=0):
        if not self._hier_fused_supported():
            return super().acquire_hierarchical_blocking(
                tenant, key, count, tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                priority=priority)
        return self.acquire_hierarchical_many_blocking(
            [tenant], [key], [int(count)], tenant_capacity,
            tenant_fill_rate_per_sec, capacity, fill_rate_per_sec)[0]

    async def acquire_hierarchical_many(self, tenants, keys, counts,
                                        tenant_capacity,
                                        tenant_fill_rate_per_sec,
                                        capacity, fill_rate_per_sec, *,
                                        with_remaining: bool = True,
                                        priority: int = 0):
        await self.connect()
        if not self._hier_fused_supported():
            return await BucketStore.acquire_hierarchical_many(
                self, tenants, keys, counts, tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                with_remaining=with_remaining, priority=priority)
        counts_np = np.asarray(counts, np.int64)
        outs = self._hier_dispatch(tenants, keys, counts_np,
                                   tenant_capacity,
                                   tenant_fill_rate_per_sec,
                                   capacity, fill_rate_per_sec)
        loop = asyncio.get_running_loop()
        res = await loop.run_in_executor(
            None, lambda: self._hier_gather(outs, len(keys),
                                            with_remaining))
        _grant_zero_probes(res.granted, counts_np)
        return res

    def acquire_hierarchical_many_blocking(self, tenants, keys, counts,
                                           tenant_capacity,
                                           tenant_fill_rate_per_sec,
                                           capacity, fill_rate_per_sec,
                                           *, with_remaining: bool = True,
                                           priority: int = 0):
        if not self._hier_fused_supported():
            return BucketStore.acquire_hierarchical_many_blocking(
                self, tenants, keys, counts, tenant_capacity,
                tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
                with_remaining=with_remaining, priority=priority)
        counts_np = np.asarray(counts, np.int64)
        outs = self._hier_dispatch(tenants, keys, counts_np,
                                   tenant_capacity,
                                   tenant_fill_rate_per_sec,
                                   capacity, fill_rate_per_sec)
        res = self._hier_gather(outs, len(keys), with_remaining)
        _grant_zero_probes(res.granted, counts_np)
        return res

    # -- concurrency semaphore ---------------------------------------------
    def _sema_slot(self, key: str) -> int:
        with self._lock:
            return int(_resolve_with_reclaim(
                self._sema_dir, [key],
                lambda pinned: self._sweep_semas(),
                self._grow_semas,
            )[0])

    def _sweep_semas(self) -> None:
        with self.profiler.span("sweep_semas", self._semas.active.shape[0]):
            self._semas, freed = K.sweep_semas(
                self._semas, jnp.int32(self.clock.now_ticks())
            )
            freed_np = np.asarray(freed)
            if freed_np.any():
                dead = np.nonzero(freed_np)[0].astype(np.int32)
                self.metrics.slots_evicted += self._sema_dir.remove_slots(dead)
            self.metrics.sweeps += 1

    def _grow_semas(self) -> None:
        old_n = self._semas.active.shape[0]
        self._semas = K.SemaState(
            active=jnp.concatenate([self._semas.active, jnp.zeros((old_n,), jnp.int32)]),
            last_ts=jnp.concatenate([self._semas.last_ts, jnp.zeros((old_n,), jnp.int32)]),
            exists=jnp.concatenate([self._semas.exists, jnp.zeros((old_n,), bool)]),
        )
        self._sema_dir.add_slots(old_n, old_n * 2)

    def _sema_dispatch(self, key: str, delta: int, limit: int):
        if delta <= 0:
            # Read-only probe — and release of an unknown key (a spurious
            # or buggy double-release): neither may allocate a directory
            # slot; a nothing-to-release no-op beats a dead slot lingering
            # for the full TTL.
            with self._lock:
                slot = self._sema_dir.lookup(key)
            if slot is None:
                return None  # unknown key ⇒ zero held
        else:
            slot = self._sema_slot(key)
        with self.profiler.span("sema"), self._lock:
            b = _pad_size(1, floor=8)
            packed = np.full((4, b), -1, np.int32)
            packed[1] = 0
            packed[2] = 0
            packed[0, 0] = slot
            packed[1, 0] = delta
            packed[2, 0] = limit
            packed[3] = self.now_ticks_checked()
            self._semas, out = K.sema_batch_packed(
                self._semas, jnp.asarray(packed)
            )
            return out

    async def concurrency_acquire(self, key: str, count: int,
                                  limit: int) -> AcquireResult:
        await self.connect()
        out = self._sema_dispatch(key, count, limit)
        if out is None:  # probe of an unknown key: zero permits held
            return AcquireResult(True, 0.0)
        loop = asyncio.get_running_loop()
        out_np = await loop.run_in_executor(None, lambda: np.asarray(out))
        return AcquireResult(bool(out_np[0, 0] > 0.5), float(out_np[1, 0]))

    def concurrency_acquire_blocking(self, key: str, count: int,
                                     limit: int) -> AcquireResult:
        out = self._sema_dispatch(key, count, limit)
        if out is None:
            return AcquireResult(True, 0.0)
        out_np = np.asarray(out)
        return AcquireResult(bool(out_np[0, 0] > 0.5), float(out_np[1, 0]))

    async def concurrency_release(self, key: str, count: int) -> None:
        out = self._sema_dispatch(key, -count, 0)
        if out is None:  # unknown key: nothing to release
            return
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, lambda: np.asarray(out))

    def concurrency_release_blocking(self, key: str, count: int) -> None:
        out = self._sema_dispatch(key, -count, 0)
        if out is not None:
            np.asarray(out)

    async def concurrency_acquire_many(self, keys, deltas, limit):
        """Packed-kernel bulk semaphore ops: one ``sema_batch_packed``
        dispatch per 4096-row chunk (chunks run in request order on the
        donated state, so cross-chunk duplicates stay serialized).
        Acquire rows resolve-with-allocate; probe/release rows look up
        only — an unknown key answers (True, 0.0) host-side, never
        allocating (same contract as the scalar path).

        Same-key rows that MIX a release with anything else bypass the
        packed dispatch and run as sequential single-op dispatches: the
        kernel clamps a slot's net batch delta at zero, which would let
        an over-release swallow a granted acquire's permit (per-op
        semantics must survive over-release, not amplify it)."""
        await self.connect()
        n = len(keys)
        deltas_np = np.asarray(deltas, np.int64)
        limits_np = self._sema_limits(limit, n)
        granted = np.zeros(n, bool)
        remaining = np.zeros(n, np.float32)
        slots = np.full(n, -1, np.int64)
        acq_idx = np.nonzero(deltas_np > 0)[0]
        other_idx = np.nonzero(deltas_np <= 0)[0]
        # Mixed-sign duplicate hazard: keys with a release AND ≥2 rows.
        # Vectorized — releases are ~half of steady-state sema traffic,
        # so this branch runs on most flushes and must not reintroduce
        # per-request Python into the per-flush path.
        if (deltas_np < 0).any():
            uniq_inv = np.unique(np.asarray(keys, object),
                                 return_inverse=True, return_counts=True)
            _, inv, cnt = uniq_inv
            rel_key = np.zeros(len(cnt), bool)
            rel_key[inv[deltas_np < 0]] = True
            hazard = rel_key[inv] & (cnt[inv] > 1)
        else:
            hazard = np.zeros(n, bool)
        outs = []
        with self.profiler.span("sema_bulk", n), self._lock:
            if len(acq_idx):
                resolved = _resolve_with_reclaim(
                    self._sema_dir, [keys[i] for i in acq_idx.tolist()],
                    lambda pinned: self._sweep_semas(), self._grow_semas)
                slots[acq_idx] = np.asarray(resolved, np.int64)
            for i in other_idx.tolist():
                s = self._sema_dir.lookup(keys[i])
                slots[i] = -1 if s is None else s
            known = slots >= 0
            granted[~known] = True  # unknown-key probe/release: no-op OK
            idx_known = np.nonzero(known & ~hazard)[0]
            for s0 in range(0, len(idx_known), 4096):
                sub = idx_known[s0:s0 + 4096]
                b = _pad_size(len(sub), floor=8)
                packed = np.full((4, b), -1, np.int32)
                packed[1] = 0
                packed[2] = 0
                packed[0, :len(sub)] = slots[sub]
                packed[1, :len(sub)] = deltas_np[sub]
                packed[2, :len(sub)] = limits_np[sub]
                packed[3] = self.now_ticks_checked()
                self._semas, out = K.sema_batch_packed(
                    self._semas, jnp.asarray(packed))
                outs.append((sub, out))
            for i in np.nonzero(known & hazard)[0].tolist():
                d = int(deltas_np[i])
                # Mirror the scalar entry points: acquires and probes
                # carry the row's limit, releases carry 0 (ignored).
                out = self._sema_dispatch(keys[i], d,
                                          int(limits_np[i]) if d >= 0
                                          else 0)
                if out is None:
                    # Key vanished between the top-of-call lookup and
                    # this row (an interleaved acquire row's resolve can
                    # sweep a zero-held stale slot): same contract as
                    # the scalar path — unknown-key release/probe is a
                    # successful no-op.
                    granted[i] = True
                    remaining[i] = 0.0
                else:
                    outs.append((np.array([i]), out))
        loop = asyncio.get_running_loop()
        for sub, out in outs:
            out_np = await loop.run_in_executor(
                None, lambda o=out: np.asarray(o))
            m = len(sub)
            granted[sub] = out_np[0, :m] > 0.5
            remaining[sub] = np.where(deltas_np[sub] < 0, 0.0,
                                      out_np[1, :m])
        return BulkAcquireResult(granted, remaining)

    # -- sliding window ----------------------------------------------------
    async def window_acquire(self, key: str, count: int, limit: float,
                             window_sec: float) -> AcquireResult:
        await self.connect()
        table = self._wtable(limit, window_sec)
        return await table.batcher.submit(_AcquireReq(key, count))

    def window_acquire_blocking(self, key: str, count: int, limit: float,
                                window_sec: float) -> AcquireResult:
        return self._wtable(limit, window_sec).acquire_blocking(key, count)

    # -- fixed window ------------------------------------------------------
    async def fixed_window_acquire(self, key: str, count: int, limit: float,
                                   window_sec: float) -> AcquireResult:
        await self.connect()
        table = self._wtable(limit, window_sec, fixed=True)
        return await table.batcher.submit(_AcquireReq(key, count))

    def fixed_window_acquire_blocking(self, key: str, count: int,
                                      limit: float,
                                      window_sec: float) -> AcquireResult:
        return self._wtable(limit, window_sec,
                            fixed=True).acquire_blocking(key, count)

    # -- TTL maintenance ---------------------------------------------------
    def sweep_all(self) -> None:
        """One TTL-eviction pass over every table (buckets, windows,
        counters). On-demand sweeps already run on allocation pressure
        (invariant 5); this is the *active* expiry pass — Redis's
        background expiration cycle — so an idle store's memory shrinks
        without waiting for the next allocation to force it."""
        with self._lock:
            for t in list(self._tables.values()):
                t._sweep()
            for wt in list(self._wtables.values()):
                wt._sweep()
            self._sweep_counters()
            self._sweep_semas()

    def start_sweeper(self, period_s: float = 30.0) -> None:
        """Start the periodic active-expiry task on the running event loop
        (idempotent). Stops automatically in :meth:`aclose`."""
        if self._sweeper_task is not None and not self._sweeper_task.done():
            return
        self._sweeper_task = start_periodic_sweeper(self.sweep_all, period_s)

    # -- lifecycle / ops ---------------------------------------------------
    async def aclose(self) -> None:
        if self._sweeper_task is not None:
            self._sweeper_task.cancel()
            try:
                await self._sweeper_task
            except (asyncio.CancelledError, Exception):
                pass  # a failed sweeper must not abort batcher cleanup
            self._sweeper_task = None
        for t in self._tables.values():
            await t.batcher.aclose()
        for t in self._wtables.values():
            await t.batcher.aclose()
        # Join until no live warm threads remain: a bulk acquire running
        # concurrently with this aclose can spawn a NEW pregrow thread
        # after any one-shot snapshot — discard only what was joined.
        while True:
            live = [t for t in self._bg_threads if t.is_alive()]
            if not live:
                break
            for t in live:
                await asyncio.to_thread(t.join, 120.0)
            self._bg_threads.difference_update(live)
        self._bg_threads.clear()  # drop finished-thread references

    # -- dirty accounting (incremental checkpoints; OPERATIONS.md §10) ------
    def enable_dirty_tracking(self) -> None:
        """Arm per-table dirty accounting: between two saves, every
        launched-upon slot is counted, so OP_STATS can report the
        dirty/total ratio that predicts the next v4 delta's size
        (runtime/checkpoint.py). Observability only — the delta itself
        is a structural diff, correct with or without this. Counter and
        semaphore tiers are deliberately untracked: their state is a
        handful of fixed arrays, noise next to the key tables."""
        with self._lock:
            self._dirty_tracking = True
            for t in (*self._tables.values(), *self._wtables.values()):
                _arm_dirty(t)

    def mark_snapshot_base(self) -> None:
        """Start a fresh dirty window (called by the checkpoint chain at
        every save — the window is save-to-save)."""
        with self._lock:
            for t in (*self._tables.values(), *self._wtables.values()):
                _dirty_clear(t)

    def dirty_stats(self) -> dict:
        with self._lock:
            dirty = total = 0
            for t in (*self._tables.values(), *self._wtables.values()):
                dirty += _dirty_count(t)
                total += t.n_slots
            return {"dirty": dirty, "total": total}

    def snapshot(self) -> dict:
        """Pull all live state to host (planned-restart checkpoint).
        ``now_ticks`` is captured so restore into a *different* process
        (fresh clock epoch) can re-align every timestamp."""
        with self._lock:
            tables = {}
            for (cap, rate), t in self._tables.items():
                tables[(cap, rate)] = t.to_snap()
            wtables = {}
            for (limit, wt, fixed), t in self._wtables.items():
                wtables[(limit, wt, fixed)] = t.to_snap()
            return {
                "now_ticks": self.clock.now_ticks(),
                "tables": tables,
                "wtables": wtables,
                "counter_dir": self._counter_dir.to_dict(),
                "counters": {
                    "value": np.asarray(self._counters.value),
                    "period": np.asarray(self._counters.period),
                    "last_ts": np.asarray(self._counters.last_ts),
                    "exists": np.asarray(self._counters.exists),
                },
                "sema_dir": self._sema_dir.to_dict(),
                "semas": {
                    "active": np.asarray(self._semas.active),
                    "last_ts": np.asarray(self._semas.last_ts),
                    "exists": np.asarray(self._semas.exists),
                },
            }

    def restore(self, snap: dict) -> None:
        """Restore a checkpoint, re-aligning timestamps to THIS process's
        clock epoch: elapsed-since-touch is preserved by shifting every
        stored timestamp by ``now_here − now_at_snapshot`` (without this, a
        restore into a fresh process would clamp all elapsed time to zero
        and restored buckets would stop refilling)."""
        with self._lock:
            shift = int(self.clock.now_ticks()) - int(snap["now_ticks"])
            for (cap, rate), data in snap["tables"].items():
                self._table(cap, rate).load_snap(data, shift)
            for wkey, data in snap.get("wtables", {}).items():
                # Pre-fixed-window snapshots carry 2-tuple keys (sliding).
                limit, wt = wkey[0], wkey[1]
                fixed = wkey[2] if len(wkey) > 2 else False
                self._wtable(limit, wt / bm.TICKS_PER_SECOND,
                             fixed).load_snap(data, shift)
            c = snap["counters"]
            self._counters = K.CounterState(
                value=jnp.asarray(c["value"]),
                period=jnp.asarray(c["period"]),
                last_ts=jnp.asarray(_shift_ts(c["last_ts"], shift)),
                exists=jnp.asarray(c["exists"]),
            )
            self._counter_dir.load(snap["counter_dir"],
                                   self._counters.value.shape[0])
            if "semas" in snap:  # absent in pre-semaphore checkpoints
                s = snap["semas"]
                self._semas = K.SemaState(
                    active=jnp.asarray(s["active"]),
                    last_ts=jnp.asarray(_shift_ts(s["last_ts"], shift)),
                    exists=jnp.asarray(s["exists"]),
                )
                self._sema_dir.load(snap["sema_dir"],
                                    self._semas.active.shape[0])


# Table classes are defined after DeviceBucketStore, so the bindings
# land here (subclasses override the attributes, not the methods).
DeviceBucketStore._TABLE_CLS = _DeviceTable
DeviceBucketStore._WTABLE_CLS = _DeviceWindowTable


class InProcessBucketStore(BucketStore):
    """Pure-Python store with identical semantics, executed serially per
    request — the test fake and the Redis-class CPU baseline (one 'script'
    per op, no batching)."""

    def __init__(self, clock: Clock | None = None) -> None:
        self.clock = clock or MonotonicClock()
        self._buckets: dict[tuple, tuple[float, int]] = {}   # (tokens, ts)
        self._counters: dict[str, tuple[float, float, int]] = {}  # (v, p, ts)
        self._windows: dict[tuple, tuple[float, float, int]] = {}
        self._semas: dict[str, int] = {}                     # active permits
        self._connected = False
        # Dirty-key accounting for incremental checkpoints (OPERATIONS.md
        # §10) — None (one falsy check per write) until armed.
        self._dirty: "set | None" = None

    async def connect(self) -> None:
        self._connected = True

    def _acquire_core(self, key, count, capacity, rate_per_sec) -> AcquireResult:
        now = self.clock.now_ticks()
        rate = _rate_per_tick(rate_per_sec)
        bkey = (key, float(capacity), float(rate_per_sec))
        entry = self._buckets.get(bkey)
        if entry is None:
            refilled = float(capacity)
        else:
            tokens, ts = entry
            refilled = min(float(capacity), tokens + max(0, now - ts) * rate)
        granted = refilled >= count
        self._buckets[bkey] = (refilled - (count if granted else 0), now)
        if self._dirty is not None:
            self._dirty.add(bkey)
        return AcquireResult(granted, self._buckets[bkey][0])

    async def acquire(self, key, count, capacity, fill_rate_per_sec):
        await self.connect()
        return self._acquire_core(key, count, capacity, fill_rate_per_sec)

    def acquire_blocking(self, key, count, capacity, fill_rate_per_sec):
        return self._acquire_core(key, count, capacity, fill_rate_per_sec)

    async def acquire_many(self, keys, counts, capacity, fill_rate_per_sec,
                           *, with_remaining: bool = True):
        """Serial-core bulk: one in-order pass over the batch with NO
        task-per-key (the base class's gather spends ~10µs/key on task
        scheduling — measurable when this store backs the native front-end
        as the zero-cost-kernel stand-in). Still awaits ``self.acquire``
        per key: the per-key method stays the single override point for
        test fakes and subclasses, and awaiting a non-suspending
        coroutine costs no loop round trip."""
        await self.connect()
        n = len(keys)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        # Direct-core loop only when per-key acquire is NOT overridden:
        # subclasses/test fakes that intercept acquire() must see every
        # bulk key too (a coroutine frame per key costs ~20% on the
        # native-front-end stand-in, so the unsubclassed store skips it).
        direct = type(self).acquire is InProcessBucketStore.acquire
        for i, (k, c) in enumerate(zip(keys, counts)):
            r = (self._acquire_core(k, int(c), capacity, fill_rate_per_sec)
                 if direct else
                 await self.acquire(k, int(c), capacity, fill_rate_per_sec))
            granted[i] = r.granted
            if remaining is not None:
                remaining[i] = r.remaining
        return BulkAcquireResult(granted, remaining)

    async def window_acquire_many(self, keys, counts, limit, window_sec, *,
                                  fixed: bool = False,
                                  with_remaining: bool = True):
        await self.connect()
        op = (self.fixed_window_acquire if fixed else self.window_acquire)
        n = len(keys)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        for i, (k, c) in enumerate(zip(keys, counts)):
            r = await op(k, int(c), limit, window_sec)
            granted[i] = r.granted
            if remaining is not None:
                remaining[i] = r.remaining
        return BulkAcquireResult(granted, remaining)

    def peek_blocking(self, key, capacity, fill_rate_per_sec):
        now = self.clock.now_ticks()
        bkey = (key, float(capacity), float(fill_rate_per_sec))
        entry = self._buckets.get(bkey)
        if entry is None:
            return float(np.floor(capacity))
        tokens, ts = entry
        rate = _rate_per_tick(fill_rate_per_sec)
        return float(np.floor(min(float(capacity), tokens + max(0, now - ts) * rate)))

    async def debit_many(self, keys, amounts, capacity, fill_rate_per_sec):
        """Serial saturating debit — identical semantics to the device
        kernel (:func:`~.ops.kernels.debit_batch_packed`): refill, then
        subtract clamped at zero, reporting the clamped shortfall."""
        now = self.clock.now_ticks()
        rate = _rate_per_tick(fill_rate_per_sec)
        n = len(keys)
        remaining = np.empty(n, np.float64)
        shortfall = np.empty(n, np.float64)
        for i, (k, amt) in enumerate(zip(keys, amounts)):
            amt = float(amt)
            bkey = (k, float(capacity), float(fill_rate_per_sec))
            entry = self._buckets.get(bkey)
            if entry is None:
                refilled = float(capacity)
            else:
                tokens, ts = entry
                refilled = min(float(capacity),
                               tokens + max(0, now - ts) * rate)
            applied = min(amt, max(refilled, 0.0))
            self._buckets[bkey] = (refilled - applied, now)
            if self._dirty is not None:
                self._dirty.add(bkey)
            remaining[i] = refilled - applied
            shortfall[i] = amt - applied
        return remaining, shortfall

    # -- hierarchical tenant → key admission (exact serial core) -----------
    def _hier_refill(self, bkey: tuple, capacity: float,
                     rate_per_sec: float, now: int) -> float:
        entry = self._buckets.get(bkey)
        if entry is None:
            return float(capacity)
        tokens, ts = entry
        rate = _rate_per_tick(rate_per_sec)
        return min(float(capacity), tokens + max(0, now - ts) * rate)

    def _hier_core(self, tenant, key, count, tcap, trate, cap, rate
                   ) -> AcquireResult:
        """Atomic two-level decision — the serial reference the fused
        kernel (:func:`~.ops.kernels.acquire_hierarchical_packed`) is
        differential-tested against: refill both levels, grant iff
        both cover ``count``, debit both-or-neither, advance BOTH
        timestamps either way (a denied request leaves each bucket
        exactly as a refill-only touch would — the refund contract,
        closed algebraically)."""
        check_hierarchical_args(count, tcap, trate, cap, rate)
        now = self.clock.now_ticks()
        ckey = (key, float(cap), float(rate))
        pkey = (tenant, float(tcap), float(trate))
        c_ref = self._hier_refill(ckey, cap, rate, now)
        p_ref = self._hier_refill(pkey, tcap, trate, now)
        granted = c_ref >= count and p_ref >= count
        spend = count if granted else 0
        self._buckets[ckey] = (c_ref - spend, now)
        self._buckets[pkey] = (p_ref - spend, now)
        if self._dirty is not None:
            self._dirty.add(ckey)
            self._dirty.add(pkey)
        return AcquireResult(granted,
                             min(c_ref - spend, p_ref - spend))

    async def acquire_hierarchical(self, tenant, key, count,
                                   tenant_capacity,
                                   tenant_fill_rate_per_sec, capacity,
                                   fill_rate_per_sec, *, priority=0):
        await self.connect()
        return self._hier_core(tenant, key, int(count), tenant_capacity,
                               tenant_fill_rate_per_sec, capacity,
                               fill_rate_per_sec)

    def acquire_hierarchical_blocking(self, tenant, key, count,
                                      tenant_capacity,
                                      tenant_fill_rate_per_sec, capacity,
                                      fill_rate_per_sec, *, priority=0):
        return self._hier_core(tenant, key, int(count), tenant_capacity,
                               tenant_fill_rate_per_sec, capacity,
                               fill_rate_per_sec)

    async def acquire_hierarchical_many(self, tenants, keys, counts,
                                        tenant_capacity,
                                        tenant_fill_rate_per_sec,
                                        capacity, fill_rate_per_sec, *,
                                        with_remaining: bool = True,
                                        priority: int = 0):
        """Serial-core bulk: one in-order pass, no per-row coroutine —
        the per-row cost stays within 2× of the flat serial core (one
        extra dict round per row), which is the llm_workload bench's
        hierarchical-overhead contract on the in-memory backing."""
        await self.connect()
        return self.acquire_hierarchical_many_blocking(
            tenants, keys, counts, tenant_capacity,
            tenant_fill_rate_per_sec, capacity, fill_rate_per_sec,
            with_remaining=with_remaining, priority=priority)

    def acquire_hierarchical_many_blocking(self, tenants, keys, counts,
                                           tenant_capacity,
                                           tenant_fill_rate_per_sec,
                                           capacity, fill_rate_per_sec,
                                           *, with_remaining: bool = True,
                                           priority: int = 0):
        n = len(keys)
        granted = np.empty(n, bool)
        remaining = np.empty(n, np.float32) if with_remaining else None
        core = self._hier_core
        for i in range(n):
            r = core(tenants[i], keys[i], int(counts[i]),
                     tenant_capacity, tenant_fill_rate_per_sec,
                     capacity, fill_rate_per_sec)
            granted[i] = r.granted
            if remaining is not None:
                remaining[i] = r.remaining
        return BulkAcquireResult(granted, remaining)

    async def sync_counter(self, key, local_count, decay_rate_per_sec):
        return self.sync_counter_blocking(key, local_count, decay_rate_per_sec)

    def sync_counter_blocking(self, key, local_count, decay_rate_per_sec):
        now = self.clock.now_ticks()
        rate = _rate_per_tick(decay_rate_per_sec)
        entry = self._counters.get(key)
        if entry is None:
            v, p = float(local_count), float(now)
        else:
            v0, p0, ts = entry
            delta = max(0, now - ts)
            v = max(0.0, v0 - delta * rate) + local_count
            p = (1 - bm.PERIOD_EWMA_ALPHA) * p0 + bm.PERIOD_EWMA_ALPHA * delta
        self._counters[key] = (v, p, now)
        if self._dirty is not None:
            self._dirty.add(key)
        return SyncResult(v, p)

    async def concurrency_acquire(self, key, count, limit):
        return self.concurrency_acquire_blocking(key, count, limit)

    def concurrency_acquire_blocking(self, key, count, limit):
        active = self._semas.get(key, 0)
        if active + count <= limit:
            if count > 0:  # count == 0 is a read-only probe
                self._semas[key] = active + count
                if self._dirty is not None:
                    self._dirty.add(key)
            return AcquireResult(True, float(active + count))
        return AcquireResult(False, float(active))

    async def concurrency_release(self, key, count):
        self.concurrency_release_blocking(key, count)

    def concurrency_release_blocking(self, key, count):
        if key not in self._semas:
            return  # unknown key: nothing to release, create nothing
        self._semas[key] = max(0, self._semas[key] - count)
        if self._dirty is not None:
            self._dirty.add(key)

    async def window_acquire(self, key, count, limit, window_sec):
        return self.window_acquire_blocking(key, count, limit, window_sec)

    def window_acquire_blocking(self, key, count, limit, window_sec):
        return self._window_core(key, count, limit, window_sec,
                                 interpolate=True)

    async def fixed_window_acquire(self, key, count, limit, window_sec):
        return self._window_core(key, count, limit, window_sec,
                                 interpolate=False)

    def fixed_window_acquire_blocking(self, key, count, limit, window_sec):
        return self._window_core(key, count, limit, window_sec,
                                 interpolate=False)

    def _window_core(self, key, count, limit, window_sec, *, interpolate):
        now = self.clock.now_ticks()
        wt = int(window_sec * bm.TICKS_PER_SECOND)
        wkey = (key, float(limit), wt, interpolate)
        entry = self._windows.get(wkey)
        idx_now = now // wt
        if entry is None:
            prev = curr = 0.0
        else:
            prev, curr, idx = entry
            steps = idx_now - idx
            if steps == 1:
                prev, curr = curr, 0.0
            elif steps >= 2:
                prev = curr = 0.0
        if interpolate:
            frac = (now - idx_now * wt) / wt
            est = curr + prev * (1.0 - frac)
        else:
            est = curr
        granted = est + count <= limit
        if granted:
            curr += count
        self._windows[wkey] = (prev, curr, idx_now)
        if self._dirty is not None:
            self._dirty.add(wkey)
        return AcquireResult(granted, max(0.0, limit - est - (count if granted else 0)))

    async def aclose(self) -> None:
        pass

    async def import_entries(self, entries: dict) -> int:
        """Exact merge lane for migration handoffs (the generic replay
        in :func:`placement.import_entries` is for stores whose state
        only the kernels can write). Conservative on collisions — a
        re-pushed batch or pre-existing local state must never inflate
        a budget: buckets keep the smaller balance, windows sum their
        counts (clamped to the limit), counters and semaphores keep the
        larger value."""
        now = self.clock.now_ticks()
        n = 0
        for key, cap, rate, tokens, age in entries.get("buckets", ()):
            bkey = (key, float(cap), float(rate))
            ts = now - int(age)
            entry = self._buckets.get(bkey)
            if entry is None:
                self._buckets[bkey] = (float(tokens), ts)
            else:
                self._buckets[bkey] = (min(entry[0], float(tokens)),
                                       max(entry[1], ts))
            if self._dirty is not None:
                self._dirty.add(bkey)
            n += 1
        for key, limit, wt, interp, prev, curr, behind in \
                entries.get("windows", ()):
            wkey = (key, float(limit), int(wt), bool(interp))
            idx = now // int(wt) - int(behind)
            entry = self._windows.get(wkey)
            if entry is None or entry[2] < idx:
                # no local state, or the LOCAL entry is the stale one
                # (an earlier epoch's leftovers): the push wins outright
                self._windows[wkey] = (float(prev), float(curr), idx)
            elif entry[2] == idx:
                self._windows[wkey] = (
                    min(float(limit), entry[0] + float(prev)),
                    min(float(limit), entry[1] + float(curr)), idx)
            # a stale PUSHED window (older idx) carries no usage to keep
            if self._dirty is not None:
                self._dirty.add(wkey)
            n += 1
        for key, value, period, age in entries.get("counters", ()):
            entry = self._counters.get(key)
            if entry is None or entry[0] < value:
                self._counters[key] = (float(value), float(period),
                                       now - int(age))
            if self._dirty is not None:
                self._dirty.add(key)
            n += 1
        for key, active in entries.get("semas", ()):
            self._semas[key] = max(self._semas.get(key, 0), int(active))
            if self._dirty is not None:
                self._dirty.add(key)
            n += 1
        return n

    # -- dirty accounting (incremental checkpoints; OPERATIONS.md §10) ------
    def enable_dirty_tracking(self) -> None:
        """Arm exact per-entry dirty accounting (the device store's
        counterpart tracks slots): between two saves every written entry
        key is counted, so OP_STATS reports the dirty/total ratio that
        predicts the next v4 delta's size. Observability only — the
        delta is a structural diff either way."""
        if self._dirty is None:
            self._dirty = set()

    def mark_snapshot_base(self) -> None:
        if self._dirty is not None:
            self._dirty.clear()

    def dirty_stats(self) -> dict:
        return {"dirty": len(self._dirty or ()),
                "total": (len(self._buckets) + len(self._windows)
                          + len(self._counters) + len(self._semas))}

    def snapshot(self) -> dict:
        return {
            "now_ticks": self.clock.now_ticks(),
            "buckets": dict(self._buckets),
            "counters": dict(self._counters),
            "windows": dict(self._windows),
            "semas": dict(self._semas),
        }

    def restore(self, snap: dict) -> None:
        """Same clock-epoch re-alignment as the device store: stored
        timestamps shift by ``now_here − now_at_snapshot`` so elapsed time
        (refill/decay) survives a restore into a fresh process."""
        # Snapshots from before the epoch field behave as same-process.
        shift = (int(self.clock.now_ticks()) - int(snap["now_ticks"])
                 if "now_ticks" in snap else 0)
        self._buckets = {
            k: (tokens, ts + shift)
            for k, (tokens, ts) in snap["buckets"].items()
        }
        self._counters = {
            k: (v, p, ts + shift)
            for k, (v, p, ts) in snap["counters"].items()
        }
        # Pre-fixed-window snapshots carry 3-tuple window keys (sliding);
        # normalize to the 4-tuple (key, limit, wt, interpolate=True).
        self._windows = {
            (k if len(k) == 4 else (*k, True)): (prev, curr, idx + shift // k[2])
            for k, (prev, curr, idx) in snap["windows"].items()
        }
        self._semas = dict(snap.get("semas", {}))  # counts are epoch-free
