"""Python half of the native serving front-end (``native/frontend.cc``).

The C++ side owns the sockets: an epoll IO thread accepts connections,
parses the v4 wire protocol, answers PING itself, accumulates per-request
ACQUIRE/WINDOW/FWINDOW frames into micro-batches (timerfd deadline +
flush-on-idle + max-batch, mirroring :class:`~.batcher.MicroBatcher`'s
policy), and encodes/writes every reply natively. This module is the
*decision* half: a pump thread blocks in ``fe_wait`` (GIL released) and
dispatches each batch onto the server's asyncio loop as ONE store bulk
call — so Python cost is per-flush, not per-request. The hot set is the
four per-request decision ops — ACQUIRE, WINDOW, FWINDOW, and SEMA
(signed-delta semaphore rows batch into ``concurrency_acquire_many``) —
plus, since round 8, OP_ACQUIRE_MANY: bulk frames parse, tier-0-decide,
and encode RESP_BULK in C, and only the residue rows cross here as one
zero-copy KeyBlob batch (``_serve_bulk``). Non-hot ops (HELLO, PEEK,
SYNC, STATS, SAVE, control ops, …) and MALFORMED bulk frames arrive as
passthrough frames and are served by the same
:class:`~.server.BucketStoreServer` handler the asyncio path uses;
:mod:`~.wire` stays the single protocol authority for those shapes.

Why this exists: the per-request serving ceiling of the asyncio socket
path was ~13K req/s/core even with a zero-cost kernel on a builder's
CPU host — per-request framing plus task scheduling (not measured on
the chip's host). The reference's answer to that
class of cost is the Redis *server* — a C epoll loop. This is that
component for the TPU store.
"""

from __future__ import annotations

import asyncio
import ctypes
import logging
import threading
import time
from dataclasses import dataclass

import numpy as np

from distributedratelimiting.redis_tpu.runtime import (
    liveconfig,
    placement,
    wire,
)
from distributedratelimiting.redis_tpu.runtime.store import BucketStore
from distributedratelimiting.redis_tpu.utils import faults, log, tracing
from distributedratelimiting.redis_tpu.utils.metrics import (
    LatencyHistogram,
    Tier0Metrics,
)
from distributedratelimiting.redis_tpu.utils.native import (
    URING_OFF,
    URING_ON,
    URING_SQPOLL,
    load_frontend_lib,
)

__all__ = ["NativeFrontend", "Tier0Config", "native_loadgen",
           "native_bulk_loadgen", "uring_probe"]

logger = logging.getLogger(__name__)

#: Accepted spellings of the uring knob (constructor param, env var,
#: CLI) → fe_start_sharded2 transport mode. The C side accepts the same
#: strings in DRL_TPU_URING (uring_mode_from_env) so the two resolution
#: paths can never disagree on a spelling.
_URING_SPELLINGS = {
    "": URING_OFF, "0": URING_OFF, "off": URING_OFF,
    "1": URING_ON, "on": URING_ON, "uring": URING_ON,
    "2": URING_SQPOLL, "sqpoll": URING_SQPOLL,
}


def _resolve_uring_mode(uring: "str | bool | int | None") -> int:
    """Constructor/CLI knob → transport mode. ``None`` defers to the
    ``DRL_TPU_URING`` env var (off when unset) — the conservative
    default that keeps every existing caller on the epoll lane unless
    the operator opts in."""
    import os

    if uring is None:
        uring = os.environ.get("DRL_TPU_URING", "")
    if isinstance(uring, bool):
        return URING_ON if uring else URING_OFF
    if isinstance(uring, int):
        if uring not in (URING_OFF, URING_ON, URING_SQPOLL):
            raise ValueError(f"unknown uring mode {uring!r}")
        return uring
    key = str(uring).strip().lower()
    if key not in _URING_SPELLINGS:
        raise ValueError(
            f"unknown uring mode {uring!r}; use off/on/sqpoll")
    return _URING_SPELLINGS[key]


def uring_probe() -> tuple[bool, str]:
    """Runtime io_uring availability: ``(available, reason)``. Reason is
    human-readable either way (the probe's success string names the
    feature level it verified; failure names the refusing syscall or
    gate — OPERATIONS.md §17 shows the table)."""
    lib = load_frontend_lib()
    if lib is None or not getattr(lib, "has_uring", False):
        return False, ("native front-end library unavailable or "
                       "predates the uring ABI")
    buf = ctypes.create_string_buffer(256)
    ok = lib.fe_uring_probe(buf, len(buf))
    return bool(ok), buf.value.decode("utf-8", "replace")


@dataclass(frozen=True)
class Tier0Config:
    """Knobs of the front-end's tier-0 admission cache (see
    docs/OPERATIONS.md "Tier-0 approximate admission" for the decision
    table and the epsilon derivation). The budget policy mirrors
    :func:`~.models.approximate.headroom_budget`; the documented
    over-admission bound per key is
    ``overadmit_epsilon(headroom_budget(capacity, ...),
    fill_rate, sync_interval_s)``."""

    #: Replica table slots PER SHARD SLICE (rounded up to a power of
    #: two; any shard can see any key, so each slice is full-size).
    #: Memory is bounded: shards × slots × (entry + key ≤ 256 B) —
    #: ~1.5 MB per shard at the default.
    slots: int = 4096
    #: Fraction of the last-synced balance granted as local headroom.
    budget_fraction: float = 0.5
    #: Below this budget a key is not hosted locally (small buckets keep
    #: exact per-request semantics — also what keeps tier-0 semantically
    #: invisible to low-capacity workloads like the parity fuzz).
    min_budget: float = 64.0
    #: Budget ceiling (bounds epsilon for huge-capacity buckets).
    max_budget: float = float(1 << 20)
    #: Sync pump cadence: how often local grants drain into the store.
    sync_interval_s: float = 0.02
    #: Max age of the envelope a local decision may be served from.
    #: Generous relative to the sync interval on purpose: during a device
    #: outage this is how long tier-0 keeps answering from its last-known
    #: envelope instead of stalling behind the dead store.
    max_stale_s: float = 2.0
    #: Idle replica eviction.
    ttl_s: float = 30.0

# Bound to locals for the batch-group dispatch; wire.py stays the single
# source of the values (frontend.cc mirrors them and is covered by the
# protocol-parity tests).
_OP_BUCKET = wire.OP_ACQUIRE
_OP_WINDOW = wire.OP_WINDOW
_OP_FWINDOW = wire.OP_FWINDOW
_OP_SEMA = wire.OP_SEMA

#: fe_complete's kRowSkip sentinel (frontend.cc): the row was already
#: answered from Python via fe_send (per-row placement error on the
#: batch lane) — C sends no decision reply and skips the tier-0 install.
_ROW_SKIP = 2


class NativeFrontend:
    """Own the C++ listener for a :class:`~.server.BucketStoreServer`.

    Lifecycle: constructed inside ``server.start()`` on the running loop;
    ``aclose()`` (from ``server.aclose()``) stops the IO thread, fails the
    pump out of its wait, and frees the handle.
    """

    def __init__(self, server, *, host: str, port: int,
                 max_batch: int = 4096, deadline_us: int = 300,
                 tier0: "Tier0Config | bool | None" = None,
                 bulk: bool = True, shards: int = 1,
                 pin_shards: bool = False,
                 uring: "str | bool | int | None" = None) -> None:
        lib = load_frontend_lib()
        if lib is None:
            raise RuntimeError(
                "native front-end unavailable (no compiler or "
                "DRL_TPU_NO_NATIVE=1) — use the asyncio server")
        self._lib = lib
        self._server = server
        self._loop = asyncio.get_running_loop()
        # The C side binds numeric IPv4 only — resolve names here so
        # --host localhost works exactly like the asyncio listener.
        # (IPv6 listeners are asyncio-path-only for now.)
        import socket

        try:
            infos = socket.getaddrinfo(host, port, socket.AF_INET,
                                       socket.SOCK_STREAM)
            numeric_host = infos[0][4][0]
        except socket.gaierror as exc:
            raise OSError(
                f"native front-end cannot resolve {host!r} as IPv4: {exc}"
            ) from exc
        # Multi-shard serving (round 11): N epoll shards accepting on
        # SO_REUSEPORT listeners bound to ONE port, each with its own IO
        # thread, connection table, and pump thread below; the tier-0
        # replica table is partitioned by key hash so the node keeps ONE
        # envelope (docs/DESIGN.md §16). A stale .so without the shard
        # ABI serves single-shard — availability over scale, loudly.
        shards = max(1, int(shards))
        has_shards = getattr(lib, "has_shards", False)
        if shards > 1 and not has_shards:
            logger.warning(
                "native front-end shards=%d requested but the loaded "
                "binary predates the shard ABI; serving single-shard",
                shards)
            shards = 1
        # io_uring transport (round 16): the data plane swaps under the
        # same reply bytes — DESIGN.md §21. Default is epoll unless the
        # knob (param > DRL_TPU_URING env > off) asks otherwise; a
        # stale .so or failed runtime probe falls back loudly, never
        # fails the bind (availability over throughput, same posture as
        # the shard fallback above).
        mode = _resolve_uring_mode(uring)
        has_uring = getattr(lib, "has_uring", False)
        if mode != URING_OFF and not has_uring:
            logger.warning(
                "io_uring transport requested but the loaded binary "
                "predates the uring ABI; serving on epoll")
            mode = URING_OFF
        self.uring_mode = mode
        if has_uring:
            self._h = lib.fe_start_sharded2(
                numeric_host.encode(), port, max_batch, deadline_us,
                1 if server.auth_token is not None else 0, shards,
                1 if pin_shards else 0, mode)
        elif has_shards:
            self._h = lib.fe_start_sharded(
                numeric_host.encode(), port, max_batch, deadline_us,
                1 if server.auth_token is not None else 0, shards,
                1 if pin_shards else 0)
        else:
            self._h = lib.fe_start(
                numeric_host.encode(), port, max_batch, deadline_us,
                1 if server.auth_token is not None else 0)
        if not self._h:
            raise OSError(f"native front-end failed to bind {host}:{port}")
        if mode != URING_OFF:
            # Per-shard fallback is graceful but never silent: name
            # every shard that could not get a ring and why.
            n_uring = int(lib.fe_uring_shards(self._h))
            n_total = int(lib.fe_shard_count(self._h)) if has_shards else 1
            if n_uring < n_total:
                buf = ctypes.create_string_buffer(256)
                for i in range(n_total):
                    if lib.fe_uring_reason(self._h, i, buf,
                                           len(buf)) == 0:
                        logger.warning(
                            "io_uring requested but shard %d fell back "
                            "to epoll: %s", i,
                            buf.value.decode("utf-8", "replace")
                            or "no reason recorded")
            self.uring_shards = n_uring
        else:
            self.uring_shards = 0
        self.port = lib.fe_port(self._h)
        self.host = host
        self._stopping = False
        # Per-shard sub-handles: every fe_* call a pump or serve task
        # makes goes through the shard handle its work arrived on (conn
        # ids, batch ids, and bulk job ids are all per-shard).
        if has_shards:
            self.n_shards = int(lib.fe_shard_count(self._h))
            self._shards = [int(lib.fe_shard(self._h, i))
                            for i in range(self.n_shards)]
        else:
            self.n_shards = 1
            self._shards = [self._h]
        # Per-(shard, connection) tail task for chained ACQUIRE_MANY
        # chunks — the same request-order contract the asyncio server
        # keeps (server.py `bulk_tail`); a connection lives on one shard
        # for its whole life, so the contract stays shard-local. Entries
        # drop when their task is still the tail at completion, so the
        # dict tracks active bulk conns only.
        self._bulk_tails: dict[tuple[int, int], asyncio.Task] = {}
        # Loop tasks still holding the C handle: aclose must drain these
        # BETWEEN fe_stop (no new work) and fe_free (handle invalid) — a
        # straggler batch completing after fe_free would call
        # fe_complete through a dangling pointer.
        self._loop_tasks: set[asyncio.Task] = set()
        # Tier-0 admission cache: decisions served from the C-side replica
        # table; this side runs the sync pump (harvest → bulk debit →
        # ack) that keeps every replica's envelope honest.
        self._tier0: Tier0Config | None = None
        self._t0_task: asyncio.Task | None = None
        self.t0_metrics = Tier0Metrics()
        # Consecutive failed sync rounds — the degraded-mode streak that
        # trips the server's flight recorder (0 while healthy).
        self._t0_fail_streak = 0
        #: drained-but-unreconciled amounts surviving a failed sync round
        #: (degraded mode: carried into the next round, never dropped).
        self._t0_carry: dict[tuple[str, float, float], float] = {}
        if tier0:
            self._tier0_setup(
                tier0 if isinstance(tier0, Tier0Config) else Tier0Config())
        # Native bulk lane (round 8): OP_ACQUIRE_MANY parses, tier-0
        # decides hot rows, and RESP_BULK encodes in C; only the residue
        # crosses here (fe_wait kind 3). Armed explicitly — a new .so
        # under an older pump keeps the round-7 passthrough behavior,
        # and a stale .so under this pump falls back the same way.
        self._bulk_native = bool(bulk) and getattr(lib, "has_bulk", False)
        if bulk and not self._bulk_native:
            logger.warning(
                "native bulk lane requested but the loaded front-end "
                "binary predates the fe_bulk ABI; ACQUIRE_MANY stays on "
                "the passthrough lane")
        self._hot_task: asyncio.Task | None = None
        # The fe_handoff stage (C stamp -> the loop's first line), kept
        # in Python beside the C side's native_queue/native_exec.
        self.handoff_latency = LatencyHistogram()
        self._stage_stamps = getattr(lib, "has_stage_stamps", False)
        if self._bulk_native:
            hh = getattr(server, "heavy_hitters", None)
            # One call arms the lane on EVERY shard (the C side fans
            # out) — a frame can never race a half-armed shard mix.
            lib.fe_bulk_configure(self._h, 1, 1, 1 if hh is not None
                                  else 0)
            if hh is not None:
                # The bulk lane's keys never materialize in Python, so
                # the C side aggregates per-frame top-K and this pump
                # offers the survivors to the sketch (the scalar batch
                # lane's offer_many discipline, re-hosted below the
                # ABI). One pump drains every shard's ring, so the
                # sketch keeps whole-node ranks.
                self._hot_task = asyncio.get_running_loop().create_task(
                    self._hot_harvest_loop())
        # One pump thread per shard: fe_wait and its batch/bulk/pt
        # cursors are per-shard state, so each pump drives exactly one
        # shard and the shards never contend a shared C queue.
        self._pumps = [
            threading.Thread(target=self._pump_loop, args=(sh,),
                             daemon=True,
                             name=f"native-frontend-pump-{i}")
            for i, sh in enumerate(self._shards)
        ]
        for p in self._pumps:
            p.start()

    def _tier0_setup(self, cfg: Tier0Config) -> None:
        if not getattr(self._lib, "has_tier0", False):
            logger.warning("tier-0 requested but the loaded front-end "
                           "binary predates the tier-0 ABI; serving "
                           "without it")
            return
        store = self._server.store
        if type(store).debit_many is BucketStore.debit_many:
            # No reconciliation entry point on this store: local grants
            # could never drain back, so the envelope would be a lie.
            logger.warning(
                "tier-0 requested but %s has no debit_many "
                "reconciliation path; serving without tier-0",
                type(store).__name__)
            return
        slots = self._lib.fe_t0_configure(
            self._h, int(cfg.slots), float(cfg.budget_fraction),
            float(cfg.min_budget), float(cfg.max_budget),
            max(1, int(cfg.max_stale_s * 1e3)),
            max(1, int(cfg.ttl_s * 1e3)))
        self._tier0 = cfg
        # Harvest buffers, allocated once: sized so even a full table of
        # max-length (256 B) keys drains in one round, and the pump's
        # per-round cost is the C call, not buffer churn.
        self._t0_blob = ctypes.create_string_buffer(slots * 256)
        self._t0_klens = np.zeros(slots, np.int32)
        self._t0_amounts = np.zeros(slots, np.float64)
        self._t0_caps = np.zeros(slots, np.float64)
        self._t0_rates = np.zeros(slots, np.float64)
        self._t0_task = asyncio.get_running_loop().create_task(
            self._t0_sync_loop())

    def _track_task(self, coro) -> asyncio.Task:
        """Start ``coro`` as a loop task tracked for shutdown draining
        (every task holding the C handle must finish before fe_free).
        Loop-thread only."""
        # Loop-thread only: the pump thread reaches this exclusively
        # through call_soon_threadsafe (_track).
        # drl-check: ok(task-off-loop)
        task = asyncio.ensure_future(coro)
        self._loop_tasks.add(task)
        task.add_done_callback(self._loop_tasks.discard)
        return task

    def _track(self, coro) -> None:
        """Schedule ``coro`` on the loop from the pump thread, tracked
        for shutdown draining."""
        self._loop.call_soon_threadsafe(self._track_task, coro)

    # -- pump thread -------------------------------------------------------

    def _pump_loop(self, sh: int) -> None:
        """One shard's pump: blocks in fe_wait (GIL released) on the
        SHARD handle and dispatches that shard's work onto the loop."""
        lib = self._lib
        while not self._stopping:
            kind = lib.fe_wait(sh, 200)
            if kind == -1:
                break
            try:
                if kind == 1:
                    with tracing.annotate("drl/fe.pump"):
                        self._dispatch_batch(sh)
                elif kind == 2:
                    self._dispatch_passthrough(sh)
                elif kind == 3:
                    with tracing.annotate("drl/fe.pump"):
                        self._dispatch_bulk(sh)
            except Exception as exc:  # noqa: BLE001 — the pump is the one
                # thread every connection on its shard depends on: it
                # must survive any single bad batch/frame (the items get
                # error replies via fe_fail where possible; the
                # connections stay up).
                log.error_evaluating_kernel(exc)
                if kind == 1:
                    try:
                        self._lib.fe_fail(sh, self._lib.fe_batch_id(sh),
                                          repr(exc)[:200].encode())
                    # the batch failure above was already logged;
                    # fe_fail itself dying adds nothing
                    # drl-check: ok(swallowed-exception)
                    except Exception:  # noqa: BLE001
                        pass
                elif kind == 3:
                    try:
                        self._lib.fe_bulk_fail(
                            sh, self._lib.fe_bulk_id(sh),
                            repr(exc)[:200].encode())
                    # same posture as fe_fail above
                    # drl-check: ok(swallowed-exception)
                    except Exception:  # noqa: BLE001
                        pass

    def _dispatch_batch(self, sh: int) -> None:
        lib, h = self._lib, sh
        bid = lib.fe_batch_id(h)
        n = lib.fe_batch_n(h)
        if n <= 0:
            return
        t_cut_ns = lib.fe_batch_t_cut_ns(h) if self._stage_stamps else 0
        kb = lib.fe_batch_key_bytes(h)
        blob = ctypes.create_string_buffer(max(int(kb), 1))
        klens = np.empty(n, np.int32)
        counts = np.empty(n, np.int32)
        ops = np.empty(n, np.uint8)
        seqs = np.empty(n, np.uint32)
        conn_ids = np.empty(n, np.uint64)
        a_arr = np.empty(n, np.float64)
        b_arr = np.empty(n, np.float64)
        c = ctypes
        lib.fe_batch_copy(
            h, blob,
            klens.ctypes.data_as(c.POINTER(c.c_int32)),
            counts.ctypes.data_as(c.POINTER(c.c_int32)),
            ops.ctypes.data_as(c.POINTER(c.c_uint8)),
            seqs.ctypes.data_as(c.POINTER(c.c_uint32)),
            conn_ids.ctypes.data_as(c.POINTER(c.c_uint64)),
            a_arr.ctypes.data_as(c.POINTER(c.c_double)),
            b_arr.ctypes.data_as(c.POINTER(c.c_double)))
        # Decode keys off-loop (the pump has idle time while the loop
        # runs store calls); ascii fast path matches wire.py's.
        # surrogateescape: wire keys are bytes; invalid UTF-8 still maps
        # 1:1 to a stable str key, so a hostile/corrupt key rate-limits
        # under its own identity instead of poisoning its whole batch.
        keys = wire.decode_key_blob(blob.raw[:int(kb)], klens,
                                    errors="surrogateescape")
        traces = None
        if (getattr(lib, "has_trace", False)
                and tracing.get_tracer().enabled
                and lib.fe_batch_traced_n(h) > 0):
            # Trace contexts ride as parallel arrays (flag bit 0 marks
            # traced rows) — feature-detected like fe_stage_hist, so a
            # stale binary just serves untraced. The traced_n gate keeps
            # the common all-untraced batch (at 1% head sampling, ~99%
            # of them) at one C int call, no allocations.
            tr_hi = np.zeros(n, np.uint64)
            tr_lo = np.zeros(n, np.uint64)
            tr_par = np.zeros(n, np.uint64)
            tr_fl = np.zeros(n, np.uint8)
            lib.fe_batch_traces(
                h, tr_hi.ctypes.data_as(c.POINTER(c.c_uint64)),
                tr_lo.ctypes.data_as(c.POINTER(c.c_uint64)),
                tr_par.ctypes.data_as(c.POINTER(c.c_uint64)),
                tr_fl.ctypes.data_as(c.POINTER(c.c_uint8)))
            traces = (tr_hi, tr_lo, tr_par, tr_fl)
        self._track(self._serve_batch(sh, bid, keys, counts, ops, a_arr,
                                      b_arr, traces, seqs, conn_ids,
                                      t_cut_ns))

    def _dispatch_passthrough(self, sh: int) -> None:
        lib, h = self._lib, sh
        conn_id = lib.fe_pt_conn(h)
        ln = lib.fe_pt_len(h)
        buf = ctypes.create_string_buffer(max(ln, 1))
        lib.fe_pt_copy(h, buf)
        body = buf.raw[:ln]
        self._track(self._serve_passthrough(sh, int(conn_id), body))

    def _dispatch_bulk(self, sh: int) -> None:
        """Hand one bulk residue job to the loop. The key blob, offsets,
        counts, and residue arrays are ZERO-COPY views into the C-held
        job (the ``wire.KeyBlob`` → ``dir_resolve_batch`` lane on the
        Python side): valid until fe_bulk_complete/discard/fail erases
        the job, which only ``_serve_bulk`` does — after its last read."""
        lib, h = self._lib, sh
        c = ctypes
        u = np.zeros(11, np.uint64)
        f = np.zeros(2, np.float64)
        lib.fe_bulk_meta(h, u.ctypes.data_as(c.POINTER(c.c_uint64)),
                         f.ctypes.data_as(c.POINTER(c.c_double)))
        jid = int(u[0])
        if jid == 0:
            return
        t_arrival_ns = lib.fe_bulk_t_ns(h) if self._stage_stamps else 0
        n, blob_len, res_n = int(u[4]), int(u[5]), int(u[6])
        ptrs = np.zeros(4, np.uint64)
        lib.fe_bulk_ptrs(h, ptrs.ctypes.data_as(c.POINTER(c.c_uint64)))
        # A (c_char × len) view passes anywhere the KeyBlob contract
        # needs it: c_char_p args (dir_resolve_batch, dir_route_batch,
        # dir_fp64_batch) take it directly and slicing yields bytes for
        # the serial stores' lazy per-key decode. No blob copy, no
        # Python strings.
        blob = ((c.c_char * blob_len).from_address(int(ptrs[0]))
                if blob_len else b"")
        offsets = np.ctypeslib.as_array(
            c.cast(int(ptrs[1]), c.POINTER(c.c_int64)), (n + 1,))
        counts = np.ctypeslib.as_array(
            c.cast(int(ptrs[2]), c.POINTER(c.c_int64)), (n,))
        residue = np.ctypeslib.as_array(
            c.cast(int(ptrs[3]), c.POINTER(c.c_int32)), (res_n,))
        tctx = None
        if int(u[10]) & 1 and tracing.get_tracer().enabled:
            tctx = tracing.TraceContext(int(u[7]), int(u[8]), int(u[9]),
                                        1 if int(u[10]) & 2 else 0)
        self._track(self._serve_bulk(
            sh, jid, int(u[1]), int(u[2]), int(u[3]), float(f[0]),
            float(f[1]), wire.KeyBlob(blob, offsets), counts, residue,
            tctx, t_arrival_ns))

    # -- loop-side serving -------------------------------------------------

    async def _serve_batch(self, sh: int, bid: int, keys: list[str],
                           counts: np.ndarray, ops: np.ndarray,
                           a_arr: np.ndarray, b_arr: np.ndarray,
                           traces=None, seqs: np.ndarray | None = None,
                           conn_ids: np.ndarray | None = None,
                           t_cut_ns: int = 0) -> None:
        self._record_handoff(t_cut_ns)
        n = len(keys)
        t_start = time.perf_counter()
        pgate = full = None
        try:
            hh = getattr(self._server, "heavy_hitters", None)
            if hh is not None:
                # Keys are already materialized for the store call; one
                # C-speed Counter pass + a bounded top-2K merge
                # (utils/heavy_hitters.py overhead discipline). Offers
                # are COST-weighted — an N-token acquire weighs N, so
                # the sketch ranks hot-cost keys (the split-candidate
                # feed), not just hot-count keys. Rows with count <= 0
                # (SEMA releases/probes) are not admission demand —
                # filter only when any exist (rare outside semaphore
                # traffic; the mask check is one vector op).
                if (counts <= 0).any():
                    mask = counts > 0
                    hh.offer_many([k for k, keep in zip(keys, mask)
                                   if keep], counts[mask])
                elif int(counts.max(initial=0)) <= 1:
                    # All-unit batch (the overwhelmingly common shape):
                    # weights are identical, keep the Counter fast path.
                    hh.offer_many(keys)
                else:
                    hh.offer_many(keys, counts)
            # Placement gate (runtime/placement.py): the C batch lane
            # must honor keyspace ownership exactly like the asyncio
            # lane's scalar gate. Dormant (None) until a map is
            # announced; mid-handoff rows serve their fair-share
            # envelope, moved rows answer the routable MOVED error and
            # parked rows with no envelope value (SEMA, releases) answer
            # the transient handoff deferral — both pre-encoded here and
            # pushed through fe_send, with the kRowSkip sentinel telling
            # fe_complete those rows are already answered. A stale .so
            # without the row-skip ABI falls back to denying them (deny
            # is admission-safe but strands stale clients and leaks SEMA
            # permits — the loader rebuilds on source change, so the
            # fallback is a transient condition, not a mode).
            ps = self._server.placement
            pgate = ps.bulk_gate(keys) if ps.active else None
            # Config gate (runtime/liveconfig.py): the C batch lane must
            # honor retired configs exactly like the asyncio lane —
            # mirror of the placement-gate treatment above. Dormant (one
            # attribute read) until a rule commits; then rows carrying a
            # retired (op-kind, a, b) are answered per-row with the
            # routable "config moved" error (fe_send + kRowSkip) so the
            # per-request client chases once and re-sends translated.
            # One forward() probe per distinct config in the batch — the
            # overwhelmingly common single-config batch probes once.
            lc = self._server.liveconfig
            cmoved: "list[tuple[int, tuple, tuple]] | None" = None
            if lc.active and n:
                # One forward() probe per DISTINCT config in the batch
                # (numpy grouping — the rules dict stays populated
                # forever once a mutation commits, so this path is
                # steady-state for mutated fleets and must not pay a
                # per-row Python loop on the fast lane). OP_KINDS is
                # THE shared op→kind table; PEEK never rides a batch.
                ckinds = liveconfig.OP_KINDS
                rec = np.empty(n, dtype=[("op", np.uint8),
                                         ("a", np.float64),
                                         ("b", np.float64)])
                rec["op"], rec["a"], rec["b"] = ops, a_arr, b_arr
                uniq, inverse = np.unique(rec, return_inverse=True)
                rows = []
                for gi, u in enumerate(uniq):
                    ck = ckinds.get(int(u["op"]))
                    if ck is None:
                        continue
                    pk = (ck, float(u["a"]), float(u["b"]))
                    fwd = lc.forward(*pk)
                    if fwd is not None:
                        rows.extend((int(i), pk, fwd) for i in
                                    np.nonzero(inverse == gi)[0])
                cmoved = rows or None
            if pgate is not None or cmoved is not None:
                full = (n, keys, counts, ops, a_arr, b_arr)
                serve_mask = (pgate[0].copy() if pgate is not None
                              else np.ones(n, bool))
                if cmoved is not None:
                    for i, _pk, _fwd in cmoved:
                        serve_mask[i] = False
                serve_idx = np.nonzero(serve_mask)[0]
                keys = [keys[int(i)] for i in serve_idx]
                counts, ops = counts[serve_idx], ops[serve_idx]
                a_arr, b_arr = a_arr[serve_idx], b_arr[serve_idx]
                n = len(keys)
            granted = np.zeros(n, np.uint8)
            remaining = np.zeros(n, np.float64)
            # SEMA rows go as ONE store call in arrival order with
            # per-row limits: grouping them by (a, b) like the bucket
            # ops would execute releases (a=0) in a separate group from
            # acquires (a=limit), reordering same-key pipelined
            # acquire→release pairs and leaking held permits.
            sema_mask = ops == _OP_SEMA
            groups: list = []
            if sema_mask.any():
                groups.append((_OP_SEMA, 0.0, 0.0,
                               np.nonzero(sema_mask)[0]))
            rest = np.nonzero(~sema_mask)[0]
            if n and len(rest) == n and ((ops == ops[0]).all()
                                   and (a_arr == a_arr[0]).all()
                                   and (b_arr == b_arr[0]).all()):
                # Single-config fast path: every frame carries the same
                # (op, capacity, rate) — the overwhelmingly common shape
                # (one limiter config per fleet). One bulk call.
                groups = [(int(ops[0]), float(a_arr[0]), float(b_arr[0]),
                           None)]
            elif len(rest):
                rec = np.empty(len(rest), dtype=[("op", np.uint8),
                                                 ("a", np.float64),
                                                 ("b", np.float64)])
                rec["op"] = ops[rest]
                rec["a"] = a_arr[rest]
                rec["b"] = b_arr[rest]
                uniq, inverse = np.unique(rec, return_inverse=True)
                groups.extend(
                    (int(u["op"]), float(u["a"]), float(u["b"]),
                     rest[np.nonzero(inverse == gi)[0]])
                    for gi, u in enumerate(uniq))
            # Elected dispatch span (first traced row): the store-level
            # profiler spans of this batch's bulk calls nest under it,
            # so a native-lane trace decomposes like the asyncio lane's.
            espan = tracing._NULL_SPAN
            if traces is not None:
                tr_hi, tr_lo, tr_par, tr_fl = traces
                idxs = np.nonzero(tr_fl & 1)[0]
                tracer = tracing.get_tracer()
                if len(idxs) and tracer.enabled:
                    i0 = int(idxs[0])
                    espan = tracer.start_span(
                        "fe.dispatch",
                        parent=tracing.TraceContext(
                            int(tr_hi[i0]), int(tr_lo[i0]),
                            int(tr_par[i0]), 1 if tr_fl[i0] & 2 else 0),
                        attrs={"n": n})
            with espan:
                for op, a, b, idx in groups:
                    if idx is None:
                        gkeys, gcounts = keys, counts
                    else:
                        gkeys = [keys[i] for i in idx.tolist()]
                        gcounts = counts[idx]
                    if op == _OP_BUCKET:
                        res = await self._server.store.acquire_many(
                            gkeys, gcounts, a, b, with_remaining=True)
                    elif op == _OP_SEMA:
                        # Signed deltas; each row's `a` carries its permit
                        # limit (releases wire a=0, ignored per-row).
                        res = await self._server.store.concurrency_acquire_many(
                            gkeys, gcounts,
                            a_arr[idx].astype(np.int64))
                    else:
                        res = await self._server.store.window_acquire_many(
                            gkeys, gcounts, a, b,
                            fixed=(op == _OP_FWINDOW),
                            with_remaining=True)
                    g = np.asarray(res.granted, np.uint8)
                    r = (np.zeros(len(gkeys), np.float64)
                         if res.remaining is None
                         else np.asarray(res.remaining, np.float64))
                    if idx is None:
                        granted, remaining = g, r
                    else:
                        granted[idx] = g
                        remaining[idx] = r
            if pgate is not None or cmoved is not None:
                # Scatter the served subset back into the full batch,
                # decide the parked rows from their handoff envelopes,
                # and answer moved / retired-config / non-envelope
                # parked rows per-row.
                n, keys, counts, ops, a_arr, b_arr = full
                g_full = np.zeros(n, np.uint8)
                r_full = np.zeros(n, np.float64)
                g_full[serve_idx] = granted
                r_full[serve_idx] = remaining
                row_skip = (getattr(self._lib, "has_row_skip", False)
                            and seqs is not None and conn_ids is not None)
                ekinds = {_OP_BUCKET: "bucket", _OP_WINDOW: "window",
                          _OP_FWINDOW: "fwindow"}
                if cmoved is not None:
                    for i, pk, fwd in cmoved:
                        if row_skip:
                            # The moved() counter + message — the same
                            # routable error the asyncio lanes answer;
                            # the store was never touched for this row,
                            # so the client's translated re-send is not
                            # a replay.
                            self._send(sh, int(conn_ids[i]),
                                       wire.encode_response(
                                           int(seqs[i]), wire.RESP_ERROR,
                                           lc.moved(pk[0], pk[1], pk[2],
                                                    fwd)))
                            g_full[i] = _ROW_SKIP
                        # Without the row-skip ABI (stale .so — a
                        # transient condition, the loader rebuilds on
                        # source change): deny. Admission-safe; the
                        # stale client converges on its next scalar
                        # call through the asyncio gate.
                for i, handoff in (pgate[1] if pgate is not None else ()):
                    if g_full[i] == _ROW_SKIP:
                        continue  # already answered config-moved
                    ekind = ekinds.get(int(ops[i]))
                    if ekind is not None and counts[i] >= 0:
                        gr, rem = ps.envelope_acquire(
                            handoff, keys[i], int(counts[i]),
                            float(a_arr[i]), float(b_arr[i]), ekind)
                        g_full[i] = gr
                        r_full[i] = rem
                    elif row_skip:
                        # Parked SEMA / release rows have no envelope
                        # value: a denied decision would silently eat a
                        # permit release (leaking held permits for the
                        # migrated semaphore) — answer the same typed
                        # transient error the asyncio lane does so the
                        # caller retries after the window.
                        ps.handoff_deferrals += 1
                        self._send(sh, int(conn_ids[i]),
                                   wire.encode_response(
                            int(seqs[i]), wire.RESP_ERROR,
                            f"{placement.HANDOFF_DEFERRAL_PREFIX} for "
                            f"this key (target epoch "
                            f"{handoff.target_epoch}); retry shortly"))
                        g_full[i] = _ROW_SKIP
                if row_skip and pgate is not None and pgate[2].any():
                    # Moved rows answer the routable MOVED error — the
                    # signal the client's chase / background refresh
                    # converges on (bulk_gate already counted them).
                    for i in np.nonzero(pgate[2])[0].tolist():
                        if g_full[i] == _ROW_SKIP:
                            continue  # already answered config-moved
                        self._send(sh, int(conn_ids[i]),
                                   wire.encode_response(
                            int(seqs[i]), wire.RESP_ERROR,
                            ps.moved_message(
                                keys[i],
                                int(ps.pmap.node_of(keys[i])))))
                        g_full[i] = _ROW_SKIP
                granted, remaining = g_full, r_full
            with tracing.annotate("drl/fe.complete"):
                if traces is not None:
                    self._record_batch_spans(traces, granted, ops, t_start)
                c = ctypes
                self._lib.fe_complete(
                    sh, bid,
                    np.ascontiguousarray(granted).ctypes.data_as(
                        c.POINTER(c.c_uint8)),
                    np.ascontiguousarray(remaining).ctypes.data_as(
                        c.POINTER(c.c_double)))
        except Exception as exc:  # noqa: BLE001 — every request must get
            log.error_evaluating_kernel(exc)  # a routable error reply
            if traces is not None:
                # The gates slice `ops` to the served subset; the trace
                # arrays are full-batch, so restore the full ops before
                # attributing error spans.
                self._record_batch_spans(
                    traces, None, ops if full is None else full[3],
                    t_start)
            self._lib.fe_fail(sh, bid, repr(exc)[:200].encode())

    def _record_handoff(self, t0_ns: int) -> None:
        """The ``fe_handoff`` stage: from the C side's stamp (batch cut,
        bulk arrival; 0 from a binary without the stamp accessors) to
        this first line on the loop — the pump's wake-up, copy and key
        decode, and the loop's queue. Both clocks are CLOCK_MONOTONIC."""
        if t0_ns:
            self.handoff_latency.record(
                (time.perf_counter_ns() - t0_ns) * 1e-9)

    def _record_batch_spans(self, traces, granted, ops: np.ndarray,
                            t_start: float) -> None:
        """One ``fe.batch`` span per traced row of a native micro-batch,
        parented on the row's wire context (the sampled minority — rows
        without the trace flag cost nothing here). ``granted=None``
        marks the whole batch errored."""
        tracer = tracing.get_tracer()
        if not tracer.enabled:
            return
        tr_hi, tr_lo, tr_par, tr_fl = traces
        t_end = time.perf_counter()
        for i in np.nonzero(tr_fl & 1)[0].tolist():
            if i >= len(ops):
                # Defensive bound only: both call sites hand the
                # full-batch ops (the error path restores them after
                # the placement gate's subset slice).
                break
            ctx = tracing.TraceContext(int(tr_hi[i]), int(tr_lo[i]),
                                       int(tr_par[i]),
                                       1 if tr_fl[i] & 2 else 0)
            if granted is None or granted[i] == _ROW_SKIP:
                # Whole-batch failure, or a row pre-answered with a
                # per-row placement error (MOVED / handoff deferral).
                status = "error"
            else:
                status = "ok" if granted[i] else "denied"
            tracer.record_span(
                "fe.batch", ctx, t_start, t_end, status=status,
                attrs={"op": wire.op_name(int(ops[i]))})

    async def _serve_bulk(self, sh: int, jid: int, conn_id: int,
                          seq: int, flags: int, a: float, b: float,
                          keys: "wire.KeyBlob", counts: np.ndarray,
                          residue: np.ndarray, tctx=None,
                          t_arrival_ns: int = 0) -> None:
        """Loop half of the native bulk lane: decide the residue rows
        the C side could not (cold keys, windows, probes), mirroring the
        asyncio server's ACQUIRE_MANY branch gate for gate — config,
        drain, placement, in that order — so the two lanes stay
        reply-for-reply identical; then ``fe_bulk_complete`` merges the
        verdicts and encodes RESP_BULK in C. Frame-level gate errors are
        answered via fe_send + fe_bulk_discard (the kRowSkip posture,
        whole-frame edition). Rows tier-0 already granted in a frame
        that then hits a gate stay debited through the sync/retire lane
        — the documented ≤-one-interval epsilon family, same as the
        scalar lanes' commit races."""
        self._record_handoff(t_arrival_ns)
        srv = self._server
        lib, h = self._lib, sh
        n = len(keys)
        try:
            # wire.py stays the single layout authority for the flags
            # byte (the C mirror is drl-check-diffed; a third hand-coded
            # copy here would sit outside that conformance net).
            with_rem = bool(flags & wire._FLAG_WITH_REMAINING)
            kind = (flags & wire._KIND_MASK) >> wire._KIND_SHIFT
            ckind = liveconfig.BULK_KINDS.get(kind)
            lc = srv.liveconfig
            if lc.active and ckind is not None:
                fwd = lc.forward(ckind, a, b)
                if fwd is not None:
                    # Retired config: the frame-level routable moved
                    # error, byte-identical to the asyncio gate (no
                    # residue row was applied, so the translated
                    # re-send is not a replay).
                    self._send(sh, conn_id, wire.encode_response(
                        seq, wire.RESP_ERROR,
                        lc.moved(ckind, a, b, fwd)))
                    lib.fe_bulk_discard(h, jid)
                    return
            env = srv._drain_envelope
            if env is not None:
                resp = srv._serve_bulk_draining(seq, keys, counts, a, b,
                                                with_rem, kind, env)
                self._send(sh, conn_id, resp)
                lib.fe_bulk_discard(h, jid)
                return
            gate = (srv.placement.bulk_gate(keys)
                    if srv.placement.active else None)
            if gate is not None and gate[2].any():
                # Misrouted rows: frame-level moved error (the asyncio
                # lane's posture — a bulk-only client needs the refresh
                # trigger; no row was applied).
                i = int(np.nonzero(gate[2])[0][0])
                key = keys[i]
                self._send(sh, conn_id, wire.encode_response(
                    seq, wire.RESP_ERROR, srv.placement.moved_message(
                        key, int(srv.placement.pmap.node_of(key)))))
                lib.fe_bulk_discard(h, jid)
                return
            rn = len(residue)
            granted = np.zeros(rn, np.uint8)
            remaining = np.zeros(rn, np.float64)
            espan = tracing._NULL_SPAN
            if tctx is not None:
                tracer = tracing.get_tracer()
                if tracer.enabled:
                    espan = tracer.start_span(
                        "fe.bulk", parent=tctx,
                        attrs={"n": n, "residue": rn})
            with espan:
                if gate is None:
                    # Whole-frame residue keeps the zero-copy KeyBlob
                    # (the common tier-0-cold / window-kind shape); a
                    # partial residue decodes only its own minority.
                    sub_keys = (keys if rn == n
                                else [keys[int(i)] for i in residue])
                    sub_counts = (counts if rn == n
                                  else np.asarray(counts)[residue])
                    res = await self._bulk_store_call(
                        sub_keys, sub_counts, a, b, kind, with_rem)
                    granted = np.asarray(res.granted, np.uint8)
                    if res.remaining is not None:
                        remaining = np.asarray(res.remaining, np.float64)
                else:
                    serve_mask, envelope_rows, _moved = gate
                    env_rows = dict(envelope_rows)
                    store_pos = [p for p in range(rn)
                                 if serve_mask[int(residue[p])]]
                    if store_pos:
                        sub_keys = [keys[int(residue[p])]
                                    for p in store_pos]
                        sub_counts = np.asarray(counts)[
                            np.asarray(residue)[store_pos]]
                        res = await self._bulk_store_call(
                            sub_keys, sub_counts, a, b, kind, with_rem)
                        granted[store_pos] = np.asarray(res.granted,
                                                        np.uint8)
                        if res.remaining is not None:
                            remaining[store_pos] = np.asarray(
                                res.remaining, np.float64)
                    # Parked rows serve their handoff envelope, exactly
                    # like _serve_bulk_gated's rows (same helper, same
                    # order: store first, envelopes after).
                    for p in range(rn):
                        i = int(residue[p])
                        handoff = env_rows.get(i)
                        if handoff is not None:
                            g, rem = srv.placement.envelope_acquire(
                                handoff, keys[i], int(counts[i]), a, b,
                                liveconfig.BULK_KINDS[kind])
                            granted[p] = g
                            remaining[p] = rem
            c = ctypes
            with tracing.annotate("drl/fe.complete"):
                lib.fe_bulk_complete(
                    h, jid,
                    np.ascontiguousarray(granted).ctypes.data_as(
                        c.POINTER(c.c_uint8)),
                    np.ascontiguousarray(remaining).ctypes.data_as(
                        c.POINTER(c.c_double)))
        except Exception as exc:  # noqa: BLE001 — every frame must get
            log.error_evaluating_kernel(exc)  # a routable error reply
            lib.fe_bulk_fail(h, jid, repr(exc)[:200].encode())

    async def _bulk_store_call(self, keys, counts, a: float, b: float,
                               kind: int, with_rem: bool):
        """The same store entry the asyncio ACQUIRE_MANY branch calls —
        shared shape, shared semantics (the differential fuzz pins the
        two lanes reply-for-reply)."""
        if kind == wire.BULK_KIND_BUCKET:
            return await self._server.store.acquire_many(
                keys, counts, a, b, with_remaining=with_rem)
        return await self._server.store.window_acquire_many(
            keys, counts, a, b,
            fixed=(kind == wire.BULK_KIND_FWINDOW),
            with_remaining=with_rem)

    async def _serve_passthrough(self, sh: int, conn_id: int,
                                 body: bytes) -> None:
        try:
            op = body[5] if len(body) >= 6 else 0
            if op == wire.OP_HELLO:
                await self._serve_hello(sh, conn_id, body)
                return
            if op != wire.OP_ACQUIRE_MANY:
                await self._serve_passthrough_inner(sh, conn_id, body)
                return
            # Only MALFORMED bulk frames (or a disabled/stale bulk lane)
            # reach this path since round 8 — well-formed ones are
            # native. They still run as their own tasks so a long store
            # call can't stall the pump's other passthrough work;
            # chained chunks order behind the connection's tail (conn
            # ids are per-shard, so the tail key carries the shard).
            prev = (self._bulk_tails.get((sh, conn_id))
                    if wire.bulk_request_chained(body) else None)
            task = self._track_task(
                self._serve_passthrough_inner(sh, conn_id, body,
                                              after=prev))
            self._bulk_tails[(sh, conn_id)] = task

            def _clear(t, key=(sh, conn_id)):
                if self._bulk_tails.get(key) is t:
                    del self._bulk_tails[key]

            task.add_done_callback(_clear)
        except Exception as exc:  # noqa: BLE001
            log.error_evaluating_kernel(exc)

    async def _serve_passthrough_inner(self, sh: int, conn_id: int,
                                       body: bytes,
                                       after: "asyncio.Task | None" = None
                                       ) -> None:
        if after is not None:
            await asyncio.gather(after, return_exceptions=True)
        resp = await self._server.handle_frame_body(body)
        self._send(sh, conn_id, resp)

    async def _serve_hello(self, sh: int, conn_id: int,
                           body: bytes) -> None:
        import hmac

        try:
            seq, _, token, _, _, _ = wire.decode_request(body)
        except Exception:
            self._send(sh, conn_id, wire.encode_response(
                0, wire.RESP_ERROR, "malformed HELLO frame"))
            self._lib.fe_close_conn(sh, conn_id)
            return
        auth_token = self._server.auth_token
        # surrogateescape mirrors the wire decode: a token with invalid
        # UTF-8 must compare (and fail) cleanly — a raising .encode()
        # here left the connection stuck in auth_pending forever.
        if auth_token is not None and not hmac.compare_digest(
                token.encode("utf-8", "surrogateescape"),
                auth_token.encode()):
            self._send(sh, conn_id, wire.encode_response(
                seq, wire.RESP_ERROR, "authentication failed"))
            self._lib.fe_close_conn(sh, conn_id)
            return
        self._lib.fe_set_authed(sh, conn_id, 1)
        self._send(sh, conn_id, wire.encode_response(seq, wire.RESP_EMPTY))

    def _send(self, sh: int, conn_id: int, resp: bytes) -> None:
        self._lib.fe_send(sh, conn_id, resp, len(resp))

    # -- tier-0 sync pump --------------------------------------------------

    def _t0_harvest(self) -> dict[tuple[str, float, float], float]:
        """Drain accumulated local grants out of the C replica table:
        ``{(key, capacity, rate): amount}``. Buffers are preallocated in
        ``_tier0_setup`` (the pump runs this every ``sync_interval_s``)."""
        c = ctypes
        blob, klens = self._t0_blob, self._t0_klens
        amounts, caps, rates = (self._t0_amounts, self._t0_caps,
                                self._t0_rates)
        n = self._lib.fe_t0_harvest(
            self._h, blob, len(blob),
            klens.ctypes.data_as(c.POINTER(c.c_int32)),
            amounts.ctypes.data_as(c.POINTER(c.c_double)),
            caps.ctypes.data_as(c.POINTER(c.c_double)),
            rates.ctypes.data_as(c.POINTER(c.c_double)), len(klens))
        if n <= 0:
            return {}
        # string_at copies only the used prefix (blob.raw would
        # materialize the whole preallocated buffer every round).
        used = ctypes.string_at(blob, int(klens[:n].sum()))
        keys = wire.decode_key_blob(used, klens[:n],
                                    errors="surrogateescape")
        # SUM duplicate idents: with N shards each hosts its own replica
        # slice, so one harvest can return the same key once per shard —
        # the merged amount is the node's whole drained grant, debited
        # once and acked back into every slice (fe_t0_ack fans out).
        out: dict[tuple[str, float, float], float] = {}
        for i, k in enumerate(keys):
            ident = (k, float(caps[i]), float(rates[i]))
            out[ident] = out.get(ident, 0.0) + float(amounts[i])
        return out

    def _t0_retire(self, cap: float, rate: float
                   ) -> list[tuple[str, float]]:
        """Kill every C replica of one retired (cap, rate) config and
        return its un-harvested ``(key, amount)`` grants — one locked
        ABI call (``fe_t0_retire``), so no grant slips between the
        harvest and the kill (runtime/liveconfig.py)."""
        c = ctypes
        blob, klens = self._t0_blob, self._t0_klens
        amounts = self._t0_amounts
        n = self._lib.fe_t0_retire(
            self._h, cap, rate, blob, len(blob),
            klens.ctypes.data_as(c.POINTER(c.c_int32)),
            amounts.ctypes.data_as(c.POINTER(c.c_double)), len(klens))
        if n <= 0:
            return []
        used = ctypes.string_at(blob, int(klens[:n].sum()))
        keys = wire.decode_key_blob(used, klens[:n],
                                    errors="surrogateescape")
        return [(k, float(amounts[i])) for i, k in enumerate(keys)]

    def _t0_ack(self, keys: list[str], cap: float, rate: float,
                remaining: np.ndarray) -> None:
        c = ctypes
        n = len(keys)
        kb = [k.encode("utf-8", "surrogateescape") for k in keys]
        blob = b"".join(kb)
        klens = np.fromiter((len(b) for b in kb), np.int32, n)
        caps = np.full(n, cap, np.float64)
        rates = np.full(n, rate, np.float64)
        rem = np.ascontiguousarray(remaining, np.float64)
        self._lib.fe_t0_ack(
            self._h, blob,
            klens.ctypes.data_as(c.POINTER(c.c_int32)),
            caps.ctypes.data_as(c.POINTER(c.c_double)),
            rates.ctypes.data_as(c.POINTER(c.c_double)),
            rem.ctypes.data_as(c.POINTER(c.c_double)), n)

    async def _t0_sync_loop(self) -> None:
        """Reconciliation pump: every ``sync_interval_s``, harvest each
        replica's locally-granted permits, debit them from the
        authoritative store in one bulk launch per (capacity, rate)
        config, and ack the fresh balances back into the replica table
        (which re-sizes every key's budget). A failed round (device
        unhealthy — the r04/r05 outage mode) carries its amounts into the
        next round instead of dropping them; meanwhile the C side keeps
        serving within each key's last-acked envelope."""
        cfg = self._tier0
        assert cfg is not None
        store = self._server.store
        recorder = getattr(self._server, "flight_recorder", None)
        hh = getattr(self._server, "heavy_hitters", None)
        while True:
            await asyncio.sleep(cfg.sync_interval_s)
            self._harvest_tier0_traces()
            # Everything harvested was already zeroed out of the C table:
            # from here until it is debited it exists ONLY in `merged`,
            # so every exit path — per-config failure, unexpected error,
            # cancellation mid-await (aclose) — must route the undrained
            # remainder back into the carry dict. The finally below is
            # that single restore point; successful groups pop themselves
            # out of `merged` first.
            merged = self._t0_carry
            self._t0_carry = {}
            round_failures = 0
            round_keys = 0
            round_shortfall = 0.0
            try:
                for ident, amount in self._t0_harvest().items():
                    merged[ident] = merged.get(ident, 0.0) + amount
                    if hh is not None:
                        # The keys the sync pump drains ARE the tier-0 hot
                        # set — the telemetry that explains hit rate.
                        hh.offer(ident[0], amount)
                if not merged:
                    self._t0_fail_streak = 0
                    continue
                if faults._INJECTOR is not None:  # chaos seam: a fault
                    # here fails the round — harvested rows re-carry via
                    # the finally, the degraded streak advances.
                    await faults._INJECTOR.on_event("t0.sync")
                by_cfg: dict[tuple[float, float], list[tuple[str, float]]] = {}
                for (key, cap, rate), amount in merged.items():
                    by_cfg.setdefault((cap, rate), []).append((key, amount))
                lc = self._server.liveconfig
                for (cap, rate), rows in by_cfg.items():
                    # Retired config (live mutation committed since these
                    # replicas were installed): kill the C replicas via
                    # fe_t0_retire — one locked call that also returns
                    # any grants admitted since the harvest above — and
                    # debit EVERYTHING through the REPLACEMENT config's
                    # table, the one the rebase carried the balances
                    # into. Dead replicas make later stale frames fall
                    # through to the batch lane's routable "config
                    # moved" error (and _ROW_SKIP keeps them from
                    # re-installing). Over-admission is bounded by one
                    # sync interval's headroom — the same epsilon family
                    # as the tier-0 budget itself. A stale .so without
                    # the retire ABI falls back to a zero ack: admission-
                    # safe (confident local denies), converging once the
                    # loader rebuilds.
                    fwd = (lc.forward("bucket", cap, rate)
                           if lc.active else None)
                    if fwd is not None and getattr(self._lib,
                                                   "has_t0_retire",
                                                   False):
                        for key, amount in self._t0_retire(cap, rate):
                            ident = (key, cap, rate)
                            merged[ident] = merged.get(ident, 0.0) \
                                + amount
                        rows = [(k, a) for (k, c2, r2), a
                                in merged.items()
                                if (c2, r2) == (cap, rate)]
                    keys = [k for k, _ in rows]
                    amounts = [a for _, a in rows]
                    dcap, drate = (cap, rate) if fwd is None else \
                        (fwd[0], fwd[1])
                    try:
                        remaining, shortfall = await store.debit_many(
                            keys, amounts, dcap, drate)
                    except asyncio.CancelledError:
                        raise
                    except Exception as exc:  # degraded: rows stay in
                        # `merged` and re-carry via the finally
                        log.error_evaluating_kernel(exc)
                        self.t0_metrics.sync_failures += 1
                        round_failures += 1
                        continue
                    if fwd is not None:
                        self.t0_metrics.retired_config_rows += len(keys)
                        if not getattr(self._lib, "has_t0_retire",
                                       False):
                            remaining = np.zeros(len(keys), np.float64)
                            self._t0_ack(keys, cap, rate, remaining)
                    else:
                        self._t0_ack(keys, cap, rate, remaining)
                    self.t0_metrics.record_sync(len(keys), shortfall,
                                                time.monotonic())
                    round_keys += len(keys)
                    round_shortfall += float(sum(shortfall))
                    for k, _ in rows:
                        merged.pop((k, cap, rate), None)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # the pump must outlive any bad round
                log.error_evaluating_kernel(exc)
                self.t0_metrics.sync_failures += 1
                round_failures += 1
            finally:
                for ident, amount in merged.items():
                    if amount > 0.0:
                        self._t0_carry[ident] = (
                            self._t0_carry.get(ident, 0.0) + amount)
                self._t0_record_round(recorder, round_keys,
                                      round_shortfall, round_failures)

    def _harvest_tier0_traces(self) -> None:
        """Drain the C-side ring of traced tier-0 local decisions into
        the tracer (one completed ``fe.tier0`` span each) — this is how
        a request that never left the epoll loop still contributes its
        hop to the exported trace. Start/duration were stamped in C on
        CLOCK_MONOTONIC, the same epoch ``perf_counter`` reads."""
        lib = self._lib
        if not getattr(lib, "has_trace", False):
            return
        tracer = tracing.get_tracer()
        if not tracer.enabled:
            return
        buf = np.zeros(6 * 256, np.uint64)
        while True:
            got = lib.fe_trace_harvest(
                self._h, buf.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint64)), 256)
            if got <= 0:
                return
            recs = buf[:6 * got].reshape(got, 6)
            for hi, lo, parent, start_ns, dur_ns, meta in recs.tolist():
                ctx = tracing.TraceContext(
                    int(hi), int(lo), int(parent),
                    1 if int(meta) & 2 else 0)
                granted = bool(int(meta) & 0x100)
                tracer.record_span(
                    "fe.tier0", ctx, start_ns * 1e-9,
                    (start_ns + dur_ns) * 1e-9,
                    status="ok" if granted else "denied",
                    attrs={"op": wire.op_name((int(meta) >> 16) & 0xFF),
                           "local": True})
            if got < 256:
                return

    #: Cadence of the bulk-lane hot-key harvest (C ring → sketch). The
    #: ring is bounded (oldest drop), so a slower drain costs tail
    #: fidelity, never memory.
    _HOT_HARVEST_S = 0.5

    async def _hot_harvest_loop(self) -> None:
        """Drain the C bulk lane's per-frame top-K ring into the
        server's heavy-hitter sketch. This closes the PR-2 exemption for
        the native lane: zero-copy bulk keys never materialize in
        Python, so the C side aggregates (top-K per frame) and this pump
        offers only the survivors — exactly the traffic tier-0 bulk
        needs surfaced for hot-row identification."""
        hh = self._server.heavy_hitters
        blob = ctypes.create_string_buffer(256 * 256)
        klens = np.zeros(256, np.int32)
        weights = np.zeros(256, np.float64)
        c = ctypes
        while True:
            await asyncio.sleep(self._HOT_HARVEST_S)
            while True:
                got = self._lib.fe_hot_harvest(
                    self._h, blob, len(blob),
                    klens.ctypes.data_as(c.POINTER(c.c_int32)),
                    weights.ctypes.data_as(c.POINTER(c.c_double)), 256)
                if got <= 0:
                    break
                used = ctypes.string_at(blob, int(klens[:got].sum()))
                keys = wire.decode_key_blob(used, klens[:got],
                                            errors="surrogateescape")
                for k, w in zip(keys, weights[:got].tolist()):
                    hh.offer(k, w)
                if got < 256:
                    break

    def bulk_stats(self) -> dict | None:
        """C-side native-bulk gauges (``None`` when the lane is off).
        ``rows_local`` are per-row tier-0 decisions (grant or confident
        deny) made without leaving C; ``permits_local`` is the granted
        amount the tier-0 sync pump debits — the "bulk sync debits"
        gauge of the epsilon audit."""
        if not self._bulk_native:
            return None
        counts = (ctypes.c_longlong * 7)()
        self._lib.fe_bulk_counts(self._h, counts)
        (frames, frames_local, rows, rows_local, rows_residue,
         permits_local, hot_dropped) = (int(v) for v in counts)
        return {
            "frames": frames,
            "frames_local": frames_local,
            "rows": rows,
            "rows_local": rows_local,
            "rows_residue": rows_residue,
            "permits_local": permits_local,
            "hot_ring_dropped": hot_dropped,
        }

    #: Consecutive failed sync rounds that count as a degraded-mode
    #: streak and trip the flight recorder.
    T0_STREAK_DUMP = 3

    def _t0_record_round(self, recorder, n_keys: int, shortfall: float,
                         failures: int) -> None:
        """Per-sync flight-recorder frame + the degraded-mode triggers:
        a dump on entry into a failure streak of :data:`T0_STREAK_DUMP`
        rounds (rate-limited inside the recorder), so the outage window
        leaves captured state instead of prose."""
        if failures:
            self._t0_fail_streak += 1
        else:
            self._t0_fail_streak = 0
        if recorder is None:
            return
        recorder.record("t0_sync", keys=n_keys, shortfall=shortfall,
                        failures=failures,
                        streak=self._t0_fail_streak,
                        carry_keys=len(self._t0_carry))
        if self._t0_fail_streak == self.T0_STREAK_DUMP:
            recorder.auto_dump(
                "t0_sync_streak",
                {"streak": self._t0_fail_streak,
                 "carry_keys": len(self._t0_carry)})

    def tier0_stats(self) -> dict | None:
        """Merged C + pump-side tier-0 gauges (``None`` when disabled)."""
        if self._tier0 is None:
            return None
        counts = (ctypes.c_longlong * 6)()
        self._lib.fe_t0_counts(self._h, counts)
        hits, denies, misses, installs, evictions, entries = (
            int(v) for v in counts)
        eligible = hits + denies + misses
        out = {
            "hits": hits,
            "local_denies": denies,
            "misses": misses,
            "hit_rate": (hits + denies) / eligible if eligible else 0.0,
            "installs": installs,
            "evictions": evictions,
            "entries": entries,
            "carry_keys": len(self._t0_carry),
            **self.t0_metrics.snapshot(time.monotonic()),
        }
        eps = self.t0_eps_tokens()
        if eps is not None:
            # C-side ε-consumption witness (round 18): cumulative
            # locally-granted tokens, summed over slices — the audit
            # plane's tier-0 "admitted" side.
            out["grant_tokens"] = sum(eps)
        return out

    def t0_eps_tokens(self) -> "list[float] | None":
        """Per-slice cumulative locally-granted tokens (fe_t0_eps) —
        ``None`` when tier-0 is off or the binary predates the ABI."""
        if self._tier0 is None or not getattr(self._lib, "has_t0_eps",
                                              False):
            return None
        buf = (ctypes.c_double * max(1, self.n_shards))()
        n = self._lib.fe_t0_eps(self._h, buf, len(buf))
        return [float(buf[i]) for i in range(int(n))]

    def shard_stats(self) -> "list[dict] | None":
        """Per-shard breakdown of the serving / tier-0 / bulk gauges
        (``None`` single-shard — the merged top-level gauges already
        tell the whole story there). Every row reads through that
        shard's sub-handle; the top-level OP_STATS gauges stay the
        whole-node SUM the C side computes over the same state, so
        ``sum(shards[*].x) == merged x`` is an invariant the tests
        pin. Tier-0 rows read the shard's own replica SLICE (each
        shard hosts its own replicas with a split budget share —
        docs/DESIGN.md §16), so `entries` counts replicas, not
        distinct keys."""
        if self.n_shards <= 1:
            return None
        c = ctypes
        out: list[dict] = []
        for i, sh in enumerate(self._shards):
            req = c.c_longlong()
            conns = c.c_longlong()
            batches = c.c_longlong()
            self._lib.fe_counts(sh, c.byref(req), c.byref(conns),
                                c.byref(batches))
            row: dict = {
                "shard": i,
                "requests_served": req.value,
                "connections_served": conns.value,
                "batches_flushed": batches.value,
            }
            if self._tier0 is not None:
                t0 = (c.c_longlong * 6)()
                self._lib.fe_t0_counts(sh, t0)
                row["tier0"] = {
                    "hits": int(t0[0]), "local_denies": int(t0[1]),
                    "misses": int(t0[2]), "installs": int(t0[3]),
                    "evictions": int(t0[4]), "entries": int(t0[5]),
                }
                if getattr(self._lib, "has_t0_eps", False):
                    # This shard's own slice ε-consumption (round 18):
                    # one row per shard handle, so the per-slice
                    # breakdown rides the same shards=[...] surface.
                    eps = (c.c_double * 1)()
                    if self._lib.fe_t0_eps(sh, eps, 1) == 1:
                        row["tier0"]["grant_tokens"] = float(eps[0])
            if self._bulk_native:
                bk = (c.c_longlong * 7)()
                self._lib.fe_bulk_counts(sh, bk)
                row["native_bulk"] = {
                    "frames": int(bk[0]), "frames_local": int(bk[1]),
                    "rows": int(bk[2]), "rows_local": int(bk[3]),
                    "rows_residue": int(bk[4]),
                    "permits_local": int(bk[5]),
                }
            out.append(row)
        return out

    def transport_stats(self) -> dict | None:
        """Uring transport gauges (``None`` when the loaded binary
        predates the uring ABI): shard counts by transport, ring
        counters (enter syscalls, SQEs submitted, CQEs reaped), the
        self-instrumented data-plane syscall counter both transports
        maintain (the ``syscalls/frame`` numerator), and per-shard
        fallback reasons
        when uring was requested but a shard serves on epoll."""
        if not getattr(self._lib, "has_uring", False) or self._h is None:
            return None
        c = ctypes
        counts = (c.c_longlong * 8)()
        self._lib.fe_uring_counts(self._h, counts)
        out = {
            "mode": {URING_OFF: "epoll", URING_ON: "uring",
                     URING_SQPOLL: "uring+sqpoll"}[self.uring_mode],
            "uring_shards": int(counts[0]),
            "sqpoll_shards": int(counts[1]),
            "enters": int(counts[2]),
            "sqes_submitted": int(counts[3]),
            "cqes_seen": int(counts[4]),
            "io_syscalls": int(counts[5]),
            "fallbacks": int(counts[6]),
        }
        if self.uring_mode != URING_OFF and out["fallbacks"]:
            buf = ctypes.create_string_buffer(256)
            reasons = {}
            for i in range(self.n_shards):
                if self._lib.fe_uring_reason(self._h, i, buf,
                                             len(buf)) == 0:
                    reasons[i] = (buf.value.decode("utf-8", "replace")
                                  or "no reason recorded")
            if reasons:
                out["fallback_reasons"] = reasons
        return out

    # -- stats / lifecycle -------------------------------------------------

    def counts(self) -> tuple[int, int, int]:
        """One locked C call for ``(requests_served, connections_served,
        batches_flushed)`` — stats readers take the front-end mutex once,
        not once per counter."""
        c = ctypes
        req = c.c_longlong()
        conns = c.c_longlong()
        batches = c.c_longlong()
        self._lib.fe_counts(self._h, c.byref(req), c.byref(conns),
                            c.byref(batches))
        return req.value, conns.value, batches.value

    def latency_histogram(self) -> LatencyHistogram:
        """Snapshot the C-side serving histogram into the shared Python
        class (same 82 log-1.25 buckets, so quantiles read identically)."""
        if getattr(self._lib, "has_stage_hist", False):
            hist = self._stage_histogram(0)
            if hist is not None:
                return hist
        counts = np.zeros(LatencyHistogram.N_BUCKETS, np.uint64)
        total = self._lib.fe_hist(
            self._h, counts.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint64)))
        hist = LatencyHistogram()
        hist.counts = [int(x) for x in counts]
        hist.total = int(total)
        return hist

    def _stage_histogram(self, stage: int) -> LatencyHistogram | None:
        counts = np.zeros(LatencyHistogram.N_BUCKETS, np.uint64)
        sum_s = ctypes.c_double()
        total = self._lib.fe_stage_hist(
            self._h, stage,
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.byref(sum_s))
        if total < 0:
            return None
        hist = LatencyHistogram()
        hist.counts = [int(x) for x in counts]
        hist.total = int(total)
        hist.sum_s = float(sum_s.value)
        return hist

    def stage_histograms(self) -> "dict[str, LatencyHistogram] | None":
        """The per-stage decomposition of the native serving span: the C
        side's ``native_queue`` (frame parsed → batch cut) and
        ``native_exec`` (batch cut → completion), then the host segments
        of exec: ``fe_handoff`` (batch cut or bulk arrival → the loop's
        first line) and the store's ``store_host`` / ``store_readback``
        (when its metrics keep them). ``None`` when the loaded binary
        predates the stage-hist ABI."""
        if not getattr(self._lib, "has_stage_hist", False):
            return None
        out: dict[str, LatencyHistogram] = {}
        for stage, name in ((1, "native_queue"), (2, "native_exec")):
            hist = self._stage_histogram(stage)
            if hist is None:
                return None
            out[name] = hist
        if self._stage_stamps:
            out["fe_handoff"] = self.handoff_latency
        out.update(self._store_stages())
        return out

    def _store_stages(self) -> "dict[str, LatencyHistogram]":
        metrics = getattr(self._server.store, "metrics", None)
        return {name: hist for name in ("store_host", "store_readback")
                if (hist := getattr(metrics, name, None)) is not None}

    def reset_latency(self) -> None:
        """Start a measurement window: the serving histogram and every
        stage of :meth:`stage_histograms`."""
        self._lib.fe_hist_reset(self._h)  # stage hists reset with it
        self.handoff_latency.reset()
        for hist in self._store_stages().values():
            hist.reset()

    async def aclose(self) -> None:
        if self._stopping:
            return
        self._stopping = True
        # Order matters: (0) stop the tier-0 sync pump — it reads the C
        # handle (harvest/ack); (1) fe_stop joins the IO thread — no new
        # frames; (2) the pump sees -1 from fe_wait and exits — no new
        # loop tasks; (3) drain the loop tasks still in flight, whose
        # fe_complete/fe_send calls need the handle alive (the sockets
        # are gone, so completions just fall into the void); only then
        # (4) free the handle.
        if self._t0_task is not None:
            self._t0_task.cancel()
            try:
                await self._t0_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._t0_task = None
        if self._hot_task is not None:
            # Same handle discipline as the t0 pump: fe_hot_harvest
            # reads the C handle, so the drain must die before fe_free.
            self._hot_task.cancel()
            try:
                await self._hot_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._hot_task = None
        await asyncio.to_thread(self._lib.fe_stop, self._h)
        for pump in self._pumps:
            await asyncio.to_thread(pump.join, 5.0)
        while self._loop_tasks:
            # Loop, not a one-shot gather: a bulk passthrough parent can
            # spawn its _serve_passthrough_inner child AFTER the snapshot
            # was taken — the child also holds the handle.
            await asyncio.gather(*list(self._loop_tasks),
                                 return_exceptions=True)
        if any(pump.is_alive() for pump in self._pumps):
            # A pump blew past the join timeout: it may still be inside
            # fe_wait/fe_batch_copy holding the handle. Freeing now would
            # be a use-after-free on its next C call — leak the handle
            # (one struct + sockets already closed) and say so instead.
            logger.error(
                "native front-end pump thread still alive after 5s; "
                "leaking the C handle instead of freeing under it")
            self._h = None
            return
        self._lib.fe_free(self._h)
        self._h = None


#: Ops the load generator can drive (all share the keyed-request frame
#: layout; (a, b) mean (capacity, rate) / (limit, window_s) / (limit, -)).
_LOADGEN_OPS = {
    "acquire": wire.OP_ACQUIRE,
    "window": wire.OP_WINDOW,
    "fixed_window": wire.OP_FWINDOW,
    "sema": wire.OP_SEMA,
}


def native_loadgen(host: str, port: int, *, conns: int = 4, depth: int = 32,
                   reqs_per_conn: int = 10000, keyspace: int = 1000,
                   capacity: float = 1e7, fill_rate: float = 1e7,
                   op: str = "acquire") -> tuple[int, int, float]:
    """Closed-loop native measurement client: ``conns`` connections each
    keeping ``depth`` pipelined requests of ``op`` (acquire / window /
    fixed_window / sema) in flight. Returns ``(replies, granted,
    elapsed_s)``. Runs in C (one epoll thread) so a Python client's
    ~14µs/request scheduling floor doesn't bound the measurement — the
    asymmetric rig the per-request ceiling analysis called for."""
    if op not in _LOADGEN_OPS:
        raise ValueError(
            f"unknown loadgen op {op!r}; choose from "
            f"{sorted(_LOADGEN_OPS)}")
    lib = load_frontend_lib()
    if lib is None:
        raise RuntimeError("native front-end library unavailable")
    c = ctypes
    elapsed = c.c_double()
    replies = c.c_longlong()
    granted = c.c_longlong()
    rc = lib.fe_loadgen(host.encode(), port, conns, depth, reqs_per_conn,
                        keyspace, capacity, fill_rate, _LOADGEN_OPS[op],
                        c.byref(elapsed), c.byref(replies),
                        c.byref(granted))
    if rc != 0:
        raise OSError("native loadgen failed to connect")
    return replies.value, granted.value, elapsed.value


def native_bulk_loadgen(host: str, port: int, *, conns: int = 8,
                        depth: int = 4, frames_per_conn: int = 200,
                        rows_per_frame: int = 4096, keyspace: int = 64,
                        capacity: float = 1e8, fill_rate: float = 1e8,
                        uring: bool = False
                        ) -> tuple[int, int, int, float]:
    """Closed-loop native BULK measurement client: ``conns`` connections
    each keeping ``depth`` pipelined OP_ACQUIRE_MANY frames of
    ``rows_per_frame`` rows in flight, frames built and replies counted
    in C (``fe_lg_bulk``). Returns ``(frames, rows, granted_rows,
    elapsed_s)``. This is the shard-sweep rig's client: at multi-shard
    bulk rates even a per-frame Python client bounds the node, and the
    kernel's SO_REUSEPORT hash spreads the ``conns`` across shards.
    Requires a front-end binary with the shard ABI.

    ``uring=True`` drives the frames through the loadgen's own
    submission ring (``fe_lg_bulk_uring`` — one ``io_uring_enter`` per
    burst instead of one send/recv syscall pair per frame) so the
    client stops being the syscall bottleneck it was in the r11 sweep;
    when the ring is unavailable (kernel, seccomp, or a stale .so) the
    call falls back to the epoll-era client loudly and the measurement
    still happens."""
    lib = load_frontend_lib()
    if lib is None or not getattr(lib, "has_shards", False):
        raise RuntimeError(
            "native bulk loadgen unavailable (library missing or "
            "predates the fe_lg_bulk ABI)")
    c = ctypes
    elapsed = c.c_double()
    frames = c.c_longlong()
    rows = c.c_longlong()
    granted = c.c_longlong()
    if uring and getattr(lib, "has_uring", False):
        rc = lib.fe_lg_bulk_uring(
            host.encode(), port, conns, depth, frames_per_conn,
            rows_per_frame, keyspace, capacity, fill_rate,
            c.byref(elapsed), c.byref(frames), c.byref(rows),
            c.byref(granted))
        if rc == 0:
            return frames.value, rows.value, granted.value, elapsed.value
        if rc != -2:
            raise OSError("native uring bulk loadgen failed to connect")
        logger.warning("uring bulk loadgen requested but no ring is "
                       "available on this host; using the syscall client")
    elif uring:
        logger.warning("uring bulk loadgen requested but the loaded "
                       "binary predates the uring ABI; using the "
                       "syscall client")
    rc = lib.fe_lg_bulk(host.encode(), port, conns, depth,
                        frames_per_conn, rows_per_frame, keyspace,
                        capacity, fill_rate, c.byref(elapsed),
                        c.byref(frames), c.byref(rows), c.byref(granted))
    if rc != 0:
        raise OSError("native bulk loadgen failed to connect")
    return frames.value, rows.value, granted.value, elapsed.value
