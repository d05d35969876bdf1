"""Loader for the native host-runtime library (``native/directory.cc``).

The C++ directory is a performance component, not a correctness one: the
store works identically on the pure-Python fallback (see
:mod:`~.runtime.directory`). Build strategy: the shared objects are never
committed; they are compiled with ``g++`` into ``native/build/`` on first
import when missing or built from other source than the tree's. A failed
build (no compiler, read-only checkout) warns with the compiler's error
and yields ``None``; callers fall back, and ``chip_smoke.py`` refuses to
run on the fallback. Set ``DRL_TPU_NO_NATIVE=1`` to force it.

Sanitizer legs (``make asan-test`` / ``make tsan-test``):
``DRL_TPU_SANITIZE`` selects an instrumented build into a separate
directory (the production ``.so`` is never clobbered):

- ``asan`` (or the legacy ``1``): ``-fsanitize=address,undefined -g -O1``
  into ``native/build/asan/`` — run the native test files with
  ``libasan`` preloaded.
- ``tsan``: ``-fsanitize=thread -g -O1`` into ``native/build/tsan/`` —
  run with ``libtsan`` preloaded and the ``native/tsan.supp``
  suppressions file (jaxlib's uninstrumented thread pools).

See the ``native/Makefile`` targets for the full invocations.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess

__all__ = ["load_directory_lib", "load_frontend_lib",
           "URING_OFF", "URING_ON", "URING_SQPOLL"]

#: Transport mode for ``fe_start_sharded2`` — MUST mirror the
#: ``kUringOff``/``kUringOn``/``kUringSqpoll`` constexprs in
#: ``native/frontend.cc`` (drl-check's ``transport-flag`` rule pins the
#: pair both directions; a drift here is a build break, not a silent
#: transport swap).
URING_OFF = 0
URING_ON = 1
URING_SQPOLL = 2

_REPO_NATIVE = pathlib.Path(__file__).resolve().parents[3] / "native"
_LIB: ctypes.CDLL | None = None
_TRIED = False

#: Serializes first-load across threads. The load generators are run
#: from worker THREADS (the multi-shard bench rig starts several at
#: once); without the lock, racing first callers see ``_TRIED`` set by
#: a loader still mid-build and return ``None`` for a library that is
#: about to exist.
import threading as _threading

_LOAD_LOCK = _threading.Lock()

#: Sanitizer opt-in (the `make asan-test` / `make tsan-test` env hook):
#: value selects the instrumented build directory and flag set ("asan"
#: or legacy "1" → build/asan, "tsan" → build/tsan). -O1 keeps stack
#: traces honest; these binaries are for the sanitizer legs, not serving.
SANITIZE_ENV = "DRL_TPU_SANITIZE"
_SANITIZE_MODES = {
    "asan": (["-fsanitize=address,undefined", "-g", "-O1",
              "-fno-omit-frame-pointer"], "asan"),
    "tsan": (["-fsanitize=thread", "-g", "-O1",
              "-fno-omit-frame-pointer"], "tsan"),
}


def _sanitize_mode() -> tuple[list[str], str] | None:
    """``(extra_flags, build_subdir)`` for the selected sanitizer, or
    ``None`` for a production build. ``1`` keeps its historical meaning
    (the ASan leg); any other unrecognized value raises — silently
    serving an ASan binary to someone who asked for ``thread``/a typo'd
    ``tsna`` would hand them a race-free "pass" with no thread
    instrumentation at all."""
    val = os.environ.get(SANITIZE_ENV, "").strip().lower()
    if not val:
        return None
    if val == "1":
        val = "asan"
    if val not in _SANITIZE_MODES:
        raise ValueError(
            f"{SANITIZE_ENV}={val!r} is not a known sanitizer; use "
            f"{sorted(_SANITIZE_MODES)} (or legacy '1' for asan)")
    flags, subdir = _SANITIZE_MODES[val]
    return list(flags), subdir


def _out_path(name: str) -> pathlib.Path:
    build = _REPO_NATIVE / "build"
    mode = _sanitize_mode()
    if mode is not None:
        return build / mode[1] / name
    return build / name


def _extra_flags() -> list[str]:
    mode = _sanitize_mode()
    return mode[0] if mode is not None else []


def _source_hash(src: pathlib.Path) -> str:
    return hashlib.sha256(src.read_bytes()).hexdigest()


def _hash_path(out: pathlib.Path) -> pathlib.Path:
    return out.with_name(out.name + ".hash")


def _is_stale(src: pathlib.Path, out: pathlib.Path) -> bool:
    """A binary is fresh only when its sidecar records the CURRENT source
    hash. Mtime comparison is not enough: a fresh clone materializes
    source and committed binary with equal mtimes, so source/binary
    drift in the repo would silently serve the stale ``.so``."""
    if not out.exists():
        return True
    try:
        return _hash_path(out).read_text().strip() != _source_hash(src)
    except OSError:
        return True  # no/unreadable sidecar: rebuild to establish one


def _build(src: pathlib.Path, out: pathlib.Path) -> bool:
    """Prefer a build with the CPython API enabled (zero-copy list[str]
    resolve); fall back to the plain C ABI if headers are unavailable.
    The compiler writes a per-process temporary name in the same
    directory that is then ``os.replace``d into place, so concurrent
    first imports (pytest-xdist workers) never load a half-written
    ``.so``. A successful build stamps the source-hash sidecar
    ``_is_stale`` checks on load."""
    import sysconfig
    import warnings

    out.parent.mkdir(parents=True, exist_ok=True)
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    base = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared"]
    base += _extra_flags()  # sanitizer leg: DRL_TPU_SANITIZE=1
    include = sysconfig.get_paths().get("include")
    attempts = []
    if include and (pathlib.Path(include) / "Python.h").exists():
        attempts.append(base + ["-DDRL_WITH_PYTHON", f"-I{include}",
                                str(src), "-o", str(tmp)])
    attempts.append(base + [str(src), "-o", str(tmp)])
    err = ""
    for cmd in attempts:
        try:
            proc = subprocess.run(cmd, capture_output=True, timeout=300)
        except (OSError, subprocess.TimeoutExpired) as exc:
            err = repr(exc)
            break
        if proc.returncode == 0 and tmp.exists():
            os.replace(tmp, out)
            try:
                side = _hash_path(out)
                side_tmp = side.with_name(f"{side.name}.{os.getpid()}.tmp")
                side_tmp.write_text(_source_hash(src) + "\n")
                os.replace(side_tmp, side)
            except OSError:
                pass  # read-only checkout: next load re-checks and
                # rebuilds into the same (tmpfs/overlay) place
            return True
        err = proc.stderr.decode(errors="replace")[-2000:]
    tmp.unlink(missing_ok=True)
    warnings.warn(f"native build of {src.name} failed; falling back to "
                  f"Python: {err}", RuntimeWarning, stacklevel=3)
    return False


def _ensure_built(src: pathlib.Path, out: pathlib.Path) -> bool:
    """Build ``out`` from ``src`` unless it is fresh. One process builds
    while concurrent first importers wait on a lock file beside it and
    then find the fresh binary."""
    if not _is_stale(src, out):
        return True
    import fcntl

    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out.parent / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        return not _is_stale(src, out) or _build(src, out)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.dir_new.argtypes = [c.c_int64]
    lib.dir_new.restype = c.c_void_p
    lib.dir_free.argtypes = [c.c_void_p]
    lib.dir_free.restype = None
    lib.dir_size.argtypes = [c.c_void_p]
    lib.dir_size.restype = c.c_int64
    lib.dir_free_count.argtypes = [c.c_void_p]
    lib.dir_free_count.restype = c.c_int64
    lib.dir_resolve_batch.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int64,
        c.POINTER(c.c_int32)]
    lib.dir_resolve_batch.restype = c.c_int64
    lib.dir_lookup.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.dir_lookup.restype = c.c_int32
    lib.dir_remove_slots.argtypes = [c.c_void_p, c.POINTER(c.c_int32),
                                     c.c_int64]
    lib.dir_remove_slots.restype = c.c_int64
    lib.dir_add_slots.argtypes = [c.c_void_p, c.c_int32, c.c_int32]
    lib.dir_add_slots.restype = None
    lib.dir_insert.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.c_int32]
    lib.dir_insert.restype = c.c_int32
    lib.dir_set_free.argtypes = [c.c_void_p, c.POINTER(c.c_int32), c.c_int64]
    lib.dir_set_free.restype = None
    lib.dir_arena_bytes.argtypes = [c.c_void_p]
    lib.dir_arena_bytes.restype = c.c_int64
    lib.dir_dump.argtypes = [c.c_void_p, c.c_char_p, c.POINTER(c.c_int64),
                             c.POINTER(c.c_int32)]
    lib.dir_dump.restype = c.c_int64
    lib.dir_route_batch.argtypes = [
        c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_int32,
        c.POINTER(c.c_int32)]
    lib.dir_route_batch.restype = None
    lib.dir_resolve_sharded_batch.argtypes = [
        c.c_char_p, c.POINTER(c.c_int64), c.c_int64,
        c.POINTER(c.c_void_p), c.c_int32, c.POINTER(c.c_int32),
        c.POINTER(c.c_int32)]
    lib.dir_resolve_sharded_batch.restype = c.c_int64
    lib.dir_fp64_batch.argtypes = [
        c.c_char_p, c.POINTER(c.c_int64), c.c_int64,
        c.POINTER(c.c_uint32)]
    lib.dir_fp64_batch.restype = c.c_int64
    try:
        lib.dir_resolve_pylist.argtypes = [c.c_void_p, c.py_object,
                                           c.POINTER(c.c_int32)]
        lib.dir_resolve_pylist.restype = c.c_int64
        lib.dir_route_pylist.argtypes = [c.py_object, c.c_int32,
                                         c.POINTER(c.c_int32)]
        lib.dir_route_pylist.restype = c.c_int64
        lib.dir_resolve_sharded_pylist.argtypes = [
            c.py_object, c.POINTER(c.c_void_p), c.c_int32,
            c.POINTER(c.c_int32), c.POINTER(c.c_int32)]
        lib.dir_resolve_sharded_pylist.restype = c.c_int64
        lib.dir_fp64_pylist.argtypes = [c.py_object,
                                        c.POINTER(c.c_uint32)]
        lib.dir_fp64_pylist.restype = c.c_int64
        lib.has_pylist = True
    except AttributeError:  # built without Python.h
        lib.has_pylist = False
    return lib


def load_directory_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the native directory; ``None`` on any
    failure — callers must fall back to the Python implementation."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    with _LOAD_LOCK:
        if _TRIED:
            return _LIB
        return _load_directory_locked()


def _load_directory_locked() -> ctypes.CDLL | None:
    # _TRIED is published LAST (see the tail): the unlocked fast path in
    # load_directory_lib reads it before taking the lock, so setting it
    # before the build/load completes would hand concurrent first
    # callers a permanent None for a library that is about to exist.
    global _LIB, _TRIED
    if os.environ.get("DRL_TPU_NO_NATIVE"):
        _TRIED = True
        return None
    src = _REPO_NATIVE / "directory.cc"
    out = _out_path("_directory.so")
    try:
        if not src.exists():
            return None
        if not _ensure_built(src, out):
            return None
        # PyDLL: calls hold the GIL, required for dir_resolve_pylist (which
        # reads str objects); the remaining calls are short host ops already
        # serialized under the store lock, so no parallelism is lost.
        _LIB = _bind(ctypes.PyDLL(str(out)))
    except Exception:
        _LIB = None
    finally:
        _TRIED = True
    return _LIB


_FE_LIB: ctypes.CDLL | None = None
_FE_TRIED = False


def _bind_frontend(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.fe_start.argtypes = [c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int]
    lib.fe_start.restype = c.c_void_p
    lib.fe_port.argtypes = [c.c_void_p]
    lib.fe_port.restype = c.c_int
    lib.fe_wait.argtypes = [c.c_void_p, c.c_int]
    lib.fe_wait.restype = c.c_int
    lib.fe_batch_id.argtypes = [c.c_void_p]
    lib.fe_batch_id.restype = c.c_longlong
    lib.fe_batch_n.argtypes = [c.c_void_p]
    lib.fe_batch_n.restype = c.c_int
    lib.fe_batch_key_bytes.argtypes = [c.c_void_p]
    lib.fe_batch_key_bytes.restype = c.c_longlong
    lib.fe_batch_copy.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        c.POINTER(c.c_uint8), c.POINTER(c.c_uint32), c.POINTER(c.c_uint64),
        c.POINTER(c.c_double), c.POINTER(c.c_double)]
    lib.fe_batch_copy.restype = None
    lib.fe_complete.argtypes = [c.c_void_p, c.c_longlong,
                                c.POINTER(c.c_uint8), c.POINTER(c.c_double)]
    lib.fe_complete.restype = None
    lib.fe_fail.argtypes = [c.c_void_p, c.c_longlong, c.c_char_p]
    lib.fe_fail.restype = None
    lib.fe_pt_conn.argtypes = [c.c_void_p]
    lib.fe_pt_conn.restype = c.c_longlong
    lib.fe_pt_len.argtypes = [c.c_void_p]
    lib.fe_pt_len.restype = c.c_int
    lib.fe_pt_copy.argtypes = [c.c_void_p, c.c_char_p]
    lib.fe_pt_copy.restype = None
    lib.fe_send.argtypes = [c.c_void_p, c.c_uint64, c.c_char_p, c.c_int]
    lib.fe_send.restype = None
    lib.fe_set_authed.argtypes = [c.c_void_p, c.c_uint64, c.c_int]
    lib.fe_set_authed.restype = None
    lib.fe_close_conn.argtypes = [c.c_void_p, c.c_uint64]
    lib.fe_close_conn.restype = None
    lib.fe_counts.argtypes = [c.c_void_p, c.POINTER(c.c_longlong),
                              c.POINTER(c.c_longlong),
                              c.POINTER(c.c_longlong)]
    lib.fe_counts.restype = None
    lib.fe_hist.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
    lib.fe_hist.restype = c.c_longlong
    lib.fe_hist_reset.argtypes = [c.c_void_p]
    lib.fe_hist_reset.restype = None
    try:
        lib.fe_stage_hist.argtypes = [c.c_void_p, c.c_int,
                                      c.POINTER(c.c_uint64),
                                      c.POINTER(c.c_double)]
        lib.fe_stage_hist.restype = c.c_longlong
        lib.has_stage_hist = True
    except AttributeError:  # stale binary without the stage-hist ABI
        lib.has_stage_hist = False
    try:
        lib.fe_batch_traced_n.argtypes = [c.c_void_p]
        lib.fe_batch_traced_n.restype = c.c_int
        lib.fe_batch_traces.argtypes = [c.c_void_p, c.POINTER(c.c_uint64),
                                        c.POINTER(c.c_uint64),
                                        c.POINTER(c.c_uint64),
                                        c.POINTER(c.c_uint8)]
        lib.fe_batch_traces.restype = None
        lib.fe_trace_harvest.argtypes = [c.c_void_p,
                                         c.POINTER(c.c_uint64), c.c_int]
        lib.fe_trace_harvest.restype = c.c_int
        lib.has_trace = True
    except AttributeError:  # stale binary without the trace ABI
        lib.has_trace = False
    try:
        lib.fe_has_row_skip.argtypes = []
        lib.fe_has_row_skip.restype = c.c_int
        lib.has_row_skip = True
    except AttributeError:
        # Stale binary whose fe_complete would read the kRowSkip
        # sentinel as "granted" — Python must fall back to deny-only
        # gating on the batch lane.
        lib.has_row_skip = False
    lib.fe_stop.argtypes = [c.c_void_p]
    lib.fe_stop.restype = None
    lib.fe_free.argtypes = [c.c_void_p]
    lib.fe_free.restype = None
    try:
        # Round 11 (multi-shard front-end): N epoll shards accepting on
        # SO_REUSEPORT listeners bound to one port. fe_shard hands out
        # per-shard sub-handles every fe_* entry accepts; stats/harvest
        # calls aggregate across shards for the Frontend handle and
        # slice per shard for a sub-handle. A stale binary without
        # these exports serves single-shard (has_shards gates it).
        lib.fe_start_sharded.argtypes = [c.c_char_p, c.c_int, c.c_int,
                                         c.c_int, c.c_int, c.c_int,
                                         c.c_int]
        lib.fe_start_sharded.restype = c.c_void_p
        lib.fe_shard_count.argtypes = [c.c_void_p]
        lib.fe_shard_count.restype = c.c_int
        lib.fe_shard.argtypes = [c.c_void_p, c.c_int]
        lib.fe_shard.restype = c.c_void_p
        lib.fe_lg_bulk.argtypes = [
            c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
            c.c_int, c.c_double, c.c_double, c.POINTER(c.c_double),
            c.POINTER(c.c_longlong), c.POINTER(c.c_longlong),
            c.POINTER(c.c_longlong)]
        lib.fe_lg_bulk.restype = c.c_int
        lib.has_shards = True
    except AttributeError:  # stale binary without the shard ABI
        lib.has_shards = False
    lib.fe_loadgen.argtypes = [
        c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int, c.c_double,
        c.c_double, c.c_int, c.POINTER(c.c_double),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.fe_loadgen.restype = c.c_int
    try:
        lib.fe_t0_configure.argtypes = [
            c.c_void_p, c.c_int, c.c_double, c.c_double, c.c_double,
            c.c_int, c.c_int]
        lib.fe_t0_configure.restype = c.c_int
        lib.fe_t0_harvest.argtypes = [
            c.c_void_p, c.c_char_p, c.c_int, c.POINTER(c.c_int32),
            c.POINTER(c.c_double), c.POINTER(c.c_double),
            c.POINTER(c.c_double), c.c_int]
        lib.fe_t0_harvest.restype = c.c_int
        lib.fe_t0_ack.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_int32),
            c.POINTER(c.c_double), c.POINTER(c.c_double),
            c.POINTER(c.c_double), c.c_int]
        lib.fe_t0_ack.restype = None
        lib.fe_t0_counts.argtypes = [c.c_void_p, c.POINTER(c.c_longlong)]
        lib.fe_t0_counts.restype = None
        lib.has_tier0 = True
    except AttributeError:  # stale binary without the tier-0 ABI
        lib.has_tier0 = False
    try:
        # Round 7 (live config mutation): retire one (cap, rate)
        # config's replicas, returning their un-harvested grants.
        lib.fe_t0_retire.argtypes = [
            c.c_void_p, c.c_double, c.c_double, c.c_char_p, c.c_int,
            c.POINTER(c.c_int32), c.POINTER(c.c_double), c.c_int]
        lib.fe_t0_retire.restype = c.c_int
        lib.has_t0_retire = True
    except AttributeError:  # stale binary without the retire ABI
        lib.has_t0_retire = False
    try:
        # Round 18 (conservation audit plane): per-slice cumulative
        # locally-granted tokens — the C-side ε-consumption witness.
        lib.fe_t0_eps.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                  c.c_int]
        lib.fe_t0_eps.restype = c.c_int
        lib.has_t0_eps = True
    except AttributeError:  # stale binary without the eps ABI
        lib.has_t0_eps = False
    try:
        # Round 8 (native bulk lane): OP_ACQUIRE_MANY parses, tier-0
        # decides, and RESP_BULK encodes in C; fe_wait returns 3 for a
        # residue job. Armed explicitly via fe_bulk_configure so a new
        # binary under an older pump keeps the passthrough behavior.
        lib.fe_bulk_configure.argtypes = [c.c_void_p, c.c_int, c.c_int,
                                          c.c_int]
        lib.fe_bulk_configure.restype = c.c_int
        lib.fe_bulk_id.argtypes = [c.c_void_p]
        lib.fe_bulk_id.restype = c.c_longlong
        lib.fe_bulk_meta.argtypes = [c.c_void_p, c.POINTER(c.c_uint64),
                                     c.POINTER(c.c_double)]
        lib.fe_bulk_meta.restype = None
        lib.fe_bulk_ptrs.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
        lib.fe_bulk_ptrs.restype = None
        lib.fe_bulk_complete.argtypes = [c.c_void_p, c.c_longlong,
                                         c.POINTER(c.c_uint8),
                                         c.POINTER(c.c_double)]
        lib.fe_bulk_complete.restype = None
        lib.fe_bulk_discard.argtypes = [c.c_void_p, c.c_longlong]
        lib.fe_bulk_discard.restype = None
        lib.fe_bulk_fail.argtypes = [c.c_void_p, c.c_longlong, c.c_char_p]
        lib.fe_bulk_fail.restype = None
        lib.fe_bulk_counts.argtypes = [c.c_void_p,
                                       c.POINTER(c.c_longlong)]
        lib.fe_bulk_counts.restype = None
        lib.fe_hot_harvest.argtypes = [
            c.c_void_p, c.c_char_p, c.c_int, c.POINTER(c.c_int32),
            c.POINTER(c.c_double), c.c_int]
        lib.fe_hot_harvest.restype = c.c_int
        lib.has_bulk = True
    except AttributeError:  # stale binary without the bulk ABI
        lib.has_bulk = False
    try:
        # Round 16 (io_uring data plane): fe_start_sharded2 is
        # fe_start_sharded plus an explicit transport mode (URING_OFF /
        # URING_ON / URING_SQPOLL module constants); fe_uring_* expose
        # the runtime probe, per-shard transport status + fallback
        # reason, and ring counters; fe_lg_bulk_uring is the bulk
        # loadgen's uring submission path (returns -2 when the ring is
        # unavailable — callers fall back to fe_lg_bulk). A stale
        # binary without these exports serves epoll-only (has_uring
        # gates it; the epoll lane is byte-identical by contract).
        lib.fe_start_sharded2.argtypes = [c.c_char_p, c.c_int, c.c_int,
                                          c.c_int, c.c_int, c.c_int,
                                          c.c_int, c.c_int]
        lib.fe_start_sharded2.restype = c.c_void_p
        lib.fe_uring_available.argtypes = []
        lib.fe_uring_available.restype = c.c_int
        lib.fe_uring_probe.argtypes = [c.c_char_p, c.c_int]
        lib.fe_uring_probe.restype = c.c_int
        lib.fe_uring_shards.argtypes = [c.c_void_p]
        lib.fe_uring_shards.restype = c.c_int
        lib.fe_uring_reason.argtypes = [c.c_void_p, c.c_int, c.c_char_p,
                                        c.c_int]
        lib.fe_uring_reason.restype = c.c_int
        lib.fe_uring_counts.argtypes = [c.c_void_p,
                                        c.POINTER(c.c_longlong)]
        lib.fe_uring_counts.restype = None
        lib.fe_lg_bulk_uring.argtypes = [
            c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
            c.c_int, c.c_double, c.c_double, c.POINTER(c.c_double),
            c.POINTER(c.c_longlong), c.POINTER(c.c_longlong),
            c.POINTER(c.c_longlong)]
        lib.fe_lg_bulk_uring.restype = c.c_int
        lib.has_uring = True
    except AttributeError:  # stale binary without the uring ABI
        lib.has_uring = False
    return lib


def load_frontend_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the native serving front-end
    (``native/frontend.cc``); ``None`` on any failure — the server then
    falls back to the asyncio socket path. Loaded as plain ``CDLL`` (NOT
    PyDLL): its blocking ``fe_wait`` must release the GIL so the pump
    thread's wait never stalls the event loop."""
    global _FE_LIB, _FE_TRIED
    if _FE_TRIED:
        return _FE_LIB
    with _LOAD_LOCK:
        if _FE_TRIED:
            return _FE_LIB
        return _load_frontend_locked()


def _load_frontend_locked() -> ctypes.CDLL | None:
    # Same publication order as _load_directory_locked: _FE_TRIED last.
    global _FE_LIB, _FE_TRIED
    if os.environ.get("DRL_TPU_NO_NATIVE"):
        _FE_TRIED = True
        return None
    src = _REPO_NATIVE / "frontend.cc"
    out = _out_path("_frontend.so")
    try:
        if not src.exists():
            return None
        if not _ensure_built(src, out):
            return None
        _FE_LIB = _bind_frontend(ctypes.CDLL(str(out)))
    except Exception:
        _FE_LIB = None
    finally:
        _FE_TRIED = True
    return _FE_LIB
