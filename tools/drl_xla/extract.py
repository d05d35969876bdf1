"""Extraction: find every jitted admission kernel, rebuild its operand
shapes, and trace it to a compiled artifact.

Discovery is AST-level (``@jax.jit`` / ``@partial(jax.jit, ...)``
decorators in the four ``ops/`` modules, launch sites in the two
``runtime/`` stores), so a kernel the analyzers never saw is a
*structural* failure — exit 2, never a fake clean. Shapes are derived
from each kernel's signature plus the packed-operand layouts the bodies
themselves encode (``packed[3]`` ⇒ a 4-row flush operand,
``_unpack_rows`` ⇒ the scan kernels' 5-byte rows, ``[..., 2]`` off an
``astype`` alias ⇒ 3-byte trailing rows): an operand the deriver
cannot place is an :class:`ExtractionError`, not a skip.

Tracing happens under ``JAX_PLATFORMS=cpu``. The properties the
analyzers read — jaxpr primitive counts, input→output aliasing
attributes in the lowered StableHLO, jit cache entries — are decided at
trace/lowering time and are platform-portable; only wall-clock is not,
and the ledger makes no wall-clock claims (docs/DESIGN.md §23).
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import importlib
import importlib.util
import pathlib
import re
import sys
import warnings

__all__ = [
    "DIMS", "KERNEL_FLOOR", "LAUNCH_SITE_FLOOR", "OPS_FILES",
    "RUNTIME_FILES", "ExtractionError", "KernelDecl", "Leaf",
    "KernelArtifact", "discover", "launch_sites", "trace_kernels",
    "source_hashes",
]

#: Representative trace dims: B requests/flush, K scan steps, N table
#: slots. Small on purpose — op COUNTS, aliasing, and cache entries are
#: shape-independent for these kernels (everything is vectorized over
#: B/N; nothing unrolls per element), and small shapes keep the full
#: 36-kernel trace in seconds. N != B so an aval match between a table
#: leaf and an output is never a batch-array coincidence.
DIMS = {"B": 8, "K": 2, "N": 64}

#: ops/ holds 36 jitted kernels today (23 kernels.py + 12
#: fp_directory.py + 1 pallas). The floor is the drl-verify
#: extractor-richness posture: fewer extracted kernels means the
#: extractor went blind (decorator refactor, file move), and a blind
#: extractor must fail loudly (exit 2), not report a clean ledger.
KERNEL_FLOOR = 30
#: runtime/store.py + runtime/fp_store.py dispatch those kernels from
#: 39 call sites today; same posture.
LAUNCH_SITE_FLOOR = 17

OPS_FILES = ("ops/kernels.py", "ops/fp_directory.py",
             "ops/bucket_math.py", "ops/pallas_kernels.py")
RUNTIME_FILES = ("runtime/store.py", "runtime/fp_store.py")
_PKG_DIR = ("distributedratelimiting", "redis_tpu")


class ExtractionError(RuntimeError):
    """The extractor cannot see (missing file, un-derivable operand,
    un-jitted symbol). Always exit 2 — never degrade to a clean run."""


@dataclasses.dataclass(frozen=True)
class KernelDecl:
    """One ``@jax.jit``-decorated function, as the AST sees it."""

    name: str
    file: str                 # repo-relative
    line: int
    path: pathlib.Path        # absolute source path
    donate_argnums: tuple[int, ...]
    static_argnames: tuple[str, ...]
    params: tuple[tuple[str, str | None], ...]   # (name, annotation)

    @property
    def key(self) -> str:
        return f"{self.file}::{self.name}"


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One flattened array argument of a traced kernel."""

    name: str                 # e.g. "state.tokens"
    index: int                # position in the flattened operand list
    shape: tuple[int, ...]
    dtype: str
    table: bool               # N-sized resident state (HBM in prod)
    donated: bool             # per the jit wrapper (lowered.args_info)


@dataclasses.dataclass
class KernelArtifact:
    """A kernel traced to its compiled artifact."""

    decl: KernelDecl
    fn: object                # the live jitted callable
    args1: tuple
    args2: tuple              # same shapes/dtypes, different values
    statics: dict             # statics for args1 (trace/lowering call)
    statics2: dict            # variant statics — the retrace probe's
                              # second call (differs iff a data value
                              # is routed through static_argnames)
    leaves: tuple[Leaf, ...]
    jaxpr: object             # ClosedJaxpr
    lowered_text: str
    kept: tuple[int, ...]     # flat arg indices surviving DCE
    aliased: frozenset[int]   # flat arg indices with tf.aliasing_output
    out_avals: tuple[tuple[tuple[int, ...], str], ...]


# -- AST discovery ----------------------------------------------------------

def _literal(node: ast.AST):
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _jit_call_info(dec: ast.expr) -> "dict | None":
    """Recognize ``@jax.jit``, ``@jit``, ``@jax.jit(...)`` and
    ``@(functools.)partial(jax.jit, ...)``; return the decorator's
    keyword map (donate_argnums / static_argnames live there)."""
    def is_jit_ref(node: ast.expr) -> bool:
        if isinstance(node, ast.Name):
            return node.id == "jit"
        if isinstance(node, ast.Attribute):
            return node.attr == "jit"
        return False

    if is_jit_ref(dec):
        return {}
    if not isinstance(dec, ast.Call):
        return None
    if is_jit_ref(dec.func):
        return {kw.arg: kw.value for kw in dec.keywords if kw.arg}
    fname = dec.func.attr if isinstance(dec.func, ast.Attribute) else (
        dec.func.id if isinstance(dec.func, ast.Name) else "")
    if fname == "partial" and dec.args and is_jit_ref(dec.args[0]):
        return {kw.arg: kw.value for kw in dec.keywords if kw.arg}
    return None


def _decl_from_def(node: ast.FunctionDef, file: str,
                   path: pathlib.Path) -> "KernelDecl | None":
    for dec in node.decorator_list:
        kws = _jit_call_info(dec)
        if kws is None:
            continue
        donate = _literal(kws["donate_argnums"]) \
            if "donate_argnums" in kws else ()
        if isinstance(donate, int):
            donate = (donate,)
        statics = _literal(kws["static_argnames"]) \
            if "static_argnames" in kws else ()
        if isinstance(statics, str):
            statics = (statics,)
        statics = list(statics or ())
        params = tuple(
            (a.arg, ast.unparse(a.annotation) if a.annotation else None)
            for a in node.args.args)
        # static_argnums names the same contract by position — fold it
        # into the name set so the operand model skips those too.
        nums = _literal(kws["static_argnums"]) \
            if "static_argnums" in kws else ()
        if isinstance(nums, int):
            nums = (nums,)
        for i in nums or ():
            if 0 <= i < len(params):
                statics.append(params[i][0])
        return KernelDecl(
            name=node.name, file=file, line=node.lineno, path=path,
            donate_argnums=tuple(donate or ()),
            static_argnames=tuple(statics or ()), params=params)
    return None


def discover(root: pathlib.Path, *, kernel_floor: int = KERNEL_FLOOR
             ) -> "list[KernelDecl]":
    """Every jitted kernel in the ops/ modules, floor-checked."""
    base = root.joinpath(*_PKG_DIR)
    decls: list[KernelDecl] = []
    seen_any_file = False
    for relf in OPS_FILES:
        path = base / relf
        if not path.exists():
            continue
        seen_any_file = True
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as exc:
            raise ExtractionError(f"cannot parse {relf}: {exc}") from exc
        file = str(pathlib.PurePosixPath(*_PKG_DIR) / relf)
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                d = _decl_from_def(node, file, path)
                if d is not None:
                    decls.append(d)
    if not seen_any_file:
        raise ExtractionError(
            f"no ops/ modules found under {base} — the extractor is "
            "pointed at the wrong tree")
    if len(decls) < kernel_floor:
        raise ExtractionError(
            f"extracted only {len(decls)} jitted kernels from ops/ "
            f"(floor {kernel_floor}) — the decorator extractor has gone "
            "blind; a clean report from a blind extractor is worthless")
    return decls


def launch_sites(root: pathlib.Path, decls: "list[KernelDecl]", *,
                 site_floor: int = LAUNCH_SITE_FLOOR
                 ) -> "dict[str, list[tuple[str, int]]]":
    """Kernel name → [(file, line)] dispatch sites in the runtime
    stores (``K.acquire_batch_packed(...)`` or a direct import)."""
    names = {d.name for d in decls}
    sites: dict[str, list[tuple[str, int]]] = {}
    base = root.joinpath(*_PKG_DIR)
    total = 0
    for relf in RUNTIME_FILES:
        path = base / relf
        if not path.exists():
            raise ExtractionError(f"launch-site file missing: {relf}")
        file = str(pathlib.PurePosixPath(*_PKG_DIR) / relf)
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            called = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if called in names:
                sites.setdefault(called, []).append((file, node.lineno))
                total += 1
    if total < site_floor:
        raise ExtractionError(
            f"found only {total} kernel launch sites in runtime/ "
            f"(floor {site_floor}) — the launch-site extractor has gone "
            "blind")
    return sites


# -- operand-layout derivation ----------------------------------------------

#: Fused-operand unpack helpers whose layout is part of the wire
#: contract (bytes-per-decision): helper name → (trailing dim, dtype).
_HELPER_LAYOUTS = {
    "_unpack_rows": (5, "uint8"),        # pack_rows:    u8[..., 5 | 8]
    "_unpack_fp12": (3, "uint32"),       # pack_fp12:    u32[..., 3]
}


def _operand_layout(tree: ast.Module, func: ast.FunctionDef,
                    pname: str, _depth: int = 0):
    """How does this kernel index its packed operand? Returns
    ``("rows", R)`` for the i32[R, B] flush layouts or
    ``("trailing", T, dtype)`` for byte-packed trailing-dim layouts —
    derived from the subscripts the body (or the unpack helper it
    calls) actually performs."""
    if _depth > 3:
        return None
    aliases = {pname}
    max_row = None
    trailing = None
    for node in ast.walk(func):
        # track `p = packed.astype(...)` style aliases
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and isinstance(node.value.func.value, ast.Name)
                and node.value.func.value.id in aliases):
            aliases.add(node.targets[0].id)
    for node in ast.walk(func):
        if isinstance(node, ast.Subscript) and \
                isinstance(node.value, ast.Name) and \
                node.value.id in aliases:
            sl = node.slice
            idxs = sl.elts if isinstance(sl, ast.Tuple) else [sl]
            first = idxs[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, int):
                max_row = max(max_row or 0, first.value)
            elif isinstance(first, ast.Constant) and \
                    first.value is Ellipsis and len(idxs) > 1 and \
                    isinstance(idxs[-1], ast.Constant) and \
                    isinstance(idxs[-1].value, int):
                trailing = max(trailing or 0, idxs[-1].value)
        if isinstance(node, ast.Call):
            callee = node.func.id if isinstance(node.func, ast.Name) \
                else None
            if callee is None:
                continue
            arg_pos = [i for i, a in enumerate(node.args)
                       if isinstance(a, ast.Name) and a.id in aliases]
            if not arg_pos:
                continue
            if callee in _HELPER_LAYOUTS:
                t, dt = _HELPER_LAYOUTS[callee]
                return ("trailing", t, dt)
            helper = next((n for n in tree.body
                           if isinstance(n, ast.FunctionDef)
                           and n.name == callee), None)
            if helper is not None and arg_pos[0] < len(helper.args.args):
                inner = _operand_layout(
                    tree, helper, helper.args.args[arg_pos[0]].arg,
                    _depth + 1)
                if inner is not None:
                    return inner
    if trailing is not None:
        return ("trailing", trailing + 1, "uint8")
    if max_row is not None:
        return ("rows", max_row + 1)
    # scan kernels destructure the stacked operand inside a nested scan
    # body under a local name (`(fused, now) = xs`); the unpack-helper
    # call is still the layout authority, whatever the local is called.
    if pname.startswith("fused"):
        for node in ast.walk(func):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id in _HELPER_LAYOUTS:
                t, dt = _HELPER_LAYOUTS[node.func.id]
                return ("trailing", t, dt)
    return None


# -- representative operand construction ------------------------------------

_STATE_FIELD_DTYPES = {
    "tokens": "float32", "last_ts": "int32", "exists": "bool",
    "value": "float32", "period": "float32",
    "prev_count": "float32", "curr_count": "float32",
    "window_idx": "int32", "active": "int32",
}


def _resolve_annotation(module, annotation: str):
    obj = module
    for part in annotation.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            return None
    return obj


def _build_args(decl: KernelDecl, module, tree: ast.Module,
                func: ast.FunctionDef, dims: dict, variant: int):
    """Concrete operands for one trace. ``variant`` perturbs every
    numeric value (same shapes/dtypes) — the retrace probe's second
    call. Returns (args, leaves, statics) where leaves mirrors jax's
    flattening order (argument order, NamedTuple field order).

    Statics get variant-dependent values too (except ``interpret``,
    which is a genuine mode flag): a data operand routed through
    static_argnames/static_argnums keys the jit cache per value, and
    the retrace probe can only see that if the probe actually varies
    the static — that IS the leak xla-retrace exists to catch."""
    import numpy as np

    B, K, N = dims["B"], dims["K"], dims["N"]
    v = variant
    pnames = [p for p, _ in decl.params]
    scanned = "nows_k" in pnames
    args: list = []
    leaves: list[tuple[str, bool]] = []   # (leaf name, table?)
    statics: dict = {}
    for pname in decl.static_argnames:
        if pname == "interpret":
            statics[pname] = True
        elif pname in pnames:
            statics[pname] = 64 + v

    def arr(value, dtype, shape=None):
        a = np.asarray(value, dtype=dtype)
        return a if shape is None else np.broadcast_to(a, shape).copy()

    def slots(shape):
        flat = (np.arange(int(np.prod(shape))) + v) % N
        return flat.reshape(shape).astype(np.int32)

    for pname, annotation in decl.params:
        if pname in decl.static_argnames:
            continue   # statics are not operands; defaults apply
        state_cls = _resolve_annotation(module, annotation) \
            if annotation else None
        if state_cls is not None and hasattr(state_cls, "_fields"):
            fields = []
            for f in state_cls._fields:
                dt = _STATE_FIELD_DTYPES.get(f)
                if dt is None:
                    raise ExtractionError(
                        f"{decl.key}: state field {annotation}.{f} has "
                        "no dtype rule — teach extract.py its layout")
                if dt == "bool":
                    fields.append(arr(True, np.bool_, (N,)))
                elif dt == "float32":
                    fields.append(arr(1.0 + v, np.float32, (N,)))
                else:
                    fields.append(arr(v, np.int32, (N,)))
                leaves.append((f"{pname}.{f}", True))
            args.append(state_cls(*fields))
            continue
        if pname == "fp":
            args.append(arr(v, np.uint32, (N, 2)))
            leaves.append((pname, True))
        elif pname == "kpair":
            base = (np.arange(B * 2).reshape(B, 2) + 1 + v)
            args.append(base.astype(np.uint32))
            leaves.append((pname, False))
        elif pname == "exists_i8":
            args.append(arr(1, np.int8, (N,)))
            leaves.append((pname, True))
        elif pname in ("tokens", "last_ts", "exists"):
            table = "exists_i8" in pnames   # pallas sweep: N-sized plane
            n = N if table else B
            if pname == "tokens":
                args.append(arr(1.0 + v, np.float32, (n,)))
            elif pname == "last_ts":
                args.append(arr(v, np.int32, (n,)))
            else:
                args.append(arr(True, np.bool_, (n,)))
            leaves.append((pname, table))
        elif pname in ("packed", "fused", "fused_k", "rows"):
            layout = _operand_layout(tree, func, pname)
            if layout is None:
                raise ExtractionError(
                    f"{decl.key}: cannot derive the {pname!r} operand "
                    "layout from the body — teach extract.py (or the "
                    "kernel) its packing")
            if layout[0] == "rows":
                rows = np.full((layout[1], B), 1 + v, np.int32)
                rows[0] = slots((B,))
                args.append(rows)
            else:
                _, t, dt = layout
                shape = (K, B, t) if scanned else (B, t)
                fill = (1 + v) & 0x3
                args.append(arr(fill, np.dtype(dt), shape))
            leaves.append((pname, False))
        elif pname.endswith("_k"):
            if pname == "nows_k":
                args.append((100 + v + np.arange(K) * 10
                             ).astype(np.int32))
            elif pname.startswith("valid"):
                args.append(arr(True, np.bool_, (K, B)))
            elif pname.startswith("slots"):
                args.append(slots((K, B)))
            else:
                args.append(arr(1 + v, np.int32, (K, B)))
            leaves.append((pname, False))
        elif pname == "slots":
            args.append(slots((B,)))
            leaves.append((pname, False))
        elif pname == "valid":
            args.append(arr(True, np.bool_, (B,)))
            leaves.append((pname, False))
        elif pname in ("counts", "deltas", "limits"):
            args.append(arr(1 + v, np.int32, (B,)))
            leaves.append((pname, False))
        elif pname in ("amounts", "local_counts", "prefix",
                       "prev_count", "curr_count"):
            args.append(arr(1.0 + v, np.float32, (B,)))
            leaves.append((pname, False))
        elif pname == "window_idx":
            args.append(arr(v, np.int32, (B,)))
            leaves.append((pname, False))
        elif pname == "now":
            args.append(np.int32(100 + v))
            leaves.append((pname, False))
        elif "capacity" in pname or "limit" in pname:
            args.append(np.float32(8.0 + v))
            leaves.append((pname, False))
        elif "rate" in pname or "decay" in pname:
            args.append(np.float32(0.5 + 0.25 * v))
            leaves.append((pname, False))
        elif "ticks" in pname or "windows" in pname:
            args.append(np.int32(64 + v))
            leaves.append((pname, False))
        else:
            raise ExtractionError(
                f"{decl.key}: no shape rule for parameter {pname!r} — "
                "a kernel the extractor cannot operand-model is a "
                "kernel the analyzers cannot see; add the rule")
    return tuple(args), leaves, statics


# -- tracing ----------------------------------------------------------------

_ARG_ATTR_RE = re.compile(
    r"%arg(\d+): tensor<[^>]*>\s*(\{[^}]*\})?")


def _parse_aliased(text: str) -> "frozenset[int]":
    """MLIR positions (0-based, post-DCE) whose parameter carries a
    ``tf.aliasing_output`` attribute in the lowered module. Typed
    ``%argN: tensor<...>`` bindings only occur in function signatures;
    the public @main comes first, so first occurrence per index wins
    over any private helper func reusing the numbering."""
    seen: dict[int, bool] = {}
    for m in _ARG_ATTR_RE.finditer(text):
        idx = int(m.group(1))
        if idx not in seen:
            seen[idx] = bool(m.group(2) and
                             "tf.aliasing_output" in m.group(2))
    return frozenset(i for i, ok in seen.items() if ok)


def _load_module(decl_path: pathlib.Path, root: pathlib.Path):
    """Import the kernel module. The real tree imports by package name
    (so the analyzers and the serving path share the SAME jit objects
    and caches); any other root gets an isolated file-load."""
    base = root.joinpath(*_PKG_DIR)
    try:
        relative = decl_path.resolve().relative_to(base.resolve())
        dotted = ".".join(_PKG_DIR + tuple(relative.with_suffix("").parts))
        mod = importlib.import_module(dotted)
        if pathlib.Path(mod.__file__).resolve() == decl_path.resolve():
            return mod
    except (ValueError, ImportError):
        pass
    tag = hashlib.sha1(str(decl_path).encode()).hexdigest()[:10]
    name = f"_drl_xla_target_{decl_path.stem}_{tag}"
    spec = importlib.util.spec_from_file_location(name, decl_path)
    if spec is None or spec.loader is None:
        raise ExtractionError(f"cannot load module {decl_path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def trace_kernels(decls: "list[KernelDecl]", root: pathlib.Path,
                  dims: "dict | None" = None) -> "list[KernelArtifact]":
    """Trace every discovered kernel to jaxpr + lowered StableHLO.
    Any failure is an ExtractionError: a kernel that cannot be traced
    is a kernel whose artifact nobody is checking."""
    import jax

    dims = dims or DIMS
    artifacts: list[KernelArtifact] = []
    by_path: dict[pathlib.Path, list[KernelDecl]] = {}
    for d in decls:
        by_path.setdefault(d.path, []).append(d)
    for path, group in sorted(by_path.items()):
        module = _load_module(path, root)
        tree = ast.parse(path.read_text())
        funcs = {n.name: n for n in tree.body
                 if isinstance(n, ast.FunctionDef)}
        for decl in group:
            fn = getattr(module, decl.name, None)
            if fn is None or not hasattr(fn, "lower"):
                raise ExtractionError(
                    f"{decl.key}: decorated with jax.jit in the AST but "
                    "not a jit wrapper at runtime — the artifact the "
                    "tree ships is not the one the source claims")
            try:
                args1, leaf_meta, statics = _build_args(
                    decl, module, tree, funcs[decl.name], dims, 0)
                args2, _, statics2 = _build_args(
                    decl, module, tree, funcs[decl.name], dims, 1)
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    lowered = fn.lower(*args1, **statics)
                    text = lowered.as_text()
                    closed = fn.trace(*args1, **statics).jaxpr
            except ExtractionError:
                raise
            except Exception as exc:
                raise ExtractionError(
                    f"{decl.key}: trace failed ({type(exc).__name__}: "
                    f"{exc}) — the operand model no longer matches the "
                    "kernel; fix extract.py's shape rules") from exc
            info_leaves = jax.tree_util.tree_leaves(
                lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))
            flat1 = jax.tree_util.tree_leaves(args1)
            if not (len(info_leaves) == len(flat1) == len(leaf_meta)):
                raise ExtractionError(
                    f"{decl.key}: operand flattening mismatch "
                    f"({len(info_leaves)} vs {len(flat1)} vs "
                    f"{len(leaf_meta)} leaves)")
            try:
                kept = sorted(
                    lowered._lowering.compile_args["kept_var_idx"])
            except Exception:
                kept = list(range(len(flat1)))
            leaves = tuple(
                Leaf(name=nm, index=i, shape=tuple(a.shape),
                     dtype=str(a.dtype), table=tbl,
                     donated=bool(getattr(info, "donated", False)))
                for i, ((nm, tbl), a, info)
                in enumerate(zip(leaf_meta, flat1, info_leaves)))
            out_avals = tuple(
                (tuple(av.shape), str(av.dtype))
                for av in closed.out_avals)
            artifacts.append(KernelArtifact(
                decl=decl, fn=fn, args1=args1, args2=args2,
                statics=statics, statics2=statics2,
                leaves=leaves, jaxpr=closed,
                lowered_text=text, kept=tuple(kept),
                aliased=_parse_aliased(text), out_avals=out_avals))
    return artifacts


def source_hashes(root: pathlib.Path) -> "dict[str, str]":
    """sha256 of every ops/ module the ledger describes — the stamp
    that makes a stale budgets.json a freshness finding (the .so.hash
    sidecar idiom from tools/drl_check/build_freshness.py)."""
    base = root.joinpath(*_PKG_DIR)
    out = {}
    for relf in OPS_FILES:
        path = base / relf
        if path.exists():
            file = str(pathlib.PurePosixPath(*_PKG_DIR) / relf)
            out[file] = hashlib.sha256(path.read_bytes()).hexdigest()
    return out
