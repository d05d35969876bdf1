"""The op-count budget ledger: per-kernel compiled-artifact costs as a
checked-in, ratcheted fact.

``budgets.json`` records, for every jitted admission kernel, the
primitive counts that price the serving path — kernel launches,
gathers, scatters, device-side loops (while + scan), sorts, and the
operand/result counts (host↔device transfers per launch, the r04
lesson). The ratchet: **tightening is auto-accepted** (the ledger is
restamped in place and the improvement becomes the new floor);
**loosening fails loudly** with the per-key diff. That is what turns
"``acquire_hierarchical_packed`` pays two table gathers" from prose
into a recorded fact the ROADMAP-item-1 fused kernel must visibly
beat.

Freshness rides the ``.so.hash`` sidecar idiom
(tools/drl_check/build_freshness.py): the ledger carries the sha256 of
every ops/ source it describes plus the jax version and trace dims.
A ledger whose stamp disagrees with the tree is a finding
(``xla-stale-ledger``) in ``--no-restamp`` mode — never a silent pass.

Counts are *static program size* (each primitive occurrence counted
once, loop bodies not multiplied by trip count) measured on the jaxpr,
recursively through scan/while/cond/pjit sub-jaxprs. No wall-clock
claims — docs/DESIGN.md §23 spells out what the ledger does and does
not prove.
"""

from __future__ import annotations

import hashlib
import json
import pathlib

from tools.drl_check.common import Finding

from tools.drl_xla import extract

__all__ = [
    "BUDGET_KEYS", "ledger_path", "measure", "measure_all", "load",
    "make_ledger", "compare", "ledger_hash", "key_line",
]

#: The budgeted keys. launches/gather/scatter/while/sort are the
#: artifact-shape ratchet; operands/results price host↔device transfer
#: count per launch (ops/kernels.py's own contract; their cost on the
#: chip is not measured).
BUDGET_KEYS = ("launches", "gather", "scatter", "while", "sort",
               "operands", "results")


def ledger_path(root: pathlib.Path) -> pathlib.Path:
    return root / "tools" / "drl_xla" / "budgets.json"


def _subjaxprs(eqn):
    from jax.extend import core
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if isinstance(x, core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, core.Jaxpr):
                yield x


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _iter_eqns(sub)


def measure(artifact: "extract.KernelArtifact") -> "dict[str, int]":
    counts = {k: 0 for k in BUDGET_KEYS}
    counts["launches"] = 1   # one fused dispatch per jitted kernel
    for eqn in _iter_eqns(artifact.jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "gather":
            counts["gather"] += 1
        elif name.startswith("scatter"):
            counts["scatter"] += 1
        elif name in ("while", "scan"):
            counts["while"] += 1
        elif name == "sort":
            counts["sort"] += 1
        elif name == "pallas_call":
            counts["launches"] += 1   # a nested device launch
    counts["operands"] = len(artifact.kept)
    counts["results"] = len(artifact.out_avals)
    return counts


def measure_all(artifacts) -> "dict[str, dict[str, int]]":
    return {a.decl.key: measure(a) for a in artifacts}


def load(path: pathlib.Path) -> "dict | None":
    if not path.exists():
        return None
    try:
        return json.loads(path.read_text())
    except ValueError:
        return {}   # a torn ledger is drift, not a crash


def make_ledger(root: pathlib.Path, measured: "dict[str, dict[str, int]]"
                ) -> dict:
    import jax
    return {
        "stamp": {
            "sources": extract.source_hashes(root),
            "jax": jax.__version__,
            "dims": dict(extract.DIMS),
        },
        "kernels": {k: dict(sorted(v.items()))
                    for k, v in sorted(measured.items())},
    }


def dumps(ledger: dict) -> str:
    return json.dumps(ledger, indent=2, sort_keys=True) + "\n"


def ledger_hash(path: pathlib.Path) -> "str | None":
    """Short content hash of the checked-in ledger — the annotation
    benchmarks/recapture.py stamps on every device-debt row so a
    settled debt names the exact compiled artifacts it measured."""
    if not path.exists():
        return None
    return hashlib.sha256(path.read_bytes()).hexdigest()[:12]


def key_line(path: pathlib.Path, key: str) -> int:
    """Line of a kernel's entry inside budgets.json (findings point at
    the ledger side too — file:line on BOTH sides of the diff)."""
    if not path.exists():
        return 1
    needle = f'"{key}":'
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if needle in line:
            return i
    return 1


def compare(root: pathlib.Path, artifacts, *,
            sites: "dict[str, list[tuple[str, int]]] | None" = None,
            path: "pathlib.Path | None" = None, restamp: bool = True):
    """Measured artifacts vs the checked-in ledger.

    Returns ``(findings, status)``; status is one of ``"clean"``
    (exact match), ``"restamped"`` (drift auto-accepted and written),
    ``"loosened"`` (budget findings emitted, ledger untouched) or
    ``"stale"`` (--no-restamp and the ledger needs a restamp).
    """
    path = path or ledger_path(root)
    measured = measure_all(artifacts)
    fresh = make_ledger(root, measured)
    old = load(path)
    findings: list[Finding] = []
    by_key = {a.decl.key: a for a in artifacts}

    old_kernels = (old or {}).get("kernels", {})
    loosened = False
    for key, counts in sorted(measured.items()):
        recorded = old_kernels.get(key)
        if recorded is None:
            continue   # new kernel: drift, restampable
        worse = {k: (recorded.get(k, 0), counts[k]) for k in BUDGET_KEYS
                 if counts[k] > recorded.get(k, counts[k])}
        if worse:
            loosened = True
            decl = by_key[key].decl
            diff = ", ".join(f"{k} {a}→{b}" for k, (a, b)
                             in sorted(worse.items()))
            related = [(decl.file, decl.line, "kernel definition")]
            for sf, sl in (sites or {}).get(decl.name, [])[:3]:
                related.append((sf, sl, "launch site"))
            findings.append(Finding(
                "xla-budget",
                f"{decl.name}: compiled artifact loosened its op "
                f"budget ({diff}) — the ledger ratchet only moves "
                "down; make the kernel meet its recorded cost, or "
                "restamp deliberately (make xla-budget-restamp) and "
                "say why in the commit",
                str(path.relative_to(root)) if path.is_relative_to(root)
                else str(path),
                key_line(path, key), tuple(related)))
    if loosened:
        return findings, "loosened"

    drift = old is None or old != fresh
    if not drift:
        return [], "clean"
    if restamp:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(dumps(fresh))
        return [], "restamped"
    why = ("no ledger exists" if old is None else
           "stamp or counts no longer match the tree")
    changed = [f for f, h in fresh["stamp"]["sources"].items()
               if (old or {}).get("stamp", {}).get("sources", {})
               .get(f) != h]
    related = tuple((f, 1, "source hash differs from the stamp")
                    for f in changed[:4])
    findings.append(Finding(
        "xla-stale-ledger",
        f"budgets.json is stale ({why}) — the ledger does not "
        "describe the artifacts this tree compiles to; run "
        "`python -m tools.drl_xla` (or `make xla-budget`) to restamp, "
        "then commit the ledger",
        str(path.relative_to(root)) if path.is_relative_to(root)
        else str(path), 1, related))
    return findings, "stale"
