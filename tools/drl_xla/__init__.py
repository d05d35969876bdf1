"""drl-xla — compiled-artifact conformance for the admission kernels.

drl-check (PR 4) lints the AST and drl-verify (PR 14) model-checks the
protocol state machines; this tool closes the remaining gap — what the
~36 ``@jax.jit`` kernels in ``ops/`` actually **compile to**. It
discovers every jitted kernel and its runtime launch sites via ast,
rebuilds representative operands from the signatures and the packed
layouts, traces each kernel to jaxpr + lowered StableHLO under
``JAX_PLATFORMS=cpu``, and runs four analyzers over the artifacts:

- **hot-path purity** (``xla-purity``): no Python callbacks, host
  transfers, or 64-bit promotion reachable in an admission jaxpr;
- **donation conformance** (``xla-donation``): every state-table
  argument both declared donated AND actually aliased in the lowered
  module — an XLA-declined donation is a silent HBM doubling;
- **retrace stability** (``xla-retrace``): two calls, same
  shapes/dtypes, different values ⇒ exactly one jit cache entry;
- **op-count budget ledger** (``xla-budget`` / ``xla-stale-ledger``):
  checked-in per-kernel {launches, gather, scatter, while, sort,
  operands, results} in ``budgets.json`` — tightening auto-restamps,
  loosening fails with the diff.

Posture (drl-check's): exit 0 clean, 1 with file:line findings on both
sides of a diff, 2 when the extractor itself is blind — never a fake
clean. Runbook: docs/OPERATIONS.md §19; contract: docs/DESIGN.md §23.
"""

from __future__ import annotations

import pathlib

from tools.drl_xla import analyzers, budgets, extract

__all__ = ["run_all", "analyzers", "budgets", "extract"]


def run_all(root: "pathlib.Path | None" = None, *, restamp: bool = False,
            ledger: "pathlib.Path | None" = None,
            dims: "dict | None" = None):
    """Full pipeline. Returns ``(findings, report)`` where report maps
    stage names to their artifacts (for the non-vacuity pins in
    tests/test_drl_xla.py). ExtractionError propagates — the CLI turns
    it into exit 2."""
    root = root or pathlib.Path(__file__).resolve().parents[2]
    decls = extract.discover(root)
    sites = extract.launch_sites(root, decls)
    artifacts = extract.trace_kernels(decls, root, dims)
    findings = []
    findings += analyzers.check_purity(artifacts, sites)
    findings += analyzers.check_donation(artifacts, sites)
    findings += analyzers.check_retrace(artifacts, sites)
    budget_findings, status = budgets.compare(
        root, artifacts, sites=sites, path=ledger, restamp=restamp)
    findings += budget_findings
    findings = analyzers.apply_suppressions(findings, root, decls)
    report = {
        "decls": decls, "sites": sites, "artifacts": artifacts,
        "budget_status": status,
        "measured": budgets.measure_all(artifacts),
    }
    return findings, report
