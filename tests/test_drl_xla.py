"""drl-xla gets checked: the compiled-artifact analyzers must (a) pass
the live tree — the repo ships conformant kernels and an exact budget
ledger — and (b) catch each seeded divergence EXACTLY once, with the
right rule and file:line. The seeded matrix traces real jax kernels in
a synthetic ops/ tree (an un-donated table argument, an XLA-declined
donation, an injected pure_callback, a value leaked through
static_argnames, a loosened ledger), so these tests also pin that the
extractor still derives operands for real decorator shapes — a
refactor that blinds it fails the floor test, not just the live one."""

from __future__ import annotations

import json
import pathlib
import textwrap

import pytest

from tools.drl_check.common import INLINE_SUPPRESSIBLE, KNOWN_RULES
from tools.drl_xla import analyzers, budgets, extract, run_all
from tools.drl_xla.__main__ import main as xla_main

ROOT = pathlib.Path(__file__).resolve().parents[1]
LEDGER = ROOT / "tools" / "drl_xla" / "budgets.json"


# -- shared pipelines (traced once per module, not per test) ----------------

@pytest.fixture(scope="module")
def live():
    """The full pipeline against the live tree, ledger frozen
    (restamp=False): any drift must surface as a finding here, never
    as a silent rewrite inside the test suite."""
    findings, report = run_all(ROOT)
    return findings, report


_SEEDED_SRC = textwrap.dedent("""
    import functools
    import jax
    import jax.numpy as jnp

    @jax.jit
    def missed_donation_kernel(fp, now):
        return fp.at[0, 0].set(jnp.uint32(now)), now + jnp.int32(1)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def declined_donation_kernel(fp, now):
        return (fp[0, 0] + jnp.uint32(now)).astype(jnp.int32)

    @jax.jit
    def callback_kernel(counts, now):
        out = jax.pure_callback(
            lambda x: x,
            jax.ShapeDtypeStruct(counts.shape, counts.dtype), counts)
        return out + now

    @functools.partial(jax.jit, static_argnames=("windows",))
    def leaked_scalar_kernel(counts, windows):
        return counts * windows
""")


def _make_root(base: pathlib.Path, src: str) -> pathlib.Path:
    ops = base / "distributedratelimiting" / "redis_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "kernels.py").write_text(src)
    return base


def _def_line(src: str, name: str) -> int:
    for i, line in enumerate(src.splitlines(), start=1):
        if line.startswith(f"def {name}"):
            return i
    raise AssertionError(f"def {name} not in seeded source")


@pytest.fixture(scope="module")
def seeded(tmp_path_factory):
    root = _make_root(tmp_path_factory.mktemp("xla_seeded"), _SEEDED_SRC)
    decls = extract.discover(root, kernel_floor=1)
    arts = extract.trace_kernels(decls, root)
    findings = (analyzers.check_purity(arts)
                + analyzers.check_donation(arts)
                + analyzers.check_retrace(arts))
    return root, arts, findings


# -- the live tree is clean -------------------------------------------------

def test_live_tree_is_clean(live):
    findings, _ = live
    assert findings == [], "\n".join(f.format() for f in findings)


def test_live_ledger_is_exact(live):
    _, report = live
    assert report["budget_status"] == "clean"


def test_extraction_is_rich(live):
    """Non-vacuity: a clean verdict only counts if the extractor saw
    the whole kernel surface. Today's tree holds 36 jitted kernels and
    39 runtime launch sites; the floors trip first on a partial
    regression, this pins the actual population."""
    _, report = live
    assert len(report["decls"]) >= 36 >= extract.KERNEL_FLOOR
    assert sum(len(v) for v in report["sites"].values()) \
        >= 37 >= extract.LAUNCH_SITE_FLOOR
    names = {d.name for d in report["decls"]}
    assert {"acquire_batch_packed", "acquire_hierarchical_packed",
            "fp_debit_batch", "sweep_expired_pallas"} <= names


def test_ledger_stamp_matches_tree():
    """The .so.hash sidecar idiom: the checked-in ledger names the
    exact ops/ sources it measured. A stale stamp here means someone
    edited a kernel without re-running make xla-budget-restamp."""
    ledger = json.loads(LEDGER.read_text())
    assert ledger["stamp"]["sources"] == extract.source_hashes(ROOT)
    assert ledger["stamp"]["dims"] == extract.DIMS


def test_sweep_exists_plane_is_donated_and_aliased(live):
    """Regression pin for the real defect this round fixed:
    sweep_expired_pallas did not donate its exists_i8 occupancy plane,
    double-buffering 1 byte/slot (10 MB transient at 10M slots) on
    every full-table sweep. The fix declares donate_argnums=(2,) — and
    this pin checks the COMPILED artifact, not the decorator: the leaf
    must carry tf.aliasing_output in the lowered StableHLO."""
    _, report = live
    art = next(a for a in report["artifacts"]
               if a.decl.name == "sweep_expired_pallas")
    assert art.decl.donate_argnums == (2,)
    leaf = next(l for l in art.leaves if l.name == "exists_i8")
    assert leaf.donated and leaf.table
    rank = {flat: pos for pos, flat in enumerate(art.kept)}
    assert rank[leaf.index] in art.aliased, \
        "exists_i8 is declared donated but XLA declined the alias"


def test_ledger_records_the_gather_economics(live):
    """The recorded fact the ROADMAP-item-1 fused kernel must beat:
    the two-level hierarchical decision pays strictly more table
    gathers per launch than the flat batch kernel."""
    _, report = live
    m = report["measured"]
    pfx = "distributedratelimiting/redis_tpu/ops/kernels.py::"
    hier = m[pfx + "acquire_hierarchical_packed"]
    flat = m[pfx + "acquire_batch_packed"]
    assert hier["gather"] > flat["gather"] >= 1
    recorded = json.loads(LEDGER.read_text())["kernels"]
    assert recorded[pfx + "acquire_hierarchical_packed"] == hier
    assert recorded[pfx + "acquire_batch_packed"] == flat


# -- seeded divergence matrix -----------------------------------------------

_FILE = "distributedratelimiting/redis_tpu/ops/kernels.py"


def _hits(findings, rule, kernel):
    return [f for f in findings
            if f.rule == rule and f.message.startswith(kernel + ":")]


def test_seeded_missed_donation_fires_once(seeded):
    _, _, findings = seeded
    hits = _hits(findings, "xla-donation", "missed_donation_kernel")
    assert len(hits) == 1
    assert hits[0].file == _FILE
    assert hits[0].line == _def_line(_SEEDED_SRC, "missed_donation_kernel")
    assert "not donated" in hits[0].message


def test_seeded_declined_donation_fires_once(seeded):
    _, _, findings = seeded
    hits = _hits(findings, "xla-donation", "declined_donation_kernel")
    assert len(hits) == 1
    assert hits[0].line == _def_line(_SEEDED_SRC,
                                     "declined_donation_kernel")
    assert "declared donated" in hits[0].message


def test_seeded_callback_fires_once(seeded):
    _, _, findings = seeded
    hits = _hits(findings, "xla-purity", "callback_kernel")
    assert len(hits) == 1
    assert hits[0].line == _def_line(_SEEDED_SRC, "callback_kernel")
    assert "pure_callback" in hits[0].message


def test_seeded_leaked_scalar_fires_once(seeded):
    _, _, findings = seeded
    hits = _hits(findings, "xla-retrace", "leaked_scalar_kernel")
    assert len(hits) == 1
    assert hits[0].line == _def_line(_SEEDED_SRC, "leaked_scalar_kernel")
    assert "cache entries" in hits[0].message


def test_seeded_matrix_is_exact(seeded):
    """Exactly the four seeded defects, nothing else — the analyzers
    neither miss a divergence nor invent one on the clean kernels."""
    _, _, findings = seeded
    assert sorted(f.rule for f in findings) == [
        "xla-donation", "xla-donation", "xla-purity", "xla-retrace"]


def test_seeded_budget_loosening_fails_with_diff(seeded):
    root, arts, _ = seeded
    measured = budgets.measure_all(arts)
    ledger = budgets.make_ledger(root, measured)
    key = (_FILE + "::declined_donation_kernel")
    ledger["kernels"][key]["launches"] -= 1   # recorded tighter than real
    path = root / "budgets.json"
    path.write_text(budgets.dumps(ledger))
    before = path.read_text()
    findings, status = budgets.compare(root, arts, sites=None,
                                       path=path, restamp=True)
    assert status == "loosened"
    assert [f.rule for f in findings] == ["xla-budget"]
    assert "launches 0→1" in findings[0].message
    assert findings[0].file == "budgets.json"
    assert findings[0].line == budgets.key_line(path, key)
    assert findings[0].related[0][1] == _def_line(
        _SEEDED_SRC, "declined_donation_kernel")
    assert path.read_text() == before, \
        "a loosening must never be auto-restamped"


def test_seeded_tightening_restamps_and_staleness_is_loud(seeded):
    root, arts, _ = seeded
    measured = budgets.measure_all(arts)
    ledger = budgets.make_ledger(root, measured)
    key = (_FILE + "::callback_kernel")
    ledger["kernels"][key]["gather"] += 3   # recorded looser than real
    path = root / "tightened.json"
    path.write_text(budgets.dumps(ledger))
    # frozen: the improvement is drift, reported not rewritten
    findings, status = budgets.compare(root, arts, sites=None,
                                       path=path, restamp=False)
    assert status == "stale"
    assert [f.rule for f in findings] == ["xla-stale-ledger"]
    # interactive: the improvement restamps and becomes the new floor
    findings, status = budgets.compare(root, arts, sites=None,
                                       path=path, restamp=True)
    assert (findings, status) == ([], "restamped")
    assert json.loads(path.read_text())["kernels"][key] == measured[key]
    assert budgets.compare(root, arts, sites=None, path=path,
                           restamp=False) == ([], "clean")


def test_missing_ledger_is_a_stale_finding(seeded):
    root, arts, _ = seeded
    findings, status = budgets.compare(
        root, arts, sites=None, path=root / "absent.json", restamp=False)
    assert status == "stale"
    assert [f.rule for f in findings] == ["xla-stale-ledger"]
    assert "no ledger exists" in findings[0].message


# -- suppression: honored at the def line, audited when dormant -------------

_SUPPRESSED_SRC = textwrap.dedent("""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def excused_kernel(fp, now):  # drl-check: ok(xla-donation)
        return fp.at[0, 0].set(jnp.uint32(now))

    @jax.jit
    def dormant_kernel(counts, now):  # drl-check: ok(xla-purity)
        return counts + now
""")


def test_suppression_honored_and_audited(tmp_path):
    root = _make_root(tmp_path, _SUPPRESSED_SRC)
    decls = extract.discover(root, kernel_floor=1)
    arts = extract.trace_kernels(decls, root)
    raw = (analyzers.check_purity(arts)
           + analyzers.check_donation(arts)
           + analyzers.check_retrace(arts))
    assert [f.rule for f in raw] == ["xla-donation"]   # excused_kernel
    kept = analyzers.apply_suppressions(raw, root, decls)
    # the real finding was eaten by its ok(...); the dormant ok(...)
    # became a stale-suppression finding at ITS line
    assert [f.rule for f in kept] == ["stale-suppression"]
    assert kept[0].line == _def_line(_SUPPRESSED_SRC, "dormant_kernel")
    assert "xla-purity" in kept[0].message


def test_xla_rules_are_registered_with_drl_check():
    """drl-check owns the suppression registry: every xla-* rule must
    be a known spelling, suppressible except the freshness rule — a
    stale ledger is a fact about the tree, not a judgment call."""
    assert analyzers.XLA_RULES <= KNOWN_RULES
    assert {"jit-f64", "jit-closed-scalar"} <= KNOWN_RULES
    assert (analyzers.XLA_RULES - {"xla-stale-ledger"}) \
        <= INLINE_SUPPRESSIBLE
    assert "xla-stale-ledger" not in INLINE_SUPPRESSIBLE


# -- extractor non-vacuity: a blind extractor exits 2, never "clean" --------

def test_blind_extractor_raises(tmp_path):
    root = _make_root(tmp_path, _SEEDED_SRC)   # 4 kernels < floor 40
    with pytest.raises(extract.ExtractionError, match="gone blind"):
        extract.discover(root)
    assert len(extract.discover(root, kernel_floor=1)) == 4


def test_underivable_operand_raises(tmp_path):
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def mystery_kernel(enigma):
            return enigma
    """)
    root = _make_root(tmp_path, src)
    decls = extract.discover(root, kernel_floor=1)
    with pytest.raises(extract.ExtractionError, match="no shape rule"):
        extract.trace_kernels(decls, root)


# -- CLI exit codes: 0 clean / 1 findings / 2 blinded -----------------------

def test_cli_exit_0_on_live_tree(capsys):
    assert xla_main(["--no-restamp", "--only", "budget"]) == 0
    out = capsys.readouterr().out
    assert "ledger clean; clean" in out


def test_cli_exit_1_on_loosened_ledger(tmp_path, capsys):
    doctored = json.loads(LEDGER.read_text())
    key = next(k for k, v in sorted(doctored["kernels"].items())
               if v["gather"] > 0)
    doctored["kernels"][key]["gather"] -= 1
    path = tmp_path / "budgets.json"
    path.write_text(budgets.dumps(doctored))
    assert xla_main(["--no-restamp", "--only", "budget",
                     "--ledger", str(path)]) == 1
    out = capsys.readouterr().out
    assert "error[xla-budget]" in out
    assert "kernel definition" in out   # file:line on BOTH sides


def test_cli_exit_2_on_blind_extractor(tmp_path, capsys):
    root = _make_root(tmp_path, _SEEDED_SRC)
    assert xla_main(["--root", str(root)]) == 2
    assert "gone blind" in capsys.readouterr().err


# -- satellite: the recapture ledger names its budget ledger ----------------

def test_recapture_rows_carry_the_budget_ledger_hash():
    from benchmarks.recapture import _budget_ledger_hash
    h = _budget_ledger_hash()
    assert h == budgets.ledger_hash(LEDGER)
    assert isinstance(h, str) and len(h) == 12
