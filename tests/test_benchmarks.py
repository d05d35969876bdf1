"""Smoke-run every BASELINE benchmark config on the CPU test mesh.

The suite is part of the product (the reference has no benchmarks at all,
SURVEY.md §6) — these tests keep all five configs runnable so the real
perf runs never discover bitrot."""

import json

import pytest

from benchmarks import suite


@pytest.mark.parametrize("name", list(suite.CONFIGS))
def test_config_smoke(name):
    result = suite.CONFIGS[name](smoke=True)
    assert result["config"] == name
    assert result["value"] > 0
    assert result["unit"] == "decisions/s"
    json.dumps(result)  # must be JSON-serializable


def test_cli_runs_named_config(capsys):
    assert suite.main(["single_bucket_cpu", "--smoke"]) == 0
    line = capsys.readouterr().out.strip()
    assert json.loads(line)["config"] == "single_bucket_cpu"


def test_scaleout_harness_smoke():
    # The aggregate scale-out harness (benchmarks/scaleout.py) spawns
    # real server/client processes over localhost TCP; keep it runnable.
    from benchmarks import scaleout

    out = scaleout._measure(1, 1, 1.0, "cpu")
    assert out["n_nodes"] == 1 and out["n_clients"] == 1
    assert out["aggregate_decisions_per_sec"] > 0
    json.dumps(out)


def test_two_level_global_tier_accumulates():
    result = suite.CONFIGS["two_level_mesh"](smoke=True)
    # Every request grants (huge capacity), so the psum-fed global counter
    # must have absorbed consumption from all shards of the LAST step at
    # minimum (earlier steps decay).
    assert result["global_score_after"] > 0
    assert result["n_devices"] >= 1


def test_recapture_debt_ledger_semantics(tmp_path):
    """The device-bench debt list (benchmarks/recapture.py): debts are
    owed until an `ok` row that SETTLES lands in the ledger — CPU
    stand-in rows never settle, and a torn tail row hides nothing."""
    from benchmarks import recapture

    names = [n for n, _why, _fn in recapture.DEBTS]
    assert names == ["fp_mesh_fixed", "fp_bulk_optimized",
                     "llm_workload_device", "llm_reservations_device",
                     "federation_device", "storm_goodput_device"]
    ledger = tmp_path / "recapture.jsonl"
    assert recapture.owed(ledger) == names  # nothing settled yet
    recapture._append(ledger, {"debt": names[0], "status": "ok",
                               "settles_debt": False})  # CPU stand-in
    assert recapture.owed(ledger) == names
    recapture._append(ledger, {"debt": names[0], "status": "ok",
                               "settles_debt": True})  # real device row
    assert recapture.owed(ledger) == names[1:]
    with open(ledger, "a", encoding="utf-8") as f:
        f.write('{"torn json\n')  # a torn tail row must not mask debts
    assert recapture.owed(ledger) == names[1:]


def test_llm_workload_smoke_and_hier_ratio():
    """The LLM workload bench (ISSUE 10): the in-memory lane runs, is
    JSON-serializable, and holds the acceptance ratio — the
    hierarchical (two-level) path costs ≤ 2× the flat path per row on
    the in-memory backing (one extra bucket touch, amortized loop)."""
    import json as _json

    from benchmarks import llm_workload

    row = llm_workload.run_lane("inprocess", seed=1, n_rows=20_000)
    assert row["rows_per_sec"] > 0 and row["tokens_per_sec"] > 0
    assert row["hier_over_flat_per_row"] <= \
        llm_workload.HIER_RATIO_BUDGET, row
    _json.dumps(row)


def test_llm_workload_generator_is_seed_deterministic():
    from benchmarks import llm_workload

    a = llm_workload.gen_workload(3, 500)
    b = llm_workload.gen_workload(3, 500)
    assert a[0] == b[0] and a[1] == b[1]
    assert (a[2] == b[2]).all() and (a[3] == b[3]).all()
    # The advertised shape: heavy-tailed costs, clamped, all ≥ 1.
    assert int(a[2].min()) >= 1 and int(a[2].max()) <= llm_workload.MAX_COST
    assert a[2].std() > a[2].mean()  # genuinely heavy-tailed


def test_llm_workload_wire_lane_smoke():
    """The bulk wire lane end to end at tiny size (plumbing: HBUCKET
    frames, per-tenant batching, token accounting)."""
    from benchmarks import llm_workload

    row = llm_workload.run_lane("asyncio_bulk", seed=2, n_rows=400)
    assert row["rows"] == 400 and row["frames"] >= 1
    assert row["tokens_per_sec"] > 0
