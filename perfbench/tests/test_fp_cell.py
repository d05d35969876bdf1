"""The fingerprint-store cell, ``fp10m.bulk-uniform``: its configuration
resolves with its least bytes, and the cell runs through the whole
harness on the CPU at a tiny size (20,000 keys in 2^16 slots), traced
and untraced."""

import pytest

import rehearsal
import spec

CELL = "fp10m.bulk-uniform"


def test_least_bytes_per_decision():
    cell = spec.Cell(spec.load(), CELL)
    assert cell.config["store"]["class"].endswith(".FingerprintBucketStore")
    # state read + written, directory cell, fingerprint + count, grant
    # bit, remaining balance
    assert cell.min_bytes_per_decision() == 2 * 8 + 8 + 8 + 1 + 1 / 8 + 4
    assert cell.min_bytes_per_decision() == 37.125


def test_cell_reports_its_metrics():
    cell = spec.Cell(spec.load(), CELL)
    assert [m["name"] for m in cell.end_to_end] == ["decisions_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "kernel_roofline.fp", "store_host_p99_ms.fp", "readback_p99_ms.fp",
        "device_idle.fp", "fe_handoff_p99_ms.bulk"}


@pytest.mark.parametrize("trace", [False, True])
def test_fp_cell_rehearsal(trace):
    r = rehearsal.rehearse(CELL, seconds=1.5, trace=trace)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    if trace:
        # The device metrics read a chip's trace: none on the CPU.
        assert set(r["metrics"]) == {"store_host_p99_ms.fp",
                                     "readback_p99_ms.fp",
                                     "fe_handoff_p99_ms.bulk"}
        assert "breakdown" in r and "busy_s" in r["device"]
    else:
        assert set(r["metrics"]) == {"decisions_per_s", "setup_s"}
