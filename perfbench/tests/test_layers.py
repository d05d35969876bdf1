"""The program-layer readings: the stage readers on synthetic contexts,
``layers.py``'s span reductions on a small trace with ``drl/`` spans,
and each cell's traced CPU rehearsal reporting every stage metric."""

import json
import os

import pytest

import layers
import rehearsal
import spec
import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")

#: Each new stage reader and the stage histogram it reads.
STAGE_READERS = {
    "fe_handoff_p99_ms.scalar": "fe_handoff",
    "store_host_p99_ms.scalar": "store_host",
    "readback_p99_ms.scalar": "store_readback",
    "fe_handoff_p99_ms.bulk": "fe_handoff",
    "store_host_p99_ms.bulk": "store_host",
    "readback_p99_ms.bulk": "store_readback",
}


def _load(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


class _Run:
    def __init__(self, stages):
        self.stages = stages


def _reader(name):
    cell = ("tb10m.scalar-zipf" if name.endswith(".scalar")
            else "tb10m.bulk-uniform")
    return spec.Cell(spec.load(), cell).reader(name)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_on_a_synthetic_context(name):
    read = _reader(name)
    stage = STAGE_READERS[name]
    assert read(_Run({stage: {"p99_s": 0.0025, "count": 7}})) == \
        pytest.approx(2.5)
    # Nothing to read: a program without the stage (the parent of this
    # change), or a window in which it took no sample.
    assert read(_Run({"native_exec": {"p99_s": 0.01, "count": 3}})) is None
    assert read(_Run({stage: {"p99_s": 0.0, "count": 0}})) is None


def test_program_spans_and_gap_labels():
    planes = _load("trace_spans.json")
    # Window [1000, 11000): the pump span [200, 1200) counts from 1000,
    # the second readback [11500, 11800) not at all.
    assert layers.program_spans_s(planes) == {
        "drl/acquire_many": pytest.approx(2000e-9),
        "drl/fe.complete": pytest.approx(100e-9),
        "drl/fe.pump": pytest.approx(200e-9),
        "drl/store.dispatch": pytest.approx(700e-9),
        "drl/store.readback": pytest.approx(300e-9),
        "drl/store.resolve": pytest.approx(1000e-9),
    }
    assert layers.span_counts(planes) == {
        "drl/acquire_many": 2, "drl/fe.complete": 1,
        "drl/store.dispatch": 2, "drl/store.readback": 1,
        "drl/store.resolve": 2}
    # Device 0 idles [2000,4000) (at 3000: acquire_many, its resolve on
    # the loop, a readback on another thread), [5000,8000) (at 6500:
    # acquire_many and its resolve) and [8500,10500) (no program span
    # at 9500; the store span ended at 9000).
    g = layers.idle_gaps(planes)
    assert g["idle_gaps"] == [
        ["drl/acquire_many+drl/store.resolve", pytest.approx(3000e-9)],
        ["drl/acquire_many+drl/store.readback+drl/store.resolve",
         pytest.approx(2000e-9)],
        ["no store call", pytest.approx(2000e-9)]]
    assert sum(g["idle_by_label"].values()) == pytest.approx(7000e-9)
    # The one reply [10600, 10700) follows the readback ending at 3200.
    assert layers.readback_to_complete(planes) == {
        "mean_s": pytest.approx(7400e-9), "p50_s": pytest.approx(7400e-9),
        "count": 1}


def test_gap_labels_fall_back_to_the_store_span():
    planes = _load("trace_small.json")
    assert layers.program_spans_s(planes) == {}
    g = layers.idle_gaps(planes)
    assert g["idle_gaps"] == trace.reduce(planes)["idle_gaps"]


def test_stage_readings_cover_native_exec():
    from distributedratelimiting.redis_tpu.utils.metrics import (
        LatencyHistogram,
    )

    hists = {name: LatencyHistogram() for name in
             ("native_exec", "fe_handoff", "store_host", "store_readback")}
    for name, seconds in (("native_exec", 0.010), ("fe_handoff", 0.002),
                          ("store_host", 0.003), ("store_readback", 0.004)):
        for _ in range(4):
            hists[name].record(seconds)
    out = layers.stage_readings(hists)
    assert out["store_host"]["count"] == 4
    assert out["store_host"]["mean_s"] == pytest.approx(0.003)
    assert out["exec_covered"] == pytest.approx(0.9)
    r = layers.reduce(_load("trace_spans.json"), out)
    assert r["resolves_per_call"] == pytest.approx(2 / 4)


@pytest.mark.parametrize("cell", ["tb10m.scalar-zipf", "tb10m.bulk-uniform"])
def test_traced_rehearsal_reports_every_stage_metric(cell):
    r = rehearsal.rehearse(cell, seconds=1.5, trace=True)
    assert r["correct"] is True, r["checks"]
    listed = {m["name"] for m in spec.Cell(spec.load(), cell).per_layer}
    for name in STAGE_READERS:
        if name in listed:
            assert r["metrics"][name]["value"] > 0, name


def test_layers_rehearsal_keeps_program_spans():
    lay, result = layers.run(rehearsal.tiny_cell("tb10m.bulk-uniform"), 7,
                             1.5, require_tpu=False)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    spans = lay["program_spans_s"]
    for name in ("drl/fe.pump", "drl/acquire_many", "drl/store.resolve",
                 "drl/store.dispatch", "drl/store.readback",
                 "drl/fe.complete"):
        assert spans.get(name, 0.0) > 0.0, name
    # Every uniform frame declines the grouped plan: two resolves a call.
    assert lay["resolves_per_call"] == pytest.approx(2.0, abs=0.2)
    assert lay["stages"]["store_host"]["count"] > 0
    assert lay["end_to_end"]["decisions_per_s"] > 0
    # No TPU plane on the CPU: nothing idles, nothing is labelled.
    assert lay["idle_by_label"] == {} and lay["idle_gaps"] == []
    # The harness is as it was after the block.
    assert trace.load is not layers.load
