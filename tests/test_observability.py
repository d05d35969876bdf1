"""The unified observability plane (ISSUE 2).

Covers, layer by layer:

- ``LatencyHistogram`` edge cases: quantiles at exact bucket boundaries,
  ``reset()`` identity preservation under a live holder, ``record_bulk``
  vs per-decision parity, the running ``sum_s``.
- OpenMetrics rendering (``MetricsRegistry``): escaping, label sets,
  counter ``_total`` suffixing, cumulative histogram buckets with a
  mandatory ``+Inf``, empty-registry exposition, snapshot-dict adoption.
- ``HeavyHitters`` space-saving sketch: bounded memory, the overcount/
  error contract, batched feeding.
- ``FlightRecorder``: ring bound, parseable JSONL dumps, trigger rate
  limiting.
- The serving integration, acceptance criteria of the issue: a
  ``curl``-able ``/metrics`` endpoint and the ``OP_METRICS`` wire op on
  BOTH the asyncio and native front-end servers, per-stage latency
  decomposition in stats and exposition, ``cluster_metrics()``
  aggregating two live nodes, and a forced degraded-mode window leaving
  a parseable flight-recorder dump.
"""

from __future__ import annotations

import asyncio
import json
import math

import pytest

from distributedratelimiting.redis_tpu.runtime.cluster import (
    ClusterBucketStore,
)
from distributedratelimiting.redis_tpu.runtime.remote import RemoteBucketStore
from distributedratelimiting.redis_tpu.runtime.server import BucketStoreServer
from distributedratelimiting.redis_tpu.runtime.store import (
    BucketStore,
    DeviceBucketStore,
    InProcessBucketStore,
)
from distributedratelimiting.redis_tpu.utils.flight_recorder import (
    FlightRecorder,
)
from distributedratelimiting.redis_tpu.utils.heavy_hitters import HeavyHitters
from distributedratelimiting.redis_tpu.utils.metrics import (
    LatencyHistogram,
    LimiterMetrics,
    MetricsRegistry,
    aggregate_openmetrics,
    parse_openmetrics,
)
from distributedratelimiting.redis_tpu.utils.native import load_frontend_lib


def run(coro):
    return asyncio.run(coro)


async def _http_get(host: str, port: int, path: str,
                    accept: str | None = None) -> tuple[int, bytes]:
    status, body, _ = await _http_get_full(host, port, path, accept)
    return status, body


async def _http_get_full(host: str, port: int, path: str,
                         accept: str | None = None
                         ) -> tuple[int, bytes, str]:
    reader, writer = await asyncio.open_connection(host, port)
    req = f"GET {path} HTTP/1.1\r\nHost: test\r\n"
    if accept is not None:
        req += f"Accept: {accept}\r\n"
    writer.write((req + "\r\n").encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionResetError, BrokenPipeError):
        pass
    head, _, body = data.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    ctype = ""
    for line in head.decode("latin-1").split("\r\n"):
        if line.lower().startswith("content-type:"):
            ctype = line.split(":", 1)[1].strip()
    return status, body, ctype


# -- LatencyHistogram edge cases --------------------------------------------

class TestLatencyHistogram:
    def test_empty_quantiles_are_zero(self):
        h = LatencyHistogram()
        assert h.quantile(0.5) == 0.0
        assert h.p99 == 0.0

    def test_exemplars_render_and_parse(self):
        """A traced observation renders as an OpenMetrics exemplar on
        its bucket line; the aggregation parser strips it; the plain
        rendering suppresses it; reset clears it."""
        h = LatencyHistogram()
        h.record(0.004)
        h.record(0.004, trace_id="cafe" * 8)
        reg = MetricsRegistry()
        reg.histogram("x_seconds", "test", lambda: h)
        text = reg.render()
        ex_lines = [ln for ln in text.splitlines() if " # {" in ln]
        assert len(ex_lines) == 1
        assert 'trace_id="' + "cafe" * 8 + '"' in ex_lines[0]
        # value 2 (cumulative count) precedes the exemplar annotation
        assert ex_lines[0].split(" # ")[0].endswith(" 2")
        # the parser (aggregation path) reads the sample value cleanly
        _, samples = parse_openmetrics(text)
        bucket = [v for n, l, v in samples if n == "drl_x_seconds_bucket"
                  and v == 2.0]
        assert bucket
        assert " # {" not in reg.render(exemplars=False)
        # exemplar() annotates without counting
        h2 = LatencyHistogram()
        h2.exemplar(0.01, "beef" * 8)
        assert h2.total == 0 and h2.exemplars
        h2.reset()
        assert h2.exemplars is None

    def test_exemplar_strip_spares_label_values_containing_hash(self):
        """Hot keys are user-controlled label values: a key containing
        ' # ' must survive the exemplar strip (only the annotation
        AFTER the label set's closing brace drops)."""
        text = ('# TYPE drl_hot_key_count gauge\n'
                'drl_hot_key_count{key="tenant # 7"} 12\n'
                'drl_x_bucket{le="0.01",key="a # b"} 5'
                ' # {trace_id="cafe"} 0.003 1.5\n'
                '# EOF\n')
        _, samples = parse_openmetrics(text)
        by_name = {n: (dict(l), v) for n, l, v in samples}
        assert by_name["drl_hot_key_count"] == (
            {"key": "tenant # 7"}, 12.0)
        assert by_name["drl_x_bucket"] == (
            {"le": "0.01", "key": "a # b"}, 5.0)

    def test_quantile_at_exact_bucket_boundaries(self):
        """A sample recorded exactly on a bucket's upper edge must read
        back as an upper bound within one bucket width (the documented
        +25% quantile error), never below the true value."""
        h = LatencyHistogram()
        for k in (0, 1, 7, 40, LatencyHistogram.N_BUCKETS - 2):
            h.reset()
            v = LatencyHistogram.MIN_S * (LatencyHistogram.BASE ** k)
            h.record(v)
            q = h.quantile(1.0)
            assert q >= v * (1 - 1e-9), (k, v, q)
            assert q <= v * LatencyHistogram.BASE * (1 + 1e-9), (k, v, q)

    def test_min_and_overflow_buckets(self):
        h = LatencyHistogram()
        h.record(0.0)           # <= MIN_S clamps into bucket 0
        h.record(-1.0)          # pathological negative: bucket 0, no raise
        h.record(1e9)           # far past the table: overflow bucket
        assert h.counts[0] == 2
        assert h.counts[-1] == 1
        assert h.quantile(1.0) == h.bucket_upper_bounds()[-1]

    def test_quantile_cdf_boundary(self):
        """q landing exactly on a cumulative boundary reads the bucket
        that completes the mass, not the next one."""
        h = LatencyHistogram()
        for _ in range(50):
            h.record(2e-6)   # one bucket
        for _ in range(50):
            h.record(1e-3)   # a later bucket
        assert h.quantile(0.5) < 1e-3   # exactly half the mass
        assert h.quantile(0.51) > 1e-3

    def test_reset_preserves_identity_under_live_holder(self):
        """Holders capture the histogram object (MicroBatcher does at
        construction): reset must zero IN PLACE, never swap the object."""
        h = LatencyHistogram()

        class Holder:
            def __init__(self, hist):
                self.hist = hist

            def observe(self, s):
                self.hist.record(s)

        holder = Holder(h)
        holder.observe(1e-3)
        assert h.total == 1
        h.reset()
        assert h.total == 0 and h.sum_s == 0.0
        holder.observe(2e-3)  # records through the held reference...
        assert h.total == 1   # ...and is visible in the original
        assert holder.hist is h

    def test_sum_tracks_recorded_seconds(self):
        h = LatencyHistogram()
        h.record(0.25)
        h.record(0.75)
        assert h.sum_s == pytest.approx(1.0)

    def test_record_bulk_vs_per_decision_parity(self):
        """One record_bulk(n, granted) must leave the counters exactly
        where n record_decision calls do; latency intentionally differs —
        bulk records ONE sample (the whole call's), per-decision n."""
        bulk, single = LimiterMetrics(), LimiterMetrics()
        bulk.record_bulk(10, 7, latency_s=1e-3)
        for i in range(10):
            single.record_decision(i < 7, latency_s=1e-3)
        assert bulk.decisions == single.decisions == 10
        assert bulk.grants == single.grants == 7
        assert bulk.denials == single.denials == 3
        assert bulk.denial_rate == single.denial_rate
        assert bulk.acquire_latency.total == 1
        assert single.acquire_latency.total == 10

    def test_bucket_bounds_match_quantile_convention(self):
        bounds = LatencyHistogram.bucket_upper_bounds()
        assert len(bounds) == LatencyHistogram.N_BUCKETS
        assert bounds[0] == LatencyHistogram.MIN_S
        assert bounds[5] == pytest.approx(
            LatencyHistogram.MIN_S * LatencyHistogram.BASE ** 5)


# -- OpenMetrics rendering ---------------------------------------------------

class TestOpenMetricsRendering:
    def test_empty_registry_renders_eof_only(self):
        assert MetricsRegistry().render() == "# EOF\n"

    def test_counter_gets_total_suffix_and_gauge_does_not(self):
        reg = MetricsRegistry()
        reg.counter("reqs", "requests", lambda: 5)
        reg.gauge("depth", "queue depth", lambda: 2.5)
        text = reg.render()
        assert "# TYPE drl_reqs counter" in text
        assert "drl_reqs_total 5" in text
        assert "drl_depth 2.5" in text
        assert text.endswith("# EOF\n")

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.gauge("g", "h", lambda: 1,
                  labels={"key": 'a"b\\c\nd'})
        text = reg.render()
        assert 'key="a\\"b\\\\c\\nd"' in text
        # and it round-trips through the parser
        _, samples = parse_openmetrics(text)
        assert samples[0][1] == (("key", 'a"b\\c\nd'),)

    def test_label_sets_share_one_family(self):
        reg = MetricsRegistry()
        for stage in ("queue", "flush"):
            h = LatencyHistogram()
            h.record(1e-3)
            reg.histogram("stage_seconds", "stages",
                          lambda h=h: h, labels={"stage": stage})
        text = reg.render()
        assert text.count("# TYPE drl_stage_seconds histogram") == 1
        assert 'stage="queue"' in text and 'stage="flush"' in text

    def test_histogram_cumulative_buckets_and_inf(self):
        reg = MetricsRegistry()
        h = LatencyHistogram()
        h.record(2e-6)
        h.record(2e-6)
        h.record(1e9)  # overflow bucket
        reg.histogram("lat_seconds", "latency", lambda: h)
        text = reg.render()
        _, samples = parse_openmetrics(text)
        buckets = [(dict(lbl)["le"], v) for name, lbl, v in samples
                   if name == "drl_lat_seconds_bucket"]
        assert buckets[-1][0] == "+Inf"
        assert buckets[-1][1] == 3  # cumulative: everything
        values = [v for _, v in buckets]
        assert values == sorted(values)  # cdf is monotone
        count = [v for name, _, v in samples
                 if name == "drl_lat_seconds_count"]
        assert count == [3]
        sums = [v for name, _, v in samples
                if name == "drl_lat_seconds_sum"]
        assert sums[0] == pytest.approx(1e9 + 4e-6)

    def test_histogram_none_skipped(self):
        reg = MetricsRegistry()
        reg.histogram("absent_seconds", "maybe", lambda: None)
        assert "absent_seconds_bucket" not in reg.render()

    def test_numeric_dict_adoption(self):
        reg = MetricsRegistry()
        reg.register_numeric_dict(
            "store", "store metrics",
            lambda: {"launches": 4, "occupancy": 0.5,
                     "name": "skipped", "flag": True, "nested": {}},
            counters={"launches"})
        text = reg.render()
        assert "drl_store_launches_total 4" in text
        assert "drl_store_occupancy 0.5" in text
        assert "skipped" not in text and "nested" not in text
        assert "drl_store_flag" not in text  # bools are not numbers here

    def test_dynamic_labeled_gauges(self):
        reg = MetricsRegistry()
        series = [({"key": "a"}, 3.0), ({"key": "b"}, 1.0)]
        reg.labeled_gauges("hot", "hot keys", lambda: series)
        text = reg.render()
        assert 'drl_hot{key="a"} 3' in text
        assert 'drl_hot{key="b"} 1' in text

    def test_broken_reader_does_not_kill_scrape(self):
        reg = MetricsRegistry()
        reg.gauge("bad", "raises", lambda: 1 / 0)
        reg.gauge("good", "fine", lambda: 7)
        text = reg.render()
        assert "drl_good 7" in text and "drl_bad" not in text

    def test_type_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x", "h", lambda: 1)
        with pytest.raises(ValueError):
            reg.gauge("x", "h", lambda: 1)

    def test_aggregate_openmetrics(self):
        reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
        reg_a.counter("reqs", "r", lambda: 10)
        reg_b.counter("reqs", "r", lambda: 32)
        merged = aggregate_openmetrics([reg_a.render(), reg_b.render()])
        assert "drl_reqs_total 42" in merged
        assert 'drl_reqs_total{node="0"} 10' in merged
        assert 'drl_reqs_total{node="1"} 32' in merged
        assert "# TYPE drl_reqs counter" in merged
        assert merged.endswith("# EOF\n")

    def test_aggregate_families_stay_contiguous(self):
        """OpenMetrics forbids interleaving families: every family's
        samples (aggregated + per-node) must form one contiguous block
        after its single # TYPE line."""
        regs = []
        for v in (1, 2):
            reg = MetricsRegistry()
            reg.counter("alpha", "a", lambda v=v: v)
            reg.gauge("beta", "b", lambda v=v: v * 10)
            regs.append(reg)
        merged = aggregate_openmetrics([r.render() for r in regs])
        lines = [l for l in merged.splitlines() if l != "# EOF"]
        fam_of = []
        for line in lines:
            name = line.split(None, 2)[2].split()[0] if \
                line.startswith("# TYPE") else line.split("{")[0].split()[0]
            fam_of.append("alpha" if "alpha" in name else "beta")
        # one contiguous run per family → exactly one transition
        transitions = sum(1 for a, b in zip(fam_of, fam_of[1:]) if a != b)
        assert transitions == 1, lines
        assert merged.count("# TYPE drl_alpha counter") == 1
        assert merged.count("# TYPE drl_beta gauge") == 1


# -- HeavyHitters ------------------------------------------------------------

class TestHeavyHitters:
    def test_exact_when_under_capacity(self):
        hh = HeavyHitters(k=8)
        for _ in range(5):
            hh.offer("a")
        hh.offer("b", 3)
        top = hh.top()
        assert top[0] == ("a", 5.0, 0.0)
        assert top[1] == ("b", 3.0, 0.0)
        assert hh.offered == 8.0

    def test_bounded_memory_and_error_contract(self):
        hh = HeavyHitters(k=4)
        # A true heavy hitter among a long cold tail.
        for i in range(200):
            hh.offer(f"cold{i}")
            if i % 2 == 0:
                hh.offer("hot")
        assert len(hh) <= 4
        top = hh.top()
        hot = next(t for t in top if t[0] == "hot")
        # Space-saving: reported count ≥ true count, overshoot ≤ error.
        assert hot[1] >= 100
        assert hot[1] - hot[2] <= 100

    def test_offer_many_matches_offers_for_small_batches(self):
        a, b = HeavyHitters(k=16), HeavyHitters(k=16)
        keys = ["x"] * 5 + ["y"] * 3 + ["z"]
        a.offer_many(keys)
        for k in keys:
            b.offer(k)
        assert dict((k, c) for k, c, _ in a.top()) == \
            dict((k, c) for k, c, _ in b.top())
        assert a.offered == b.offered == 9.0

    def test_offer_many_truncation_keeps_offered_honest(self):
        hh = HeavyHitters(k=2, batch_top=2)
        hh.offer_many(["a", "a", "b", "c", "d"])  # c, d truncated
        assert hh.offered == 5.0
        assert len(hh) <= 2

    def test_reset(self):
        hh = HeavyHitters(k=2)
        hh.offer("a")
        hh.reset()
        assert len(hh) == 0 and hh.offered == 0.0
        assert hh.snapshot()["top"] == []


# -- FlightRecorder ----------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self, tmp_path):
        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
        for i in range(50):
            rec.record("flush", n=i)
        assert len(rec.frames()) == 8
        assert rec.frames()[0]["n"] == 42  # oldest surviving frame
        assert rec.frames_recorded == 50

    def test_dump_is_parseable_jsonl(self, tmp_path):
        rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
        rec.record("flush", n=1, wall_ms=0.5, error=None)
        rec.record("t0_sync", keys=3, failures=1)
        path = rec.dump("unit_test", {"note": "hello"})
        assert path is not None
        lines = [json.loads(line) for line in open(path)]
        assert lines[0]["kind"] == "header"
        assert lines[0]["reason"] == "unit_test"
        assert lines[0]["note"] == "hello"
        assert [f["kind"] for f in lines[1:]] == ["flush", "t0_sync"]
        assert rec.dumps_written == 1
        assert rec.last_dump_path == path

    def test_auto_dump_rate_limited(self, tmp_path):
        rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                             min_dump_interval_s=3600.0)
        rec.record("flush", n=1)
        assert rec.auto_dump("streak") is not None
        assert rec.auto_dump("streak") is None  # suppressed
        assert rec.dumps_suppressed == 1
        assert rec.dump("operator") is not None  # explicit bypasses

    def test_unwritable_dir_fails_soft(self):
        rec = FlightRecorder(capacity=4,
                             dump_dir="/nonexistent-dir-for-test")
        rec.record("flush", n=1)
        assert rec.dump("x") is None  # no raise on the serving path


# -- Serving integration: asyncio server ------------------------------------

class TestAsyncioServerExposition:
    @pytest.mark.jax_backend
    def test_metrics_op_http_and_stage_decomposition(self):
        async def body():
            backing = DeviceBucketStore(n_slots=1 << 10)
            srv = BucketStoreServer(backing, metrics_port=0)
            await srv.start()
            store = RemoteBucketStore(address=(srv.host, srv.port),
                                      coalesce_requests=False)
            try:
                for i in range(60):
                    await store.acquire(f"user{i % 5}", 1, 1000.0, 10.0)
                # OP_METRICS on the wire
                text = await store.metrics()
                assert text.endswith("# EOF\n")
                assert "drl_serving_latency_seconds_bucket" in text
                for stage in ("queue", "flush", "reply"):
                    assert (f'drl_stage_latency_seconds_bucket{{stage='
                            f'"{stage}"') in text, stage
                assert "drl_store_launches_total" in text
                assert 'drl_hot_key_count{key="user0"}' in text
                # the same bytes over plain HTTP (the curl path)
                status, http_body = await _http_get(
                    srv.host, srv.metrics_port, "/metrics")
                assert status == 200
                assert b"drl_serving_latency_seconds_bucket" in http_body
                status, _ = await _http_get(srv.host, srv.metrics_port,
                                            "/nope")
                assert status == 404
                # Content negotiation: scrapers that Accept openmetrics
                # get the full ctype; everyone else gets Prometheus
                # text 0.0.4 (and no exemplar annotations).
                _, _, ctype = await _http_get_full(
                    srv.host, srv.metrics_port, "/metrics",
                    accept="application/openmetrics-text; version=1.0.0")
                assert ctype == MetricsRegistry.CONTENT_TYPE
                _, _, ctype = await _http_get_full(
                    srv.host, srv.metrics_port, "/metrics")
                assert ctype == BucketStoreServer.PLAIN_CONTENT_TYPE
                _, _, ctype = await _http_get_full(
                    srv.host, srv.metrics_port, "/metrics",
                    accept="text/plain")
                assert ctype == BucketStoreServer.PLAIN_CONTENT_TYPE
                # stats carries the decomposition numerically
                stats = await store.stats()
                stages = stats["stages"]
                assert {"queue", "flush", "reply"} <= set(stages)
                for s in ("queue", "flush", "reply"):
                    assert stages[s]["samples"] > 0
                assert stats["hot_keys"]["tracked"] == 5
                # reset opens a fresh window for every stage histogram
                await store.stats(reset=True)
                stats2 = await store.stats()
                assert stats2.get("stages", {}).get(
                    "queue", {"samples": 0})["samples"] == 0
            finally:
                await store.aclose()
                await srv.aclose()
                await backing.aclose()

        run(body())

    @pytest.mark.jax_backend
    def test_stats_flight_dump_trigger(self, tmp_path):
        async def body():
            backing = DeviceBucketStore(n_slots=1 << 10)
            srv = BucketStoreServer(backing, flight_dir=str(tmp_path))
            await srv.start()
            # Per-request framing: the scalar lane rides the micro-
            # batcher, whose flush observer is what feeds the recorder.
            store = RemoteBucketStore(address=(srv.host, srv.port),
                                      coalesce_requests=False)
            try:
                await store.acquire("k", 1, 100.0, 10.0)
                stats = await store.stats(dump_flight=True)
                path = stats["flight_recorder"]["last_dump_path"]
                assert path is not None
                lines = [json.loads(line) for line in open(path)]
                assert lines[0]["kind"] == "header"
                assert any(f["kind"] == "flush" for f in lines[1:])
            finally:
                await store.aclose()
                await srv.aclose()
                await backing.aclose()

        run(body())

    def test_sema_releases_and_probes_not_counted_as_hot_keys(self):
        """OP_SEMA's count is a signed delta: releases (<0) and probes
        (0) are not admission demand and must not feed the sketch (a
        balanced acquire/release stream would double-weight its keys)."""
        from distributedratelimiting.redis_tpu.runtime import wire

        async def body():
            srv = BucketStoreServer(InProcessBucketStore())
            acq = wire.encode_request(1, wire.OP_SEMA, "sema-key", 1,
                                      10.0, 0.0)[4:]
            rel = wire.encode_request(2, wire.OP_SEMA, "sema-key", -1,
                                      0.0, 0.0)[4:]
            probe = wire.encode_request(3, wire.OP_SEMA, "sema-key", 0,
                                        10.0, 0.0)[4:]
            await srv.handle_frame_body(acq)
            await srv.handle_frame_body(rel)
            await srv.handle_frame_body(probe)
            top = srv.heavy_hitters.top()
            assert top == [("sema-key", 1.0, 0.0)], top

        run(body())

    def test_http_flight_trigger_is_rate_limited(self, tmp_path):
        async def body():
            srv = BucketStoreServer(InProcessBucketStore(),
                                    metrics_port=0,
                                    flight_dir=str(tmp_path))
            await srv.start()
            try:
                srv.flight_recorder.record("flush", n=1)
                status, body1 = await _http_get(
                    srv.host, srv.metrics_port, "/flight")
                assert status == 200
                first = json.loads(body1)
                assert first["dumped"] and not first["suppressed"]
                status, body2 = await _http_get(
                    srv.host, srv.metrics_port, "/flight")
                second = json.loads(body2)
                # within min_dump_interval_s: suppressed, no new file —
                # an unauthenticated peer cannot disk-fill through here.
                assert second["dumped"] is None and second["suppressed"]
            finally:
                await srv.aclose()

        run(body())

    def test_observability_off_still_exposes_latency(self):
        async def body():
            srv = BucketStoreServer(InProcessBucketStore(),
                                    observability=False)
            await srv.start()
            store = RemoteBucketStore(address=(srv.host, srv.port),
                                      coalesce_requests=False)
            try:
                await store.acquire("k", 1, 100.0, 10.0)
                assert srv.heavy_hitters is None
                assert srv.flight_recorder is None
                text = await store.metrics()
                assert "drl_serving_latency_seconds_bucket" in text
                assert "drl_hot_key_count" not in text
                stats = await store.stats()
                assert "hot_keys" not in stats
            finally:
                await store.aclose()
                await srv.aclose()

        run(body())


# -- Serving integration: native front-end ----------------------------------

_LIB = load_frontend_lib()
native_only = pytest.mark.skipif(
    _LIB is None, reason="native front-end library unavailable")
tier0_native_only = pytest.mark.skipif(
    _LIB is None or not getattr(_LIB, "has_tier0", False),
    reason="native front-end library (with tier-0 ABI) unavailable")


@native_only
def test_native_server_metrics_and_stage_decomposition():
    async def body():
        srv = BucketStoreServer(InProcessBucketStore(),
                                native_frontend=True, metrics_port=0)
        await srv.start()
        assert srv._native is not None
        store = RemoteBucketStore(address=(srv.host, srv.port),
                                  coalesce_requests=False)
        try:
            for i in range(150):
                await store.acquire(f"key{i % 4}", 1, 1e6, 1e6)
            text = await store.metrics()
            assert "drl_native_frontend 1" in text
            for stage in ("native_queue", "native_exec", "fe_handoff"):
                assert (f'drl_stage_latency_seconds_bucket{{stage='
                        f'"{stage}"') in text, stage
            assert 'drl_hot_key_count{key="key0"}' in text
            stats = await store.stats()
            st = stats["stages"]
            assert st["native_queue"]["samples"] > 0
            assert st["native_exec"]["samples"] > 0
            # The pump -> loop hand-off: one sample per batch the C side
            # cut (one request in flight at a time: one row each).
            batches = srv._native.counts()[2]
            assert st["fe_handoff"]["samples"] == batches == 150
            assert st["native_exec"]["samples"] == batches
            assert (srv._native.handoff_latency.sum_s
                    <= srv._native.stage_histograms()["native_exec"].sum_s)
            # serving covers queue + exec: its p99 can't be below either
            # stage's p50 by construction (same windows, same samples).
            assert stats["serving_p99_ms"] >= st["native_queue"]["p50_ms"]
            # the HTTP endpoint serves beside the native wire listener
            status, http_body = await _http_get(srv.host,
                                                srv.metrics_port,
                                                "/metrics")
            assert status == 200
            assert b'stage="native_exec"' in http_body
            # reset clears the C-side stage windows too
            await store.stats(reset=True)
            stats2 = await store.stats()
            assert "native_queue" not in stats2.get("stages", {})
            assert "fe_handoff" not in stats2.get("stages", {})
        finally:
            await store.aclose()
            await srv.aclose()

    run(body())


@native_only
@pytest.mark.jax_backend
def test_native_server_store_stages_one_sample_per_call():
    """Behind the native front end a device store splits native_exec:
    ``fe_handoff`` takes one sample per batch or bulk job, and the store's
    ``store_host`` / ``store_readback`` one per store call; the window
    reset zeroes all three with the C stages."""
    async def body():
        dev = DeviceBucketStore(n_slots=1024, counter_slots=64,
                                max_batch=64)
        srv = BucketStoreServer(dev, native_frontend=True)
        await srv.start()
        store = RemoteBucketStore(address=(srv.host, srv.port),
                                  coalesce_requests=False)
        keys = [f"bulk{i}" for i in range(64)]
        try:
            # Compile both shapes before the window.
            await store.acquire("warm", 1, 100.0, 10.0)
            await store.acquire_many(keys, [1] * 64, 100.0, 10.0)
            await store.stats(reset=True)
            b0 = srv._native.counts()[2]
            for i in range(12):
                await store.acquire(f"k{i}", 1, 100.0, 10.0)
            for _ in range(5):
                await store.acquire_many(keys, [1] * 64, 100.0, 10.0)
            batches = srv._native.counts()[2] - b0
            assert batches == 12
            st = (await store.stats())["stages"]
            assert st["fe_handoff"]["samples"] == batches + 5
            assert st["store_host"]["samples"] == batches + 5
            assert st["store_readback"]["samples"] == batches + 5
            hists = srv._native.stage_histograms()
            assert hists["store_host"] is dev.metrics.store_host
            assert dev.metrics.store_host.sum_s > 0.0
            assert dev.metrics.store_readback.sum_s > 0.0
            text = await store.metrics()
            for stage in ("fe_handoff", "store_host", "store_readback"):
                assert (f'drl_stage_latency_seconds_count{{stage='
                        f'"{stage}"}} {batches + 5}') in text, stage
            srv._native.reset_latency()
            hists = srv._native.stage_histograms()
            assert all(hists[s].total == 0 for s in
                       ("fe_handoff", "store_host", "store_readback"))
        finally:
            await store.aclose()
            await srv.aclose()
            await dev.aclose()

    run(body())


class _OutageStore(InProcessBucketStore):
    """Backing store whose device-touching paths fail on demand (the
    r04/r05 outage mode as the front-end sees it — test_tier0's rig)."""

    def __init__(self):
        super().__init__()
        self.fail = False

    def _check(self):
        if self.fail:
            raise RuntimeError("simulated device outage")

    async def acquire_many(self, *a, **kw):
        self._check()
        return await super().acquire_many(*a, **kw)

    async def debit_many(self, *a, **kw):
        self._check()
        return await super().debit_many(*a, **kw)


@tier0_native_only
def test_flight_recorder_dumps_on_forced_degraded_mode(tmp_path):
    """Acceptance criterion: a forced degraded-mode window (tier-0 sync
    pump failing against a dead store) must leave a parseable JSONL dump
    on disk, written by the sync-failure-streak trigger."""
    from distributedratelimiting.redis_tpu.runtime.native_frontend import (
        Tier0Config,
    )

    async def body():
        backing = _OutageStore()
        cfg = Tier0Config(sync_interval_s=0.01, min_budget=8.0,
                          max_stale_s=10.0)
        srv = BucketStoreServer(backing, native_frontend=True,
                                native_tier0=cfg,
                                flight_dir=str(tmp_path))
        await srv.start()
        store = RemoteBucketStore(address=(srv.host, srv.port),
                                  coalesce_requests=False)
        try:
            # Warm: install the replica, let one healthy sync land.
            for _ in range(50):
                await store.acquire("hot", 1, 10000.0, 1e-9)
            await asyncio.sleep(0.05)
            # With tier-0 armed the exposition carries its gauges and
            # the pump-fed hot-key series (acceptance criterion).
            text = await store.metrics()
            assert "drl_tier0_hits_total" in text
            assert "drl_tier0_last_sync_age_s" in text
            assert 'drl_hot_key_count{key="hot"}' in text
            backing.fail = True
            # Keep tier-0 granting locally so every sync round has
            # harvested amounts to fail on.
            deadline = asyncio.get_running_loop().time() + 5.0
            dumped = None
            while asyncio.get_running_loop().time() < deadline:
                for _ in range(20):
                    await store.acquire("hot", 1, 10000.0, 1e-9)
                await asyncio.sleep(0.03)
                rec = srv.flight_recorder
                if rec is not None and rec.dumps_written:
                    dumped = rec.last_dump_path
                    break
            assert dumped is not None, "degraded streak never dumped"
            lines = [json.loads(line) for line in open(dumped)]
            assert lines[0]["kind"] == "header"
            assert lines[0]["reason"] == "t0_sync_streak"
            syncs = [f for f in lines[1:] if f["kind"] == "t0_sync"]
            assert syncs, lines[1:]
            assert any(f["failures"] for f in syncs)
            assert max(f["streak"] for f in syncs) >= 1
        finally:
            backing.fail = False
            await store.aclose()
            await srv.aclose()

    run(body())


# -- Cluster aggregation -----------------------------------------------------

def test_cluster_metrics_aggregates_two_nodes():
    async def body():
        servers = []
        for _ in range(2):
            s = BucketStoreServer(InProcessBucketStore())
            await s.start()
            servers.append(s)
        cluster = ClusterBucketStore(
            addresses=[(s.host, s.port) for s in servers])
        try:
            keys = [f"ck{i}" for i in range(64)]
            res = await cluster.acquire_many(keys, [1] * 64, 1000.0, 10.0)
            assert res.granted.all()
            text = await cluster.cluster_metrics()
            lines = text.splitlines()
            agg = [l for l in lines
                   if l.startswith("drl_requests_served_total ")]
            n0 = [l for l in lines
                  if l.startswith('drl_requests_served_total{node="0"}')]
            n1 = [l for l in lines
                  if l.startswith('drl_requests_served_total{node="1"}')]
            assert agg and n0 and n1
            assert float(agg[0].split()[-1]) == pytest.approx(
                float(n0[0].split()[-1]) + float(n1[0].split()[-1]))
            # both nodes actually served a sub-batch (crc32 spreads 64
            # keys across 2 nodes with overwhelming probability)
            assert float(n0[0].split()[-1]) >= 1
            assert float(n1[0].split()[-1]) >= 1
            assert text.endswith("# EOF\n")
        finally:
            await cluster.aclose()
            for s in servers:
                await s.aclose()

    run(body())


# -- MicroBatcher stage instrumentation --------------------------------------

def test_batcher_queue_stage_and_flush_observer():
    """The queue-stage histogram records the oldest member's wait once
    per flush; the observer sees (n, wall, error, trace_id) including
    failures — the flight recorder's feed contract (trace_id is None
    whenever no member of the flush was sampled)."""
    from distributedratelimiting.redis_tpu.runtime.batcher import (
        MicroBatcher,
    )

    async def body():
        qhist = LatencyHistogram()
        seen: list[tuple] = []

        async def flush(reqs):
            await asyncio.sleep(0.001)
            return [r * 2 for r in reqs]

        mb = MicroBatcher(flush, max_batch=8, queue_latency=qhist,
                          flush_observer=lambda *a: seen.append(a))
        out = await asyncio.gather(*(mb.submit(i) for i in range(8)))
        assert out == [i * 2 for i in range(8)]
        await mb.aclose()
        assert qhist.total >= 1
        assert seen and seen[0][0] == 8 and seen[0][2] is None
        assert seen[0][1] >= 0.001
        assert seen[0][3] is None  # untraced flush: no elected trace

        async def bad_flush(reqs):
            raise RuntimeError("boom")

        seen.clear()
        mb2 = MicroBatcher(bad_flush, max_batch=4,
                           flush_observer=lambda *a: seen.append(a))
        with pytest.raises(RuntimeError):
            await mb2.submit(1)
        await mb2.aclose()
        assert seen and seen[0][2] is not None
        assert "boom" in seen[0][2]

        # An observer that itself raises must not fail a flush that
        # succeeded (nor be re-invoked on a phantom error path).
        calls = []

        def exploding_observer(n, dt, err, trace_id=None):
            calls.append(err)
            raise ValueError("observer bug")

        mb3 = MicroBatcher(flush, max_batch=4,
                           flush_observer=exploding_observer)
        assert await mb3.submit(21) == 42  # result survives the observer
        await mb3.aclose()
        assert calls == [None]  # called once, success-shaped

    run(body())


@pytest.mark.jax_backend
def test_flush_error_triggers_degraded_entry_dump(tmp_path):
    """The store-side degraded trigger without any native dependency:
    a failing flush fires the observer, which records the frame and
    auto-dumps through the attached recorder."""
    store = DeviceBucketStore(n_slots=64)
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
    store.metrics.flight_recorder = rec
    store._flush_observer(64, 0.002, None)
    store._flush_observer(64, 0.1, "RuntimeError('device gone')")
    assert rec.dumps_written == 1
    lines = [json.loads(line) for line in open(rec.last_dump_path)]
    assert lines[0]["reason"] == "flush_error"
    assert [f["kind"] for f in lines[1:]] == ["flush", "flush"]
    assert lines[-1]["error"] is not None
