"""Observability tests: metrics primitives, trace propagation, contract.

Three layers are pinned here:

* ``repro.obs.metrics`` / ``repro.obs.trace`` primitives — thread-safe
  counters/gauges/histograms, Prometheus text exposition shape, the
  process-wide kill switch, trace-id grammar, span ring, slow-log lines.
* The **trace propagation matrix** — one trace id minted client-side is
  demonstrably visible in the client's attempt span, the server's
  structured slow-request log line, and a histogram exemplar, across
  every serving path: HTTP solo, HTTP coalesced, binary pipelined,
  dedup'd duplicate, and the poison-isolated solo re-run.
* The **metric-name contract** — family names are append-only once
  shipped; the snapshot test below is the tripwire (extending the list
  is fine, renaming/removing a name is a breaking change for scrapers).
"""
import json
import threading
import time

import pytest

from repro.core import hardware, sweep
from repro.core.workload import TileConfig, WorkloadTable, gemm_workload
from repro.launch import compile_cache
from repro.obs import metrics, trace
from repro.serve.client import PredictionClient
from repro.serve.server import Coalescer, PredictionServer

B200 = hardware.B200
TILES = [TileConfig(bm, bn, 32) for bm in (64, 128) for bn in (64, 128)]


def small_table(name="g"):
    return WorkloadTable.tile_lattice(
        gemm_workload(name, 1024, 1024, 1024, precision="fp16"), TILES)


def poison_table(name="POISON"):
    return WorkloadTable.tile_lattice(
        gemm_workload(name, 1024, 1024, 1024, precision="fp64"), TILES)


class PoisonEngine(sweep.SweepEngine):
    """Refuses any table containing an fp64 row (see test_serve_faults)."""

    def predict_table(self, table, hw, **kw):
        if "fp64" in {table.precision_vocab[c]
                      for c in table.precision_codes}:
            raise ValueError("poisoned row (fp64 sentinel)")
        return super().predict_table(table, hw, **kw)


def exemplar_ids():
    """Every trace id currently attached to a histogram exemplar."""
    ids = set()
    for fam in metrics.snapshot().values():
        for s in fam["series"]:
            for ex in s.get("exemplars", ()):
                ids.add(ex["trace_id"])
    return ids


def assert_trace_visible(tid, slow_lines, wait_s=10.0):
    """The matrix invariant: one id, three observation points.

    The server writes the slow-log line and the latency exemplar after it
    has handed the reply to the transport, so the client can hold the
    reply first: those two points are waited for, up to ``wait_s``."""
    def on_server():
        return (any(json.loads(l).get("trace_id") == tid
                    for l in list(slow_lines))
                and tid in exemplar_ids())

    deadline = time.monotonic() + wait_s
    while not on_server() and time.monotonic() < deadline:
        time.sleep(0.005)
    client_spans = trace.recent_spans(trace_id=tid, name="client.attempt")
    assert client_spans, f"no client.attempt span for {tid}"
    logged = [json.loads(l) for l in slow_lines]
    assert any(r.get("trace_id") == tid for r in logged), \
        f"trace {tid} missing from slow-request log"
    assert tid in exemplar_ids(), \
        f"trace {tid} not attached to any histogram exemplar"


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

class TestMetricsPrimitives:
    def test_counter(self):
        reg = metrics.Registry()
        c = reg.counter("t_requests_total", "help text", transport="http")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge(self):
        reg = metrics.Registry()
        g = reg.gauge("t_depth")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value == 4.0

    def test_histogram_buckets_cumulative(self):
        reg = metrics.Registry()
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)
        text = reg.render_prometheus()
        assert 't_seconds_bucket{le="0.1"} 1' in text
        assert 't_seconds_bucket{le="1"} 3' in text
        assert 't_seconds_bucket{le="10"} 4' in text
        assert 't_seconds_bucket{le="+Inf"} 5' in text
        assert "t_seconds_count 5" in text

    def test_histogram_boundary_is_inclusive(self):
        # le is <=: an observation exactly on a bound lands in its bucket
        reg = metrics.Registry()
        h = reg.histogram("t_edge", buckets=(1.0,))
        h.observe(1.0)
        assert 't_edge_bucket{le="1"} 1' in reg.render_prometheus()

    def test_histogram_exemplar_keeps_last(self):
        reg = metrics.Registry()
        h = reg.histogram("t_ex_seconds")
        h.observe(0.1, trace_id="aaaaaaaaaaaaaaaa")
        h.observe(0.2, trace_id="bbbbbbbbbbbbbbbb")
        h.observe(0.3)                         # no id: exemplar unchanged
        assert h.exemplar == ("bbbbbbbbbbbbbbbb", 0.2)
        assert [t for t, _ in h.exemplars] \
            == ["aaaaaaaaaaaaaaaa", "bbbbbbbbbbbbbbbb"]
        snap = reg.snapshot()["t_ex_seconds"]["series"][0]
        assert snap["exemplar"] == {"trace_id": "bbbbbbbbbbbbbbbb",
                                    "value": 0.2}
        # exemplars never leak into the text exposition
        assert "bbbbbbbbbbbbbbbb" not in reg.render_prometheus()

    def test_get_or_create_is_idempotent(self):
        reg = metrics.Registry()
        a = reg.counter("t_same_total", "h", op="argmin")
        b = reg.counter("t_same_total", "h", op="argmin")
        assert a is b
        c = reg.counter("t_same_total", "h", op="topk")
        assert c is not a

    def test_kind_conflict_raises(self):
        reg = metrics.Registry()
        reg.counter("t_conflict")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("t_conflict")

    def test_bad_names_raise(self):
        reg = metrics.Registry()
        with pytest.raises(ValueError):
            reg.counter("0bad")
        with pytest.raises(ValueError):
            reg.counter("ok_total", **{"le": "x", "0bad": "y"})

    def test_disabled_registry_is_a_no_op(self):
        reg = metrics.Registry(enabled=False)
        c = reg.counter("t_off_total")
        h = reg.histogram("t_off_seconds")
        c.inc()
        h.observe(1.0)
        assert c.value == 0 and h.count == 0
        reg.enabled = True
        c.inc()
        assert c.value == 1

    def test_global_kill_switch(self):
        c = metrics.counter("t_kill_total")
        before = c.value
        metrics.set_enabled(False)
        try:
            assert not metrics.enabled()
            c.inc()
            assert c.value == before
        finally:
            metrics.set_enabled(True)
        c.inc()
        assert c.value == before + 1

    def test_label_escaping(self):
        reg = metrics.Registry()
        reg.counter("t_esc_total", reason='quo"te\nnl').inc()
        assert 'reason="quo\\"te\\nnl"' in reg.render_prometheus()

    def test_help_and_type_lines(self):
        reg = metrics.Registry()
        reg.counter("t_doc_total", "what it counts").inc()
        text = reg.render_prometheus()
        assert "# HELP t_doc_total what it counts" in text
        assert "# TYPE t_doc_total counter" in text

    def test_latency_ladder_shape(self):
        # fixed log-spaced ladder: 1 us .. 50 s, 3 buckets per decade
        assert metrics.LATENCY_BUCKETS_S[0] == 1e-6
        assert metrics.LATENCY_BUCKETS_S[-1] == 50.0
        assert 2.5e-3 in metrics.LATENCY_BUCKETS_S
        assert list(metrics.LATENCY_BUCKETS_S) \
            == sorted(metrics.LATENCY_BUCKETS_S)


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------

class TestTracePrimitives:
    def test_id_grammar(self):
        tid = trace.new_trace_id()
        assert trace.is_trace_id(tid)
        assert len(tid) == 16
        assert len({trace.new_trace_id() for _ in range(64)}) == 64

    def test_coerce(self):
        assert trace.coerce_trace_id(" ABCDEF0123456789 ") \
            == "abcdef0123456789"
        for bad in (None, "", "zzzz", "abc", 42, "abcdef012345678g"):
            assert trace.coerce_trace_id(bad) is None

    def test_spans_filter_and_noop(self):
        tid = trace.new_trace_id()
        trace.record_span("unit.op", tid, 0.01, op="argmin")
        assert trace.record_span("unit.op", None, 0.01) is None
        got = trace.recent_spans(trace_id=tid)
        assert len(got) == 1 and got[0].attrs == {"op": "argmin"}
        assert trace.recent_spans(trace_id=tid, name="other") == []

    def test_span_contextmanager(self):
        tid = trace.new_trace_id()
        with trace.span("unit.ctx", tid, stage="x"):
            pass
        sp = trace.recent_spans(trace_id=tid, name="unit.ctx")
        assert sp and sp[0].duration_s >= 0

    def test_kill_switch_silences_spans(self):
        tid = trace.new_trace_id()
        metrics.set_enabled(False)
        try:
            trace.record_span("unit.off", tid, 0.01)
        finally:
            metrics.set_enabled(True)
        assert trace.recent_spans(trace_id=tid) == []

    def test_slow_log_line(self):
        lines = []
        out = trace.slow_log({"event": "slow_request", "trace_id": "ab",
                              "duration_ms": 12.5}, sink=lines.append)
        assert lines == [out]
        assert json.loads(out) == {"event": "slow_request",
                                   "trace_id": "ab", "duration_ms": 12.5}


# ---------------------------------------------------------------------------
# serving paths: the trace propagation matrix + exposition parity
# ---------------------------------------------------------------------------

@pytest.mark.serve
class TestTracePropagationMatrix:
    def test_http_solo(self):
        lines = []
        with PredictionServer(port=0, slow_request_ms=0.0,
                              slow_log_sink=lines.append).start() as srv:
            client = PredictionClient(*srv.address, transport="http")
            tid = trace.new_trace_id()
            client.argmin(small_table(), "b200", trace_id=tid)
            assert_trace_visible(tid, lines)
            assert trace.recent_spans(trace_id=tid, name="serve.eval")
            client.close()

    def test_http_coalesced(self):
        lines = []
        with PredictionServer(port=0, coalesce_window_s=0.2,
                              slow_request_ms=0.0,
                              slow_log_sink=lines.append).start() as srv:
            client = PredictionClient(*srv.address, transport="http")
            tids = [trace.new_trace_id() for _ in range(3)]
            threads = [threading.Thread(
                target=client.argmin,
                args=(small_table(f"co{i}"), "b200"),
                kwargs={"trace_id": tid})
                for i, tid in enumerate(tids)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            assert not any(t.is_alive() for t in threads)
            for tid in tids:
                assert_trace_visible(tid, lines)
                assert trace.recent_spans(trace_id=tid, name="serve.eval")
            client.close()

    def test_binary_pipelined(self):
        lines = []
        with PredictionServer(port=0, binary_port=0,
                              coalesce_window_s=0.1,
                              slow_request_ms=0.0,
                              slow_log_sink=lines.append).start() as srv:
            client = PredictionClient(*srv.address, transport="binary")
            tids = [trace.new_trace_id() for _ in range(2)]
            client.argmin_many(
                [small_table("bp0"), small_table("bp1")], "b200",
                trace_ids=tids)
            for tid in tids:
                assert_trace_visible(tid, lines)
                assert trace.recent_spans(trace_id=tid, name="serve.eval")
            client.close()

    def test_binary_dedup_duplicate(self):
        lines = []
        with PredictionServer(port=0, binary_port=0,
                              coalesce_window_s=0.2,
                              slow_request_ms=0.0,
                              slow_log_sink=lines.append).start() as srv:
            client = PredictionClient(*srv.address, transport="binary")
            table = small_table("dup")
            tids = [trace.new_trace_id() for _ in range(2)]
            client.argmin_many([table, table], "b200", trace_ids=tids)
            assert srv.stats()["coalescer_deduped_requests"] >= 1
            for tid in tids:
                assert_trace_visible(tid, lines)
            # the duplicate kept its own identity through dedup
            dedup_spans = [
                s for tid in tids
                for s in trace.recent_spans(trace_id=tid,
                                            name="serve.eval")
                if s.attrs.get("dedup")]
            assert dedup_spans, "no serve.eval span marked dedup=True"
            client.close()

    def test_poison_isolated_rerun(self):
        lines = []
        with PredictionServer(port=0, engine=PoisonEngine(),
                              coalesce_window_s=0.15,
                              slow_request_ms=0.0,
                              slow_log_sink=lines.append).start() as srv:
            client = PredictionClient(*srv.address, max_retries=0)
            healthy_tids = [trace.new_trace_id() for _ in range(2)]
            poison_tid = trace.new_trace_id()
            failures = {}

            def run(key, table, tid):
                try:
                    client.argmin(table, "b200", trace_id=tid)
                except BaseException as e:     # noqa: BLE001
                    failures[key] = e

            threads = [threading.Thread(target=run, args=(i, t, tid))
                       for i, (t, tid) in enumerate(
                           [(small_table("h0"), healthy_tids[0]),
                            (small_table("h1"), healthy_tids[1]),
                            (poison_table(), poison_tid)])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            assert not any(t.is_alive() for t in threads)
            assert set(failures) == {2}
            assert srv.stats()["coalescer_isolated_failures"] >= 1
            # the healthy batchmates kept their ids through the solo
            # re-run after the poisoned fused batch failed
            for tid in healthy_tids:
                assert_trace_visible(tid, lines)
                solo = [s for s in trace.recent_spans(
                    trace_id=tid, name="serve.eval")
                    if s.attrs.get("solo")]
                assert solo, f"no solo re-run span for {tid}"
            client.close()


@pytest.mark.serve
class TestMetricsEndpoints:
    def test_http_and_binary_serve_the_same_snapshot(self):
        with PredictionServer(port=0, binary_port=0).start() as srv:
            http_c = PredictionClient(*srv.address, transport="http")
            bin_c = PredictionClient(*srv.address, transport="binary")
            http_c.argmin(small_table("m0"), "b200")
            via_http = http_c.metrics_text()
            via_bin = bin_c.metrics_text()

            def families(text):
                return {l for l in text.splitlines()
                        if l.startswith("# TYPE ")}

            def series(text, name):
                return sorted(l for l in text.splitlines()
                              if l.startswith(name))

            assert families(via_http) == families(via_bin)
            # sweep counters were quiescent between the two fetches, so
            # the request-counter samples agree exactly
            assert series(via_http, "repro_serve_requests_total") \
                == series(via_bin, "repro_serve_requests_total")
            http_c.close()
            bin_c.close()

    def test_metrics_endpoint_is_plain_prometheus_text(self):
        import http.client
        with PredictionServer(port=0).start() as srv:
            host, port = srv.address
            conn = http.client.HTTPConnection(host, port, timeout=10)
            conn.request("GET", "/v1/metrics")
            resp = conn.getresponse()
            body = resp.read().decode("utf-8")
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith("text/plain")
            assert "# TYPE repro_serve_queue_depth gauge" in body
            conn.close()

    def test_stats_snapshot_is_consistent(self):
        # satellite 1: stats() reads under one lock — dedup/shed counts
        # can never exceed the requests they derive from, even torn reads
        engine = sweep.SweepEngine()
        co = Coalescer(engine, window_s=0.02)
        try:
            stop = threading.Event()
            bad = []

            def reader():
                while not stop.is_set():
                    s = co.stats_snapshot()
                    if s["deduped_requests"] > s["requests"] or \
                            s["coalesced_requests"] > s["requests"]:
                        bad.append(dict(s))

            r = threading.Thread(target=reader)
            r.start()
            table = small_table("snap")
            threads = [threading.Thread(
                target=co.submit,
                args=("argmin", table, B200, None))
                for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            stop.set()
            r.join(timeout=5.0)
            assert not bad, f"torn stats read: {bad[:3]}"
        finally:
            co.close()


# ---------------------------------------------------------------------------
# the metric-name contract
# ---------------------------------------------------------------------------

#: shipped family names — APPEND-ONLY.  Extending this list is fine;
#: renaming or removing an entry breaks scrapers and dashboards (see
#: serve/README.md "Observability").
EXPECTED_FAMILIES = [
    "repro_client_attempt_seconds",
    "repro_client_attempts_total",
    "repro_client_backoff_seconds_total",
    "repro_client_breaker_open_total",
    "repro_client_retries_total",
    "repro_jax_backend_compiles_total",
    "repro_jax_compile_cache_total",
    "repro_jax_compile_seconds_total",
    "repro_jax_lowerings_total",
    "repro_pool_shard_seconds",
    "repro_pool_straggler_redispatch_total",
    "repro_serve_binary_connections_total",
    "repro_serve_binary_inflight",
    "repro_serve_dedup_rows_saved_total",
    "repro_serve_deduped_requests_total",
    "repro_serve_fused_batch_cost",
    "repro_serve_fused_batch_requests",
    "repro_serve_fused_batch_rows",
    "repro_serve_isolated_failures_total",
    "repro_serve_queue_depth",
    "repro_serve_request_seconds",
    "repro_serve_requests_total",
    "repro_serve_shed_total",
    "repro_serve_slow_requests_total",
    "repro_serve_stage_seconds",
    "repro_sweep_predict_table_seconds",
    "repro_sweep_rows_total",
    "repro_jax_prefill_lowerings_total",
    "repro_jax_mla_lowerings_total",
]


@pytest.mark.serve
def test_metric_name_contract():
    # touching every instrumented layer registers every family
    compile_cache.count_compiles()
    import repro.models.model  # noqa: F401  (its import registers prefill's)
    with PredictionServer(port=0, binary_port=0).start() as srv:
        client = PredictionClient(*srv.address)
        client.argmin(small_table("contract"), "b200")
        client.close()
    missing = set(EXPECTED_FAMILIES) - set(metrics.REGISTRY.family_names())
    assert not missing, \
        f"shipped metric families disappeared (breaking change): {missing}"
