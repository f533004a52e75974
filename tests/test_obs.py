"""Observability plane (``predictionio_tpu/obs``, docs/observability.md).

Five layers:

1. **Registry semantics**: histogram bucket math on the fixed log-scale
   buckets, percentile estimation, the cardinality bound's overflow
   collapse, and schema pinning (name reuse with a different kind/label
   set must raise).
2. **Exposition**: a golden Prometheus text document for a fixed
   registry, label escaping, and the parse round trip ``pio top`` and
   ``loadgen --scrape-metrics`` rely on.
3. **Tracing**: span parent/child structure on injected clocks, ring
   buffer bounds, header sanitization.
4. **Server wiring**: all three servers (query, event, storage) plus the
   dashboard serve ``GET /metrics`` in valid exposition format, and a
   single client-set ``X-PIO-Trace`` id is observable in the span dumps
   of BOTH the query server and the storage server for the same request
   — end-to-end through the remote storage client, and through replica
   failover after the primary dies (the ISSUE 4 acceptance proof).
5. **Instrumentation**: ServingStats percentiles (every pre-existing
   camelCase key preserved), MicroBatcher flush/queue metrics, train
   phase persistence, and the ``obs-*`` lint fixture twins.

Everything runs on injected clocks with zero wall-clock sleeps: the only
waiting anywhere is HTTP round trips on loopback.
"""

from __future__ import annotations

import math
import os
import re
import time

import pytest
import requests

from predictionio_tpu.obs import expo
from predictionio_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    OVERFLOW_VALUE,
    percentile_from_buckets,
)
from predictionio_tpu.obs.trace import (
    TRACE_HEADER,
    SpanStore,
    Tracer,
    sanitize_trace_id,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")


class FakeClock:
    """Injected monotonic clock: advances only when told."""

    def __init__(self, start: float = 1000.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


# ---------------------------------------------------------------------------
# 1. Registry semantics
# ---------------------------------------------------------------------------


class TestHistogramBucketMath:
    def test_default_buckets_are_log_scale(self):
        ratios = {
            round(b2 / b1, 6)
            for b1, b2 in zip(DEFAULT_BUCKETS, DEFAULT_BUCKETS[1:])
        }
        assert ratios == {2.0}
        assert DEFAULT_BUCKETS[0] == pytest.approx(0.0005)

    def test_cumulative_counts_and_sum(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.0, 1.5, 3.0, 100.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(106.0)
        # cumulative: <=1 -> 2 (0.5, 1.0 sits ON the bound), <=2 -> 3,
        # <=4 -> 4, +Inf -> 5
        assert snap["buckets"] == [
            (1.0, 2),
            (2.0, 3),
            (4.0, 4),
            (math.inf, 5),
        ]

    def test_percentile_interpolates_within_bucket(self):
        # 10 observations all in (1, 2]: p50 lands mid-bucket
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=[1.0, 2.0, 4.0])
        for _ in range(10):
            h.observe(1.5)
        assert h.percentile(0.5) == pytest.approx(1.5)
        assert h.percentile(1.0) == pytest.approx(2.0)

    def test_percentile_beyond_last_bucket_clamps(self):
        assert percentile_from_buckets([1.0, 2.0], [0, 0, 5], 0.99) == 2.0

    def test_percentile_empty_is_zero(self):
        assert percentile_from_buckets([1.0], [0, 0], 0.5) == 0.0

    def test_buckets_must_increase(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=[2.0, 1.0])

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_schema_pinning(self):
        reg = MetricsRegistry()
        reg.counter("x", labelnames=("a",))
        assert reg.counter("x", labelnames=("a",)) is reg.counter(
            "x", labelnames=("a",)
        )
        with pytest.raises(ValueError):
            reg.gauge("x")  # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("x", labelnames=("b",))  # label schema mismatch

    def test_label_value_mismatch_raises(self):
        reg = MetricsRegistry()
        c = reg.counter("c", labelnames=("kind",))
        with pytest.raises(ValueError):
            c.inc(1, wrong="x")


class TestCardinalityBound:
    def test_overflow_collapse(self):
        reg = MetricsRegistry(max_label_sets=3)
        c = reg.counter("c", labelnames=("user",))
        for i in range(10):
            c.inc(1, user=f"u{i}")
        series = dict(c.series())
        # 3 real series + ONE overflow absorbing the other 7
        assert len(series) == 4
        assert series[(OVERFLOW_VALUE,)].value == 7
        # the overflow series keeps totals honest
        assert sum(ch.value for ch in series.values()) == 10


# ---------------------------------------------------------------------------
# 2. Exposition
# ---------------------------------------------------------------------------


class TestExposition:
    def test_golden_document(self):
        reg = MetricsRegistry()
        c = reg.counter("pio_requests_total", "Requests", ("route",))
        c.inc(3, route="POST /queries.json")
        g = reg.gauge("pio_lag", "Lag")
        g.set(2.5)
        h = reg.histogram("pio_lat_seconds", "Latency", buckets=[0.1, 1.0])
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        assert expo.render(reg) == (
            "# HELP pio_lag Lag\n"
            "# TYPE pio_lag gauge\n"
            "pio_lag 2.5\n"
            "# HELP pio_lat_seconds Latency\n"
            "# TYPE pio_lat_seconds histogram\n"
            'pio_lat_seconds_bucket{le="0.1"} 1\n'
            'pio_lat_seconds_bucket{le="1"} 2\n'
            'pio_lat_seconds_bucket{le="+Inf"} 3\n'
            "pio_lat_seconds_sum 5.55\n"
            "pio_lat_seconds_count 3\n"
            "# HELP pio_requests_total Requests\n"
            "# TYPE pio_requests_total counter\n"
            'pio_requests_total{route="POST /queries.json"} 3\n'
        )

    def test_label_escaping_round_trips(self):
        reg = MetricsRegistry()
        nasty = 'a"b\\c\nd'
        reg.counter("c", labelnames=("v",)).inc(1, v=nasty)
        parsed = expo.parse_text(expo.render(reg))
        assert parsed["c"] == [({"v": nasty}, 1.0)]

    def test_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.gauge("g", labelnames=("a", "b")).set(7, a="x", b="y")
        h = reg.histogram("h", buckets=[1.0])
        h.observe(0.5)
        parsed = expo.parse_text(expo.render(reg))
        assert parsed["g"] == [({"a": "x", "b": "y"}, 7.0)]
        assert ({"le": "+Inf"}, 1.0) in parsed["h_bucket"]
        assert parsed["h_count"] == [({}, 1.0)]

    def test_nan_and_infinities_never_break_render(self):
        reg = MetricsRegistry()
        reg.gauge("g_nan").set(float("nan"))
        reg.gauge("g_ninf").set(float("-inf"))
        reg.gauge_callback("g_cb", lambda: float("nan"))
        text = expo.render(reg)  # must not raise — ever
        assert "g_nan NaN" in text
        assert "g_ninf -Inf" in text
        parsed = expo.parse_text(text)
        assert math.isnan(parsed["g_nan"][0][1])
        assert parsed["g_ninf"][0][1] == -math.inf

    def test_backslash_before_n_round_trips(self):
        # 'a\nb' with a LITERAL backslash then n: chained unescape would
        # corrupt it into a newline
        reg = MetricsRegistry()
        reg.counter("c", labelnames=("v",)).inc(1, v="a\\nb")
        parsed = expo.parse_text(expo.render(reg))
        assert parsed["c"] == [({"v": "a\\nb"}, 1.0)]

    def test_instrument_clear_drops_series(self):
        reg = MetricsRegistry()
        g = reg.gauge("g", labelnames=("phase",))
        g.set(1.0, phase="old")
        g.clear()
        g.set(2.0, phase="new")
        assert [key for key, _c in g.series()] == [("new",)]

    def test_callback_gauge_pulled_at_collect(self):
        state = {"v": 1}
        reg = MetricsRegistry()
        reg.gauge_callback("g", lambda: state["v"], labels={"dep": "x"})
        assert 'g{dep="x"} 1' in expo.render(reg)
        state["v"] = 9
        assert 'g{dep="x"} 9' in expo.render(reg)


# ---------------------------------------------------------------------------
# 3. Tracing
# ---------------------------------------------------------------------------


class TestTracer:
    def test_spans_on_injected_clocks(self):
        clock, wall = FakeClock(0.0), FakeClock(5000.0)
        tracer = Tracer("svc", clock=clock, wall=wall)
        with tracer.server_span("root", header_value="abc123") as root:
            clock.advance(0.25)
            with tracer.span("child", tags={"k": "v"}) as child:
                clock.advance(0.5)
            assert child.trace_id == "abc123"
        spans = tracer.store.dump()
        assert [s["name"] for s in spans] == ["child", "root"]
        child_s, root_s = spans
        assert root_s["traceId"] == child_s["traceId"] == "abc123"
        assert child_s["parentId"] == root_s["spanId"]
        assert root_s["durationMs"] == pytest.approx(750.0)
        assert child_s["durationMs"] == pytest.approx(500.0)
        assert child_s["tags"] == {"k": "v"}
        assert root_s["kind"] == "server"

    def test_error_spans_tagged(self):
        tracer = Tracer("svc", clock=FakeClock(), wall=FakeClock())
        with pytest.raises(RuntimeError):
            with tracer.server_span("boom"):
                raise RuntimeError("x")
        assert tracer.store.dump()[0]["error"] == "RuntimeError"

    def test_missing_header_mints_id(self):
        tracer = Tracer("svc", clock=FakeClock(), wall=FakeClock())
        with tracer.server_span("r", header_value=None) as ctx:
            pass
        assert re.fullmatch(r"[0-9a-f]{16}", ctx.trace_id)

    def test_sanitize(self):
        assert sanitize_trace_id("  ok-id_1.2  ") == "ok-id_1.2"
        assert sanitize_trace_id('ha"}\n{x') == "hax"
        assert sanitize_trace_id("") is None
        assert sanitize_trace_id("x" * 200) == "x" * 64

    def test_ring_buffer_bounds(self):
        store = SpanStore(capacity=3)
        for i in range(10):
            store.add({"traceId": "t", "i": i})
        assert [s["i"] for s in store.dump()] == [7, 8, 9]


# ---------------------------------------------------------------------------
# 4. Server wiring (the acceptance layer)
# ---------------------------------------------------------------------------

def _spans_once_named(store, trace_id, name, timeout_s=5.0):
    """A trace's spans once one named ``name`` is there. A server records
    its admission span when the handler returns, AFTER the response has
    gone out: a client that reads the store the moment it has its answer
    can be ahead of that by a thread switch (seen once under six xdist
    workers), so the read waits for the span, within a bound."""
    deadline = time.monotonic() + timeout_s
    while True:
        spans = store.for_trace(trace_id)
        if any(s["name"] == name for s in spans) or time.monotonic() > deadline:
            return spans
        time.sleep(0.005)


#: every exposition line is a comment or `name[{labels}] value`
_EXPO_LINE = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+)$"
)


def _assert_valid_exposition(text: str) -> dict:
    for line in text.rstrip("\n").splitlines():
        assert _EXPO_LINE.match(line), f"invalid exposition line: {line!r}"
    parsed = expo.parse_text(text)
    assert parsed, "no samples in exposition"
    return parsed


@pytest.fixture()
def registry(tmp_path):
    from predictionio_tpu.storage import StorageRegistry

    return StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)})


def _storage_pair(tmp_path):
    """Primary (with changefeed) + tailing replica, background-started."""
    from predictionio_tpu.storage import MetadataStore, SqliteEventStore
    from predictionio_tpu.storage.changefeed import Changefeed
    from predictionio_tpu.storage.model_store import SqliteModelStore
    from predictionio_tpu.storage.oplog import OpLog
    from predictionio_tpu.storage.replica import StorageReplica
    from predictionio_tpu.storage.storage_server import StorageServer

    primary = StorageServer(
        "127.0.0.1", 0,
        SqliteEventStore(":memory:"), MetadataStore(":memory:"),
        SqliteModelStore(":memory:"),
    )
    primary.changefeed = Changefeed(
        OpLog(str(tmp_path / "oplog")),
        primary.events, primary.metadata, primary.models,
    )
    primary.start_background()
    replica = StorageReplica(
        "127.0.0.1", 0,
        SqliteEventStore(":memory:"), MetadataStore(":memory:"),
        SqliteModelStore(":memory:"),
        f"http://127.0.0.1:{primary.bound_port}",
        str(tmp_path / "replica_state"),
        catchup_wait_s=0.0,
    )
    replica.start_background()
    return primary, replica


class TestMetricsRoutes:
    def test_event_server_metrics(self, registry):
        from predictionio_tpu.api import EventServer, EventServerConfig

        srv = EventServer(
            EventServerConfig(ip="127.0.0.1", port=0),
            events=registry.get_events(),
            metadata=registry.get_metadata(),
        )
        srv.start_background()
        try:
            base = f"http://127.0.0.1:{srv.bound_port}"
            assert requests.get(base + "/").status_code == 200
            r = requests.get(base + "/metrics")
            assert r.status_code == 200
            assert r.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4"
            )
            parsed = _assert_valid_exposition(r.text)
            assert "pio_http_responses_total" in parsed
            assert "pio_http_request_seconds_count" in parsed
        finally:
            srv.shutdown()
            srv.server_close()

    def test_storage_server_and_replica_metrics(self, tmp_path):
        primary, replica = _storage_pair(tmp_path)
        try:
            base = f"http://127.0.0.1:{primary.bound_port}"
            from predictionio_tpu.storage import remote

            store = remote.RemoteEventStore(base)
            store.init(1)
            replica.catch_up()
            parsed = _assert_valid_exposition(
                requests.get(base + "/metrics").text
            )
            assert parsed["pio_changefeed_seq"][0][1] >= 1
            assert "pio_storage_op_seconds_count" in parsed
            rparsed = _assert_valid_exposition(
                requests.get(
                    f"http://127.0.0.1:{replica.bound_port}/metrics"
                ).text
            )
            assert rparsed["pio_replication_lag_ops"][0][1] == 0
        finally:
            primary.kill()
            replica.kill()

    def test_dashboard_metrics_and_train_runs(self, registry):
        from predictionio_tpu.tools.dashboard import (
            DashboardConfig,
            DashboardServer,
        )

        srv = DashboardServer(
            DashboardConfig(ip="127.0.0.1", port=0), registry
        )
        srv.start_background()
        try:
            base = f"http://127.0.0.1:{srv.bound_port}"
            _assert_valid_exposition(requests.get(base + "/metrics").text)
            assert requests.get(base + "/train_runs").status_code == 200
            assert (
                requests.get(base + "/train_runs.json").json() == []
            )
        finally:
            srv.shutdown()
            srv.server_close()


# -- the query-server end-to-end (needs a trained toy engine) ---------------


def _make_query_server(registry, remote_store, clock=None):
    """Train the sample engine and deploy it with a Serving whose
    supplement reads through ``remote_store`` — the realistic serve-time
    storage dependency the trace must follow."""
    import time

    from predictionio_tpu.controller import Engine, WorkflowParams
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.serving import QueryServer, ServerConfig

    from sample_engine import (
        Algo0,
        DataSource0,
        Preparator0,
        Query,
        Serving0,
    )
    from test_engine import make_params

    class TypedAlgo(Algo0):
        count = 0

        def query_class(self):
            return Query

    class RemoteReadingServing(Serving0):
        count = 0
        store = remote_store

        def supplement(self, query):
            if type(self).store is not None:
                type(self).store.get("missing-event", 1)
            return query

    engine = Engine(
        {"": DataSource0},
        {"": Preparator0},
        {"": TypedAlgo},
        {"": RemoteReadingServing},
    )
    run_train(
        engine, make_params(algo_ids=(11,)), registry,
        engine_id="default", engine_version="1",
        workflow_params=WorkflowParams(batch="obs-test"),
    )
    server = QueryServer(
        ServerConfig(ip="127.0.0.1", port=0, batch_wait_ms=0.0),
        engine,
        registry,
        clock=clock or time.monotonic,
    )
    server.start_background()
    return server


class TestTraceEndToEnd:
    """The ISSUE 4 acceptance: one client-set ``X-PIO-Trace`` id visible
    in the span dumps of the query server AND the storage server for the
    same request — and, across failover, in the replica's."""

    @pytest.fixture(autouse=True)
    def _fast_breaker(self, monkeypatch):
        from predictionio_tpu.storage import remote

        monkeypatch.setenv("PIO_BREAKER_FAILURES", "1")
        remote.reset_resilience()
        yield
        remote.reset_resilience()

    def test_trace_id_spans_query_and_storage_servers(
        self, registry, tmp_path
    ):
        from predictionio_tpu.storage import remote
        from predictionio_tpu.storage.event import Event

        primary, replica = _storage_pair(tmp_path)
        clock = FakeClock()
        server = None
        try:
            # injected clocks on every tracer in the chain: durations are
            # deterministic, nothing sleeps
            primary.tracer = Tracer(
                "storage-server", clock=FakeClock(), wall=FakeClock()
            )
            replica.tracer = Tracer(
                "storage-replica", clock=FakeClock(), wall=FakeClock()
            )
            store = remote.RemoteEventStore(
                f"pio+ha://127.0.0.1:{primary.bound_port},"
                f"127.0.0.1:{replica.bound_port}",
                timeout=10.0,
            )
            store.init(1)
            store.insert(
                Event(event="rate", entity_type="user", entity_id="u1"), 1
            )
            replica.catch_up()
            server = _make_query_server(registry, store, clock=clock)
            base = f"http://127.0.0.1:{server.bound_port}"

            tid = "e2e-trace-0001"
            r = requests.post(
                f"{base}/queries.json",
                json={"id": 1},
                headers={TRACE_HEADER: tid},
            )
            assert r.status_code == 200
            assert r.headers[TRACE_HEADER] == tid

            # query-server side: admission span + the remote client span
            qspans = _spans_once_named(
                server.tracer.store, tid, "POST /queries.json"
            )
            names = {s["name"] for s in qspans}
            assert "POST /queries.json" in names
            assert "storage.GET" in names
            # the micro-batcher's queue-wait/device split rode the same
            # trace (captured across the thread hop)
            assert {"batch.queue-wait", "batch.device"} <= names
            # storage-server side: same trace id at admission, via the
            # X-PIO-Trace header the remote client forwarded
            pspans = _spans_once_named(
                primary.tracer.store, tid, "GET /events"
            )
            assert any(s["name"] == "GET /events" for s in pspans)
            assert all(s["service"] == "storage-server" for s in pspans)

            # -- failover leg: kill the primary; the same client trace id
            # must surface in the REPLICA's span dump
            primary.kill()
            tid2 = "e2e-trace-0002"
            r = requests.post(
                f"{base}/queries.json",
                json={"id": 2},
                headers={TRACE_HEADER: tid2},
            )
            assert r.status_code == 200
            rspans = _spans_once_named(
                replica.tracer.store, tid2, "GET /events"
            )
            assert any(s["name"] == "GET /events" for s in rspans)
            assert all(s["service"] == "storage-replica" for s in rspans)

            # /traces.json exposes the same dumps over HTTP, and the CLI
            # stitches them (pio trace)
            doc = requests.get(f"{base}/traces.json").json()
            assert doc["service"] == "query-server"
            assert any(s["traceId"] == tid for s in doc["spans"])
            from predictionio_tpu.obs.top import collect_trace, render_trace

            nodes = (
                f"127.0.0.1:{server.bound_port},"
                f"127.0.0.1:{replica.bound_port}"
            )
            stitched = collect_trace(tid2, nodes)
            assert {s["service"] for s in stitched} >= {
                "query-server",
                "storage-replica",
            }
            assert tid2 in render_trace(tid2, stitched)

            # -- jit telemetry rides the same exposition (ISSUE 8): the
            # process telemetry is bound to this server's registry, so a
            # compile observed anywhere in-process surfaces as series on
            # the query server's /metrics. Driven with a fake jitted fn
            # so the assertion is deterministic under any cache warmth.
            from predictionio_tpu.obs.profile import default_telemetry

            class _FakeJit:
                def __init__(self):
                    self._sigs = set()

                def _cache_size(self):
                    return len(self._sigs)

                def __call__(self, sig):
                    self._sigs.add(sig)
                    return sig

            fake = _FakeJit()
            default_telemetry().call("obs_e2e.fn", fake, "a")
            default_telemetry().call("obs_e2e.fn", fake, "b")
            text = requests.get(f"{base}/metrics").text
            parsed = _assert_valid_exposition(text)
            compiles = {
                labels.get("fn"): value
                for labels, value in parsed["pio_jit_compiles_total"]
            }
            assert compiles["obs_e2e.fn"] == 2.0
            retraces = {
                labels.get("fn"): value
                for labels, value in parsed["pio_jit_retraces_total"]
            }
            assert retraces["obs_e2e.fn"] == 1.0
            assert "pio_jit_compile_seconds_bucket" in parsed
            assert "pio_jit_cache_hits" in parsed
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            primary.kill()
            replica.kill()

    def test_feedback_delivery_carries_trace(self, registry, monkeypatch):
        """The feedback POST (pool thread) forwards the request's trace
        id: the Event Server's admission span joins the trace."""
        import dataclasses as dc

        from predictionio_tpu.api import EventServer, EventServerConfig
        from predictionio_tpu.storage.metadata import AccessKey

        md = registry.get_metadata()
        registry.get_events().init(1)
        from predictionio_tpu.storage.metadata import App

        app_id = md.app_insert(App(id=0, name="obs-app"))
        md.access_key_insert(AccessKey(key="k", appid=app_id, events=()))
        es = EventServer(
            EventServerConfig(ip="127.0.0.1", port=0),
            events=registry.get_events(),
            metadata=md,
        )
        es.start_background()
        server = None
        try:
            server = _make_query_server(registry, None)
            server.config = dc.replace(
                server.config,
                feedback=True,
                event_server_ip="127.0.0.1",
                event_server_port=es.bound_port,
                access_key="k",
            )
            tid = "feedback-trace-01"
            r = requests.post(
                f"http://127.0.0.1:{server.bound_port}/queries.json",
                json={"id": 3},
                headers={TRACE_HEADER: tid},
            )
            assert r.status_code == 200
            server._feedback_pool.shutdown(wait=True)  # drain delivery
            es_names = {
                s["name"]
                for s in _spans_once_named(
                    es.tracer.store, tid, "POST /events.json"
                )
            }
            assert "POST /events.json" in es_names
            q_names = {
                s["name"] for s in server.tracer.store.for_trace(tid)
            }
            assert "serving.feedback" in q_names
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            es.shutdown()
            es.server_close()


# ---------------------------------------------------------------------------
# 5. Instrumentation details
# ---------------------------------------------------------------------------


class TestServingStats:
    def test_percentiles_and_preserved_keys(self):
        from predictionio_tpu.workflow.serving import ServingStats

        stats = ServingStats()
        for ms in (1, 1, 1, 1, 1, 1, 1, 1, 1, 500):
            stats.record_request(ms / 1000.0)
        stats.inc("shed")
        snap = stats.snapshot()
        # every pre-observability wire key survives
        for key in (
            "requests", "lastServingMs", "avgServingMs", "shed",
            "deadlineExpired", "feedbackSent", "feedbackFailures",
            "feedbackSkipped", "errorLogFailures", "errorLogSkipped",
        ):
            assert key in snap, key
        assert snap["requests"] == 10
        assert snap["shed"] == 1
        # the tail is no longer invisible: p50 stays ~1ms while p99
        # reflects the 500ms outlier the average smears away
        assert snap["p50Ms"] < 10
        assert snap["p99Ms"] > 100
        assert snap["p95Ms"] >= snap["p50Ms"]

    def test_unknown_counter_still_rejected(self):
        from predictionio_tpu.workflow.serving import ServingStats

        with pytest.raises(ValueError):
            ServingStats().inc("nope")


class TestBatcherMetrics:
    def test_flush_reasons_and_queue_metrics(self):
        from predictionio_tpu.workflow.batching import MicroBatcher

        reg = MetricsRegistry()
        mb = MicroBatcher(
            lambda items: [x * 2 for x in items],
            max_batch=4,
            max_wait_ms=0.0,
            metrics=reg,
        )
        try:
            assert mb.submit(21) == 42
        finally:
            mb.close()
        parsed = expo.parse_text(expo.render(reg))
        assert parsed["pio_batch_size_count"][0][1] == 1
        assert parsed["pio_batch_items_total"][0][1] == 1
        flushes = {
            labels["reason"]: v
            for labels, v in parsed["pio_batch_flush_total"]
        }
        assert sum(flushes.values()) == 1
        assert parsed["pio_batch_queue_wait_seconds_count"][0][1] == 1

    def test_failed_batches_still_counted(self):
        from predictionio_tpu.workflow.batching import MicroBatcher

        def boom(items):
            raise RuntimeError("device died")

        reg = MetricsRegistry()
        mb = MicroBatcher(boom, max_batch=1, max_wait_ms=0.0, metrics=reg)
        try:
            with pytest.raises(RuntimeError, match="device died"):
                mb.submit(1)
        finally:
            mb.close()
        parsed = expo.parse_text(expo.render(reg))
        # the erroring fleet is exactly when the batch signals matter:
        # the failed batch still counts as a flush AND as a failure
        assert parsed["pio_batch_failures_total"][0][1] == 1
        assert sum(v for _l, v in parsed["pio_batch_flush_total"]) == 1
        assert parsed["pio_batch_size_count"][0][1] == 1


class TestTrainPhases:
    def test_persisted_and_served(self, registry):
        from predictionio_tpu.utils.profiling import (
            TRAIN_PHASES_ENV_KEY,
            phases_from_env,
        )

        server = _make_query_server(registry, None)
        try:
            inst = server.deployment.instance
            assert TRAIN_PHASES_ENV_KEY in inst.env
            phases = phases_from_env(inst.env)
            assert {"read", "prepare", "train[0]"} <= set(phases)
            status = requests.get(
                f"http://127.0.0.1:{server.bound_port}/status.json"
            ).json()
            assert set(status["trainPhases"]) == set(phases)
            parsed = _assert_valid_exposition(
                requests.get(
                    f"http://127.0.0.1:{server.bound_port}/metrics"
                ).text
            )
            exported = {
                labels["phase"]
                for labels, _v in parsed["pio_train_phase_seconds"]
            }
            assert exported == set(phases)
        finally:
            server.shutdown()
            server.server_close()

    def test_reload_clears_stale_phase_series(self, registry):
        """A redeploy to an instance without phase data must not leave
        the old instance's gauges on /metrics."""
        import dataclasses as dc

        server = _make_query_server(registry, None)
        try:
            gauge = server.metrics.gauge(
                "pio_train_phase_seconds", labelnames=("phase",)
            )
            assert gauge.series()  # exported at deploy time
            server.deployment = dc.replace(
                server.deployment,
                instance=dc.replace(server.deployment.instance, env={}),
            )
            server._export_train_phases()
            assert gauge.series() == []
            status = requests.get(
                f"http://127.0.0.1:{server.bound_port}/status.json"
            ).json()
            assert "trainPhases" not in status
        finally:
            server.shutdown()
            server.server_close()

    def test_phases_from_env_tolerates_garbage(self):
        from predictionio_tpu.utils.profiling import (
            TRAIN_PHASES_ENV_KEY,
            phases_from_env,
        )

        assert phases_from_env(None) == {}
        assert phases_from_env({}) == {}
        assert phases_from_env({TRAIN_PHASES_ENV_KEY: "{not json"}) == {}


class TestLoadgenScrape:
    def test_digest_serving_metrics(self):
        from predictionio_tpu.tools.loadgen import digest_serving_metrics
        from predictionio_tpu.workflow.serving import ServingStats

        stats = ServingStats()
        for _ in range(100):
            stats.record_request(0.002)
        stats.inc("shed")
        digest = digest_serving_metrics(
            expo.parse_text(expo.render(stats.metrics))
        )
        assert digest["requests"] == 100
        assert 0 < digest["p50_ms"] < 10
        assert digest["p99_ms"] >= digest["p50_ms"]
        assert digest["shed"] == 1


class TestPioTop:
    def test_node_row_and_table(self, tmp_path):
        primary, replica = _storage_pair(tmp_path)
        try:
            from predictionio_tpu.obs.top import node_row, render_table

            rows = [
                node_row(f"127.0.0.1:{primary.bound_port}"),
                node_row(f"127.0.0.1:{replica.bound_port}"),
                node_row("127.0.0.1:1"),  # nothing listens here
            ]
            assert rows[0]["up"] and rows[1]["up"]
            assert rows[1]["lag"] == 0
            assert rows[2] == {"node": "127.0.0.1:1", "up": False}
            # garbled node specs render DOWN, never crash the table
            assert node_row("127.0.0.1:abc")["up"] is False
            table = render_table(rows)
            assert "NODE" in table and "LAG" in table and "DOWN" in table
        finally:
            primary.kill()
            replica.kill()

    def test_console_has_top_and_trace(self):
        from predictionio_tpu.tools.console import build_parser

        p = build_parser()
        args = p.parse_args(["top", "--nodes", "a:1", "--json"])
        assert args.command == "top" and args.nodes == "a:1"
        args = p.parse_args(["trace", "deadbeef", "--nodes", "a:1"])
        assert args.command == "trace" and args.trace_id == "deadbeef"


# ---------------------------------------------------------------------------
# obs-* lint fixtures (the round-5 fixture discipline, family D)
# ---------------------------------------------------------------------------


class TestObsLintFixtures:
    def _unsuppressed(self, path):
        from predictionio_tpu.lint import lint_file

        return [f for f in lint_file(path) if not f.suppressed]

    def test_bad_fixture_fires_exactly_intended_rule(self):
        path = os.path.join(FIXTURES, "obs_label_bad.py")
        findings = self._unsuppressed(path)
        assert [f.rule_id for f in findings] == ["obs-unbounded-label"], [
            (f.rule_id, f.line) for f in findings
        ]
        with open(path) as fh:
            marked = next(
                i for i, line in enumerate(fh, 1) if "BAD" in line
            )
        assert findings[0].line == marked

    def test_clean_twin_has_no_findings(self):
        findings = self._unsuppressed(
            os.path.join(FIXTURES, "obs_label_clean.py")
        )
        assert findings == [], [(f.rule_id, f.line) for f in findings]

    def test_interpolation_shapes_all_flagged(self):
        from predictionio_tpu.lint import lint_file

        src = (
            "def f(c, uid):\n"
            "    c.inc(1, user=f'u-{uid}')\n"
            "    c.inc(1, user='u-' + uid)\n"
            "    c.inc(1, user='u-%s' % uid)\n"
            "    c.inc(1, user='u-{}'.format(uid))\n"
            "    c.inc(1, user=str(uid))\n"
            "    c.labels(user=f'{uid}').inc()\n"
        )
        findings = [
            f
            for f in lint_file("x.py", source=src)
            if f.rule_id == "obs-unbounded-label"
        ]
        assert len(findings) == 6

    def test_bounded_shapes_clean(self):
        from predictionio_tpu.lint import lint_file

        src = (
            "def f(c, route, reg, breaker):\n"
            "    c.inc(1, route=route)\n"
            "    c.inc(1, route='POST /queries.json')\n"
            "    c.inc(2.0, amount=2.0)\n"
            "    reg.gauge_callback('g', lambda: 1, labels={'dep': 'es'})\n"
        )
        findings = [
            f
            for f in lint_file("x.py", source=src)
            if f.rule_id == "obs-unbounded-label"
        ]
        assert findings == []
