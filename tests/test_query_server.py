"""Query server tests: deploy lifecycle + REST surface.

Covers the behaviors of ``CreateServer.scala``: latest-completed instance
selection, query decode → multi-algo predict → serving combine, the
``/reload`` hot swap, ``/stop``, the status page bookkeeping
(``:567-574``) and the feedback loop with prId stamping (``:505-565``).
"""

import time

import pytest
import requests

from predictionio_tpu.api import EventServer, EventServerConfig
from predictionio_tpu.controller import WorkflowParams
from predictionio_tpu.storage import (
    AccessKey,
    App,
    EventFilter,
    StorageRegistry,
)
from predictionio_tpu.workflow.core_workflow import run_train
from predictionio_tpu.workflow.serving import (
    QueryServer,
    ServerConfig,
    decode_query,
    encode_result,
    prepare_deployment,
)

from sample_engine import Query, reset_all_counts
from test_engine import make_engine, make_params


@pytest.fixture(autouse=True)
def _reset():
    reset_all_counts()


@pytest.fixture()
def registry(tmp_path):
    return StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)})


class TypedQueryAlgoMixin:
    def query_class(self):
        return Query


def _typed_engine():
    from sample_engine import Algo0, DataSource0, Preparator0, Serving0
    from predictionio_tpu.controller import Engine

    class TypedAlgo(TypedQueryAlgoMixin, Algo0):
        count = 0

    return Engine(
        {"": DataSource0},
        {"": Preparator0},
        {"": TypedAlgo, "second": TypedAlgo},
        {"": Serving0},
    )


def _train(registry, engine, algo_ids=(11,)):
    params = make_params(algo_ids=algo_ids)
    if len(algo_ids) > 1:
        import dataclasses as dc
        from sample_engine import IdParams

        params = dc.replace(
            params,
            algorithm_params_list=[
                ("" if i == 0 else "second", IdParams(id=a))
                for i, a in enumerate(algo_ids)
            ],
        )
    return run_train(
        engine, params, registry, engine_id="default", engine_version="1",
        workflow_params=WorkflowParams(batch="deploy-test"),
    )


@pytest.fixture()
def server(registry):
    engine = _typed_engine()
    _train(registry, engine, algo_ids=(11, 13))
    srv = QueryServer(
        ServerConfig(ip="127.0.0.1", port=0), engine, registry
    )
    srv.start_background()
    yield f"http://127.0.0.1:{srv.bound_port}", srv, registry, engine
    try:
        srv.shutdown()
        srv.server_close()
    except Exception:
        pass


def test_prepare_deployment_picks_latest_completed(registry):
    engine = make_engine()
    _train(registry, engine)
    second = _train(registry, engine)
    dep = prepare_deployment(engine, registry, ServerConfig())
    assert dep.instance.id == second


def test_prepare_deployment_no_instance_raises(registry):
    with pytest.raises(RuntimeError, match="No completed engine instance"):
        prepare_deployment(make_engine(), registry, ServerConfig())


def test_query_roundtrip(server):
    base, srv, _, _ = server
    r = requests.post(f"{base}/queries.json", json={"id": 42})
    assert r.status_code == 200
    body = r.json()
    # Serving0 combines both algos' predictions
    assert body["combined"] == [11, 13]
    assert body["query"]["id"] == 42
    assert srv.request_count == 1
    assert srv.avg_serving_sec > 0


def test_query_malformed_json_400(server):
    base, _, _, _ = server
    r = requests.post(
        f"{base}/queries.json",
        data="{nope",
        headers={"Content-Type": "application/json"},
    )
    assert r.status_code == 400


def test_status_page(server):
    base, _, _, _ = server
    requests.post(f"{base}/queries.json", json={"id": 1})
    r = requests.get(f"{base}/")
    assert r.status_code == 200
    assert "Engine Server" in r.text
    assert "Request count" in r.text


def test_status_json_reports_resolved_topk_path(server):
    """/status.json surfaces each algorithm's RESOLVED serving top-k
    path ("streaming" | "dense") once it has served — the serve-side
    lever record (docs/performance.md#levers). Sample-engine algos
    don't expose one, so the block is absent here; an algo that does is
    picked up by name."""
    base, srv, _, _ = server
    requests.post(f"{base}/queries.json", json={"id": 1})
    doc = requests.get(f"{base}/status.json").json()
    assert "topkPath" not in doc  # sample algos carry no topk_path
    # graft a reporting algorithm in: the server reads the attribute
    srv.deployment.algorithms[0].topk_path = "dense"
    try:
        doc = requests.get(f"{base}/status.json").json()
        key = f"0:{type(srv.deployment.algorithms[0]).__name__}"
        assert doc["topkPath"] == {key: "dense"}
    finally:
        del srv.deployment.algorithms[0].topk_path


def test_status_json_reports_the_device(server):
    """/status.json names the device JAX gave the server process — a
    deploy that came up on the wrong backend cannot look healthy."""
    import jax

    base, _, _, _ = server
    doc = requests.get(f"{base}/status.json").json()
    assert doc["device"] == {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }


def test_reload_hot_swaps_to_latest(server):
    base, srv, registry, engine = server
    old_id = srv.deployment.instance.id
    new_id = _train(registry, engine, algo_ids=(11, 13))
    assert new_id != old_id
    r = requests.get(f"{base}/reload")
    assert r.status_code == 200
    assert srv.deployment.instance.id == new_id
    # still serves correctly after the swap
    r = requests.post(f"{base}/queries.json", json={"id": 7})
    assert r.status_code == 200


def test_reload_under_traffic(server):
    """Hot swap while queries are in flight: the micro-batcher may see a
    batch mixing deployments across the swap — the mixed-generation
    grouping in ``QueryServer._predict_batch`` must route every query to
    its own deployment and none may error (``GET /reload`` parity with
    the MasterActor swap, ``CreateServer.scala:250-372``)."""
    import threading

    base, srv, registry, engine = server
    stop = threading.Event()
    failures = []
    ok = [0]

    def hammer():
        while not stop.is_set():
            try:
                r = requests.post(f"{base}/queries.json", json={"id": 3},
                                  timeout=10)
                if r.status_code != 200 or r.json()["combined"] != [11, 13]:
                    failures.append(r.text[:200])
                else:
                    ok[0] += 1
            except Exception as exc:
                failures.append(repr(exc))

    workers = [threading.Thread(target=hammer) for _ in range(8)]
    for w in workers:
        w.start()
    try:
        for _ in range(3):  # three hot swaps under load
            new_id = _train(registry, engine, algo_ids=(11, 13))
            r = requests.get(f"{base}/reload", timeout=30)
            assert r.status_code == 200
            assert srv.deployment.instance.id == new_id
            time.sleep(0.2)
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30)
    assert not failures, failures[:3]
    assert ok[0] > 20  # real traffic flowed throughout


def test_repeated_reloads_drop_retired_model_references(server):
    """Swapping a ``Deployment`` must drop every server-side reference
    to the retired models so device buffers are reclaimable — a leak
    here grows resident HBM by one model table per retrain forever
    (docs/rollouts.md teardown contract)."""
    import gc
    import weakref

    base, srv, registry, engine = server
    retired = []
    for _ in range(3):
        retired.append(weakref.ref(srv.deployment.models[0]))
        _train(registry, engine, algo_ids=(11, 13))
        r = requests.post(f"{base}/reload")
        assert r.status_code == 200
    gc.collect()
    assert [ref() for ref in retired] == [None, None, None]


def test_stop_shuts_down(server):
    base, srv, _, _ = server
    r = requests.get(f"{base}/stop")
    assert r.status_code == 200
    deadline = time.time() + 5
    while time.time() < deadline:
        try:
            requests.get(f"{base}/", timeout=0.2)
            time.sleep(0.05)
        except (requests.ConnectionError, requests.Timeout):
            break
    else:
        pytest.fail("server did not shut down")


def test_feedback_loop(registry, tmp_path):
    # stand up an event server to receive feedback
    md = registry.get_metadata()
    app_id = md.app_insert(App(id=0, name="fbapp"))
    md.access_key_insert(AccessKey(key="FBKEY", appid=app_id, events=[]))
    registry.get_events().init(app_id)
    ev_srv = EventServer(
        EventServerConfig(ip="127.0.0.1", port=0, stats=False),
        registry.get_events(),
        md,
    )
    ev_srv.start_background()

    engine = _typed_engine()
    _train(registry, engine)
    q_srv = QueryServer(
        ServerConfig(
            ip="127.0.0.1",
            port=0,
            feedback=True,
            event_server_ip="127.0.0.1",
            event_server_port=ev_srv.bound_port,
            access_key="FBKEY",
        ),
        engine,
        registry,
    )
    q_srv.start_background()
    try:
        base = f"http://127.0.0.1:{q_srv.bound_port}"
        r = requests.post(f"{base}/queries.json", json={"id": 5})
        assert r.status_code == 200
        deadline = time.time() + 5
        events = []
        while time.time() < deadline and not events:
            events = list(
                registry.get_events().find(
                    app_id, EventFilter(event_names=["predict"])
                )
            )
            time.sleep(0.05)
        assert len(events) == 1
        fb = events[0]
        assert fb.entity_type == "pio_pr"
        assert len(fb.entity_id) == 64
        assert fb.properties.get("query")["id"] == 5
        assert fb.properties.get("prediction")["combined"] == [11]
    finally:
        q_srv.shutdown()
        q_srv.server_close()
        ev_srv.shutdown()
        ev_srv.server_close()


def test_decode_query_typed_and_untyped():
    class A:
        def query_class(self):
            return Query

    assert decode_query([A()], {"id": 9}) == Query(id=9)

    class B:
        def query_class(self):
            return None

    assert decode_query([B()], {"x": 1}) == {"x": 1}


def test_encode_result_nested():
    import dataclasses

    @dataclasses.dataclass
    class Inner:
        v: int

    @dataclasses.dataclass
    class Outer:
        inner: Inner
        xs: tuple

    import numpy as np

    assert encode_result(Outer(Inner(3), (1, np.float32(2.5)))) == {
        "inner": {"v": 3},
        "xs": [1, 2.5],
    }


def test_error_log_posted_to_log_url(registry):
    """Serving failures POST to --log-url (CreateServer.scala:409-420)."""
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    received = []
    got_one = threading.Event()

    class Sink(BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
            received.append(_json.loads(body))
            got_one.set()
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = ThreadingHTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=sink.serve_forever, daemon=True).start()

    engine = _typed_engine()
    _train(registry, engine, algo_ids=(11, 13))
    srv = QueryServer(
        ServerConfig(
            ip="127.0.0.1", port=0,
            log_url=f"http://127.0.0.1:{sink.server_address[1]}/log",
        ),
        engine, registry,
    )
    srv.start_background()
    base = f"http://127.0.0.1:{srv.bound_port}"
    try:
        # Serving0 raises on a poison query marker → 500 → error log POST
        import unittest.mock as mock

        with mock.patch.object(
            srv.deployment.serving, "serve",
            side_effect=RuntimeError("boom-for-log"),
        ):
            r = requests.post(f"{base}/queries.json", json={"id": 1})
        assert r.status_code == 500
        assert got_one.wait(timeout=10)
        assert received[0]["message"] == "boom-for-log"
        assert received[0]["query"] == {"id": 1}
    finally:
        srv.shutdown()
        srv.server_close()
        sink.shutdown()
        sink.server_close()
