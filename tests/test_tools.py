"""CLI console + tools tests.

Covers the ``pio``-equivalent console (SURVEY §2.3: ``Console.scala``
dispatch), engine registration manifests, export/import round-trips, the
dashboard server, and the full build→train→deploy→query→undeploy lifecycle
over a scaffolded bundled template — the analogue of the reference
quickstart exercised end-to-end in one process.
"""

import datetime as dt
import json
import os
import urllib.request

import pytest

from predictionio_tpu.storage import Event, StorageRegistry, get_registry
from predictionio_tpu.tools import console
from predictionio_tpu.tools import register as register_mod
from predictionio_tpu.tools import run_server, run_workflow
from predictionio_tpu.tools.export_events import export_events
from predictionio_tpu.tools.import_events import ImportError_, import_events
from predictionio_tpu.tools.templates import get_template, list_templates

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


@pytest.fixture()
def registry(tmp_path, monkeypatch):
    """Global-registry-backed fixture: templates read via get_registry()."""
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    reg = get_registry(refresh=True)
    yield reg
    get_registry(refresh=True)


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read().decode() or "{}")


def _get_raw(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def _post(url, payload):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read().decode())


# ---------------------------------------------------------------------------
# app / accesskey consoles
# ---------------------------------------------------------------------------


def test_app_lifecycle(registry):
    out = console.app_new(registry, "myapp", access_key="k1")
    assert out["accessKey"] == "k1" and out["id"] >= 1
    with pytest.raises(ValueError):
        console.app_new(registry, "myapp")

    apps = console.app_list(registry)
    assert [a["name"] for a in apps] == ["myapp"]
    assert apps[0]["accessKeys"] == ["k1"]

    show = console.app_show(registry, "myapp")
    assert show["accessKeys"][0]["key"] == "k1"

    console.accesskey_new(registry, "myapp", events=["rate"], key="k2")
    keys = console.accesskey_list(registry, "myapp")
    assert {k["key"] for k in keys} == {"k1", "k2"}
    console.accesskey_delete(registry, "k2")
    assert len(console.accesskey_list(registry)) == 1

    # data-delete wipes events but keeps the app
    store = registry.get_events()
    app_id = out["id"]
    store.insert(
        Event(event="$set", entity_type="user", entity_id="u1", event_time=T0),
        app_id,
    )
    from predictionio_tpu.storage import EventFilter

    console.app_data_delete(registry, "myapp")
    assert list(store.find(app_id, EventFilter())) == []

    console.app_delete(registry, "myapp")
    assert console.app_list(registry) == []


def test_console_main_app_commands(registry, capsys):
    assert console.main(["app", "new", "cliapp"], registry) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["name"] == "cliapp"
    assert console.main(["app", "list"], registry) == 0
    # destructive command without --force in a non-tty context is refused
    assert console.main(["app", "delete", "cliapp"], registry) == 1
    assert console.app_list(registry), "refused delete must not remove the app"
    capsys.readouterr()
    assert console.main(["app", "delete", "cliapp", "--force"], registry) == 0
    # unknown app → error path, exit 1
    assert console.main(["app", "show", "nope"], registry) == 1
    # not an engine project → JSON error, not a traceback
    capsys.readouterr()
    assert console.main(["build", "--engine-dir", "/tmp"], registry) == 1
    assert "error" in json.loads(capsys.readouterr().out)


def test_status(registry):
    result = console.status(registry)
    assert result["ok"] and set(result["storage"]) == {
        "metadata", "modeldata", "eventdata",
    }


# ---------------------------------------------------------------------------
# export / import
# ---------------------------------------------------------------------------


def _ingest_rates(registry, app_id=1, n_users=8, n_items=6):
    store = registry.get_events()
    store.init(app_id)
    events = []
    for u in range(n_users):
        for i in range(n_items):
            if (u + i) % 2 == 0:
                events.append(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties={"rating": float(1 + (u * i) % 5)},
                        event_time=T0 + dt.timedelta(minutes=u * n_items + i),
                    )
                )
    store.write(events, app_id)
    return len(events)


def test_export_import_roundtrip(registry, tmp_path):
    n = _ingest_rates(registry, app_id=1)
    out_file = tmp_path / "events.jsonl"
    with open(out_file, "w") as fh:
        assert export_events(registry, 1, fh) == n

    with open(out_file) as fh:
        assert import_events(registry, 2, fh, batch_size=7) == n

    from predictionio_tpu.storage import EventFilter

    src = list(registry.get_events().find(1, EventFilter()))
    dst = list(registry.get_events().find(2, EventFilter()))
    assert len(src) == len(dst) == n
    assert {e.entity_id for e in src} == {e.entity_id for e in dst}
    assert sorted(e.properties.get("rating", 0) for e in src) == sorted(
        e.properties.get("rating", 0) for e in dst
    )


def test_import_rejects_bad_lines(registry):
    with pytest.raises(ImportError_, match="line 2"):
        import_events(
            registry, 3,
            ['{"event":"rate","entityType":"user","entityId":"u1"}', "not-json"],
        )


# ---------------------------------------------------------------------------
# template gallery + registration
# ---------------------------------------------------------------------------


def test_template_list_and_get(tmp_path):
    names = {t["name"] for t in list_templates()}
    assert names == {"recommendation", "classification", "similarproduct",
                     "ecommerce", "sequencerec"}
    target = tmp_path / "proj"
    out = get_template("recommendation", str(target))
    assert os.path.exists(target / "engine.json")
    assert os.path.exists(target / "engine.py")
    assert out["template"] == "recommendation"
    with pytest.raises(ValueError):
        get_template("recommendation", str(target))  # non-empty dir
    with pytest.raises(KeyError):
        get_template("nope", str(tmp_path / "x"))


def test_register_engine_manifest(registry, tmp_path):
    target = tmp_path / "proj"
    get_template("classification", str(target))
    ed = register_mod.register_engine(registry, str(target))
    stored = registry.get_metadata().manifest_get(ed.manifest.id, ed.manifest.version)
    assert stored is not None and stored.engine_factory == "engine:engine_factory"
    assert os.path.exists(target / "manifest.json")

    # Editing the project bumps the version (rebuilt-jar fingerprint analogue)
    (target / "engine.py").write_text(
        (target / "engine.py").read_text() + "\n# edited\n"
    )
    ed2 = register_mod.register_engine(registry, str(target))
    assert ed2.manifest.id == ed.manifest.id
    assert ed2.manifest.version != ed.manifest.version


# ---------------------------------------------------------------------------
# end-to-end: build → train → deploy → query → reload → undeploy
# ---------------------------------------------------------------------------


def test_full_lifecycle_recommendation(registry, tmp_path, capsys):
    _ingest_rates(registry, app_id=1)
    target = tmp_path / "proj"
    get_template("recommendation", str(target))

    assert console.main(["build", "--engine-dir", str(target)], registry) == 0
    build_out = json.loads(capsys.readouterr().out)

    assert console.main(["train", "--engine-dir", str(target)], registry) == 0
    train_out = json.loads(capsys.readouterr().out)
    instance_id = train_out["engineInstanceId"]
    inst = registry.get_metadata().engine_instance_get(instance_id)
    assert inst is not None and inst.status == "COMPLETED"
    assert inst.engine_id == build_out["engineId"]

    srv_args = run_server.build_parser().parse_args(
        ["--engine-dir", str(target), "--port", "0"]
    )
    server = run_server.make_server(srv_args, registry, block=False)
    try:
        port = server.bound_port
        stat, body = _post(
            f"http://localhost:{port}/queries.json", {"user": "u1", "num": 3}
        )
        assert stat == 200
        assert len(body["itemScores"]) == 3
        scores = [s["score"] for s in body["itemScores"]]
        assert scores == sorted(scores, reverse=True)

        stat, _ = _get(f"http://localhost:{port}/reload")
        assert stat == 200
        stat2, body2 = _post(
            f"http://localhost:{port}/queries.json", {"user": "u1", "num": 3}
        )
        assert stat2 == 200 and body2["itemScores"]

        out = console.undeploy("localhost", port)
        assert out["status"] == 200
    finally:
        server.stop_async()
        server.server_close()


def test_train_via_spawned_subprocess(registry, tmp_path):
    """The process-boundary path (RunWorkflow.scala:103-169 analogue)."""
    import subprocess, sys

    _ingest_rates(registry, app_id=1)
    target = tmp_path / "proj"
    get_template("recommendation", str(target))

    env = dict(os.environ)
    env["PIO_FS_BASEDIR"] = os.environ["PIO_FS_BASEDIR"]
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "predictionio_tpu.tools.run_workflow",
            "--engine-dir", str(target),
        ],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    inst = registry.get_metadata().engine_instance_get(out["engineInstanceId"])
    assert inst is not None and inst.status == "COMPLETED"
    # the line names the device the child trained on, the levers as they
    # resolved there, and the compile cache it used
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert out["levers"]["als"] == {
        "solve_mode": "chunked", "gather_dtype": "f32",
        "fused_gather": False, "shards": 1,
    }
    assert set(out["compileCache"]) == {"dir", "hits", "misses"}


def test_result_line_resolves_levers_per_shard_count(registry, tmp_path):
    """``run_workflow.result_line`` reads the instance's stored params:
    one shard resolves like ``ALSConfig.resolve_levers()``, more than one
    like the sharded trainer; an unknown instance has no levers."""
    from predictionio_tpu.tools import run_workflow

    _ingest_rates(registry, app_id=1)
    target = tmp_path / "proj"
    get_template("recommendation", str(target))
    args = run_workflow.build_parser().parse_args(
        ["--engine-dir", str(target)])
    instance_id = run_workflow.run(args, registry)
    line = run_workflow.result_line(instance_id, registry)
    assert line["engineInstanceId"] == instance_id
    assert line["device"]["platform"] == "cpu"
    assert line["levers"]["als"]["shards"] == 1
    assert line["levers"]["als"]["solve_mode"] == "chunked"
    sharded = run_workflow.resolved_levers(registry, instance_id, shards=4)
    assert sharded["als"] == {
        "solve_mode": "chunked", "gather_dtype": "f32",
        "fused_gather": False, "shards": 4,
    }
    assert run_workflow.resolved_levers(registry, "no-such-instance") == {}


def test_custom_engine_model_pickles_across_train_and_deploy(registry, tmp_path):
    """A model class defined inside the project-local engine.py must survive
    the pickle → model store → unpickle roundtrip (the 'customize the
    scaffold in place' workflow; regression for the synthetic-module-name
    pickling failure)."""
    target = tmp_path / "custom"
    target.mkdir()
    (target / "engine.json").write_text(json.dumps({
        "engineFactory": "engine:engine_factory",
        "algorithms": [{"name": "", "params": {}}],
    }))
    (target / "engine.py").write_text(
        "import dataclasses\n"
        "from predictionio_tpu.controller import (\n"
        "    Algorithm, DataSource, Engine, FirstServing, IdentityPreparator)\n"
        "\n"
        "@dataclasses.dataclass\n"
        "class MyModel:\n"
        "    weight: float\n"
        "\n"
        "class DS(DataSource):\n"
        "    def read_training(self, ctx):\n"
        "        return [1.0, 2.0, 3.0]\n"
        "\n"
        "class Algo(Algorithm):\n"
        "    def train(self, ctx, pd):\n"
        "        return MyModel(weight=sum(pd))\n"
        "    def predict(self, model, query):\n"
        "        return {'w': model.weight * query.get('x', 1)}\n"
        "\n"
        "def engine_factory():\n"
        "    return Engine({'': DS}, {'': IdentityPreparator}, {'': Algo},\n"
        "                  {'': FirstServing})\n"
    )
    assert console.main(["train", "--engine-dir", str(target)], registry) == 0

    srv_args = run_server.build_parser().parse_args(
        ["--engine-dir", str(target), "--port", "0"]
    )
    server = run_server.make_server(srv_args, registry, block=False)
    try:
        stat, body = _post(
            f"http://localhost:{server.bound_port}/queries.json", {"x": 2.0}
        )
        assert stat == 200 and body["w"] == 12.0
    finally:
        server.stop_async()
        server.server_close()


# ---------------------------------------------------------------------------
# dashboard
# ---------------------------------------------------------------------------


def test_dashboard_lists_evaluations(registry):
    from predictionio_tpu.storage import STATUS_EVALCOMPLETED
    from predictionio_tpu.storage.metadata import EvaluationInstance
    from predictionio_tpu.tools.dashboard import (
        DashboardConfig,
        create_dashboard,
    )

    md = registry.get_metadata()
    inst_id = md.evaluation_instance_insert(
        EvaluationInstance(
            id="",
            status=STATUS_EVALCOMPLETED,
            start_time=T0,
            end_time=T0,
            evaluation_class="MyEval",
            engine_params_generator_class="MyGen",
            evaluator_results="metric=0.9",
            evaluator_results_html="<html><body>0.9</body></html>",
            evaluator_results_json='{"metric": 0.9}',
        )
    )
    server = create_dashboard(DashboardConfig(port=0), registry, block=False)
    try:
        port = server.bound_port
        stat, html_body = _get_raw(f"http://localhost:{port}/")
        assert stat == 200 and "MyEval" in html_body and inst_id in html_body
        stat, js = _get(
            f"http://localhost:{port}/engine_instances/{inst_id}/evaluator_results.json"
        )
        assert stat == 200 and js["metric"] == 0.9
        stat, html2 = _get_raw(
            f"http://localhost:{port}/engine_instances/{inst_id}/evaluator_results.html"
        )
        assert stat == 200 and "0.9" in html2
        with pytest.raises(urllib.error.HTTPError):
            _get(f"http://localhost:{port}/engine_instances/zzz/evaluator_results.json")
    finally:
        server.stop_async()
        server.server_close()


def test_upgrade_migrates_between_backends(tmp_path, monkeypatch):
    """pio upgrade: sqlite → native migration preserves every event."""
    import datetime as dt

    from predictionio_tpu.storage.data_map import DataMap
    from predictionio_tpu.storage.event import Event
    from predictionio_tpu.storage.sqlite_events import SqliteEventStore
    from predictionio_tpu.storage.native_events import NativeEventStore
    from predictionio_tpu.tools.upgrade import migrate_events

    src = SqliteEventStore(str(tmp_path / "src" / "events.db"))
    src.init(1)
    src.init(2)
    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    for i in range(25):
        src.insert(
            Event(event="rate", entity_type="user", entity_id=f"u{i % 3}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap({"rating": float(i % 5)}),
                  event_time=t0 + dt.timedelta(minutes=i)),
            1 if i % 2 else 2,
        )
    dst = NativeEventStore(str(tmp_path / "dst"))
    counts = migrate_events(src, dst, [1, 2])
    assert counts == {1: 13, 2: 12} or counts == {1: 12, 2: 13}
    for app in (1, 2):
        src_events = {e.event_id: e for e in src.find(app)}
        dst_events = {e.event_id: e for e in dst.find(app)}
        assert set(src_events) == set(dst_events)
        for eid, e in src_events.items():
            got = dst_events[eid]
            assert got.properties.to_dict() == e.properties.to_dict()
            assert got.event_time == e.event_time
    # idempotent: rerunning does not duplicate (upsert by event id)
    counts2 = migrate_events(src, dst, [1])
    assert sum(1 for _ in dst.find(1)) == counts2[1] == counts[1]
    src.close(); dst.close()


def test_upgrade_cli(tmp_path, monkeypatch):
    import json as _json

    from predictionio_tpu.storage.event import Event
    from predictionio_tpu.storage.sqlite_events import SqliteEventStore
    from predictionio_tpu.tools.console import main

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
    from predictionio_tpu.storage import get_registry

    get_registry(refresh=True)
    src = SqliteEventStore(str(tmp_path / "a" / "events.db"))
    src.init(5)
    src.insert(Event(event="x", entity_type="t", entity_id="1"), 5)
    src.close()
    rc = main([
        "upgrade", "--from-type", "sqlite", "--from-path", str(tmp_path / "a"),
        "--to-type", "native", "--to-path", str(tmp_path / "b"),
        "--appid", "5",
    ])
    assert rc == 0
    from predictionio_tpu.storage.native_events import NativeEventStore

    dst = NativeEventStore(str(tmp_path / "b" / "events_native"))
    assert sum(1 for _ in dst.find(5)) == 1
    dst.close()
    get_registry(refresh=True)


class TestParquetExportImport:
    """Parquet archive roundtrip (the reference EventsToFile's default
    format) — exact event fidelity including $unset null properties."""

    def test_roundtrip(self, registry, tmp_path):
        import datetime as dt

        from predictionio_tpu.storage import DataMap, Event
        from predictionio_tpu.tools.export_events import export_events_parquet
        from predictionio_tpu.tools.import_events import import_events_parquet

        ev = registry.get_events()
        ev.init(1)
        t = dt.datetime(2026, 7, 3, 12, 0, tzinfo=dt.timezone.utc)
        events = [
            Event(event="rate", entity_type="user", entity_id="u1",
                  target_entity_type="item", target_entity_id="i1",
                  properties=DataMap({"rating": 4.5, "note": "héllo"}),
                  event_time=t, pr_id="PR123"),
            Event(event="$set", entity_type="user", entity_id="u2",
                  properties=DataMap({"plan": "gold"}), event_time=t),
            Event(event="$unset", entity_type="user", entity_id="u2",
                  properties=DataMap({"plan": None}), event_time=t,
                  tags=("a", "b")),
        ]
        ev.write(events, 1)
        path = str(tmp_path / "events.parquet")
        n = export_events_parquet(registry, 1, path)
        assert n == 3

        n2 = import_events_parquet(registry, 2, path)
        assert n2 == 3
        from predictionio_tpu.storage.events import EventFilter

        back = list(ev.find(2, EventFilter()))
        assert len(back) == 3
        rate = [e for e in back if e.event == "rate"][0]
        assert rate.properties["rating"] == 4.5
        assert rate.properties["note"] == "héllo"
        assert rate.pr_id == "PR123"
        unset = [e for e in back if e.event == "$unset"][0]
        assert unset.properties.to_dict() == {"plan": None}  # keys survive
        assert unset.tags == ("a", "b")

    def test_empty_export_imports_cleanly(self, registry, tmp_path):
        from predictionio_tpu.tools.export_events import export_events_parquet
        from predictionio_tpu.tools.import_events import import_events_parquet

        registry.get_events().init(5)
        path = str(tmp_path / "empty.parquet")
        assert export_events_parquet(registry, 5, path) == 0
        assert import_events_parquet(registry, 6, path) == 0

    def test_cli_flags(self, registry, tmp_path, monkeypatch):
        import predictionio_tpu.storage.registry as regmod
        from predictionio_tpu.storage import DataMap, Event
        from predictionio_tpu.tools.console import main

        monkeypatch.setattr(regmod, "_default_registry", registry)
        ev = registry.get_events()
        ev.init(3)
        ev.write([Event(event="view", entity_type="user", entity_id="u9",
                        target_entity_type="item", target_entity_id="i9")], 3)
        out = str(tmp_path / "a.parquet")
        assert main(["export", "--appid", "3", "--output", out,
                     "--format", "parquet"], registry) == 0
        assert main(["import", "--appid", "4", "--input", out,
                     "--format", "parquet"], registry) == 0
        from predictionio_tpu.storage.events import EventFilter

        assert len(list(ev.find(4, EventFilter()))) == 1
