"""The ``pio`` console: operator CLI for the whole framework.

Rebuild of ``tools/src/main/scala/io/prediction/tools/console/Console.scala``
(scopt grammar ``:122-558``, dispatch ``:582-644``) plus the app/accesskey
consoles (``console/{App,AccessKey}.scala``).  Subcommands:

    app new|list|show|delete|data-delete
    accesskey new|list|delete
    build                      — verify + register the engine project
    train | eval               — run the training / evaluation workflow
    deploy | undeploy          — query server lifecycle (undeploy = GET /stop)
    eventserver | dashboard    — REST servers
    status                     — storage verification (Storage.scala:230-250)
    export | import            — events ↔ JSON-lines files
    template list|get          — bundled + remote engine templates

Process model: the reference launches train/deploy as separate JVMs via
spark-submit (``RunWorkflow.scala:103-169``); here ``--spawn`` runs them as
``python -m predictionio_tpu.tools.run_workflow`` / ``run_server`` child
processes with the same metadata-store handshake, and the default is
in-process (the simplification called out in SURVEY §7).
"""

from __future__ import annotations

import argparse
import json
import os
import secrets
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import List, Optional, Sequence

from ..storage import StorageRegistry, get_registry
from ..storage.metadata import AccessKey, App
from . import register as register_mod
from . import run_server, run_workflow

EXIT_OK = 0
EXIT_FAIL = 1


# ---------------------------------------------------------------------------
# app / accesskey consoles (console/App.scala, console/AccessKey.scala)
# ---------------------------------------------------------------------------


def app_new(
    registry: StorageRegistry,
    name: str,
    app_id: Optional[int] = None,
    access_key: Optional[str] = None,
    description: Optional[str] = None,
) -> dict:
    """``pio app new`` (``App.scala:33-77``): create app, init its event
    store, mint a default access key valid for all events."""
    md = registry.get_metadata()
    if md.app_get_by_name(name) is not None:
        raise ValueError(f"App {name!r} already exists")
    new_id = md.app_insert(
        App(id=app_id or 0, name=name, description=description)
    )
    if new_id is None:
        raise ValueError(f"Could not create app {name!r} (id conflict?)")
    registry.get_events().init(new_id)
    key = access_key or secrets.token_urlsafe(32)
    md.access_key_insert(AccessKey(key=key, appid=new_id, events=()))
    return {"name": name, "id": new_id, "accessKey": key}


def app_list(registry: StorageRegistry) -> List[dict]:
    md = registry.get_metadata()
    out = []
    for app in sorted(md.app_get_all(), key=lambda a: a.name):
        keys = [ak.key for ak in md.access_key_get_by_app(app.id)]
        out.append({"name": app.name, "id": app.id, "accessKeys": keys})
    return out


def app_show(registry: StorageRegistry, name: str) -> dict:
    md = registry.get_metadata()
    app = md.app_get_by_name(name)
    if app is None:
        raise KeyError(f"App {name!r} not found")
    keys = [
        {"key": ak.key, "events": list(ak.events)}
        for ak in md.access_key_get_by_app(app.id)
    ]
    return {
        "name": app.name,
        "id": app.id,
        "description": app.description,
        "accessKeys": keys,
    }


def app_delete(registry: StorageRegistry, name: str) -> dict:
    """``pio app delete``: remove app + keys + event data (``App.scala:79-120``)."""
    md = registry.get_metadata()
    app = md.app_get_by_name(name)
    if app is None:
        raise KeyError(f"App {name!r} not found")
    registry.get_events().remove(app.id)
    for ak in md.access_key_get_by_app(app.id):
        md.access_key_delete(ak.key)
    md.app_delete(app.id)
    return {"name": name, "id": app.id, "deleted": True}


def app_data_delete(registry: StorageRegistry, name: str) -> dict:
    """``pio app data-delete``: wipe + re-init the app's event store
    (``App.scala:122-141``)."""
    md = registry.get_metadata()
    app = md.app_get_by_name(name)
    if app is None:
        raise KeyError(f"App {name!r} not found")
    ev = registry.get_events()
    ev.remove(app.id)
    ev.init(app.id)
    return {"name": name, "id": app.id, "dataDeleted": True}


def accesskey_new(
    registry: StorageRegistry,
    app_name: str,
    events: Sequence[str] = (),
    key: Optional[str] = None,
) -> dict:
    md = registry.get_metadata()
    app = md.app_get_by_name(app_name)
    if app is None:
        raise KeyError(f"App {app_name!r} not found")
    new_key = key or secrets.token_urlsafe(32)
    md.access_key_insert(AccessKey(key=new_key, appid=app.id, events=tuple(events)))
    return {"app": app_name, "accessKey": new_key, "events": list(events)}


def accesskey_list(
    registry: StorageRegistry, app_name: Optional[str] = None
) -> List[dict]:
    md = registry.get_metadata()
    apps = (
        [a for a in [md.app_get_by_name(app_name)] if a is not None]
        if app_name
        else md.app_get_all()
    )
    out = []
    for app in apps:
        for ak in md.access_key_get_by_app(app.id):
            out.append(
                {"key": ak.key, "app": app.name, "events": list(ak.events)}
            )
    return out


def accesskey_delete(registry: StorageRegistry, key: str) -> dict:
    if not registry.get_metadata().access_key_delete(key):
        raise KeyError(f"Access key {key!r} not found")
    return {"accessKey": key, "deleted": True}


# ---------------------------------------------------------------------------
# undeploy / status (Console.scala:798-824, :930-986)
# ---------------------------------------------------------------------------


def undeploy(ip: str = "localhost", port: int = 8000) -> dict:
    """HTTP GET /stop against a running query server."""
    url = f"http://{ip}:{port}/stop"
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return {"url": url, "status": resp.status}
    except (urllib.error.URLError, OSError) as exc:
        raise RuntimeError(f"Nothing to undeploy at {url}: {exc}") from exc


def status(registry: StorageRegistry) -> dict:
    """``pio status``: verify every storage repository with live operations."""
    results = registry.verify_all_data_objects()
    return {"storage": results, "ok": all(results.values())}


# ---------------------------------------------------------------------------
# rollout console (docs/rollouts.md) — thin HTTP client over the query
# server's /rollout routes, like undeploy over /stop
# ---------------------------------------------------------------------------


def _rollout_request(
    ip: str, port: int, method: str, path: str, body: Optional[dict] = None
) -> dict:
    url = f"http://{ip}:{port}{path}"
    data = json.dumps(body).encode("utf-8") if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        raw = exc.read().decode("utf-8", "replace")
        try:
            message = json.loads(raw).get("message", raw)
        except ValueError:
            message = raw
        raise RuntimeError(
            f"query server answered {exc.code}: {message}"
        ) from exc
    except (urllib.error.URLError, OSError) as exc:
        raise RuntimeError(f"no query server at {url}: {exc}") from exc


def continuous_command(args: argparse.Namespace) -> dict:
    """``pio continuous start|status|pause|trigger`` — thin HTTP client
    over the query server's /continuous routes (docs/continuous.md)."""
    sub = args.continuous_command
    if sub == "status":
        return _rollout_request(args.ip, args.port, "GET", "/continuous.json")
    body: dict = {}
    if sub == "trigger" and args.full:
        body["full"] = True
    return _rollout_request(
        args.ip, args.port, "POST", f"/continuous/{sub}", body
    )


def rollout_command(args: argparse.Namespace) -> dict:
    """``pio rollout start|status|promote|abort``."""
    sub = args.rollout_command
    if sub == "start":
        gates = {}
        for item in args.gate:
            key, sep, value = item.partition("=")
            if not sep:
                raise ValueError(f"bad --gate {item!r}: expected KEY=VALUE")
            gates[key.strip()] = float(value)
        body: dict = {}
        if args.instance_id:
            body["instanceId"] = args.instance_id
        if args.percent is not None:
            body["percent"] = args.percent
        if gates:
            body["gates"] = gates
        return _rollout_request(args.ip, args.port, "POST", "/rollout/start", body)
    if sub == "status":
        return _rollout_request(args.ip, args.port, "GET", "/rollout.json")
    if sub == "promote":
        return _rollout_request(
            args.ip, args.port, "POST", "/rollout/promote",
            {"reason": args.reason},
        )
    return _rollout_request(
        args.ip, args.port, "POST", "/rollout/abort", {"reason": args.reason}
    )


def migrate_command(args: argparse.Namespace) -> int:
    """``pio migrate start|pump|status|cutover|abort`` — drives one
    :class:`~predictionio_tpu.storage.migration.PartitionMigration`
    over its durable state dir (docs/storage.md#live-migration). Every
    invocation is a fresh coordinator instance resuming from the files;
    ``pump`` is the bounded tick an operator (or cron) repeats until
    ``status`` reports the watermark ok, then ``cutover`` flips."""
    from ..storage.migration import open_migration

    sub = args.migrate_command
    mig = open_migration(
        args.state,
        old_url=getattr(args, "old", "") or "",
        new_url=getattr(args, "new", "") or "",
    )
    try:
        if sub == "start":
            _emit(mig.start())
        elif sub == "pump":
            rounds = [
                mig.pump(max_ops=args.max_ops)
                for _ in range(max(1, args.rounds))
            ]
            _emit({"rounds": rounds, "status": mig.status()})
        elif sub == "status":
            out = mig.status()
            if mig.mirroring():
                out["watermark"] = mig.watermark()
            _emit(out)
        elif sub == "cutover":
            _emit(mig.cutover(timeout_s=args.timeout))
        elif sub == "abort":
            _emit(mig.abort(args.reason))
        return EXIT_OK
    finally:
        mig.close()


def autoscale_command(args: argparse.Namespace) -> int:
    """``pio autoscale --signals FILE [--ticks N] [--execute]`` — run
    the :class:`~predictionio_tpu.fleet.autoscale.FleetAutoscaler`
    control loop over a signals snapshot and print every decision
    (docs/robustness.md#autoscaler). Dry-run unless ``--execute``; the
    CLI wires no actuator, so even executed runs emit recommendations —
    the posture still flips the ``dry_run`` label on the counter and
    the ledger, which is what the drill pins."""
    from ..fleet.autoscale import (
        AutoscaleConfig,
        FleetAutoscaler,
        signals_from_dict,
    )

    with open(args.signals, encoding="utf-8") as fh:
        signals = signals_from_dict(json.load(fh))
    config = AutoscaleConfig.from_env(
        **({"dry_run": False} if args.execute else {})
    )
    scaler = FleetAutoscaler(config)
    actions = []
    for _ in range(max(1, args.ticks)):
        for action in scaler.observe(signals):
            actions.append(action.to_json())
    _emit({
        "dryRun": config.dry_run,
        "ticks": scaler.tick_count,
        "actions": actions,
        "decisions": scaler.decisions(),
    })
    return EXIT_OK


# ---------------------------------------------------------------------------
# CLI grammar + dispatch
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="PredictionIO-TPU operator console"
    )
    sub = p.add_subparsers(dest="command", required=True)

    app = sub.add_parser("app", help="manage apps")
    app_sub = app.add_subparsers(dest="app_command", required=True)
    ap_new = app_sub.add_parser("new")
    ap_new.add_argument("name")
    ap_new.add_argument("--id", type=int, default=None)
    ap_new.add_argument("--access-key", default=None)
    ap_new.add_argument("--description", default=None)
    app_sub.add_parser("list")
    for nm in ("show", "delete", "data-delete"):
        sp = app_sub.add_parser(nm)
        sp.add_argument("name")
        if nm != "show":
            sp.add_argument("--force", "-f", action="store_true")

    ak = sub.add_parser("accesskey", help="manage access keys")
    ak_sub = ak.add_subparsers(dest="accesskey_command", required=True)
    ak_new = ak_sub.add_parser("new")
    ak_new.add_argument("app_name")
    ak_new.add_argument("events", nargs="*")
    ak_list = ak_sub.add_parser("list")
    ak_list.add_argument("app_name", nargs="?", default=None)
    ak_del = ak_sub.add_parser("delete")
    ak_del.add_argument("key")

    build = sub.add_parser("build", help="verify + register engine project")
    build.add_argument("--engine-dir", default=".")

    train = sub.add_parser("train", help="run the training workflow")
    for flag, kw in _WORKFLOW_FLAGS:
        train.add_argument(flag, **kw)
    train.add_argument("--spawn", action="store_true")

    ev = sub.add_parser("eval", help="run an evaluation")
    ev.add_argument("evaluation_class")
    ev.add_argument("engine_params_generator_class", nargs="?", default=None)
    for flag, kw in _WORKFLOW_FLAGS:
        ev.add_argument(flag, **kw)
    ev.add_argument("--spawn", action="store_true")

    dp = sub.add_parser("deploy", help="serve the latest trained instance")
    dp.add_argument("--engine-dir", default=".")
    dp.add_argument("--engine-instance-id", default=None)
    dp.add_argument("--ip", default="localhost")
    dp.add_argument("--port", type=int, default=8000)
    dp.add_argument("--feedback", action="store_true")
    dp.add_argument("--event-server-ip", default="localhost")
    dp.add_argument("--event-server-port", type=int, default=7070)
    dp.add_argument("--accesskey", default=None)
    dp.add_argument("--batch", default="")
    dp.add_argument("--log-url", default=None,
                    help="POST serving errors here (CreateServer --log-url)")
    dp.add_argument("--batch-max", type=int, default=None,
                    help="micro-batch size cap (size to catalog and depth)")
    dp.add_argument("--batch-pipeline-depth", type=int, default=None,
                    help="batches in flight at once (default 2)")
    dp.add_argument("--shard-index", type=int, default=None, metavar="I",
                    help="serve item-factor shard I of --shard-count "
                    "behind a `pio router --sharded` tier (docs/fleet.md)")
    dp.add_argument("--shard-count", type=int, default=None, metavar="N",
                    help="total item-factor shards (1 = unsharded)")
    dp.add_argument("--continuous-app", type=int, default=None,
                    metavar="APP_ID",
                    help="attach the continuous-learning loop for this app "
                    "(docs/continuous.md)")
    dp.add_argument("--continuous-feed", default=None, metavar="URL",
                    help="storage primary to tail for the continuous loop")
    dp.add_argument("--spawn", action="store_true")

    ud = sub.add_parser("undeploy", help="stop a running query server")
    ud.add_argument("--ip", default="localhost")
    ud.add_argument("--port", type=int, default=8000)

    ro = sub.add_parser(
        "rollout",
        help="staged deploys against a running query server: shadow -> "
        "canary -> live with metric gates (docs/rollouts.md)",
    )
    ro_sub = ro.add_subparsers(dest="rollout_command", required=True)
    ro_start = ro_sub.add_parser(
        "start", help="load a candidate instance and enter SHADOW"
    )
    ro_start.add_argument(
        "--instance-id", default=None,
        help="candidate engine instance (default: latest COMPLETED newer "
        "than the deployed baseline)",
    )
    ro_start.add_argument(
        "--percent", type=float, default=None,
        help="canary traffic share (default 10)",
    )
    ro_start.add_argument(
        "--gate", action="append", default=[], metavar="KEY=VALUE",
        help="gate override, repeatable (window_s, min_samples, "
        "max_error_rate_delta, max_p99_latency_ratio, max_divergence, "
        "shadow_hold_s, canary_hold_s, canary_percent)",
    )
    ro_sub.add_parser("status", help="active plan, windows, gate verdict")
    ro_prom = ro_sub.add_parser(
        "promote", help="advance one stage regardless of gates"
    )
    ro_prom.add_argument("--reason", default="manual promote")
    ro_abort = ro_sub.add_parser(
        "abort", help="retire the candidate; baseline takes 100%%"
    )
    ro_abort.add_argument("--reason", default="manual abort")
    for sp in (ro_start, ro_prom, ro_abort) + tuple(
        [ro_sub.choices["status"]]
    ):
        sp.add_argument("--ip", default="localhost")
        sp.add_argument("--port", type=int, default=8000)

    co = sub.add_parser(
        "continuous",
        help="continuous-learning loop on a running query server: "
        "changefeed-driven fold-in training with automatic rollout "
        "submission (docs/continuous.md)",
    )
    co_sub = co.add_subparsers(dest="continuous_command", required=True)
    co_start = co_sub.add_parser(
        "start", help="(re)start the background watch/train loop"
    )
    co_sub.add_parser(
        "status", help="cursor, feed lag, pending delta, last cycle"
    )
    co_pause = co_sub.add_parser(
        "pause", help="stop triggering cycles (the cursor keeps its place)"
    )
    co_trig = co_sub.add_parser(
        "trigger", help="force a training cycle on the next tick"
    )
    co_trig.add_argument(
        "--full", action="store_true",
        help="force a full retrain instead of fold-in",
    )
    for sp in (co_start, co_pause, co_trig, co_sub.choices["status"]):
        sp.add_argument("--ip", default="localhost")
        sp.add_argument("--port", type=int, default=8000)

    rt = sub.add_parser(
        "router",
        help="serving-fleet router tier: fronts N query servers with "
        "consistent routing, per-app quotas, replica failover and "
        "sharded-model top-k merge (docs/fleet.md)",
    )
    rt.add_argument("--ip", default="localhost")
    rt.add_argument("--port", type=int, default=8700)
    rt.add_argument(
        "--backends", required=True, metavar="HOST:PORT,...",
        help="query servers to front; in --sharded mode position i must "
        "serve shard i of N",
    )
    rt.add_argument(
        "--sharded", action="store_true",
        help="scatter/gather mode: each backend holds one item-factor "
        "partition, answers merge into the exact global top-k",
    )
    rt.add_argument(
        "--replicas-per-shard", type=int, default=1, metavar="R",
        help="with --sharded: every R consecutive backends serve one "
        "shard (backend i serves shard i//R) and a shard leg fails "
        "over inside its replica group — a sharded fleet survives a "
        "backend kill (docs/fleet.md#replicas-per-shard)",
    )
    rt.add_argument(
        "--no-cache", action="store_true",
        help="disable the router response cache (docs/fleet.md#cache; "
        "default on, PIO_ROUTER_CACHE=0 also disables)",
    )
    rt.add_argument(
        "--cache-ttl", type=float, default=None, metavar="S",
        help="response-cache TTL backstop in seconds (default "
        "PIO_ROUTER_CACHE_TTL_S or 30; correctness comes from "
        "rollout/model epoch invalidation, not the TTL)",
    )
    rt.add_argument(
        "--cache-max-entries", type=int, default=None, metavar="N",
        help="response-cache LRU bound (default PIO_ROUTER_CACHE_MAX "
        "or 2048)",
    )
    rt.add_argument(
        "--quota", action="append", default=[], metavar="APP=N",
        help="per-app in-flight cap (X-PIO-App header), repeatable",
    )
    rt.add_argument(
        "--default-quota", type=int, default=0,
        help="in-flight cap for apps without an explicit --quota "
        "(0 = unbounded)",
    )
    rt.add_argument("--timeout", type=float, default=10.0,
                    help="per-backend-leg socket timeout (seconds)")
    rt.add_argument(
        "--engine-id", default=None,
        help="engine whose active rollout plan the variant-consistency "
        "check mirrors (default: discovered from the latest completed "
        "instance)",
    )
    rt.add_argument("--engine-version", default=None)
    rt.add_argument("--engine-variant", default="engine.json")
    rt.add_argument(
        "--shared-cache", default=None, metavar="HOST:PORT",
        help="consult a `pio sharedcache` sidecar between the local LRU "
        "and the backend fan-out (docs/fleet.md#shared-cache-tier; "
        "advisory by construction — any doubt is a miss, killing the "
        "sidecar degrades to per-router caching; also "
        "PIO_ROUTER_SHARED_CACHE)",
    )
    rt.add_argument(
        "--meta-feed", default=None, metavar="URL",
        help="storage-server base URL whose metadata changefeed pushes "
        "epoch invalidations (docs/fleet.md#shared-cache-tier; the "
        "plan poll stretches to a watchdog while the subscription is "
        "live; also PIO_ROUTER_META_FEED)",
    )
    rt.add_argument(
        "--no-hedge", action="store_true",
        help="disable tail-latency request hedging (docs/fleet.md"
        "#hedging; default on, PIO_ROUTER_HEDGE=0 also disables)",
    )

    sc = sub.add_parser(
        "sharedcache",
        help="shared response-cache sidecar for a router fleet: one "
        "epoch-checked LRU every `pio router --shared-cache` replica "
        "consults before fanning out (docs/fleet.md#shared-cache-tier)",
    )
    sc.add_argument("--ip", default="localhost")
    sc.add_argument("--port", type=int, default=8800)
    sc.add_argument(
        "--max-entries", type=int, default=8192, metavar="N",
        help="LRU bound (default 8192)",
    )
    sc.add_argument(
        "--ttl", type=float, default=30.0, metavar="S",
        help="entry TTL backstop in seconds (default 30; correctness "
        "comes from epoch checks, not the TTL)",
    )

    es = sub.add_parser("eventserver", help="run the event REST server")
    es.add_argument("--ip", default="localhost")
    es.add_argument("--port", type=int, default=7070)
    es.add_argument("--stats", action="store_true")

    db = sub.add_parser("dashboard", help="run the evaluation dashboard")
    db.add_argument("--ip", default="localhost")
    db.add_argument("--port", type=int, default=9000)
    db.add_argument(
        "--nodes", default="", metavar="HOST:PORT,...",
        help="fleet nodes the /fleet panel scrapes",
    )

    ss = sub.add_parser(
        "storageserver",
        help="serve this host's storage backends over HTTP (type=remote peer)",
    )
    ss.add_argument("--ip", default="localhost")
    ss.add_argument("--port", type=int, default=7079)
    ss.add_argument(
        "--replica-of", default=None, metavar="URL",
        help="run as a warm-standby replica tailing URL's changefeed: "
             "serves reads, rejects writes with 409 + primary hint, "
             "reports lag on /status.json (docs/storage.md#replication)",
    )
    ss.add_argument(
        "--oplog-dir", default=None,
        help="changefeed op-log directory (primary mode; default "
             "$PIO_FS_BASEDIR/oplog)",
    )
    ss.add_argument(
        "--no-changefeed", action="store_true",
        help="primary mode without a changefeed (no replication, no "
             "X-PIO-Seq tokens) — the pre-ISSUE-3 behavior",
    )
    ss.add_argument(
        "--poll-interval", type=float, default=0.5,
        help="replica changefeed poll interval in seconds",
    )
    ss.add_argument(
        "--partition-index", type=int, default=0, metavar="I",
        help="this node's keyspace slot in a partitioned event store "
             "(docs/storage.md#partitioning): stamped into the oplog "
             "meta and enforced on every event write; replicas refuse "
             "to tail a primary declaring a different slot",
    )
    ss.add_argument(
        "--partition-count", type=int, default=1, metavar="N",
        help="total partitions of the event store (1 = unpartitioned)",
    )
    ss.add_argument(
        "--sync-every", type=int, default=None, metavar="N",
        help="oplog fsync cadence (primary mode; default 256): 1 = "
             "fsync before every ack, the strict power-loss-safe ack "
             "discipline",
    )

    sub.add_parser("status", help="verify storage backends")

    ex = sub.add_parser("export", help="export app events (json/parquet)")
    ex.add_argument("--appid", type=int, required=True)
    ex.add_argument("--output", required=True)
    ex.add_argument("--format", choices=("json", "parquet"), default="json")

    im = sub.add_parser("import", help="import events into an app (json/parquet)")
    im.add_argument("--appid", type=int, required=True)
    im.add_argument("--input", required=True)
    im.add_argument("--format", choices=("json", "parquet"), default="json")

    tp = sub.add_parser(
        "template",
        help="engine templates: bundled scaffolds + remote gallery "
        "(PIO_TEMPLATE_GALLERY_URL)",
    )
    tp_sub = tp.add_subparsers(dest="template_command", required=True)
    tp_sub.add_parser("list")
    tp_get = tp_sub.add_parser("get")
    tp_get.add_argument("template_name")
    tp_get.add_argument("directory")

    ln = sub.add_parser(
        "lint",
        help="TPU-hygiene static analysis (Mosaic + jit-boundary rules)",
        # the lint CLI owns its option surface (tools/lint.py) — forward
        # everything, -h included, so flags are defined exactly once
        add_help=False,
    )
    ln.add_argument("lint_args", nargs=argparse.REMAINDER)

    ck = sub.add_parser(
        "ckpt",
        help="checkpoint store: ls | verify | gc "
        "(docs/checkpoint.md#operator-surface)",
        # the ckpt CLI owns its option surface (ckpt/cli.py) — forwarded
        # verbatim like lint/perf
        add_help=False,
    )
    ck.add_argument("ckpt_args", nargs=argparse.REMAINDER)

    top = sub.add_parser(
        "top",
        help="fleet table: scrape GET /metrics from a node list "
        "(docs/observability.md)",
    )
    top.add_argument(
        "--nodes", default=None, metavar="HOST:PORT,...",
        help="nodes to scrape (default: localhost query/event/storage "
        "ports)",
    )
    top.add_argument("--json", action="store_true",
                     help="emit rows as JSON instead of the table")
    top.add_argument("--timeout", type=float, default=5.0)

    pf = sub.add_parser(
        "profile",
        help="compile/retrace + phase/roofline report: smoke train, "
        "live node, or completed instance "
        "(docs/observability.md#profiling)",
        # the profile CLI owns its option surface (tools/perf.py)
        add_help=False,
    )
    pf.add_argument("profile_args", nargs=argparse.REMAINDER)

    pp = sub.add_parser(
        "perf",
        help="durable perf ledger: `perf diff` regression gate, "
        "`perf trend` trajectory (docs/performance.md#perf-ledger)",
        add_help=False,
    )
    pp.add_argument("perf_args", nargs=argparse.REMAINDER)

    qa = sub.add_parser(
        "quality",
        help="model & data quality report: score drift (PSI), feedback "
        "hit-rate, ingest mix — from a live /metrics scrape or the "
        "quality-snapshot ledger; `--diff` is the CI drift gate "
        "(docs/observability.md#quality)",
        # the quality CLI owns its option surface (tools/quality.py)
        add_help=False,
    )
    qa.add_argument("quality_args", nargs=argparse.REMAINDER)

    tr = sub.add_parser(
        "trace",
        help="stitch one X-PIO-Trace id's spans across a node list "
        "(GET /traces.json)",
    )
    tr.add_argument("trace_id")
    tr.add_argument(
        "--nodes", default=None, metavar="HOST:PORT,...",
        help="nodes to query (default: localhost query/event/storage "
        "ports)",
    )
    tr.add_argument("--json", action="store_true",
                    help="emit raw spans as JSON")
    tr.add_argument("--timeout", type=float, default=5.0)

    mg = sub.add_parser(
        "migrate",
        help="live event-store partition migration: dual-write + "
        "backfill + watermark cutover with zero ingest downtime "
        "(docs/storage.md#live-migration)",
    )
    mg_sub = mg.add_subparsers(dest="migrate_command", required=True)
    mg_start = mg_sub.add_parser(
        "start", help="enter dual_write: every acked write mirrors to "
        "the new layout"
    )
    mg_start.add_argument(
        "--old", required=True, metavar="URL",
        help="current layout (pio+ha:// partition sets)",
    )
    mg_start.add_argument(
        "--new", required=True, metavar="URL",
        help="target layout (pio+ha:// partition sets, M partitions)",
    )
    mg_pump = mg_sub.add_parser(
        "pump", help="bounded coordinator ticks: drain the mirror "
        "queue, advance the backfill, promote to ready at the watermark"
    )
    mg_pump.add_argument("--rounds", type=int, default=1, metavar="N")
    mg_pump.add_argument("--max-ops", type=int, default=500, metavar="K",
                         help="queue entries / oplog ops per round")
    mg_status = mg_sub.add_parser(
        "status", help="phase, cursors, queue depth, per-keyspace "
        "watermark verdict"
    )
    mg_cut = mg_sub.add_parser(
        "cutover", help="freeze writes, final drain, verify the "
        "watermark per keyspace, flip reads+writes atomically"
    )
    mg_cut.add_argument("--timeout", type=float, default=30.0,
                        help="seconds the freeze may hold before the "
                        "cutover aborts (writes thaw, phase unchanged)")
    mg_abort = mg_sub.add_parser(
        "abort", help="abandon before the flip: mirror queue discarded, "
        "old layout stays the system of record, byte-identical"
    )
    mg_abort.add_argument("--reason", default="operator abort")
    for sp in (mg_start, mg_pump, mg_status, mg_cut, mg_abort):
        sp.add_argument(
            "--state", required=True, metavar="DIR",
            help="durable coordinator state dir (phase, queue, cursors)",
        )

    asc = sub.add_parser(
        "autoscale",
        help="SLO-driven fleet autoscaler: at most one bounded, "
        "hysteresis-damped action per tick, dry-run by default "
        "(docs/robustness.md#autoscaler)",
    )
    asc.add_argument(
        "--signals", required=True, metavar="FILE",
        help="JSON signals snapshot: replicasPerShard, partitionCount, "
        "firing, burn, breakerOpenBackends, shardPressure, partitionShed "
        "(docs/cli.md)",
    )
    asc.add_argument(
        "--ticks", type=int, default=1, metavar="N",
        help="control ticks over the snapshot (hysteresis needs "
        "sustained pressure: up_ticks consecutive hot ticks)",
    )
    asc.add_argument(
        "--execute", action="store_true",
        help="clear dry-run for this run (PIO_AUTOSCALE_DRY_RUN=0 "
        "equivalent); without a wired actuator actions stay "
        "recommendations",
    )

    up = sub.add_parser(
        "upgrade", help="migrate event data between storage backends"
    )
    up.add_argument("--from-type", required=True,
                    choices=("sqlite", "native"))
    up.add_argument("--from-path", required=True)
    up.add_argument("--to-type", required=True,
                    choices=("sqlite", "native"))
    up.add_argument("--to-path", required=True)
    up.add_argument("--appid", type=int, action="append", default=None,
                    help="app to migrate (repeatable; default: all apps)")
    return p


_WORKFLOW_FLAGS = [
    ("--engine-dir", {"default": "."}),
    ("--engine-variant", {"default": "engine.json"}),
    ("--engine-params-key", {"default": None}),
    ("--batch", {"default": ""}),
    ("--verbose", {"action": "store_true"}),
    ("--skip-sanity-check", {"action": "store_true"}),
    ("--stop-after-read", {"action": "store_true"}),
    ("--stop-after-prepare", {"action": "store_true"}),
    ("--eval-parallelism", {"type": int, "default": 0}),
    ("--shards", {"type": int, "default": None, "metavar": "N",
                  "help": "train with both factor tables sharded over N "
                          "devices (docs/distributed_training.md)"}),
    ("--checkpoint-every", {"type": int, "default": None, "metavar": "N",
                            "help": "checkpoint factor tables every N "
                                    "iterations (docs/checkpoint.md)"}),
    ("--resume", {"default": None,
                  "action": argparse.BooleanOptionalAction,
                  "help": "resume from the newest valid checkpoint "
                          "(default); --no-resume trains fresh "
                          "(docs/checkpoint.md)"}),
]


def _emit(obj) -> None:
    print(json.dumps(obj, indent=2, default=str))


def _spawn(module: str, argv: Sequence[str]) -> int:
    """Blocking child-process launch, the spark-submit analogue for batch
    runs — train/eval wait for completion (``RunWorkflow.scala:103-169``).

    The child gets an explicit platform environment (``jax_child_env``):
    tests pin the CPU backend, and a CPU-pinned parent produces a
    CPU-pinned child (the spark-submit ``--env`` propagation analogue,
    ``RunWorkflow.scala:37-40,169``)."""
    from ..utils.platform import jax_child_env

    return subprocess.call(
        [sys.executable, "-m", module, *argv], env=jax_child_env()
    )


def _spawn_detached(module: str, argv: Sequence[str]) -> int:
    """Detached child-process launch for long-running servers: ``deploy
    --spawn`` returns with the server pid (the reference's RunServer child,
    ``RunServer.scala:77-126`` — its CLI parent exits and the driver JVM
    keeps serving; ``undeploy`` stops it over HTTP).

    The child's output goes to a log file under ``$PIO_FS_BASEDIR/logs``
    and a short liveness poll catches immediate failures (bad port, broken
    engine dir) instead of reporting a dead pid as success."""
    from ..storage.registry import base_dir

    log_dir = os.path.join(base_dir(), "logs")
    os.makedirs(log_dir, exist_ok=True)
    stamp = time.strftime("%Y%m%d-%H%M%S")
    log_path = os.path.join(log_dir, f"{module.rsplit('.', 1)[-1]}-{stamp}.log")
    from ..utils.platform import jax_child_env

    with open(log_path, "ab") as log_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", module, *argv],
            start_new_session=True,
            stdout=log_f,
            stderr=subprocess.STDOUT,
            env=jax_child_env(),
        )
    # liveness poll: long enough to catch startup failures that surface
    # after the (slow) jax import; a healthy server costs the full window,
    # still far below the reference's spark-submit launch time.
    # PIO_SPAWN_POLL_S overrides (e.g. on heavily loaded hosts).
    deadline = time.monotonic() + float(os.environ.get("PIO_SPAWN_POLL_S", "4"))
    while time.monotonic() < deadline and proc.poll() is None:
        time.sleep(0.2)
    if proc.poll() is not None:
        with open(log_path, "rb") as f:
            tail = f.read()[-2000:].decode("utf-8", "replace")
        _emit({
            "error": f"spawned {module} exited immediately "
                     f"(code {proc.returncode})",
            "log": log_path,
            "log_tail": tail,
        })
        return EXIT_FAIL
    _emit({"spawned": module, "pid": proc.pid, "log": log_path})
    return EXIT_OK


def _workflow_argv(args: argparse.Namespace, extra: Sequence[str] = ()) -> List[str]:
    argv = [
        "--engine-dir", args.engine_dir,
        "--engine-variant", args.engine_variant,
        "--batch", args.batch,
    ]
    if args.engine_params_key:
        argv += ["--engine-params-key", args.engine_params_key]
    for flag in ("verbose", "skip_sanity_check", "stop_after_read", "stop_after_prepare"):
        if getattr(args, flag):
            argv.append("--" + flag.replace("_", "-"))
    if getattr(args, "eval_parallelism", 0):
        argv += ["--eval-parallelism", str(args.eval_parallelism)]
    if getattr(args, "shards", None) is not None:
        # forward an explicit 0 too: it must fail loudly in
        # resolve_shards, never silently train single-device
        argv += ["--shards", str(args.shards)]
    if getattr(args, "checkpoint_every", None) is not None:
        argv += ["--checkpoint-every", str(args.checkpoint_every)]
    if getattr(args, "resume", None) is not None:
        argv.append("--resume" if args.resume else "--no-resume")
    return argv + list(extra)


def main(
    argv: Optional[Sequence[str]] = None,
    registry: Optional[StorageRegistry] = None,
) -> int:
    from ..utils.platform import apply_env_platform

    import signal

    # `pio lint` forwards verbatim BEFORE argparse: the lint CLI owns its
    # whole option surface (tools/lint.py), argparse's REMAINDER cannot
    # capture leading --flags, and pure static analysis needs neither the
    # storage plane nor a jax import — it must work on an unconfigured
    # host.
    head = list(sys.argv[1:] if argv is None else argv)[:1]
    if head == ["lint"]:
        from . import lint as lint_mod

        tail = list(sys.argv[2:] if argv is None else argv[1:])
        return lint_mod.main(tail)
    if head == ["ckpt"]:
        # forwarded verbatim like lint: the ckpt CLI owns its option
        # surface (ckpt/cli.py) and is pure filesystem — it must work on
        # an unconfigured host, the box you ssh into after a preemption.
        from ..ckpt import cli as ckpt_cli

        tail = list(sys.argv[2:] if argv is None else argv[1:])
        return ckpt_cli.main(tail)
    if head == ["quality"]:
        # forwarded verbatim like lint/perf: the quality CLI owns its
        # whole option surface (tools/quality.py) and needs neither the
        # storage plane nor jax — a pure scraper/snapshot reader.
        from . import quality as quality_mod

        tail = list(sys.argv[2:] if argv is None else argv[1:])
        return quality_mod.main(tail)
    if head in (["health"], ["alerts"], ["blackbox"]):
        # the fleet-health CLIs (tools/health.py, docs/slo.md) own their
        # option surface and are pure scrapers/ledger readers — jax-free,
        # storage-free, forwarded verbatim with the subcommand included.
        from . import health as health_mod

        tail = list(sys.argv[2:] if argv is None else argv[1:])
        return health_mod.main(head + tail)
    if head in (["profile"], ["perf"]):
        # same REMAINDER limitation as lint: these CLIs own their whole
        # option surface (tools/perf.py), so forward verbatim. `perf`
        # needs neither storage nor jax; `profile --train-smoke` imports
        # jax itself, after the platform env is applied below.
        from . import perf as perf_mod

        tail = list(sys.argv[2:] if argv is None else argv[1:])
        if head == ["perf"]:
            return perf_mod.run_perf(
                perf_mod.build_perf_parser().parse_args(tail)
            )
        apply_env_platform()
        return perf_mod.run_profile(
            perf_mod.build_profile_parser().parse_args(tail),
            registry=registry,
        )

    apply_env_platform()
    args = build_parser().parse_args(argv)
    # Short-lived CLI commands die quietly on a closed pipe (`pio app new
    # | grep -q ...` closes stdout early) — default Unix behavior, not a
    # Python traceback. Server subcommands keep Python's SIGPIPE=ignored
    # so a client disconnect mid-write surfaces as the BrokenPipeError
    # their handlers treat as normal operation, instead of killing the
    # process. The old disposition is RESTORED on return (after a flush
    # that still runs under SIG_DFL, so a dead pipe kills quietly before
    # the interpreter's exit flush can raise noisily): in-process callers
    # (tests, embedding apps) must not inherit a process-killing SIGPIPE.
    prev = None
    if args.command in (
        "eventserver", "dashboard", "storageserver", "deploy", "router",
        "sharedcache",
    ):
        # long-running server commands arm the crash path (docs/slo.md):
        # with PIO_FLIGHT_DIR set, SIGTERM/exit leaves the flight-
        # recorder timeline behind; a CLI entry point may own signal
        # dispositions (run_server does the same for spawned deploys)
        from ..obs.flight import arm

        arm(signals=True)
    else:
        try:
            cur = signal.getsignal(signal.SIGPIPE)
            if cur is not None:  # None = C-installed handler: unrestorable,
                signal.signal(signal.SIGPIPE, signal.SIG_DFL)  # leave as-is
                prev = cur
        except (AttributeError, ValueError):
            pass  # non-POSIX, or a non-main thread (tests)
    try:
        registry = registry or get_registry()
        return _dispatch(args, registry)
    except KeyboardInterrupt:
        return EXIT_FAIL
    except Exception as exc:  # every operator error → JSON + exit 1
        _emit({"error": str(exc)})
        return EXIT_FAIL
    finally:
        if prev is not None:
            try:
                sys.stdout.flush()
            except (BrokenPipeError, OSError):
                pass
            try:
                signal.signal(signal.SIGPIPE, prev)
            except (AttributeError, ValueError):
                pass


def _confirm_destructive(args: argparse.Namespace, action: str) -> bool:
    """``App.scala:79-120``: destructive app commands prompt 'YES' unless
    --force; non-interactive invocations must pass --force explicitly."""
    if args.force:
        return True
    if not sys.stdin.isatty():
        _emit({"error": f"refusing to {action} without --force (non-interactive)"})
        return False
    answer = input(f"About to {action}. Enter 'YES' to proceed: ")
    if answer != "YES":
        _emit({"error": "aborted"})
        return False
    return True


def _dispatch(args: argparse.Namespace, registry: StorageRegistry) -> int:
    cmd = args.command
    if cmd == "app":
        sub = args.app_command
        if sub == "new":
            _emit(app_new(registry, args.name, args.id, args.access_key, args.description))
        elif sub == "list":
            _emit(app_list(registry))
        elif sub == "show":
            _emit(app_show(registry, args.name))
        elif sub == "delete":
            if not _confirm_destructive(args, f"delete app {args.name!r} and ALL its data"):
                return EXIT_FAIL
            _emit(app_delete(registry, args.name))
        elif sub == "data-delete":
            if not _confirm_destructive(args, f"delete ALL event data of app {args.name!r}"):
                return EXIT_FAIL
            _emit(app_data_delete(registry, args.name))
        return EXIT_OK

    if cmd == "accesskey":
        sub = args.accesskey_command
        if sub == "new":
            _emit(accesskey_new(registry, args.app_name, args.events))
        elif sub == "list":
            _emit(accesskey_list(registry, args.app_name))
        elif sub == "delete":
            _emit(accesskey_delete(registry, args.key))
        return EXIT_OK

    if cmd == "build":
        from ..workflow.version_check import check_upgrade

        check_upgrade("build")  # Console.scala:842-844
        ed = register_mod.register_engine(registry, args.engine_dir)
        # Pre-compile the native runtime components so the first train /
        # deploy doesn't pay the C++ build (the reference's `pio build`
        # runs sbt compile up front — same idea, RunWorkflow launches are
        # then pure execution). Best-effort: a toolchain-less host falls
        # back to the Python paths at runtime anyway.
        from ..native import LIBRARIES, NativeBuildError, build_library

        native_built = []
        for name in LIBRARIES:
            try:
                build_library(name)
                native_built.append(name)
            except (NativeBuildError, OSError):
                # best-effort: toolchain-less or read-only installs fall
                # back to the Python paths at runtime
                pass
        _emit({
            "engineId": ed.manifest.id,
            "engineVersion": ed.manifest.version,
            "nativeLibraries": native_built,
        })
        return EXIT_OK

    if cmd == "train":
        register_mod.register_engine(registry, args.engine_dir, verify_import=False)
        if args.spawn:
            return _spawn("predictionio_tpu.tools.run_workflow", _workflow_argv(args))
        from ..utils.jax_cache import enable_compilation_cache

        enable_compilation_cache()
        wf_args = run_workflow.build_parser().parse_args(_workflow_argv(args))
        instance_id = run_workflow.run(wf_args, registry)
        _emit(run_workflow.result_line(instance_id, registry, args.shards))
        return EXIT_OK

    if cmd == "eval":
        extra = ["--evaluation-class", args.evaluation_class]
        if args.engine_params_generator_class:
            extra += [
                "--engine-params-generator-class",
                args.engine_params_generator_class,
            ]
        if args.spawn:
            return _spawn(
                "predictionio_tpu.tools.run_workflow", _workflow_argv(args, extra)
            )
        wf_args = run_workflow.build_parser().parse_args(_workflow_argv(args, extra))
        instance_id = run_workflow.run(wf_args, registry)
        _emit({"evaluationInstanceId": instance_id})
        return EXIT_OK

    if cmd == "deploy":
        srv_argv = [
            "--engine-dir", args.engine_dir,
            "--ip", args.ip,
            "--port", str(args.port),
            "--event-server-ip", args.event_server_ip,
            "--event-server-port", str(args.event_server_port),
            "--batch", args.batch,
        ]
        if args.engine_instance_id:
            srv_argv += ["--engine-instance-id", args.engine_instance_id]
        if args.feedback:
            srv_argv.append("--feedback")
        if args.accesskey:
            srv_argv += ["--accesskey", args.accesskey]
        if args.log_url:
            srv_argv += ["--log-url", args.log_url]
        if args.batch_max is not None:
            srv_argv += ["--batch-max", str(args.batch_max)]
        if args.batch_pipeline_depth is not None:
            srv_argv += ["--batch-pipeline-depth",
                         str(args.batch_pipeline_depth)]
        if args.shard_index is not None:
            srv_argv += ["--shard-index", str(args.shard_index)]
        if args.shard_count is not None:
            srv_argv += ["--shard-count", str(args.shard_count)]
        if args.continuous_app is not None:
            srv_argv += ["--continuous-app", str(args.continuous_app)]
        if args.continuous_feed:
            srv_argv += ["--continuous-feed", args.continuous_feed]
        if args.spawn:
            return _spawn_detached("predictionio_tpu.tools.run_server", srv_argv)
        srv_args = run_server.build_parser().parse_args(srv_argv)
        run_server.make_server(srv_args, registry, block=True)
        return EXIT_OK

    if cmd == "undeploy":
        _emit(undeploy(args.ip, args.port))
        return EXIT_OK

    if cmd == "rollout":
        _emit(rollout_command(args))
        return EXIT_OK

    if cmd == "continuous":
        _emit(continuous_command(args))
        return EXIT_OK

    if cmd == "router":
        from ..fleet.router import RouterConfig, create_router

        backends = tuple(
            b.strip() for b in args.backends.split(",") if b.strip()
        )
        quotas = {}
        for item in args.quota:
            app, sep, value = item.partition("=")
            if not sep:
                raise ValueError(f"bad --quota {item!r}: expected APP=N")
            try:
                quotas[app.strip()] = int(value)
            except ValueError:
                raise ValueError(
                    f"bad --quota {item!r}: N must be an integer"
                ) from None
        config = RouterConfig(
            ip=args.ip,
            port=args.port,
            backends=backends,
            sharded=args.sharded,
            replicas_per_shard=args.replicas_per_shard,
            quotas=quotas,
            default_quota=args.default_quota,
            timeout_s=args.timeout,
            engine_id=args.engine_id,
            engine_version=args.engine_version,
            engine_variant=args.engine_variant,
            cache_enabled=False if args.no_cache else None,
            cache_ttl_s=args.cache_ttl,
            cache_max_entries=args.cache_max_entries,
            shared_cache=args.shared_cache,
            meta_feed=args.meta_feed,
            hedge_enabled=False if args.no_hedge else None,
        )
        create_router(config, registry=registry, block=True)
        return EXIT_OK

    if cmd == "sharedcache":
        from ..fleet.sharedcache import SharedCacheServer

        server = SharedCacheServer(
            ip=args.ip,
            port=args.port,
            max_entries=args.max_entries,
            ttl_s=args.ttl,
        )
        _emit(
            f"shared cache sidecar on {args.ip}:{server.bound_port} "
            f"({args.max_entries} entries, {args.ttl}s TTL)"
        )
        try:
            server.serve_forever()
        finally:
            server.server_close()
        return EXIT_OK

    if cmd == "eventserver":
        from ..api.event_server import EventServerConfig, create_event_server

        create_event_server(
            EventServerConfig(ip=args.ip, port=args.port, stats=args.stats),
            registry=registry,
            block=True,
        )
        return EXIT_OK

    if cmd == "dashboard":
        from .dashboard import DashboardConfig, create_dashboard

        create_dashboard(
            DashboardConfig(ip=args.ip, port=args.port, nodes=args.nodes),
            registry,
            block=True,
        )
        return EXIT_OK

    if cmd == "storageserver":
        if args.replica_of:
            from ..storage.replica import create_storage_replica

            replica = create_storage_replica(
                args.ip, args.port, args.replica_of, registry,
                partition_index=args.partition_index,
                partition_count=args.partition_count,
            )
            replica.start_tailing(poll_interval_s=args.poll_interval)
            _emit({
                "status": "serving", "role": "replica",
                "port": replica.bound_port, "primary": args.replica_of,
                "partition": [args.partition_index, args.partition_count],
            })
            try:
                replica.serve_forever()
            except KeyboardInterrupt:
                replica.stop_tailing()
                replica.server_close()
            return EXIT_OK

        from ..storage.registry import base_dir
        from ..storage.storage_server import create_storage_server

        oplog_dir = None
        if not args.no_changefeed:
            oplog_dir = args.oplog_dir or os.path.join(base_dir(), "oplog")
        server = create_storage_server(
            args.ip, args.port, registry, oplog_dir=oplog_dir,
            partition_index=args.partition_index,
            partition_count=args.partition_count,
            sync_every=args.sync_every,
        )
        _emit({
            "status": "serving", "role": "primary",
            "port": server.bound_port,
            "changefeed": oplog_dir is not None,
            "partition": [args.partition_index, args.partition_count],
        })
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            server.server_close()
        return EXIT_OK

    if cmd == "top":
        from ..obs.top import DEFAULT_NODES, run_top

        return run_top(
            args.nodes or DEFAULT_NODES,
            timeout=args.timeout,
            as_json=args.json,
        )

    if cmd == "trace":
        from ..obs.top import DEFAULT_NODES, run_trace

        return run_trace(
            args.trace_id,
            args.nodes or DEFAULT_NODES,
            timeout=args.timeout,
            as_json=args.json,
        )

    if cmd == "status":
        result = status(registry)
        _emit(result)
        return EXIT_OK if result["ok"] else EXIT_FAIL

    if cmd == "migrate":
        return migrate_command(args)

    if cmd == "autoscale":
        return autoscale_command(args)

    if cmd == "upgrade":
        from .upgrade import run_upgrade

        _emit(run_upgrade(
            registry, args.from_type, args.from_path,
            args.to_type, args.to_path, app_ids=args.appid,
        ))
        return EXIT_OK

    if cmd == "export":
        from .export_events import export_events, export_events_parquet

        if args.format == "parquet":
            n = export_events_parquet(registry, args.appid, args.output)
        else:
            with open(args.output, "w", encoding="utf-8") as fh:
                n = export_events(registry, args.appid, fh)
        _emit({"appId": args.appid, "events": n, "output": args.output,
               "format": args.format})
        return EXIT_OK

    if cmd == "import":
        from .import_events import import_events, import_events_parquet

        if args.format == "parquet":
            n = import_events_parquet(registry, args.appid, args.input)
        else:
            with open(args.input, "r", encoding="utf-8") as fh:
                n = import_events(registry, args.appid, fh)
        _emit({"appId": args.appid, "events": n, "input": args.input})
        return EXIT_OK

    if cmd == "template":
        from .gallery import GalleryError, gallery_url, get_remote, list_remote
        from .templates import get_template, list_templates

        if args.template_command == "list":
            # one flat list (the original CLI contract — scripts iterate
            # entries); remote entries are tagged by "source"
            out = [dict(t, source="bundled") for t in list_templates()]
            if gallery_url():
                # a broken gallery (unreachable, HTML error page, malformed
                # index) must not take down the bundled listing
                try:
                    out.extend(
                        dict(t, source="remote") for t in list_remote()
                    )
                except Exception as exc:
                    print(
                        f"warning: remote gallery failed: "
                        f"{type(exc).__name__}: {exc}",
                        file=sys.stderr,
                    )
            _emit(out)
        else:
            # bundled names win; anything else resolves via the remote
            # gallery when one is configured (Template.scala:287-375)
            try:
                _emit(get_template(args.template_name, args.directory))
            except KeyError:
                if not gallery_url():
                    raise
                _emit(get_remote(args.template_name, args.directory))
        return EXIT_OK

    raise ValueError(f"Unknown command {cmd!r}")


if __name__ == "__main__":
    sys.exit(main())
