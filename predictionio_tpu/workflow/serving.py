"""Query server: REST deployment of trained engines.

Rebuild of ``core/src/main/scala/io/prediction/workflow/CreateServer.scala``:

- ``POST /queries.json`` — decode query, ``predict`` over every algorithm,
  ``serve`` combine, optional feedback loop (``CreateServer.scala:458-577``);
- ``GET /reload``       — hot-swap to the latest completed engine instance
  (``MasterActor`` ReloadServer, ``CreateServer.scala:300-321``);
- ``GET /stop``         — graceful shutdown (``CreateServer.scala:389-397``);
- ``GET /``             — status page with engine info and serving stats
  (``CreateServer.scala:421-456``; twirl ``index.scala.html``).

The reference's akka ``MasterActor``/``ServerActor`` pair and its
serve-time SparkContext collapse into one threaded HTTP server holding the
live model pytrees (factor tables stay resident in HBM between requests; a
reload swaps the table references under a lock — the TPU analogue of
respawning the server actor).

Feedback events mirror ``CreateServer.scala:505-565``: a ``predict`` event
with ``entityType=pio_pr``, a generated 64-char ``prId``, and properties
``{engineInstanceId, query, prediction}`` POSTed to the Event Server; when
the prediction carries a ``prId`` field the response is stamped with the
generated id.

Resilience (``docs/robustness.md``): requests carry an optional
``X-PIO-Deadline-Ms`` budget checked at admission and again before the
MicroBatcher dispatch (an expired query never wastes a device slot);
admission is bounded (``PIO_SERVING_MAX_QUEUE`` in-flight queries, then
``503`` + ``Retry-After`` instead of unbounded thread pile-up); the
Event-Server feedback and ``--log-url`` POSTs ride a shared
``RetryPolicy`` (feedback events carry an ``idempotencyKey`` so the
retries cannot double-insert) behind per-sink ``CircuitBreaker``s; when
a breaker is open the server keeps answering from the HBM-resident
last-good model and reports ``degraded: true`` in its status.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import html
import json
import logging
import os
import random
import string
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, List, Optional, Sequence, Tuple
from urllib.parse import urlparse

import requests

from ..api.http import BackgroundHTTPServer, JsonHTTPHandler
from ..controller.engine import Engine, EngineParams
from ..obs.flight import record as flight_record
from ..obs.metrics import MetricsRegistry
from ..obs.trace import TRACE_HEADER, SpanContext, Tracer, current_context
from ..rollout.manager import RolloutError, RolloutManager
from ..rollout.plan import BASELINE, CANDIDATE, VARIANT_HEADER
from ..storage import StorageRegistry, utcnow
from ..storage.metadata import (
    ROLLOUT_SHADOW,
    STATUS_COMPLETED,
    EngineInstance,
)
from ..testing.faults import fault_point
from ..utils.resilience import (
    DEADLINE_HEADER,
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
    deadline_scope,
)
from .batching import MicroBatcher
from .context import WorkflowContext
from .core_workflow import load_models

logger = logging.getLogger(__name__)

#: Default in-flight admission cap (``PIO_SERVING_MAX_QUEUE`` overrides):
#: enough to keep batch_max-sized micro-batches formable under load,
#: small enough that a stalled device fails new arrivals in microseconds
#: instead of stacking handler threads until the process dies.
DEFAULT_MAX_QUEUE = 128


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """``ServerConfig`` (``CreateServer.scala:71-98``); query port default
    8000 (``CreateServer.scala:76``)."""

    ip: str = "localhost"
    port: int = 8000
    engine_instance_id: Optional[str] = None  # None = latest COMPLETED
    engine_id: Optional[str] = None
    engine_version: Optional[str] = None
    engine_variant: str = "engine.json"
    feedback: bool = False
    event_server_ip: str = "localhost"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    batch: str = ""
    # Micro-batching (the accelerator replacement for the reference's
    # per-request predictBase, CreateServer.scala:479-485): concurrent
    # queries are aggregated for <= batch_wait_ms into one batched device
    # dispatch. Worst-case added latency = batch_wait_ms; under load the
    # batch fills instantly and the wait never triggers.
    batching: bool = True
    # 512 keeps the padded top-k program set small (pad_pow2) while
    # amortizing the host↔device round trip over a large batch; device
    # time grows sub-linearly.
    # Memory envelope: scoring materializes a [batch, n_items] f32 matrix
    # PER IN-FLIGHT BATCH, so peak device memory scales with
    # batch_pipeline_depth × batch_max — at 10M items and depth 2,
    # 2×512×1e7×4 B ≈ 41 GB. Size batch_max to the catalog AND depth:
    # batch_max ≲ device_bytes / (batch_pipeline_depth × n_items × 4)
    # (e.g. 64 for 10M items at depth 2 on a 16 GB chip). The fused
    # streaming top-k (auto-selected on TPU past 64 MB of would-be
    # scores — ops.scoring.STREAMING_TOPK_BYTES; /status.json topkPath
    # reports the resolved path) sidesteps the score matrix entirely.
    batch_max: int = 512
    batch_wait_ms: float = 1.0
    # In-flight batch pipelining: while one batch's results travel back
    # from the device, the next is already dispatched. Depth 2 hides one
    # full host↔device round trip; raise it when round_trip >>
    # device_time. Peak
    # device memory scales with depth × the batch_max envelope above.
    batch_pipeline_depth: int = 2
    #: Remote error log: serving failures POST {message, query} here
    #: (``--log-url``, ``CreateServer.scala:409-420``). None = disabled.
    log_url: Optional[str] = None
    #: Bounded admission: max queries in flight (handler threads admitted
    #: past the front door) before new arrivals shed with 503 +
    #: Retry-After. None = ``PIO_SERVING_MAX_QUEUE`` env (default
    #: ``DEFAULT_MAX_QUEUE``); 0 disables shedding (unbounded, the
    #: pre-resilience behavior).
    max_queue: Optional[int] = None
    #: Continuous-learning loop: a ``ContinuousConfig``
    #: (``predictionio_tpu/continuous``) attaches a changefeed-driven
    #: fold-in controller to this server — candidates auto-submit
    #: through the rollout plane (docs/continuous.md). None = disabled.
    continuous: Optional[Any] = None
    #: Quality-observability knobs: a ``QualityConfig``
    #: (``predictionio_tpu/obs/quality``) for the served-score drift /
    #: feedback-join monitor every query server carries
    #: (docs/observability.md#quality). None = defaults.
    quality: Optional[Any] = None
    #: Fleet-health knobs: a ``HealthConfig``
    #: (``predictionio_tpu/obs/slo``) for the SLO burn-rate engine,
    #: stall watchdog and flight recorder every server carries
    #: (docs/slo.md). None = env defaults.
    health: Optional[Any] = None
    #: Sharded-model serving (docs/fleet.md): with ``shard_count > 1``
    #: this server holds only partition ``shard_index`` of the item
    #: factors (item row ``i`` lives on shard ``i % shard_count``) and
    #: answers with its *local* top-k; a ``pio router --sharded`` tier
    #: fans queries out to every shard and k-way-merges the answers into
    #: the exact global top-k. Every algorithm in the engine must
    #: implement ``shard_model`` — deploy fails loudly otherwise. The
    #: shard spec rides ``dataclasses.replace`` into rollout candidate
    #: deployments, so a canary on a sharded fleet is sharded
    #: identically.
    shard_index: int = 0
    shard_count: int = 1


# ---------------------------------------------------------------------------
# Query / prediction JSON codecs (per-algo querySerializer analogue,
# CreateServer.scala:475-478)
# ---------------------------------------------------------------------------


def decode_query(algorithms: Sequence[Any], payload: Any) -> Any:
    """Decode a JSON query using the first algorithm's declared query class
    (plain dicts pass through, like json4s ``DefaultFormats``)."""
    for algo in algorithms:
        cls = algo.query_class()
        if cls is not None:
            if dataclasses.is_dataclass(cls):
                fields = {f.name for f in dataclasses.fields(cls)}
                return cls(**{k: v for k, v in payload.items() if k in fields})
            return cls(**payload)
    return payload


def encode_result(obj: Any) -> Any:
    """Prediction → JSON-compatible structure.

    A result type may define ``to_json_dict`` to control its wire shape (the
    per-algo querySerializer analogue, ``CreateServer.scala:475-478``) —
    templates use it for the reference's camelCase field names."""
    # hot path: most nodes of a result tree are leaves
    if obj is None or type(obj) in (str, int, float, bool):
        return obj
    if hasattr(obj, "to_json_dict") and not isinstance(obj, type):
        return encode_result(obj.to_json_dict())
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: encode_result(v) for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, dict):
        return {k: encode_result(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [encode_result(v) for v in obj]
    if not isinstance(obj, (str, bytes)):
        if hasattr(obj, "tolist"):
            return obj.tolist()  # numpy / jax arrays (any shape)
        if hasattr(obj, "item"):
            try:
                # pio: lint-ok[jit-host-sync-serving] encode_result IS the encode-time sync point the rule defers to — the one place a device scalar must become JSON
                return obj.item()  # other scalar wrappers
            except (TypeError, ValueError):
                pass
    return obj


def _gen_pr_id() -> str:
    """64 alphanumeric chars (``CreateServer.scala:513``)."""
    alphabet = string.ascii_letters + string.digits
    return "".join(random.choice(alphabet) for _ in range(64))


def _get_pr_id(obj: Any) -> Optional[str]:
    """The ``WithPrId`` protocol: a ``pr_id`` attribute or ``prId`` key."""
    if isinstance(obj, dict):
        return obj.get("prId") if "prId" in obj else None
    return getattr(obj, "pr_id", None)


def _has_pr_id(obj: Any) -> bool:
    return (isinstance(obj, dict) and "prId" in obj) or hasattr(obj, "pr_id")


# ---------------------------------------------------------------------------
# Serving stats (CreateServer.scala:392-394,567-574, grown with the
# resilience counters the status page reports)
# ---------------------------------------------------------------------------


class ServingStats:
    """Thread-safe serving counters, backed by the obs metrics plane.

    Beyond the reference's request count / serving times, every
    resilience outcome is *counted*, not just logged: shed admissions,
    expired deadlines, retries, feedback/error-log delivery failures and
    breaker-skipped deliveries — a fleet monitor reads these off
    ``GET /`` instead of scraping logs.

    Request latency feeds a log-scale registry histogram
    (``pio_serving_request_seconds``), so :meth:`snapshot` reports
    p50/p95/p99 — last/avg alone are blind to exactly the tail behavior
    that matters at millions of users (a 2x p99 regression moves the
    average by noise). Every pre-existing camelCase wire key is
    preserved; the percentiles are additive."""

    _COUNTERS = (
        "shed",
        "deadline_expired",
        "retries",
        "feedback_sent",
        "feedback_failures",
        "feedback_skipped",
        "error_log_failures",
        "error_log_skipped",
    )

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        # standalone construction (tests, loadgen) gets a private
        # registry; servers pass theirs so /metrics sees the same series
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._hist = self.metrics.histogram(
            "pio_serving_request_seconds",
            "End-to-end /queries.json latency",
        )
        self._events = self.metrics.counter(
            "pio_serving_events_total",
            "Serving resilience outcomes",
            labelnames=("kind",),
        )
        self._lock = threading.Lock()
        self.request_count = 0
        self.last_serving_sec = 0.0
        self.avg_serving_sec = 0.0
        for name in self._COUNTERS:
            setattr(self, name, 0)

    def record_request(self, elapsed_s: float) -> None:
        with self._lock:
            self.last_serving_sec = elapsed_s
            self.avg_serving_sec = (
                self.avg_serving_sec * self.request_count + elapsed_s
            ) / (self.request_count + 1)
            self.request_count += 1
        self._hist.observe(elapsed_s)

    def inc(self, counter: str) -> None:
        if counter not in self._COUNTERS:
            raise ValueError(f"unknown serving counter {counter!r}")
        with self._lock:
            setattr(self, counter, getattr(self, counter) + 1)
        self._events.inc(1, kind=counter)  # kind is a closed set: safe label

    def percentile_ms(self, q: float) -> float:
        return round(self._hist.percentile(q) * 1000.0, 3)

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "requests": self.request_count,
                "lastServingMs": round(self.last_serving_sec * 1000, 3),
                "avgServingMs": round(self.avg_serving_sec * 1000, 3),
            }
            for name in self._COUNTERS:
                # camelCase the wire names to match the rest of the API
                parts = name.split("_")
                key = parts[0] + "".join(p.title() for p in parts[1:])
                out[key] = getattr(self, name)
        # histogram-estimated tail latency (outside the lock: the
        # histogram has its own)
        out["p50Ms"] = self.percentile_ms(0.50)
        out["p95Ms"] = self.percentile_ms(0.95)
        out["p99Ms"] = self.percentile_ms(0.99)
        return out


# ---------------------------------------------------------------------------
# Deployment state (what MasterActor rebuilds on reload)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Deployment:
    """One live engine instance: algorithms + in-memory (HBM) models +
    serving combiner (``createServerActorWithEngine``,
    ``CreateServer.scala:184-248``)."""

    instance: EngineInstance
    engine_params: EngineParams
    algorithms: List[Any]
    models: List[Any]
    serving: Any


def prepare_deployment(
    engine: Engine,
    registry: StorageRegistry,
    config: ServerConfig,
    ctx: Optional[WorkflowContext] = None,
) -> Deployment:
    """Load the target engine instance and make its models live
    (``CreateServer.scala:184-248`` + ``Engine.prepareDeploy``)."""
    md = registry.get_metadata()
    if config.engine_instance_id:
        instance = md.engine_instance_get(config.engine_instance_id)
        if instance is None:
            raise KeyError(
                f"Engine instance {config.engine_instance_id} not found"
            )
    else:
        # positional args: survives the metadata RPC wire ({method, args},
        # no kwargs channel) so deploy works on remote/HA storage
        instance = md.engine_instance_get_latest_completed(
            config.engine_id or "default",
            config.engine_version or "1",
            config.engine_variant,
        )
        if instance is None:
            raise RuntimeError(
                "No completed engine instance found; run train first "
                "(Console.scala:742-780)"
            )
    if instance.status != STATUS_COMPLETED:
        raise RuntimeError(
            f"Engine instance {instance.id} has status {instance.status}, "
            "not COMPLETED"
        )

    ctx = ctx or WorkflowContext(mode="Serving", batch=config.batch)
    engine_params = engine.engine_instance_to_engine_params(instance)
    persisted = load_models(registry, instance.id)
    live_models = engine.prepare_deploy(ctx, engine_params, instance.id, persisted)
    algorithms = engine._algorithms(engine_params)
    serving = engine._serving(engine_params)
    if config.shard_count > 1:
        live_models = _shard_models(algorithms, live_models, config)
    return Deployment(
        instance=instance,
        engine_params=engine_params,
        algorithms=algorithms,
        models=live_models,
        serving=serving,
    )


def _shard_models(
    algorithms: Sequence[Any], models: List[Any], config: ServerConfig
) -> List[Any]:
    """Replace each live model with its ``shard_index``-of-``shard_count``
    partition (docs/fleet.md). Every algorithm must opt in via a
    ``shard_model(model, shard_index, shard_count)`` method: a server
    that silently held the full catalog on a sharded fleet would make
    the router's merged top-k wrong (duplicated items), so a
    non-shardable algorithm fails the deploy, not the first query."""
    if not (0 <= config.shard_index < config.shard_count):
        raise ValueError(
            f"shard_index {config.shard_index} out of range for "
            f"shard_count {config.shard_count}"
        )
    sharded: List[Any] = []
    for algo, model in zip(algorithms, models):
        shard = getattr(algo, "shard_model", None)
        if shard is None:
            raise ValueError(
                f"{type(algo).__name__} does not implement shard_model; "
                "this engine cannot serve in sharded mode (docs/fleet.md)"
            )
        sharded.append(shard(model, config.shard_index, config.shard_count))
    return sharded


# ---------------------------------------------------------------------------
# HTTP server
# ---------------------------------------------------------------------------


class QueryDecodeError(ValueError):
    """Query JSON does not fit the engine's query shape → 400, matching the
    reference's MappingException handling (``CreateServer.scala:578-585``)."""


class _QueryHandler(JsonHTTPHandler):
    server: "QueryServer"

    #: every response of this server carries a variant label (closed
    #: {-, baseline, candidate} vocabulary; "-" = no rollout involved)
    #: so canary/shadow traffic is attributable on the shared
    #: ``pio_http_responses_total`` series (docs/rollouts.md)
    response_label_defaults = {"variant": "-"}

    def do_POST(self) -> None:  # noqa: N802
        self.response_labels = None  # handler instances persist per-connection
        raw = self.read_body()
        path = urlparse(self.path).path
        if path == "/queries.json":
            self._handle_queries(raw)
        elif path == "/reload":
            # reload is a state-changing op: POST is the proper verb
            # (GET kept below for CreateServer parity, deprecated —
            # docs/serving.md)
            self._handle_reload()
        elif path in ("/rollout/start", "/rollout/promote", "/rollout/abort"):
            self._handle_rollout(path, raw)
        elif path in (
            "/continuous/start",
            "/continuous/pause",
            "/continuous/trigger",
        ):
            self._handle_continuous(path, raw)
        else:
            self.respond(404, {"message": "Not Found"})

    def _handle_queries(self, raw: bytes) -> None:
        try:
            payload = json.loads(raw.decode("utf-8")) if raw else {}
        except ValueError as exc:
            self.respond(400, {"message": str(exc)})
            return
        # Bounded admission BEFORE any engine work: at the cap the
        # overload answer is an instant 503 + Retry-After, not another
        # handler thread piling onto a saturated device (the shed-don't-
        # queue discipline of the ads-serving paper in PAPERS.md).
        if not self.server.admit():
            self.server.stats.inc("shed")
            self.respond(
                503,
                {"message": "server overloaded; shedding load"},
                headers={"Retry-After": self.server.retry_after_s()},
            )
            return
        deadline = Deadline.from_header(
            self.headers.get(DEADLINE_HEADER), clock=self.server.clock
        )
        span = None
        # Mutable out-channel for the serving variant: handle_query fills
        # it, the admission span records it as a tag (the dict is read at
        # span close), and the response counter labels it.
        info: dict = {"variant": "-"}
        try:
            if deadline is not None:
                # admission-stage check: a budget that is already gone
                # spends zero decode/supplement work
                deadline.check("admission")
            # Admission span: joins the client's X-PIO-Trace id (or roots
            # a fresh trace) and becomes ambient for the request, so the
            # engine's supplement/serve storage calls and the batcher
            # spans all land in the same trace (docs/observability.md).
            with self.server.tracer.server_span(
                "POST /queries.json",
                header_value=self.headers.get(TRACE_HEADER),
                tags=info,
            ) as span:
                result, status = self.server.handle_query(
                    payload, deadline, info=info
                )
            self.response_labels = {"variant": info["variant"]}
            # VARIANT_HEADER echoes the serving variant to the client —
            # the router tier's fleet-consistency check compares it
            # against its own pure-function assignment (docs/fleet.md),
            # and a chaos drill can assert stickiness across a backend
            # kill without scraping metrics.
            self.respond(
                status,
                result,
                headers={
                    TRACE_HEADER: span.trace_id,
                    VARIANT_HEADER: info["variant"],
                },
            )
        except DeadlineExceeded as exc:
            self.response_labels = {"variant": info["variant"]}
            self.server.stats.inc("deadline_expired")
            self.respond(504, {"message": str(exc), "stage": exc.stage})
        except QueryDecodeError as exc:
            # the reference remote-logs the bad-query branch too
            # (CreateServer.scala:583-590)
            self.response_labels = {"variant": info["variant"]}
            self.server.post_error_log(str(exc), payload, trace_ctx=span)
            self.respond(400, {"message": str(exc)})
        except Exception as exc:
            logger.exception("Query failed")
            self.response_labels = {"variant": info["variant"]}
            self.server.post_error_log(str(exc), payload, trace_ctx=span)
            self.respond(500, {"message": str(exc)})
        finally:
            self.server.release()

    def _handle_reload(self) -> None:
        rollout = self.server.rollout
        if rollout is not None and rollout.active:
            self.respond(
                409,
                {
                    "message": (
                        f"rollout {rollout.plan.id} in progress "
                        f"(stage {rollout.stage}); promote or abort it "
                        "before reloading"
                    ),
                },
            )
            return
        try:
            self.server.reload()
            self.respond(200, {"message": "Reloaded"})
        except Exception as exc:
            logger.exception("Reload failed")
            self.respond(500, {"message": str(exc)})

    def _handle_rollout(self, path: str, raw: bytes) -> None:
        """``POST /rollout/start|promote|abort`` (docs/rollouts.md)."""
        rollout = self.server.rollout
        try:
            body = json.loads(raw.decode("utf-8")) if raw else {}
        except ValueError as exc:
            self.respond(400, {"message": str(exc)})
            return
        if not isinstance(body, dict):
            self.respond(400, {"message": "expected a JSON object body"})
            return
        try:
            if path == "/rollout/start":
                out = rollout.start(
                    candidate_instance_id=body.get("instanceId"),
                    percent=body.get("percent"),
                    gates=body.get("gates"),
                )
            elif path == "/rollout/promote":
                out = rollout.promote(body.get("reason", "manual promote"))
            else:
                out = rollout.abort(body.get("reason", "manual abort"))
            self.respond(200, out)
        except RolloutError as exc:
            self.respond(409, {"message": str(exc)})
        except ValueError as exc:  # e.g. an unknown gate option
            self.respond(400, {"message": str(exc)})
        except Exception as exc:
            logger.exception("rollout %s failed", path)
            self.respond(500, {"message": str(exc)})

    def _handle_continuous(self, path: str, raw: bytes) -> None:
        """``POST /continuous/start|pause|trigger`` (docs/continuous.md)."""
        continuous = self.server.continuous
        if continuous is None:
            self.respond(
                409,
                {
                    "message": (
                        "no continuous controller attached; deploy with "
                        "--continuous-app (docs/continuous.md)"
                    ),
                },
            )
            return
        try:
            body = json.loads(raw.decode("utf-8")) if raw else {}
        except ValueError as exc:
            self.respond(400, {"message": str(exc)})
            return
        if not isinstance(body, dict):
            self.respond(400, {"message": "expected a JSON object body"})
            return
        try:
            if path == "/continuous/start":
                continuous.start()
                out = continuous.status()
            elif path == "/continuous/pause":
                out = continuous.pause()
            else:
                out = continuous.trigger(full=bool(body.get("full")))
            self.respond(200, out)
        except Exception as exc:
            logger.exception("continuous %s failed", path)
            self.respond(500, {"message": str(exc)})

    def do_GET(self) -> None:  # noqa: N802
        self.response_labels = None  # handler instances persist per-connection
        path = urlparse(self.path).path
        if self.serve_obs(path):  # /metrics + /traces.json
            return
        if path == "/" or path == "/status.json":
            # content negotiation: browsers keep the HTML status page,
            # monitors GET /status.json (or Accept: application/json)
            # for the machine-readable twin with breaker states and
            # shed counters
            accept = self.headers.get("Accept", "")
            if path == "/status.json" or "application/json" in accept:
                self.respond(200, self.server.status_json())
            else:
                self.respond(
                    200, self.server.status_html(), content_type="text/html"
                )
        elif path == "/rollout.json":
            self.respond(200, self.server.rollout.status())
        elif path == "/shard.json":
            # shard metadata for the router tier / fleet tooling
            # (docs/fleet.md): which partition this server holds
            self.respond(200, self.server.shard_json())
        elif path == "/continuous.json":
            continuous = self.server.continuous
            if continuous is None:
                self.respond(200, {"enabled": False})
            else:
                self.respond(200, continuous.status())
        elif path == "/reload":
            # deprecated spelling (state change behind a GET), kept for
            # PredictionIO CreateServer parity — use POST /reload
            self._handle_reload()
        elif path == "/stop":
            self.respond(200, {"message": "Shutting down"})
            self.server.stop_async()
        else:
            self.respond(404, {"message": "Not Found"})


class QueryServer(BackgroundHTTPServer):
    """The serving process (``ServerActor`` + ``MasterActor``,
    ``CreateServer.scala:250-628``)."""

    def __init__(
        self,
        config: ServerConfig,
        engine: Engine,
        registry: StorageRegistry,
        deployment: Optional[Deployment] = None,
        ctx: Optional[WorkflowContext] = None,
        clock: Callable[[], float] = time.monotonic,
        retry_policy: Optional[RetryPolicy] = None,
        feedback_breaker: Optional[CircuitBreaker] = None,
        error_log_breaker: Optional[CircuitBreaker] = None,
        reload_breaker: Optional[CircuitBreaker] = None,
    ):
        self.config = config
        self.engine = engine
        self.registry = registry
        self.ctx = ctx or WorkflowContext(mode="Serving", batch=config.batch)
        self._deploy_lock = threading.RLock()
        self.deployment = deployment or prepare_deployment(
            engine, registry, config, self.ctx
        )
        # Resilience plumbing (docs/robustness.md). The clock and policy
        # objects are injectable so the whole fault suite runs without a
        # wall-clock sleep; defaults come from the PIO_BREAKER_* env.
        self.clock = clock
        # Observability plane (docs/observability.md): one registry +
        # tracer per server process, exposed on /metrics + /traces.json.
        metrics = MetricsRegistry(clock=clock)
        self.stats = ServingStats(metrics)
        # Quality-observability plane (docs/observability.md#quality):
        # per-variant served-score sketches (drift vs a baseline snapshot
        # pinned at model LIVE) and the feedback join the continuous
        # plane feeds — pio_quality_* on /metrics, `pio quality` reads
        # them fleet-wide.
        from ..obs.quality import QualityMonitor

        self.quality = QualityMonitor(
            metrics, clock=clock, config=config.quality
        )
        # Jit boundary telemetry (docs/observability.md#profiling): the
        # process telemetry mirrors onto this registry so /metrics shows
        # pio_jit_compiles_total / pio_jit_retraces_total — bind() replays
        # totals, so the deploy-time serving compiles that happened
        # before this registry existed are not lost.
        from ..obs.profile import default_telemetry

        default_telemetry().bind(metrics)
        default_telemetry().attach_monitoring()
        # Quantized-serving gate outcomes (docs/quantization.md#gate):
        # the quant module counts runs/refusals process-wide; callback
        # gauges export them so a refusal is a visible series on
        # /metrics, not just a stack trace in the deploy log.
        from ..quant import gate_counts

        metrics.gauge_callback(
            "pio_quant_gate_runs_total",
            lambda: gate_counts().get("runs", 0),
            "Quantized-serving exactness gate evaluations",
        )
        metrics.gauge_callback(
            "pio_quant_gate_refusals_total",
            lambda: gate_counts().get("refusals", 0),
            "Quantized-serving tables refused by the exactness gate",
        )
        self._retry = retry_policy or RetryPolicy(
            attempts=3,
            base_delay_s=0.05,
            max_delay_s=1.0,
            on_retry=lambda _i: self.stats.inc("retries"),
        )
        self.feedback_breaker = feedback_breaker or CircuitBreaker.from_env(
            "event-server", clock=clock
        )
        self.error_log_breaker = error_log_breaker or CircuitBreaker.from_env(
            "error-log", clock=clock
        )
        self.reload_breaker = reload_breaker or CircuitBreaker.from_env(
            "reload", clock=clock
        )
        if config.max_queue is not None:
            self._max_queue = config.max_queue
        else:
            self._max_queue = int(
                os.environ.get("PIO_SERVING_MAX_QUEUE", str(DEFAULT_MAX_QUEUE))
            )
        self._admission_lock = threading.Lock()
        self._inflight = 0
        # Bounded async feedback delivery (CreateServer's fire-and-forget
        # future, without unbounded thread growth under load).
        self._feedback_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="feedback"
        )
        # Micro-batching prediction dispatch (see ServerConfig.batching).
        # The deployment travels WITH each queued item, so a /reload
        # mid-batch is safe: in-flight queries finish on the model they
        # arrived under.
        tracer = Tracer("query-server", clock=clock)
        self._batcher: Optional[MicroBatcher] = (
            MicroBatcher(
                self._predict_batch,
                max_batch=config.batch_max,
                max_wait_ms=config.batch_wait_ms,
                name="predict-batch",
                pipeline_depth=config.batch_pipeline_depth,
                metrics=metrics,
                tracer=tracer,
                clock=clock,
            )
            if config.batching
            else None
        )
        # Serving stats (CreateServer.scala:392-394,567-574 + resilience)
        self.server_start_time = utcnow()
        # breaker states + lifetime opens, pulled at scrape time
        for dep, breaker in (
            ("event-server", self.feedback_breaker),
            ("error-log", self.error_log_breaker),
            ("reload", self.reload_breaker),
        ):
            metrics.gauge_callback(
                "pio_breaker_state",
                (lambda b=breaker: b.state_value),
                "Breaker state (0 closed, 1 half-open, 2 open)",
                labels={"dep": dep},
            )
            # monotonic, but exposed as a gauge (the callback pull
            # model) — so no `_total` suffix, like pio_changefeed_seq
            metrics.gauge_callback(
                "pio_breaker_opens",
                (lambda b=breaker: b.open_count),
                "Lifetime breaker open transitions",
                labels={"dep": dep},
            )
        # Observer-fault accounting (docs/slo.md): every swallowed
        # observer/monitor exception is COUNTED, never just debug-logged
        # — a quality monitor that starts throwing on every query is
        # invisible in logs and a flat line on this counter is the
        # proof the observers are healthy (the obs-swallowed-observer
        # lint rule pins the pattern).
        self._observer_errors = metrics.counter(
            "pio_observer_errors_total",
            "Swallowed observer/monitor exceptions by site",
            labelnames=("site",),
        )
        super().__init__(
            (config.ip, config.port),
            _QueryHandler,
            metrics=metrics,
            tracer=tracer,
            health_kind="query",
            health_config=config.health,
        )
        self._export_train_phases()
        # Rollout plane (docs/rollouts.md): the manager owns any staged
        # deploy of this engine. resume() re-resolves an active plan
        # from metadata, so a server restarted mid-canary keeps the
        # exact same sticky split; a broken plan degrades to plain
        # baseline serving, never a failed boot.
        self.rollout = RolloutManager(self)
        try:
            self.rollout.resume()
        except Exception:
            logger.exception(
                "rollout resume failed; serving the baseline only"
            )
        # Continuous-learning plane (docs/continuous.md): the controller
        # resumes its durable cursor and any in-flight candidate on
        # construction; a broken loop degrades to plain serving, never a
        # failed boot (the loop is an optimization, the server is not).
        self.continuous = None
        if config.continuous is not None:
            try:
                from ..continuous.controller import ContinuousController

                self.continuous = ContinuousController(self, config.continuous)
                if config.continuous.autostart:
                    self.continuous.start()
            except Exception:
                self.continuous = None
                logger.exception(
                    "continuous controller failed to attach; serving "
                    "without the continuous-learning loop"
                )

    # Pre-resilience attribute surface, kept for callers/tests that read
    # the counters straight off the server object.
    @property
    def request_count(self) -> int:
        return self.stats.request_count

    @property
    def last_serving_sec(self) -> float:
        return self.stats.last_serving_sec

    @property
    def avg_serving_sec(self) -> float:
        return self.stats.avg_serving_sec

    # -- admission (bounded queue → shed, never pile up) -------------------
    def admit(self) -> bool:
        if self._max_queue <= 0:  # 0 = unbounded (explicit opt-out)
            return True
        with self._admission_lock:
            if self._inflight >= self._max_queue:
                return False
            self._inflight += 1
            return True

    def release(self) -> None:
        if self._max_queue <= 0:
            return
        with self._admission_lock:
            self._inflight = max(0, self._inflight - 1)

    def retry_after_s(self) -> int:
        """Retry-After for a shed request: one worst-case batch drain,
        floored at 1 s (the resolution HTTP gives us)."""
        drain = self.stats.avg_serving_sec * 2
        return max(1, int(drain + 0.999))

    @property
    def degraded(self) -> bool:
        """True while any dependency breaker is not closed — the server
        still answers (from the HBM-resident last-good model), but a
        fleet monitor should know the feedback/reload plane is impaired."""
        return any(
            b.state != CircuitBreaker.CLOSED
            for b in (
                self.feedback_breaker,
                self.error_log_breaker,
                self.reload_breaker,
            )
        )

    # -- query path (CreateServer.scala:458-577) --------------------------
    def handle_query(
        self,
        payload: Any,
        deadline: Optional[Deadline] = None,
        info: Optional[dict] = None,
    ) -> Tuple[Any, int]:
        """One query end to end. ``info`` (when given) is filled with the
        serving ``variant`` (and ``fallback`` on candidate containment)
        — the handler forwards it into span tags and response labels."""
        # Stall watchdog (docs/slo.md): every in-flight request is
        # tracked with its deadline budget — a request still running at
        # a multiple of that budget is a wedge the watchdog dumps
        # forensics for, whether or not the client is still waiting.
        watchdog = self.health.watchdog if self.health is not None else None
        token = (
            watchdog.enter(
                "serving.request",
                budget_s=(
                    deadline.remaining_s() if deadline is not None else None
                ),
            )
            if watchdog is not None
            else None
        )
        try:
            return self._handle_query_tracked(payload, deadline, info)
        finally:
            if watchdog is not None:
                watchdog.exit(token)

    def _handle_query_tracked(
        self,
        payload: Any,
        deadline: Optional[Deadline] = None,
        info: Optional[dict] = None,
    ) -> Tuple[Any, int]:
        started = time.monotonic()
        query_time = utcnow()
        rollout = self.rollout
        if rollout is not None:
            # land any transition whose metadata write failed — terminal
            # transitions have no later observe() to ride
            rollout.retry_pending_persist()
        rollout_active = rollout is not None and rollout.active
        variant = BASELINE
        variant_started = started
        dep = None
        if rollout_active:
            # Deterministic sticky split (docs/rollouts.md): CANARY
            # routes the plan's percent of entity keys to the candidate;
            # SHADOW always serves baseline (the duplicate is async).
            variant = rollout.variant_for(payload)
            if variant == CANDIDATE:
                dep = rollout.candidate_deployment()
                if dep is None:  # rollback won a race: serve baseline
                    variant = BASELINE
        if dep is None:
            with self._deploy_lock:
                dep = self.deployment
        if info is not None and rollout_active:
            info["variant"] = variant
        try:
            query, prediction = self._serve_one(dep, payload, deadline, variant)
        except DeadlineExceeded as exc:
            # An exhausted budget cannot be re-served from the baseline,
            # but a serving variant that burns client deadlines must feed
            # its error window, or a too-slow canary never rolls back.
            # Only the batch-wait stage is the variant's doing — a budget
            # already gone at admission/dispatch is the client's. Both
            # variants record, so the delta gate stays a *delta*.
            if rollout_active and exc.stage == "batch-wait":
                rollout.observe(variant, time.monotonic() - started, ok=False)
            raise
        except Exception:
            if variant != CANDIDATE:
                # Baseline failures count too: errors the whole fleet is
                # suffering (shared dependency down, malformed client
                # traffic) must raise BOTH windows' error rates, or the
                # delta gate degenerates into an absolute candidate
                # threshold and rolls back a healthy canary.
                if rollout_active:
                    rollout.observe(
                        BASELINE, time.monotonic() - started, ok=False
                    )
                raise
            # Canary containment: a sick candidate is a *rollout* signal
            # (counted against its error gate), never a client error —
            # the same request is re-served from the resident baseline.
            # QueryDecodeError included: a query the candidate's
            # algorithms cannot decode is a candidate defect.
            rollout.observe(CANDIDATE, time.monotonic() - started, ok=False)
            logger.exception(
                "candidate %s failed; serving baseline", dep.instance.id
            )
            variant = BASELINE
            variant_started = time.monotonic()  # gate windows see only
            # the baseline's own work, not the failed candidate attempt
            if info is not None:
                info["variant"] = variant
                info["fallback"] = True
            with self._deploy_lock:
                dep = self.deployment
            try:
                query, prediction = self._serve_one(
                    dep, payload, deadline, variant
                )
            except Exception:
                if rollout_active:  # the fallback itself failed: baseline's
                    rollout.observe(
                        BASELINE, time.monotonic() - variant_started, ok=False
                    )
                raise
        result = encode_result(prediction)

        # Quality plane: score distribution + the served-list record the
        # feedback join reads. BEFORE the prId stamp, like the shadow
        # duplicate — the signals describe the model's answer. Swallowed
        # on error but COUNTED (docs/slo.md): observability must never
        # fail a query, and a failing observer must never be invisible.
        try:
            self.quality.observe_result(variant, payload, result)
        except Exception:
            self._observer_errors.inc(1, site="serving.quality")
            logger.debug("quality observe failed", exc_info=True)

        # Shadow duplication BEFORE the feedback prId stamp: divergence
        # must compare model outputs, not the per-request id noise.
        if rollout_active and rollout.stage == ROLLOUT_SHADOW:
            rollout.submit_shadow(payload, result)

        if self.config.feedback:
            result = self._send_feedback(
                dep, query_time, query, prediction, result, variant
            )

        now = time.monotonic()
        if rollout_active:
            rollout.observe(variant, now - variant_started, ok=True)
        self.stats.record_request(now - started)
        return result, 200

    def _serve_one(
        self,
        dep: Deployment,
        payload: Any,
        deadline: Optional[Deadline],
        variant: str,
    ) -> Tuple[Any, Any]:
        """Decode → supplement → (batched) predict → combine against ONE
        deployment; the shared path under the live request, the canary
        fallback retry, and a shadow duplicate. Returns
        ``(query, prediction)``."""
        with deadline_scope(deadline):
            try:
                query = decode_query(dep.algorithms, payload)
            except (TypeError, AttributeError, KeyError) as exc:
                raise QueryDecodeError(f"Invalid query: {exc}") from exc
            query = dep.serving.supplement(query)
            if deadline is not None:
                # the load-shed moment that matters most: an expired query
                # must never occupy a device slot (ISSUE 2 tentpole)
                deadline.check("dispatch")
            # chaos hook (docs/slo.md): the loadgen --brownout scenario
            # wedges the predict path here — fault-injected latency and
            # refusals, not a kill — proving the stall watchdog and the
            # SLO burn alerts on a backend that is sick, not dead
            fault_point("serving.predict", instance=dep.instance.id)
            if variant == CANDIDATE:
                # chaos hook: the loadgen --rollout scenario fails the
                # candidate exactly here, proving auto-rollback with
                # zero client-visible failures (docs/rollouts.md)
                fault_point("serving.candidate", instance=dep.instance.id)
            if self._batcher is not None:
                try:
                    predictions = self._batcher.submit(
                        (dep, query),
                        timeout=(
                            deadline.remaining_s()
                            if deadline is not None
                            else None
                        ),
                    )
                except FutureTimeoutError:
                    raise DeadlineExceeded(
                        "deadline exceeded waiting for batched dispatch",
                        stage="batch-wait",
                    ) from None
            else:
                predictions = self._predict_one(dep, query)
            prediction = dep.serving.serve(query, predictions)
        return query, prediction

    def _post_json(
        self,
        site: str,
        url: str,
        data: Any,
        trace_ctx: Optional[SpanContext] = None,
    ) -> None:
        """One retried JSON POST to a sink (the shared delivery path of
        the feedback and error-log planes). Raises on final failure so
        the caller's breaker records ONE failure per logical delivery,
        not one per attempt. Retrying a *write* is safe here because
        both sinks dedupe: feedback events carry an ``idempotencyKey``
        and the error log is an append-only diagnostic stream.

        ``trace_ctx`` is the originating request's span context, captured
        *before* the hop onto the feedback pool thread (contextvars do
        not follow): the delivery records a child span and forwards the
        trace id so the Event Server's spans join the same trace."""
        headers = {}
        if trace_ctx is not None:
            headers[TRACE_HEADER] = trace_ctx.trace_id

        def attempt() -> None:
            fault_point(site, url=url)
            resp = requests.post(url, json=data, timeout=10, headers=headers)
            if resp.status_code not in (200, 201):
                raise RuntimeError(
                    f"{site} POST -> HTTP {resp.status_code}"
                )

        if trace_ctx is None:
            self._retry.call(attempt)
            return
        with self.tracer.span(site, parent=trace_ctx):
            self._retry.call(attempt)

    def post_error_log(
        self,
        message: str,
        payload: Any,
        trace_ctx: Optional[SpanContext] = None,
    ) -> None:
        """Fire-and-forget POST of a serving failure to ``log_url``
        (``CreateServer.scala:409-420`` — remote error reporting for
        fleet-monitored deployments). Rides the bounded feedback pool so
        an error storm against a slow sink cannot spawn unbounded
        threads, and never adds a failure of its own to the request; a
        dead sink trips ``error_log_breaker`` so the storm stops paying
        connect timeouts entirely."""
        url = self.config.log_url
        if not url:
            return
        # engine-instance identity so a shared fleet sink can attribute
        # the error (the reference posts {engineInstance, message},
        # CreateServer.scala:412-414)
        try:
            instance_id = self.deployment.instance.id
        except Exception:
            instance_id = None
        data = {
            "engineInstance": instance_id,
            "message": message,
            "query": payload,
        }
        if trace_ctx is None:
            trace_ctx = current_context()  # captured before the thread hop

        def send() -> None:
            try:
                self.error_log_breaker.call(
                    self._post_json, "serving.error_log", url, data,
                    trace_ctx=trace_ctx,
                )
            except CircuitOpen:
                self.stats.inc("error_log_skipped")
            except Exception:
                self.stats.inc("error_log_failures")
                logger.debug("error-log POST to %s failed", url, exc_info=True)

        try:
            self._feedback_pool.submit(send)
        except RuntimeError:
            # pool already shut down (/stop racing an in-flight failure):
            # the log post is best-effort; the response must still go out
            logger.debug("error-log skipped: feedback pool closed")

    @staticmethod
    def _predict_one(dep: Deployment, query: Any) -> List[Any]:
        """Unbatched per-query path (the reference's per-request
        ``predictBase`` loop, ``CreateServer.scala:479-485``)."""
        return [
            algo.predict(model, query)
            for algo, model in zip(dep.algorithms, dep.models)
        ]

    @staticmethod
    def _predict_batch(items: Sequence[Tuple[Deployment, Any]]) -> List[List[Any]]:
        """Batched prediction for micro-batched items ``(deployment,
        query)`` → per-item list of per-algorithm predictions.

        Queries are grouped by deployment (a reload mid-batch may leave
        two generations in one batch); within a group, each algorithm gets
        ONE ``batch_predict(model, [(idx, query)])`` call for the whole
        group — a single gather-dot top-k device dispatch for the TPU
        algorithms; the base-class default maps ``predict`` for the rest."""
        out: List[Any] = [None] * len(items)
        groups: dict = {}
        for pos, (dep, query) in enumerate(items):
            groups.setdefault(id(dep), (dep, []))[1].append((pos, query))
        for dep, indexed in groups.values():
            try:
                per_algo: List[dict] = []
                for algo, model in zip(dep.algorithms, dep.models):
                    per_algo.append(dict(algo.batch_predict(model, indexed)))
                for pos, _query in indexed:
                    out[pos] = [results[pos] for results in per_algo]
            except Exception:
                # Poison-query containment: one bad query must not 500 the
                # whole batch. Retry the group per-query; only the queries
                # that actually fail carry their exception (MicroBatcher's
                # per-item failure channel).
                for pos, query in indexed:
                    try:
                        out[pos] = QueryServer._predict_one(dep, query)
                    except Exception as exc:
                        out[pos] = exc
        return out  # every position was covered by exactly one group

    def _send_feedback(
        self,
        dep: Deployment,
        query_time: _dt.datetime,
        query: Any,
        prediction: Any,
        result: Any,
        variant: str = BASELINE,
    ) -> Any:
        """Async ``predict`` event to the Event Server
        (``CreateServer.scala:505-565``). The event carries the serving
        ``variant`` so offline evaluation can score canary vs. baseline
        straight from the event store (docs/rollouts.md)."""
        existing = _get_pr_id(prediction)
        new_pr_id = existing if existing else _gen_pr_id()
        data = {
            "event": "predict",
            "eventTime": query_time.isoformat(timespec="milliseconds"),
            "entityType": "pio_pr",
            "entityId": new_pr_id,
            "properties": {
                "engineInstanceId": dep.instance.id,
                "query": encode_result(query),
                "prediction": encode_result(prediction),
                "variant": variant,
            },
            # prId is unique per prediction, so it doubles as the event's
            # idempotency key: the RetryPolicy may replay this POST after
            # an ambiguous failure and the Event Server still inserts
            # exactly one event (docs/robustness.md).
            "idempotencyKey": new_pr_id,
        }
        query_pr_id = _get_pr_id(query)
        if query_pr_id is not None:
            data["prId"] = query_pr_id

        url = (
            f"http://{self.config.event_server_ip}:"
            f"{self.config.event_server_port}/events.json"
            f"?accessKey={self.config.access_key or ''}"
        )

        self._feedback_pool.submit(
            self._deliver_feedback, url, data, current_context()
        )

        # Stamp the generated prId into the response only for predictions
        # that carry a prId slot (CreateServer.scala:558-565).
        if _has_pr_id(prediction) and isinstance(result, dict):
            result = dict(result)
            result.pop("pr_id", None)  # replace the stale slot, don't duplicate
            result["prId"] = new_pr_id
        return result

    def _deliver_feedback(
        self,
        url: str,
        data: dict,
        trace_ctx: Optional[SpanContext] = None,
    ) -> None:
        """Breaker-guarded, retried feedback delivery (pool thread).

        While the Event Server is down the breaker opens after
        ``failure_threshold`` deliveries and subsequent feedback is
        *skipped* (counted, not attempted): queries keep serving from the
        resident model at full speed instead of each paying a connect
        timeout — the degraded mode ``GET /`` surfaces."""
        try:
            self.feedback_breaker.call(
                self._post_json, "serving.feedback", url, data,
                trace_ctx=trace_ctx,
            )
            self.stats.inc("feedback_sent")
        except CircuitOpen:
            self.stats.inc("feedback_skipped")
        except Exception as exc:
            self.stats.inc("feedback_failures")
            logger.error("Feedback event failed: %s", exc)

    # -- lifecycle --------------------------------------------------------
    def server_close(self) -> None:
        if self._batcher is not None:
            self._batcher.close()  # fail queued requests fast, join thread
        self._feedback_pool.shutdown(wait=False)
        if getattr(self, "continuous", None) is not None:
            self.continuous.stop()
        if getattr(self, "rollout", None) is not None:
            self.rollout.close()
        super().server_close()

    def _adopt_deployment(self, dep: Deployment) -> None:
        """Install ``dep`` as THE serving deployment (rollout go-live,
        docs/rollouts.md). The retired deployment's last server-side
        reference dies with the swap, so its model buffers are
        reclaimable; in-flight queries finish on the deployment they
        were routed to (they hold their own reference through the
        micro-batch items)."""
        with self._deploy_lock:
            old = self.deployment.instance.id
            self.deployment = dep
        self._export_train_phases()
        # re-pin the quality baseline: drift must be measured against the
        # distribution of the model NOW serving, not its predecessor's
        # (the closing state persists as a snapshot first)
        try:
            self.quality.model_live(dep.instance.id)
        except Exception:
            self._observer_errors.inc(1, site="serving.quality")
            logger.debug("quality re-pin failed", exc_info=True)
        flight_record(
            "deploy", "serving.adopt",
            fromInstance=old, toInstance=dep.instance.id,
        )
        logger.info(
            "Deployment swapped: engine instance %s -> %s",
            old, dep.instance.id,
        )

    def reload(self) -> None:
        """Hot-swap to the latest completed instance
        (``CreateServer.scala:300-321``): the new tables are staged first,
        then the references swap under the lock.

        Refused while a rollout is in flight: the latest completed
        instance IS the rollout's candidate, and loading it as the
        baseline would corrupt the split — promote or abort instead
        (docs/rollouts.md).

        Failures (storage down, corrupt instance) ride
        ``reload_breaker``: the resident last-good tables keep serving
        (degradation is nearly free — they never left HBM), repeated
        failures open the breaker so reload storms fast-fail, and the
        status page shows ``degraded: true`` until a probe reload
        succeeds."""
        rollout = getattr(self, "rollout", None)
        if rollout is not None and rollout.active:
            raise RuntimeError(
                f"rollout {rollout.plan.id} in progress (stage "
                f"{rollout.stage}); promote or abort it before reloading"
            )
        cfg = dataclasses.replace(
            self.config,
            engine_instance_id=None,
            engine_id=self.deployment.instance.engine_id,
            engine_version=self.deployment.instance.engine_version,
            engine_variant=self.deployment.instance.engine_variant,
        )
        fresh = self.reload_breaker.call(
            prepare_deployment, self.engine, self.registry, cfg, self.ctx
        )
        with self._deploy_lock:
            old = self.deployment.instance.id
            self.deployment = fresh
        self._export_train_phases()
        # a reload is a model go-live too: re-pin the drift baseline
        try:
            self.quality.model_live(fresh.instance.id)
        except Exception:
            self._observer_errors.inc(1, site="serving.quality")
            logger.debug("quality re-pin failed", exc_info=True)
        flight_record(
            "deploy", "serving.reload",
            fromInstance=old, toInstance=fresh.instance.id,
        )
        logger.info(
            "Reloaded: engine instance %s -> %s", old, fresh.instance.id
        )

    def _export_train_phases(self) -> None:
        """Re-export the deployed instance's persisted training phase
        timings as gauges (``pio top`` reads them off ``/metrics``).
        Phase names are read/prepare/train[i] — bounded by algo count.
        The previous export is cleared first: after a ``/reload`` the
        series must describe the instance actually deployed, not linger
        from the one it replaced (including when the new record carries
        no phases at all)."""
        from ..utils.profiling import phases_from_env

        phases = phases_from_env(self.deployment.instance.env)
        gauge = self.metrics.gauge(
            "pio_train_phase_seconds",
            "Wall-clock of each training phase of the deployed instance",
            labelnames=("phase",),
        )
        gauge.clear()
        for name, seconds in phases.items():
            gauge.set(seconds, phase=name)

    def shard_json(self) -> dict:
        """``GET /shard.json``: which item-factor partition this server
        holds (docs/fleet.md). ``items`` counts rows per model where the
        model exposes an ``item_factors`` table (the recommender
        templates); other models report None — the route is metadata,
        not a capability probe."""
        with self._deploy_lock:
            dep = self.deployment
        return {
            "sharded": self.config.shard_count > 1,
            "shardIndex": self.config.shard_index,
            "shardCount": self.config.shard_count,
            "engineInstance": dep.instance.id,
            "models": [
                {
                    "type": type(m).__name__,
                    "items": (
                        len(m.item_factors)
                        if getattr(m, "item_factors", None) is not None
                        else None
                    ),
                }
                for m in dep.models
            ],
        }

    # -- status page (CreateServer.scala:421-456) -------------------------
    def status_json(self) -> dict:
        """Machine-readable status: the HTML page's facts plus breaker
        states, shed/deadline counters and the degraded flag (``GET
        /status.json``, or ``GET /`` with ``Accept: application/json``)."""
        dep = self.deployment
        out = {
            "status": "degraded" if self.degraded else "alive",
            "degraded": self.degraded,
            "engineInstance": dep.instance.id,
            "engine": {
                "id": dep.instance.engine_id,
                "version": dep.instance.engine_version,
                "factory": dep.instance.engine_factory,
            },
            "startTime": str(self.server_start_time),
            "feedback": self.config.feedback,
            "maxQueue": self._max_queue,
            "stats": self.stats.snapshot(),
            "breakers": {
                "eventServer": self.feedback_breaker.snapshot(),
                "errorLog": self.error_log_breaker.snapshot(),
                "reload": self.reload_breaker.snapshot(),
            },
        }
        from ..utils.platform import device_info

        out["device"] = device_info()
        if self.config.shard_count > 1:
            out["shard"] = {
                "index": self.config.shard_index,
                "count": self.config.shard_count,
            }
        # resolved serving top-k path per algorithm ("streaming" = the
        # fused device-resident Pallas kernel, "dense" = XLA score +
        # lax.top_k; None until the first query) — the serve-side lever
        # record, matching the train side's resolved-flag discipline
        # (docs/performance.md#levers)
        topk = {
            f"{idx}:{type(algo).__name__}": algo.topk_path
            for idx, algo in enumerate(dep.algorithms)
            if getattr(algo, "topk_path", None) is not None
        }
        if topk:
            out["topkPath"] = topk
        # quantized-serving gate status per algorithm (table dtype,
        # bytes, compression ratio, gate matchRate — set at model
        # attach, docs/quantization.md): present only while the
        # quantized_serving lever is resolved ON, same shape the
        # profile dicts carry
        quant = {
            f"{idx}:{type(algo).__name__}": algo.quant_status
            for idx, algo in enumerate(dep.algorithms)
            if getattr(algo, "quant_status", None) is not None
        }
        if quant:
            from ..quant import gate_counts

            out["quantServing"] = quant
            out["quantGate"] = gate_counts()
        if self._batcher is not None:
            out["batching"] = self._batcher.stats
        if getattr(self, "quality", None) is not None:
            out["quality"] = self.quality.summary()
        if getattr(self, "rollout", None) is not None:
            out["rollout"] = self.rollout.status()
        if getattr(self, "continuous", None) is not None:
            out["continuous"] = self.continuous.status()
        from ..utils.profiling import phases_from_env

        phases = phases_from_env(dep.instance.env)
        if phases:
            out["trainPhases"] = phases
        return out

    def status_html(self) -> str:
        dep = self.deployment
        stats = self.stats.snapshot()
        rows = [
            ("Engine instance", dep.instance.id),
            ("Engine", f"{dep.instance.engine_id} {dep.instance.engine_version}"),
            ("Engine factory", dep.instance.engine_factory),
            ("Start time", str(self.server_start_time)),
            ("Algorithms", ", ".join(type(a).__name__ for a in dep.algorithms)),
            ("Models", ", ".join(type(m).__name__ for m in dep.models)),
            ("Serving", type(dep.serving).__name__),
            ("Feedback enabled", str(self.config.feedback)),
            ("Request count", str(stats["requests"])),
            ("Average serving time", f"{stats['avgServingMs']:.3f} ms"),
            ("Last serving time", f"{stats['lastServingMs']:.3f} ms"),
            ("Degraded", str(self.degraded)),
            (
                "Rollout",
                (
                    f"{self.rollout.plan.id} stage={self.rollout.stage}"
                    if getattr(self, "rollout", None) is not None
                    and self.rollout.plan is not None
                    else "none"
                ),
            ),
            ("Shed requests", str(stats["shed"])),
            ("Expired deadlines", str(stats["deadlineExpired"])),
            (
                "Breakers",
                ", ".join(
                    f"{name}={b.state}"
                    for name, b in (
                        ("event-server", self.feedback_breaker),
                        ("error-log", self.error_log_breaker),
                        ("reload", self.reload_breaker),
                    )
                ),
            ),
        ]
        if self._batcher is not None:
            bs = self._batcher.stats
            rows.append(
                (
                    "Micro-batching",
                    f"{bs['batches']} batches, "
                    f"avg {bs['avg_batch']:.1f} queries/batch",
                )
            )
        cells = "".join(
            f"<tr><th>{html.escape(k)}</th><td>{html.escape(v)}</td></tr>"
            for k, v in rows
        )
        return (
            "<!DOCTYPE html><html><head><title>"
            f"{html.escape(dep.instance.engine_id)} - predictionio_tpu engine "
            "server</title></head><body>"
            "<h1>PredictionIO-TPU Engine Server</h1>"
            f"<table>{cells}</table>"
            "<p>POST JSON queries to <code>/queries.json</code>; "
            "<a href=\"/reload\">reload</a> latest model.</p>"
            "</body></html>"
        )


def create_query_server(
    engine: Engine,
    config: ServerConfig = ServerConfig(),
    registry: Optional[StorageRegistry] = None,
    block: bool = True,
) -> QueryServer:
    """Deploy an engine (``CreateServer.main``, ``CreateServer.scala:100-182``)."""
    from ..storage.registry import get_registry
    from .version_check import check_upgrade

    check_upgrade("deployment", type(engine).__name__)  # CreateServer.scala:246
    registry = registry or get_registry()
    server = QueryServer(config, engine, registry)
    logger.info(
        "Query server: engine instance %s on %s:%d",
        server.deployment.instance.id,
        config.ip,
        server.bound_port,
    )
    if block:
        try:
            server.serve_forever()
        finally:
            server.server_close()
    else:
        server.start_background()
    return server
