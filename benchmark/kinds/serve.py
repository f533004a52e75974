"""A serving cell: the real ``QueryServer`` with its default batching
over seeded factor tables, driven over localhost by ``lib/loadgen.py``.

The harness process holds the chip and runs the server; the generator is
a child that never touches JAX. Set-up ends, and the window starts, at
the moment the generator is told to start.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time
from typing import Dict

import numpy as np

from ..lib import manifest, reference, synth
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in, traced_window


def _pad_pow2(n: int, lo: int = 1) -> int:
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


def _control_topk(precision_name: str):
    """The control: the reference's own mathematics (score product, then
    the best k) put in the place of the program's top-k entry, computed
    at the precision below the one the configuration states."""
    import functools

    import jax
    import jax.numpy as jnp

    precision = getattr(jax.lax.Precision, precision_name)

    @functools.partial(jax.jit, static_argnames=("k", "mode"))
    def topk(user_factors, item_factors, user_idx, k, exclude_idx=None, mode="auto"):
        scores = jnp.einsum(
            "br,ir->bi", user_factors[user_idx], item_factors,
            preferred_element_type=jnp.float32, precision=precision,
        )
        return jax.lax.top_k(scores, k)

    return topk


def _deployment(config: Dict, tables, control: str):
    from predictionio_tpu.controller.dase import FirstServing
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.storage.metadata import STATUS_COMPLETED, EngineInstance
    from predictionio_tpu.workflow.serving import Deployment

    users, items = tables
    if control:
        rec.top_k_for_users_fused = _control_topk(control)
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(**config["algorithm"]))
    model = rec.ALSModel(
        rank=config["sizes"]["rank"], user_factors=users, item_factors=items,
        user_map=id_map("u", len(users)),
        item_map=id_map("i", len(items)),
    )
    now = datetime.datetime.now(datetime.timezone.utc)
    instance = EngineInstance(
        id="bench", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="bench", engine_version="1", engine_variant="engine.json",
        engine_factory="predictionio_tpu.models.recommendation.engine_factory",
    )
    dep = Deployment(
        instance=instance, engine_params=None, algorithms=[algo],
        models=[model], serving=FirstServing(),
    )
    return dep, algo, model, rec.Query


def _hist(srv, name: str) -> Dict[str, float]:
    snap = srv.metrics.instrument(name).snapshot()
    return {"sum": float(snap["sum"]), "count": float(snap["count"])}


def _mean_ms(after: Dict, before: Dict) -> float:
    n = after["count"] - before["count"]
    return (after["sum"] - before["sum"]) / n * 1e3 if n else float("nan")


class Served:
    """The server under test, warmed up, with everything a window needs."""

    def __init__(self, ctx):
        from predictionio_tpu.storage.registry import StorageRegistry
        from predictionio_tpu.workflow.serving import QueryServer, ServerConfig

        from predictionio_tpu.obs.profile import default_telemetry

        self.ctx = ctx
        # the generator gets the last core this process may use, to itself:
        # the server's threads (started from here on) and the generator
        # then never take turns on one core. Measured (PR 23, six runs
        # each): serve_qps spread 4.4-7.4 % -> 1.8 %, ML-20M p50 6-9 % -> 1.5 %
        cpus = sorted(os.sched_getaffinity(0))
        self.generator_cpu = None
        if len(cpus) >= 4:
            self.generator_cpu = cpus[-1]
            os.sched_setaffinity(0, cpus[:-1])
        cfg, traffic = ctx.config, ctx.workload["traffic_params"]
        self.sizes = cfg["sizes"]
        t_in = time.monotonic()
        self.tables = synth.factor_tables(self.sizes, ctx.seed)
        dep, algo, model, Query = _deployment(cfg, self.tables, ctx.control)
        if ctx.trace:
            algo.batch_predict = ctx.spans.wrap(
                "batch_predict", algo.batch_predict,
                label=lambda _model, queries: f" b={_pad_pow2(len(queries))}",
            )
        # the cell's own shapes: every padded batch the admission cap
        # allows, k padded to 16 (num 10); the path each resolves to is
        # the program's own report
        self.num, self.paths = traffic["num"], {}
        b = 1
        while b <= traffic["max_batch"]:
            algo.batch_predict(
                model, [(n, Query(user=f"u{n}", num=self.num)) for n in range(b)])
            self.paths[b] = algo.topk_path
            b *= 2
        store = os.path.join(manifest.WORK, "store")
        os.makedirs(store, exist_ok=True)
        self.srv = QueryServer(
            ServerConfig(ip="127.0.0.1", port=0), engine=None,
            registry=StorageRegistry(env={"PIO_FS_BASEDIR": store}), deployment=dep,
        )
        self.srv.start_background()
        cache = default_telemetry().snapshot()["cache"]
        self.setup_note = (
            f"{t_in - ctx.t0:.1f} s to reach the chip, {time.monotonic() - t_in:.1f} s "
            f"for tables, warm-up and server; compile cache {cache['hits']} hits, "
            f"{cache['misses']} misses")

    def _counters(self) -> Dict:
        from predictionio_tpu.obs.profile import default_telemetry

        return {
            "jit": default_telemetry().snapshot(),
            "batcher": dict(self.srv._batcher.stats),
            "server": _hist(self.srv, "pio_serving_request_seconds"),
            "queue": _hist(self.srv, "pio_batch_queue_wait_seconds"),
        }

    def window(self, traffic: Dict, seconds: float, seed: int, on_start=None) -> Dict:
        """One generator child, one window. Returns what was observed;
        ``start`` is the window's first instant on ``time.monotonic``."""
        from predictionio_tpu.obs.profile import default_telemetry

        mode = traffic["mode"]
        out = os.path.join(manifest.WORK, "loadgen")
        if mode == "open":
            offsets = synth.poisson_offsets(traffic["rate"], seconds, seed)
            np.save(out + ".offsets.npy", offsets)
            count = len(offsets)
        else:
            count = int(traffic["max_rate"] * seconds)
        np.save(out + ".users.npy", synth.zipf_users(
            self.sizes["n_users"], traffic["user_exponent"], count, seed))
        params = {
            "host": "127.0.0.1", "port": self.srv.bound_port, "mode": mode,
            "connections": traffic["connections"], "num": self.num,
            "seconds": seconds, "timeout_s": traffic["timeout_s"],
            "warm_s": traffic["warm_s"], "cpu": self.generator_cpu,
            "users": out + ".users.npy", "offsets": out + ".offsets.npy", "out": out,
        }
        with open(out + ".json", "w") as f:
            json.dump(params, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(manifest.ROOT, "lib", "loadgen.py"), out + ".json"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        try:
            if child.stdout.readline().strip() != "ready":
                raise RuntimeError("the load generator did not come up")
            before = self._counters()
            start = time.monotonic() + 0.2
            child.stdin.write(f"{start!r}\n")
            child.stdin.flush()
            cut = on_start(start) if on_start is not None else None
            child.wait(timeout=seconds + traffic["timeout_s"] + 60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if child.returncode != 0:
            raise RuntimeError(f"the load generator exited with {child.returncode}")
        # the layers' counters are read over the part of the window that
        # ran untraced (``cut``: where the traced slice began), so that
        # the profiler's own stalls are not booked to the server
        until = cut["until"] if cut else seconds
        after = cut["counters"] if cut else self._counters()
        got = np.load(out + ".npz")
        with open(out + ".bodies", "rb") as f:
            blob = f.read()
        status, due, sent, done = got["status"], got["due"], got["sent"], got["done"]
        ok = status == 200
        latency_ms = (done - due) * 1e3
        batches = after["batcher"]["batches"] - before["batcher"]["batches"]
        return {
            "start": start, "ok": ok, "due": due, "done": done,
            "users": got["users"], "body_offsets": got["body_offsets"], "blob": blob,
            "attempted": int(len(status)),
            "latency_ms": latency_ms.tolist(),
            # every answer to a request sent inside the window, over the
            # seconds to the last of them: counting only what is done at the
            # close moves by a whole batch (64 of 1,400 at 2.3 M items)
            # with the phase the close happens to cut
            "qps": float(np.sum(ok) / max(seconds, float(np.max(done)))),
            "lateness_ms": ((sent - due) * 1e3).tolist() if mode == "open" else [],
            "latency_counted_ms": latency_ms[due < until].tolist(),
            "client_mean_ms": float(np.mean(latency_ms[due < until])),
            "server_mean_ms": _mean_ms(after["server"], before["server"]),
            "queue_wait_mean_ms": _mean_ms(after["queue"], before["queue"]),
            "avg_batch": (
                (after["batcher"]["submitted"] - before["batcher"]["submitted"]) / batches
                if batches else float("nan")
            ),
            "counted_until": until,
            "window_compiles": compiles_in(default_telemetry().delta_since(before["jit"])),
        }

    def close(self) -> None:
        self.srv.shutdown()
        self.srv.server_close()


def lateness_line(win: Dict) -> str:
    late, lat = win["lateness_ms"], win["latency_ms"]
    head = (
        f"generator: {win['attempted']} requests, {int(win['ok'].sum())} answered 200, "
        "latency p50/p95/p99/max "
        + "/".join(f"{np.percentile(lat, q):.1f}" for q in (50, 95, 99, 100)) + " ms; ")
    if not late:
        return head + "closed loop, no schedule to be late for"
    return head + (
        f"ran late by p50 {np.percentile(late, 50):.3f} ms, "
        f"p99 {np.percentile(late, 99):.3f} ms, max {np.max(late):.3f} ms")


def run(ctx) -> Dict:
    cfg, traffic, seed = ctx.config, ctx.workload["traffic_params"], ctx.seed
    served = Served(ctx)
    marks = {}

    def on_start(start: float):
        marks["setup_s"] = start - ctx.t0
        ctx.say(f"set-up {marks['setup_s']:.1f} s: {served.setup_note}")
        if not ctx.trace:
            return None
        # the traced slice is the window's end: what comes before it is
        # measured untraced
        at = ctx.seconds - traffic["trace_seconds"] - 1.0
        time.sleep(max(0.0, start + at - time.monotonic()))
        cut = {"until": at, "counters": served._counters()}
        with traced_window(ctx.trace_dir):
            time.sleep(traffic["trace_seconds"])
        return cut

    try:
        win = served.window(traffic, ctx.seconds, seed, on_start)
    finally:
        served.close()
    ctx.say(lateness_line(win))
    num = served.num
    obs = {k: win[k] for k in (
        "attempted", "latency_ms", "latency_counted_ms", "qps", "lateness_ms", "client_mean_ms",
        "server_mean_ms", "queue_wait_mean_ms", "avg_batch", "window_compiles")}
    obs["setup_s"] = marks["setup_s"]
    obs["dispatch_ms"] = [
        d * 1e3 for d in ctx.spans.durations(
            "batch_predict", win["start"], win["start"] + win["counted_until"])]
    obs["topk"] = {
        "n_items": served.sizes["n_items"], "rank": served.sizes["rank"],
        "k": _pad_pow2(num, lo=8), "paths": served.paths,
    }

    # -- correct: a seeded sample of the window's answers against float64
    ok = win["ok"]
    finished = np.flatnonzero(ok)
    want = traffic["check_answers"]
    sample = np.sort(synth.rng_for(seed, "sample").choice(
        finished, size=min(want, len(finished)), replace=False))
    offs, blob = win["body_offsets"], win["blob"]
    items = np.zeros((len(sample), num), np.int64)
    scores = np.full((len(sample), num), np.inf)
    for row, n in enumerate(sample):
        try:
            answer = json.loads(blob[offs[n]:offs[n + 1]])["itemScores"]
            items[row] = [int(s["item"][1:]) for s in answer]
            scores[row] = [s["score"] for s in answer]
        except (ValueError, KeyError, TypeError):
            pass  # a malformed answer keeps its infinite score error
    readings = reference.topk_gaps(
        served.tables[0], served.tables[1], win["users"][sample], items, scores)
    limits = cfg["limits"]["serve"]
    wrong = int(np.sum(
        (readings.pop("rank_gap_each") > limits["rank_gap"])
        | (readings.pop("score_err_each") > limits["score_err"])))
    readings["window_compiles"] = float(obs["window_compiles"])
    readings["answers_short"] = float(want - len(sample))
    obs["readings"] = readings
    obs["failed"] = int(np.sum(~ok)) + wrong
    obs["verdict"] = reference.verdict(
        readings, {**limits, "window_compiles": 0.0, "answers_short": 0.0})
    return obs
