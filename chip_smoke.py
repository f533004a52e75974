#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the normal path once, through the entry points a user calls, at
the full width of the model this repo is built around: the recommendation
template, explicit ALS, rank 50, 10 iterations, 138,493 users x 26,744
items (MovieLens-20M's widths). Ratings come from ``--seed`` with
power-law degrees (the recipe of ``bench.synth_ml20m``), users and items
held at full width; only the NUMBER of ratings is cut, because ``pio
import`` is host work that proves nothing about the chip.

One chip (the default, what the driver runs)::

    python chip_smoke.py

    device   a child asks JAX what it has; anything but a TPU stops here
    import   bin/pio app new / template get / import
    train    bin/pio train: platform tpu, solve_mode pallas, fused_gather
             true, finite factors, train RMSE below the ratings' std
    serve    bin/pio deploy --spawn; POST /queries.json (8 single users
             and one burst of 32); every answer's item ids equal a plain
             numpy argsort over the factors read back from the model store
    undeploy bin/pio undeploy; the server process is gone, the chip free

Four chips (``--chips 4``, the builder runs it; nothing else runs)::

    device, import, then bin/pio train --shards 1 and --shards 4 on the
    same import, 2 iterations each, f32 matmuls: factors allclose, RMSE
    equal, and the sharded tables on 4 distinct devices

Every phase is a child process; this parent never initializes a JAX
backend that could hold the chip (it pins itself to the CPU before it
reads the model store). Any phase that fails ends the run non-zero at
once. The last line of stdout is the result object and is printed only
when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
PIO = os.path.join(REPO, "bin", "pio")
#: Fresh on every run, at a fixed path under the checkout (``.gitignore``
#: lists it): store, engine dir, generated events and child logs.
WORKDIR = os.path.join(REPO, ".chip_smoke")

#: The contract gives the whole script 1200 s, compilation included.
TIME_LIMIT_S = 1150.0

ML20M_RATINGS = 20_000_263


@dataclasses.dataclass(frozen=True)
class Workload:
    n_users: int = 138_493
    n_items: int = 26_744
    #: the one cut. ``pio import`` is host work (parse, validate, commit):
    #: on the chip machine it took 527 s for 2 M events (PR 22), which
    #: proves nothing about the chip and would eat half the time limit.
    n_ratings: int = 1_000_000
    rank: int = 50
    iterations: int = 10
    #: single-user queries, then one concurrent burst
    single_queries: int = 8
    burst: int = 32
    num: int = 10


@dataclasses.dataclass(frozen=True)
class Expect:
    """What the train child must report. The chip's values are the
    defaults; the CPU rehearsal in tests passes its own."""

    platform: str = "tpu"
    solve_mode: str = "pallas"
    fused_gather: bool = True


class SmokeFailure(Exception):
    """A phase did not meet its check."""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Run:
    """One smoke run: the workload, the children's environment, and what
    earlier phases learned for later ones."""

    def __init__(self, workload: Workload, expect: Expect, chips: int,
                 seed: int):
        self.w = workload
        self.expect = expect
        self.chips = chips
        self.seed = seed
        self.deadline = time.monotonic() + TIME_LIMIT_S
        self.engine_dir = os.path.join(WORKDIR, "engine")
        self.events_path = os.path.join(WORKDIR, "events.jsonl")
        #: children inherit the caller's environment (so the caller's
        #: platform choice reaches them) plus the fresh store
        self.env = dict(os.environ, PIO_FS_BASEDIR=os.path.join(WORKDIR, "store"))
        self.device: Dict[str, object] = {}
        self.app_id = 0
        self.ratings: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        self.model = None  # the ALSModel read back after phase_train
        self.factors: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.server_pid: Optional[int] = None
        self.port = 0

    # -- children ----------------------------------------------------------
    def child(self, name: str, argv: Sequence[str], timeout_s: float = 900.0,
              cwd: Optional[str] = None,
              env: Optional[Dict[str, str]] = None) -> str:
        """Run one child to completion; returns its stdout. Its stderr
        goes to ``<workdir>/logs/<name>.log``; a non-zero exit or a
        timeout fails the phase with the log's tail. ``env`` adds to the
        run's environment for this child only."""
        log_path = os.path.join(WORKDIR, "logs", f"{name}.log")
        remaining = self.deadline - time.monotonic()
        if remaining <= 0:
            raise SmokeFailure(f"{name}: the run's {TIME_LIMIT_S:.0f}s are spent")
        t0 = time.monotonic()
        with open(log_path, "wb") as log:
            try:
                proc = subprocess.run(
                    list(argv), env=dict(self.env, **(env or {})),
                    cwd=cwd or WORKDIR,
                    stdout=subprocess.PIPE, stderr=log,
                    timeout=min(timeout_s, remaining),
                )
            except subprocess.TimeoutExpired:
                raise SmokeFailure(
                    f"{name}: timed out after {time.monotonic() - t0:.0f}s\n"
                    + _tail(log_path)
                ) from None
        out = proc.stdout.decode("utf-8", "replace")
        if proc.returncode != 0:
            raise SmokeFailure(
                f"{name}: exit code {proc.returncode}\n{out[-2000:]}\n"
                + _tail(log_path)
            )
        say(f"{name}: ok in {time.monotonic() - t0:.1f}s")
        return out

    def pio(self, name: str, *args: str, **kw) -> dict:
        """``bin/pio <args>``; its stdout is one JSON document."""
        out = self.child(name, [PIO, *args], **kw)
        try:
            return json.loads(out[out.index("{"):])
        except ValueError:
            raise SmokeFailure(f"{name}: no JSON on stdout: {out[-500:]!r}") from None


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError:
        return "(no log)"


# -- data ---------------------------------------------------------------------


def synth_ratings(w: Workload, seed: int):
    """ML-20M-shaped ratings at FULL width: power-law user/item degrees,
    rank-8 ground truth, sd-0.5 noise (``bench.synth_ml20m``'s recipe),
    with every user and every item rated at least once so the trained
    tables have exactly ``n_users`` x ``n_items`` rows whatever the cut
    in the number of ratings."""
    if w.n_ratings < w.n_users or w.n_users < w.n_items:
        raise ValueError("need n_ratings >= n_users >= n_items")
    rng = np.random.default_rng(seed)
    extra = w.n_ratings - w.n_users
    u_w = 1.0 / np.arange(1, w.n_users + 1) ** 0.8
    i_w = 1.0 / np.arange(1, w.n_items + 1) ** 0.9
    users = np.concatenate([
        np.arange(w.n_users),
        rng.choice(w.n_users, size=extra, p=u_w / u_w.sum()),
    ])
    items = np.concatenate([
        rng.permutation(w.n_users) % w.n_items,  # hits every item
        rng.choice(w.n_items, size=extra, p=i_w / i_w.sum()),
    ])
    gt_rank = 8
    x = rng.normal(size=(w.n_users, gt_rank)) / np.sqrt(gt_rank)
    y = rng.normal(size=(w.n_items, gt_rank)) / np.sqrt(gt_rank)
    ratings = (
        (x[users] * y[items]).sum(axis=1) + 3.5
        + rng.normal(0, 0.5, w.n_ratings)
    ).astype(np.float32)
    order = rng.permutation(w.n_ratings)
    return users[order], items[order], ratings[order]


def write_events(path: str, users, items, ratings) -> None:
    """One ``rate`` event per rating, in ``pio import``'s JSON-lines form."""
    line = (
        '{{"event":"rate","entityType":"user","entityId":"u{}",'
        '"targetEntityType":"item","targetEntityId":"i{}",'
        '"properties":{{"rating":{:.4f}}}}}\n'
    ).format
    with open(path, "w") as f:
        step = 100_000
        for lo in range(0, len(ratings), step):
            hi = lo + step
            f.write("".join(map(
                line, users[lo:hi].tolist(), items[lo:hi].tolist(),
                ratings[lo:hi].tolist(),
            )))


# -- reading the model store (parent, CPU-pinned) -------------------------------


def load_model(run: Run, instance_id: str):
    """The trained ALS model read back from the model store. Pins THIS
    process to the CPU backend first: nothing the parent imports may
    take the chip from a child."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from predictionio_tpu.storage import StorageRegistry
    from predictionio_tpu.workflow.core_workflow import load_models

    return load_models(StorageRegistry(env=run.env), instance_id)[0]


def model_rmse(model, users, items, ratings, sample: int = 200_000) -> float:
    """RMSE of the factors on (a fixed sample of) the ratings, in numpy."""
    pick = np.random.default_rng(1).permutation(len(ratings))[:sample]
    rows = np.asarray(
        [model.user_map[f"u{u}"] for u in users[pick].tolist()], np.int64)
    cols = np.asarray(
        [model.item_map[f"i{i}"] for i in items[pick].tolist()], np.int64)
    pred = (
        np.asarray(model.user_factors)[rows]
        * np.asarray(model.item_factors)[cols]
    ).sum(axis=1)
    return float(np.sqrt(np.mean((pred - ratings[pick]) ** 2)))


def check_levers(run: Run, name: str, line: dict, solve_mode: str,
                 fused_gather: bool, shards: int) -> None:
    device = line.get("device") or {}
    levers = (line.get("levers") or {}).get("als") or {}
    say(f"{name}: device {json.dumps(device)}")
    say(f"{name}: solve_mode: {levers.get('solve_mode')}")
    say(f"{name}: fused_gather: {json.dumps(levers.get('fused_gather'))}")
    say(f"{name}: shards: {levers.get('shards')}")
    cache = line.get("compileCache") or {}
    say(f"{name}: compile cache {cache.get('dir')} "
        f"hits={cache.get('hits')} misses={cache.get('misses')}")
    if device.get("platform") != run.expect.platform:
        raise SmokeFailure(
            f"{name} ran on platform {device.get('platform')!r}, "
            f"not {run.expect.platform!r}")
    got = (levers.get("solve_mode"), levers.get("fused_gather"),
           levers.get("shards"))
    if got != (solve_mode, fused_gather, shards):
        raise SmokeFailure(
            f"{name} resolved (solve_mode, fused_gather, shards) = {got}, "
            f"expected {(solve_mode, fused_gather, shards)}")


# -- phases ---------------------------------------------------------------------

#: the same ``device`` object ``pio train`` and ``/status.json`` report
_DEVICE_SNIPPET = (
    "import json; from predictionio_tpu.utils.platform import device_info; "
    "print(json.dumps(device_info()))"
)


def phase_device(run: Run) -> None:
    """Ask JAX, in a child that exits (and frees the chip), what it has."""
    out = run.child("device", [sys.executable, "-c", _DEVICE_SNIPPET],
                    timeout_s=300.0, cwd=REPO)
    run.device = json.loads(out.strip().splitlines()[-1])
    say(f"device: {json.dumps(run.device)}")
    if run.device["platform"] != run.expect.platform:
        raise SmokeFailure(
            f"JAX found platform {run.device['platform']!r} "
            f"({run.device['kind']}, {run.device['count']} device(s)); "
            f"this smoke needs {run.expect.platform!r}")
    if run.device["count"] < run.chips:
        raise SmokeFailure(
            f"--chips {run.chips} needs {run.chips} devices, "
            f"JAX found {run.device['count']}")


def phase_import(run: Run) -> None:
    w = run.w
    say(f"model: recommendation template, explicit ALS, rank {w.rank}, "
        f"{w.n_users} users x {w.n_items} items")
    say(f"reduced: ratings {w.n_ratings}/{ML20M_RATINGS} (import rate)")
    t0 = time.monotonic()
    run.ratings = synth_ratings(w, run.seed)
    write_events(run.events_path, *run.ratings)
    say(f"generated {w.n_ratings} ratings from seed {run.seed} "
        f"in {time.monotonic() - t0:.1f}s (std {run.ratings[2].std():.4f})")
    app = run.pio("app-new", "app", "new", "chipsmoke")
    run.app_id = int(app["id"])
    run.pio("template-get", "template", "get", "recommendation",
            run.engine_dir)
    imported = run.pio("import", "import", "--appid", str(run.app_id),
                       "--input", run.events_path)
    if imported.get("events") != w.n_ratings:
        raise SmokeFailure(f"import took {imported.get('events')} events, "
                           f"not {w.n_ratings}")


def write_engine_json(run: Run, iterations: int, **levers) -> None:
    path = os.path.join(run.engine_dir, "engine.json")
    with open(path) as f:
        variant = json.load(f)
    variant["datasource"]["params"]["app_id"] = run.app_id
    variant["algorithms"][0]["params"].update(
        rank=run.w.rank, num_iterations=iterations, lambda_=0.05, seed=0,
        **levers)
    with open(path, "w") as f:
        json.dump(variant, f, indent=2)


def train_and_load(run: Run, name: str, *args: str, env=None):
    """``bin/pio train <args>`` → (result line, model read back)."""
    line = run.pio(name, "train", *args, cwd=run.engine_dir, env=env)
    model = load_model(run, line["engineInstanceId"])
    users, items = (np.asarray(model.user_factors),
                    np.asarray(model.item_factors))
    want = ((run.w.n_users, run.w.rank), (run.w.n_items, run.w.rank))
    if (users.shape, items.shape) != want:
        raise SmokeFailure(
            f"{name}: factor shapes {users.shape} / {items.shape}, "
            f"expected {want}")
    if not (np.isfinite(users).all() and np.isfinite(items).all()):
        raise SmokeFailure(f"{name}: factors are not finite")
    return line, model


def phase_train(run: Run) -> None:
    write_engine_json(run, run.w.iterations)
    say(f"train: rank {run.w.rank}, {run.w.iterations} iterations")
    line, model = train_and_load(run, "train")
    check_levers(run, "train", line, run.expect.solve_mode,
                 run.expect.fused_gather, shards=1)
    rmse = model_rmse(model, *run.ratings)
    std = float(run.ratings[2].std())
    say(f"train: factors finite, shapes "
        f"{np.asarray(model.user_factors).shape} "
        f"{np.asarray(model.item_factors).shape}; "
        f"train RMSE {rmse:.4f} (ratings std {std:.4f})")
    if not rmse < std:
        raise SmokeFailure(f"train RMSE {rmse} is not below the ratings' "
                           f"std {std}")
    run.factors = (np.asarray(model.user_factors, np.float32),
                   np.asarray(model.item_factors, np.float32))
    run.model = model


def _http(method: str, url: str, body: Optional[dict] = None,
          timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode("utf-8", "replace")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def check_answer(run: Run, user: int, answer: dict) -> float:
    """Served item ids == numpy ``argsort(-(U[u] @ I.T))[:num]``; a
    position may differ only where the two items' numpy scores tie within
    1e-4. Returns the largest |served score - numpy score|."""
    model, (uf, itf) = run.model, run.factors
    scores = uf[model.user_map[f"u{user}"]] @ itf.T
    expected = np.argsort(-scores, kind="stable")[: run.w.num]
    served = answer.get("itemScores") or []
    if len(served) != run.w.num:
        raise SmokeFailure(
            f"user u{user}: {len(served)} items served, not {run.w.num}")
    rows = [model.item_map[s["item"]] for s in served]
    if len(set(rows)) != len(rows):
        raise SmokeFailure(f"user u{user}: an item was served twice")
    gap = np.abs(scores[rows] - scores[expected])
    if rows != expected.tolist() and gap.max() > 1e-4:
        raise SmokeFailure(
            f"user u{user}: served {rows} != numpy {expected.tolist()} "
            f"(numpy scores differ by up to {gap.max():.3g} by position)")
    return float(np.max(np.abs(
        np.asarray([s["score"] for s in served]) - scores[rows])))


def phase_serve(run: Run) -> None:
    w = run.w
    run.port = _free_port()
    spawned = run.pio("deploy", "deploy", "--port", str(run.port),
                      "--spawn", cwd=run.engine_dir)
    run.server_pid = int(spawned["pid"])
    base = f"http://127.0.0.1:{run.port}"
    t0 = time.monotonic()
    while True:
        try:
            _http("GET", base + "/status.json", timeout=5.0)
            break
        except OSError:
            if not _alive(run.server_pid):
                raise SmokeFailure(
                    "the query server died:\n" + _tail(spawned["log"]))
            if time.monotonic() - t0 > 300 or time.monotonic() > run.deadline:
                raise SmokeFailure(
                    "the query server did not answer in "
                    f"{time.monotonic() - t0:.0f}s:\n" + _tail(spawned["log"]))
            time.sleep(0.5)
    say(f"deploy: server pid {run.server_pid} answers on :{run.port} "
        f"after {time.monotonic() - t0:.1f}s")

    rng = np.random.default_rng(run.seed + 1)
    users = rng.choice(w.n_users, size=w.single_queries + w.burst,
                       replace=False).tolist()
    latencies: List[float] = []
    worst = 0.0

    def ask(user: int) -> Tuple[int, dict, float]:
        t = time.monotonic()
        body = _http("POST", base + "/queries.json",
                     {"user": f"u{user}", "num": w.num})
        return user, json.loads(body), time.monotonic() - t

    for user in users[: w.single_queries]:
        user, answer, dt = ask(user)
        latencies.append(dt)
        worst = max(worst, check_answer(run, user, answer))
    results: List[Optional[Tuple[int, dict, float]]] = [None] * w.burst
    errors: List[BaseException] = []

    def worker(slot: int, user: int) -> None:
        try:
            results[slot] = ask(user)
        except BaseException as exc:  # reported below, on the main thread
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(slot, user))
        for slot, user in enumerate(users[w.single_queries:])
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if errors or any(r is None for r in results):
        raise SmokeFailure(f"burst of {w.burst}: {len(errors)} errors, "
                           f"first: {errors[:1]}")
    for user, answer, _ in results:
        worst = max(worst, check_answer(run, user, answer))
    n = w.single_queries + w.burst
    say(f"serve: {n}/{n} answers equal numpy argsort(-(U[u] @ I.T))[:{w.num}] "
        f"({w.single_queries} single, one burst of {w.burst}); "
        f"max |served score - numpy score| {worst:.3g}")

    status = json.loads(_http("GET", base + "/status.json"))
    say(f"serve: /status.json device {json.dumps(status.get('device'))}")
    say(f"serve: topkPath {json.dumps(status.get('topkPath'))}, "
        f"batching {json.dumps(status.get('batching'))}")
    lat = sorted(latencies[1:]) or latencies  # first request compiles
    say(f"serve: first request {latencies[0] * 1e3:.0f} ms, p50 of the "
        f"next {len(lat)} single requests {lat[len(lat) // 2] * 1e3:.1f} ms "
        "(information, not a metric)")
    metrics = _http("GET", base + "/metrics")
    counts = {
        k: float(m.group(1)) for k in ("hits", "misses")
        if (m := re.search(rf"^pio_jit_cache_{k} (\S+)", metrics, re.M))
    }
    say(f"serve: compile cache hits={counts.get('hits')} "
        f"misses={counts.get('misses')}")
    if (status.get("device") or {}).get("platform") != run.expect.platform:
        raise SmokeFailure(
            f"/status.json reports device {status.get('device')}, "
            f"not platform {run.expect.platform!r}")


def _alive(pid: int) -> bool:
    """Is ``pid`` a running (not zombie) process?"""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


def phase_undeploy(run: Run) -> None:
    run.pio("undeploy", "undeploy", "--port", str(run.port))
    t0 = time.monotonic()
    while _alive(run.server_pid):
        if time.monotonic() - t0 > 60:
            raise SmokeFailure(
                f"server pid {run.server_pid} still alive 60s after undeploy")
        time.sleep(0.2)
    say(f"undeploy: server pid {run.server_pid} is gone after "
        f"{time.monotonic() - t0:.1f}s; the chip is free")
    run.server_pid = None


_SHARD_LOG = re.compile(r"sharded ALS tables: (\{.*\})")


def phase_sharded_compare(run: Run) -> None:
    """``pio train --shards N`` against ``--shards 1`` on the same import:
    the tolerances ``tests/test_sharded_train.py`` pins for shard-count
    equivalence, at 2 iterations (drift grows with iteration count).

    Those tolerances are f32 reassociation tolerances, and the tests run
    where every matmul is f32 and ``auto`` is the einsum build for both
    shard counts. So both children here get that math: ``solve_mode:
    chunked`` (the sharded trainer's own build) and
    ``JAX_DEFAULT_MATMUL_PRECISION=highest`` (JAX's own variable). At
    the TPU's default precision f32 matmul inputs are rounded to bf16
    passes, differently in every program: measured on the v5e (PR 22)
    after 2 iterations, pallas against einsum build differ by up to 0.31,
    and the single-device trainer against the sharded loop on ONE device
    by up to 0.098 — at ``highest``, by 4.5e-5. That is solver precision
    and says nothing about sharding.

    ``atol`` is 2e-3 here, not the tests' 1e-4: theirs is pinned at rank 8
    with 25 ratings a row. At rank 50 with a few ratings for most users
    the ridge alone determines most of a row, and f32 reassociation
    between shard counts reaches 1.0e-3 — measured on the CPU backend
    (pure f32, 4 virtual devices) at exactly these sizes before the
    four-chip run, where 0.25 % of user entries and 0.006 % of item
    entries were outside atol 1e-4. The share outside the tests'
    tolerance is printed; RMSE keeps its 1e-3."""
    rtol, atol = 1e-3, 2e-3
    iterations = 2
    f32_matmuls = {"JAX_DEFAULT_MATMUL_PRECISION": "highest"}
    write_engine_json(run, iterations, solve_mode="chunked")
    n = run.chips
    say(f"sharded: rank {run.w.rank}, {iterations} iterations, "
        f"solve_mode chunked, {json.dumps(f32_matmuls)}, "
        f"--shards 1 against --shards {n}")
    line_1, model_1 = train_and_load(run, "train-shards-1", "--shards", "1",
                                     env=f32_matmuls)
    check_levers(run, "train-shards-1", line_1, "chunked", False, shards=1)
    line_n, model_n = train_and_load(run, f"train-shards-{n}", "--shards",
                                     str(n), env=f32_matmuls)
    check_levers(run, f"train-shards-{n}", line_n, "chunked", False,
                 shards=n)
    placed = _SHARD_LOG.findall(
        _tail(os.path.join(WORKDIR, "logs", f"train-shards-{n}.log"), 10**6))
    if not placed:
        raise SmokeFailure("the sharded trainer did not log where its "
                           "tables sit")
    placement = json.loads(placed[-1])
    say(f"sharded: tables {json.dumps(placement)}")
    for side in ("user", "item"):
        if len(set(placement[side])) != n:
            raise SmokeFailure(
                f"{side} table's shards sit on devices {placement[side]}, "
                f"not on {n} distinct devices")
    for side, a, b in (
        ("user", model_1.user_factors, model_n.user_factors),
        ("item", model_1.item_factors, model_n.item_factors),
    ):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        diff = np.abs(a - b)
        outside_tests = (diff > 1e-4 + 1e-3 * np.abs(b)).mean()
        outside = diff > atol + rtol * np.abs(b)
        say(f"sharded: {side} factors max |1-shard - {n}-shard| "
            f"{diff.max():.3g}; outside rtol {rtol:g} / atol {atol:g}: "
            f"{outside.sum()} of {outside.size}; outside the tests' "
            f"rtol 1e-3 / atol 1e-4: {outside_tests:.4%}")
        if outside.any():
            raise SmokeFailure(
                f"{side} factors differ beyond rtol {rtol:g} / atol "
                f"{atol:g} at {outside.sum()} of {outside.size} entries")
    rmse_1 = model_rmse(model_1, *run.ratings)
    rmse_n = model_rmse(model_n, *run.ratings)
    say(f"sharded: train RMSE 1 shard {rmse_1:.6f}, {n} shards {rmse_n:.6f}")
    if abs(rmse_1 - rmse_n) > 1e-3:
        raise SmokeFailure(
            f"RMSE differs by {abs(rmse_1 - rmse_n):.3g} (> 1e-3)")


Phase = Callable[[Run], None]
PHASES_ONE_CHIP: Tuple[Phase, ...] = (
    phase_device, phase_import, phase_train, phase_serve, phase_undeploy)
PHASES_SHARDED: Tuple[Phase, ...] = (
    phase_device, phase_import, phase_sharded_compare)


def run_smoke(workload: Workload = Workload(), expect: Expect = Expect(),
              chips: int = 1, seed: int = 0) -> dict:
    """Run every phase for ``chips``; returns the result object. Raises
    :class:`SmokeFailure` from the first phase that fails. Whatever
    happens, no server is left running."""
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(os.path.join(WORKDIR, "logs"))
    run = Run(workload, expect, chips, seed)
    t0 = time.monotonic()
    try:
        for phase in PHASES_ONE_CHIP if chips == 1 else PHASES_SHARDED:
            phase(run)
    finally:
        if run.server_pid is not None and _alive(run.server_pid):
            os.kill(run.server_pid, 9)
    say(f"all phases passed in {time.monotonic() - t0:.1f}s")
    return {"ok": True, "device": run.device}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="Drive pio import -> train -> deploy -> query once on "
                    "the chip at ML-20M widths, rank 50, and check the "
                    "answers. Exits non-zero unless JAX finds a TPU.")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1 (default): the whole path on one chip. 4: only "
                        "pio train --shards 4 against --shards 1")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the generated ratings (default 0)")
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if not os.path.isfile(PIO):
        print(f"chip_smoke: {PIO} is missing — run this from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    try:
        result = run_smoke(chips=args.chips, seed=args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
