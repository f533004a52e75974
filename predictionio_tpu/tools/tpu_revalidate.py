#!/usr/bin/env python3
"""One-shot TPU re-validation: the queued round-3 A/B matrix.

A queue of on-chip steps, each its own subprocess with its own timeout,
that runs unattended and lands in one JSON-lines file:

1. ``python bench.py`` — full-scale ALS baseline (expect ≤ 18.3 s),
   repeated ``--repeats`` times (default 3) for run-to-run spread — the
   previous last-good number was a single leg with compile in iter 1.
2. Compiled-path unknowns, cheapest first (``_reval_steps``): the fused
   gather+Gramian kernel and the shard_map-wrapped pallas solve have
   only ever run in interpret mode; a 1-device mesh on the real chip
   closes the Mosaic-lowering question without multi-chip hardware.
   Plus the pure device-dispatch serving cycle at big-catalog shapes.
3. ``BENCH_GATHER_DTYPE=bf16`` — halved gather bytes; RMSE-gated.
4. ``BENCH_SORT_GATHER=1`` — gather-locality sort; RMSE-gated.
5. bf16 + sort combined (only if both individually pass the gate).
6. ``BENCH_FUSED_GATHER=1`` — the fused-kernel A/B (only if the smoke
   step passed); RMSE-gated like the others.
7. With ``--engine-dir <trained engine project>``: serving loadgen over
   pipeline depth 1/2/4/8 — HTTP (deploys on the chip per depth) AND
   in-process (isolates the stack from the wire). Without the flag the
   sweep is skipped with instructions.

Each step appends its JSON line (plus a ``step`` key) to
``TPU_REVALIDATION.jsonl``. A step that times out is recorded and the
remaining independent steps still run; completed steps are always on
disk. RMSE gate: within +0.002 of the f32 baseline's holdout RMSE.

Usage:
``python -m predictionio_tpu.tools.tpu_revalidate [--engine-dir D]``

Tiering: ``--tier a`` runs only the headline records — one f32 baseline
plus the two never-compiled-kernel verdicts, ≤5 min of device time.
``--tier b`` runs everything else, reusing tier-A records younger than
6 h from the evidence file instead of re-spending device time.
``--tier all`` (default) runs both inline.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(REPO, "TPU_REVALIDATION.jsonl")
RMSE_GATE_DELTA = 0.002


def log(msg: str) -> None:
    print(f"[revalidate +{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr)


def append(record: dict) -> None:
    record.setdefault("t_unix", round(time.time(), 1))
    with open(OUT, "a") as f:
        f.write(json.dumps(record) + "\n")


def _recent(step: str, max_age_s: float = 6 * 3600.0) -> dict | None:
    """Newest record for ``step`` in OUT if it was written in the last
    ``max_age_s`` seconds — how tier B reuses tier A's records instead of
    re-spending device time on them. Unstamped (pre-tier) records never
    qualify, and neither do CPU-sourced ones: a stray CPU-env invocation
    must not become the RMSE gate — or stand in for Mosaic validation —
    of a TPU run."""
    try:
        with open(OUT) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return None
    for line in reversed(lines):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("step") == step:
            t = rec.get("t_unix")
            if t is None or time.time() - float(t) > max_age_s:
                return None
            dev = f"{rec.get('device', '')} {rec.get('backend', '')}"
            if "cpu" in dev.lower():
                return None
            return rec
    return None


def _on_tpu(rec: dict) -> bool:
    """Did bench.py measure this record on a TPU? (Records that predate
    the ``platform`` field carry none and are taken at their word.)"""
    return rec.get("platform", "tpu") == "tpu"


def run_bench(step: str, env_extra: dict, timeout_s: float = 1800) -> dict:
    env = dict(os.environ, **env_extra)
    log(f"bench step {step}: {env_extra or '(baseline)'}")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        # a step past its timeout must not kill the chain: record it
        # and let the remaining independent steps try
        rec = {
            "step": step, "rc": -1,
            "error": f"bench timed out after {timeout_s:.0f}s",
        }
        append(rec)
        log(f"  -> TIMEOUT after {timeout_s:.0f}s; continuing the queue")
        return rec
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    try:
        rec = json.loads(lines[-1]) if lines else {"error": "no JSON line"}
    except ValueError:
        rec = {"error": f"malformed JSON line: {lines[-1][:120]!r}"}
    rec["step"] = step
    rec["rc"] = proc.returncode
    if not _on_tpu(rec):
        rec["note"] = "NOT MEASURED ON A TPU — evidence invalid for this step"
    append(rec)
    log(f"  -> value={rec.get('value')} rmse={rec.get('holdout_rmse')} "
        f"device={rec.get('device')}")
    return rec


def run_step(step: str, timeout_s: float = 900,
             env_extra: dict | None = None) -> dict:
    """Run one ``_reval_steps`` subcommand in a subprocess (a step past
    its timeout must be a recorded timeout, not a dead queue).
    ``env_extra`` overlays the inherited environment — how the
    implicit-quality gate receives the lever flags under test."""
    log(f"device step {step}" + (f" env={env_extra}" if env_extra else ""))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools._reval_steps",
             step],
            cwd=REPO, capture_output=True, text=True, timeout=timeout_s,
            env=dict(os.environ, **env_extra) if env_extra else None,
        )
    except subprocess.TimeoutExpired:
        rec = {"step": step, "rc": -1,
               "error": f"timed out after {timeout_s:.0f}s"}
        append(rec)
        log(f"  -> TIMEOUT after {timeout_s:.0f}s; continuing the queue")
        return rec
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    rec = None
    if lines:
        try:
            rec = json.loads(lines[-1])
        except ValueError:
            rec = {"error": f"malformed JSON line: {lines[-1][:120]!r}"}
    if rec is None:
        tail = proc.stderr.strip().splitlines()
        rec = {"error": tail[-1] if tail else "no JSON line"}
    # one name per logical step regardless of outcome (the inner record's
    # own step name, if any, is preserved under inner_step)
    if rec.get("step") not in (None, step):
        rec["inner_step"] = rec["step"]
    rec["step"] = step
    rec["rc"] = proc.returncode
    append(rec)
    log(f"  -> {json.dumps({k: v for k, v in rec.items() if k != 'step'})[:200]}")
    return rec


def _engine_env(engine_dir: str) -> dict:
    """Environment for deploy/loadgen children of ``engine_dir``.

    The quickstart/big-engine recipe keeps each demo's storage in a
    ``storage/`` sibling of the engine project
    (``examples/movielens_quickstart/run.sh`` exports
    ``PIO_FS_BASEDIR=$WORK/storage``). The queue inherits neither shell,
    so without this the deploys come up against the DEFAULT store and die
    with "No completed engine instance" — discovered by the round-5
    end-to-end drive, which is exactly how every loadgen sweep would have
    failed on hardware day. An explicit PIO_FS_BASEDIR in the caller's
    environment still wins."""
    env = dict(os.environ)
    storage = os.path.join(
        os.path.dirname(os.path.abspath(engine_dir)), "storage"
    )
    if "PIO_FS_BASEDIR" not in os.environ and os.path.isdir(storage):
        env["PIO_FS_BASEDIR"] = storage
    return env


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_inprocess_sweep(engine_dir: str, duration_s: float,
                        concurrency: int, tag: str = "") -> list:
    """In-process loadgen at each pipeline depth: the serving stack's own
    ceiling (micro-batcher + device dispatch) with the HTTP wire removed —
    one subprocess per depth so the device state is fresh each time.
    Returns the step names that errored (for the exit-code roll-up)."""
    failed = []
    env = _engine_env(engine_dir)
    for depth in (1, 2, 4, 8):
        step = f"loadgen_inproc_depth{depth}{tag}"
        log(f"in-process loadgen: depth={depth}")
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "predictionio_tpu.tools.loadgen",
                 "--in-process", "--engine-dir", engine_dir,
                 "--pipeline-depth", str(depth),
                 "--concurrency", str(concurrency),
                 "--duration", str(duration_s)],
                cwd=REPO, capture_output=True, text=True, timeout=600,
                env=env,
            )
        except subprocess.TimeoutExpired:
            append({"step": step,
                    "error": "timed out"})
            failed.append(step)
            continue
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        rec = None
        if lines:
            try:
                rec = json.loads(lines[-1])
            except ValueError:
                rec = {"error": f"malformed JSON: {lines[-1][:120]!r}"}
        if rec is None:
            tail = proc.stderr.strip().splitlines()
            rec = {"error": tail[-1] if tail else "no JSON"}
        rec["step"] = step
        rec["rc"] = proc.returncode
        append(rec)
        if proc.returncode != 0 or "error" in rec:
            failed.append(step)
        log(f"  -> depth {depth}: qps={rec.get('qps')} "
            f"p99={rec.get('p99_ms')}ms errors={rec.get('errors')}")
    return failed


def run_loadgen_sweep(engine_dir: str, duration_s: float,
                      concurrency: int, tag: str = "") -> list:
    """Deploy the engine at each pipeline depth, hammer it, undeploy.
    Returns the step names that errored (for the exit-code roll-up)."""
    import urllib.request

    failed = []
    env = _engine_env(engine_dir)
    pio = os.path.join(REPO, "bin", "pio")
    for depth in (1, 2, 4, 8):
        step = f"loadgen_depth{depth}{tag}"
        port = _free_port()
        log(f"loadgen sweep: deploying depth={depth} on :{port}")
        rc = subprocess.run(
            [pio, "deploy", "--engine-dir", engine_dir,
             "--port", str(port), "--batch-pipeline-depth", str(depth),
             "--spawn"],
            cwd=engine_dir, capture_output=True, text=True, env=env,
        ).returncode
        if rc != 0:
            append({"step": step, "error": f"deploy failed rc={rc}"})
            failed.append(step)
            continue
        up = False
        for _ in range(60):
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/", timeout=2
                ).read()
                up = True
                break
            except Exception:
                # pio: lint-ok[robust-bare-sleep-retry] readiness poll of a local spawn at a fixed 1 s cadence (60 s budget); one waiter, so jitter has nothing to spread
                time.sleep(1)
        try:
            if not up:
                append({"step": step, "error": "server never came up"})
                failed.append(step)
                continue
            time.sleep(3)  # let the first-query compile settle
            proc = subprocess.run(
                [sys.executable, "-m", "predictionio_tpu.tools.loadgen",
                 "--url", f"http://127.0.0.1:{port}/queries.json",
                 "--concurrency", str(concurrency),
                 "--duration", str(duration_s)],
                cwd=REPO, capture_output=True, text=True, timeout=600,
            )
            lines = [
                l for l in proc.stdout.splitlines() if l.startswith("{")
            ]
            try:
                rec = (
                    json.loads(lines[-1]) if lines
                    else {"error": "no loadgen JSON"}
                )
            except ValueError:
                rec = {"error": f"malformed JSON: {lines[-1][:120]!r}"}
            rec["step"] = step
            rec["rc"] = proc.returncode
            append(rec)
            if proc.returncode != 0 or "error" in rec:
                failed.append(step)
            log(f"  -> depth {depth}: qps={rec.get('qps')} "
                f"p99={rec.get('p99_ms')}ms errors={rec.get('errors')}")
        except subprocess.TimeoutExpired:
            append({"step": step, "error": "loadgen timed out"})
            failed.append(step)
        finally:
            subprocess.run(
                [pio, "undeploy", "--port", str(port)],
                capture_output=True,
            )
            time.sleep(1)
    return failed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-loadgen", action="store_true")
    ap.add_argument("--engine-dir", default=None,
                    help="trained engine project for the loadgen sweep "
                         "(e.g. a movielens_quickstart workdir's engine/); "
                         "omitting it skips the sweep with instructions")
    ap.add_argument("--engine-dir-big", default=None,
                    help="trained BIG-catalog engine (60k+ items — "
                         "streaming-top-k territory) for an additional "
                         "loadgen pass at the catalog shapes the serving "
                         "claims are priced at")
    ap.add_argument("--loadgen-duration", type=float, default=15.0)
    ap.add_argument("--loadgen-concurrency", type=int, default=128)
    ap.add_argument("--iterations", default=None,
                    help="override BENCH_ITERATIONS")
    ap.add_argument("--repeats", type=int, default=3,
                    help="baseline bench repeat count (run-to-run spread)")
    ap.add_argument("--tier", choices=["a", "b", "all"], default="all",
                    help="a: headline records only (≤5 min of device "
                         "time — one f32 baseline + fused_smoke + "
                         "mesh_pallas); b: everything else, reusing "
                         "tier-A records younger than 6 h; all: both "
                         "inline")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    from predictionio_tpu.utils.jax_cache import compilation_cache_dir

    # every subprocess leg below (bench runs, _reval_steps, deploys,
    # loadgen) resolves this same directory, so only the first compiler
    # of each program pays for it. This parent never touches JAX: a chip
    # belongs to one process at a time, and the steps need it.
    log(f"persistent compilation cache: {compilation_cache_dir()}")

    base_env: dict = {
        # the queue runs bench.py ~8x; cache the deterministic synthetic
        # dataset so generation cost is paid once, not per run
        "BENCH_SYNTH_CACHE": os.environ.get(
            "BENCH_SYNTH_CACHE", "/tmp/pio-bench-synth"
        ),
    }
    if args.iterations:
        base_env["BENCH_ITERATIONS"] = str(args.iterations)

    failures: list = []

    def _track(rec: dict) -> dict:
        """A step that timed out or errored must surface in the exit
        code: a tier-B run that reused its baseline and then had every
        step time out would otherwise report 'complete' with nothing
        measured."""
        if rec.get("rc") != 0 or "error" in rec:
            failures.append(rec.get("step"))
        return rec

    def _reused(rec: dict) -> dict:
        """Tag + report a tier-A record reused instead of re-measured.
        The evidence file gets an explicit marker under a DISTINCT step
        name (so ``_recent`` can never mistake the marker for a fresh
        measurement and chain reuse past the 6 h limit), and the
        in-memory record carries ``reused=True`` so downstream
        aggregation — the baseline_variance spread — can tell a
        reused leg from one measured in this invocation."""
        now = time.time()
        append({
            "step": "reused_tier_a_record",
            "of": rec.get("step"),
            "source_t_unix": rec.get("t_unix"),
            "age_s": round(now - float(rec.get("t_unix", now)), 1),
        })
        return {**rec, "reused": True}

    def step_once(step: str) -> dict:
        """Tier B reuses a recent (≤6 h, successful) tier-A record for
        ``step`` rather than re-spending device time; everything else
        runs it. A failed/timed-out record (rc!=0) is never reused —
        the step gets a fresh chance on the healthy device."""
        if args.tier == "b":
            rec = _recent(step)
            if rec is not None and rec.get("rc") == 0:
                log(f"reusing recent {step} record (t_unix="
                    f"{rec.get('t_unix')})")
                return _reused(rec)
        return _track(run_step(step))

    baseline = None
    if args.tier == "b":
        rec = _recent("baseline_f32")
        # the reused record must have been measured under THIS run's
        # bench config — a gate computed from a different scale or
        # iteration count would quietly invalidate every A/B verdict
        want_scale = float(os.environ.get("BENCH_SCALE", "1.0"))
        want_iters = int(
            args.iterations or os.environ.get("BENCH_ITERATIONS", "10")
        )
        if (rec is not None and rec.get("rc") == 0
                and _on_tpu(rec) and "holdout_rmse" in rec
                and float(rec.get("scale", -1.0)) == want_scale
                and int(rec.get("iterations", -1)) == want_iters):
            baseline = _reused(rec)
            log(f"tier B: reusing tier-A baseline "
                f"({rec.get('value')}s, rmse {rec.get('holdout_rmse')})")
    if baseline is None:
        baseline = run_bench("baseline_f32", dict(base_env))
        if baseline.get("rc") != 0 or not _on_tpu(baseline):
            log("baseline failed or did not run on a TPU; aborting the "
                "A/B chain")
            return 1

    if args.tier == "a":
        # the two never-compiled-kernel verdicts are the other
        # highest-information records; then stop — tier B owns the
        # repeats and sweeps. A step that timed out/errored makes tier A
        # rc=1, so a caller chaining A then B does not start B.
        _track(run_step("fused_smoke"))
        _track(run_step("mesh_pallas"))
        if failures:
            log(f"tier A done with FAILED steps {failures}; "
                f"evidence in {OUT}")
            return 1
        log(f"tier A complete; evidence in {OUT}")
        return 0

    gate = float(baseline["holdout_rmse"]) + RMSE_GATE_DELTA

    # repeat runs: the prior last-good number was a single leg whose first
    # iteration included compile; record spread + steady-state separately.
    # The spread is a WITHIN-invocation statistic — a tier-B baseline
    # reused from an earlier tier-A run (possibly hours old) would fold
    # run-to-run drift into it, so only legs measured in this
    # invocation enter the aggregate.
    repeats = [] if baseline.get("reused") else [baseline]
    for rep in range(2, max(1, args.repeats) + 1):
        rec = _track(run_bench(f"baseline_f32_r{rep}", dict(base_env)))
        if rec.get("rc") == 0 and _on_tpu(rec):
            repeats.append(rec)
    if len(repeats) > 1:
        trains = [float(r["value"]) for r in repeats]
        steadies = [
            float(sum(r["iteration_s"][1:]) / len(r["iteration_s"][1:]))
            for r in repeats if len(r.get("iteration_s", [])) > 1
        ]
        append({
            "step": "baseline_variance",
            "runs": len(repeats),
            "reused_baseline_excluded": bool(baseline.get("reused")),
            "train_s": trains,
            "train_s_spread": round(max(trains) - min(trains), 3),
            "steady_iter_s": [round(s, 4) for s in steadies],
            "bucketize_stage_s": [
                r.get("bucketize_stage_s") for r in repeats
            ],
        })


    def gated(step: str, env: dict) -> dict:
        # _track: an rc!=0/timeout leg is a failure; a leg that merely
        # FAILS the RMSE gate is a completed measurement, not a failure
        rec = _track(run_bench(step, {**base_env, **env}))
        ok = (
            rec.get("rc") == 0
            and _on_tpu(rec)
            and float(rec.get("holdout_rmse", 9.9)) <= gate
        )
        rec["rmse_gate"] = "pass" if ok else "FAIL"
        append({"step": f"{step}_gate", "gate": rec["rmse_gate"],
                "threshold": round(gate, 4)})
        return rec

    bf16 = gated("bf16_gather", {"BENCH_GATHER_DTYPE": "bf16"})
    srt = gated("sort_gather", {"BENCH_SORT_GATHER": "1"})
    if bf16.get("rmse_gate") == "pass" and srt.get("rmse_gate") == "pass":
        gated("bf16_plus_sort",
              {"BENCH_GATHER_DTYPE": "bf16", "BENCH_SORT_GATHER": "1"})

    # Never-compiled paths only AFTER the proven-lever evidence is on
    # disk: a Mosaic experiment that hangs its step must not cost the
    # bf16/sort measurements.
    # fused_smoke's verdict gates the full-scale fused A/B. (Under
    # --tier b these two were usually already run by tier A.)
    fused_smoke = step_once("fused_smoke")
    step_once("mesh_pallas")
    _track(run_step("dispatch_bench"))
    _track(run_step("flash_pallas"))
    # real profiler trace of the two hot paths: op-level device timings
    # for the HBM-utilization story (summary lands in the evidence file,
    # full trace stays under PIO_PROFILE_DIR for TensorBoard)
    _track(run_step("profile_trace", timeout_s=1200))
    fused = None
    if fused_smoke.get("ok"):
        fused = gated("fused_gather", {"BENCH_FUSED_GATHER": "1"})
        if fused.get("rmse_gate") == "pass" and bf16.get("rmse_gate") == "pass":
            # composability check, NOT a byte saving: the fused kernel
            # upcasts bf16 tables (per-row DMA floor is 128 lanes × 32
            # bits — see gramian_fused), so this leg measures fused at
            # f32 table width with bf16 gathers everywhere else
            gated("fused_plus_bf16",
                  {"BENCH_FUSED_GATHER": "1", "BENCH_GATHER_DTYPE": "bf16"})
    else:
        append({"step": "fused_gather", "skipped":
                "fused_smoke failed or did not run — Mosaic lowering "
                "unvalidated, full-scale A/B withheld"})

    # Implicit-mode quality gate (VERDICT r4 item 5): levers that passed
    # the EXPLICIT RMSE gate must also clear a ranking-metric gate on the
    # implicit path before any default flip — explicit evidence alone
    # cannot certify Hu-Koren confidence weighting.
    passed_levers = {}
    if bf16.get("rmse_gate") == "pass":
        passed_levers["BENCH_GATHER_DTYPE"] = "bf16"
    if srt.get("rmse_gate") == "pass":
        passed_levers["BENCH_SORT_GATHER"] = "1"
    if fused is not None and fused.get("rmse_gate") == "pass":
        passed_levers["BENCH_FUSED_GATHER"] = "1"
    if passed_levers:
        # gather dtype is ALWAYS explicit: the step's standalone default
        # is bf16, which must not leak in when bf16 just FAILED its gate
        # and only sort/fused are under certification
        _track(run_step(
            "implicit_gate", timeout_s=1800,
            env_extra={"BENCH_GATHER_DTYPE": "f32", **passed_levers},
        ))
    else:
        append({"step": "implicit_gate", "skipped":
                "no lever passed the explicit RMSE gate; nothing to "
                "certify for implicit mode"})

    if args.skip_loadgen:
        pass
    else:
        if args.engine_dir:
            failures += run_loadgen_sweep(
                args.engine_dir, args.loadgen_duration,
                args.loadgen_concurrency,
            )
            failures += run_inprocess_sweep(
                args.engine_dir, args.loadgen_duration,
                args.loadgen_concurrency,
            )
        if args.engine_dir_big:
            # independent of --engine-dir: the big-catalog pass alone is
            # a valid (and sometimes the only wanted) measurement
            failures += run_loadgen_sweep(
                args.engine_dir_big, args.loadgen_duration,
                args.loadgen_concurrency, tag="_big",
            )
            failures += run_inprocess_sweep(
                args.engine_dir_big, args.loadgen_duration,
                args.loadgen_concurrency, tag="_big",
            )
        if not (args.engine_dir or args.engine_dir_big):
            log("loadgen sweep skipped: pass --engine-dir <trained engine "
                "project> (e.g. run examples/movielens_quickstart/run.sh "
                "once, then point at <workdir>/engine)")

    if failures:
        # rc=1 tells the caller to run the queue again: completed
        # records are on disk, but the matrix is not done
        log(f"done with FAILED/timed-out steps {failures}; evidence in {OUT}")
        return 1
    log(f"done; evidence in {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
