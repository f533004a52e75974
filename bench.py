"""Benchmark: ALS rank-50 on a MovieLens-20M-shaped workload.

Prints ONE JSON line on stdout:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``
(diagnostics go to stderr).

The north-star target (BASELINE.json) is MLlib ALS rank-50 on MovieLens-20M
training in < 60 s on a v5e-8 at RMSE parity. This bench runs on the device
JAX gives it and says which on stderr: it synthesizes a
20M-rating matrix with ML-20M's shape (138k users x 27k items, power-law
degrees, low-rank ground truth + noise), trains rank-50 for 10 iterations —
wall-clock includes bucketization, host→device staging and training — and
verifies holdout RMSE approaches the noise floor (quality gate; the run
fails loudly rather than reporting a fast-but-wrong number).

There is no fallback: whatever ``run_bench`` raises ends the run non-zero
with the error, and a record names the device it was measured on.

``vs_baseline`` = 60 s / measured train seconds (>1 beats the 8-chip target
even on this single chip).

Env knobs: ``BENCH_SCALE`` (default 1.0) scales the rating count for quick
smoke runs; ``BENCH_ITERATIONS`` (default 10); ``BENCH_SYNTH_CACHE``
(off by default) names a directory where
the deterministic synthetic dataset is cached across runs — cache files
are keyed by (generator version, scale, seed). Lever knobs
(``BENCH_SOLVE_MODE``/``BENCH_GATHER_DTYPE``)
are documented at their ALSConfig fields (the fused gather+Gramian
build comes with the ``pallas`` solver) and every round trains
a bf16-gather twin whose holdout RMSE must stay within
``BENCH_BF16_RMSE_GATE`` (default 0.01) of the f32 run —
``BENCH_BF16_GATE=0`` opts out, a drift fails the bench loudly. The
recorded lever flags are the RESOLVED values, and the gate's margin
rides the record (``bf16_gate``) into the perf ledger's ``extra``.
"""

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

#: North-star wall-clock target (BASELINE.md): ML-20M rank-50 in < 60 s.
_BASELINE_S = 60.0

# Device peaks (keyed by device_kind) live in
# predictionio_tpu.obs.profile.DEVICE_PEAKS — one home shared with
# `pio profile`'s roofline columns, so the two reports can never
# disagree about the same run.

#: Version of the synth_ml20m generation recipe — part of the cache key;
#: bump on ANY change to the sampling/ground-truth/noise code.
_SYNTH_VERSION = 1

def synth_ml20m(scale: float, seed: int = 0):
    """ML-20M-shaped synthetic ratings: power-law user/item degrees, rank-8
    ground truth, sd-0.5 observation noise.

    Deterministic in (scale, seed), so when ``BENCH_SYNTH_CACHE`` names a
    directory the triplets are saved there once and reloaded by later
    runs, each of which would otherwise repeat the ~minute of host-side
    generation."""
    cache_dir = os.environ.get("BENCH_SYNTH_CACHE")
    cache = None
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        # _SYNTH_VERSION is part of the key: bump it with ANY change to
        # the generation code below, or a persistent cache dir would
        # silently serve the pre-change dataset as current evidence
        cache = os.path.join(
            cache_dir,
            f"synth_ml20m_v{_SYNTH_VERSION}_s{scale}_seed{seed}.npz",
        )
        if os.path.exists(cache):
            try:
                z = np.load(cache)
                return (
                    z["users"], z["items"], z["ratings"],
                    int(z["n_users"]), int(z["n_items"]),
                )
            except Exception as exc:  # torn write: regenerate
                print(f"bench: synth cache unreadable ({exc}); "
                      "regenerating", file=sys.stderr)
    rng = np.random.default_rng(seed)
    n_users = max(64, int(138_000 * min(1.0, scale)))
    n_items = max(32, int(27_000 * min(1.0, scale)))
    nnz = int(20_000_000 * scale)

    # power-law sampling via Zipf-ish inverse-rank weights
    u_w = 1.0 / np.arange(1, n_users + 1) ** 0.8
    i_w = 1.0 / np.arange(1, n_items + 1) ** 0.9
    users = rng.choice(n_users, size=nnz, p=u_w / u_w.sum()).astype(np.int64)
    items = rng.choice(n_items, size=nnz, p=i_w / i_w.sum()).astype(np.int64)

    gt_rank = 8
    x = rng.normal(size=(n_users, gt_rank)) / np.sqrt(gt_rank)
    y = rng.normal(size=(n_items, gt_rank)) / np.sqrt(gt_rank)
    ratings = (
        (x[users] * y[items]).sum(axis=1) + 3.5 + rng.normal(0, 0.5, nnz)
    ).astype(np.float32)
    if cache:
        # tmp name keeps the .npz suffix so np.savez writes it verbatim;
        # atomic rename = concurrent bench runs never see a torn file.
        # Sweep predecessors' orphans first: a bench killed mid-savez
        # (a step timeout) leaves a ~400 MB tmp behind. Only
        # reap a tmp whose writer pid is gone — a concurrent bench's
        # live tmp must not vanish out from under its savez.
        import glob

        for orphan in glob.glob(f"{cache}.*.tmp.npz"):
            try:
                age_s = time.time() - os.path.getmtime(orphan)
            except OSError:
                continue  # vanished under us (another reaper won)
            if age_s < 6 * 3600.0:
                # young tmp: only reap if its writer pid is gone. Old
                # tmps are reaped regardless — a recycled pid must not
                # make a ~400 MB orphan permanent.
                try:
                    pid = int(os.path.basename(orphan).split(".")[-3])
                    os.kill(pid, 0)  # raises if no such process
                    continue  # writer still alive; leave its tmp alone
                except (ValueError, IndexError, ProcessLookupError):
                    pass  # unparseable name or dead writer: orphan
                except OSError:
                    continue  # exists but not signalable: assume alive
            try:
                os.remove(orphan)
            except OSError:
                pass
        tmp = f"{cache}.{os.getpid()}.tmp.npz"
        try:
            np.savez(tmp, users=users, items=items, ratings=ratings,
                     n_users=n_users, n_items=n_items)
            os.replace(tmp, cache)
        except BaseException:
            if os.path.exists(tmp):
                os.remove(tmp)
            raise
    return users, items, ratings, n_users, n_items


def holdout_mask(nnz: int) -> np.ndarray:
    """The bench's holdout split (5%, fixed seed). Shared with
    ``tools/prewarm_cache`` so the AOT-compiled programs keep the EXACT
    bench bucket shapes — any change here changes the compiled program
    and must flow to both users."""
    return np.random.default_rng(1).random(nnz) < 0.05


def _append_ledger(record: dict) -> None:
    """Durable perf-ledger append (``BENCH_LEDGER=path`` opts in —
    docs/performance.md#perf-ledger). Strictly additive: stdout stays
    the one-JSON-line contract, and a ledger failure never fails the
    bench."""
    path = os.environ.get("BENCH_LEDGER")
    if not path:
        return
    try:
        from predictionio_tpu.obs import perfledger

        perfledger.append_record(
            path,
            perfledger.bench_to_record(record),
        )
        # serving-fleet numbers (loadgen --replicas) gate alongside the
        # train time: p99 as a lower-is-better "s" record, QPS as a
        # trend-only record (docs/fleet.md, docs/performance.md)
        for fleet_record in perfledger.fleet_records(record):
            perfledger.append_record(path, fleet_record)
        # serve-from-memory numbers (loadgen --cached-hot-set): cached
        # p99 gated at its declared wide band, the step-function QPS
        # and hit-rate as trend records (docs/fleet.md#cache)
        for cache_record in perfledger.cache_records(record):
            perfledger.append_record(path, cache_record)
        # shared-tier numbers (loadgen --shared-cache-drill): the
        # hedged healthy-phase p99 gated at its declared wide band, the
        # fleet-wide hit rate as a trend record
        # (docs/fleet.md#shared-cache-tier)
        for shared_record in perfledger.shared_cache_records(record):
            perfledger.append_record(path, shared_record)
        # quantized-serving numbers (BENCH_QUANT block): the int8 table
        # byte count gated as a deterministic lower-is-better "bytes"
        # record, the top-k match rate as a trend record
        # (docs/quantization.md)
        for quant_record in perfledger.quant_records(record):
            perfledger.append_record(path, quant_record)
        # model-quality trajectory (score PSI / feedback hit-rate from
        # the feedback-stream drill) rides as trend-only records so
        # `pio perf trend` shows quality next to latency
        # (docs/observability.md#quality)
        for quality_record in perfledger.quality_records(record):
            perfledger.append_record(path, quality_record)
        # alert noisiness from the brownout drill, trend-only
        # (docs/slo.md): alert hygiene gets a trajectory too
        for alert_record in perfledger.alert_records(record):
            perfledger.append_record(path, alert_record)
        # ingest throughput per partition count, trend-only and keyed
        # by N via scale (docs/storage.md#partitioning): different
        # partition counts never gate each other
        for ingest_record in perfledger.ingest_records(record):
            perfledger.append_record(path, ingest_record)
        # sharded-train wall clock per shard count, keyed by N via scale
        # the same way (docs/distributed_training.md): each shard count
        # has its own gated trajectory, declared wide-band
        for sharded_record in perfledger.sharded_records(record):
            perfledger.append_record(path, sharded_record)
        # lint-sweep cold wall clock, trend-only (docs/lint.md#cache):
        # the warm time and cache byte-identity ride in extra
        for lint_record in perfledger.lint_records(record):
            perfledger.append_record(path, lint_record)
        # migration-drill wall + dual-write overhead, trend-only and
        # keyed by "N->M" via scale (docs/storage.md#live-migration):
        # an expansion and a merge never share a trajectory
        for migration_record in perfledger.migration_records(record):
            perfledger.append_record(path, migration_record)
        # checkpointing overhead ratio from the preemption drill,
        # trend-only (docs/checkpoint.md): the cost of never losing a
        # run gets a trajectory, never a gate
        for ckpt_record in perfledger.ckpt_records(record):
            perfledger.append_record(path, ckpt_record)
    except Exception as exc:
        print(f"bench: ledger append failed (ignored): {exc}",
              file=sys.stderr)


#: Child program for one sharded-train measurement. Runs in a SUBPROCESS
#: because the virtual device count must be pinned in XLA_FLAGS before
#: the first `import jax`; the recipe is deterministic in its seed so
#: every shard count trains the identical dataset (docs/
#: distributed_training.md — equivalence is pinned in tier-1, this
#: measures wall clock).
_SHARDED_SNIPPET = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from predictionio_tpu.ops.als import ALSConfig, rmse
from predictionio_tpu.ops.als_sharded import als_train_sharded

shards = {shards}
rng = np.random.default_rng(7)
nnz, n_u, n_i = 60_000, 2_000, 600
w = 1.0 / np.arange(1, n_u + 1) ** 0.8
u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
i = rng.integers(0, n_i, nnz).astype(np.int32)
v = rng.integers(1, 6, nnz).astype(np.float32)
cfg = ALSConfig(rank=16, iterations=3, lambda_=0.05, seed=0)
profile = {{}}
t0 = time.monotonic()
factors = als_train_sharded(
    u, i, v, n_users=n_u, n_items=n_i, cfg=cfg, shards=shards,
    profile=profile,
)
np.asarray(factors.user_factors)
train_s = time.monotonic() - t0
import jax
out = {{
    "trainS": round(train_s, 3),
    "rmse": round(rmse(factors, u, i, v), 4),
    "shards": profile.get("shards"),
    "device": str(jax.devices()[0]),
    "nnz": nnz,
    "iterations": cfg.iterations,
    "solve_mode": profile.get("solve_mode", "chunked"),
    "gather_dtype": profile.get("gather_dtype", "f32"),
    "sort_gather": False,  # a component of the perf ledger's key; nothing sorts
    "fused_gather": profile.get("fused_gather", False),
    "flopImbalance": (profile.get("shard_plan") or {{}}).get(
        "flopImbalance"
    ),
}}
print("SHARDED_JSON " + json.dumps(out))
"""


#: Child program for the preemption drill (docs/checkpoint.md). Two
#: modes in a SUBPROCESS each (virtual device count must be pinned
#: before the first `import jax`): "kill" trains with checkpointing and
#: SIGKILLs itself the instant the chosen step commits — a reclaimed VM,
#: not a clean shutdown — and "resume" picks the run back up at a
#: DIFFERENT shard count, compares against an uninterrupted in-process
#: twin within the PR-12 reassociation tolerances, and measures the
#: checkpointing overhead ratio on an untouched third run.
_CKPT_SNIPPET = r"""
import json, os, shutil, signal, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from predictionio_tpu.ckpt import CheckpointStore
from predictionio_tpu.ops.als import ALSConfig, rmse
from predictionio_tpu.ops.als_sharded import als_train_sharded

mode = {mode!r}
ckpt_dir = {ckpt_dir!r}
shards = {shards}
kill_step = {kill_step}

rng = np.random.default_rng(11)
nnz, n_u, n_i = 30_000, 1_000, 400
w = 1.0 / np.arange(1, n_u + 1) ** 0.8
u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
i = rng.integers(0, n_i, nnz).astype(np.int32)
v = rng.integers(1, 6, nnz).astype(np.float32)
cfg = ALSConfig(rank=8, iterations=3, lambda_=0.05, seed=3)

if mode == "kill":
    class KillingStore(CheckpointStore):
        def save(self, step, arrays, meta):
            out = super().save(step, arrays, meta)
            if step == kill_step:
                os.kill(os.getpid(), signal.SIGKILL)
            return out

    als_train_sharded(
        u, i, v, n_users=n_u, n_items=n_i, cfg=cfg, shards=shards,
        checkpoint=KillingStore(ckpt_dir), checkpoint_every=1,
    )
    print("CKPT_JSON " + json.dumps({{"error": "kill never fired"}}))
    sys.exit(3)

profile = {{}}
t0 = time.monotonic()
resumed = als_train_sharded(
    u, i, v, n_users=n_u, n_items=n_i, cfg=cfg, shards=shards,
    checkpoint=CheckpointStore(ckpt_dir), checkpoint_every=1,
    profile=profile,
)
ru = np.asarray(resumed.user_factors)
ri = np.asarray(resumed.item_factors)
resume_s = time.monotonic() - t0

t0 = time.monotonic()
plain = als_train_sharded(
    u, i, v, n_users=n_u, n_items=n_i, cfg=cfg, shards=shards,
)
plain_s = time.monotonic() - t0
pu = np.asarray(plain.user_factors)
pi = np.asarray(plain.item_factors)

fresh = ckpt_dir + ".overhead"
shutil.rmtree(fresh, ignore_errors=True)
t0 = time.monotonic()
als_train_sharded(
    u, i, v, n_users=n_u, n_items=n_i, cfg=cfg, shards=shards,
    checkpoint=CheckpointStore(fresh), checkpoint_every=1,
)
ckpt_s = time.monotonic() - t0
shutil.rmtree(fresh, ignore_errors=True)

import jax
ck = profile.get("ckpt") or {{}}
rmse_resumed = rmse(resumed, u, i, v)
rmse_plain = rmse(plain, u, i, v)
out = {{
    "resumedFrom": ck.get("resumedFrom"),
    "equivalent": bool(
        np.allclose(ru, pu, rtol=1e-3, atol=1e-4)
        and np.allclose(ri, pi, rtol=1e-3, atol=1e-4)
        and abs(rmse_resumed - rmse_plain) <= 1e-3
    ),
    "maxAbsDiff": round(float(max(
        np.max(np.abs(ru - pu)), np.max(np.abs(ri - pi))
    )), 6),
    "rmseResumed": round(float(rmse_resumed), 4),
    "rmsePlain": round(float(rmse_plain), 4),
    "resumeS": round(resume_s, 3),
    "plainS": round(plain_s, 3),
    "ckptS": round(ckpt_s, 3),
    "overheadRatio": (
        round(ckpt_s / plain_s, 4) if plain_s > 0 else None
    ),
    "snapshotS": ck.get("snapshotS"),
    "written": ck.get("written"),
    "dropped": ck.get("dropped"),
    "errors": ck.get("errors"),
    "device": str(jax.devices()[0]),
    "nnz": nnz,
    "iterations": cfg.iterations,
}}
print("CKPT_JSON " + json.dumps(out))
"""


def run_ckpt_resume(
    train_shards: int = 2, resume_shards: int = 4, timeout_s: float = 600.0
) -> dict:
    """The preemption drill (docs/checkpoint.md#preemption-drill):
    checkpointed training at N shards SIGKILLed the instant a chosen
    step commits, resumed at M shards, compared against an uninterrupted
    twin within the PR-12 tolerances. The overhead ratio (ckpt-on wall /
    plain wall) rides the ledger trend-only as
    ``train_ckpt_overhead_ratio``. Returns the ``ckptResume`` bench
    block (``ok`` only when the kill fired, the resume picked up the
    killed run's last committed step, and the factors match)."""
    import random
    import shutil
    import signal
    import tempfile

    from predictionio_tpu.utils.platform import force_cpu_env

    # a random kill point keeps the drill honest over bench history —
    # resume must work from ANY committed step, not a lucky one
    kill_step = random.choice((1, 2))
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    block: dict = {
        "trainShards": train_shards,
        "resumeShards": resume_shards,
        "killStep": kill_step,
        "ok": False,
    }

    def _child(mode: str, shards: int) -> subprocess.CompletedProcess:
        return subprocess.run(
            [
                sys.executable,
                "-c",
                _CKPT_SNIPPET.format(
                    repo=_REPO_ROOT, mode=mode, ckpt_dir=ckpt_dir,
                    shards=shards, kill_step=kill_step,
                ),
            ],
            env=force_cpu_env(n_devices=shards),
            cwd=_REPO_ROOT,
            timeout=timeout_s,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )

    try:
        kill = _child("kill", train_shards)
        if kill.returncode != -signal.SIGKILL:
            tail = kill.stderr.decode("utf-8", "replace").strip().splitlines()
            block["error"] = (
                f"kill child rc={kill.returncode}, expected SIGKILL: "
                f"{tail[-1] if tail else '(no stderr)'}"
            )
            return block
        proc = _child("resume", resume_shards)
        line = next(
            (
                ln[len("CKPT_JSON "):]
                for ln in proc.stdout.decode("utf-8", "replace").splitlines()
                if ln.startswith("CKPT_JSON ")
            ),
            None,
        )
        if proc.returncode != 0 or line is None:
            tail = proc.stderr.decode("utf-8", "replace").strip().splitlines()
            block["error"] = (
                f"resume child rc={proc.returncode}: "
                f"{tail[-1] if tail else '(no stderr)'}"
            )
            return block
        block.update(json.loads(line))
        if block.get("resumedFrom") != kill_step:
            block["error"] = (
                f"resumed from step {block.get('resumedFrom')}, "
                f"expected the killed run's last commit {kill_step}"
            )
        elif not block.get("equivalent"):
            block["error"] = (
                f"resumed factors drifted beyond tolerance "
                f"(maxAbsDiff {block.get('maxAbsDiff')})"
            )
        else:
            block["ok"] = True
        print(
            f"bench ckptResume: killed@{kill_step} "
            f"{train_shards}->{resume_shards} shards "
            f"ok={block['ok']} overhead {block.get('overheadRatio')}",
            file=sys.stderr,
        )
        return block
    except subprocess.TimeoutExpired:
        block["error"] = f"timed out after {timeout_s:.0f}s"
        return block
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def run_lint_sweep() -> dict:
    """Cold-vs-warm full-package lint sweep with a throwaway cache;
    returns the ``lintSweep`` bench block (``coldS``/``warmS``/
    ``files``/``identical``, ``ok`` only when both sweeps ran clean of
    engine errors AND the warm findings were byte-identical). The
    engine is stdlib-only, so this runs in-process on any box."""
    import tempfile

    from predictionio_tpu.lint import lint_paths, render_json

    package_dir = os.path.join(_REPO_ROOT, "predictionio_tpu")
    with tempfile.TemporaryDirectory() as tmp:
        cache = os.path.join(tmp, "lint_cache.json")
        t0 = time.perf_counter()
        cold = lint_paths([package_dir], cache_path=cache)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = lint_paths([package_dir], cache_path=cache)
        warm_s = time.perf_counter() - t0
    identical = render_json(cold) == render_json(warm)
    return {
        "coldS": cold_s,
        "warmS": warm_s,
        "files": cold.files,
        "findings": len(cold.findings),
        "identical": identical,
        "ok": bool(
            not cold.errors and not warm.errors and identical
        ),
    }


def run_quant_serve(user_factors, item_factors, k: int = 10) -> dict:
    """Quantize THIS round's trained item table and measure what the
    ledger wants to trend: the int8 serving footprint vs its f32 twin
    (serve_table_bytes, GATED — bytes are deterministic, so any
    compression regression trips the band) and the exactness-gate
    match rate (quant_topk_match_rate, trend-only — the id-identity
    margin the serve lever needs before it can turn on for this
    recipe). Uses the ungated constructor + gate probe directly: the
    bench MEASURES the gate margin, it does not refuse on it."""
    import jax

    from predictionio_tpu.quant import (
        default_probe_idx,
        estimate_table_bytes,
        quantize_table,
        top_k_quantized,
        topk_match_gate,
    )

    user_factors = np.asarray(user_factors, dtype=np.float32)
    item_factors = np.asarray(item_factors, dtype=np.float32)
    qtable = quantize_table(item_factors)
    probe = default_probe_idx(user_factors.shape[0])
    match_rate = topk_match_gate(
        user_factors, item_factors, qtable, probe, k
    )
    # quantized top-k wall over the probe batch (steady state: second
    # call, first one pays the jit)
    top_k_quantized(user_factors, qtable, probe, k)
    t0 = time.perf_counter()
    jax.block_until_ready(
        top_k_quantized(user_factors, qtable, probe, k)
    )
    topk_s = time.perf_counter() - t0
    return {
        "ok": True,
        "tableDtype": qtable.dtype,
        "tableBytes": qtable.table_bytes,
        "f32Bytes": qtable.f32_bytes,
        "ratio": round(qtable.compression_ratio, 3),
        "estTableBytes": estimate_table_bytes(
            qtable.n_rows, qtable.rank, qtable.dtype
        ),
        "matchRate": round(match_rate, 4),
        "probes": int(probe.size),
        "k": int(min(k, item_factors.shape[0])),
        "topkS": round(topk_s, 4),
        "rank": qtable.rank,
        "nItems": qtable.n_rows,
    }


def run_sharded_train(shard_counts=(1, 2, 4), timeout_s: float = 600.0) -> dict:
    """Train the small deterministic sharded recipe at each shard count
    in a forced-virtual-device subprocess; returns the ``shardedTrain``
    bench block (``counts`` keyed by N, ``ok`` only when every count
    measured)."""
    from predictionio_tpu.utils.platform import force_cpu_env

    counts: dict = {}
    ok = True
    for n in shard_counts:
        env = force_cpu_env(n_devices=n)
        try:
            proc = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    _SHARDED_SNIPPET.format(repo=_REPO_ROOT, shards=n),
                ],
                env=env,
                cwd=_REPO_ROOT,
                timeout=timeout_s,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        except subprocess.TimeoutExpired:
            counts[str(n)] = {"error": f"timed out after {timeout_s:.0f}s"}
            ok = False
            continue
        line = next(
            (
                ln[len("SHARDED_JSON "):]
                for ln in proc.stdout.decode("utf-8", "replace").splitlines()
                if ln.startswith("SHARDED_JSON ")
            ),
            None,
        )
        if proc.returncode != 0 or line is None:
            tail = proc.stderr.decode("utf-8", "replace").strip().splitlines()
            counts[str(n)] = {
                "error": (
                    f"rc={proc.returncode}: "
                    f"{tail[-1] if tail else '(no stderr)'}"
                )
            }
            ok = False
            continue
        counts[str(n)] = json.loads(line)
        print(
            f"bench shardedTrain: shards={n} "
            f"train {counts[str(n)]['trainS']}s "
            f"rmse {counts[str(n)]['rmse']}",
            file=sys.stderr,
        )
    return {"counts": counts, "ok": ok}


def run_bench(scale: float, iterations: int) -> int:
    import jax

    from predictionio_tpu.obs.profile import default_telemetry
    from predictionio_tpu.ops.als import (
        ALSConfig,
        als_train,
        bucketize,
        rmse,
        stage,
    )

    jit_before = default_telemetry().snapshot()

    users, items, ratings, n_users, n_items = synth_ml20m(scale)
    nnz = len(ratings)

    # holdout split for the quality gate
    test = holdout_mask(nnz)
    tr = ~test

    solve_mode = os.environ.get("BENCH_SOLVE_MODE", "auto")
    gather_dtype = os.environ.get("BENCH_GATHER_DTYPE", "f32")
    cfg = ALSConfig(
        rank=50, iterations=iterations, lambda_=0.05, seed=0,
        solve_mode=solve_mode, gather_dtype=gather_dtype,
    )

    # Warm the compilation cache with the REAL bucket shapes (jit keys on
    # shapes: a smaller sliver would leave the timed run paying XLA compile).
    # 2 warm-up iterations: the first executed iteration runs as two
    # half-programs (staging overlap), later ones as the fused program —
    # both must be compiled before the timed section; the timed section
    # then measures steady-state bucketize + staging + training.
    warm_cfg = ALSConfig(
        rank=cfg.rank, iterations=2, lambda_=cfg.lambda_, seed=cfg.seed,
        solve_mode=solve_mode, gather_dtype=gather_dtype,
    )
    wu = stage(bucketize(users[tr], items[tr], ratings[tr],
                         n_users, n_items, pad_to_blocks=True))
    wi = stage(bucketize(items[tr], users[tr], ratings[tr],
                         n_items, n_users, pad_to_blocks=True))
    np.asarray(als_train(wu, wi, warm_cfg).user_factors)
    del wu, wi

    profile: dict = {}
    t0 = time.time()
    t_b = time.monotonic()
    # phase timers: bucketize is host CPU (threaded C++ scatter), stage is
    # view-reshape + async device_put issue — separating them tells the
    # hardware run WHICH host-side cost dominates (the transfer wait
    # itself lands in iteration_s[0], excluded from steady-state)
    bu = bucketize(users[tr], items[tr], ratings[tr], n_users,
                   n_items, pad_to_blocks=True)
    t_s1 = time.monotonic()
    by_user = stage(bu)  # async puts: item bucketize below overlaps them
    t_s2 = time.monotonic()
    bi = bucketize(items[tr], users[tr], ratings[tr], n_items,
                   n_users, pad_to_blocks=True)
    t_s3 = time.monotonic()
    by_item = stage(bi)
    t_end = time.monotonic()
    bucketize_stage_s = t_end - t_b
    phase_s = {
        "bucketize_user": round(t_s1 - t_b, 3),
        "stage_user": round(t_s2 - t_s1, 3),
        "bucketize_item": round(t_s3 - t_s2, 3),
        "stage_item": round(t_end - t_s3, 3),
    }
    factors = als_train(by_user, by_item, cfg, profile=profile)
    # force full materialization onto the host: the timed section ends
    # when the factors are usable, not when the last dispatch returns
    np.asarray(factors.user_factors)
    np.asarray(factors.item_factors)
    train_s = time.time() - t0

    holdout = rmse(factors, users[test], items[test], ratings[test])

    iter_s = profile.get("iteration_s", [])
    flops = profile.get("flops_per_iteration", 0.0)
    hbm_bytes = profile.get("hbm_bytes_per_iteration", 0.0)
    # steady state: the first iteration absorbs the async staging transfer
    steady = iter_s[1:] if len(iter_s) > 1 else iter_s
    avg_iter = float(np.mean(steady)) if steady else 0.0
    from predictionio_tpu.obs.profile import roofline
    from predictionio_tpu.utils.platform import device_info

    # utilization only against the peaks of the device that ran: an
    # unknown device_kind gets achieved rates and no mfu / hbm_util
    rf = roofline(flops, hbm_bytes, avg_iter)
    device = device_info()

    record = {
        "metric": "ml20m_als_rank50_train_s",
        "value": round(train_s, 3),
        "unit": "s",
        "vs_baseline": round(_BASELINE_S / train_s, 2),
        "holdout_rmse": round(holdout, 4),
        "nnz": int(tr.sum()),
        "scale": scale,
        "iterations": iterations,
        "device": str(jax.devices()[0]),
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "bucketize_stage_s": round(bucketize_stage_s, 3),
        "bucketize_stage_phases_s": phase_s,
        "iteration_s": [round(s, 4) for s in iter_s],
        "est_tflops_per_s": round(rf["tflops_per_s"], 2),
        "est_hbm_gb_per_iter": round(hbm_bytes / 1e9, 2),
        "est_hbm_gb_per_s": round(rf["hbm_gb_per_s"], 2),
        "bucket_shapes": profile.get("bucket_shapes"),
        # RESOLVED lever flags from the train run itself (tri-state
        # defaults resolve inside als_train) — the ledger must record
        # what executed, not what was requested. ``sort_gather`` is a
        # component of the ledger's key (obs/perfledger.py): no run
        # sorts a row's indices
        "solve_mode": profile.get("solve_mode", solve_mode),
        "gather_dtype": profile.get("gather_dtype", gather_dtype),
        "sort_gather": False,
        "fused_gather": profile.get("fused_gather", False),
        # compile/retrace accounting for THIS process (warmup included):
        # a bench round whose timed section quietly recompiled is not
        # measuring steady state, and this field says so
        "jit": default_telemetry().delta_since(jit_before),
    }
    if "mfu" in rf:
        record["est_mfu_f32"] = round(rf["mfu"], 4)
        record["est_hbm_util"] = round(rf["hbm_util"], 3)
    # quality gate: noise floor is 0.5; MLlib-parity training lands near it.
    if holdout > 0.62:
        record["vs_baseline"] = 0.0
        record["error"] = f"holdout RMSE {holdout:.4f} failed quality gate"
        _append_ledger(record)
        print(json.dumps(record))
        return 1
    # bf16 precision gate (docs/performance.md#levers): every round
    # trains a reduced-precision twin on the SAME staged (and sorted)
    # buckets — only gather_dtype differs — and bounds its holdout-RMSE
    # drift vs the f32 run. The gate keeps the bf16 lever adoptable:
    # the bench fails LOUDLY the round bf16 precision drifts, instead
    # of a dashboard noticing a quality slide later. Default bound
    # 0.01 absolute RMSE: measured drift on the CPU at scale 0.01 is
    # <1e-4 (round 12 — two orders of magnitude of headroom; the λ·n_u
    # ridge keeps the solves stable), while a real precision bug (e.g.
    # bf16 accumulation sneaking into the Gramian) shifts holdout RMSE
    # by >0.05. BENCH_BF16_GATE=0 opts out; BENCH_BF16_RMSE_GATE
    # overrides the bound.
    if os.environ.get("BENCH_BF16_GATE", "1") != "0":
        import dataclasses as _dc

        gate = float(os.environ.get("BENCH_BF16_RMSE_GATE", "0.01"))
        twin_dtype = "bf16" if record["gather_dtype"] == "f32" else "f32"
        # the twin runs the EINSUM build (the ``chunked`` solve):
        # gramian_fused upcasts bf16 tables to f32 at kernel entry
        # (Mosaic cannot DMA half-width sublanes), so a fused-path twin
        # would measure f32 math under a bf16 label — the einsum path
        # is where the bf16 lever actually feeds the MXU at reduced
        # precision, and the only path where it buys HBM bytes
        # (estimate_iteration_hbm_bytes)
        twin_cfg = _dc.replace(
            cfg, gather_dtype=twin_dtype, solve_mode="chunked"
        )
        twin = als_train(by_user, by_item, twin_cfg)
        twin_rmse = rmse(twin, users[test], items[test], ratings[test])
        if record["gather_dtype"] == "bf16" and record["fused_gather"]:
            # a bf16 MAIN run that resolved the fused build rode the
            # upcasting kernel — its holdout is f32 math under a bf16
            # label, not a bf16 measurement; train the einsum-built
            # bf16 leg explicitly so the gate compares real reduced-
            # precision math against the f32 twin
            bf16_leg = als_train(
                by_user, by_item,
                _dc.replace(cfg, gather_dtype="bf16", solve_mode="chunked"),
            )
            bf16_rmse = rmse(
                bf16_leg, users[test], items[test], ratings[test]
            )
            f32_rmse = twin_rmse
        else:
            f32_rmse = (
                holdout if record["gather_dtype"] == "f32" else twin_rmse
            )
            bf16_rmse = twin_rmse if twin_dtype == "bf16" else holdout
        margin = abs(bf16_rmse - f32_rmse)
        record["bf16_gate"] = {
            "rmse_f32": round(f32_rmse, 4),
            "rmse_bf16": round(bf16_rmse, 4),
            "margin": round(margin, 4),
            "gate": gate,
            "ok": margin <= gate,
        }
        if margin > gate:
            record["vs_baseline"] = 0.0
            record["error"] = (
                f"bf16 gather RMSE drifted {margin:.4f} vs f32 "
                f"(gate {gate})"
            )
            _append_ledger(record)
            print(json.dumps(record))
            return 1
    # Closed-loop freshness (docs/continuous.md): the tiny in-process
    # feedback-stream scenario gives every BENCH round a measured
    # event-ingest → model-live number next to the train time. Opt out
    # with BENCH_FEEDBACK_STREAM=0; a failure here never fails the bench.
    if os.environ.get("BENCH_FEEDBACK_STREAM") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_feedback_stream

            fs = run_feedback_stream(total_events=40, burst=20)
            record["continuousFreshness"] = {
                "freshnessS": fs.get("freshnessS"),
                "events": fs.get("events"),
                "cycles": fs.get("cycles"),
                "mode": (fs.get("lastCycle") or {}).get("mode"),
                "ok": fs.get("ok"),
            }
            # quality block (docs/observability.md#quality): the drill's
            # monitor measured score PSI vs its pinned train-time
            # baseline and the feedback join's hit-rate — every BENCH
            # round gets a quality trajectory point next to train time
            quality = fs.get("quality")
            if isinstance(quality, dict):
                record["quality"] = dict(
                    quality, ok=bool(fs.get("ok") and quality.get("ok"))
                )
        except Exception as exc:  # the headline metric must still report
            record["continuousFreshness"] = {"error": str(exc)}
    # Serving-fleet trajectory (docs/fleet.md): a small in-process
    # router + replicas drive gives every BENCH round a servedQPS /
    # servedP99Ms number next to train time — the serving-scale metric
    # the ROADMAP asked for. Opt out with BENCH_FLEET=0; a failure here
    # never fails the bench.
    if os.environ.get("BENCH_FLEET") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_fleet_chaos

            fleet = run_fleet_chaos(
                replicas=2, kill_backend_at=None, queries=96
            )
            record["servingFleet"] = {
                "replicas": fleet.get("replicas"),
                "sharded": fleet.get("sharded"),
                "servedQPS": fleet.get("servedQPS"),
                "servedP50Ms": fleet.get("servedP50Ms"),
                "servedP99Ms": fleet.get("servedP99Ms"),
                "ok": fleet.get("ok"),
            }
        except Exception as exc:  # the headline metric must still report
            record["servingFleet"] = {"error": str(exc)}
    # Serve-from-memory (docs/fleet.md#cache): the cached-hot-set drive
    # gives every BENCH round the router cache's step-function QPS win
    # next to the uncached servedQPS — with the byte-identity and
    # zero-stale-after-rollout proofs hard-gating the block's ok. Opt
    # out with BENCH_CACHE=0; a failure here never fails the bench.
    if os.environ.get("BENCH_CACHE") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_cached_hot_set

            cached = run_cached_hot_set(queries=160)
            record["cachedFleet"] = {
                "replicas": cached.get("replicas"),
                "cachedQPS": cached.get("cachedQPS"),
                "uncachedQPS": cached.get("uncachedQPS"),
                "speedup": cached.get("speedup"),
                "hitRate": cached.get("hitRate"),
                "cachedP50Ms": cached.get("cachedP50Ms"),
                "cachedP99Ms": cached.get("cachedP99Ms"),
                "byteIdentical": cached.get("byteIdentical"),
                "staleAfterRollout": cached.get("staleAfterRollout"),
                "ok": cached.get("ok"),
            }
        except Exception as exc:
            record["cachedFleet"] = {"error": str(exc)}
    # Shared cache tier (docs/fleet.md#shared-cache-tier): the
    # kill-the-tier drill gives every BENCH round the fleet-wide hit
    # rate and the hedged healthy-phase p99 — with the zero-stale,
    # byte-identity, recorded-degrade and recovery proofs hard-gating
    # the block's ok. Opt out with BENCH_SHAREDCACHE=0; a failure here
    # never fails the bench.
    if os.environ.get("BENCH_SHAREDCACHE") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_shared_cache_drill

            shared = run_shared_cache_drill(queries=96)
            record["sharedCache"] = {
                "healthyQPS": shared.get("healthyQPS"),
                "hedgedP99Ms": shared.get("hedgedP99Ms"),
                "sharedHitRate": shared.get("sharedHitRate"),
                "degradesRecorded": shared.get("degradesRecorded"),
                "byteIdenticalAfterKill": shared.get(
                    "byteIdenticalAfterKill"
                ),
                "staleAfterRollout": shared.get("staleAfterRollout"),
                "clientFailures": shared.get("clientFailures"),
                "warmedEntries": shared.get("warmedEntries"),
                "ok": shared.get("ok"),
            }
        except Exception as exc:
            record["sharedCache"] = {"error": str(exc)}
    # Alert hygiene (docs/slo.md): the in-process brownout drill gives
    # every BENCH round a fired/cleared/false-positive count, so alert
    # noisiness is tracked across rounds like perf and quality already
    # are. Opt out with BENCH_BROWNOUT=0; a failure never fails the
    # bench.
    if os.environ.get("BENCH_BROWNOUT") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_brownout

            brownout = run_brownout()
            per_objective = brownout.get("alerts") or {}
            record["alerts"] = {
                "fired": sum(
                    a.get("fired", 0) for a in per_objective.values()
                ),
                "cleared": sum(
                    a.get("cleared", 0) for a in per_objective.values()
                ),
                "falsePositives": brownout.get("falsePositives"),
                "stallsDetected": brownout.get("stallsDetected"),
                "ok": brownout.get("ok"),
            }
        except Exception as exc:
            record["alerts"] = {"error": str(exc)}
    # Ingest scaling (docs/storage.md#partitioning): acked-writes/second
    # at 1, 2 and 4 event-store partitions — subprocess primaries with
    # the strict fsync-per-ack oplog, concurrent writer processes, best
    # of 2 rounds per N on this (possibly contended) box. Scaling tops
    # out at the box's core count: a 2-core CI box shows the 1→2 win
    # and a 4-way plateau; real silicon shows the full fan. Opt out
    # with BENCH_INGEST_SCALING=0; a failure never fails the bench.
    if os.environ.get("BENCH_INGEST_SCALING") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_ingest_scaling

            scaling = run_ingest_scaling()
            record["ingestScaling"] = {
                "counts": scaling.get("counts"),
                "writers": scaling.get("writers"),
                "rounds": scaling.get("rounds"),
                "ok": scaling.get("ok"),
            }
        except Exception as exc:
            record["ingestScaling"] = {"error": str(exc)}
    # Live-migration drill (docs/storage.md#live-migration): the full
    # N=2 -> M=3 chaos choreography — dual-write, coordinator kill,
    # new-primary kill mid-backfill, watermark, flip, cursor handoff.
    # Wall time and the dual-write ingest overhead ride the ledger
    # trend-only, keyed by "N->M" as `scale` so different layout moves
    # never compare. Opt out with BENCH_MIGRATE=0; a failure never
    # fails the bench.
    if os.environ.get("BENCH_MIGRATE") != "0":
        try:
            from predictionio_tpu.tools.loadgen import run_migrate_drill

            drill = run_migrate_drill()
            record["migrationDrill"] = {
                k: drill.get(k)
                for k in (
                    "ok", "oldPartitions", "newPartitions", "opsPerPhase",
                    "wallS", "dualWriteOverhead", "lostAckedWrites",
                    "duplicateFolds",
                )
            }
        except Exception as exc:
            record["migrationDrill"] = {"error": str(exc)}
    # Sharded training (docs/distributed_training.md): the ALX-style
    # shard_map trainer at 1/2/4 shards on forced virtual CPU devices —
    # subprocesses, because the device count must be pinned before jax
    # imports. Each shard count's wall clock rides the ledger keyed by N
    # as `scale` (train_sharded_s), so counts never gate each other.
    # Opt out with BENCH_SHARDED=0; a failure never fails the bench.
    if os.environ.get("BENCH_SHARDED") != "0":
        try:
            record["shardedTrain"] = run_sharded_train()
        except Exception as exc:
            record["shardedTrain"] = {"error": str(exc)}
    # Preemption drill (docs/checkpoint.md#preemption-drill): a
    # checkpointed sharded run SIGKILLed mid-train resumes at a
    # DIFFERENT shard count and lands within tolerance of the
    # uninterrupted twin; the checkpointing overhead ratio rides the
    # ledger trend-only (train_ckpt_overhead_ratio). Opt out with
    # BENCH_CKPT=0; a failure never fails the bench.
    if os.environ.get("BENCH_CKPT") != "0":
        try:
            record["ckptResume"] = run_ckpt_resume()
        except Exception as exc:
            record["ckptResume"] = {"error": str(exc)}
    # Lint-sweep wall clock (docs/lint.md#cache): cold vs warm over the
    # package with a throwaway cache, in-process (the linter is stdlib-
    # only — no device, no subprocess needed). Rides the ledger trend-
    # only as lint_wall_s; `identical` pins the cache contract where a
    # regression would show in history. Opt out with BENCH_LINT=0; a
    # failure never fails the bench.
    if os.environ.get("BENCH_LINT") != "0":
        try:
            record["lintSweep"] = run_lint_sweep()
        except Exception as exc:
            record["lintSweep"] = {"error": str(exc)}
    # Quantized serving tables (docs/quantization.md): quantize this
    # round's trained item table, measure the int8 footprint vs the f32
    # twin (serve_table_bytes, GATED) and the exactness-gate top-k
    # match rate (trend-only). Opt out with BENCH_QUANT=0; a failure
    # never fails the bench.
    if os.environ.get("BENCH_QUANT") != "0":
        try:
            record["quantServe"] = run_quant_serve(
                np.asarray(factors.user_factors),
                np.asarray(factors.item_factors),
            )
        except Exception as exc:
            record["quantServe"] = {"error": str(exc)}
    _append_ledger(record)
    print(json.dumps(record))
    return 0


def main() -> int:
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    iterations = int(os.environ.get("BENCH_ITERATIONS", "10"))

    # persistent compilation cache: every bench run is a fresh process
    # and would otherwise re-pay the full XLA compile
    sys.path.insert(0, _REPO_ROOT)
    from predictionio_tpu.utils.jax_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    if cache_dir:
        print(f"bench: persistent compilation cache at {cache_dir}",
              file=sys.stderr)
    from predictionio_tpu.utils.platform import device_info

    device = device_info()
    print(
        f"bench: running on {device['platform']} ({device['kind']}, "
        f"{device['count']} device(s))",
        file=sys.stderr,
    )
    try:
        return run_bench(scale, iterations)
    except Exception as exc:
        # no fallback and no second attempt: the error is the result
        traceback.print_exc(file=sys.stderr)
        failed = {
            "metric": "ml20m_als_rank50_train_s",
            "value": -1.0,
            "unit": "s",
            "vs_baseline": 0.0,
            "error": f"{type(exc).__name__}: {exc}",
        }
        _append_ledger(failed)
        print(json.dumps(failed))
        return 1


if __name__ == "__main__":
    sys.exit(main())
