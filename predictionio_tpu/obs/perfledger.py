"""Durable performance ledger + regression gates (``pio perf``).

BENCH went five rounds without moving and nothing noticed, because each
round's number lived in its own ``BENCH_r0N.json`` and no tool ever put
two of them side by side. The ledger is the fix (the TensorFlow/ads-
infrastructure papers' "regression tracking is load-bearing
infrastructure" discipline, PAPERS.md):

- every ``bench.py`` run (``BENCH_LEDGER=path``) and training run
  (``PIO_PERF_LEDGER=path``) appends ONE schema-versioned JSON line —
  value, device, scale, lever flags, RMSE, phases — to an append-only
  JSONL file;
- ``pio perf diff`` loads the ledger plus the checked-in
  ``BENCH_r0*.json`` history, groups records that are honestly
  comparable (same metric, device class, scale and lever flags — a CPU
  fallback number must never gate a TPU number), and flags any latest
  value that is worse than the median of its predecessors beyond a
  noise band; exit 1 is the CI regression signal;
- ``pio perf trend`` renders the full trajectory so the kernel arc
  (sort-gather, fused gather, bf16) has a history it is accountable to.

Records are dicts, the file is line-delimited JSON, corrupt lines are
skipped on load (an append torn by a crash must not eat the history),
and appends fsync — the ledger is evidence, not a cache.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "SCHEMA_VERSION",
    "alert_records",
    "append_record",
    "bench_to_record",
    "cache_records",
    "ckpt_records",
    "comparable_key",
    "detect_regressions",
    "find_no_prior",
    "fleet_records",
    "ingest_records",
    "load_bench_history",
    "load_ledger",
    "make_record",
    "migration_records",
    "quality_records",
    "quant_records",
    "render_trend",
    "shared_cache_records",
    "sharded_records",
]

SCHEMA_VERSION = 1

#: env naming the ledger file training runs append to (bench.py has its
#: own ``BENCH_LEDGER`` knob, which leaves its stdout contract alone)
LEDGER_ENV = "PIO_PERF_LEDGER"

#: Flag a latest value this much worse than the median of its
#: predecessors. The checked-in CPU-fallback history wobbles ~10%
#: run-to-run on a contended host (BENCH_r02–r05: 12.36–13.71 s), so
#: the default band sits above that noise and below the 20% injected-
#: regression bar the tier-1 self-test drives.
DEFAULT_NOISE_BAND = 0.15

#: comparisons need at least this many predecessor records — one prior
#: point is an anecdote, not a baseline
MIN_HISTORY = 2

_BENCH_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def make_record(
    source: str,
    metric: str,
    value: float,
    unit: str = "s",
    device: Optional[str] = None,
    scale: Optional[float] = None,
    levers: Optional[Dict[str, object]] = None,
    rmse: Optional[float] = None,
    vs_baseline: Optional[float] = None,
    phases: Optional[Dict[str, float]] = None,
    extra: Optional[dict] = None,
    recorded_at: Optional[float] = None,
) -> dict:
    """One schema-versioned ledger record. ``unit == "s"`` and
    ``unit == "bytes"`` mean lower is better (the only units the
    regression gate compares); everything else is trend-only."""
    record: dict = {
        "schema": SCHEMA_VERSION,
        "source": source,
        "metric": metric,
        "value": float(value),
        "unit": unit,
    }
    if recorded_at is not None:
        record["recorded_at_unix"] = float(recorded_at)
    if device is not None:
        record["device"] = device
    if scale is not None:
        record["scale"] = scale
    if levers:
        record["levers"] = dict(levers)
    if rmse is not None:
        record["rmse"] = rmse
    if vs_baseline is not None:
        record["vs_baseline"] = vs_baseline
    if phases:
        record["phases"] = dict(phases)
    if extra:
        record["extra"] = dict(extra)
    return record


def bench_to_record(bench: dict, source: str = "bench") -> dict:
    """Normalize one ``bench.py`` stdout record into the ledger schema.
    Lever flags travel under ``levers`` so :func:`comparable_key` has a
    single place to read them from, old and new records alike."""
    return make_record(
        source=source,
        metric=str(bench.get("metric", "unknown")),
        value=float(bench.get("value", -1.0)),
        unit=str(bench.get("unit", "s")),
        device=bench.get("device"),
        scale=bench.get("scale"),
        levers={
            "solve_mode": bench.get("solve_mode", "auto"),
            "gather_dtype": bench.get("gather_dtype", "f32"),
            "sort_gather": bool(bench.get("sort_gather", False)),
            "fused_gather": bool(bench.get("fused_gather", False)),
            "fallback": bench.get("fallback", ""),
        },
        rmse=bench.get("holdout_rmse"),
        vs_baseline=bench.get("vs_baseline"),
        phases=bench.get("bucketize_stage_phases_s"),
        extra={
            key: bench[key]
            for key in (
                "iterations", "nnz", "error", "jit", "servingFleet",
                "quality", "bf16_gate", "ingestScaling", "cachedFleet",
                "shardedTrain", "migrationDrill", "sharedCache",
                "quantServe", "ckptResume",
            )
            if key in bench
        },
    )


def fleet_records(bench: dict, source: str = "bench") -> List[dict]:
    """The serving-fleet numbers a bench run attached
    (``bench["servingFleet"]``, from ``loadgen --replicas`` —
    docs/fleet.md) as their own ledger records, so serving scale gates
    alongside train time:

    - ``fleet_served_p50_s`` — seconds, lower-better → gated by
      ``pio perf diff`` at a per-record 0.25 band: the median of the
      drive is statistically stable, but it is still wall-clock from an
      in-process fleet sharing a possibly-contended CI box (the same
      reason the jax-cache compile-ratio assertion was retired), so the
      bar sits above scheduler weather and below a real 1.3×+ slowdown;
    - ``fleet_served_p99_s`` — seconds, lower-better, gated at a WIDER
      band (0.5): the p99 of a ~100-request in-process drive is one
      scheduler hiccup away from 2×, so only a serving collapse (an
      accidental sleep, a lock convoy) should fire the gate, not
      CI-box weather;
    - ``fleet_served_qps`` — higher-better, so it rides as a trend-only
      record (the gate only ever compares ``unit == "s"``).

    The replica count travels as ``scale``: a 3-replica run must never
    gate a 2-replica run. A failed fleet drive (``ok`` false) records
    nothing — its latencies measured a broken fleet, not the code."""
    fleet = bench.get("servingFleet")
    if not isinstance(fleet, dict) or not fleet.get("ok"):
        return []
    out: List[dict] = []
    # sharded drives are a different workload (scatter/gather to every
    # backend per query) — their latency must never gate a replicated
    # drive's, so the fleet shape lives in the METRIC NAME, like the
    # replica count lives in scale
    prefix = (
        "fleet_sharded_served" if fleet.get("sharded") else "fleet_served"
    )
    for key, metric, band in (
        ("servedP50Ms", f"{prefix}_p50_s", 0.25),
        ("servedP99Ms", f"{prefix}_p99_s", 0.5),
    ):
        value_ms = fleet.get(key)
        if isinstance(value_ms, (int, float)) and value_ms > 0:
            record = make_record(
                source=source,
                metric=metric,
                value=float(value_ms) / 1000.0,
                unit="s",
                device=bench.get("device"),
                scale=fleet.get("replicas"),
                extra={"sharded": bool(fleet.get("sharded"))},
            )
            record["noise_band"] = band
            out.append(record)
    qps = fleet.get("servedQPS")
    if isinstance(qps, (int, float)) and qps > 0:
        out.append(
            make_record(
                source=source,
                metric=f"{prefix}_qps",
                value=float(qps),
                unit="qps",
                device=bench.get("device"),
                scale=fleet.get("replicas"),
                extra={"sharded": bool(fleet.get("sharded"))},
            )
        )
    return out


def cache_records(bench: dict, source: str = "bench") -> List[dict]:
    """The serve-from-memory numbers a bench run attached
    (``bench["cachedFleet"]``, from ``loadgen --cached-hot-set`` —
    docs/fleet.md#cache) as their own ledger records:

    - ``fleet_cached_p99_s`` — seconds through the cache-on router on
      the Zipfian hot-set mix, lower-better → GATED, at the same wide
      record-declared band (0.5) as the fleet p99: the tail of a small
      in-process drive is one scheduler hiccup from 2×, so only a cache
      collapse (a lock convoy, an accidental always-miss) should fire;
    - ``fleet_cached_qps`` — the step-function headline, higher-better →
      trend-only (the gate only compares ``unit == "s"``); the uncached
      twin QPS and the speedup travel in ``extra`` so the trend renders
      the step, not just the number;
    - ``fleet_cache_hit_rate`` — trend-only ``ratio`` (the drill itself
      hard-gates correctness: byte identity and zero stale responses).

    A failed drive (``ok`` false) records nothing — its numbers measured
    a broken cache, not the code."""
    cached = bench.get("cachedFleet")
    if not isinstance(cached, dict) or not cached.get("ok"):
        return []
    out: List[dict] = []
    p99_ms = cached.get("cachedP99Ms")
    if isinstance(p99_ms, (int, float)) and p99_ms > 0:
        record = make_record(
            source=source,
            metric="fleet_cached_p99_s",
            value=float(p99_ms) / 1000.0,
            unit="s",
            device=bench.get("device"),
            scale=cached.get("replicas"),
            extra={"hitRate": cached.get("hitRate")},
        )
        record["noise_band"] = 0.5
        out.append(record)
    qps = cached.get("cachedQPS")
    if isinstance(qps, (int, float)) and qps > 0:
        out.append(
            make_record(
                source=source,
                metric="fleet_cached_qps",
                value=float(qps),
                unit="qps",
                device=bench.get("device"),
                scale=cached.get("replicas"),
                extra={
                    "uncachedQPS": cached.get("uncachedQPS"),
                    "speedup": cached.get("speedup"),
                    "hitRate": cached.get("hitRate"),
                },
            )
        )
    hit_rate = cached.get("hitRate")
    if isinstance(hit_rate, (int, float)):
        out.append(
            make_record(
                source=source,
                metric="fleet_cache_hit_rate",
                value=float(hit_rate),
                unit="ratio",
                device=bench.get("device"),
                scale=cached.get("replicas"),
            )
        )
    return out


def shared_cache_records(bench: dict, source: str = "bench") -> List[dict]:
    """The shared-tier numbers a bench run attached
    (``bench["sharedCache"]``, from ``loadgen --shared-cache-drill`` —
    docs/fleet.md#shared-cache-tier) as their own ledger records:

    - ``fleet_hedged_p99_s`` — seconds through the hedged router on the
      healthy (tier-up) phase of the drill, lower-better → GATED at the
      same wide record-declared band (0.5) as the other in-process
      serving tails: one scheduler hiccup doubles a small drive's p99,
      so only a real collapse (hedging gone wrong, a tier that blocks
      the request path) should fire;
    - ``fleet_shared_hit_rate`` — trend-only ``ratio`` (the drill
      itself hard-gates correctness: zero stale responses, byte
      identity across the kill, every degrade recorded).

    A failed drill (``ok`` false) records nothing — its numbers
    measured a broken tier, not the code."""
    shared = bench.get("sharedCache")
    if not isinstance(shared, dict) or not shared.get("ok"):
        return []
    out: List[dict] = []
    p99_ms = shared.get("hedgedP99Ms")
    if isinstance(p99_ms, (int, float)) and p99_ms > 0:
        record = make_record(
            source=source,
            metric="fleet_hedged_p99_s",
            value=float(p99_ms) / 1000.0,
            unit="s",
            device=bench.get("device"),
            extra={
                "sharedHitRate": shared.get("sharedHitRate"),
                "healthyQPS": shared.get("healthyQPS"),
            },
        )
        record["noise_band"] = 0.5
        out.append(record)
    hit_rate = shared.get("sharedHitRate")
    if isinstance(hit_rate, (int, float)):
        out.append(
            make_record(
                source=source,
                metric="fleet_shared_hit_rate",
                value=float(hit_rate),
                unit="ratio",
                device=bench.get("device"),
                extra={"degradesRecorded": shared.get("degradesRecorded")},
            )
        )
    return out


def quant_records(bench: dict, source: str = "bench") -> List[dict]:
    """The quantized-serving numbers a bench run attached
    (``bench["quantServe"]``, from the ``BENCH_QUANT`` block —
    docs/quantization.md) as their own ledger records:

    - ``serve_table_bytes`` — resident bytes of the int8 serving table
      (codes + per-row scales), lower-better → GATED: the count is
      deterministic for a given recipe, so any growth is a real layout
      regression, not noise. The f32 twin and the compression ratio
      travel in ``extra`` so ``pio perf trend`` can show the reduction
      without a second comparable group;
    - ``quant_topk_match_rate`` — trend-only ``ratio``: the fraction of
      probe users whose int8 top-k id SET matches f32 exactly. Serving
      hard-gates this at model load (:class:`~..quant.QuantGateError`);
      the bench just measures the margin.

    A failed block (``ok`` false or an ``error`` entry) records nothing
    — its numbers measured a broken table, not the code."""
    quant = bench.get("quantServe")
    if not isinstance(quant, dict) or not quant.get("ok"):
        return []
    out: List[dict] = []
    table_bytes = quant.get("tableBytes")
    if isinstance(table_bytes, (int, float)) and table_bytes > 0:
        out.append(
            make_record(
                source=source,
                metric="serve_table_bytes",
                value=float(table_bytes),
                unit="bytes",
                device=bench.get("device"),
                extra={
                    "ratio": quant.get("ratio"),
                    "f32Bytes": quant.get("f32Bytes"),
                    "tableDtype": quant.get("tableDtype"),
                    "rank": quant.get("rank"),
                    "nItems": quant.get("nItems"),
                },
            )
        )
    match_rate = quant.get("matchRate")
    if isinstance(match_rate, (int, float)):
        out.append(
            make_record(
                source=source,
                metric="quant_topk_match_rate",
                value=float(match_rate),
                unit="ratio",
                device=bench.get("device"),
                extra={"probes": quant.get("probes"), "k": quant.get("k")},
            )
        )
    return out


def quality_records(bench: dict, source: str = "bench") -> List[dict]:
    """The model-quality numbers a bench run attached
    (``bench["quality"]``, from the in-process feedback-stream drill —
    docs/observability.md#quality) as their own trend records, so
    ``pio perf trend`` shows the quality trajectory alongside latency:

    - ``quality_score_psi`` — the live score distribution's PSI vs the
      drill's pinned baseline (unit ``psi``, trend-only: PSI is not a
      lower-is-better wall-clock, and small-sample drill PSI is too
      noisy to gate; the serving-time gate lives in the rollout plane);
    - ``quality_feedback_hitrate`` — the feedback join's hit-rate (unit
      ``ratio``, trend-only for the same reason).

    A drill that failed (``ok`` false) records nothing."""
    quality = bench.get("quality")
    if not isinstance(quality, dict) or not quality.get("ok", True):
        return []
    out: List[dict] = []
    score_psi = quality.get("scorePsi")
    if isinstance(score_psi, (int, float)):
        out.append(
            make_record(
                source=source,
                metric="quality_score_psi",
                value=float(score_psi),
                unit="psi",
                device=bench.get("device"),
            )
        )
    hit_rate = quality.get("feedbackHitRate")
    if isinstance(hit_rate, (int, float)):
        out.append(
            make_record(
                source=source,
                metric="quality_feedback_hitrate",
                value=float(hit_rate),
                unit="ratio",
                device=bench.get("device"),
                extra={
                    "samples": quality.get("feedbackSamples"),
                },
            )
        )
    return out


def alert_records(bench: dict, source: str = "bench") -> List[dict]:
    """The alert-noisiness numbers a bench run attached
    (``bench["alerts"]``, from the in-process brownout drill —
    docs/slo.md) as trend-only ledger records, so alert hygiene is
    tracked across BENCH rounds like perf and quality already are:

    - ``alert_false_positives`` — control-run fires plus flaps (unit
      ``count``, trend-only: the gate only ever compares ``unit ==
      "s"``; the drill itself is the hard gate — a noisy round fails
      tier-1, the ledger shows the trajectory).

    A drill that failed (``ok`` false) records nothing — its counts
    measured a broken drill, not the alerting plane."""
    alerts = bench.get("alerts")
    if not isinstance(alerts, dict) or not alerts.get("ok"):
        return []
    false_positives = alerts.get("falsePositives")
    if not isinstance(false_positives, (int, float)):
        return []
    return [
        make_record(
            source=source,
            metric="alert_false_positives",
            value=float(false_positives),
            unit="count",
            device=bench.get("device"),
            extra={
                "fired": alerts.get("fired"),
                "cleared": alerts.get("cleared"),
            },
        )
    ]


def ingest_records(bench: dict, source: str = "bench") -> List[dict]:
    """The ingest-scaling numbers a bench run attached
    (``bench["ingestScaling"]``, from ``loadgen --ingest-scaling`` —
    docs/storage.md#partitioning) as their own trend records:

    - ``ingest_acked_qps`` — acked event writes per second through the
      partitioned write path (unit ``qps``, higher-better → trend-only:
      the gate only ever compares ``unit == "s"``).

    The partition count travels as ``scale``, exactly like the fleet
    records carry their replica count: ``comparable_key`` groups by
    scale, so ``pio perf diff`` never gates a 4-partition run against a
    1-partition run — each N has its own trajectory. A failed drive
    (``ok`` false) records nothing."""
    scaling = bench.get("ingestScaling")
    if not isinstance(scaling, dict) or not scaling.get("ok"):
        return []
    out: List[dict] = []
    counts = scaling.get("counts") or {}
    for key in sorted(counts, key=lambda k: int(k)):
        row = counts[key] or {}
        qps = row.get("ackedQPS")
        if isinstance(qps, (int, float)) and qps > 0:
            out.append(
                make_record(
                    source=source,
                    metric="ingest_acked_qps",
                    value=float(qps),
                    unit="qps",
                    device=bench.get("device"),
                    scale=int(key),
                    extra={
                        "writers": scaling.get("writers"),
                        "acked": row.get("acked"),
                        "inProcess": scaling.get("inProcess"),
                    },
                )
            )
    return out


def migration_records(bench: dict, source: str = "bench") -> List[dict]:
    """The live-migration drill numbers a bench run attached
    (``bench["migrationDrill"]``, from ``loadgen --migrate-drill`` —
    docs/storage.md#live-migration) as trend-only ledger records:

    - ``migration_drill_wall_s`` — full drill wall clock (unit
      ``wall_s``, NOT the gated ``s``: the drill is chaos choreography
      on a possibly-contended box, a trajectory not a gate);
    - ``migration_dualwrite_overhead`` — dual-write wave wall over the
      plain-write baseline wave (unit ``ratio``) — the ingest tax of
      mirroring, the number an operator sizes the migration window by.

    The layout move travels as ``scale`` verbatim (``"2->3"``):
    ``comparable_key`` groups by scale, so a 2→3 expansion and a 3→2
    merge never share a trajectory. A failed drill (``ok`` false)
    records nothing — its timings measured a broken run."""
    block = bench.get("migrationDrill")
    if not isinstance(block, dict) or not block.get("ok"):
        return []
    out: List[dict] = []
    scale = f"{block.get('oldPartitions')}->{block.get('newPartitions')}"
    extra = {
        k: block[k]
        for k in ("opsPerPhase", "lostAckedWrites", "duplicateFolds")
        if k in block
    }
    wall = block.get("wallS")
    if isinstance(wall, (int, float)) and wall > 0:
        out.append(
            make_record(
                source=source,
                metric="migration_drill_wall_s",
                value=float(wall),
                unit="wall_s",
                device=bench.get("device"),
                scale=scale,
                extra=extra,
            )
        )
    overhead = block.get("dualWriteOverhead")
    if isinstance(overhead, (int, float)) and overhead > 0:
        out.append(
            make_record(
                source=source,
                metric="migration_dualwrite_overhead",
                value=float(overhead),
                unit="ratio",
                device=bench.get("device"),
                scale=scale,
                extra=extra,
            )
        )
    return out


def sharded_records(bench: dict, source: str = "bench") -> List[dict]:
    """The sharded-train numbers a bench run attached
    (``bench["shardedTrain"]``, from the forced-virtual-device subprocess
    drive — docs/distributed_training.md) as their own ledger records:

    - ``train_sharded_s`` — wall-clock of the small sharded recipe (unit
      ``s``, lower-better → gated), with the SHARD COUNT as ``scale``
      exactly like ``ingest_acked_qps`` carries its partition count:
      ``comparable_key`` groups by scale, so ``pio perf diff`` never
      gates a 4-shard run against a 1-shard run — each N has its own
      trajectory. Records declare a wide ``noise_band`` (0.5): the drive
      is a subprocess on a possibly-contended CI box, so only a collapse
      should fire the gate, not scheduler weather.

    A failed drive (``ok`` false) records nothing — its wall-clock
    measured a broken run, not the code."""
    block = bench.get("shardedTrain")
    if not isinstance(block, dict) or not block.get("ok"):
        return []
    out: List[dict] = []
    counts = block.get("counts") or {}
    for key in sorted(counts, key=lambda k: int(k)):
        row = counts[key] or {}
        train_s = row.get("trainS")
        if isinstance(train_s, (int, float)) and train_s > 0:
            record = make_record(
                source=source,
                metric="train_sharded_s",
                value=float(train_s),
                unit="s",
                device=row.get("device"),
                scale=int(key),
                levers={
                    "solve_mode": row.get("solve_mode", "chunked"),
                    "gather_dtype": row.get("gather_dtype", "f32"),
                    "sort_gather": bool(row.get("sort_gather", True)),
                    "fused_gather": bool(row.get("fused_gather", False)),
                    "fallback": "",
                },
                rmse=row.get("rmse"),
                extra={
                    k: row[k]
                    for k in ("nnz", "iterations", "flopImbalance")
                    if k in row
                },
            )
            record["noise_band"] = 0.5
            out.append(record)
    return out


def ckpt_records(bench: dict, source: str = "bench") -> List[dict]:
    """The preemption-drill numbers a bench run attached
    (``bench["ckptResume"]``, from the SIGKILL + cross-shard-resume
    subprocess drive — docs/checkpoint.md#preemption-drill) as
    trend-only ledger records:

    - ``train_ckpt_overhead_ratio`` — checkpointed wall / plain wall of
      the same recipe at the same shard count (unit ``ratio``,
      deliberately NOT ``s``: the gate only compares lower-is-better
      ``s``/``bytes`` units, and the cost of never losing a run must
      never fail a perf gate on a contended CI box — the trajectory is
      the product). The resume wall, snapshot seconds, writer counters
      and the factor-equivalence evidence ride in ``extra`` so a
      creeping overhead or a tolerance near-miss is visible in history.

    The metric name is this family's namespace: ``comparable_key``
    groups by metric first, so these records can never gate — or be
    gated by — the ``train_sharded_s``/``quant``/``fleet`` families.
    A failed drill (``ok`` false) records nothing — its ratio measured
    a broken resume, not the writer."""
    block = bench.get("ckptResume")
    if not isinstance(block, dict) or not block.get("ok"):
        return []
    ratio = block.get("overheadRatio")
    if not isinstance(ratio, (int, float)) or ratio <= 0:
        return []
    return [
        make_record(
            source=source,
            metric="train_ckpt_overhead_ratio",
            value=float(ratio),
            unit="ratio",
            device=block.get("device"),
            scale=block.get("resumeShards"),
            extra={
                k: block[k]
                for k in (
                    "trainShards", "killStep", "resumedFrom", "resumeS",
                    "plainS", "ckptS", "snapshotS", "written", "dropped",
                    "errors", "maxAbsDiff",
                )
                if k in block
            },
        )
    ]


def lint_records(bench: dict, source: str = "bench") -> List[dict]:
    """The lint-sweep timings a bench run attached (``bench["lintSweep"]``,
    from the in-process cold-vs-warm engine drive — docs/lint.md#cache)
    as trend-only ledger records:

    - ``lint_wall_s`` — cold full-package sweep wall-clock (unit
      ``wall_s``, deliberately NOT ``s``: the gate only ever compares
      ``unit == "s"``, and a lint sweep on a contended CI box must
      never fail a perf gate — the trajectory is the product). The warm
      wall-clock, file count, and the byte-identity verdict ride along
      in ``extra`` so a cache regression (warm ≈ cold, or
      ``identical: false``) is visible in the ledger history.

    A failed sweep (``ok`` false) records nothing — its wall-clock
    measured a broken engine run, not the linter."""
    block = bench.get("lintSweep")
    if not isinstance(block, dict) or not block.get("ok"):
        return []
    cold_s = block.get("coldS")
    if not isinstance(cold_s, (int, float)) or cold_s <= 0:
        return []
    return [
        make_record(
            source=source,
            metric="lint_wall_s",
            value=float(cold_s),
            unit="wall_s",
            device=bench.get("device"),
            extra={
                "warmS": block.get("warmS"),
                "files": block.get("files"),
                "identical": block.get("identical"),
            },
        )
    ]


def append_record(path: str, record: dict) -> None:
    """Append one record as a JSON line, fsynced — the ledger is the
    durable evidence trail, a torn tail must cost at most one line."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    line = json.dumps(record, sort_keys=True) + "\n"
    with open(path, "a", encoding="utf-8") as fh:
        fh.write(line)
        fh.flush()
        os.fsync(fh.fileno())


def load_ledger(path: str) -> List[dict]:
    """Every parseable record in file order; unparseable lines (a torn
    append, hand-editing damage) are skipped, never fatal."""
    records: List[dict] = []
    try:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    parsed = json.loads(line)
                except ValueError:
                    continue
                if isinstance(parsed, dict) and "value" in parsed:
                    records.append(parsed)
    except OSError:
        return []
    return records


def load_bench_history(history_dir: str) -> List[dict]:
    """The checked-in ``BENCH_r0*.json`` driver records, normalized and
    ordered by round. A round whose bench failed outright (``parsed``
    null — the r01 bring-up failure) contributes nothing."""
    records: List[dict] = []
    for path in sorted(glob.glob(os.path.join(history_dir, "BENCH_r*.json"))):
        match = _BENCH_ROUND_RE.search(os.path.basename(path))
        if not match:
            continue
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if not isinstance(parsed, dict):
            continue
        records.append(
            bench_to_record(parsed, source=f"bench_r{int(match.group(1)):02d}")
        )
    return records


def _device_class(device: Optional[str]) -> str:
    text = (device or "").lower()
    if "tpu" in text:
        return "tpu"
    if "cpu" in text:
        return "cpu"
    if "gpu" in text or "cuda" in text:
        return "gpu"
    return text or "unknown"


def comparable_key(record: dict) -> Tuple:
    """Records sharing this key measure the same thing and may gate each
    other: metric, device *class* (chip generations differ less than a
    CPU fallback differs from any chip), scale, and every lever flag."""
    levers = record.get("levers") or {}
    return (
        record.get("metric"),
        _device_class(record.get("device")),
        record.get("scale"),
        levers.get("solve_mode", "auto"),
        levers.get("gather_dtype", "f32"),
        bool(levers.get("sort_gather", False)),
        bool(levers.get("fused_gather", False)),
    )


def _median(values: List[float]) -> float:
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    return (
        ordered[mid]
        if n % 2
        else (ordered[mid - 1] + ordered[mid]) / 2.0
    )


def _key_dict(key: Tuple) -> dict:
    """A comparable key rendered as the verdict dict both gates share."""
    return {
        "metric": key[0],
        "device_class": key[1],
        "scale": key[2],
        "solve_mode": key[3],
        "gather_dtype": key[4],
        "sort_gather": key[5],
        "fused_gather": key[6],
    }


def _gateable_groups(records: List[dict]) -> Dict[Tuple, List[dict]]:
    """Records eligible for the regression gate, grouped by comparable
    key in given (= chronological) order: lower-is-better units only
    (seconds, plus deterministic byte counts like ``serve_table_bytes``),
    failed runs (value -1) and error-carrying runs excluded — a
    quality-gate failure carries a real (positive) wall time but
    measured an invalid run, so it must neither be gated nor pollute a
    baseline median."""
    groups: Dict[Tuple, List[dict]] = {}
    for record in records:
        if record.get("unit", "s") not in ("s", "bytes"):
            continue
        value = record.get("value")
        if not isinstance(value, (int, float)) or value <= 0:
            continue
        if record.get("error") or (record.get("extra") or {}).get("error"):
            continue
        groups.setdefault(comparable_key(record), []).append(record)
    return groups


#: ``find_no_prior`` only reports groups whose latest record sits
#: within this many trailing records — an abandoned one-off lever
#: experiment ages out of the diff output once enough newer evidence
#: lands, instead of printing a stale "no comparable prior" forever.
NO_PRIOR_RECENT_WINDOW = 12


def find_no_prior(
    records: List[dict],
    min_history: int = MIN_HISTORY,
    recent_window: int = NO_PRIOR_RECENT_WINDOW,
) -> List[dict]:
    """Gate-able groups whose latest record has FEWER than
    ``min_history`` predecessors — measured, but with nothing honest to
    compare against. Distinct from "stable": lever flags are part of
    the comparable key, so flipping a default starts a fresh group and
    a silent exit-0 would read as "no regression" when the truth is
    "no baseline yet" (``pio perf diff`` prints these explicitly —
    docs/performance.md#perf-ledger). One verdict dict per group, with
    the history count the group still needs. Only groups still ACTIVE
    — latest record within the trailing ``recent_window`` gate-able
    records — are reported, so a forgotten one-off experiment stops
    cluttering the diff once newer evidence buries it."""
    groups = _gateable_groups(records)
    # recency = position in the gate-able stream (same record objects
    # the groups hold, so id() is a stable key even for duplicates)
    gateable_ids = {id(r) for g in groups.values() for r in g}
    positions: Dict[int, int] = {}
    for record in records:
        if id(record) in gateable_ids and id(record) not in positions:
            positions[id(record)] = len(positions)
    total = len(positions)
    out: List[dict] = []
    for key, group in groups.items():
        if len(group) >= min_history + 1:
            continue
        latest = group[-1]
        if total > recent_window and (
            positions.get(id(latest), total) < total - recent_window
        ):
            continue  # stale experiment: aged out of the report
        out.append(
            {
                "key": _key_dict(key),
                "latest": float(latest["value"]),
                "latest_source": latest.get("source"),
                "history": len(group) - 1,
                "needed": min_history,
            }
        )
    return out


def detect_regressions(
    records: List[dict],
    noise_band: float = DEFAULT_NOISE_BAND,
    min_history: int = MIN_HISTORY,
) -> List[dict]:
    """Per comparable group (records in given = chronological order):
    compare the latest value against the median of its predecessors.
    Lower-is-better (``unit in ("s", "bytes")``; other units are
    trend-only).
    A record may carry its own ``noise_band`` (a noisier measurement —
    the fleet drive's small-sample p99); the group's effective band is
    the WIDER of it and the caller's, so a noisy metric can never be
    held to a tighter bar than its producer declared. Returns one
    verdict dict per flagged group — empty means clean (groups without
    enough history are NOT clean, they are unestablished — see
    :func:`find_no_prior`)."""
    groups = _gateable_groups(records)
    flagged: List[dict] = []
    for key, group in groups.items():
        if len(group) < min_history + 1:
            continue
        latest = group[-1]
        prior = [float(r["value"]) for r in group[:-1]]
        baseline = _median(prior)
        if baseline <= 0:
            continue
        try:
            declared = max(
                float(r.get("noise_band", 0.0) or 0.0) for r in group
            )
        except (TypeError, ValueError):
            declared = 0.0  # a hand-edited band never breaks the gate
        band = max(noise_band, declared)
        ratio = float(latest["value"]) / baseline
        if ratio > 1.0 + band:
            flagged.append(
                {
                    "key": _key_dict(key),
                    "latest": float(latest["value"]),
                    "latest_source": latest.get("source"),
                    "baseline_median": round(baseline, 4),
                    "ratio": round(ratio, 4),
                    "noise_band": band,
                    "history": len(prior),
                }
            )
    return flagged


def render_trend(records: List[dict]) -> str:
    """The full trajectory, grouped by comparable key, chronological
    within each group — the ``pio perf trend`` table."""
    if not records:
        return "(no performance records)"
    groups: Dict[Tuple, List[dict]] = {}
    for record in records:
        groups.setdefault(comparable_key(record), []).append(record)
    lines: List[str] = []
    for key in sorted(groups, key=str):
        metric, device_class, scale = key[0], key[1], key[2]
        levers = (
            f"solve={key[3]} gather={key[4]}"
            + (" sort" if key[5] else "")
            + (" fused" if key[6] else "")
        )
        lines.append(
            f"{metric} [{device_class} scale={scale} {levers}]"
        )
        for record in groups[key]:
            # a foreign/hand-edited line may carry non-numeric fields;
            # the trend must render around it, never traceback
            value = record.get("value", 0.0)
            if not isinstance(value, (int, float)):
                continue
            rmse = record.get("rmse")
            vs = record.get("vs_baseline")
            lines.append(
                f"  {record.get('source', '?'):<14}"
                f"{value:>10.3f} {record.get('unit', 's')}"
                + (
                    f"  vs_baseline={vs:g}"
                    if isinstance(vs, (int, float))
                    else ""
                )
                + (
                    f"  rmse={rmse:g}"
                    if isinstance(rmse, (int, float))
                    else ""
                )
            )
    return "\n".join(lines)
