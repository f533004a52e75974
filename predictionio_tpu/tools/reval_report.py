"""Summarize TPU_REVALIDATION.jsonl into the PERF.md-ready tables.

The revalidation queue (``tpu_revalidate``) appends one JSON line per
step; this tool folds them into a readable report — baseline spread,
the A/B lever matrix with RMSE gates, compiled-path verdicts, and the
serving sweeps — so the analysis is one command, the same every time.

Usage: ``python -m predictionio_tpu.tools.reval_report [path]``
(default: repo-root ``TPU_REVALIDATION.jsonl``; reads ALL runs in the
file, newest occurrence of each step wins).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(path: str) -> dict:
    """Newest record per step name."""
    steps: dict = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if "step" in rec:
                steps[rec["step"]] = rec
    return steps


def _fmt_bench(rec: dict) -> str:
    if rec is None:
        return "— not run"
    if "error" in rec:
        return f"ERROR: {rec['error']}"
    parts = [f"{rec.get('value')}s train"]
    if rec.get("iteration_s"):
        it = rec["iteration_s"]
        steady = it[1:] if len(it) > 1 else it
        parts.append(f"steady iter {sum(steady)/len(steady):.3f}s")
    for k, lbl in (("holdout_rmse", "rmse"), ("bucketize_stage_s", "stage"),
                   ("est_hbm_util", "hbm_util"), ("device", "")):
        if rec.get(k) is not None:
            parts.append(f"{lbl + ' ' if lbl else ''}{rec[k]}")
    if rec.get("rmse_gate"):
        parts.append(f"gate={rec['rmse_gate']}")
    if rec.get("platform", "tpu") != "tpu":
        parts.append(f"RAN ON {rec['platform']} — INVALID")
    return ", ".join(str(p) for p in parts)


def report(steps: dict) -> str:
    out = ["# TPU revalidation report", ""]

    out.append("## ALS bench (ML-20M shape, rank 50, 10 iter)")
    # repeat legs are named baseline_f32_rN for N=2..--repeats: derive
    # them from the records present rather than hard-coding N<=3
    repeat_names = sorted(
        (n for n in steps
         if n.startswith("baseline_f32_r") and n[14:].isdigit()),
        key=lambda n: int(n[14:]),
    )
    for name in ("baseline_f32", *repeat_names,
                 "bf16_gather", "sort_gather", "bf16_plus_sort",
                 "fused_gather", "fused_plus_bf16"):
        if name in steps:
            out.append(f"- **{name}**: {_fmt_bench(steps[name])}")
    var = steps.get("baseline_variance")
    if var:
        out.append(
            f"- spread over {var.get('runs')} runs: train_s "
            f"{var.get('train_s')} (Δ {var.get('train_s_spread')}s), "
            f"steady iters {var.get('steady_iter_s')}"
        )

    out.append("")
    out.append("## Compiled-path verdicts")
    for name in ("fused_smoke", "mesh_pallas", "flash_pallas"):
        rec = steps.get(name)
        if rec is None:
            out.append(f"- {name}: — not run")
        elif rec.get("ok"):
            detail = {
                k: v for k, v in rec.items()
                if any(t in k for t in ("rel", "err", "_ms_"))
            }
            out.append(
                f"- **{name}**: OK compiled={rec.get('compiled')} "
                f"({detail})"
            )
        else:
            out.append(f"- **{name}**: FAILED — {rec}")

    rec = steps.get("implicit_gate")
    if rec is not None:
        out.append("")
        out.append("## Implicit-mode quality gate (precision@10)")
        if "skipped" in rec:
            out.append(f"- skipped: {rec['skipped']}")
        elif "error" in rec:
            out.append(f"- ERROR: {rec['error']}")
        else:
            out.append(
                f"- f32 {rec.get('p10_f32')} vs lever "
                f"{rec.get('p10_lever')} (Δ {rec.get('delta')}) — "
                f"gate={rec.get('gate')}, lever={rec.get('lever')}"
            )

    rec = steps.get("profile_trace")
    if rec is not None:
        out.append("")
        out.append("## Profiler trace (op-level device timings)")
        if "error" in rec or "parse_error" in rec:
            out.append(f"- {rec.get('error') or rec.get('parse_error')} "
                       f"(trace dir: {rec.get('trace_dir')})")
        else:
            for plane, data in (rec.get("planes") or {}).items():
                out.append(f"- **{plane}** total {data.get('total_ms')} ms")
                for op, ms in list(data.get("top_ops_ms", {}).items())[:8]:
                    out.append(f"  - {op}: {ms} ms")
            out.append(f"- full trace: {rec.get('xplane')}")

    rec = steps.get("dispatch_bench")
    if rec and "catalogs" in rec:
        out.append("")
        out.append("## Device dispatch (batch-512 top-10)")
        out.append("| catalog | ms/batch | implied QPS @ depth 1 |")
        out.append("|---|---|---|")
        for n, d in rec["catalogs"].items():
            out.append(
                f"| {n} | {d['dispatch_ms_per_batch']} | "
                f"{d['implied_qps_at_depth1']:.0f} |"
            )

    for tag, title in (("", "Serving loadgen — quickstart catalog"),
                       ("_big", "Serving loadgen — 60k-item catalog")):
        rows = []
        for depth in (1, 2, 4, 8):
            h = steps.get(f"loadgen_depth{depth}{tag}")
            p = steps.get(f"loadgen_inproc_depth{depth}{tag}")
            if h or p:
                rows.append((depth, h, p))
        if rows:
            out.append("")
            out.append(f"## {title}")
            out.append(
                "| depth | HTTP QPS | HTTP p99 ms | in-proc QPS "
                "| in-proc p99 ms |"
            )
            out.append("|---|---|---|---|---|")
            for depth, h, p in rows:
                def cell(r, k):
                    if r is None:
                        return "—"
                    return r.get(k, f"ERR:{r.get('error', '?')[:40]}")
                out.append(
                    f"| {depth} | {cell(h, 'qps')} | {cell(h, 'p99_ms')} "
                    f"| {cell(p, 'qps')} | {cell(p, 'p99_ms')} |"
                )

    covered = {
        "baseline_f32", "baseline_variance", "bf16_gather", "sort_gather",
        "bf16_plus_sort", "fused_gather", "fused_plus_bf16",
        "fused_smoke", "mesh_pallas", "flash_pallas", "dispatch_bench",
        "implicit_gate", "profile_trace",
    } | set(repeat_names) | {
        f"loadgen_{kind}depth{d}{t}"
        for kind in ("", "inproc_") for d in (1, 2, 4, 8) for t in ("", "_big")
    } | {f"{n}_gate" for n in ("bf16_gather", "sort_gather",
                               "bf16_plus_sort", "fused_gather",
                               "fused_plus_bf16")}
    extra = sorted(set(steps) - covered)
    if extra:
        out.append("")
        out.append("## Other steps")
        for name in extra:
            out.append(f"- {name}: {json.dumps(steps[name])[:160]}")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    path = argv[0] if argv else os.path.join(REPO, "TPU_REVALIDATION.jsonl")
    if not os.path.exists(path):
        print(f"no evidence file at {path}", file=sys.stderr)
        return 1
    print(report(load(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
