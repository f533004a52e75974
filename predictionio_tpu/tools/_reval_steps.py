"""Single-purpose TPU revalidation steps (VERDICT r3 items 3 and 5).

Each subcommand runs ONE device experiment and prints ONE JSON line on
stdout; ``tpu_revalidate`` invokes them in subprocesses so a step past
its timeout is a recorded timeout, not a dead queue. They are deliberately
tiny: the point is to exercise code paths that have never been COMPILED
on a TPU (Mosaic lowering inside shard_map, the fused gather+Gramian
kernel) with the one available chip, and to time the pure device-dispatch
serving cycle that the HTTP loadgen numbers fold into their wire costs.

Usage: ``python -m predictionio_tpu.tools._reval_steps <step>`` where
step is ``mesh_pallas`` | ``fused_smoke`` | ``dispatch_bench``.
"""

from __future__ import annotations

import json
import sys
import time


def _train_pair(cfg_kwargs_a: dict, cfg_kwargs_b: dict, mesh_for_a=False):
    """Train the same small problem under two configs; return factor pairs
    and max relative difference."""
    import numpy as np

    from ..ops.als import ALSConfig, als_train_coo
    from ..parallel.mesh import create_mesh

    rng = np.random.default_rng(11)
    nnz, n_u, n_i = 30_000, 900, 250
    w = 1.0 / np.arange(1, n_u + 1) ** 0.8
    u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    v = rng.integers(1, 6, nnz).astype(np.float32)

    fa = als_train_coo(
        u, i, v, n_users=n_u, n_items=n_i, cfg=ALSConfig(**cfg_kwargs_a),
        mesh=create_mesh() if mesh_for_a else None,
    )
    fb = als_train_coo(
        u, i, v, n_users=n_u, n_items=n_i, cfg=ALSConfig(**cfg_kwargs_b)
    )
    diffs = []
    for x, y in ((fa.user_factors, fb.user_factors),
                 (fa.item_factors, fb.item_factors)):
        x, y = np.asarray(x), np.asarray(y)
        diffs.append(
            float(np.max(np.abs(x - y) / (np.abs(y) + 1e-6)))
        )
    return max(diffs)


def step_mesh_pallas() -> dict:
    """COMPILED (non-interpret) run of the shard_map-wrapped pallas solve
    on a real device mesh — the path `ops/als.py` routes under a mesh,
    which before this step had only ever executed in interpret mode on
    the CPU test mesh. Equality vs the chunked XLA solve."""
    import jax

    base = dict(rank=12, iterations=2, lambda_=0.05, seed=2)
    max_rel = _train_pair(
        dict(base, solve_mode="pallas"),
        dict(base, solve_mode="chunked"),
        mesh_for_a=True,
    )
    return {
        "step": "mesh_pallas_compiled",
        "backend": jax.default_backend(),
        "compiled": jax.default_backend() == "tpu",
        "n_mesh_devices": len(jax.devices()),
        "max_rel_vs_chunked": round(max_rel, 6),
        "ok": max_rel < 2e-2,
    }


def step_fused_smoke() -> dict:
    """COMPILED gramian_fused: kernel-level equality vs the einsum build
    at shapes that exercise K tiling and padding, plus a small end-to-end
    fused train vs the chunked solve. First Mosaic validation of the
    per-row-DMA gather kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.pallas_kernels import gramian_fused

    worst = 0.0
    for (b, k, n, r, seed) in (
        (32, 16, 500, 56, 0), (16, 512, 300, 56, 1), (8, 1024, 200, 24, 2),
        (25, 13, 77, 16, 3),
        # bench-realistic wide buckets: k=8192 hits the single-call SMEM
        # high-water mark ([4, 8192] int32 index block = the full
        # _FUSED_SMEM_IDX budget), k=32768 exercises the K-slice split —
        # both must survive Mosaic BEFORE the full-scale A/B commits
        (4, 8192, 300, 56, 4), (2, 32768, 300, 56, 5),
    ):
        rng = np.random.default_rng(seed)
        y = rng.standard_normal((n, r), dtype=np.float32)
        idx = rng.integers(0, n, (b, k)).astype(np.int32)
        w2 = (rng.random((b, k)) < 0.7).astype(np.float32)
        rhs = rng.standard_normal((b, k)).astype(np.float32) * w2
        ridge = rng.random(b).astype(np.float32)
        a, bv = gramian_fused(jnp.asarray(y), jnp.asarray(idx),
                              jnp.asarray(w2), jnp.asarray(rhs),
                              jnp.asarray(ridge))
        g = y[idx]
        a_ref = np.einsum("bkr,bk,bks->brs", g, w2, g) + (
            ridge[:, None, None] * np.eye(r, dtype=np.float32)
        )
        b_ref = np.einsum("bkr,bk->br", g, rhs)
        scale = float(np.max(np.abs(a_ref))) + 1e-6
        worst = max(
            worst,
            float(np.max(np.abs(np.asarray(a) - a_ref))) / scale,
            float(np.max(np.abs(np.asarray(bv) - b_ref))) / scale,
        )

    base = dict(rank=12, iterations=2, lambda_=0.05, seed=2)
    max_rel = _train_pair(
        dict(base, solve_mode="pallas", fused_gather=True),
        dict(base, solve_mode="chunked"),
    )
    return {
        "step": "fused_kernel_compiled",
        "backend": jax.default_backend(),
        "compiled": jax.default_backend() == "tpu",
        "kernel_max_rel": round(worst, 6),
        "train_max_rel_vs_chunked": round(max_rel, 6),
        "ok": worst < 1e-3 and max_rel < 2e-2,
    }


def step_dispatch_bench() -> dict:
    """Pure device-dispatch cycle for the serving hot op: batch-512 top-10
    over catalogs up to big-catalog shapes (60k/120k items — streaming
    kernel territory). Separates 'the device' from 'the wire' in the
    ≥10k QPS/chip question: in-process and HTTP loadgen numbers fold the
    host stack and the wire into every cycle; this is the floor the
    chip itself sets per batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.pallas_kernels import top_k_streaming

    import os

    reps = int(os.environ.get("PIO_DISPATCH_REPS", "50"))
    batch, rank, k = 512, 50, 10
    rng = np.random.default_rng(0)
    out = {
        "step": "dispatch_bench",
        "backend": jax.default_backend(),
        "batch": batch, "rank": rank, "k": k,
        "catalogs": {},
    }
    for n_items in (2_700, 27_000, 60_000, 120_000):
        items = jnp.asarray(
            rng.standard_normal((n_items, rank), dtype=np.float32)
        )
        q = jnp.asarray(
            rng.standard_normal((batch, rank), dtype=np.float32)
        )
        s, i = top_k_streaming(q, items, k)  # compile
        jax.block_until_ready((s, i))
        t0 = time.monotonic()
        for _ in range(reps):
            s, i = top_k_streaming(q, items, k)
        jax.block_until_ready((s, i))
        per_batch_ms = (time.monotonic() - t0) / reps * 1e3
        out["catalogs"][str(n_items)] = {
            "dispatch_ms_per_batch": round(per_batch_ms, 3),
            "implied_qps_at_depth1": round(batch / (per_batch_ms / 1e3), 0),
        }
    return out


def step_flash_pallas() -> dict:
    """COMPILED flash-attention kernel vs the XLA online-softmax path —
    first Mosaic validation, plus a timing rep at a serving-realistic
    shape."""
    import jax
    import numpy as np

    from ..ops.attention import flash_attention, flash_attention_pallas

    worst = 0.0
    for (b, h, lq, lk, d, causal, seed) in (
        (2, 4, 256, 256, 32, True, 0),
        (1, 2, 60, 60, 8, False, 1),
        (2, 8, 1024, 1024, 64, True, 2),
    ):
        rng = np.random.default_rng(seed)
        q = rng.normal(size=(b, h, lq, d)).astype(np.float32)
        k = rng.normal(size=(b, h, lk, d)).astype(np.float32)
        v = rng.normal(size=(b, h, lk, d)).astype(np.float32)
        got = np.asarray(flash_attention_pallas(q, k, v, causal=causal))
        ref = np.asarray(flash_attention(q, k, v, causal=causal))
        worst = max(worst, float(np.max(np.abs(got - ref))))

    rec = {
        "step": "flash_pallas",
        "backend": jax.default_backend(),
        "compiled": jax.default_backend() == "tpu",
        "max_abs_err": round(worst, 8),
        "ok": worst < 1e-3,
    }
    if jax.default_backend() == "tpu":
        # timing only where it means something (interpret mode off-TPU
        # would burn minutes to record incomparable numbers)
        q = np.random.default_rng(3).normal(
            size=(4, 8, 2048, 64)
        ).astype(np.float32)
        for name, fn in (("pallas", flash_attention_pallas),
                         ("xla", flash_attention)):
            out = fn(q, q, q, causal=True)
            jax.block_until_ready(out)
            t0 = time.monotonic()
            for _ in range(10):
                out = fn(q, q, q, causal=True)
            jax.block_until_ready(out)
            rec[f"{name}_ms_2048"] = round(
                (time.monotonic() - t0) / 10 * 1e3, 3
            )
    return rec


def step_implicit_gate() -> dict:
    """Ranking-quality gate for the IMPLICIT ALS path (VERDICT r4 item
    5). The queue's RMSE gate certifies levers on explicit mode only;
    implicit training (Hu-Koren confidence weighting — MLlib
    ``trainImplicit`` semantics, the similarproduct template's mode)
    exercises different code: the YᵀY base term, c−1 Gramian weights,
    c·p right-hand sides. This step trains a cluster-structured implicit
    dataset twice — reference f32 config, then the levered config from
    the same ``BENCH_*`` envs bench.py reads — and gates on
    precision@10 over held-out interactions. Without any lever env set
    it A/Bs bf16 gathers (the most likely adoption candidate); the
    queue always passes BENCH_GATHER_DTYPE explicitly so this
    standalone default cannot leak into a certification where bf16
    failed its explicit gate."""
    import os

    import numpy as np

    import jax

    from ..ops.als import ALSConfig, als_train_coo

    rng = np.random.default_rng(17)
    n_u, n_i, nnz, n_c = 20_000, 5_000, 1_500_000, 64
    # cluster-preference structure: most events hit the user's own item
    # cluster, the rest are uniform noise — learnable, cheap to generate
    uc = rng.integers(0, n_c, n_u)
    ic = rng.integers(0, n_c, n_i)
    users = rng.integers(0, n_u, nnz).astype(np.int64)
    in_cluster = rng.random(nnz) < 0.7
    items = rng.integers(0, n_i, nnz).astype(np.int64)
    by_cluster = [np.where(ic == c)[0] for c in range(n_c)]
    for c in range(n_c):
        m = in_cluster & (uc[users] == c)
        if m.any() and len(by_cluster[c]):
            items[m] = rng.choice(by_cluster[c], m.sum())

    holdout = rng.random(nnz) < 0.1
    tr_u, tr_i = users[~holdout], items[~holdout]
    # collapse duplicates into counts: value magnitude IS the implicit
    # confidence input (c = 1 + alpha·val)
    pair = tr_u * n_i + tr_i
    uniq, counts = np.unique(pair, return_counts=True)
    tr_u = (uniq // n_i).astype(np.int32)
    tr_i = (uniq % n_i).astype(np.int32)
    tr_v = counts.astype(np.float32)

    base = dict(rank=32, iterations=5, lambda_=0.05, alpha=10.0,
                implicit_prefs=True, seed=3)
    # tri-state lever envs mirror bench.py round 12: unset rides the
    # ALSConfig defaults (sort ON for bucketized inputs; fused resolves
    # with the solver), "0"/"1" force the leg explicitly
    sort_env = os.environ.get("BENCH_SORT_GATHER")
    fused_env = os.environ.get("BENCH_FUSED_GATHER")
    lever = dict(
        gather_dtype=os.environ.get("BENCH_GATHER_DTYPE", "bf16"),
        sort_gather_indices=None if sort_env is None else sort_env == "1",
        fused_gather=None if fused_env is None else fused_env == "1",
    )
    if lever["fused_gather"]:
        lever["solve_mode"] = "pallas"

    # holdout positives per user, minus train items (rank the unseen)
    ho_by_user: dict = {}
    for u, i in zip(users[holdout], items[holdout]):
        ho_by_user.setdefault(int(u), set()).add(int(i))
    train_by_user: dict = {}
    for u, i in zip(tr_u, tr_i):
        train_by_user.setdefault(int(u), set()).add(int(i))
    eval_users = [u for u in ho_by_user
                  if ho_by_user[u] - train_by_user.get(u, set())][:2000]

    def precision_at_10(cfg_kwargs: dict) -> float:
        f = als_train_coo(tr_u, tr_i, tr_v, n_users=n_u, n_items=n_i,
                          cfg=ALSConfig(**cfg_kwargs))
        uf = np.asarray(f.user_factors)
        yf = np.asarray(f.item_factors)
        scores = uf[eval_users] @ yf.T  # [2000, n_i] — small
        hits, total = 0, 0
        for row, u in enumerate(eval_users):
            s = scores[row]
            seen = train_by_user.get(u, set())
            if seen:
                s[list(seen)] = -np.inf  # rank only unseen items
            top = np.argpartition(-s, 10)[:10]
            want = ho_by_user[u] - seen
            hits += len(set(top.tolist()) & want)
            total += 10
        return hits / total

    p_ref = precision_at_10(dict(base))
    p_lever = precision_at_10(dict(base, **lever))
    delta = p_lever - p_ref
    return {
        "step": "implicit_gate",
        "backend": jax.default_backend(),
        "n_users": n_u, "n_items": n_i, "train_nnz": int(len(tr_v)),
        "eval_users": len(eval_users),
        "lever": {k: v for k, v in lever.items()},
        "p10_f32": round(p_ref, 5),
        "p10_lever": round(p_lever, 5),
        "delta": round(delta, 5),
        # ranking metrics are noisier than RMSE: absolute -0.005 bound
        "gate": "pass" if delta >= -0.005 else "FAIL",
        "ok": delta >= -0.005,
    }


def step_profile_trace() -> dict:
    """Capture a real profiler trace of the two hot paths (VERDICT r4
    item 7): one warm ALS training pass and a burst of serving top-k
    dispatches, under ``jax.profiler.trace``. The summary is parsed
    natively with ``jax.profiler.ProfileData`` (no TensorBoard needed)
    and recorded into the evidence file, so the HBM-utilization story
    can graduate from analytic byte accounting to measured op timings;
    the full trace stays on disk for TensorBoard's profile plugin."""
    import glob
    import os

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..ops.als import ALSConfig, als_train_coo
    from ..ops.pallas_kernels import top_k_streaming

    trace_dir = os.environ.get("PIO_PROFILE_DIR", "/tmp/pio-profile")
    os.makedirs(trace_dir, exist_ok=True)

    rng = np.random.default_rng(9)
    n_u, n_i, nnz = 60_000, 10_000, 2_000_000
    w = 1.0 / np.arange(1, n_u + 1) ** 0.8
    u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    v = rng.integers(1, 6, nnz).astype(np.float32)
    cfg = ALSConfig(rank=32, iterations=2, lambda_=0.05, seed=4)

    items = jnp.asarray(
        rng.standard_normal((60_000, 50), dtype=np.float32)
    )
    q = jnp.asarray(rng.standard_normal((512, 50), dtype=np.float32))

    # warm both programs OUTSIDE the trace: the trace should show the
    # steady-state op mix, not one giant XlaCompile block
    als_train_coo(u, i, v, n_users=n_u, n_items=n_i, cfg=cfg)
    jax.block_until_ready(top_k_streaming(q, items, 10))

    with jax.profiler.trace(trace_dir):
        f = als_train_coo(u, i, v, n_users=n_u, n_items=n_i, cfg=cfg)
        jax.block_until_ready((f.user_factors, f.item_factors))
        for _ in range(20):
            s, idx = top_k_streaming(q, items, 10)
        jax.block_until_ready((s, idx))

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    rec = {
        "step": "profile_trace",
        "backend": jax.default_backend(),
        "trace_dir": trace_dir,
    }
    if not paths:
        rec["error"] = "trace produced no .xplane.pb"
        return rec
    rec["xplane"] = paths[-1]
    try:
        pd = jax.profiler.ProfileData.from_file(paths[-1])
        planes = {}
        for plane in pd.planes:
            by_op: dict = {}
            total = 0.0
            for line in plane.lines:
                for ev in line.events:
                    d = ev.duration_ns or 0
                    by_op[ev.name] = by_op.get(ev.name, 0.0) + d
                    total += d
            top = sorted(by_op.items(), key=lambda kv: -kv[1])[:12]
            planes[plane.name] = {
                "total_ms": round(total / 1e6, 3),
                "top_ops_ms": {
                    k[:80]: round(ns / 1e6, 3) for k, ns in top
                },
            }
        # the device plane is the measurement; host planes are context
        rec["planes"] = {
            name: data for name, data in planes.items()
            if "TPU" in name or "/device" in name.lower()
        } or planes
    except Exception as exc:
        rec["parse_error"] = f"{type(exc).__name__}: {exc}"
    return rec


STEPS = {
    "mesh_pallas": step_mesh_pallas,
    "fused_smoke": step_fused_smoke,
    "dispatch_bench": step_dispatch_bench,
    "flash_pallas": step_flash_pallas,
    "implicit_gate": step_implicit_gate,
    "profile_trace": step_profile_trace,
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) != 1 or argv[0] not in STEPS:
        print(f"usage: _reval_steps {{{'|'.join(STEPS)}}}", file=sys.stderr)
        return 2
    from ..utils.jax_cache import enable_compilation_cache

    enable_compilation_cache()
    rec = STEPS[argv[0]]()
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
