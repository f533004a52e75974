"""Offline TPU compile of the exact bench/serving programs (deviceless).

Two jobs, one mechanism — ``jit(fn).lower(avals).compile()`` against a
compile-only v5e topology (``jax.experimental.topologies``; no chip
attached):

1. **Full-program validation.** ``tests/test_mosaic_aot.py`` compiles
   each Pallas kernel in isolation; this tool compiles the WHOLE
   bench-shape ALS programs (``_als_half`` + ``_als_iteration`` per
   lever variant, every bucket, real ML-20M-shaped bucketization) and
   the serving top-k dispatch at the four catalog sizes the queue's
   ``dispatch_bench`` step measures. A lowering problem anywhere in the
   real program surfaces here, offline, instead of on the chip.

2. **Cache pre-warming (experimental).** The compiled executables land
   in the persistent compilation cache (``utils/jax_cache``). If the
   real chip computes the same cache key as the deviceless topology
   (same libtpu, same program, same options), the chip run skips
   these compiles entirely; if the key differs, the attempt cost
   no chip time. Either way the compile *times* recorded here bound
   what the chip run will pay.

Usage::

    python -m predictionio_tpu.tools.prewarm_cache [--scale 1.0]
        [--variants f32,bf16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: ALSConfig kwargs per lever variant; solve_mode is "pallas" because
#: that is what bench's "auto" resolves to on a TPU backend — the
#: program compiled here must BE the program the chip runs.
VARIANTS = {
    "f32": dict(gather_dtype="f32"),
    "bf16": dict(gather_dtype="bf16"),
}

DISPATCH_CATALOGS = (2_700, 27_000, 60_000, 120_000)


def _memory_record(compiled) -> dict:
    """XLA's OWN numbers for the compiled program — upgrades the
    hand-computed HBM accounting in PERF.md to compiler-reported data:
    ``temp_gb`` is the peak scratch the program actually allocates
    (does the [B, K, R] gathered intermediate materialize?), and
    ``bytes_accessed_gb``/``flops`` come from the compiler's cost model
    when it exposes one. Fully best-effort: an analysis gap must never
    turn a successful (cache-populating) compile into a failure."""
    rec: dict = {}
    try:
        m = compiled.memory_analysis()
        rec = {
            "arg_gb": round(m.argument_size_in_bytes / 1e9, 3),
            "out_gb": round(m.output_size_in_bytes / 1e9, 3),
            "temp_gb": round(m.temp_size_in_bytes / 1e9, 3),
            "code_mb": round(m.generated_code_size_in_bytes / 1e6, 2),
        }
    except Exception:
        pass
    try:
        costs = compiled.cost_analysis()
        if isinstance(costs, (list, tuple)):
            costs = costs[0] if costs else {}
        if costs.get("bytes accessed") is not None:
            rec["bytes_accessed_gb"] = round(
                costs["bytes accessed"] / 1e9, 3
            )
        if costs.get("flops") is not None:
            rec["gflops"] = round(costs["flops"] / 1e9, 2)
    except Exception:
        pass  # not all backends expose a cost model
    return rec


def _stage_avals(side, sh, row_multiple: int = 1):
    """Mirror ``ops.als.stage()``'s chunked device layout as
    ShapeDtypeStructs (same block rounding — including the mesh
    ``row_multiple`` round-up — padding and uint16 index narrowing; see
    ``stage()``), without touching any device. ``tests/test_prewarm.py``
    asserts this stays shape-identical to the real ``stage()``."""
    import jax

    from ..ops import als

    buckets = []
    for bucket in side.buckets:
        # right-sized allocation, same rule as stage(): the block is
        # capped by the bucket's own pow2 row envelope (round 12)
        n = bucket.rows.shape[0]
        block = als._alloc_block(bucket.width, n)
        if row_multiple > 1:
            block = (
                (block + row_multiple - 1) // row_multiple
            ) * row_multiple
        n_chunks = max(1, (n + block - 1) // block)
        idx_dtype = als._idx_dtype(side.n_cols)
        aval = lambda shape, dt: jax.ShapeDtypeStruct(
            shape, dt, sharding=sh
        )
        buckets.append((
            aval((n_chunks, block), bucket.rows.dtype),
            aval((n_chunks, block, bucket.width), idx_dtype),
            aval((n_chunks, block, bucket.width), bucket.val.dtype),
            aval((n_chunks, block), bucket.counts.dtype),
        ))
    return tuple(buckets)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="prewarm_cache")
    ap.add_argument("--scale", type=float,
                    default=float(os.environ.get("BENCH_SCALE", "1.0")))
    ap.add_argument("--rank", type=int, default=50)
    ap.add_argument("--variants", default="f32,bf16")
    ap.add_argument("--skip-dispatch", action="store_true")
    args = ap.parse_args(argv)

    from ..utils.jax_cache import enable_compilation_cache
    from ..utils.platform import force_cpu_in_process

    # This tool is ALWAYS offline: every TPU compile goes through the
    # deviceless topology client, never the default backend. Pinning the
    # default backend to CPU keeps any stray jnp op (or backend query
    # during lowering) from initializing a device plugin and taking a
    # chip another process needs.
    force_cpu_in_process()
    cache_dir = enable_compilation_cache()

    import jax

    # The kernels choose interpret mode from jax.default_backend(), which
    # is the CPU here — and an interpreted kernel compiled for the
    # described chip says nothing about Mosaic. Every compile below
    # targets the TPU, so answer for it (PR 22: without this the "whole
    # program" passed while three gramian_fused blockings did not lower).
    jax.default_backend = lambda: "tpu"
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ..ops import als
    from ..ops.pallas_kernels import top_k_streaming
    from ..utils.topology import get_deviceless_topology

    sys.path.insert(0, REPO)
    import bench

    # cache the deterministic dataset like the queue does: a tool meant
    # for cheap offline iteration must not re-pay a minute of host-side
    # generation per run
    os.environ.setdefault("BENCH_SYNTH_CACHE", "/tmp/pio-bench-synth")

    t_all = time.monotonic()
    try:
        # generous retry: a test session holding the libtpu lockfile
        # must delay this tool, not abort it
        topo = get_deviceless_topology(
            "v5e:1x1", retries=5, retry_delay_s=20.0,
            chips_per_host_bounds=(1, 1, 1),
        )
    except Exception as exc:
        print(json.dumps({"step": "prewarm_aot",
                          "error": f"no deviceless TPU topology: {exc}"}))
        return 1
    sh = SingleDeviceSharding(topo.devices[0])

    users, items, ratings, n_users, n_items = bench.synth_ml20m(args.scale)
    tr = ~bench.holdout_mask(len(ratings))  # the bench's exact split
    by_user = als.bucketize(users[tr], items[tr], ratings[tr],
                            n_users, n_items, pad_to_blocks=True)
    by_item = als.bucketize(items[tr], users[tr], ratings[tr],
                            n_items, n_users, pad_to_blocks=True)
    ub, ib = _stage_avals(by_user, sh), _stage_avals(by_item, sh)
    rank = args.rank
    y_aval = jax.ShapeDtypeStruct((n_items, rank), jnp.float32, sharding=sh)
    x_aval = jax.ShapeDtypeStruct((n_users, rank), jnp.float32, sharding=sh)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=sh)

    rec = {"step": "prewarm_aot", "scale": args.scale, "rank": rank,
           "cache_dir": cache_dir, "programs": {}, "memory": {},
           "failed": []}
    for name in [v.strip() for v in args.variants.split(",") if v.strip()]:
        kw = VARIANTS[name]
        common = dict(rank=rank, implicit=False, solve_mode="pallas",
                      mesh=None, **kw)
        for prog, build in (
            (f"{name}/half_user", lambda: als._als_half.lower(
                y_aval, ub, scalar, scalar, n_rows=n_users, side="user",
                **common)),
            (f"{name}/half_item", lambda: als._als_half.lower(
                x_aval, ib, scalar, scalar, n_rows=n_items, side="item",
                **common)),
            (f"{name}/iteration", lambda: als._als_iteration.lower(
                ub, ib, y_aval, scalar, scalar,
                n_users=n_users, n_items=n_items, **common)),
        ):
            t0 = time.monotonic()
            try:
                compiled = build().compile()
                rec["programs"][prog] = round(time.monotonic() - t0, 2)
                rec["memory"][prog] = _memory_record(compiled)
            except Exception as exc:
                rec["failed"].append(
                    {prog: f"{type(exc).__name__}: {str(exc)[:300]}"}
                )
            print(f"[prewarm] {prog}: "
                  f"{rec['programs'].get(prog, 'FAILED')}s "
                  f"{rec['memory'].get(prog, '')}",
                  file=sys.stderr)

    if not args.skip_dispatch:
        import functools

        q = jax.ShapeDtypeStruct((512, rank), jnp.float32, sharding=sh)
        # one jit wrapper for every catalog size: each .lower() below is
        # a distinct program (that is the point of the prewarm), but the
        # wrapper itself must not be rebuilt per iteration
        dispatch_fn = jax.jit(functools.partial(
            top_k_streaming, k=10, interpret=False
        ))
        for n_cat in DISPATCH_CATALOGS:
            cat = jax.ShapeDtypeStruct((n_cat, rank), jnp.float32,
                                       sharding=sh)
            t0 = time.monotonic()
            try:
                compiled = dispatch_fn.lower(q, cat).compile()
                rec["programs"][f"dispatch/{n_cat}"] = round(
                    time.monotonic() - t0, 2
                )
                rec["memory"][f"dispatch/{n_cat}"] = _memory_record(
                    compiled
                )
            except Exception as exc:
                rec["failed"].append(
                    {f"dispatch/{n_cat}":
                     f"{type(exc).__name__}: {str(exc)[:300]}"}
                )

    rec["total_s"] = round(time.monotonic() - t_all, 1)
    rec["ok"] = not rec["failed"]
    print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
