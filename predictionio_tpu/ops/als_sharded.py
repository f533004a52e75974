"""ALX-style sharded ALS: both factor tables sharded over a named mesh axis.

The pod-scale data plane the ROADMAP calls "the single biggest unlock for
'fast as the hardware allows'": *ALX: Large Scale Matrix Factorization on
TPUs* (arXiv:2112.02194, PAPERS.md) shards BOTH factor matrices across
devices, balances density-bucketed batches per shard, and overlaps
off-shard factor gathers with solves. This module is that trainer, built
on ``shard_map`` so the collective schedule is explicit:

1. **Row → shard assignment** (:func:`assign_rows_balanced`): every row's
   solve cost is a pure function of its padded bucket width (the degree
   buckets of ``ops/als.py``), so rows are dealt to shards greedily
   least-loaded per width class, widest first — a deliberately skewed
   degree histogram still splits within a small FLOP-imbalance bound
   (pinned in tests/test_sharded_train.py).
2. **Per-shard bucketization**: each shard bucketizes ITS rows
   independently with the right-sized ``_alloc_block`` allocation, so no
   shard pays another shard's padding; shards are then padded to a common
   per-width envelope (which the balancing keeps tight) purely so the
   slabs stack into one ``[S, C, B, K]`` array ``shard_map`` can split.
3. **Sharded factor layout**: the table for a side with ``n`` rows lives
   as ``[S * cap, R]`` sharded ``P(SHARD_AXIS)`` — shard ``s`` owns local
   slots ``[s*cap, (s+1)*cap)``; rating column indices are pre-translated
   into this permuted space on the host, so the device program never
   needs the global permutation.
4. **Off-shard gathers overlapped with solves**: inside the mapped body,
   one tiled ``all_gather`` fetches the opposite table's row shards; each
   bucket's slab then reads its referenced rows through the shared
   ragged/deduplicated gather (``quant.ragged_gather`` — each unique row
   touched once, duplicates replayed via the inverse map; bit-identical
   to the dense ``y_full[idx]`` it replaced), issued — in program order,
   dataflow-independent — BEFORE the previous bucket's solves, a
   software pipeline XLA's latency-hiding scheduler can overlap on TPU.
   (Extending the ragged fetch across shards — skipping the dense
   all-gather entirely at shard counts where replicating the table per
   device no longer fits — has not been built: see
   docs/distributed_training.md.)
5. **Implicit mode** builds YᵀY as a ``psum`` of per-shard Gramians — the
   collective the ``spmd-*`` lint family pins this file as the clean
   exemplar for.

Equivalence contract (the CI-runnable proof, on the 8-virtual-CPU-device
test mesh): factors at 1/2/4/8 shards match the single-device trainer
within the PR-12 reassociation tolerances (rtol 1e-3 / atol 1e-4, holdout
RMSE 1e-3) — sharding changes accumulation ORDER (each shard's rows
hold their ratings in the permuted id space's bucketize order), never
the per-row math. The multi-host
``jax.distributed`` drive has not been run
(docs/distributed_training.md).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import json
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from ..quant.ragged import ragged_gather
from ..parallel.mesh import DATA_AXIS, MeshConfig, create_mesh
from .als import (
    ALSConfig,
    ALSFactors,
    DEFAULT_BUCKET_WIDTHS,
    _alloc_block,
    _cho_solve,
    _idx_dtype,
    _system_explicit_g,
    _system_implicit_g,
    als_train,
    bucketize,
    init_factors,
)

__all__ = [
    "SHARD_AXIS",
    "SHARDS_ENV",
    "assign_rows_balanced",
    "als_train_sharded",
    "plan_side",
    "resolve_shards",
    "row_solve_flops",
]

logger = logging.getLogger(__name__)

#: Solve rows ride the mesh ``data`` axis — the same axis name the rest of
#: the parallel plane uses, so a hybrid (DCN x ICI) mesh slots in directly.
SHARD_AXIS = DATA_AXIS

#: Env override for the ``shards`` tri-state (``pio train --shards`` sets
#: it; docs/cli.md#environment-variables).
SHARDS_ENV = "PIO_TRAIN_SHARDS"


def resolve_shards(
    shards: Optional[int] = None,
    env: Optional[Dict[str, str]] = None,
) -> int:
    """The CONCRETE shard count a train run will execute — the
    ``ALSAlgorithmParams.shards`` tri-state resolved per the PR-12 lever
    discipline: an explicit value wins, else :data:`SHARDS_ENV` (what
    ``pio train --shards N`` sets), else 1 — the single-device trainer,
    byte-identical config resolution to today's path. Resolution never
    silently clamps: a count the device pool cannot satisfy fails loudly
    in :func:`als_train_sharded`, not here."""
    if shards is not None:
        n = int(shards)
        if n < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        return n
    e = env if env is not None else os.environ
    raw = e.get(SHARDS_ENV)
    if raw:
        try:
            n = int(raw)
        except ValueError:
            raise ValueError(f"{SHARDS_ENV} must be an integer, got {raw!r}")
        if n < 1:
            raise ValueError(f"{SHARDS_ENV} must be >= 1, got {raw!r}")
        return n
    return 1


def row_solve_flops(width: int, rank: int) -> float:
    """Padded solve cost of ONE bucket row of width K — the same
    accounting as ``ops.als.estimate_iteration_flops`` (Gramian + rhs
    einsums, Cholesky, triangular solves), which makes it the right
    balancing weight: what the device actually executes per row."""
    k = float(width)
    r = float(rank)
    return k * (2.0 * r * r + 2.0 * r) + r**3 / 3.0 + 2.0 * r * r


def _padded_widths(
    degrees: np.ndarray, widths: Sequence[int]
) -> np.ndarray:
    """Each row's padded bucket width (rows above the largest width
    truncate to it, mirroring ``bucketize``)."""
    ws = np.asarray(sorted(widths), dtype=np.int64)
    capped = np.minimum(degrees.astype(np.int64), ws[-1])
    return ws[np.searchsorted(ws, capped, side="left")]


def assign_rows_balanced(
    degrees: np.ndarray,
    shards: int,
    bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
    rank: int = 10,
) -> np.ndarray:
    """Deal rows to shards balancing per-shard solve FLOPs.

    Every row in one width class costs the same, so balance reduces to
    dealing each class's rows (widest/heaviest class first) to the
    currently least-loaded shard — deterministic (ties break on shard
    index, rows visit in ascending id order) and within one row-cost of
    perfect per class. Zero-degree rows carry no solve cost and are dealt
    last to the emptiest shards so local row counts stay even (they size
    the sharded factor table's per-shard ``cap``).

    Returns the ``[n_rows]`` int32 shard assignment.
    """
    n = len(degrees)
    assign = np.zeros(n, dtype=np.int32)
    if shards <= 1:
        return assign
    widths = _padded_widths(np.asarray(degrees), bucket_widths)
    load = [(0.0, s) for s in range(shards)]  # (flops, shard) min-heap
    heapq.heapify(load)
    rated = np.nonzero(np.asarray(degrees) > 0)[0]
    # widest class first: the heaviest rows set the landscape the lighter
    # classes then level out
    order = np.lexsort((rated, -widths[rated]))
    for row in rated[order]:
        cost = row_solve_flops(int(widths[row]), rank)
        flops, s = heapq.heappop(load)
        assign[row] = s
        heapq.heappush(load, (flops + cost, s))
    # zero-degree rows: even out the LOCAL ROW COUNTS (table cap), not the
    # flops — they never solve
    counts = np.bincount(assign[rated], minlength=shards)
    count_heap = [(int(counts[s]), s) for s in range(shards)]
    heapq.heapify(count_heap)
    for row in np.nonzero(np.asarray(degrees) <= 0)[0]:
        c, s = heapq.heappop(count_heap)
        assign[row] = s
        heapq.heappush(count_heap, (c + 1, s))
    return assign


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """One side's row → (shard, local slot) layout.

    The permuted factor table is ``[shards * cap, R]`` sharded over
    :data:`SHARD_AXIS`; global row ``r`` lives at flat index
    ``assign[r] * cap + slot[r]``. Slots beyond a shard's real row count
    are zero padding (never referenced, never solved)."""

    shards: int
    assign: np.ndarray  # [n] -> owning shard
    slot: np.ndarray  # [n] -> local slot within the shard
    cap: int  # local rows per shard (max over shards, >= 1)
    per_shard_flops: Tuple[float, ...]  # balancing evidence

    @property
    def flop_imbalance(self) -> float:
        """max/mean per-shard solve FLOPs (1.0 = perfect balance)."""
        mean = sum(self.per_shard_flops) / max(1, len(self.per_shard_flops))
        if mean <= 0:
            return 1.0
        return max(self.per_shard_flops) / mean

    def flat_index(self, rows: np.ndarray) -> np.ndarray:
        return (
            self.assign[rows].astype(np.int64) * self.cap
            + self.slot[rows].astype(np.int64)
        )


def plan_side(
    degrees: np.ndarray,
    shards: int,
    bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
    rank: int = 10,
) -> ShardPlan:
    """Assignment + local slots + per-shard FLOP stats for one side."""
    degrees = np.asarray(degrees)
    n = len(degrees)
    assign = assign_rows_balanced(degrees, shards, bucket_widths, rank)
    # local slot = rank of the row within its shard, ascending global id
    # (stable sort keeps the order deterministic)
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=shards)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.zeros(n, dtype=np.int32)
    slot[order] = (
        np.arange(n, dtype=np.int64) - np.repeat(starts, counts)
    ).astype(np.int32)
    widths = _padded_widths(degrees, bucket_widths)
    flops = np.array(
        [row_solve_flops(int(w), rank) for w in np.sort(np.unique(widths))]
    )
    per_shard = []
    uniq = np.sort(np.unique(widths))
    rated = degrees > 0
    for s in range(shards):
        sel = rated & (assign == s)
        total = 0.0
        for wi, w in enumerate(uniq):
            total += float(flops[wi]) * int(np.sum(widths[sel] == w))
        per_shard.append(total)
    cap = max(1, int(counts.max()))
    return ShardPlan(
        shards=shards,
        assign=assign,
        slot=slot,
        cap=cap,
        per_shard_flops=tuple(per_shard),
    )


def _build_side(
    row_ids: np.ndarray,
    col_ids: np.ndarray,
    vals: np.ndarray,
    row_plan: ShardPlan,
    col_plan: ShardPlan,
    bucket_widths: Sequence[int],
):
    """Per-shard right-sized buckets, stacked into shard-leading slabs.

    Each shard bucketizes its OWN rows (local slot ids, opposite-side
    column ids pre-translated into the permuted ``[S * cap_col]`` space)
    with ``pad_to_blocks=True`` — the PR-12 right-sized allocation, so a
    shard's slab envelope follows ITS row histogram. Shards then pad to
    the max envelope per width (sentinel rows: ``rows == cap`` dropped by
    the scatter, counts 0) purely to stack; the FLOP balancing is what
    keeps that common envelope tight.

    Returns ``(slabs, padded_rows)`` — slabs is a tuple of
    ``(rows [S,C,B], idx [S,C,B,K], val, counts)`` numpy stacks in width
    order; ``padded_rows`` maps width → total padded rows (profile/FLOP
    accounting).
    """
    shards = row_plan.shards
    n_cols_perm = col_plan.shards * col_plan.cap
    row_ids = np.asarray(row_ids)
    perm_cols = col_plan.flat_index(np.asarray(col_ids)).astype(np.int32)
    local_rows = row_plan.slot[row_ids]
    shard_of = row_plan.assign[row_ids]
    per_shard: List[Dict[int, object]] = []
    for s in range(shards):
        sel = shard_of == s
        bm = bucketize(
            local_rows[sel],
            perm_cols[sel],
            np.asarray(vals)[sel],
            n_rows=row_plan.cap,
            n_cols=n_cols_perm,
            bucket_widths=bucket_widths,
            pad_to_blocks=True,
        )
        per_shard.append({b.width: b for b in bm.buckets})
    all_widths = sorted({w for shard in per_shard for w in shard})
    idx_dtype = _idx_dtype(n_cols_perm)
    slabs = []
    padded_rows: Dict[int, int] = {}
    for w in all_widths:
        real_max = max(
            (
                int((shard[w].counts > 0).sum())
                for shard in per_shard
                if w in shard
            ),
            default=0,
        )
        alloc_max = max(
            (shard[w].rows.shape[0] for shard in per_shard if w in shard),
            default=0,
        )
        block = _alloc_block(w, real_max)
        b_rows = max(block, -(-alloc_max // block) * block)
        n_chunks = b_rows // block
        rows = np.full((shards, b_rows), row_plan.cap, dtype=np.int32)
        idx = np.zeros((shards, b_rows, w), dtype=idx_dtype)
        val = np.zeros((shards, b_rows, w), dtype=np.float32)
        counts = np.zeros((shards, b_rows), dtype=np.int32)
        for s, shard in enumerate(per_shard):
            b = shard.get(w)
            if b is None:
                continue
            m = b.rows.shape[0]
            rows[s, :m] = b.rows
            idx[s, :m] = b.idx.astype(idx_dtype)
            val[s, :m] = b.val
            counts[s, :m] = b.counts
        slabs.append(
            (
                rows.reshape(shards, n_chunks, block),
                idx.reshape(shards, n_chunks, block, w),
                val.reshape(shards, n_chunks, block, w),
                counts.reshape(shards, n_chunks, block),
            )
        )
        padded_rows[w] = shards * b_rows
    return tuple(slabs), padded_rows


def _half_sharded_body(
    y_table,
    slabs,
    lam,
    alpha,
    *,
    mesh,
    rank,
    implicit,
    gather_dtype,
    cap_x,
):
    """One sharded half-iteration: solve every local row of one side from
    the sharded opposite table. ``y_table`` is ``[S * cap_y, R]`` sharded
    ``P(SHARD_AXIS)``; ``slabs`` are the shard-leading bucket stacks;
    returns the solved ``[S * cap_x, R]`` table, same sharding."""
    gdt = jnp.bfloat16 if gather_dtype == "bf16" else jnp.float32

    def _shard_body(y_local, local_slabs, lam_s, alpha_s):
        # Off-shard factor fetch: one tiled all-gather of the opposite
        # table's row shards; per-bucket slabs then gather raggedly from
        # it. (Skipping the all-gather itself — fetching only referenced
        # rows ACROSS shards at counts where replicating the table no
        # longer fits — stays docs/distributed_training.md#headroom.)
        y_full = jax.lax.all_gather(y_local, SHARD_AXIS, axis=0, tiled=True)
        y_g = y_full.astype(gdt) if y_full.dtype != gdt else y_full
        if implicit:
            # YᵀY over the whole table as a psum of per-shard Gramians —
            # padding slots are zero rows, so they contribute nothing
            local_yty = jnp.einsum(
                "nr,ns->rs", y_local, y_local,
                preferred_element_type=jnp.float32,
            )
            yty = jax.lax.psum(local_yty, SHARD_AXIS)
        else:
            yty = None

        def gather_chunk(idx_blk, counts_blk):
            idx_blk = idx_blk.astype(jnp.int32)  # uint16 transfer packing
            k = idx_blk.shape[-1]
            mask = (
                jnp.arange(k, dtype=jnp.int32)[None, :]
                < counts_blk[:, None]
            ).astype(gdt)
            # ragged/deduplicated slab fetch (quant.ragged_gather): a
            # solve block's columns repeat hot counterpart rows, and the
            # padding slots all point at slot 0 — each unique row is
            # read once instead of once per reference
            return ragged_gather(y_g, idx_blk) * mask[..., None], mask

        def solve_from_g(g, mask, val_blk):
            if implicit:
                a, b = _system_implicit_g(
                    g, yty, val_blk, mask, lam_s, alpha_s, rank
                )
            else:
                a, b = _system_explicit_g(g, val_blk, mask, lam_s, rank)
            return _cho_solve(a, b)

        def solve_chunk(c):
            idx_blk, val_blk, counts_blk = c
            g, mask = gather_chunk(idx_blk, counts_blk)
            return solve_from_g(g, mask, val_blk)

        # drop the leading shard dim (1 per device under shard_map)
        buckets = [tuple(t[0] for t in slab) for slab in local_slabs]
        x = jnp.zeros((cap_x, rank), dtype=jnp.float32)
        # Software pipeline: bucket b+1's first off-shard gather is issued
        # BEFORE bucket b's solves in program order and depends on none of
        # them, so the scheduler can overlap the gather DMA with the
        # previous bucket's solve chain (the ALX overlap, expressed as
        # dataflow).
        pre = None
        if buckets:
            _, idx0, _, counts0 = buckets[0]
            pre = gather_chunk(idx0[0], counts0[0])
        for bi, (rows, idx, val, counts) in enumerate(buckets):
            nxt = None
            if bi + 1 < len(buckets):
                _, idx_n, _, counts_n = buckets[bi + 1]
                nxt = gather_chunk(idx_n[0], counts_n[0])
            g, mask = pre
            first = solve_from_g(g, mask, val[0])  # prefetched chunk 0
            if idx.shape[0] > 1:
                rest = jax.lax.map(
                    solve_chunk, (idx[1:], val[1:], counts[1:])
                )
                solved = jnp.concatenate([first[None], rest], axis=0)
            else:
                solved = first[None]
            # sentinel rows carry cap_x (out of range) -> dropped
            x = x.at[rows.reshape(-1)].set(
                solved.reshape(-1, rank), mode="drop"
            )
            pre = nxt
        return x

    return shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
        out_specs=P(SHARD_AXIS),
        # the body all-gathers + psums; replication is by spec, which the
        # static VMA check cannot prove through all_gather (the
        # all_gather_rows precedent in parallel/collectives.py)
        check_vma=False,
    )(y_table, slabs, lam, alpha)


_half_sharded = functools.partial(
    jax.jit,
    static_argnames=("mesh", "rank", "implicit", "gather_dtype", "cap_x"),
)(_half_sharded_body)


def resolve_sharded_levers(cfg: ALSConfig) -> dict:
    """Lever resolution for the sharded data plane (the PR-12 "record
    resolved, not requested" discipline). The sharded trainer builds
    normal equations with the einsum path and solves with the batched
    Cholesky per shard — ``solve_mode`` must be ``auto``/``chunked``;
    composing the Pallas solver and its fused build inside the mapped
    body has not been built (docs/distributed_training.md#headroom). An
    explicit ``pallas`` fails loudly rather than train another program
    than the one asked for."""
    if cfg.solve_mode not in ("auto", "chunked"):
        raise ValueError(
            "sharded training solves 'chunked' (einsum build + batched "
            f"Cholesky per shard); solve_mode={cfg.solve_mode!r} is not "
            "supported with shards > 1 — leave solve_mode='auto'"
        )
    if cfg.gather_dtype not in ("f32", "bf16"):
        raise ValueError(
            f"gather_dtype must be 'f32' or 'bf16', got {cfg.gather_dtype!r}"
        )
    return {
        "solve_mode": "chunked",
        "gather_dtype": cfg.gather_dtype,
        "fused_gather": False,
    }


def _permuted_table(table: np.ndarray, plan: ShardPlan) -> np.ndarray:
    """[n, R] global-order table → [S * cap, R] permuted layout (padding
    slots zero — required by the implicit psum'd Gramian and harmless
    everywhere else: no rating references them, no bucket solves them)."""
    n, rank = table.shape
    out = np.zeros((plan.shards * plan.cap, rank), dtype=np.float32)
    out[plan.flat_index(np.arange(n))] = np.asarray(table, dtype=np.float32)
    return out


def als_train_sharded(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    cfg: ALSConfig,
    shards: Optional[int] = None,
    mesh=None,
    devices=None,
    checkpoint=None,
    checkpoint_every: int = 0,
    profile: Optional[dict] = None,
) -> ALSFactors:
    """Train ALS with both factor tables sharded over ``shards`` devices.

    ``shards`` is the tri-state: explicit N wins, else :data:`SHARDS_ENV`,
    else 1 — and 1 IS the single-device trainer (the degenerate path
    delegates to :func:`~predictionio_tpu.ops.als.als_train` with the
    identical config, so ``shards=1`` and an unset tri-state on a single
    device resolve byte-identically). ``mesh`` (optional) supplies a
    prebuilt mesh whose :data:`SHARD_AXIS` size is the shard count —
    multi-host runs pass the ``hybrid_mesh`` built after
    ``initialize_from_env()`` (docs/distributed_training.md);
    single-host runs build a mesh over the first ``shards`` devices.

    ``checkpoint`` (a :class:`~predictionio_tpu.ckpt.CheckpointStore`)
    enables sharded step-resume (docs/checkpoint.md): every
    ``checkpoint_every`` iterations both factor tables are snapshotted to
    host in CANONICAL (global, unpermuted) row order and committed by a
    background writer thread — the loop never stalls on disk. Because
    the snapshot is canonical, resume re-deals rows through the balancer
    at ANY shard count: a run checkpointed at N shards resumes at M and
    lands within the PR-12 reassociation tolerances of the uninterrupted
    run. Resuming against a mismatched recipe raises
    :class:`~predictionio_tpu.ckpt.CheckpointMismatch` (loud refusal); a
    corrupt step is skipped loudly to the previous valid one. When a
    store is passed, ``shards=1`` runs the sharded loop on a one-device
    mesh instead of delegating (the ckpt contract is tolerance-bounded,
    not byte-identical, and owns every shard count uniformly).

    ``profile`` receives the resolved levers (+ ``shards``), per-iteration
    wall clock, the ``shard_plan`` balance evidence (per-shard FLOPs,
    imbalance ratio, rows per shard) — and,
    when checkpointing, a ``ckpt`` block (written/dropped/errors counts,
    snapshot seconds, the step resumed from).
    """
    import time as _time

    if cfg.iterations < 1:
        raise ValueError(f"ALS iterations must be >= 1, got {cfg.iterations}")
    if checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0, got {checkpoint_every}"
        )
    if checkpoint_every > 0 and checkpoint is None:
        raise ValueError(
            "checkpoint_every > 0 needs a checkpoint store — pass "
            "checkpoint=CheckpointStore(dir) (docs/checkpoint.md)"
        )
    n = resolve_shards(shards)
    if mesh is not None:
        n = int(mesh.shape[SHARD_AXIS])
    if n == 1 and checkpoint is None:
        # Degenerate path: byte-identical config resolution to today's
        # trainer — same bucketize call, same als_train, same profile
        # fields (plus the resolved shard count).
        by_user = bucketize(
            users, items, ratings, n_users, n_items, pad_to_blocks=True
        )
        by_item = bucketize(
            items, users, ratings, n_items, n_users, pad_to_blocks=True
        )
        factors = als_train(by_user, by_item, cfg, profile=profile)
        if profile is not None:
            profile["shards"] = 1
        return factors

    levers = resolve_sharded_levers(cfg)
    if mesh is None:
        pool = list(devices if devices is not None else jax.devices())
        if len(pool) < n:
            raise ValueError(
                f"shards={n} needs {n} devices, have {len(pool)} — on a "
                "single host force virtual devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "before importing jax (docs/distributed_training.md)"
            )
        mesh = create_mesh(MeshConfig(((SHARD_AXIS, n),)), pool[:n])

    users = np.ascontiguousarray(np.asarray(users), dtype=np.int32)
    items = np.ascontiguousarray(np.asarray(items), dtype=np.int32)
    ratings = np.ascontiguousarray(np.asarray(ratings), dtype=np.float32)
    rank = cfg.rank

    t_stage = _time.monotonic()
    user_deg = np.bincount(users, minlength=n_users)
    item_deg = np.bincount(items, minlength=n_items)
    user_plan = plan_side(user_deg, n, rank=rank)
    item_plan = plan_side(item_deg, n, rank=rank)
    user_slabs_np, user_padded = _build_side(
        users, items, ratings, user_plan, item_plan,
        DEFAULT_BUCKET_WIDTHS,
    )
    item_slabs_np, item_padded = _build_side(
        items, users, ratings, item_plan, user_plan,
        DEFAULT_BUCKET_WIDTHS,
    )
    table_sharding = NamedSharding(mesh, P(SHARD_AXIS))
    slab_sharding = NamedSharding(mesh, P(SHARD_AXIS))
    put = lambda a: jax.device_put(a, slab_sharding)  # noqa: E731
    user_slabs = tuple(tuple(put(a) for a in slab) for slab in user_slabs_np)
    item_slabs = tuple(tuple(put(a) for a in slab) for slab in item_slabs_np)

    # Sharded step-resume (docs/checkpoint.md#resume-contract): the
    # config identity a checkpoint must match to be resumable. The shard
    # count is deliberately ABSENT — snapshots are canonical row order,
    # so any N resumes at any M; the balancer re-deals above.
    ck_meta = {
        "rank": cfg.rank,
        "lambda": cfg.lambda_,
        "alpha": cfg.alpha,
        "implicit": cfg.implicit_prefs,
        "seed": cfg.seed,
        "nnz": int(len(ratings)),
        "n_users": int(n_users),
        "n_items": int(n_items),
    }
    start_iter = 0
    y_canonical = None
    if checkpoint is not None:
        # mismatched recipe → CheckpointMismatch propagates (loud
        # refusal); corrupt steps are skipped + counted inside load()
        loaded = checkpoint.load(
            expect_meta=ck_meta, max_step=cfg.iterations
        )
        if loaded is not None:
            x_canonical = np.asarray(loaded.arrays["x"], np.float32)
            y_canonical = np.asarray(loaded.arrays["y"], np.float32)
            if x_canonical.shape != (n_users, rank) or (
                y_canonical.shape != (n_items, rank)
            ):
                from ..ckpt import CheckpointMismatch

                raise CheckpointMismatch(
                    f"step {loaded.step}: factor shapes "
                    f"{x_canonical.shape}/{y_canonical.shape} do not "
                    f"match this run's ({n_users}, {rank})/"
                    f"({n_items}, {rank})"
                )
            start_iter = int(loaded.meta.get("iteration", loaded.step))
            if profile is not None:
                profile["ckpt"] = {"resumedFrom": start_iter}
            if start_iter >= cfg.iterations:
                # the interrupted run had already finished its sweeps —
                # nothing to train, return the checkpointed factors
                if profile is not None:
                    profile["stage_s"] = _time.monotonic() - t_stage
                    profile["shards"] = n
                    profile["iteration_s"] = []  # zero sweeps re-run
                    profile.update(levers)
                return ALSFactors(
                    user_factors=jnp.asarray(x_canonical),
                    item_factors=jnp.asarray(y_canonical),
                    rank=rank,
                )

    # MLlib iteration order: item factors initialize, users solve first.
    # The SAME global init the single-device trainer mints, permuted —
    # every global row starts from the identical value at any shard count.
    # On resume the checkpointed canonical table replaces the init: the
    # loop consumes only y at an iteration boundary, so restoring y is
    # the complete sweep state (x is re-solved from it immediately).
    y = jax.device_put(
        _permuted_table(
            np.asarray(init_factors(n_items, rank, cfg.seed))
            if y_canonical is None else y_canonical,
            item_plan,
        ),
        table_sharding,
    )
    if profile is not None:
        profile["stage_s"] = _time.monotonic() - t_stage
        profile["shards"] = n
        profile.update(levers)
        flops = sum(
            rows * row_solve_flops(w, rank)
            for padded in (user_padded, item_padded)
            for w, rows in padded.items()
        )
        if cfg.implicit_prefs:
            flops += 2.0 * (n_users + n_items) * rank * rank  # YᵀY
        profile["flops_per_iteration"] = flops
        profile["shard_plan"] = {
            "shards": n,
            "rowsPerShard": {
                "user": user_plan.cap,
                "item": item_plan.cap,
            },
            "perShardFlops": {
                "user": [round(f, 1) for f in user_plan.per_shard_flops],
                "item": [round(f, 1) for f in item_plan.per_shard_flops],
            },
            "flopImbalance": {
                "user": round(user_plan.flop_imbalance, 4),
                "item": round(item_plan.flop_imbalance, 4),
            },
        }
        profile.setdefault("iteration_s", [])

    lam = jnp.float32(cfg.lambda_)
    alpha = jnp.float32(cfg.alpha)
    common = dict(
        mesh=mesh,
        rank=rank,
        implicit=cfg.implicit_prefs,
        gather_dtype=cfg.gather_dtype,
    )
    from ..obs.profile import default_telemetry

    _telemetry = default_telemetry()
    writer = None
    if checkpoint is not None and checkpoint_every > 0:
        from ..ckpt import CheckpointWriter, resolve_queue_depth

        writer = CheckpointWriter(
            checkpoint, queue_depth=resolve_queue_depth()
        )
    snapshot_s = 0.0
    x = None
    try:
        _ix_user = np.arange(n_users)
        _ix_item = np.arange(n_items)
        for it in range(start_iter, cfg.iterations):
            t_iter = _time.monotonic()
            x = _telemetry.call(
                "als_sharded_half", _half_sharded, y, user_slabs, lam,
                alpha, cap_x=user_plan.cap, **common,
            )
            y = _telemetry.call(
                "als_sharded_half", _half_sharded, x, item_slabs, lam,
                alpha, cap_x=item_plan.cap, **common,
            )
            if profile is not None:
                jax.block_until_ready((x, y))
                profile["iteration_s"].append(_time.monotonic() - t_iter)
            done = it + 1
            if writer is not None and (
                done % checkpoint_every == 0 or done == cfg.iterations
            ):
                # snapshot in CANONICAL row order — the layout any shard
                # count can re-permute — on the train thread (one host
                # gather per table); the disk write happens on the
                # writer thread behind the bounded queue
                t_snap = _time.monotonic()
                snap = {
                    "x": np.asarray(x)[user_plan.flat_index(_ix_user)],
                    "y": np.asarray(y)[item_plan.flat_index(_ix_item)],
                }
                meta = {**ck_meta, "iteration": done}
                if done == cfg.iterations:
                    # the final checkpoint is the run's durable result —
                    # it waits for a queue slot instead of dropping
                    writer.flush_submit(done, snap, meta)
                else:
                    writer.submit(done, snap, meta)
                snapshot_s += _time.monotonic() - t_snap
    finally:
        if writer is not None:
            stats = writer.close()
            if profile is not None:
                ck_prof = profile.setdefault("ckpt", {})
                ck_prof.update(stats)
                ck_prof["snapshotS"] = round(snapshot_s, 4)
                ck_prof["corruptSkipped"] = checkpoint.corrupt_skipped
                ck_prof.setdefault("resumedFrom", None)

    # where the trained tables' shards sit: one device each, or the mesh
    # did not place what it was asked to (chip_smoke.py --chips 4 reads it)
    logger.info("sharded ALS tables: %s", json.dumps({
        "shards": n,
        "user": [s.device.id for s in x.addressable_shards],
        "item": [s.device.id for s in y.addressable_shards],
    }))
    # permuted sharded layout → global row order (host-side unpermute)
    uf = np.asarray(x)[user_plan.flat_index(np.arange(n_users))]
    itf = np.asarray(y)[item_plan.flat_index(np.arange(n_items))]
    return ALSFactors(
        user_factors=jnp.asarray(uf),
        item_factors=jnp.asarray(itf),
        rank=rank,
    )
