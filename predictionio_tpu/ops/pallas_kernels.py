"""Pallas TPU kernels for the serving hot path.

The deployed recommender's inner loop is "score every item for a batch of
queries, keep the top k" (reference:
``MatrixFactorizationModel.recommendProducts`` dot-products invoked per query,
``examples/.../ALSAlgorithm.scala:76-86``). The XLA path in
:mod:`predictionio_tpu.ops.scoring` materializes the full ``[B, N]`` score
matrix in HBM before ``top_k``; for large catalogs that write is the
bandwidth bill. This kernel streams item blocks through VMEM instead: each
grid step computes one ``[B, T]`` score tile on the MXU and folds it into a
running ``[B, K]`` top-k kept in VMEM — the ``[B, N]`` matrix never exists.

Exclusion (seen/unavailable items — the e-commerce template's serving-time
filters) is per-query index lists (``[B, E]``, -1 padded), matched against
the block's global item indices, instead of a dense ``[B, N]`` mask.

On non-TPU backends the kernels run in interpret mode (tests). The dense XLA
path (:func:`predictionio_tpu.ops.scoring.xla_topk_with_sentinels`) is a
separate program that ``resolve_topk_path`` chooses openly; nothing here
falls back to it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = float("-inf")  # plain scalar: jnp constants cannot be captured by kernels

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _select_topk(cand_s, cand_i, k: int):
    """Top-k of (scores, indices) along axis 1 by unrolled max-extraction —
    only jnp primitives that lower in Mosaic (no sort/top_k inside kernels).
    """
    b, c = cand_s.shape
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    out_s, out_i = [], []
    for _ in range(k):
        m = jnp.max(cand_s, axis=1, keepdims=True)  # [B, 1]
        # first position attaining the max
        pos = jnp.min(
            jnp.where(cand_s == m, pos_iota, jnp.int32(c)), axis=1, keepdims=True
        )  # [B, 1]
        sel = pos_iota == pos  # [B, C] one-hot
        idx = jnp.sum(jnp.where(sel, cand_i, 0), axis=1)  # [B]
        out_s.append(m[:, 0])
        out_i.append(idx)
        cand_s = jnp.where(sel, _NEG_INF, cand_s)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _topk_kernel(q_ref, items_ref, excl_ref, out_s_ref, out_i_ref, *,
                 k: int, block_items: int, n_items: int, n_excl: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        out_s_ref[:] = jnp.full_like(out_s_ref[:], _NEG_INF)
        out_i_ref[:] = jnp.full_like(out_i_ref[:], -1)

    b = q_ref.shape[0]
    # f32 scores, as on the dense path (ops/scoring.SCORE_PRECISION): the
    # default rounds f32 inputs to bf16 passes on the chip
    scores = jax.lax.dot_general(
        q_ref[:], items_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [B, T]
    gidx = j * block_items + jax.lax.broadcasted_iota(
        jnp.int32, (b, block_items), 1
    )
    scores = jnp.where(gidx < n_items, scores, _NEG_INF)
    if n_excl:
        # One excluded id per fori_loop step: the buffer arrives
        # TRANSPOSED as [E, B], so each step reads one sublane row
        # (leading-dim index — always lowerable) and masks with a single
        # 2-D compare. Mosaic rejects lane-dim slices at unaligned
        # offsets and compiles 3-D broadcast compares pathologically
        # slowly (both deviceless-AOT findings), so the earlier
        # [B, T, C]-chunked formulation is gone; total compare work is
        # identical (E × [B, T]).
        def body(e, sc):
            # pio: lint-ok[mosaic-per-row-dma] sequential E-step is by design (ADVICE r5): E ≤ 64 and a [B] sublane row per step is the formulation that lowers; the [B,T,C] chunked compare did not
            ex = excl_ref[e]  # [B]
            hit = gidx == ex[:, None]  # [B, T]
            return jnp.where(hit, _NEG_INF, sc)

        scores = jax.lax.fori_loop(0, n_excl, body, scores)

    cand_s = jnp.concatenate([out_s_ref[:], scores], axis=1)
    cand_i = jnp.concatenate([out_i_ref[:], gidx], axis=1)
    new_s, new_i = _select_topk(cand_s, cand_i, k)
    out_s_ref[:] = new_s
    out_i_ref[:] = new_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_items", "n_excl", "interpret"),
)
def _topk_streaming_call(query_vectors, item_factors, exclude_idx, k,
                         block_items, n_excl, interpret):
    b, r = query_vectors.shape
    n_items = item_factors.shape[0]
    n_pad = _round_up(n_items, block_items)
    items = jnp.pad(item_factors, ((0, n_pad - n_items), (0, 0)))
    grid = n_pad // block_items

    kernel = functools.partial(
        _topk_kernel,
        k=k, block_items=block_items, n_items=n_items, n_excl=n_excl,
    )
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((b, r), lambda j: (0, 0)),
            pl.BlockSpec((block_items, r), lambda j: (j, 0)),
            pl.BlockSpec(exclude_idx.shape, lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((b, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        interpret=interpret,
        name="top_k_streaming",
    )(query_vectors, items, exclude_idx)


def top_k_streaming(
    query_vectors: jax.Array,  # [B, R] float32
    item_factors: jax.Array,  # [N, R] float32
    k: int,
    exclude_idx: Optional[jax.Array] = None,  # [B, E] int32, -1 padded
    block_items: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming top-k gather-dot: returns (scores ``[B, k]``, item indices
    ``[B, k]``) without materializing ``[B, N]`` scores in HBM.

    Sentinel contract (kernel and interpreter, shared with the dense XLA
    path in ``ops/scoring.py``): a slot
    with fewer than ``k`` valid candidates (catalog smaller than ``k``, or
    exclusions masking the rest) holds score ``-inf`` and index ``-1``.
    Callers gathering items by index MUST treat ``-1`` as absent — negative
    indexing would otherwise silently map it to the last catalog item.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (CPU tests). Queries/rank are padded to VPU/MXU tile boundaries; padding
    never appears in results (-inf / -1 masking).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, r = query_vectors.shape
    n_items = item_factors.shape[0]
    k_eff = min(k, n_items)
    b_pad = _round_up(b, 8)
    r_pad = _round_up(r, 128)
    q = jnp.pad(
        jnp.asarray(query_vectors, jnp.float32),
        ((0, b_pad - b), (0, r_pad - r)),
    )
    items = jnp.pad(
        jnp.asarray(item_factors, jnp.float32), ((0, 0), (0, r_pad - r))
    )
    if exclude_idx is None or exclude_idx.shape[1] == 0:
        # n_excl=0 → the kernel skips exclusion entirely (the 1-row filler
        # only exists because pallas inputs need a nonzero dim)
        excl = jnp.full((1, b_pad), -1, dtype=jnp.int32)
        n_excl = 0
    else:
        e = exclude_idx.shape[1]
        # transpose to [E, B]: the kernel reads one exclusion row per
        # loop step via a leading-dim index (see _topk_kernel)
        excl = jnp.pad(
            jnp.asarray(exclude_idx, jnp.int32),
            ((0, b_pad - b), (0, 0)),
            constant_values=-1,
        ).T
        n_excl = e

    block = min(block_items, _round_up(n_items, 128))
    scores, idx = _topk_streaming_call(
        q, items, excl, k_eff, block, n_excl, interpret
    )
    scores, idx = scores[:b], idx[:b]
    if k_eff < k:
        pad = k - k_eff
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-np.inf)
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
    return scores, idx


# ---------------------------------------------------------------------------
# Batched SPD solve (the ALS normal-equation hot op)
# ---------------------------------------------------------------------------
#
# XLA's batched Cholesky lowering runs at ~10 GFLOP/s on TPU for the [B, 50,
# 50] systems ALS produces (measured: ~6.7 µs per matrix — it was ~2/3 of the
# ALS iteration). This kernel fuses factorization + both triangular solves
# into one VMEM-resident pass in a transposed [n, n, B] layout: the batch
# rides the 128-wide lane dimension (full vector-register utilization), and
# extracting column j of every matrix is a cheap dim-0 slice instead of a
# masked reduction. Measured marginal cost ~0.24 µs per matrix (~25×).
#
# Algorithm (right-looking Cholesky, one fused FMA pass per step):
#   step j: colj = a[j]            (trailing block is symmetric)
#           lj   = colj / sqrt(a[j,j])
#           a   -= (lj - e_j) ⊗ lj (trailing update + stores L's column j
#                                    into row j of `a`, which the update has
#                                    just zeroed)
# Forward substitution interleaves with factorization (z_j available as soon
# as column j is); back substitution replays the stored rows in reverse.
# Zero-padding (rank → n multiple of 8, and all-zero padding matrices from
# bucket padding) flows through inv_d = where(d>0, 1/d, 0): padded outputs
# are exactly 0, no NaNs.

#: lane-block of matrices per grid step; VMEM scratch is n*n*blk*4 bytes.
_SPD_BLK = 128


def _spd_kernel(a_ref, b_ref, x_ref, a_s, y_s, *, n: int):
    a_s[...] = a_ref[...]
    y_s[...] = b_ref[...]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def fwd(j, _):
        colj = a_s[j]  # [n, blk] — column j of the trailing block
        ej = (row_iota == j).astype(jnp.float32)  # [n, 1]
        d2 = jnp.sum(colj * ej, axis=0)  # [blk] — diagonal entry
        inv_d = jnp.where(d2 > 0, jax.lax.rsqrt(d2), 0.0)
        lj = colj * inv_d[None, :]  # column j of L (diag value at row j)
        ljm = lj - ej  # (d - 1) at row j → the update stores lj into row j
        a_s[...] = a_s[...] - ljm[:, None, :] * lj[None, :, :]
        zj = jnp.sum(y_s[...] * ej, axis=0) * inv_d  # [blk]
        y_s[...] = y_s[...] - ljm * zj[None, :]
        return 0

    jax.lax.fori_loop(0, n, fwd, 0)
    x_ref[...] = jnp.zeros_like(x_ref)

    def bwd(jj, _):
        j = n - 1 - jj
        lrow = a_s[j]  # row j now holds L[:, j]
        ej = (row_iota == j).astype(jnp.float32)
        d = jnp.sum(lrow * ej, axis=0)
        inv_d = jnp.where(d > 0, 1.0 / d, 0.0)
        dot = jnp.sum(lrow * x_ref[...], axis=0)  # x[j] still 0 here
        zj = jnp.sum(y_s[...] * ej, axis=0)
        x_ref[...] = x_ref[...] + ej * ((zj - dot) * inv_d)[None, :]
        return 0

    jax.lax.fori_loop(0, n, bwd, 0)


def spd_solve_t(
    a_t: jax.Array,  # [n, n, B] float32 — SPD systems, batch on lanes
    b_t: jax.Array,  # [n, B] float32
    interpret: Optional[bool] = None,
) -> jax.Array:  # [n, B] float32
    """Fused batched Cholesky solve in transposed layout.

    Requires ``n % 8 == 0`` and ``B % 128 == 0`` (callers pad; zero-padding
    solves to exactly 0). ``interpret=None`` auto-selects interpreter
    off-TPU.
    """
    n, n2, bsz = a_t.shape
    if n != n2 or n % 8 != 0 or bsz % _SPD_BLK != 0:
        raise ValueError(f"spd_solve_t: bad shapes {a_t.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        functools.partial(_spd_kernel, n=n),
        grid=(bsz // _SPD_BLK,),
        in_specs=[
            pl.BlockSpec((n, n, _SPD_BLK), lambda i: (0, 0, i)),
            pl.BlockSpec((n, _SPD_BLK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, _SPD_BLK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, bsz), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, n, _SPD_BLK), jnp.float32),
            pltpu.VMEM((n, _SPD_BLK), jnp.float32),
        ],
        interpret=interpret,
        name="spd_solve_t",
    )(a_t, b_t)


# ---------------------------------------------------------------------------
# Fused gather + Gramian (the ALS normal-equation build)
# ---------------------------------------------------------------------------
#
# The XLA path materializes the gathered factors ``g = y[idx] * mask`` as a
# [B, K, R] tensor in HBM and the Gramian einsum re-reads it — the gathered
# bytes are paid ~3× (write + read + the original gather read). Measured
# consequence (PERF.md, round 3): the ALS iteration is gather-bound at
# ~0.32 of v5e HBM peak. This kernel streams each factor row HBM→VMEM
# exactly once: per solve row, per K-tile, it issues one async copy per
# rating's factor row into a VMEM tile, accumulates ``A += (g·w)ᵀ g`` and
# ``b += gᵀ rhs`` in f32 on the MXU, and writes each row's [R, R] system
# once. The [B, K, R] intermediate never exists.
#
# Cost model (why this can win despite per-row DMAs): the XLA path moves
# ~3 × B·K·R·4 bytes of HBM traffic per chunk; this kernel moves
# B·K·(R·4 + ~overhead) with K_tile copies in flight to hide latency. The
# risk is DMA-issue rate on small (rank·4 ≈ 200 B) transfers. The
# kernel is the build of every bucket as wide as the rank wherever the
# pallas solver resolves; on a v5e it moves a row in 52–67 ns, XLA's
# gather in 60 (PERF.md §5–§6).
#
# Replaces the same MLlib hot loop as the solver above (reference:
# ``examples/scala-parallel-recommendation/custom-prepartor/src/main/
# scala/ALSAlgorithm.scala:56-62``; SURVEY §2.8 "per-block normal
# equations").

#: Max factor rows (DMAs) in flight per K-tile; VMEM tile is kt·r_pad·4 B.
_FUSED_K_TILE = 512
#: Max solve rows per grid step — bounds the [Bt, R, R] output block and
#: the [Bt, K] index block in SMEM (Bt·K ≤ _FUSED_SMEM_IDX ints). R is
#: lane-padded to 128 inside the kernel, so the output block is
#: Bt·64 KiB and is double-buffered: 64 rows = 8 MiB, which with the
#: gather tile and weight blocks stays inside Mosaic's 16 MiB of scoped
#: VMEM (128 rows did not: refused for the chip at [4096, 128] buckets).
_FUSED_B_TILE = 64
#: Lanes of one SMEM ridge tile (one (8, _LANES) tile per grid step).
_LANES = 128
_FUSED_SMEM_IDX = 32768
#: Widest K a single kernel call takes. Wider problems (the rare
#: ultra-high-degree buckets) are split into K-slices summed in XLA.
#: The per-call SMEM index block is [bt, k] with bt·k ≤ _FUSED_SMEM_IDX,
#: so the real scalar-memory bound is _FUSED_SMEM_IDX·4 B = 128 KB
#: regardless of this constant; the split's job is to keep EIGHT rows'
#: index lists within that same bound — a [bt, k] block whose bt is not
#: a multiple of 8 (the sublane tiling) does not lower unless it is the
#: whole array (found on the chip: a (4, 8192) block of a (32, 8192)
#: array was refused).
_FUSED_K_SPLIT = _FUSED_SMEM_IDX // 8


def _gramian_kernel(idx_ref, w2_ref, rhs_ref, ridge_ref, y_ref, yty_ref,
                    a_ref, b_ref, gbuf, sem, *, k_tiles, kt, bt, r):
    """Double-buffered over (row, K-tile) steps: while tile s's [kt, r]
    gather block is being multiplied, tile s+1's row copies are already
    in flight into the other VMEM slot — DMA latency hides behind MXU
    work instead of serializing with it. One DMA semaphore per slot: a
    shared semaphore would mix completions of in-flight tiles and could
    release a wait with the other tile's copies."""
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    ).astype(jnp.float32)
    total = bt * k_tiles

    def copies(s, slot, action):
        """Start or wait the kt row copies of flat tile s in `slot`
        (wait recreates the same descriptors; each wait consumes one
        copy's worth of the slot's semaphore)."""
        b = s // k_tiles
        t = s % k_tiles

        def one(k, _):
            # pio: lint-ok[mosaic-per-row-dma] the per-row gather IS this kernel's design; the build of every bucket as wide as the rank under the pallas solver; 52-67 ns a row on a v5e (PERF.md §5)
            dma = pltpu.make_async_copy(
                y_ref.at[pl.ds(idx_ref[b, t * kt + k], 1), :],
                gbuf.at[slot, pl.ds(k, 1), :],
                sem.at[slot],
            )
            (dma.start if action == "start" else dma.wait)()
            return 0

        jax.lax.fori_loop(0, kt, one, 0)

    copies(0, 0, "start")

    def body(s, carry):
        a_acc, b_acc = carry
        slot = s % 2
        b = s // k_tiles
        t = s % k_tiles

        @pl.when(s + 1 < total)
        def _():
            copies(s + 1, (s + 1) % 2, "start")

        copies(s, slot, "wait")
        g = gbuf[slot]  # [kt, r] f32 (bf16 tables upcast at kernel entry)
        # reshape [kt] -> [kt, 1] in f32, THEN cast: Mosaic's layout
        # inference rejects the 1-D->2-D shape cast on bf16 vectors
        # (found by deviceless AOT compile of the bf16-gather variant)
        # pio: lint-ok[mosaic-unaligned-lane-slice] kt is a static param the AST cannot resolve; the wrapper guarantees kt % 128 == 0 (rounded at the gramian_fused entry), so t*kt offsets and kt sizes are lane-aligned
        w = w2_ref[b, pl.ds(t * kt, kt)][:, None].astype(g.dtype)
        # pio: lint-ok[mosaic-unaligned-lane-slice] same kt %128 wrapper guarantee as the w2 slice above
        rr = rhs_ref[b, pl.ds(t * kt, kt)][:, None].astype(g.dtype)
        a_acc = a_acc + jax.lax.dot_general(
            g * w, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        b_acc = b_acc + jnp.sum(
            (g * rr).astype(jnp.float32), axis=0
        )

        is_last_tile = t == k_tiles - 1

        @pl.when(is_last_tile)
        def _():
            a_ref[b] = a_acc + yty_ref[...] + ridge_ref[0, b] * eye
            b_ref[b] = b_acc

        # reset the accumulators at each row boundary — a select, not a
        # multiply: 0 * Inf = NaN would leak one bad row's overflow into
        # every subsequent row of the tile
        return (
            jnp.where(is_last_tile, jnp.zeros_like(a_acc), a_acc),
            jnp.where(is_last_tile, jnp.zeros_like(b_acc), b_acc),
        )

    jax.lax.fori_loop(
        0, total, body,
        (jnp.zeros((r, r), jnp.float32), jnp.zeros((r,), jnp.float32)),
    )


@functools.partial(
    jax.jit, static_argnames=("bt", "kt", "interpret")
)
def _gramian_fused_call(y, idx, w2, rhs, ridge, yty, bt, kt, interpret):
    b, k = idx.shape
    r = y.shape[1]
    return pl.pallas_call(
        functools.partial(
            _gramian_kernel, k_tiles=k // kt, kt=kt, bt=bt, r=r
        ),
        grid=(b // bt,),
        in_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            # each grid step's bt ridge values ride row 0 of an (8, 128)
            # SMEM tile of their own (see gramian_fused)
            pl.BlockSpec(
                (8, _LANES), lambda i: (i, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # y stays in HBM
            pl.BlockSpec((r, r), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, r, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((bt, r), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, r), jnp.float32),
            jax.ShapeDtypeStruct((b, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, kt, r), y.dtype),  # double-buffered gather tile
            pltpu.SemaphoreType.DMA((2,)),  # one per slot
        ],
        interpret=interpret,
        name="gramian_fused",
    )(idx, w2, rhs, ridge, y, yty)


def gramian_fused(
    y: jax.Array,  # [N, R] f32 or bf16 — opposite-side factor table (HBM)
    idx: jax.Array,  # [B, K] int32 — factor-row index per rating (0-padded)
    w2: jax.Array,  # [B, K] f32 — Gramian weight (mask, or c-1 implicit)
    rhs: jax.Array,  # [B, K] f32 — rhs weight (masked rating / c·p)
    ridge: jax.Array,  # [B] f32 — per-row diagonal ridge (λ·n_u)
    yty: Optional[jax.Array] = None,  # [R, R] f32 — implicit-mode base
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused normal-equation build: returns ``(A [B, R, R] f32, b [B, R]
    f32)`` with ``A_b = yty + ridge_b·I + Σ_k w2[b,k]·y[idx[b,k]]⊗y[idx[b,k]]``
    and ``b_b = Σ_k rhs[b,k]·y[idx[b,k]]`` — without materializing the
    ``[B, K, R]`` gathered-factor intermediate in HBM.

    Padding contract: invalid (b, k) slots must carry ``w2 = rhs = 0``
    (their ``idx`` may be any in-range value; 0 by convention) — the
    gathered row is multiplied by zero, so correctness never depends on
    the index padding. ``R`` must be a multiple of 8 (callers pad the rank
    once, as the solver path already does); B and K are padded here, and R
    is lane-padded to 128 internally: Mosaic requires DMA slices to be
    aligned to the 128-lane tiling (discovered by deviceless AOT compile —
    a 1×56 row copy does not lower), so the kernel streams aligned 1×128
    rows of a zero-padded table instead. The padded lanes contribute
    zeros to A and b, and a 56-wide Gramian already occupies one 128×128
    MXU tile, so the extra lanes cost DMA bytes only: r_pad·4 = 512 B per
    row vs the XLA path's ~3·r·4 = 672 B at bench rank — a thinner win
    than the unpadded 224 B, which is what the hardware A/B prices.

    ``interpret=None`` auto-selects interpreter off-TPU. No XLA fallback:
    the caller (``_solve_side_traced``) owns the dispatch — every bucket
    as wide as the rank under the pallas solver; narrower (K < rank)
    buckets keep their own builds.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, r = y.shape
    if r % 8 != 0:
        raise ValueError(f"gramian_fused: rank must be padded to 8s, got {r}")
    b, k = idx.shape
    if k > _FUSED_K_SPLIT:
        # K-slice split: base terms (ridge·I, yty) ride the first slice
        # only, the rest contribute pure Σ w·y⊗y — summing slice outputs
        # is exact. Costs one [B, R, R] add per extra slice, paid only by
        # the ultra-wide buckets.
        a_tot, b_tot = None, None
        zero_ridge = jnp.zeros_like(jnp.asarray(ridge, jnp.float32))
        for k0 in range(0, k, _FUSED_K_SPLIT):
            sl = slice(k0, min(k, k0 + _FUSED_K_SPLIT))
            a_s, b_s = gramian_fused(
                y, idx[:, sl], w2[:, sl], rhs[:, sl],
                ridge if k0 == 0 else zero_ridge,
                yty if k0 == 0 else None,
                interpret=interpret,
            )
            a_tot = a_s if a_tot is None else a_tot + a_s
            b_tot = b_s if b_tot is None else b_tot + b_s
        return a_tot, b_tot
    # kt must be lane-aligned: the kernel slices w2/rhs at [b, t*kt : +kt]
    # in the lane dim, and Mosaic rejects unaligned lane slices (the same
    # deviceless-AOT finding as the 1×56 row DMAs). The AOT sweep only
    # covers k ≥ 512 shapes where kt == _FUSED_K_TILE; rounding keeps the
    # guarantee for narrow buckets too (padding contract absorbs the
    # zero-weighted extra slots).
    kt = min(_round_up(k, 128), _FUSED_K_TILE)
    k_pad = _round_up(k, kt)
    # rows per grid step: as many as the SMEM index block allows, in
    # whole sublane tiles of 8 (k_pad <= _FUSED_K_SPLIT leaves room for 8)
    bt = min(_FUSED_B_TILE, _FUSED_SMEM_IDX // k_pad // 8 * 8)
    b_pad = _round_up(b, bt)
    idx = jnp.asarray(idx, jnp.int32)
    w2 = jnp.asarray(w2, jnp.float32)
    rhs = jnp.asarray(rhs, jnp.float32)
    if k_pad != k or b_pad != b:
        pk, pb = k_pad - k, b_pad - b
        idx = jnp.pad(idx, ((0, pb), (0, pk)))
        w2 = jnp.pad(w2, ((0, pb), (0, pk)))
        rhs = jnp.pad(rhs, ((0, pb), (0, pk)))
        ridge = jnp.pad(jnp.asarray(ridge, jnp.float32), (0, pb))
    if y.dtype == jnp.bfloat16:
        # Per-row DMA floor (deviceless-AOT finding): Mosaic cannot slice
        # one sublane of a bf16-tiled VMEM buffer, and the minimum
        # lane-aligned copy is 128 lanes × 32 bits = 512 B — so bf16
        # CANNOT reduce this kernel's gathered bytes below the f32 path's
        # 512 B/row. Upcasting is exact and keeps ``gather_dtype="bf16"``
        # composable with this kernel (which then runs at f32 table
        # width, honestly).
        y = y.astype(jnp.float32)
    # lane-pad the factor table so every per-row DMA is a tiling-aligned
    # 1×r_pad copy (see docstring); the zero lanes are inert in A and b
    r_pad = _round_up(r, 128)
    if r_pad != r:
        y = jnp.pad(y, ((0, 0), (0, r_pad - r)))
    if yty is None:
        yty = jnp.zeros((r_pad, r_pad), jnp.float32)
    elif r_pad != r:
        yty = jnp.pad(jnp.asarray(yty, jnp.float32),
                      ((0, r_pad - r), (0, r_pad - r)))
    # one (8, 128) ridge tile per grid step, the step's bt values in its
    # first row: a rank-1 (bt,) SMEM block was refused on the chip (a
    # (64,) block of a (1024,) array), and a rank-1 array of 128-wide
    # blocks by Mosaic's layout check; a whole tile is what both take
    ridge = jnp.pad(
        jnp.asarray(ridge, jnp.float32).reshape(b_pad // bt, 1, bt),
        ((0, 0), (0, 7), (0, _LANES - bt)),
    ).reshape(-1, _LANES)
    a, bvec = _gramian_fused_call(
        y, idx, w2, rhs, ridge, yty, bt, kt, interpret,
    )
    return a[:b, :r, :r], bvec[:b, :r]


def top_k_for_users_streaming(
    user_factors: jax.Array,
    item_factors: jax.Array,
    user_idx: jax.Array,
    k: int,
    exclude_idx: Optional[jax.Array] = None,
    **kw,
) -> Tuple[jax.Array, jax.Array]:
    """Known-user wrapper (gather user vectors, then stream)."""
    return top_k_streaming(
        user_factors[user_idx], item_factors, k, exclude_idx, **kw
    )
