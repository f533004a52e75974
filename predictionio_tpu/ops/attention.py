"""Attention kernels: blockwise (flash) and sequence-parallel (ring, Ulysses).

The reference has no sequence models at all (SURVEY §5 "Long-context /
sequence parallelism — absent"; its nearest neighbor is the MarkovChain
transition matrix, ``e2/.../MarkovChain.scala``). This framework treats
long-context as first-class: the sequence-recommendation engine
(:mod:`predictionio_tpu.models.sequencerec`) and any future sequence model
train over context windows sharded across the mesh ``seq`` axis.

Three schedules, one math:

- :func:`flash_attention` — single-device blockwise attention with an online
  softmax over the (Q block, KV block) pairs that can hold a kept score:
  O(block²) memory instead of O(L²) forward and backward (a flash-style
  custom VJP), grouped key/value heads, a segment mask for packed rows.
- :func:`ring_attention` — sequence parallelism over a mesh axis: every
  device keeps its Q chunk, KV chunks rotate around the ring via
  ``ppermute`` (ICI neighbor exchanges), partial results merge with the same
  online-softmax rescaling. Peak memory per device is O(L²/N²) score tiles;
  communication overlaps compute chunk by chunk.
- :func:`ulysses_attention` — all-to-all alternative: resharding seq→heads
  before attention and heads→seq after, so each device runs *full-sequence*
  attention for a subset of heads. Two all-to-alls instead of N-1 ring
  hops — better when heads ≥ devices and ICI all-to-all bandwidth is good.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from . import chosen_core

SEQ_AXIS = "seq"

_NEG_BIG = -1e30  # additive mask value (finite: keeps fully-masked rows NaN-free)


def _attend_block(q, k, v, m, l, o, mask, scale):
    """One online-softmax accumulation step.

    q [..., Lq, D], k/v [..., Lk, D]; running (m, l, o) with m/l [..., Lq]
    and o [..., Lq, D]; ``mask`` is an optional [Lq, Lk] bool (True = keep).
    """
    scores = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_BIG)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _grouped_and_padded(q, k, v, segment_ids, bq: int, bk: int):
    """What both single-device implementations take: q as [B, Hkv, G, Lq,
    D] and k, v padded to whole blocks, and the rows' history ids (all one
    history without ``segment_ids``), unpadded: the tiles mask by position."""
    b, h, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads do not divide over {hkv} key/value heads")
    if segment_ids is None:
        seg_q = jnp.zeros((b, lq), jnp.int32)
        seg_k = jnp.zeros((b, lk), jnp.int32)
    else:
        seg_q = seg_k = segment_ids.astype(jnp.int32)
    qg = q.reshape(b, hkv, h // hkv, lq, d)
    pad_q, pad_k = -lq % bq, -lk % bk
    if pad_q:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    return qg, k, v, seg_q, seg_k


def _block_pairs(lq: int, lk: int, bq: int, bk: int, causal: bool, window: int = 0):
    """Index arrays (i, j) of every Q block i and KV block j that can hold
    a kept score: all of them, or under a causal mask those on or below the
    diagonal, less, under a ``window``, those whose nearest pair of slots
    already lies a window apart."""
    pairs = [
        (i, j) for i in range(lq // bq) for j in range(lk // bk)
        if (not causal or j * bk <= (i + 1) * bq - 1)
        and (not window or i * bq - ((j + 1) * bk - 1) < window)
    ]
    return (jnp.asarray([p[0] for p in pairs], jnp.int32),
            jnp.asarray([p[1] for p in pairs], jnp.int32))


def _opaque(n: int):
    """A trip count the compiler cannot read: a loop it can count it may
    run as a scan that keeps every iteration's tiles."""
    return jax.lax.optimization_barrier(jnp.int32(n))


def _pair_meets(seg_q, seg_k, i, j, bq, bk):
    """Whether any slot of Q block i can share a history with a slot of
    KV block j: their id ranges meet. Where they do not, the whole tile
    is masked and is skipped."""
    sq = jax.lax.dynamic_slice_in_dim(seg_q, i * bq, bq, axis=1)
    sk = jax.lax.dynamic_slice_in_dim(seg_k, j * bk, bk, axis=1)
    return jnp.any((sq.max(1) >= sk.min(1)) & (sq.min(1) <= sk.max(1)))


def _pair_tile(q, k, seg_q, seg_k, i, j, bq, bk, lk, causal, scale, after, window=0,
               chosen=None):
    """Scores of Q block i against KV block j and the mask of those kept
    (under a ``window`` a slot keeps itself and the ``window - 1`` before it;
    with ``chosen`` [B, Lq, Lk] bool, a mask the step made from its data, only
    the pairs that holds true).
    q [B, Hkv, G, Lq, D], k [B, Hkv, Lk, D], seg [B, L]. ``after`` is a
    value of this iteration's carry: the barrier makes the tile wait for
    it, or the compiler computes every pair's tile ahead of the loop and
    keeps them all ([pairs, B, H, bq, bk] float32, gigabytes at 8k)."""
    qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=3)
    kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=2)
    qi, kj, after = jax.lax.optimization_barrier((qi, kj, after))
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    q_pos = i * bq + jnp.arange(bq)
    k_pos = j * bk + jnp.arange(bk)
    keep = jnp.broadcast_to(k_pos[None, :] < lk, (bq, bk))
    if causal:
        keep = keep & (q_pos[:, None] >= k_pos[None, :])
    if window:
        keep = keep & (q_pos[:, None] - k_pos[None, :] < window)
    sq = jax.lax.dynamic_slice_in_dim(seg_q, i * bq, bq, axis=1)
    sk = jax.lax.dynamic_slice_in_dim(seg_k, j * bk, bk, axis=1)
    keep = keep[None] & (sq[:, :, None] == sk[:, None, :])  # [B, bq, bk]
    if chosen is not None:
        keep = keep & jax.lax.dynamic_slice(chosen, (0, i * bq, j * bk), (keep.shape[0], bq, bk))
    return qi, kj, s, keep[:, None, None], after


def _flash_forward(q, k, v, seg_q, seg_k, causal, bq, bk, lk, stats_dtype=jnp.float32,
                   window=0, chosen=None):
    """Online softmax over the block pairs; returns o (q's dtype, v's
    width) and the log-sum-exp of every row [B, Hkv, G, Lq] (float32). The
    running maximum, sum and output are kept in ``stats_dtype`` between
    tiles and taken up to float32 inside one."""
    b, hkv, g, lq, d = q.shape
    f32 = jnp.float32
    scale = 1.0 / np.sqrt(d)
    ii, jj = _block_pairs(lq, k.shape[2], bq, bk, causal, window)

    def body(t, carry):
        i, j = ii[t], jj[t]

        def attend(carry):
            m, l, o = carry
            mi = jax.lax.dynamic_slice_in_dim(m, i * bq, bq, axis=3)
            qi, kj, s, keep, mi = _pair_tile(
                q, k, seg_q, seg_k, i, j, bq, bk, lk, causal, scale, mi, window, chosen)
            mi = mi.astype(f32)
            li = jax.lax.dynamic_slice_in_dim(l, i * bq, bq, axis=3).astype(f32)
            oi = jax.lax.dynamic_slice_in_dim(o, i * bq, bq, axis=3).astype(f32)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=2)
            sm = jnp.where(keep, s, _NEG_BIG)
            m_new = jnp.maximum(mi, sm.max(-1))
            corr = jnp.exp(mi - m_new)
            p = jnp.where(keep, jnp.exp(sm - m_new[..., None]), 0.0)
            l_new = li * corr + p.sum(-1)
            o_new = oi * corr[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(old, new.astype(stats_dtype), i * bq, axis=3)
                for old, new in ((m, m_new), (l, l_new), (o, o_new)))

        meet = _pair_meets(seg_q, seg_k, i, j, bq, bk)
        return jax.lax.cond(meet, attend, lambda c: c, carry)

    m0 = jnp.full((b, hkv, g, lq), _NEG_BIG, stats_dtype)
    l0 = jnp.zeros((b, hkv, g, lq), stats_dtype)
    o0 = jnp.zeros((b, hkv, g, lq, v.shape[-1]), stats_dtype)
    m, l, o = jax.lax.fori_loop(0, _opaque(len(ii)), body, (m0, l0, o0))
    l = jnp.maximum(l.astype(f32), 1e-30)
    return (o.astype(f32) / l[..., None]).astype(q.dtype), m.astype(f32) + jnp.log(l)


def _flash_backward(q, k, v, seg_q, seg_k, o, lse, do, causal, bq, bk, lk, window=0,
                    chosen=None):
    """The flash backward pass: scores are recomputed tile by tile from q,
    k and the saved log-sum-exp; the score matrix is never a residual."""
    b, hkv, g, lq, d = q.shape
    scale = 1.0 / np.sqrt(d)
    ii, jj = _block_pairs(lq, k.shape[2], bq, bk, causal, window)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)

    def body(t, carry):
        i, j = ii[t], jj[t]

        def attend(carry):
            dq, dk, dv = carry
            dq_old = jax.lax.dynamic_slice_in_dim(dq, i * bq, bq, axis=3)
            qi, kj, s, keep, dq_old = _pair_tile(
                q, k, seg_q, seg_k, i, j, bq, bk, lk, causal, scale, dq_old, window, chosen)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=2)
            doi = jax.lax.dynamic_slice_in_dim(do, i * bq, bq, axis=3)
            lsei = jax.lax.dynamic_slice_in_dim(lse, i * bq, bq, axis=3)
            di = jax.lax.dynamic_slice_in_dim(delta, i * bq, bq, axis=3)
            p = jnp.where(keep, jnp.exp(s - lsei[..., None]), 0.0)
            dvj = jnp.einsum("bkgqc,bkgqd->bkcd", p.astype(do.dtype), doi,
                             preferred_element_type=jnp.float32)
            dp = jnp.einsum("bkgqd,bkcd->bkgqc", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - di[..., None]) * scale).astype(q.dtype)
            dqi = jnp.einsum("bkgqc,bkcd->bkgqd", ds, kj,
                             preferred_element_type=jnp.float32)
            dkj = jnp.einsum("bkgqc,bkgqd->bkcd", ds, qi,
                             preferred_element_type=jnp.float32)

            def add(buf, blk, at, axis):
                old = jax.lax.dynamic_slice_in_dim(buf, at, blk.shape[axis], axis)
                return jax.lax.dynamic_update_slice_in_dim(buf, old + blk, at, axis)

            dq = jax.lax.dynamic_update_slice_in_dim(dq, dq_old + dqi, i * bq, 3)
            return dq, add(dk, dkj, j * bk, 2), add(dv, dvj, j * bk, 2)

        meet = _pair_meets(seg_q, seg_k, i, j, bq, bk)
        return jax.lax.cond(meet, attend, lambda c: c, carry)

    zeros = (jnp.zeros(q.shape, jnp.float32), jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32))
    dq, dk, dv = jax.lax.fori_loop(0, _opaque(len(ii)), body, zeros)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seg_q, seg_k, causal, bq, bk, lk, stats_dtype, window):
    return _flash_forward(q, k, v, seg_q, seg_k, causal, bq, bk, lk, stats_dtype, window)[0]


def _flash_vjp_fwd(q, k, v, seg_q, seg_k, causal, bq, bk, lk, stats_dtype, window):
    o, lse = _flash_forward(q, k, v, seg_q, seg_k, causal, bq, bk, lk, stats_dtype, window)
    return o, (q, k, v, seg_q, seg_k, o, lse)


def _flash_vjp_bwd(causal, bq, bk, lk, stats_dtype, window, res, do):
    q, k, v, seg_q, seg_k, o, lse = res
    dq, dk, dv = _flash_backward(
        q, k, v, seg_q, seg_k, o, lse, do, causal, bq, bk, lk, window)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_chosen(q, k, v, seg_q, seg_k, chosen, bq, bk, lk, stats_dtype):
    """:func:`_flash` under a causal mask and ``chosen``; also hands back the
    rows' log-sum-exp [B, Hkv, G, Lq], which no gradient flows through (it is
    for a pass that holds the attention weights constant)."""
    return _flash_forward(q, k, v, seg_q, seg_k, True, bq, bk, lk, stats_dtype, 0, chosen)


def _flash_chosen_fwd(q, k, v, seg_q, seg_k, chosen, bq, bk, lk, stats_dtype):
    o, lse = _flash_forward(q, k, v, seg_q, seg_k, True, bq, bk, lk, stats_dtype, 0, chosen)
    return (o, lse), (q, k, v, seg_q, seg_k, chosen, o, lse)


def _flash_chosen_bwd(bq, bk, lk, stats_dtype, res, cotangents):
    q, k, v, seg_q, seg_k, chosen, o, lse = res
    dq, dk, dv = _flash_backward(
        q, k, v, seg_q, seg_k, o, lse, cotangents[0], True, bq, bk, lk, 0, chosen)
    return dq, dk, dv, None, None, None


_flash_chosen.defvjp(_flash_chosen_fwd, _flash_chosen_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_k", "block_q", "stats_dtype", "window"))
def flash_attention(
    q: jax.Array,  # [B, H, Lq, D]
    k: jax.Array,  # [B, Hkv, Lk, D], H a multiple of Hkv
    v: jax.Array,  # [B, Hkv, Lk, Dv]: the values' width is their own
    causal: bool = True,
    block_k: int = 512,
    segment_ids: Optional[jax.Array] = None,  # [B, L]: packed rows, Lq == Lk
    block_q: Optional[int] = None,
    stats_dtype: str = "float32",
    window: int = 0,
) -> jax.Array:
    """Blockwise attention with online softmax (single device): [B, H, Lq,
    Dv]. Scores are scaled by ``1 / sqrt(D)``, the width q and k share.
    With a ``window`` (under ``causal``) a slot attends to itself and the
    ``window - 1`` slots before it: tiles wholly outside it are left out of
    the loop (:func:`tiles_skipped_by_window`), the ones that straddle its
    edge are masked inside; 0 = no window.

    Query heads share key/value heads in groups (``H / Hkv`` each). With
    ``segment_ids`` a slot attends only to slots of its own id (histories
    packed into one row); tiles whose id ranges do not meet are skipped,
    as are, under ``causal``, the tiles above the diagonal. Differentiable:
    the backward pass recomputes each tile from q, k and the rows'
    log-sum-exp, so neither direction holds an [L, L] matrix."""
    if window and not causal:
        raise ValueError("a window is one of slots before a slot: causal attention only")
    b, h, lq, d = q.shape
    lk = k.shape[2]
    bk = min(block_k, lk)
    bq = min(block_q or block_k, lq)
    qg, k, v, seg_q, seg_k = _grouped_and_padded(q, k, v, segment_ids, bq, bk)
    seg_q = jnp.pad(seg_q, ((0, 0), (0, -lq % bq)), mode="edge")
    seg_k = jnp.pad(seg_k, ((0, 0), (0, -lk % bk)), mode="edge")
    o = _flash(qg, k, v, seg_q, seg_k, causal, bq, bk, lk, jnp.dtype(stats_dtype), window)
    return o[:, :, :, :lq].reshape(b, h, lq, v.shape[-1])


def tiles_skipped_by_window(length: int, block: int, window: int) -> int:
    """Tiles of one causal pass of :func:`flash_attention` over rows of
    ``length`` slots that the ``window`` alone leaves out of the loop, from
    the static pair lists (0 without a window)."""
    blk = min(block, length)
    padded = length + -length % blk
    return (len(_block_pairs(padded, padded, blk, blk, True)[0])
            - len(_block_pairs(padded, padded, blk, blk, True, window)[0]))


@functools.partial(jax.jit, static_argnames=("block", "stats_dtype"))
def chosen_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, Hkv, L, D]
    v: jax.Array,  # [B, Hkv, L, Dv]
    chosen: jax.Array,  # [B, L, L] bool: the keys (last axis) each query reads
    segment_ids: Optional[jax.Array] = None,
    block: int = 512,
    stats_dtype: str = "float32",
):
    """:func:`flash_attention` (causal, inside histories) over the pairs that
    ``chosen``, a mask the step itself made from its data, holds true: the
    blockwise loop and its VJP run every tile the position masks leave and
    apply ``chosen`` inside it. Returns o [B, H, L, Dv] and the log-sum-exp of
    every row over its chosen keys [B, H, L] (float32; no gradient flows
    through it: it is what :func:`chosen_weights_tile` turns scores back into
    weights with). A query without a chosen key gives zeros. Where
    :func:`.chosen_core.core_kind` says so (a TPU, heads and values of whole
    lane tiles, rows of whole kernel tiles) the core is that module's Pallas
    kernel pair, whose tile is its own; the loop everywhere else."""
    b, h, lq, _ = q.shape
    if chosen_core.core_kind(h, k.shape[1], q.shape[-1], v.shape[-1], lq, stats_dtype) == "pallas":
        return chosen_core.chosen_core(q, k, v, chosen, segment_ids)
    blk = min(block, lq)
    qg, k, v, seg_q, seg_k = _grouped_and_padded(q, k, v, segment_ids, blk, blk)
    pad = -lq % blk
    seg_q = jnp.pad(seg_q, ((0, 0), (0, pad)), mode="edge")
    chosen = jnp.pad(chosen, ((0, 0), (0, pad), (0, pad)))
    o, lse = _flash_chosen(qg, k, v, seg_q, seg_q, chosen, blk, blk, lq, jnp.dtype(stats_dtype))
    return o[:, :, :, :lq].reshape(b, h, lq, v.shape[-1]), lse[:, :, :, :lq].reshape(b, h, lq)


def chosen_weights_tile(qg, k, lse, seg, chosen, i, j, block: int, after):
    """The attention weights of :func:`chosen_attention` again, for a second
    pass that holds them constant: of Q block i over KV block j, summed over
    all heads and divided by their number, [B, block, block] float32 (a
    query's sum to 1 over its chosen keys), and the tile's mask [B, block,
    block]. ``qg`` [B, Hkv, G, L, D], ``k`` [B, Hkv, L, D], ``lse`` [B, Hkv,
    G, L] as the first pass gave it, L whole blocks; ``after`` as in
    :func:`_pair_tile`."""
    heads = qg.shape[1] * qg.shape[2]
    qi, kj, s, keep, after = _pair_tile(
        qg, k, seg, seg, i, j, block, block, k.shape[2], True, 1.0 / np.sqrt(qg.shape[-1]),
        after, 0, chosen)
    lsei = jax.lax.dynamic_slice_in_dim(lse, i * block, block, axis=3)
    p = jnp.where(keep, jnp.exp(s - lsei[..., None]), 0.0)
    return p.sum((1, 2)) / heads, keep[:, 0, 0], after


#: tile edge of the Pallas kernel, forward and backward: the best of 512,
#: 1024 and 2048 (which does not fit the chip's fast memory) at 32 heads x
#: 8,192 slots, keys of 192, values of 128 (PERF.md section 6, PR 31)
SPLASH_BLOCK = 1024


@functools.lru_cache(maxsize=8)
def _splash_kernel(heads: int, length: int, block: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    causal = masks.MultiHeadMask([masks.CausalMask((length, length))] * heads)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block, block_q_dkv=block,
        block_kv_dkv=block, block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
    # the kernel keeps its block tables as arrays: made under a trace they
    # would be that trace's, and this cache outlives it
    with jax.ensure_compile_time_eval():
        made = kernel.make_splash_mha(
            causal, head_shards=1, q_seq_shards=1, block_sizes=sizes, interpret=interpret)
    return made, kernel.SegmentIds


def splash_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, H, L, D]
    v: jax.Array,  # [B, H, L, Dv]
    segment_ids: jax.Array,  # [B, L]
    block: int = SPLASH_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention inside histories by JAX's own Pallas kernel for the
    TPU (``jax.experimental.pallas.ops.tpu.splash_attention``, forward and
    fused backward): score tiles never leave the chip's fast memory, where
    :func:`flash_attention`'s XLA loop writes each to HBM. It computes
    EVERY tile on or below the diagonal and masks by ``segment_ids``
    inside it, so its time does not depend on where the histories end: the
    faster of the two on long histories, the slower where most tiles lie
    between short ones. Softmax statistics in float32. L a multiple of the
    block (itself of 128)."""
    b, h, length, d = q.shape
    made, ids = _splash_kernel(h, length, min(block, length), interpret)
    scaled = (q.astype(jnp.float32) * (1.0 / np.sqrt(d))).astype(q.dtype)
    seg = segment_ids.astype(jnp.int32)
    return jax.vmap(lambda q_, k_, v_, s: made(q_, k_, v_, ids(s, s)))(scaled, k, v, seg)


def _splash_fits(q, k, stats_dtype: str) -> bool:
    length = q.shape[2]
    return (jax.default_backend() == "tpu" and stats_dtype == "float32" and q.shape == k.shape
            and length % 128 == 0 and length % min(SPLASH_BLOCK, length) == 0)


def ring_attention(
    q: jax.Array,  # [B, H, L, D] — L sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = SEQ_AXIS,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, L], sharded like L
) -> jax.Array:
    """Sequence-parallel attention: KV chunks rotate around the mesh ring
    (with ``segment_ids`` their ids rotate with them, and a slot attends
    only to slots of its own id).

    Inputs/outputs are length-sharded over ``axis`` (chunk i on device i,
    contiguous order). Each of the N ring steps attends the local Q chunk to
    the visiting KV chunk with global-position causal masking, merging via
    online-softmax rescaling; ``ppermute`` moves KV to the next neighbor —
    N-1 ICI hops, never materializing more than one remote chunk.
    """
    n = mesh.shape[axis]
    b, h, l, d = q.shape
    assert l % n == 0, f"sequence length {l} not divisible by ring size {n}"
    chunk = l // n
    scale = 1.0 / np.sqrt(d)

    if segment_ids is None:
        segment_ids = jnp.zeros((b, l), jnp.int32)

    def local(qc, kc, vc, sq):
        # qc/kc/vc: [B, H, chunk, D] local shards; sq [B, chunk]
        my = jax.lax.axis_index(axis)
        q_pos = my * chunk + jnp.arange(chunk)
        qf = qc.astype(jnp.float32)

        def step(s, carry):
            m, l_, o, kc_, vc_, sk_ = carry
            src = (my - s) % n  # owner of the currently-visiting KV chunk
            k_pos = src * chunk + jnp.arange(chunk)
            mask = (sq[:, :, None] == sk_[:, None, :])[:, None]  # [B, 1, q, k]
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            m, l_, o = _attend_block(
                qf, kc_.astype(jnp.float32), vc_, m, l_, o, mask, scale,
            )
            perm = [(i, (i + 1) % n) for i in range(n)]
            kc_ = jax.lax.ppermute(kc_, axis, perm)
            vc_ = jax.lax.ppermute(vc_, axis, perm)
            sk_ = jax.lax.ppermute(sk_, axis, perm)
            return m, l_, o, kc_, vc_, sk_

        m0 = jnp.full((b, h, chunk), _NEG_BIG, dtype=jnp.float32)
        l0 = jnp.zeros((b, h, chunk), dtype=jnp.float32)
        o0 = jnp.zeros((b, h, chunk, v.shape[-1]), dtype=jnp.float32)
        m, l_, o, _, _, _ = jax.lax.fori_loop(
            0, n, step, (m0, l0, o0, kc, vc, sq)
        )
        return (o / jnp.maximum(l_, 1e-30)[..., None]).astype(qc.dtype)

    spec = P(None, None, axis, None)
    f = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, P(None, axis)),
        out_specs=spec, check_vma=False,
    )
    return jax.jit(f)(q, k, v, segment_ids.astype(jnp.int32))


def ulysses_attention(
    q: jax.Array,  # [B, H, L, D] — L sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = SEQ_AXIS,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, L], sharded like L
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses schedule):
    reshard seq→heads, full-sequence attention per head subset, reshard
    heads→seq. Requires ``H % mesh.shape[axis] == 0``."""
    n = mesh.shape[axis]
    b, h, l, d = q.shape
    assert h % n == 0, f"{h} heads not divisible by {n} devices"
    assert l % n == 0, f"sequence length {l} not divisible by {n} devices"

    if segment_ids is None:
        segment_ids = jnp.zeros((b, l), jnp.int32)

    def local(qc, kc, vc, sc):
        # [B, H, L/N, D] → all-to-all → [B, H/N, L, D]
        def a2a_in(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        def a2a_out(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        qh, kh, vh = a2a_in(qc), a2a_in(kc), a2a_in(vc)
        seg = jax.lax.all_gather(sc, axis, axis=1, tiled=True)  # the whole row's ids
        oh = flash_attention(qh, kh, vh, causal=causal, segment_ids=seg)
        return a2a_out(oh)

    spec = P(None, None, axis, None)
    f = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, P(None, axis)),
        out_specs=spec, check_vma=False,
    )
    return jax.jit(f)(q, k, v, segment_ids.astype(jnp.int32))


def attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, Hkv, L, D]
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis: str = SEQ_AXIS,
    causal: bool = True,
    schedule: str = "auto",
    segment_ids: Optional[jax.Array] = None,
    block: int = 512,
    stats_dtype: str = "float32",
    kernel: str = "xla",
    window: int = 0,
) -> jax.Array:
    """Dispatch: single-device flash when no mesh / 1-device axis; otherwise
    ring (default) or Ulysses (``schedule="ulysses"``, when heads divide).
    The sharded schedules repeat grouped key/value heads to one per query
    head, and keep their softmax statistics in float32 whatever
    ``stats_dtype`` says. ``kernel="splash"`` asks the single device for
    :func:`splash_attention` in place of the XLA loop; it is given where it
    can run (a TPU, packed rows of whole blocks, one key/value head a query
    head, float32 statistics) and the XLA loop elsewhere. A ``window``
    (:func:`flash_attention`) is the XLA loop's on a single device."""
    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        if (kernel == "splash" and causal and segment_ids is not None and not window
                and _splash_fits(q, k, stats_dtype)):
            return splash_attention(q, k, v, segment_ids)
        if kernel not in ("xla", "splash"):
            raise ValueError(f"unknown attention kernel {kernel!r}")
        return flash_attention(
            q, k, v, causal=causal, block_k=block, segment_ids=segment_ids,
            stats_dtype=stats_dtype, window=window)
    if window:
        raise ValueError("the sharded attention schedules know no window")
    if k.shape[1] != q.shape[1]:
        k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        v = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
    if schedule == "ulysses":
        return ulysses_attention(q, k, v, mesh, axis, causal, segment_ids)
    if schedule not in ("auto", "ring"):
        raise ValueError(f"unknown attention schedule {schedule!r}")
    return ring_attention(q, k, v, mesh, axis, causal, segment_ids)
