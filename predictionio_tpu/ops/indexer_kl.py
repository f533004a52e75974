"""The indexers' loss of learned sparse attention as a Pallas kernel pair for
the TPU: the KL of every query between the main heads' weights over its chosen
keys and the softmax of its index scores over them (:func:`.dsa.index_loss`'s
other form; the XLA loop ``dsa._kl`` is the one the CPU, toy widths, ragged
rows and a bfloat16 head-weighted sum run).

Both kernels walk WHOLE tiles of ``chosen_core.TILE`` queries by as many keys
(grid: rows, query tiles, key tiles innermost) and make everything of a tile in
VMEM: the 32 main heads' ``q k``, their weights from the core's log-sum-exp
summed over the heads, the tile's index scores a head at a time and the KL's
terms, every head walked unrolled in the body. None of the ``[T, T]`` arrays
reaches HBM. The mask a byte a pair and the tables a tile that let a grid step
skip a tile without fetching are the core's own (``chosen_core._keep``,
``_tables``): a tile that holds no kept pair adds nothing to any of the sums.

Keys lie on the sublanes and queries on the lanes (the mask's tile is turned
round in the body, once a tile), so what a query carries is a row: the core's
log-sum-exp, the heads' weights ``iw`` (handed in a head first), the running
maximum and sum of its index scores, its KL, its cotangent. ``iq`` and its
cotangent keep the layout the projection gives them, the heads side by side on
the lanes ([L, J d]: a head is a slice of the lanes; handed in a head first,
XLA laid the projection's output out that way for the choice's strips too,
and ``seq.attn.index`` read a fifth more).

Forward: scratch holds, a number a query, the running maximum and sum of ``I``
over the kept keys and ``sum_s (p log p - p I)``; a query tile's last key tile
writes the KL and the log-sum-exp of ``I``. Backward (the same grid, ONE
kernel): ``p`` and ``I`` again, ``d_I = g (softmax(I) - p)`` on the kept pairs,
then an index head at a time its scores once more, ``d_iq`` (scratch over the
key tiles), ``d_ik`` (the row's ``[L, d]`` float32 stays in VMEM as the
output's block) and ``d_iw``. Nothing flows to the main heads.

Precision, the loop's: every product takes its inputs as they come (bfloat16
on the chip; the pull-backs take ``d_s`` rounded to ``iq``'s dtype) and
accumulates in float32; scores, ``exp``, the head-weighted sum, ``p log p``,
the softmax of ``I`` and every accumulator in float32. A query without a kept
key gives the loop's numbers (``-1e30 + log(1e-30)``) and no gradient.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import chosen_core
from .chosen_core import _LANES, _NEG_BIG, _NT, _VMEM

#: what a layer's recomputation keeps of the forward kernel (its two outputs)
KEPT = "index_loss"
_TN = (((0,), (0,)), ((), ()))  # a [K, M] by b [K, N] -> [M, N]


def loss_kind(heads: int, kv_heads: int, head_dim: int, index_heads: int, index_dim: int,
              length: int, sum_dtype=jnp.float32, interpret: bool = False) -> str:
    """What implements :func:`.dsa.index_loss` at these shapes: "pallas" (this
    module's kernel pair) where the backend is a TPU (``interpret``: or the
    kernel's interpreter, for tests), the main heads' width is whole lane
    tiles, a row is whole kernel tiles (themselves whole lane tiles), the
    query heads divide over the key/value heads, the head-weighted sum is
    float32 and a grid step's blocks fit VMEM (the resident ``d_ik`` of a row,
    the query tile of all heads, the index queries and their cotangent, a
    dozen ``[T, T]`` float32 temporaries; a lane tile at least a row of each,
    4 bytes a number); "xla" (the loop over the tiles) otherwise."""
    edge = chosen_core._tile(length)
    lanes = max(index_dim, _LANES)
    blocks = 4 * (2 * length * lanes + 2 * heads * edge * max(head_dim, _LANES)
                  + 5 * index_heads * edge * lanes + 16 * edge * edge)
    whole = (head_dim > 0 and head_dim % _LANES == 0 and length > 0 and edge % _LANES == 0
             and length % edge == 0 and kv_heads > 0 and heads % kv_heads == 0
             and index_heads > 0 and index_dim > 0 and blocks <= 0.9 * _VMEM)
    f32 = jnp.dtype(sum_dtype) == jnp.float32
    return "pallas" if whole and f32 and (interpret or jax.default_backend() == "tpu") else "xla"


def forms(heads: int, kv_heads: int, head_dim: int, index_heads: int, index_dim: int,
          length: int, sum_dtype=jnp.float32) -> Dict[str, str]:
    """``index_kl`` (:func:`loss_kind`): what a sparse-attention layer's
    indexer's loss runs over rows of ``length`` slots (``index_loss`` is taken:
    a job's stats carry the loss itself under it)."""
    return {"index_kl": loss_kind(heads, kv_heads, head_dim, index_heads, index_dim, length,
                                    sum_dtype)}


# -- the kernels --------------------------------------------------------------
def _live(live_ref):
    """Whether this grid step's tile holds a kept pair (the flat table)."""
    b, i, j = (pl.program_id(axis) for axis in range(3))
    return live_ref[(b * pl.num_programs(1) + i) * pl.num_programs(2) + j] != 0


def _weights(scale, q_ref, k_ref, lse_ref, keep):
    """p [Tk, Tq]: the kept pairs' weights summed over all heads and divided by
    their number. ``q_ref`` [Hkv, G, T, D], ``k_ref`` [Hkv, T, D], ``lse_ref``
    [Hkv, G, T]. (The mask once, after the sum: a pair that is not kept may
    overflow to inf on the way and is not read. The heads are walked unrolled:
    a ``fori_loop`` over the key heads read a third more on the chip.)"""
    hkv, groups = q_ref.shape[:2]
    total = None
    for h in range(hkv):
        k = k_ref[h]
        for g in range(groups):
            s = jax.lax.dot_general(k, q_ref[h, g], _NT, preferred_element_type=jnp.float32)
            e = jnp.exp(s * scale - lse_ref[h, g:g + 1, :])
            total = e if total is None else total + e
    return jnp.where(keep, total / (hkv * groups), 0.0)


def _head(iq_ref, iw_ref, j: int):
    """Index head j of a query tile: its queries [T, d], a slice of the lanes of
    ``iq_ref`` [T, J d] (the heads side by side, as the projection leaves
    them), and its weights a query [1, T] of ``iw_ref`` [J, T]."""
    width = iq_ref.shape[1] // iw_ref.shape[0]
    return iq_ref[:, j * width:(j + 1) * width], iw_ref[j:j + 1, :]


def _index_scores(iq_ref, ik, iw_ref):
    """I [Tk, Tq] = sum_j iw_j relu(ik . iq_j), float32."""
    total = None
    for j in range(iw_ref.shape[0]):
        iq, iw = _head(iq_ref, iw_ref, j)
        s = jax.lax.dot_general(ik, iq, _NT, preferred_element_type=jnp.float32)
        weighted = jnp.maximum(s, 0.0) * iw
        total = weighted if total is None else total + weighted
    return total


def _forward_kernel(scale, live_ref, fetch_ref, q_ref, lse_ref, k_ref, iq_ref, iw_ref, ik_ref,
                    keep_ref, kl_ref, lse_i_ref, m_ref, l_ref, acc_ref):
    """One tile. ``keep_ref`` [T, T] (query, key); ``kl_ref``, ``lse_i_ref``
    and the three scratches [1, T]: a number a query."""
    del fetch_ref  # the block specs' alone
    j = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_BIG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(_live(live_ref))
    def _():
        keep = keep_ref[...].astype(f32).T != 0
        p = _weights(scale, q_ref, k_ref, lse_ref, keep)
        scores = _index_scores(iq_ref, ik_ref[...], iw_ref)
        sm = jnp.where(keep, scores, _NEG_BIG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sm.max(0, keepdims=True))
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.where(
            keep, jnp.exp(sm - m_new), 0.0).sum(0, keepdims=True)
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        acc_ref[...] += (plogp - p * scores).sum(0, keepdims=True)  # p is 0 off the kept pairs
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        lse_i = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        kl_ref[...] = acc_ref[...] + lse_i
        lse_i_ref[...] = lse_i


def _backward_kernel(scale, live_ref, fetch_ref, q_ref, lse_ref, k_ref, iq_ref, iw_ref, ik_ref,
                     keep_ref, lse_i_ref, g_ref, d_iq_ref, d_iw_ref, d_ik_ref, d_iq_acc, d_iw_acc):
    """One tile backwards. ``lse_i_ref``, ``g_ref`` [1, T]; ``d_iq_ref`` [T, J
    d] and ``d_iw_ref`` [J, T] from their float32 scratches at a query tile's
    last key tile; ``d_ik_ref`` [L, d] float32: the whole row, the block of
    every grid step of the row."""
    del fetch_ref
    i, j = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32
    edge, width = ik_ref.shape

    @pl.when((i == 0) & (j == 0))
    def _():
        d_ik_ref[...] = jnp.zeros(d_ik_ref.shape, f32)

    @pl.when(j == 0)
    def _():
        d_iq_acc[...] = jnp.zeros(d_iq_acc.shape, f32)
        d_iw_acc[...] = jnp.zeros(d_iw_acc.shape, f32)

    @pl.when(_live(live_ref))
    def _():
        keep = keep_ref[...].astype(f32).T != 0
        p = _weights(scale, q_ref, k_ref, lse_ref, keep)
        ik = ik_ref[...]
        scores = _index_scores(iq_ref, ik, iw_ref)
        d_scores = g_ref[...] * (jnp.where(keep, jnp.exp(scores - lse_i_ref[...]), 0.0) - p)

        d_ik = jnp.zeros(ik.shape, f32)
        for h in range(iw_ref.shape[0]):
            iq, iw = _head(iq_ref, iw_ref, h)
            s = jax.lax.dot_general(ik, iq, _NT, preferred_element_type=f32)
            d_iw_acc[h:h + 1, :] += (d_scores * jnp.maximum(s, 0.0)).sum(0, keepdims=True)
            d_s = jnp.where(s > 0, d_scores * iw, 0.0).astype(iq.dtype)
            d_iq_acc[:, h * width:(h + 1) * width] += jax.lax.dot_general(
                d_s, ik, _TN, preferred_element_type=f32)
            d_ik = d_ik + jnp.dot(d_s, iq, preferred_element_type=f32)
        d_ik_ref[pl.ds(pl.multiple_of(j * edge, edge), edge), :] += d_ik

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        d_iq_ref[...] = d_iq_acc[...].astype(d_iq_ref.dtype)
        d_iw_ref[...] = d_iw_acc[...]


def _a_query(edge: int):
    """The block of a query tile's numbers, a number a query: [1, T] of [B, 1, L]."""
    # pio: lint-ok[mosaic-blockspec-tiling] a block of 1 is the array's whole dimension, which a block may be
    return pl.BlockSpec((None, 1, edge), lambda b, i, j, live, fetch: (b, 0, i))


def _call(kernel, static, operands, per_query, out_specs, out_shape, scratch, name):
    """A kernel over the grid (row, query tile i, key tile j) on ``operands``
    (iq [B, L, J, d], ik [B, L, d], iw [B, L, J] float32, q [B, Hkv, G, L, D],
    k [B, Hkv, L, D], lse [B, Hkv, G, L] float32, seg [B, L], chosen [B, L, L]
    bool) and ``per_query`` ([B, 1, L] float32 each)."""
    edge, interpret = static
    iq, ik, iw, q, k, lse, seg, chosen = operands
    b, hkv, groups, length, d = q.shape
    heads, width, n = iq.shape[2], iq.shape[3], length // edge
    named = lambda fetch, b, i, j: fetch[(b * n + i) * n + j]  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, hkv, groups, edge, d), lambda b, i, j, live, fetch: (b, 0, 0, i, 0)),
        pl.BlockSpec((None, hkv, groups, edge), lambda b, i, j, live, fetch: (b, 0, 0, i)),
        pl.BlockSpec((None, hkv, edge, d),
                     lambda b, i, j, live, fetch: (b, 0, named(fetch, b, i, j), 0)),
        pl.BlockSpec((None, edge, heads * width), lambda b, i, j, live, fetch: (b, i, 0)),
        pl.BlockSpec((None, heads, edge), lambda b, i, j, live, fetch: (b, 0, i)),
        pl.BlockSpec((None, edge, width),
                     lambda b, i, j, live, fetch: (b, named(fetch, b, i, j), 0)),
        pl.BlockSpec((None, edge, edge),
                     lambda b, i, j, live, fetch: (b, i, named(fetch, b, i, j))),
    ] + [_a_query(edge)] * len(per_query)
    keep8 = chosen_core._keep(chosen, seg)
    return pl.pallas_call(
        functools.partial(kernel, 1.0 / np.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n, n), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(*chosen_core._tables(keep8, edge), q, lse, k, iq.reshape(b, length, heads * width),
      iw.transpose(0, 2, 1), ik, keep8, *per_query)


@functools.partial(jax.jit, static_argnums=(0,))
def _forward(static, *operands):
    """-> the per-query KL and the log-sum-exp of the index scores over the
    chosen keys, [B, L] float32 each."""
    b, length = operands[6].shape
    f32 = jnp.float32
    a_row = jax.ShapeDtypeStruct((b, 1, length), f32)
    # (behind a barrier: where a layer's recomputation keeps them, XLA would fuse the write
    # into the kept stack into the kernel's call, and a fused call gets 16 MB of VMEM)
    # pio: lint-ok[mosaic-blockspec-tiling] a number a query over the lanes is ONE row (6 KB in all)
    scratch = [pltpu.VMEM((1, static[0]), f32)] * 3
    kl, lse_i = jax.lax.optimization_barrier(_call(
        _forward_kernel, static, operands, (), [_a_query(static[0])] * 2, [a_row, a_row], scratch,
        "index_kl_forward"))
    return kl[:, 0], lse_i[:, 0]


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(static, lse_i, g, *operands):
    """-> the cotangents of iq, ik, iw, as they lie and in their dtypes."""
    edge = static[0]
    iq, ik = operands[:2]
    b, length, heads, width = iq.shape
    f32 = jnp.float32
    d_iq, d_iw, d_ik = _call(
        _backward_kernel, static, operands, (lse_i[:, None], g.astype(f32)[:, None]),
        [pl.BlockSpec((None, edge, heads * width), lambda b, i, j, live, fetch: (b, i, 0)),
         pl.BlockSpec((None, heads, edge), lambda b, i, j, live, fetch: (b, 0, i)),
         pl.BlockSpec((None, length, width), lambda b, i, j, live, fetch: (b, 0, 0))],
        [jax.ShapeDtypeStruct((b, length, heads * width), iq.dtype),
         jax.ShapeDtypeStruct((b, heads, length), f32),
         jax.ShapeDtypeStruct((b, length, width), f32)],
        [pltpu.VMEM((edge, heads * width), f32), pltpu.VMEM((heads, edge), f32)],
        "index_kl_backward")
    return d_iq.reshape(iq.shape), d_ik.astype(ik.dtype), d_iw.transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kl(static, iq, ik, iw, q, k, lse, seg, chosen):
    """``dsa._kl`` by the kernel pair: gradients to iq, ik, iw alone."""
    return _forward(static, iq, ik, iw, q, k, lse, seg, chosen)[0]


def _kl_fwd(static, *operands):
    """(The kernel's two outputs carry the name ``KEPT``: a checkpoint whose
    policy keeps that name runs the forward kernel once and not again in its
    recomputation.)"""
    kl, lse_i = (checkpoint_name(t, KEPT) for t in _forward(static, *operands))
    return kl, operands + (lse_i,)


def _kl_bwd(static, saved, g):
    *operands, lse_i = saved
    return _backward(static, lse_i, g, *operands) + (None,) * 5


_kl.defvjp(_kl_fwd, _kl_bwd)


def kl(iq, ik, iw, q, k, lse, seg, chosen, *, interpret: bool = False) -> jax.Array:
    """The per-query KL of :func:`.dsa.index_loss` where :func:`loss_kind`
    says "pallas": iq [B, L, J, d], ik [B, L, d], iw [B, L, J] float32; q [B,
    H, L, D], k [B, Hkv, L, D], lse [B, H, L] as the core was handed and gave
    them (held constant); seg [B, L], ``chosen`` [B, L, L] bool -> [B, L]
    float32."""
    b, h, length, d = q.shape
    hkv = k.shape[1]
    return _kl((chosen_core._tile(length), interpret), iq, ik, iw.astype(jnp.float32),
               q.reshape(b, hkv, h // hkv, length, d), k, lse.reshape(b, hkv, h // hkv, length),
               seg.astype(jnp.int32), chosen)
