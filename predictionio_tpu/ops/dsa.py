"""Learned sparse attention's own parts: a lightning indexer, the exact choice
of the keys a query reads, and the indexer's loss (the sparse attention of the
DeepSeek-V3.2 report, as Keye-VL-2.0's ``sa_config`` sizes it).

The indexer gives every (query t, key s) pair ONE score from ``J`` small heads
on ONE index key a slot: ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])``
(:func:`index_scores`). Query t then reads EXACTLY ``min(topk, its causal
keys)`` keys of its own history, those with the largest scores
(:func:`select`). The rule among equal scores (``relu`` makes many pairs
exactly 0): the most recent key first, and ``-0.0`` equals ``0.0``. The choice
is exact and sorts nothing: the ``topk``-th largest score is found by a
bisection over the scores' float32 bit patterns (compare-and-count passes),
and among the keys that equal it the position of the last one kept by a
second bisection over positions (on the chip ``lax.top_k`` at 2,048 of 16,384
read 237 ms a layer against the bisection's 32).

Nothing here holds an ``[L, L]`` matrix of scores: the choice runs by strips of
``block`` queries (a strip's scores are ``[block, L]`` float32) and leaves the
mask ``[B, L, L]`` bool, which the attention core
(``ops.attention.chosen_attention``) applies tile by tile; the loss
(:func:`index_loss`) walks the same tiles.

The loss: ``mean_t KL(p[t, S_t] || softmax_{s in S_t} I[t, s])``, p the main
heads' attention weights over the chosen keys summed over the heads and
normalised to sum 1, held constant. Its gradient reaches the indexer's inputs
alone; the choice itself has none.

Precision: the index products take their inputs as they come (the compute
dtype) and accumulate in float32; the head-weighted sum in ``sum_dtype``
(float32; bfloat16 is the benchmark's control); threshold, softmaxes and the
KL in float32.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import indexer_kl
from .attention import _block_pairs, _grouped_and_padded, _opaque, _pair_meets, chosen_weights_tile

#: bits of the threshold a bisection pass decides (each pass reads the strip's
#: scores once and counts ``2 ** bits - 1`` candidates)
BISECT_BITS = 2
#: keys a strip's scores are made against at a time: the per-head scores of
#: ``block`` queries against them are the largest temporary of the choice
KEY_CHUNK = 2048

_NEG_BIG = -1e30


def index_scores(iq, ik, iw, sum_dtype=jnp.float32):
    """iq [B, S, J, d], ik [B, T, d], iw [B, S, J] (float32) -> I [B, S, T]
    float32: ``sum_j iw[., j] * relu(iq[., j] . ik)``."""
    s = jnp.einsum("bsjd,btd->bsjt", iq, ik, preferred_element_type=jnp.float32)
    weighted = jax.nn.relu(s).astype(sum_dtype) * iw.astype(sum_dtype)[..., None]
    return weighted.sum(2).astype(jnp.float32)


def _sortable(x):
    """float32 -> uint32 keys that order as the floats do (no NaN among them)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def kth_largest_key(keys, k, width: int = 32, bits: int = BISECT_BITS):
    """keys [..., T] uint32 below ``2 ** width``, k an int or [...] int32 -> the
    largest value ``t`` [...] with ``count(keys >= t) >= k``: the k-th largest
    key where there are k of them, else 0. Decided ``bits`` bits a pass from
    the top; every pass counts, nothing is sorted."""
    t = jnp.zeros(keys.shape[:-1], jnp.uint32)
    for shift in range(width - bits, -1, -bits):
        # the candidates that set this pass's bits to 1 .. 2 ** bits - 1
        cands = [t | jnp.uint32(c << shift) for c in range(1, 1 << bits)]
        enough = [(keys >= c[..., None]).sum(-1, dtype=jnp.int32) >= k for c in cands]
        for c, ok in zip(cands, enough):  # ascending: the largest that still counts k wins
            t = jnp.where(ok, c, t)
    return t


def _strip_choice(scores, valid, topk: int):
    """scores, valid [B, S, T] -> the chosen mask [B, S, T]: of every query's
    valid keys the ``topk`` with the largest scores (all of them where there
    are no more), among equal scores the LAST keys (the most recent) first."""
    length = scores.shape[-1]
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 ties with 0.0
    keys = jnp.where(valid, _sortable(scores), jnp.uint32(0))
    threshold = kth_largest_key(keys, topk)[..., None]
    above = keys > threshold
    # the keys AT the threshold fill what the ones above it leave of topk, from
    # the last position down: their places, 1-based, bisected like the scores
    tied = valid & (keys == threshold)
    place = jnp.where(tied, jnp.arange(1, length + 1, dtype=jnp.uint32), jnp.uint32(0))
    width = -(-length.bit_length() // BISECT_BITS) * BISECT_BITS
    first = kth_largest_key(place, topk - above.sum(-1, dtype=jnp.int32), width)
    return above | (tied & (place >= first[..., None]))


@functools.partial(jax.jit, static_argnames=("topk", "block", "sum_dtype"))
def select(iq, ik, iw, seg, *, topk: int, block: int = 512, sum_dtype=jnp.float32):
    """The choice. iq [B, L, J, d], ik [B, L, d], iw [B, L, J], seg [B, L] ->
    ``chosen`` [B, L, L] bool (query, key): of the causal keys of the query's own
    history the ``topk`` with the largest scores, the most recent first among
    equals (exactly ``min(topk, causal keys)`` a query);
    ``kept`` and ``causal`` [B] int32: the chosen and the causal in-history pairs
    of the real queries (seg > 0); and, for whoever checks the choice, the
    scores of ONE strip as the choice itself saw them: ``sample`` [B, block, L]
    float32 and ``at``, the strip's number (the strip that holds the row's
    deepest slot). By strips of ``block`` queries; no gradient."""
    b, length, heads, d = iq.shape
    blk = min(block, length)
    pad = -length % blk
    if pad:
        iq, ik, iw = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (iq, ik, iw))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    padded = length + pad
    chunk = min(KEY_CHUNK, padded) if padded % min(KEY_CHUNK, padded) == 0 else blk
    n_strips = padded // blk
    pos = jnp.arange(padded)
    ik_chunks = ik.reshape(b, padded // chunk, chunk, d).transpose(1, 0, 2, 3)
    # the strip whose queries lie deepest in a history: the one worth a look
    start = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], 1)
    depth = pos - jax.lax.cummax(jnp.where(start, pos, 0), axis=1)
    at = jnp.argmax(jnp.where(seg > 0, depth, -1).max(0)) // blk

    def strip(sample, xs):
        i, iq_i, iw_i, seg_i = xs
        with jax.named_scope("seq.attn.index"):
            def against(args):
                c, ik_c = args
                # a chunk of keys wholly after the strip holds no causal pair
                return jax.lax.cond(
                    c * chunk <= i * blk + blk - 1,
                    lambda: index_scores(iq_i, ik_c, iw_i, sum_dtype),
                    lambda: jnp.zeros((b, blk, chunk), jnp.float32))
            scores = jax.lax.map(against, (jnp.arange(padded // chunk), ik_chunks))
            scores = scores.transpose(1, 2, 0, 3).reshape(b, blk, padded)
        with jax.named_scope("seq.attn.select"):
            q_pos = i * blk + jnp.arange(blk)
            valid = (q_pos[:, None] >= pos[None, :])[None] & (seg_i[:, :, None] == seg[:, None, :])
            chosen = _strip_choice(scores, valid, topk)
            real = (seg_i > 0)[:, :, None]
            counts = jnp.stack([(chosen & real).sum((1, 2), dtype=jnp.int32),
                                (valid & real).sum((1, 2), dtype=jnp.int32)])
        return jnp.where(i == at, scores, sample), (chosen, counts)

    by_strip = lambda t: t.reshape((b, n_strips, blk) + t.shape[2:]).swapaxes(0, 1)  # noqa: E731
    sample, (chosen, counts) = jax.lax.scan(
        strip, jnp.zeros((b, blk, padded), jnp.float32),
        (jnp.arange(n_strips), by_strip(iq), by_strip(iw), by_strip(seg)))
    chosen = chosen.swapaxes(0, 1).reshape(b, padded, padded)[:, :length, :length]
    kept, causal = counts.sum(0)
    return chosen, kept, causal, sample[:, :, :length], at


# -- the indexer's loss -------------------------------------------------------
def _loss_tiles(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype, tile, carry):
    """``carry`` after ``tile(carry, i, j, scores, p, keep)`` over every tile (Q
    block i, KV block j) on or below the diagonal whose histories meet: the
    index scores of the tile (made under ``jax.vjp`` by whoever wants their
    gradient: ``tile`` is handed the function, not the value), the main heads'
    weights p [B, blk, blk] and the tile's mask."""
    ii, jj = _block_pairs(seg.shape[1], seg.shape[1], blk, blk, True)

    def body(t, carry):
        i, j = ii[t], jj[t]

        def run(carry):
            p, keep, after = chosen_weights_tile(qg, k, lse, seg, chosen, i, j, blk, carry[0])
            carry = (after,) + tuple(carry[1:])
            iq_i = jax.lax.dynamic_slice_in_dim(iq, i * blk, blk, axis=1)
            iw_i = jax.lax.dynamic_slice_in_dim(iw, i * blk, blk, axis=1)
            ik_j = jax.lax.dynamic_slice_in_dim(ik, j * blk, blk, axis=1)
            scores = lambda a, b_, c: index_scores(a, b_, c, sum_dtype)  # noqa: E731
            return tile(carry, i, j, (scores, iq_i, ik_j, iw_i), p, keep)

        return jax.lax.cond(_pair_meets(seg, seg, i, j, blk, blk), run, lambda c: c, carry)

    return jax.lax.fori_loop(0, _opaque(len(ii)), body, carry)


def _kl_forward(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype):
    """Per query [B, L]: ``sum_s p log p - sum_s p I`` over its chosen keys, and
    the log-sum-exp of I over them (a running maximum and sum over the tiles)."""
    b, length = seg.shape
    f32 = jnp.float32

    def tile(carry, i, j, scored, p, keep):
        m, l, acc = carry
        fn, *inputs = scored
        s = fn(*inputs)
        at = i * blk
        mi, li, ai = (jax.lax.dynamic_slice_in_dim(t, at, blk, axis=1) for t in (m, l, acc))
        sm = jnp.where(keep, s, _NEG_BIG)
        m_new = jnp.maximum(mi, sm.max(-1))
        l_new = li * jnp.exp(mi - m_new) + jnp.where(keep, jnp.exp(sm - m_new[..., None]), 0.0).sum(-1)
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        a_new = ai + (plogp - p * jnp.where(keep, s, 0.0)).sum(-1)
        return tuple(jax.lax.dynamic_update_slice_in_dim(old, new, at, axis=1)
                     for old, new in ((m, m_new), (l, l_new), (acc, a_new)))

    m, l, acc = _loss_tiles(
        iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype, tile,
        (jnp.full((b, length), _NEG_BIG, f32), jnp.zeros((b, length), f32),
         jnp.zeros((b, length), f32)))
    return acc, m + jnp.log(jnp.maximum(l, 1e-30))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kl(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype):
    acc, lse_i = _kl_forward(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype)
    return acc + lse_i


def _kl_fwd(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype):
    acc, lse_i = _kl_forward(iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype)
    return acc + lse_i, (iq, ik, iw, qg, k, lse, seg, chosen, lse_i)


def _kl_bwd(blk, sum_dtype, res, g):
    """d KL[t] / d I[t, s] = softmax_S(I)[t, s] - p[t, s] on the chosen keys;
    through the tile's scores onto the indexer's three inputs. Nothing for the
    main heads' q, k and log-sum-exp: the weights are held constant."""
    iq, ik, iw, qg, k, lse, seg, chosen, lse_i = res
    f32 = jnp.float32

    def tile(carry, i, j, scored, p, keep):
        d_iq, d_ik, d_iw = carry
        fn, *inputs = scored
        s, pull = jax.vjp(fn, *inputs)
        at = i * blk
        li = jax.lax.dynamic_slice_in_dim(lse_i, at, blk, axis=1)
        gi = jax.lax.dynamic_slice_in_dim(g, at, blk, axis=1)
        d_s = gi[..., None] * (jnp.where(keep, jnp.exp(s - li[..., None]), 0.0) - p)
        t_iq, t_ik, t_iw = pull(d_s)

        def add(buf, blk_, where):
            old = jax.lax.dynamic_slice_in_dim(buf, where, blk_.shape[1], axis=1)
            return jax.lax.dynamic_update_slice_in_dim(buf, old + blk_.astype(f32), where, axis=1)

        return add(d_iq, t_iq, at), add(d_ik, t_ik, j * blk), add(d_iw, t_iw, at)

    zeros = tuple(jnp.zeros(t.shape, f32) for t in (iq, ik, iw))
    d_iq, d_ik, d_iw = _loss_tiles(
        iq, ik, iw, qg, k, lse, seg, chosen, blk, sum_dtype, tile, zeros)
    return (d_iq.astype(iq.dtype), d_ik.astype(ik.dtype), d_iw.astype(iw.dtype),
            None, None, None, None, None)


_kl.defvjp(_kl_fwd, _kl_bwd)


@functools.partial(jax.jit, static_argnames=("block", "sum_dtype"))
def index_loss(iq, ik, iw, q, k, lse, seg, chosen, *, block: int = 512,
               sum_dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """The indexer's loss over a batch of packed rows: the KL of every real
    query (seg > 0) summed [scalar], and how many they are; their quotient is
    the mean. ``q`` [B, H, L, D], ``k`` [B, Hkv, L, D] and ``lse`` [B, H, L]:
    what ``chosen_attention`` was handed and gave, held constant here. Where
    :func:`.indexer_kl.loss_kind` says so (a TPU, main heads of whole lane
    tiles, rows of whole kernel tiles, a float32 head-weighted sum) the
    per-query KL is that module's Pallas kernel pair, whose tile is the
    core's; the loop over the tiles everywhere else."""
    b, h, length, _ = q.shape
    if indexer_kl.loss_kind(h, k.shape[1], q.shape[-1], iq.shape[2], iq.shape[3], length,
                            sum_dtype) == "pallas":
        per_query = indexer_kl.kl(iq, ik, iw, *jax.lax.stop_gradient((q, k, lse)), seg, chosen)
    else:
        blk = min(block, length)
        pad = -length % blk
        qg, k, _, seg_p, _ = _grouped_and_padded(q, k, k, seg, blk, blk)
        seg_p = jnp.pad(seg_p, ((0, 0), (0, pad)), mode="edge")
        lse = jnp.pad(lse.reshape(qg.shape[:3] + (length,)), ((0, 0),) * 3 + ((0, pad),))
        along = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))  # noqa: E731
        chosen = jnp.pad(chosen, ((0, 0), (0, pad), (0, pad)))
        qg, k, lse = jax.lax.stop_gradient((qg, k, lse))
        per_query = _kl(along(iq), along(ik), along(iw), qg, k, lse, seg_p, chosen, blk,
                        jnp.dtype(sum_dtype))[:, :length]
    real = seg > 0
    return jnp.where(real, per_query, 0.0).sum(), real.sum()
