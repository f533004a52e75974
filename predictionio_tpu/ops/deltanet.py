"""Gated DeltaNet: a linear-attention layer whose state is a matrix per
head, rewritten by the delta rule and decayed by a gate.

Per head, with state ``S`` [dk, dv], zero at the start of every history::

    S <- alpha_t S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;  o_t = S^T q_t

:func:`gated_delta_rule` computes that in chunks of ``chunk`` tokens, in
three phases. The preparation: inside a chunk the ``u_t`` solve a unit
lower-triangular system (:func:`tri_inv`, blocked forward substitution),
which every chunk does at once. It builds each of its arrays once and
writes into no slice of one: a diagonal block takes row i of its inverse by
a select over the whole block, which the compiler resolves into the rows
computed apart and one write of the blocks, and the block rows below the
diagonal are concatenated once (the chip pads the blocks' minor dimension
of 16 to a lane tile of 128, and a row updated in place copied the whole
padded array, fifteen times a call). The walk (:func:`_walk`): only the state
goes from chunk to chunk, and the walk emits each chunk's incoming state
and corrected values and nothing else. The outputs: two batch products
over all chunks from what the walk emitted.

The walk has a VJP of its own: the state's cotangent walks the chunks once
in reverse, through the same step function (:func:`_walk_step`), and every
other cotangent is a batch product; the preparation and the outputs are
differentiated as they stand. Nothing inside the rule is recomputed. Where
the tiles allow (``dk`` and ``dv`` multiples of 128, the chunk a multiple of
16, a TPU) the walk is a Pallas kernel that keeps the state in VMEM; a
``lax.scan`` over the same step function otherwise (:func:`walk_kind`).

Packed rows: ``seg`` gives each slot the id of its history (one contiguous
run per id). A history's first token resets the state, which the chunked
form does by masking the decay between slots of different histories; the
short convolution (:func:`.shortconv.conv_chain`) reads zero where a tap would
reach into the neighbour.

Precision: gates ``alpha`` (as ``g = log alpha`` and its running sums) and
the state are ``gate_dtype`` and ``state_dtype`` (float32); the triangular
systems and the products that read the state (or, backwards, its
cotangent) are float32 at ``Precision.HIGHEST``; the other products take
``compute_dtype`` inputs (bfloat16 on the chip) and accumulate in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .shortconv import chain_kind, conv_chain

_HI = jax.lax.Precision.HIGHEST
_BLOCK = 16  # diagonal blocks solved row by row; the rest by products
_HEADS = 8  # heads whose states share a grid step of the walk's kernel


def _tri_inv_impl(a):
    """(I + a)^-1 for strictly lower-triangular ``a`` [..., C, C], float32.
    Diagonal blocks of 16 by forward substitution (row i of the inverse is
    e_i - sum_j a_ij row_j), block rows below them by products. Nothing is
    written into a slice of an array: a block takes its row by a select over
    the whole block (the compiler computes the rows apart and writes the
    blocks once), and the block rows are concatenated once."""
    c = a.shape[-1]
    b = _BLOCK if c % _BLOCK == 0 else c
    nb = c // b
    blocks = a.reshape(a.shape[:-2] + (nb, b, nb, b))
    diag = jnp.stack([blocks[..., n, :, n, :] for n in range(nb)], axis=-3)  # [..., nb, b, b]
    at = jax.lax.broadcasted_iota(jnp.int32, diag.shape, diag.ndim - 2)
    t = jnp.broadcast_to(jnp.eye(b, dtype=a.dtype), diag.shape)
    for i in range(1, b):
        row = -jnp.einsum("...j,...jk->...k", diag[..., i, :], t, precision=_HI)
        t = jnp.where(at == i, t + row[..., None, :], t)
    if nb == 1:
        return t.reshape(a.shape)
    ahead = [(0, 0)] * (a.ndim - 1)  # a block row: its diagonal block, zeros right of it
    full = [jnp.pad(t[..., 0, :, :], ahead + [(0, c - b)])]
    for n in range(1, nb):
        below = jnp.einsum("...ij,...jk->...ik", a[..., n * b:(n + 1) * b, :n * b],
                           jnp.concatenate(full, axis=-2), precision=_HI)
        left = -jnp.einsum("...ij,...jk->...ik", t[..., n, :, :], below, precision=_HI)
        full.append(left + jnp.pad(t[..., n, :, :], ahead + [(n * b, c - (n + 1) * b)]))
    return jnp.concatenate(full, axis=-2)


@jax.custom_vjp
def tri_inv(a):
    return _tri_inv_impl(a)


def _tri_inv_fwd(a):
    t = _tri_inv_impl(a)
    return t, t


def _tri_inv_bwd(t, dt):
    # d(I + a)^-1 = -T da T, so da = -T^T dT T^T, on the strict lower part
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.einsum("...ij,...jk,...kl->...il", tt, dt, tt, precision=_HI)
    c = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), da, 0.0),)


tri_inv.defvjp(_tri_inv_fwd, _tri_inv_bwd)


def _prepare(q, k, v, g, beta, seg, chunk, compute_dtype, gate_dtype):
    """What every chunk makes of its own slots, all chunks at once; q, k, v
    [B, L, H, d], L a multiple of ``chunk``. Returns, chunk axis first
    ([N, B, H, ...]): ``u`` [C, dv] float32, the chunk's values corrected
    for its own keys; ``-w`` [C, dk], what they still owe the incoming
    state; q and k [C, dk] with the decay from the chunk's first slot and
    to its last; the scores inside the chunk [C, C]; and ``keep`` [],
    what of the incoming state reaches the next chunk."""
    bsz, length, heads, _ = q.shape
    n = length // chunk

    def chunks(a):  # [B, L, H, ...] -> [N, B, H, C, ...]: the walk's own layout
        a = a.reshape((bsz, n, chunk, heads) + a.shape[3:])
        return jnp.moveaxis(a, (1, 3), (0, 2))

    f32, cd = jnp.float32, compute_dtype
    with jax.named_scope("seq.deltanet.scan.prep.layout"):
        qc, kc, vc = chunks(q.astype(f32)), chunks(k.astype(f32)), chunks(v.astype(f32))
    bc = chunks(beta.astype(f32))  # [N, B, H, C]
    gc = jnp.cumsum(chunks(g.astype(gate_dtype)), axis=-1)  # inclusive, per chunk
    sc = jnp.moveaxis(seg.reshape(bsz, n, chunk), 1, 0)  # [N, B, C]
    same = (sc[..., :, None] == sc[..., None, :])[:, :, None]  # [N, B, 1, C, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # decay from slot j to slot i of one history, i >= j; 0 elsewhere
    diff = (gc[..., :, None] - gc[..., None, :]).astype(f32)
    decay = jnp.exp(jnp.where(same & lower, diff, -jnp.inf))
    prev_last = jnp.pad(sc[:-1, :, -1], ((1, 0), (0, 0)), constant_values=-3)  # [N, B]
    carried = (sc == prev_last[..., None])[:, :, None].astype(f32)  # sees the incoming state
    to_last = (sc == sc[..., -1:])[:, :, None].astype(f32)  # reaches the chunk's last slot
    egc = jnp.exp(gc.astype(f32))
    e_last = jnp.exp((gc[..., -1:] - gc).astype(f32))

    kk = jnp.einsum("nbhid,nbhjd->nbhij", kc, kc, precision=_HI)
    a = bc[..., None] * kk * decay * jnp.tril(jnp.ones((chunk, chunk), f32), -1)
    with jax.named_scope("seq.deltanet.scan.prep.tri_inv"):
        t = tri_inv(a)
    w = jnp.einsum("nbhij,nbhjd->nbhid", t, kc * (-bc * egc * carried)[..., None], precision=_HI)
    u = jnp.einsum("nbhij,nbhjd->nbhid", t, vc * bc[..., None], precision=_HI)
    attn = jnp.einsum("nbhid,nbhjd->nbhij", qc, kc, precision=_HI) * decay
    return (
        u, w.astype(cd), (qc * (egc * carried)[..., None]).astype(cd),
        (kc * (e_last * to_last)[..., None]).astype(cd), attn.astype(cd),
        jnp.squeeze(egc[..., -1:] * carried[..., -1:], -1).astype(gate_dtype),
    )


def _with_state(spec: str, a, s):
    """A product with the state (or its cotangent) ``s``. A float32 state
    is read as float32 (``HIGHEST``: it is not rounded to feed the MXU, or
    keeping it in float32 would buy nothing); a lower one as it is."""
    if s.dtype == jnp.float32:
        return jnp.einsum(spec, a.astype(jnp.float32), s, precision=_HI)
    return jnp.einsum(spec, a, s.astype(a.dtype), preferred_element_type=jnp.float32)


def _walk_step(state, add, rows, cols, keep, extra=None):
    """One chunk of the walk, either way; leading axes are heads. Forward
    (``add`` u, ``rows`` -w, ``cols`` k): ``x`` is the chunk's corrected
    values, the new state what ``keep`` leaves of the old plus the chunk's
    own writes. Backward (``add`` the cotangent of x from the outputs,
    ``rows`` k, ``cols`` -w, ``extra`` the cotangent of the emitted state):
    ``state`` is the cotangent of the next chunk's incoming state, ``x``
    that of the corrected values. ``x`` comes in ``cols``' dtype, float32
    ones multiply at full precision. -> new state, x."""
    x = (add + _with_state("...ck,...kv->...cv", rows, state)).astype(cols.dtype)
    wrote = jnp.einsum("...ck,...cv->...kv", cols, x, preferred_element_type=jnp.float32,
                       precision=_HI if cols.dtype == jnp.float32 else None)
    new = state * keep.astype(state.dtype) + wrote.astype(state.dtype)
    if extra is not None:
        new = new + extra.astype(state.dtype)
    return new, x


def _walk_scan(state_dtype, reverse, add, rows, cols, keep, extra):
    def step(state, blocks):
        new, x = _walk_step(state, *blocks)
        return new, (state, x)

    blocks = (add, rows, cols, keep[..., None, None]) + (() if extra is None else (extra,))
    start = jnp.zeros(rows.shape[1:-2] + (rows.shape[-1], add.shape[-1]), state_dtype)
    return jax.lax.scan(step, start, blocks, reverse=reverse)[1]


def _walk_pallas(state_dtype, reverse, add, rows, cols, keep, extra, interpret):
    """The walk with the state in VMEM: grid (blocks of heads, chunks), the
    chunks in order (or in reverse order); a grid step reads one chunk's
    blocks and writes the incoming state and x of that chunk."""
    lead, (c, dk), dv = rows.shape[:-2], rows.shape[-2:], add.shape[-1]
    n, bh = lead[0], math.prod(lead[1:])
    hb = max(d for d in range(1, _HEADS + 1) if bh % d == 0)

    def at(h, i):
        return (n - 1 - i if reverse else i, h, 0, 0)

    operands = [
        (add, (c, dv)), (rows, (c, dk)), (cols, (c, dk)),
        (jnp.broadcast_to(keep[..., None, None], lead + (1, dv)), (1, dv)),
    ] + ([] if extra is None else [(extra, (dk, dv))])

    def kernel(*refs):
        blocks, (states_ref, x_ref, carry) = refs[:len(operands)], refs[len(operands):]

        @pl.when(pl.program_id(1) == 0)
        def _():
            carry[...] = jnp.zeros_like(carry)

        state = carry[...]
        states_ref[...] = state
        carry[...], x_ref[...] = _walk_step(state, *(ref[...] for ref in blocks))

    states, x = pl.pallas_call(
        kernel,
        grid=(bh // hb, n),
        in_specs=[pl.BlockSpec((None, hb) + tail, at) for _, tail in operands],
        out_specs=[pl.BlockSpec((None, hb, dk, dv), at), pl.BlockSpec((None, hb, c, dv), at)],
        out_shape=[jax.ShapeDtypeStruct((n, bh, dk, dv), state_dtype),
                   jax.ShapeDtypeStruct((n, bh, c, dv), cols.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), state_dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*(a.reshape((n, bh) + a.shape[len(lead):]) for a, _ in operands))
    return states.reshape(lead + (dk, dv)), x.reshape(lead + (c, dv))


def walk_kind(dk: int, dv: int, chunk: int, interpret: bool = False) -> str:
    """Which walk :func:`gated_delta_rule` runs at these widths: "pallas"
    where a head's state and a chunk's rows are whole tiles and the backend
    is a TPU (``interpret``: or the kernel's interpreter, for tests),
    "scan" otherwise."""
    tiles = dk % 128 == 0 and dv % 128 == 0 and chunk % 16 == 0
    return "pallas" if tiles and (interpret or jax.default_backend() == "tpu") else "scan"


def _run_walk(kind, interpret, state_dtype, reverse, add, rows, cols, keep, extra=None):
    with jax.named_scope("seq.deltanet.scan.walk"):
        if kind == "pallas":
            return _walk_pallas(state_dtype, reverse, add, rows, cols, keep, extra, interpret)
        return _walk_scan(state_dtype, reverse, add, rows, cols, keep, extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _walk(kind, interpret, state_dtype, add, rows, cols, keep):
    """The sequential part of the rule, chunk axis first: from a zero
    state, ``x_n = add_n + rows_n S_n`` and ``S_n+1 = keep_n S_n + cols_n^T
    x_n`` -> every chunk's incoming state S_n [N, ..., dk, dv]
    (``state_dtype``) and x_n [N, ..., C, dv] (``cols``' dtype)."""
    return _run_walk(kind, interpret, state_dtype, False, add, rows, cols, keep)


def _walk_fwd(kind, interpret, state_dtype, add, rows, cols, keep):
    states, x = _run_walk(kind, interpret, state_dtype, False, add, rows, cols, keep)
    return (states, x), (rows, cols, keep, states, x)


def _walk_bwd(kind, interpret, state_dtype, kept, cotangents):
    rows, cols, keep, states, x = kept
    d_states, d_x = cotangents
    f32 = jnp.float32
    # the same walk from the last chunk to the first, k and -w changing places
    d_next, dx = _run_walk(
        kind, interpret, state_dtype, True, d_x, cols,
        rows.astype(f32) if state_dtype == f32 else rows, keep, extra=d_states)
    with jax.named_scope("seq.deltanet.scan.out"):
        d_rows = _with_state("...cv,...kv->...ck", dx, states)
        d_cols = _with_state("...cv,...kv->...ck", x, d_next)
        d_keep = jnp.sum(d_next.astype(f32) * states.astype(f32), axis=(-1, -2))
    return (dx.astype(f32), d_rows.astype(rows.dtype), d_cols.astype(cols.dtype),
            d_keep.astype(keep.dtype))


_walk.defvjp(_walk_fwd, _walk_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "compute_dtype", "state_dtype", "gate_dtype", "interpret"))
def gated_delta_rule(q, k, v, g, beta, seg, chunk: int = 64,
                     compute_dtype=jnp.float32, state_dtype=jnp.float32,
                     gate_dtype=jnp.float32, interpret: bool = False):
    """q, k [B, L, H, dk] (already normalised and scaled), v [B, L, H, dv],
    g = log alpha and beta [B, L, H], seg [B, L] -> o [B, L, H, dv] float32."""
    bsz, length, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -length % chunk
    if pad:  # slots of a history of their own, which write nothing
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    with jax.named_scope("seq.deltanet.scan.prep"):
        u, w, qc, kc, attn, keep = _prepare(q, k, v, g, beta, seg, chunk, compute_dtype, gate_dtype)
    states, x = _walk(walk_kind(dk, dv, chunk, interpret), interpret, state_dtype, u, w, kc, keep)
    with jax.named_scope("seq.deltanet.scan.out"):
        o = _with_state("...ck,...kv->...cv", qc, states) + jnp.einsum(
            "...ij,...jv->...iv", attn, x, preferred_element_type=jnp.float32)  # [N, B, H, C, dv]
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(bsz, length + pad, heads, dv)
    return o[:, :length]


def _chain(key_heads: int, value_heads: int, key_dim: int, value_dim: int) -> Dict:
    """:func:`conv_chain`'s arguments: q, k and v, the wide projection's first columns."""
    return dict(channels=2 * key_heads * key_dim + value_heads * value_dim, silu=True)


def forms(shapes: Dict, length: int, *, key_heads: int, value_heads: int, key_dim: int,
          value_dim: int, chunk: int = 64, **_) -> Dict[str, str]:
    """``delta_rule_walk`` (:func:`walk_kind`) and ``conv`` ("pallas" or "xla"):
    what :func:`gated_deltanet` runs over rows of ``length`` slots, ``shapes``
    its parameters' and the keyword arguments its own."""
    return {"delta_rule_walk": walk_kind(key_dim, value_dim, chunk),
            "conv": chain_kind(length, shapes["conv_w"][0],
                               **_chain(key_heads, value_heads, key_dim, value_dim))}


def gated_deltanet(p: Dict, x, seg, *, key_heads: int, value_heads: int, key_dim: int,
                   value_dim: int, eps: float, chunk: int = 64,
                   compute_dtype=jnp.float32, state_dtype=jnp.float32,
                   gate_dtype=jnp.float32):
    """The mixer of a gated-DeltaNet layer: x [B, L, D] (normed) -> [B, L, D].
    ``p``: ``w_qkvz`` [D, 2*Hk*dk + 2*Hv*dv], ``w_ba`` [D, 2*Hv], ``conv_w``
    [K, 2*Hk*dk + Hv*dv], ``A_log`` and ``dt_bias`` [Hv], ``o_norm`` [dv],
    ``w_out`` [Hv*dv, D].

    The wide activations between the stages (the projections, q, k, v) are
    kept in ``compute_dtype``, and each stage is recomputed from them in
    the backward pass: at 16 k tokens the float32 intermediates of one
    layer, all alive at once, would not leave room for the rest.

    Also returns what the delta rule was given and what it gave, as this
    call computed them (``q``, ``k``, ``v``, ``g``, ``beta``, ``o``, each
    [B, L, Hv, ...]): a caller that wants to hold the scan that ran against
    the recurrence reads them, and a program that does not use them does
    not compute their copies."""
    bsz, length, _ = x.shape
    hk, hv, dk, dv = key_heads, value_heads, key_dim, value_dim
    cd, f32 = compute_dtype, jnp.float32
    chain = _chain(hk, hv, dk, dv)
    n_qkv = chain["channels"]
    with jax.named_scope("seq.deltanet.proj"):
        qkvz = jnp.dot(x.astype(cd), p["w_qkvz"].astype(cd), preferred_element_type=f32).astype(cd)
        # the gates' own inputs stay float32: alpha feeds an exponential
        ba = jnp.dot(x.astype(f32), p["w_ba"], precision=_HI)

    @jax.checkpoint
    def prepare(qkvz, ba, seg, conv_w, a_log, dt_bias):
        rows = qkvz.shape[0]
        with jax.named_scope("seq.deltanet.conv"):
            qkv = conv_chain(qkvz, conv_w, seg, **chain)
            q = qkv[..., : hk * dk].reshape(rows, length, hk, dk)
            k = qkv[..., hk * dk: 2 * hk * dk].reshape(rows, length, hk, dk)
            v = qkv[..., 2 * hk * dk:].reshape(rows, length, hv, dv)

            def l2(a):
                return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

            q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=2).astype(cd)
            k = jnp.repeat(l2(k), hv // hk, axis=2).astype(cd)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
            return q, k, v.astype(cd), g, beta

    rule = functools.partial(
        gated_delta_rule, chunk=chunk, compute_dtype=cd, state_dtype=state_dtype,
        gate_dtype=gate_dtype)

    @jax.checkpoint
    def finish(o, qkvz, o_norm):
        with jax.named_scope("seq.deltanet.norm"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * o_norm
            z = qkvz[..., n_qkv:].astype(f32).reshape(o.shape)
            return (o * jax.nn.silu(z)).reshape(o.shape[:2] + (hv * dv,)).astype(cd)

    @jax.checkpoint
    def one_row(row, conv_w, a_log, dt_bias, o_norm):
        qkvz, ba, seg = (a[None] for a in row)
        q, k, v, g, beta = prepare(qkvz, ba, seg, conv_w, a_log, dt_bias)
        with jax.named_scope("seq.deltanet.scan"):
            o = rule(q, k, v, g, beta, seg)
        ran = {"q": q, "k": k, "v": v, "g": g, "beta": beta, "o": o}
        return finish(o, qkvz, o_norm)[0], jax.tree_util.tree_map(lambda a: a[0], ran)

    # between the projections one row at a time: the per-chunk matrices of
    # a row (and, in the backward pass, their cotangents) are half of what
    # two rows need, and at 16 k tokens that half is what fits
    o, ran = jax.lax.map(
        lambda row: one_row(row, p["conv_w"], p["A_log"], p["dt_bias"], p["o_norm"]),
        (qkvz, ba, seg))
    with jax.named_scope("seq.deltanet.proj"):
        return jnp.dot(o, p["w_out"].astype(cd), preferred_element_type=f32), ran
