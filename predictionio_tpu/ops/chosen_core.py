"""The core of learned sparse attention as a Pallas kernel pair for the TPU:
grouped-query attention over the pairs a mask the step made from its data
holds true (:func:`.attention.chosen_attention`'s other form; the XLA loop
``_flash_chosen`` there is the one the CPU, toy widths and ragged rows run).

Both kernels compute WHOLE tiles of ``TILE`` queries by ``TILE`` keys and apply
the mask inside the tile, in VMEM: the scores, the weights and the running
statistics never reach HBM. What XLA makes before a call, one pass over the
mask each (``_keep``, ``_tables``): the mask a byte a pair with the positions'
and the histories' masks folded in (causal, the same history: what
``attention._pair_tile`` builds tile by tile), and a table a tile, handed in by
scalar prefetch: whether the tile holds a kept pair at all, and which key tile
a grid step fetches. A tile that holds none neither computes nor fetches: its
grid step names the tile the step before it named.

Forward (grid: rows, key/value heads, query tiles, key tiles innermost): the
``G = H / Hkv`` query heads of a key/value head are walked inside the body
against ONE ``k``/``v`` tile and one mask tile; running maximum, sum (a number a
query, kept over a lane tile) and output of all G heads in VMEM scratch, float32.
Backward (the same grid, ONE kernel): keys on the sublanes, queries on the lanes
(the mask's tile is turned round in the body), so a query's log-sum-exp and
``delta`` are rows; ``dq`` of the group's query tile accumulates in scratch over the key tiles,
``dk`` and ``dv`` of a key/value head's WHOLE row stay in VMEM as the output's
block (float32, [L, D] each) and take every tile's sum over the group's heads.

Precision, the loop's: products from the inputs as they come (bfloat16 on the
chip) into float32; scores, statistics, ``exp`` in float32; the weights rounded to
``v``'s dtype before ``p v`` and ``pᵀ do``, ``ds`` to q's before ``ds k`` and ``dsᵀ q``;
every accumulator float32. A query without a kept key gives zeros and the
loop's log-sum-exp (``-1e30 + log(1e-30)``).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: edge of a tile, queries and keys, forward and backward: the best on the chip
#: of 256, 512, 1,024, 512 x 1,024 and 1,024 x 512 (PERF.md section 6, PR 46)
TILE = 512
_VMEM = 100 * 2**20
#: the resident ``dk`` and ``dv`` of the backward kernel, both buffers of both
#: blocks: what a key/value head's row may take of VMEM
_ROW_BYTES = 48 * 2**20
_NEG_BIG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a [M, K] by b [N, K] -> [M, N]


def _tile(length: int) -> int:
    return min(TILE, length)


def core_kind(heads: int, kv_heads: int, head_dim: int, value_dim: int, length: int,
              stats_dtype=jnp.float32, interpret: bool = False) -> str:
    """What implements :func:`.attention.chosen_attention` at these shapes:
    "pallas" (this module's kernel pair) where the backend is a TPU
    (``interpret``: or the kernel's interpreter, for tests), the heads' and the
    values' widths are whole lane tiles, a row is whole kernel tiles (themselves
    whole lane tiles), the query heads divide over the key/value heads, the
    statistics are float32 and a key/value head's ``dk`` and ``dv`` of one row
    fit the backward kernel's VMEM; "xla" (the blockwise loop) otherwise."""
    edge = _tile(length)
    whole = (head_dim > 0 and head_dim % _LANES == 0 and value_dim > 0
             and value_dim % _LANES == 0 and length > 0 and edge % _LANES == 0
             and length % edge == 0 and kv_heads > 0 and heads % kv_heads == 0
             and 2 * 4 * length * (head_dim + value_dim) <= _ROW_BYTES)
    f32 = jnp.dtype(stats_dtype) == jnp.float32
    return "pallas" if whole and f32 and (interpret or jax.default_backend() == "tpu") else "xla"


def forms(heads: int, kv_heads: int, head_dim: int, length: int) -> Dict[str, str]:
    """``chosen_core`` (:func:`core_kind`): what a sparse-attention layer's core
    runs over rows of ``length`` slots, values as wide as the heads."""
    return {"chosen_core": core_kind(heads, kv_heads, head_dim, head_dim, length)}


# -- what XLA makes before a call ---------------------------------------------
def _keep(chosen, seg):
    """The pairs a tile keeps, a byte a pair (a kernel reads no bool):
    ``chosen`` [B, L, L] (query, key) under the causal mask and inside
    histories (``seg`` [B, L])."""
    pos = jnp.arange(chosen.shape[1])
    keep = chosen & (pos[:, None] >= pos[None, :])[None] & (seg[:, :, None] == seg[:, None, :])
    return keep.astype(jnp.int8)


def _tables(keep, edge: int):
    """A number a tile (row, query tile i, key tile j) of ``keep``, flat int32:
    ``live``, 1 where the tile holds a kept pair; ``fetch``, the key tile a
    grid step names: j itself where the tile is live, else the last live one
    before it in the query tile's walk (the first, before any), so a tile that
    is skipped fetches nothing."""
    b, length, _ = keep.shape
    n = length // edge
    # over a query tile's rows first: they are whole layout tiles, so nothing is copied
    live = keep.reshape(b, n, edge, length).max(2).reshape(b, n, n, edge).max(-1) != 0
    last = jax.lax.cummax(jnp.where(live, jnp.arange(n), -1), axis=2)
    fetch = jnp.where(last < 0, jnp.argmax(live, axis=2)[..., None], last)
    return live.astype(jnp.int32).reshape(-1), fetch.astype(jnp.int32).reshape(-1)


# -- the kernels --------------------------------------------------------------
def _at(b, i, j):
    """Where tile (b, i, j) lies in the flat tables."""
    return (b * pl.num_programs(2) + i) * pl.num_programs(3) + j


def _forward_kernel(scale, live_ref, fetch_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
                    m_ref, l_ref, acc_ref):
    """One tile for the G heads of a key/value head. ``q_ref`` [G, T, D],
    ``k_ref`` [T, D], ``v_ref`` [T, Dv], ``keep_ref`` [T, T] (query, key);
    ``m_ref``, ``l_ref`` [G, T, LANES]: the running maximum and sum, a number a
    query over all lanes; ``acc_ref`` [G, T, Dv]."""
    del fetch_ref  # the block specs' alone
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    f32 = jnp.float32
    groups, edge = q_ref.shape[0], k_ref.shape[0]
    over = lambda t, width: t if width == _LANES else jnp.tile(t, (1, width // _LANES))  # noqa: E731

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_BIG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(live_ref[_at(b, i, j)] != 0)
    def _():
        keep = keep_ref[...].astype(jnp.int32) != 0
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = jax.lax.dot_general(q_ref[g, :, :], k, _NT, preferred_element_type=f32) * scale
            sm = jnp.where(keep, s, _NEG_BIG)
            m_prev = m_ref[g, :, :]
            m_new = jnp.maximum(m_prev, sm.max(-1, keepdims=True))
            # a query that has kept no key yet: its masked scores give 0, not exp(0)
            origin = jnp.where(m_new == _NEG_BIG, 0.0, m_new)
            p = jnp.exp(sm - over(origin, edge))
            corr = jnp.exp(m_prev - m_new)
            l_ref[g, :, :] = l_ref[g, :, :] * corr + p.sum(-1, keepdims=True)
            acc_ref[g, :, :] = acc_ref[g, :, :] * over(corr, acc_ref.shape[-1]) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=f32)
            m_ref[g, :, :] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(groups):
            l = jnp.maximum(l_ref[g, :, :], 1e-30)
            o_ref[g, :, :] = (acc_ref[g, :, :] / over(l, acc_ref.shape[-1])).astype(o_ref.dtype)
            lse_ref[g:g + 1, :] = (m_ref[g, :, :] + jnp.log(l)).T[:1]  # a query a lane


def _backward_kernel(scale, live_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     keep_ref, dq_ref, dk_ref, dv_ref, dq_acc):
    """One tile backwards for the G heads of a key/value head, keys on the
    sublanes. ``q_ref``, ``do_ref`` [G, T, .], ``lse_ref``, ``delta_ref`` [G, T],
    ``keep_ref`` [T, T] (query, key: turned round here, once a tile);
    ``dk_ref``, ``dv_ref`` [L, .] float32: the
    key/value head's whole row, the block of every grid step of the row."""
    del fetch_ref
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    f32 = jnp.float32
    groups, edge = q_ref.shape[0], k_ref.shape[0]

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, f32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, f32)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, f32)

    @pl.when(live_ref[_at(b, i, j)] != 0)
    def _():
        keep = keep_ref[...].astype(f32).T != 0
        k, v = k_ref[...], v_ref[...]
        dk = jnp.zeros(k.shape, f32)
        dv = jnp.zeros(v.shape, f32)
        for g in range(groups):
            q, do = q_ref[g, :, :], do_ref[g, :, :]
            s = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32) * scale
            p = jnp.where(keep, jnp.exp(s - lse_ref[g:g + 1, :]), 0.0)
            dv = dv + jnp.dot(p.astype(do.dtype), do, preferred_element_type=f32)
            dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
            ds = p * (dp - delta_ref[g:g + 1, :]) * scale
            dk = dk + jnp.dot(ds.astype(q.dtype), q, preferred_element_type=f32)
            dq_acc[g, :, :] += jnp.dot(ds.T.astype(k.dtype), k, preferred_element_type=f32)
        rows = pl.ds(pl.multiple_of(j * edge, edge), edge)
        dk_ref[rows, :] += dk
        dv_ref[rows, :] += dv

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4, vmem_limit_bytes=_VMEM),
        interpret=interpret)


def _specs(n: int, groups: int, edge: int, d: int, dv: int):
    """Block specs of a grid step (row, key/value head, i, j): of a query
    tile of the group [G, T, width], of the key and the value tile the tables
    name, of the mask's tile, and of a query tile's numbers a query [G, T]."""
    named = lambda fetch, b, i, j: fetch[(b * n + i) * n + j]  # noqa: E731
    queries = lambda width: pl.BlockSpec(  # noqa: E731
        (None, None, groups, edge, width), lambda b, h, i, j, live, fetch: (b, h, 0, i, 0))
    keys = lambda width: pl.BlockSpec(  # noqa: E731
        (None, None, edge, width), lambda b, h, i, j, live, fetch: (b, h, named(fetch, b, i, j), 0))
    keep = pl.BlockSpec(
        (None, edge, edge), lambda b, h, i, j, live, fetch: (b, i, named(fetch, b, i, j)))
    a_query = pl.BlockSpec((None, None, groups, edge), lambda b, h, i, j, live, fetch: (b, h, 0, i))
    return queries, keys(d), keys(dv), keep, a_query


@functools.partial(jax.jit, static_argnums=(0,))
def _forward(static, q, k, v, seg, chosen):
    """q [B, Hkv, G, L, D], k [B, Hkv, L, D], v [B, Hkv, L, Dv], seg [B, L],
    chosen [B, L, L] bool -> o as q lies (q's dtype, v's width), the rows'
    log-sum-exp [B, Hkv, G, L] float32. (A jitted function, as the backward
    pass is: a step calls each at one shape, and the body is traced once.)"""
    edge, interpret = static
    b, hkv, groups, length, d = q.shape
    dv, n, f32 = v.shape[-1], length // edge, jnp.float32
    queries, keys, values, keep, a_query = _specs(n, groups, edge, d, dv)
    keep8 = _keep(chosen, seg)
    return pl.pallas_call(
        functools.partial(_forward_kernel, 1.0 / np.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, n, n),
            in_specs=[queries(d), keys, values, keep], out_specs=[queries(dv), a_query],
            scratch_shapes=[pltpu.VMEM((groups, edge, _LANES), f32),
                            pltpu.VMEM((groups, edge, _LANES), f32),
                            pltpu.VMEM((groups, edge, dv), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape[:-1] + (dv,), q.dtype),
                   jax.ShapeDtypeStruct(q.shape[:-1], f32)],
        name="chosen_core_forward", **_params(interpret),
    )(*_tables(keep8, edge), q, k, v, keep8)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(static, q, k, v, seg, chosen, o, lse, do):
    """-> the cotangents of q (as it lies, its dtype), k and v (float32)."""
    edge, interpret = static
    b, hkv, groups, length, d = q.shape
    dv, n, f32 = v.shape[-1], length // edge, jnp.float32
    delta = jnp.sum(do.astype(f32) * o.astype(f32), -1)
    queries, keys, values, keep, a_query = _specs(n, groups, edge, d, dv)
    row = lambda width: pl.BlockSpec(  # noqa: E731
        (None, None, length, width), lambda b, h, i, j, live, fetch: (b, h, 0, 0))
    keep8 = _keep(chosen, seg)
    return pl.pallas_call(
        functools.partial(_backward_kernel, 1.0 / np.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, n, n),
            in_specs=[queries(d), keys, values, queries(dv), a_query, a_query, keep],
            out_specs=[queries(d), row(d), row(dv)],
            scratch_shapes=[pltpu.VMEM((groups, edge, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        name="chosen_core_backward", **_params(interpret),
    )(*_tables(keep8, edge), q, k, v, do, lse, delta, keep8)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _core(static, q, k, v, seg, chosen):
    """``attention._flash_chosen`` by the kernel pair: o and the rows'
    log-sum-exp, which no gradient flows through (nor through the mask)."""
    return _forward(static, q, k, v, seg, chosen)


def _core_fwd(static, q, k, v, seg, chosen):
    o, lse = _forward(static, q, k, v, seg, chosen)
    return (o, lse), (q, k, v, seg, chosen, o, lse)


def _core_bwd(static, saved, cotangents):
    q, k, v, seg, chosen, o, lse = saved
    dq, dk, dv = _backward(static, q, k, v, seg, chosen, o, lse, cotangents[0])
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None


_core.defvjp(_core_fwd, _core_bwd)


def chosen_core(q, k, v, chosen, segment_ids=None, *,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """:func:`.attention.chosen_attention` where :func:`core_kind` says
    "pallas": q [B, H, L, D], k [B, Hkv, L, D], v [B, Hkv, L, Dv], ``chosen`` [B,
    L, L] bool, ``segment_ids`` [B, L] -> o [B, H, L, Dv] and the rows'
    log-sum-exp [B, H, L] float32."""
    b, h, length, _ = q.shape
    hkv = k.shape[1]
    seg = (jnp.zeros((b, length), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    o, lse = _core((_tile(length), interpret),
                   q.reshape(b, hkv, h // hkv, length, q.shape[-1]), k, v, seg, chosen)
    return o.reshape(b, h, length, v.shape[-1]), lse.reshape(b, h, length)
