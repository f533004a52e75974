"""Mamba-2: a state-space mixer whose state is a matrix per head, decayed by
a scalar a head and slot and written by an outer product.

Per head, with state ``S`` [P, N] (head width x state width), zero entering
a history's first slot, ``A = -exp(A_log)`` and ``dt`` the softplus'd step::

    S_t = exp(dt_t A) S_(t-1) + dt_t u_t (x) B_t;   y_t = S_t C_t

``B_t`` and ``C_t`` [N] are shared by the heads of a group: ``groups`` groups
of ``H / groups`` neighbouring heads, head i reading group ``i // (H /
groups)`` (one group: all heads share them). No correction of the state by
what it already holds: no triangular system, unlike the delta rule
(:mod:`.deltanet`).

:func:`ssd_scan` computes that in chunks of ``chunk`` slots (the state-space
duality form). Inside a chunk slot i reads slot j <= i of its own history
through ``exp(cum_i - cum_j) * (C_i . B_j)``, cum the running sum of ``dt A``
inside the chunk; the scores ``C B^T`` are made once for the heads of a group,
the decay matrix a head. The decay is always the exponential of a DIFFERENCE
(never ``exp(cum_i) * exp(-cum_j)``), so nothing overflows however fast a
head forgets. Every chunk writes ``sum_j e_last_j dt_j u_j (x) B_j`` to the
state at its last slot (``e_last``: what of slot j is still there), and the
state it is handed adds ``e_in_i (S C_i)`` to the slots of the history it
belongs to. It is one of two forms of that arithmetic (:func:`scan_kind`
says which, from the shapes, the dtypes and the backend).

The kernel (a TPU, heads that fill whole lane tiles, a state and chunks of
whole lane tiles, float32 state and gates): a Pallas kernel pair behind a
custom VJP that walks a row's chunks in order, ``_HEADS`` heads a grid step,
with the states of all heads in VMEM from chunk to chunk ([N, H P] float32,
2 MB at 64 heads of 64 on a state of 128). ``u`` and ``y`` are read and
written as they lie ([L, H P]: a lane tile holds two heads of 64); a grid
step's heads lie in ONE group, whose ``B`` and ``C`` it is handed by block
index ([L, G N]: group g's N columns); the scores and the mask (same history,
not above the diagonal) are made once a chunk and group; a head's [C, C]
decay matrix, its product with the scores and ``dt u`` exist in VMEM
alone. What is per slot AND head (``dt``, the running sum,
``e_in``, ``e_last``: 2 MB each a row of 8,192) is made by XLA
(``seq.ssm.scan.prep``) and handed over with the slots on the sublanes and
as rows. The forward kernel writes ``y`` and the state ENTERING every chunk
([chunks, N, H P] float32, 67 MB), the backward pass's only residual
beside the inputs. The backward kernel takes the chunks last to first
carrying the state's cotangent, makes a head's matrices again (transposed:
slot j on the sublanes) and writes the cotangents of ``u``, of ``B`` and
``C`` (summed over a group's heads in VMEM) and, a slot and head, of ``dt``
(through ``dt u``), ``e_in`` and ``e_last`` (sums over a head's lanes, made
as products with a 0/1 matrix) and of the running sum: the row sums less the
column sums of ONE matrix a head, the decay times its cotangent, so that
what slot i reads of slot j cancels to the bit in both, as it does in XLA's
form (``<dy_k, y_k> - <dt_k u_k, d(dt_k u_k)>``, which needs no such sums,
is the same number in exact arithmetic and read A's gradient 0.06 off on
the chip where XLA's form reads 0.003: PERF.md section 6). XLA
differentiates the running sum, the masked exponentials ``e_in``, ``e_last``
and the state's decay themselves, ``A`` through them. Products with the
state or its cotangent are float32: where one operand is bfloat16's (``C``,
the 0/1 matrix) the other is split into three bfloat16 terms, three passes
where ``HIGHEST`` makes six.

XLA's batch products (the CPU, toy widths, the control build's bfloat16
state), a group at a time (``vmap`` over the groups where there are several),
with no loop over slots and none over chunks, in three phases after
a preparation (``seq.ssm.scan.prep``: the layouts into chunks, ``dt u`` and
the running sums). Local (``seq.ssm.scan.local``): the decay matrix a head
and chunk times the scores, one batch product with ``dt u``, recomputed in
the backward pass (its [C, C] matrices a head and chunk are the mixer's
largest arrays). State (``seq.ssm.scan.state``): every chunk's own
contribution, one batch product; then every chunk's incoming state as the
decayed sum of the contributions before it, a product with a [chunks,
chunks] matrix a head whose entry is zero where a history ended in between.
Out (``seq.ssm.scan.out``): what the incoming state adds.

Packed rows: ``seg`` gives each slot the id of its history (one contiguous
run per id). The first slot of a history starts from a zero state: every
mask above compares ids, so a chunk may hold any number of boundaries, on
its first slot, its last, or anywhere between (in either form).

:func:`mamba2` is the mixer around the scan: in-projection to ``[z | x B
C]`` and ``dt``, a depthwise causal convolution with a bias over ``x B C``
(a tap in another history reads zero: :func:`.shortconv.conv_chain`),
SiLU, the scan, the skip ``D * u``, the gated RMS norm ``rms(y *
silu(z)) * w`` over each group's share of the inner width (one group: the
whole of it), out-projection.

Precision (both forms): ``dt``, ``dt A`` and its running sums are
``gate_dtype`` (float32: they feed exponentials), the state ``state_dtype``
(float32) and read as float32 at ``Precision.HIGHEST``
(:func:`.deltanet._with_state`); the other products take ``compute_dtype``
inputs (bfloat16 on the chip) and accumulate in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .deltanet import _HI, _with_state
from .shortconv import chain_kind, conv_chain

_LANES = 128
_HEADS = 8  # heads of a grid step of the kernel (their slots' work is unrolled in its body)
_VMEM = 100 * 2**20


def _segsum(x):
    """x [..., T] -> [..., T, T]: entry (i, m) is ``sum_(k = m+1 .. i)
    x_k`` for i >= m, each sum made of its own terms alone (no difference
    of running sums that grow with T); 0 above the diagonal."""
    t = x.shape[-1]
    rows = jnp.broadcast_to(x[..., :, None], x.shape + (t,))  # [.., k, m] = x_k
    below = jnp.tril(jnp.ones((t, t), bool), -1)  # k > m
    return jnp.cumsum(jnp.where(below, rows, 0), axis=-2)


def ssd_scan(u, dt, a, b, c, seg, *, chunk: int = 256, groups: int = 1,
             compute_dtype=jnp.float32, state_dtype=jnp.float32, gate_dtype=jnp.float32,
             interpret: bool = False):
    """u [B, L, H, P], dt [B, L, H] (after the softplus), a [H] (negative),
    b, c [B, L, G N] (group g's N numbers side by side; head i reads group ``i
    // (H / G)``), seg [B, L] -> y [B, L, H, P] float32 (without the skip
    ``D * u``). The kernel's walk or XLA's batch products: :func:`scan_kind`."""
    bsz, length, heads, p = u.shape
    pad = -length % chunk
    if pad:  # slots of a history of their own, which write nothing (dt 0, u 0)
        u, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (u, dt, b, c))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    n = (length + pad) // chunk
    cd, f32 = compute_dtype, jnp.float32
    sc = seg.reshape(bsz, n, chunk)
    with jax.named_scope("seq.ssm.scan.prep"):
        dtc = dt.astype(gate_dtype).reshape(bsz, n, chunk, heads)
        # log decay a slot, and its running sum inside the chunk (inclusive), [B, n, H, C]
        cum = jnp.cumsum(jnp.moveaxis(dtc * a.astype(gate_dtype), 2, 3), axis=-1)
    if scan_kind(heads, p, b.shape[-1] // groups, length, chunk, state_dtype, gate_dtype,
                 interpret, groups) == "pallas":
        with jax.named_scope("seq.ssm.scan.prep"):
            decays = _decays(cum, seg)
        y = _walk((interpret, chunk, groups), u.reshape(bsz, -1, heads * p), dtc, cum, *decays,
                  b.astype(cd), c.astype(cd), seg)
        return y.reshape(bsz, length + pad, heads, p)[:, :length]

    def one_group(u, dtc, cum, b, c):
        """The heads that share ``b`` and ``c`` [B, L, N]: u [B, L, H, P], dtc [B,
        n, C, H], cum [B, n, H, C] -> y [B, n, C, H, P]."""
        with jax.named_scope("seq.ssm.scan.prep"):
            uc = u.reshape(bsz, n, chunk, -1, p)
            bc, cc = (t.astype(cd).reshape(bsz, n, chunk, -1) for t in (b, c))
            x = dtc[..., None] * uc.astype(gate_dtype)  # dt u, [B, n, C, H, P]
        prev_last = jnp.pad(sc[:, :-1, -1], ((0, 0), (1, 0)), constant_values=-3)  # [B, n]

        @jax.checkpoint
        def local(cum, x, bc, cc):
            with jax.named_scope("seq.ssm.scan.local"):
                same = sc[..., :, None] == sc[..., None, :]  # [B, n, C, C]
                reads = (same & jnp.tril(jnp.ones((chunk, chunk), bool)))[:, :, None]
                diff = (cum[..., :, None] - cum[..., None, :]).astype(f32)
                decay = jnp.exp(jnp.where(reads, diff, -jnp.inf))  # [B, n, H, C, C]
                scores = jnp.einsum("bnis,bnjs->bnij", cc, bc, preferred_element_type=f32)
                m = (scores[:, :, None] * decay).astype(cd)
                return jnp.einsum("bnhij,bnjhp->bnihp", m, x.astype(cd),
                                  preferred_element_type=f32)

        y = local(cum, x, bc, cc)

        with jax.named_scope("seq.ssm.scan.state"):
            # what of slot j is still there at the chunk's last slot
            to_last = (sc == sc[..., -1:])[:, :, None]  # [B, n, 1, C]
            e_last = jnp.exp(jnp.where(to_last, (cum[..., -1:] - cum).astype(f32), -jnp.inf))
            xe = (x.astype(f32) * jnp.moveaxis(e_last, 2, 3)[..., None]).astype(cd)
            wrote = jnp.einsum("bnjhp,bnjs->bnhps", xe, bc,
                               preferred_element_type=f32).astype(state_dtype)
            # the state after chunk i: what chunk m <= i wrote, decayed over the
            # chunks between, unless a history ended in one of them
            cont = sc[..., -1] == prev_last  # chunk k ends inside the history it was handed
            breaks = jnp.cumsum(~cont, axis=-1)  # [B, n]
            whole = (breaks[..., :, None] == breaks[..., None, :]) & jnp.tril(
                jnp.ones((n, n), bool))
            total = jnp.moveaxis(cum[..., -1], 1, 2)  # [B, H, n]: a chunk's whole log decay
            carry = jnp.exp(jnp.where(whole[:, None], _segsum(total).astype(f32), -jnp.inf))
            after = _with_state("bhim,bmhps->bihps", carry, wrote).astype(state_dtype)
            incoming = jnp.pad(after[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)  # [B, n, H, P, N]

        with jax.named_scope("seq.ssm.scan.out"):
            carried = (sc == prev_last[..., None])[:, :, None]  # [B, n, 1, C]: sees the incoming state
            e_in = jnp.exp(jnp.where(carried, cum.astype(f32), -jnp.inf))  # [B, n, H, C]
            read = _with_state("bnis,bnhps->bnihp", cc, incoming)
            return y + read * jnp.moveaxis(e_in, 2, 3)[..., None]

    if groups == 1:
        y = one_group(u, dtc, cum, b, c)
    else:  # a group's heads are neighbours: [.., H, ..] -> [.., G, H / G, ..]
        def split(t, axis):
            return t.reshape(t.shape[:axis] + (groups, -1) + t.shape[axis + 1:])

        y = jax.vmap(one_group, in_axes=(2, 3, 2, 2, 2), out_axes=3)(
            split(u, 2), split(dtc, 3), split(cum, 2), split(b, 2), split(c, 2))
    return y.reshape(bsz, length + pad, heads, p)[:, :length]


def _tile(heads: int, head_dim: int, groups: int = 1) -> int:
    """Heads of a grid step: the most, up to ``_HEADS`` (and a quarter of a
    lane tile: the backward kernel's four sums a slot and head lie side by
    side in one), that divide a group's heads and fill whole lane tiles; 0
    where no number does."""
    return max((d for d in range(1, min(_HEADS, _LANES // 4) + 1)
                if heads % groups == 0 and (heads // groups) % d == 0
                and d * head_dim % _LANES == 0), default=0)


def scan_kind(heads: int, head_dim: int, state: int, length: int, chunk: int,
              state_dtype=jnp.float32, gate_dtype=jnp.float32, interpret: bool = False,
              groups: int = 1) -> str:
    """What implements :func:`ssd_scan` at these shapes: "pallas" (the
    kernel pair's walk over a row's chunks, a chunk's decay matrices and the
    state in VMEM; its backward pass keeps the state entering every chunk)
    where a lane tile holds whole heads and a grid step's heads, all of one
    of the ``groups`` groups, whole lane tiles, the state's width and the chunk
    are whole lane tiles
    (a row is padded to whole chunks in either form), state and gates
    float32 and the backend a TPU (``interpret``: or the kernel's
    interpreter, for tests); "xla" (batch products over all chunks that the
    compiler schedules) otherwise."""
    whole = (head_dim > 0 and _LANES % head_dim == 0 and _tile(heads, head_dim, groups) > 0
             and state % _LANES == 0 and chunk % _LANES == 0 and state > 0 and length > 0)
    f32 = all(jnp.dtype(t) == jnp.float32 for t in (state_dtype, gate_dtype))
    return "pallas" if whole and f32 and (interpret or jax.default_backend() == "tpu") else "xla"


def _dot(a, b, contract, high: bool):
    """``a`` and ``b`` contracted over one axis each; float32 accumulation;
    ``high``: float32 operands multiplied as float32."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_HI if high else None)


def _dot_exact(a, b, contract, exact: int = 0):
    """A product as float32 in which operand ``exact`` is bfloat16 (numbers
    bfloat16 holds exactly): the other operand in three bfloat16 terms, three
    passes where ``HIGHEST`` makes six (3.48 -> 2.94 ms a backward pass on
    the chip: PERF.md section 6); a float32 ``exact`` takes ``HIGHEST``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if (a, b)[exact].dtype == f32:
        return _dot(a.astype(f32), b.astype(f32), contract, True)
    out, rest = None, (b, a)[exact]
    for _ in range(3):
        term = rest.astype(bf16)
        part = (_dot(a.astype(bf16), term, contract, False) if exact == 0
                else _dot(term, b.astype(bf16), contract, False))
        out = part if out is None else out + part
        rest = rest - term.astype(f32)
    return out


def _spread(p: int, cols_ref, at: int, first: int, lanes):
    """Column ``at + h`` of ``cols_ref`` (a number a slot and head) over the
    lanes of head ``first + h`` of one lane tile, h = 0 .. LANES / p - 1:
    [C, LANES]."""
    out = cols_ref[:, at + first:at + first + 1]
    for h in range(1, lanes.shape[-1] // p):
        out = jnp.where(lanes < h * p, out, cols_ref[:, at + first + h:at + first + h + 1])
    return jnp.broadcast_to(out, lanes.shape)


def _lane_tile(hb: int, p: int, k: int, u_ref, cols_ref, lanes):
    """Lane tile ``k`` of a grid step's heads: where it lies, its first head,
    ``dt``, ``e_in`` and ``e_last`` over its heads' lanes, u and ``dt u`` as
    float32 [C, LANES]."""
    at, first = pl.ds(k * _LANES, _LANES), k * (_LANES // p)
    dt, e_in, e_last = (_spread(p, cols_ref, i * hb, first, lanes) for i in (0, 2, 3))
    u = u_ref[:, at].astype(jnp.float32)
    return at, first, dt, e_in, e_last, u, dt * u


def _own(t, h: int, p: int, lanes):
    """``t`` [C, LANES] with the lanes of the tile's other heads zeroed."""
    return t if p == _LANES else jnp.where((lanes >= h * p) & (lanes < (h + 1) * p), t, 0)


def _shared(seg_col_ref, seg_row_ref, b_ref, c_ref, transposed: bool):
    """What all heads of a chunk share: the scores ``C B^T`` [i, j] (or
    ``B C^T`` [j, i]) and 0 where slot i reads slot j, -inf elsewhere."""
    chunk = b_ref.shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    reads = (seg_col_ref[...] == seg_row_ref[...]) & ((sub <= lane) if transposed
                                                      else (sub >= lane))
    high = b_ref.dtype == jnp.float32
    scores = (_dot(b_ref[...], c_ref[...], (1, 1), high) if transposed
              else _dot(c_ref[...], b_ref[...], (1, 1), high))
    return scores, jnp.where(reads, 0.0, -jnp.inf)


def _group_end(per_group: int, last: bool):
    """Whether this grid step's tile of heads is its group's first (``last``:
    its last), ``per_group`` tiles a group (0: one group, all the tiles)."""
    tile = pl.program_id(2)
    if not per_group:
        return tile == pl.num_programs(2) - 1 if last else tile == 0
    return tile % per_group == (per_group - 1 if last else 0)


def _forward_kernel(hb, p, per_group, u_ref, cols_ref, rows_ref, b_ref, c_ref, seg_col_ref,
                    seg_row_ref, g_ref, y_ref, kept_ref, s_ref, scores_ref, bias_ref):
    """One chunk for ``hb`` heads of one group (the grid: rows, chunks in
    order, tiles of heads; ``b_ref``, ``c_ref``: the group's). ``cols_ref``
    [C, 4 hb]: ``dt``, the running sum, ``e_in``, ``e_last`` a slot and head;
    ``rows_ref`` [hb, C]: the running sum;
    ``g_ref`` [1, hb P]: what of the incoming state reaches the next chunk;
    ``s_ref`` [tiles, N, hb P]: the states, transposed; ``kept_ref`` takes
    the tile's state entering the chunk."""
    tile = pl.program_id(2)
    f32, cd = jnp.float32, b_ref.dtype
    high = cd == f32

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[tile] = jnp.zeros(s_ref.shape[1:], f32)

    @pl.when(_group_end(per_group, False))
    def _():
        scores_ref[...], bias_ref[...] = _shared(seg_col_ref, seg_row_ref, b_ref, c_ref, False)

    kept_ref[...] = s_ref[tile]
    chunk = b_ref.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    for k in range(hb * p // _LANES):
        at, first, _, e_in, e_last, _, x = _lane_tile(hb, p, k, u_ref, cols_ref, lanes)
        xc = x.astype(cd)
        s = s_ref[tile, :, at]  # [N, LANES]
        y = e_in * _dot_exact(c_ref[...], s, (1, 0))
        for h in range(_LANES // p):  # the heads of the lane tile
            head = first + h
            diff = cols_ref[:, hb + head:hb + head + 1] - rows_ref[head:head + 1, :]
            m = (scores_ref[...] * jnp.exp(diff + bias_ref[...])).astype(cd)
            y = y + _dot(m, _own(xc, h, p, lanes), (1, 0), high)
        y_ref[:, at] = y
        wrote = _dot(b_ref[...], (x * e_last).astype(cd), (0, 0), high)  # [N, LANES]
        s_ref[tile, :, at] = g_ref[:, at] * s + wrote


def _backward_kernel(hb, p, per_group, u_ref, cols_ref, rows_ref, b_ref, c_ref, seg_col_ref,
                     seg_row_ref, g_ref, kept_ref, dy_ref, du_ref, sums_ref, reads_ref, db_ref,
                     dc_ref, dg_ref, ds_ref, scores_ref, bias_ref, dscores_ref):
    """One chunk backwards for ``hb`` heads of one group (the grid: rows, chunks
    last to first, tiles of heads). ``ds_ref`` [tiles, N, hb P] carries the
    cotangent of the state leaving the chunk. Writes the cotangent of ``u``;
    ``sums_ref`` [C, 4 hb], a slot and head: the cotangents of ``dt``
    (through ``dt u``), ``e_in`` and ``e_last``, and what is read OF slot j
    (a column sum of the head's decay matrix times its cotangent);
    ``reads_ref`` [hb, C]: what slot i reads (the row sum of the same
    matrix: the running sum's cotangent is the one less the other); B's and
    C's cotangents, added up over the group's tiles of heads in the output's block;
    ``dg_ref`` [1, hb P]: the state entering the chunk times the cotangent
    of the state leaving it, lane by lane."""
    tile = pl.program_id(2)
    f32, cd = jnp.float32, b_ref.dtype
    high = cd == f32

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[tile] = jnp.zeros(ds_ref.shape[1:], f32)

    @pl.when(_group_end(per_group, False))
    def _():
        scores_ref[...], bias_ref[...] = _shared(seg_col_ref, seg_row_ref, b_ref, c_ref, True)
        dscores_ref[...] = jnp.zeros_like(dscores_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    chunk = b_ref.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    # lane l of a lane tile -> column (its head) of the sums, by a product with 0 and 1
    head_of = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0) // p
    column = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)

    def by_head(z, group, first):
        return _dot_exact(z, (head_of + (group * hb + first) == column).astype(jnp.bfloat16),
                          (1, 0), 1)

    sums = jnp.zeros((chunk, _LANES), f32)
    db, dc = jnp.zeros(db_ref.shape, f32), jnp.zeros(dc_ref.shape, f32)
    for k in range(hb * p // _LANES):
        at, first, dt, e_in, e_last, u, x = _lane_tile(hb, p, k, u_ref, cols_ref, lanes)
        dy = dy_ref[:, at]
        xc, dyc = x.astype(cd), dy.astype(cd)
        s, ds = kept_ref[:, at], ds_ref[tile, :, at]  # [N, LANES]
        # what the state the chunk was handed added to y
        sums = sums + by_head(dy * _dot_exact(c_ref[...], s, (1, 0)), 1, first)
        read = e_in * dy
        dc = dc + _dot(read, s, (1, 1), True)
        ds_ref[tile, :, at] = g_ref[:, at] * ds + _dot_exact(c_ref[...], read, (0, 0))
        dg_ref[:, at] = jnp.sum(ds * s, axis=0, keepdims=True)
        # what the chunk wrote to the state
        dsc = ds.astype(cd)
        db = db + _dot((x * e_last).astype(cd), dsc, (1, 1), high)
        dxe = _dot(b_ref[...], dsc, (1, 0), high)
        sums = sums + by_head(dxe * x, 2, first)
        dx = e_last * dxe
        # what its slots read of one another, a head at a time, slot j on the sublanes
        for h in range(_LANES // p):
            head = first + h
            mine, dmine = _own(xc, h, p, lanes), _own(dyc, h, p, lanes)
            diff = rows_ref[head:head + 1, :] - cols_ref[:, hb + head:hb + head + 1]
            decay = jnp.exp(diff + bias_ref[...])
            dx = dx + _dot((scores_ref[...] * decay).astype(cd), dmine, (1, 0), high)
            dscores = _dot(mine, dmine, (1, 1), high).astype(cd).astype(f32) * decay
            dscores_ref[...] += dscores
            ddecay = dscores * scores_ref[...]  # [j, i]: the decay times its cotangent
            reads_ref[head:head + 1, :] = jnp.sum(ddecay, axis=0, keepdims=True)
            sums = jnp.where(lanes == 3 * hb + head, jnp.sum(ddecay, axis=1, keepdims=True), sums)
        du_ref[:, at] = (dt * dx).astype(du_ref.dtype)
        sums = sums + by_head(u * dx, 0, first)
    sums_ref[...] = sums[:, :4 * hb]
    db_ref[...] += db
    dc_ref[...] += dc

    @pl.when(_group_end(per_group, True))
    def _():
        dscores = dscores_ref[...].astype(cd)  # [j, i]
        db_ref[...] += _dot(dscores, c_ref[...], (1, 0), high)
        dc_ref[...] += _dot(dscores, b_ref[...], (0, 0), high)


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM),
        interpret=interpret)


def _specs(chunk: int, hb: int, width: int, state: int, at, per_group: int):
    """Block specs a grid step ``(row, i, tile)``, ``at(i)`` the chunk: of
    what both kernels read (u, the columns, the rows, B, C, the ids as a
    column and as a row, the state's decay), and of a chunk's slots by the
    tile's lanes, of a state, of a chunk's slots by the state's width: the
    columns of the tile's group, ``per_group`` tiles a group (0: one group)."""
    group = (lambda t: t // per_group) if per_group else (lambda t: 0)
    wide = pl.BlockSpec((None, chunk, width), lambda r, i, t: (r, at(i), t))
    narrow = pl.BlockSpec((None, chunk, state), lambda r, i, t: (r, at(i), group(t)))
    reads = [wide,
             pl.BlockSpec((None, None, chunk, 4 * hb), lambda r, i, t: (r, t, at(i), 0)),
             pl.BlockSpec((None, None, None, hb, chunk), lambda r, i, t: (r, at(i), t, 0, 0)),
             narrow, narrow,
             pl.BlockSpec((None, chunk, 1), lambda r, i, t: (r, at(i), 0)),  # pio: lint-ok[mosaic-blockspec-tiling] a block of 1 is the array's whole dimension, which a block may be
             pl.BlockSpec((None, None, 1, chunk), lambda r, i, t: (r, at(i), 0, 0)),  # pio: lint-ok[mosaic-blockspec-tiling] a block of 1 is the array's whole dimension, which a block may be
             pl.BlockSpec((None, None, 1, width), lambda r, i, t: (r, at(i), 0, t))]  # pio: lint-ok[mosaic-blockspec-tiling] a block of 1 is the array's whole dimension, which a block may be
    kept = pl.BlockSpec((None, None, state, width), lambda r, i, t: (r, at(i), 0, t))
    return reads, wide, kept, narrow


@functools.partial(jax.jit, static_argnums=(0,))
def _forward(static, u, cols, rows, b, c, seg_col, seg_row, g):
    """u [B, L, H P], cols [B, tiles, L, 4 hb], rows [B, chunks, tiles, hb, C],
    b, c [B, L, G N], seg_col [B, L, 1], seg_row [B, chunks, 1, C], g [B,
    chunks, 1, H P] -> y as u lies (float32), the state entering every chunk
    [B, chunks, N, H P]. (A jitted function, as the backward pass is: a
    step calls each at one shape, and the body is traced once.)"""
    interpret, chunk, groups = static
    bsz, length, inner = u.shape
    tiles, hb, state = cols.shape[1], rows.shape[3], b.shape[-1] // groups
    n, width, f32 = length // chunk, inner // tiles, jnp.float32
    per_group = tiles // groups if groups > 1 else 0
    reads, wide, kept, _ = _specs(chunk, hb, width, state, lambda i: i, per_group)
    return pl.pallas_call(
        functools.partial(_forward_kernel, hb, width // hb, per_group),
        grid=(bsz, n, tiles), in_specs=reads, out_specs=[wide, kept],
        out_shape=[jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct((bsz, n, state, inner), f32)],
        scratch_shapes=[pltpu.VMEM((tiles, state, width), f32),
                        pltpu.VMEM((chunk, chunk), f32), pltpu.VMEM((chunk, chunk), f32)],
        **_params(interpret),
    )(u, cols, rows, b, c, seg_col, seg_row, g)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(static, u, cols, rows, b, c, seg_col, seg_row, g, kept, dy):
    """-> the cotangent of u (as it lies); a slot and head [B, tiles, L, 4
    hb]: the cotangents of ``dt`` (through ``dt u``), ``e_in``, ``e_last`` and
    what is read of the slot; what the slot reads, as rows [B, chunks, tiles,
    hb, C]; the cotangents of b and c [B, L, G N] float32; and that of the
    state's decay, lane by lane [B, chunks, 1, H P]."""
    interpret, chunk, groups = static
    bsz, length, inner = u.shape
    tiles, hb, state = cols.shape[1], rows.shape[3], b.shape[-1] // groups
    n, width, f32 = length // chunk, inner // tiles, jnp.float32
    per_group = tiles // groups if groups > 1 else 0
    back = lambda i: n - 1 - i  # noqa: E731
    reads, wide, kept_spec, narrow = _specs(chunk, hb, width, state, back, per_group)
    return pl.pallas_call(
        functools.partial(_backward_kernel, hb, width // hb, per_group),
        grid=(bsz, n, tiles), in_specs=reads + [kept_spec, wide],
        out_specs=[wide, reads[1], reads[2], narrow, narrow, reads[-1]],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(cols.shape, f32), jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct(b.shape, f32), jax.ShapeDtypeStruct(b.shape, f32),
                   jax.ShapeDtypeStruct(g.shape, f32)],
        scratch_shapes=[pltpu.VMEM((tiles, state, width), f32), pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32), pltpu.VMEM((chunk, chunk), f32)],
        **_params(interpret),
    )(u, cols, rows, b, c, seg_col, seg_row, g, kept, dy)


def _decays(cum, seg):
    """cum [B, chunks, H, C], seg [B, L] -> a slot and head, [B, chunks, C,
    H]: ``e_in`` (what of the incoming state slot i still sees) and
    ``e_last`` (what of slot j is still there at the chunk's last slot); and
    what of the incoming state reaches the next chunk [B, chunks, H]."""
    bsz, n, _, chunk = cum.shape
    sc = seg.reshape(bsz, n, chunk)
    prev_last = jnp.pad(sc[:, :-1, -1], ((0, 0), (1, 0)), constant_values=-3)  # [B, n]
    slots = jnp.moveaxis(cum, 2, 3)  # [B, n, C, H]
    carried = (sc == prev_last[..., None])[..., None]
    to_last = (sc == sc[..., -1:])[..., None]
    e_in = jnp.exp(jnp.where(carried, slots, -jnp.inf))
    e_last = jnp.exp(jnp.where(to_last, slots[:, :, -1:] - slots, -jnp.inf))
    cont = (sc[..., -1] == prev_last)[..., None]  # the chunk ends inside the history it was handed
    return e_in, e_last, jnp.where(cont, jnp.exp(cum[..., -1]), 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(static, u, dt, cum, e_in, e_last, g, b, c, seg):
    """u [B, L, H P]; dt, e_in, e_last [B, chunks, C, H], cum [B, chunks, H,
    C] and g [B, chunks, H] float32 (:func:`_decays`); b, c [B, L, G N]; seg [B,
    L]; L whole chunks -> y as u lies, float32. ``static``: whether the kernels
    are interpreted, the chunk, the groups."""
    return _forward(static, *_operands(static, u, dt, cum, e_in, e_last, g, b, c, seg))[0]


def _operands(static, u, dt, cum, e_in, e_last, g, b, c, seg):
    """What both kernels read: beside u, B and C the numbers a slot and head
    with the slots on the sublanes (``dt``, the running sum, ``e_in``,
    ``e_last``), a tile of heads at a time [B, tiles, L, 4 hb]; the running
    sum as rows [B, chunks, tiles, hb, C]; the ids as a column and as rows;
    the state's decay over its head's lanes."""
    _, chunk, groups = static
    bsz, length, inner = u.shape
    heads, n = dt.shape[-1], length // chunk
    hb = _tile(heads, inner // heads, groups)
    tiles = heads // hb
    with jax.named_scope("seq.ssm.scan.prep"):
        cols = jnp.stack([dt, jnp.moveaxis(cum, 2, 3), e_in, e_last], axis=3)  # [B, n, C, 4, H]
        cols = cols.reshape(bsz, n, chunk, 4, tiles, hb).transpose(0, 4, 1, 2, 3, 5)
        return (u, cols.reshape(bsz, tiles, length, 4 * hb),
                cum.reshape(bsz, n, tiles, hb, chunk), b, c, seg[..., None],
                seg.reshape(bsz, n, 1, chunk),
                jnp.repeat(g, inner // heads, axis=-1)[:, :, None])


def _walk_fwd(static, u, dt, cum, e_in, e_last, g, b, c, seg):
    """(The kernel's two outputs carry the name ``ssd``: a checkpoint whose
    policy keeps that name runs the forward kernel once and not again in its
    recomputation.)"""
    saved = (u, dt, cum, e_in, e_last, g, b, c, seg)
    y, kept = (checkpoint_name(t, "ssd") for t in _forward(static, *_operands(static, *saved)))
    return y, saved + (kept,)


def _walk_bwd(static, saved, dy):
    *given, kept = saved
    u, _, cum, _, _, g, b, c, _ = given
    bsz, length, _ = u.shape
    heads, n = g.shape[-1], g.shape[1]
    du, sums, reads, db, dc, dg = _backward(static, *_operands(static, *given), kept, dy)
    with jax.named_scope("seq.ssm.scan.prep"):
        tiles, hb = sums.shape[1], sums.shape[-1] // 4
        sums = sums.reshape(bsz, tiles, n, -1, 4, hb).transpose(4, 0, 2, 3, 1, 5)
        ddt, de_in, de_last, read_of = sums.reshape(4, bsz, n, -1, heads)  # [B, n, C, H] each
        dcum = reads.reshape(cum.shape) - jnp.moveaxis(read_of, 2, 3)
        dg = dg.reshape(bsz, n, heads, -1).sum(-1)
    return (du, ddt, dcum, de_in, de_last, dg,
            db.astype(b.dtype), dc.astype(c.dtype), None)


_walk.defvjp(_walk_fwd, _walk_bwd)


def _chain(width: int, inner: int) -> Dict:
    """:func:`conv_chain`'s arguments: x, B and C, what of the wide projection's
    ``width`` columns follows z's ``inner``."""
    return dict(channels=width - inner, at=inner, silu=True)


def forms(shapes: Dict, length: int, *, heads: int, head_dim: int, state: int, chunk: int = 256,
          groups: int = 1, state_dtype=jnp.float32, gate_dtype=jnp.float32, **_) -> Dict:
    """``ssd_scan`` (:func:`scan_kind`), ``conv`` ("pallas" or "xla") and, where
    the heads read B and C by group, ``ssd_groups``: what :func:`mamba2` runs over
    rows of ``length`` slots, ``shapes`` its parameters' and the keyword arguments
    its own."""
    return {"ssd_scan": scan_kind(heads, head_dim, state, length, chunk, state_dtype, gate_dtype,
                                  groups=groups),
            **({"ssd_groups": groups} if groups > 1 else {}),
            "conv": chain_kind(length, shapes["conv_w"][0],
                               **_chain(shapes["w_in"][1], heads * head_dim))}


def mamba2(p: Dict, x, seg, *, heads: int, head_dim: int, state: int, eps: float,
           chunk: int = 256, groups: int = 1, compute_dtype=jnp.float32,
           state_dtype=jnp.float32, gate_dtype=jnp.float32) -> Tuple[jax.Array, Dict]:
    """The mixer of a Mamba-2 layer: x [B, L, D] (normed) -> [B, L, D]
    float32. ``p``, with I = heads * head_dim and G = ``groups``: ``w_in`` [D, 2 I
    + 2 G N] (the columns ``[z | x | B | C]``, B and C a group after the other),
    ``w_dt`` [D, H] (the published in-projection's last H columns, a leaf of
    their own: they feed an exponential and are multiplied in float32),
    ``conv_w`` [K, I + 2 G N], ``conv_b`` [I + 2 G N], ``A_log``, ``dt_bias``,
    ``D`` [H], ``norm`` [I] (the gated norm's scale; the norm runs over each
    group's I / G channels), ``w_out`` [I, D].

    Also returns what the scan was given and what it gave, as this call
    computed them (``u`` [B, L, H, P], ``B``, ``C`` [B, L, G N], ``dt`` [B, L,
    H], ``y`` [B, L, H, P]): a caller that holds the scan that ran against
    the recurrence reads them."""
    bsz, length, _ = x.shape
    inner = heads * head_dim
    cd, f32 = compute_dtype, jnp.float32
    with jax.named_scope("seq.ssm.proj"):
        # the wide projection is kept in the compute dtype, as in the other mixers
        zxbc = jnp.dot(x.astype(cd), p["w_in"].astype(cd), preferred_element_type=f32).astype(cd)
        dt_raw = jnp.dot(x.astype(f32), p["w_dt"], precision=_HI)
    with jax.named_scope("seq.ssm.conv"):
        xbc = conv_chain(zxbc, p["conv_w"], seg, bias=p["conv_b"],
                         **_chain(zxbc.shape[-1], inner))
        u = xbc[..., :inner].reshape(bsz, length, heads, head_dim).astype(cd)
        wide = groups * state
        b, c = (xbc[..., inner + i * wide: inner + (i + 1) * wide].astype(cd) for i in (0, 1))
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    with jax.named_scope("seq.ssm.scan"):
        y = ssd_scan(u, dt, -jnp.exp(p["A_log"]), b, c, seg, chunk=chunk, groups=groups,
                     compute_dtype=cd, state_dtype=state_dtype, gate_dtype=gate_dtype)
    with jax.named_scope("seq.ssm.norm"):
        skipped = y + p["D"][:, None] * u.astype(f32)
        gated = skipped.reshape(bsz, length, inner) * jax.nn.silu(zxbc[..., :inner].astype(f32))
        if groups > 1:  # the norm's mean is a group's own
            gated = gated.reshape(bsz, length, groups, inner // groups)
        unit = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True) + eps)
        o = (unit.reshape(bsz, length, inner) if groups > 1 else unit) * p["norm"]
    with jax.named_scope("seq.ssm.out"):
        out = jnp.dot(o.astype(cd), p["w_out"].astype(cd), preferred_element_type=f32)
    return out, {"u": u, "B": b, "C": c, "dt": dt, "y": y}
