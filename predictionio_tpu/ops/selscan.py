"""Mamba-1: a state-space mixer whose state is a row of ``N`` numbers per
channel, decayed by channel AND state index and written by the input itself.

Per channel ``c`` and state index ``n``, with ``A = -exp(A_log)`` [C, N],
``Delta`` the softplus'd step [C] a slot and the state zero entering a
history's first slot::

    S_t[c, n] = exp(Delta_t[c] A[c, n]) S_(t-1)[c, n] + Delta_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n C_t[n] S_t[c, n]

``B_t`` and ``C_t`` [N] are made from the slot's own input (the scan is
"selective"). The decay differs by state index, so the matrix form of
:mod:`.ssd` (one scalar a head) does not apply: nothing here is a product
on the MXU, the recurrence is elementwise work over [C, N].

:func:`selective_scan` is one of two forms of the same float32 arithmetic
(:func:`scan_kind` says which, from the shapes, the dtypes and the backend).

The kernel (a TPU, channels in whole lane tiles, float32 state and gates).
A slot's state for a tile of 1,024 channels is ``N`` registers, one a state
index: the channels lie on sublanes AND lanes (``[L, C]`` is handed over as
``[L, C / 128, 128]``, a tile 8 of the lane tiles), so a slot's step is
elementwise work on whole registers, ``B_t[n]`` and ``C_t[n]`` are scalars
read from SMEM, the read-out is ``N`` multiply-adds and nothing crosses a
sublane. (The form with ``N`` on the sublanes and a lane tile of channels,
``B_t`` and ``C_t`` broadcast over the lanes, took 9.3 ms a forward pass on
the chip against 2.5: PERF.md section 6.) The forward kernel walks a grid
step's ``_WALK`` = 256 slots in a loop, ``_UNROLL`` = 2 slots a loop step,
the state in registers, carried from grid step to grid step in VMEM; it
writes ``y`` and the state ENTERING every grid step (32 x 5,120 x 16 floats,
10 MB a row: the only residual beside the inputs). The backward kernel takes
the grid steps last to first and, inside one, the channel tiles in turn: it
makes the step's states again from the kept one into VMEM (257 x 16
registers, 16.8 MB), then walks the slots backwards carrying the state's
cotangent and A's, and writes the cotangents of ``c`` and ``Delta``. What
B's and C's cotangents sum over ALL channels is added up register by
register over the channel tiles in VMEM (2 x 16.8 MB) and folded over the
sublanes once a grid step; the lanes and the rows are summed outside. A
history's first slot carries a flag (SMEM) that zeroes the state entering it
and, backwards, the cotangent leaving it. Neither direction keeps or moves a
state a slot or a chunk in HBM.

XLA's loops (the CPU, toy widths, the control build's bfloat16 state). A row
is walked in blocks of ``block`` slots, the state
carried from block to block and a block made again in the backward pass, so
neither direction keeps a state a slot (8,192 x 5,120 x 16 floats a layer if
it did). Inside a block its ``block / chunk`` chunks are walked side by side,
``chunk`` steps over [chunks, C, N] each from a zero state (one step's arrays
are large enough to hide a loop step's latency, and a step keeps the state it
started from and nothing else for the backward pass); then every chunk's
incoming state from the chunks before it, decayed over them unless a history
ended in between; then what the incoming state adds to the slots of the
history it belongs to, ``sum_n C_t[n] exp(A[c, n] cum_t[c]) S_in[c, n]`` with
``cum`` the running sum of ``Delta`` inside the chunk. Every decay is the
exponential of a non-positive number, so nothing overflows however fast a
channel forgets (in either form).

Packed rows: ``seg`` gives each slot the id of its history; a chunk may hold
any number of boundaries, on its first slot, its last, or anywhere between,
and the chunk and the block are no part of the result.

:func:`mamba1` is the mixer around the scan: in-projection to ``[x~ | z]``,
the short causal convolution with its bias and SiLU over ``x~``
(:func:`.shortconv.conv_chain`), the projection of the result to ``[delta |
B | C]``, ``Delta = softplus(W_dt delta + b_dt)``, the scan, the skip ``D *
c``, the gate ``silu(z)`` and the out-projection.

Precision: ``Delta``, the decays and the running sums are ``gate_dtype``
(float32: they feed exponentials), the state ``state_dtype`` (float32); the
projections take ``compute_dtype`` inputs (bfloat16 on the chip) and
accumulate in float32, but ``W_dt``, which is multiplied in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .deltanet import _HI
from .shortconv import chain_kind, conv_chain

#: slots of a block: what the backward pass keeps of one is its slots' states,
#: block x C x N floats (168 MB at 512 x 5,120 x 16) in the XLA form, in HBM;
#: the kernel keeps a channel tile's (16.8 MB at 256 x 1,024 x 16) in VMEM
BLOCK = 512
_LANES = 128
_SUBLANES = 8  # lane tiles of a channel tile: 1,024 channels fill a register a state index
_STRIP = 128  # slots: a row is whole strips where the kernel runs (a block of flags in SMEM is whole lane tiles)
_WALK = 256  # slots of a grid step of the kernel, and between two kept states
_VMEM = 100 * 2**20
_UNROLL = 2  # slots a loop step (1, 2, 4 on the chip: PERF.md section 6)


def selective_scan(x, dt, a, b, c, seg, *, chunk: int = 64, block: int = BLOCK,
                   state_dtype=jnp.float32, gate_dtype=jnp.float32, interpret: bool = False):
    """x [B, L, C], dt [B, L, C] (after the softplus), a [C, N] (negative),
    b, c [B, L, N], seg [B, L] -> y [B, L, C] float32 (without the skip
    ``D * x``). The kernel's walk or XLA's loops: :func:`scan_kind`."""
    bsz, length, ch = x.shape
    n = a.shape[-1]
    if scan_kind(ch, n, length, state_dtype, gate_dtype, interpret) == "pallas":
        return _walk_rows(x, dt, a, b, c, seg, interpret)
    chunk = min(chunk, length)
    block = max(chunk, min(block, length) // chunk * chunk)
    pad = -length % block
    if pad:  # slots of a history of their own, which write nothing (dt 0, x 0)
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt, b, c))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    blocks, k = (length + pad) // block, block // chunk
    first = _first(seg)
    f32, gd = jnp.float32, gate_dtype
    a = a.astype(gd)

    def lay(t):  # [B, L, W] -> [blocks, chunk, B, k, W]: a step of the walk is an axis
        return t.reshape(bsz, blocks, k, chunk, -1).transpose(1, 3, 0, 2, 4)

    @jax.checkpoint
    def slot(s, at):
        x_t, dt_t, b_t, c_t, first_t = at  # [B, k, C] x 2, [B, k, N] x 2, [B, k, 1]
        decay = jnp.exp(dt_t[..., None] * a)  # [B, k, C, N]
        s = jnp.where(first_t[..., None], 0, s).astype(gd) * decay + (
            (dt_t * x_t)[..., None] * b_t[..., None, :])
        s = s.astype(state_dtype)
        return s, jnp.sum(s.astype(f32) * c_t[..., None, :], -1)

    @jax.checkpoint
    def one_block(s0, xs):
        xb, dtb, bb, cb, fb = xs
        # every chunk from a zero state
        zero = jnp.zeros((bsz, k, ch, n), state_dtype)
        wrote, y = jax.lax.scan(slot, zero, (xb.astype(gd), dtb, bb.astype(gd), cb, fb))
        cum = jnp.cumsum(dtb, axis=0)  # [chunk, B, k, C]: Delta summed from the chunk's start
        clear = jnp.cumsum(fb, axis=0) == 0  # [chunk, B, k, 1]: no history began up to here
        # the state a chunk is handed: what the chunks before it wrote,
        # decayed over every chunk in between that no boundary cuts
        through = jnp.where(clear[-1][..., None], jnp.exp(cum[-1][..., None] * a), 0)

        def handed(s, at):
            p, w = at
            return (s.astype(gd) * p + w.astype(gd)).astype(state_dtype), s

        s_out, s_in = jax.lax.scan(
            handed, s0, (jnp.moveaxis(through, 1, 0), jnp.moveaxis(wrote, 1, 0)))
        s_in = jnp.moveaxis(s_in, 0, 1).astype(f32)  # [B, k, C, N]
        # what the incoming state adds to the slots before the chunk's first boundary
        reach = jnp.exp(cum[..., None] * a).astype(f32)  # [chunk, B, k, C, N]
        carried = jnp.sum(reach * s_in * cb[..., None, :], -1)
        return s_out, y + jnp.where(clear, carried, 0.0)

    xs = (lay(x), lay(dt.astype(gd)), lay(b), lay(c.astype(f32)),
          lay(first[..., None].astype(jnp.int32)) > 0)
    _, y = jax.lax.scan(one_block, jnp.zeros((bsz, ch, n), state_dtype), xs)
    return y.transpose(2, 0, 3, 1, 4).reshape(bsz, length + pad, ch)[:, :length]


def _first(seg):
    """seg [B, L] -> whether a slot is its history's (or the row's) first."""
    return jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)


def scan_kind(channels: int, state: int, length: int, state_dtype=jnp.float32,
              gate_dtype=jnp.float32, interpret: bool = False) -> str:
    """What implements :func:`selective_scan` at these shapes: "pallas" (the
    kernel's walk with the state in VMEM) where the channels are whole lane
    tiles (eight of them a channel tile, or all of fewer), the state's width
    a multiple of 8, a row whole strips, state and gates float32 and the
    backend a TPU (``interpret``: or the kernel's interpreter, for tests);
    "xla" (loops over a chunk's slots and a row's blocks that the compiler
    schedules) otherwise."""
    tiles = channels // _LANES
    whole = (channels % _LANES == 0 and (tiles % _SUBLANES == 0 or 0 < tiles < _SUBLANES)
             and state % 8 == 0 and length % _STRIP == 0 and length > 0)
    f32 = all(jnp.dtype(t) == jnp.float32 for t in (state_dtype, gate_dtype))
    return "pallas" if whole and f32 and (interpret or jax.default_backend() == "tpu") else "xla"


def _slots(length: int) -> int:
    """Slots of a grid step: whole strips that divide the row, at most ``_WALK``."""
    return max(n for n in range(_STRIP, min(_WALK, length) + 1, _STRIP) if length % n == 0)


def _slot(n_state: int, t, s, x_ref, dt_ref, a_ref, b_ref, c_ref, first_ref):
    """One slot of the recurrence for a channel tile: ``s`` the ``n_state``
    registers of the state after slot t - 1 -> the state after slot t and
    ``y_t``. ``B_t[n]`` and ``C_t[n]`` are scalars."""
    dt = dt_ref[t]
    u = dt * x_ref[t].astype(jnp.float32)
    keep = jnp.where(first_ref[t] != 0, 0.0, 1.0)  # zero entering a history's first slot
    at, out, y = t * n_state, [], None
    for n in range(n_state):
        # pio: lint-ok[mosaic-per-row-dma] a_ref[n] is whole registers [8, 128], b_ref and c_ref hold scalars in SMEM
        s_n = (s[n] * keep) * jnp.exp(dt * a_ref[n]) + u * b_ref[at + n]
        out.append(s_n)
        # pio: lint-ok[mosaic-per-row-dma] a scalar from SMEM
        y = s_n * c_ref[at + n] if y is None else y + s_n * c_ref[at + n]
    return tuple(out), y


def _loop(slots: int, body, carry):
    """``body(t, carry)`` over a grid step's slots in order, ``_UNROLL`` of
    them a loop step."""
    def group(j, carry):
        for k in range(_UNROLL):
            carry = body(j * _UNROLL + k, carry)
        return carry

    return jax.lax.fori_loop(0, slots // _UNROLL, group, carry)


def _forward_kernel(n_state, slots, x_ref, dt_ref, a_ref, b_ref, c_ref, first_ref,
                    y_ref, kept_ref, s_ref):
    """A channel tile's walk over one grid step's slots: the state stays in
    registers over the loop and in ``s_ref`` from step to step; ``kept_ref``
    takes the state entering the step, the backward pass's only residual."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    kept_ref[...] = s_ref[...]

    def slot(t, s):
        s, y = _slot(n_state, t, s, x_ref, dt_ref, a_ref, b_ref, c_ref, first_ref)
        y_ref[t] = y
        return s

    s = _loop(slots, slot, tuple(s_ref[n] for n in range(n_state)))
    for n in range(n_state):
        s_ref[n] = s[n]


def _backward_kernel(n_state, slots, x_ref, dt_ref, a_ref, b_ref, c_ref, first_ref, kept_ref,
                     dy_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                     g_ref, acc_ref, st_ref, p_ref, q_ref):
    """One grid step's slots backwards for one channel tile (the grid walks
    the steps last to first, the channel tiles inside a step). The step's
    states are made again from the kept one into ``st_ref``; then the walk
    carries the state's cotangent ``g`` (``g_ref`` from step to step), and
    A's cotangent in ``acc_ref``. What B's and C's cotangents sum over the
    channels is added up register by register over the channel tiles in
    ``q_ref`` and ``p_ref`` and folded over the sublanes once a step."""
    step, tile, tiles = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    f32 = jnp.float32

    @pl.when(step == 0)
    def _():
        g_ref[tile] = jnp.zeros(g_ref.shape[1:], f32)
        acc_ref[tile] = jnp.zeros(acc_ref.shape[1:], f32)

    @pl.when(tile == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    st_ref[0] = kept_ref[...]

    def again(t, s):
        s, _ = _slot(n_state, t, s, x_ref, dt_ref, a_ref, b_ref, c_ref, first_ref)
        for n in range(n_state):
            st_ref[t + 1, n] = s[n]
        return s

    _loop(slots, again, tuple(kept_ref[n] for n in range(n_state)))

    def slot(k, carry):
        g, da = carry
        t = slots - 1 - k
        dt, x, dy = dt_ref[t], x_ref[t].astype(f32), dy_ref[t]
        u = dt * x
        keep = jnp.where(first_ref[t] != 0, 0.0, 1.0)
        at, g_out, da_out, du, ddt = t * n_state, [], [], None, None
        for n in range(n_state):
            # pio: lint-ok[mosaic-per-row-dma] whole registers [8, 128] a state index
            a_n = a_ref[n]
            decay = jnp.exp(dt * a_n)
            # pio: lint-ok[mosaic-per-row-dma] a scalar from SMEM
            g_n = dy * c_ref[at + n] + g[n]  # the cotangent of S_t
            p_ref[t, n] += dy * st_ref[t + 1, n]
            q_ref[t, n] += g_n * u
            through = g_n * decay  # of what S_t got from the state before it
            e = through * (st_ref[t, n] * keep)  # of Delta_t A
            du = g_n * b_ref[at + n] if du is None else du + g_n * b_ref[at + n]
            ddt = e * a_n if ddt is None else ddt + e * a_n
            da_out.append(da[n] + e * dt)
            g_out.append(through * keep)
        dx_ref[t] = (du * dt).astype(dx_ref.dtype)
        ddt_ref[t] = ddt + du * x
        return tuple(g_out), tuple(da_out)

    g, da = _loop(slots, slot, (tuple(g_ref[tile, n] for n in range(n_state)),
                                tuple(acc_ref[tile, n] for n in range(n_state))))
    for n in range(n_state):
        g_ref[tile, n] = g[n]
        acc_ref[tile, n] = da[n]
    da_ref[...] = acc_ref[tile]

    @pl.when(tile == tiles - 1)
    def _():
        def fold(t, _):
            # pio: lint-ok[mosaic-per-row-dma] a slot's [N, 8, 128] registers in VMEM, whole lane tiles
            dc_ref[t] = jnp.sum(p_ref[t], axis=1)
            # pio: lint-ok[mosaic-per-row-dma] as above
            db_ref[t] = jnp.sum(q_ref[t], axis=1)

        jax.lax.fori_loop(0, slots, fold, None)


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM),
        interpret=interpret)


def _geometry(x, a):
    bsz, length, tiles, _ = x.shape
    sub = min(tiles, _SUBLANES)
    return bsz, length, tiles, sub, a.shape[0], _slots(length)


@functools.partial(jax.jit, static_argnums=(0,))
def _forward(interpret: bool, x, dt, a, b, c, first):
    """x, dt [B, L, C / 128, 128], a [N, C / 128, 128], b, c [B, L * N],
    first [B, L] -> y as x lies, the state entering every grid step
    [B, steps, N, C / 128, 128]. (A jitted function, as the backward pass
    is: a step calls each at one shape, and the body is traced once.)"""
    bsz, length, tiles, sub, n, slots = _geometry(x, a)
    steps, f32 = length // slots, jnp.float32
    wide = pl.BlockSpec((None, slots, sub, _LANES), lambda r, g, i: (r, i, g, 0))
    flat = pl.BlockSpec((None, slots * n), lambda r, g, i: (r, i), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_forward_kernel, n, slots),
        grid=(bsz, tiles // sub, steps),
        in_specs=[wide, wide, pl.BlockSpec((n, sub, _LANES), lambda r, g, i: (0, g, 0)),
                  flat, flat,
                  pl.BlockSpec((None, slots), lambda r, g, i: (r, i), memory_space=pltpu.SMEM)],
        out_specs=[wide, pl.BlockSpec((None, None, n, sub, _LANES),
                                      lambda r, g, i: (r, i, 0, g, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct((bsz, steps, n, tiles, _LANES), f32)],
        scratch_shapes=[pltpu.VMEM((n, sub, _LANES), f32)],
        **_params(interpret),
    )(x, dt, a, b, c, first)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(interpret: bool, x, dt, a, b, c, first, kept, dy):
    """-> the cotangents of x, dt (as they lie), a [B, N, C / 128, 128] (a
    row's own), and of b and c lane by lane [B, L, N, 128]."""
    bsz, length, tiles, sub, n, slots = _geometry(x, a)
    steps, groups, f32 = length // slots, tiles // sub, jnp.float32
    back = lambda i: steps - 1 - i  # noqa: E731
    wide = pl.BlockSpec((None, slots, sub, _LANES), lambda r, i, g: (r, back(i), g, 0))
    flat = pl.BlockSpec((None, slots * n), lambda r, i, g: (r, back(i)), memory_space=pltpu.SMEM)
    lanes = pl.BlockSpec((None, slots, n, _LANES), lambda r, i, g: (r, back(i), 0, 0))
    return pl.pallas_call(
        functools.partial(_backward_kernel, n, slots),
        grid=(bsz, steps, groups),
        in_specs=[wide, wide, pl.BlockSpec((n, sub, _LANES), lambda r, i, g: (0, g, 0)),
                  flat, flat,
                  pl.BlockSpec((None, slots), lambda r, i, g: (r, back(i)),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, None, n, sub, _LANES),
                               lambda r, i, g: (r, back(i), 0, g, 0)),
                  wide],
        out_specs=[wide, wide,
                   pl.BlockSpec((None, n, sub, _LANES), lambda r, i, g: (r, 0, g, 0)),
                   lanes, lanes],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct((bsz, n, tiles, _LANES), f32),
                   jax.ShapeDtypeStruct((bsz, length, n, _LANES), f32),
                   jax.ShapeDtypeStruct((bsz, length, n, _LANES), f32)],
        scratch_shapes=[pltpu.VMEM((groups, n, sub, _LANES), f32),
                        pltpu.VMEM((groups, n, sub, _LANES), f32),
                        pltpu.VMEM((slots + 1, n, sub, _LANES), f32),
                        pltpu.VMEM((slots, n, sub, _LANES), f32),
                        pltpu.VMEM((slots, n, sub, _LANES), f32)],
        **_params(interpret),
    )(x, dt, a, b, c, first, kept, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(interpret, x, dt, a, b, c, first):
    return _forward(interpret, x, dt, a, b, c, first)[0]


def _walk_fwd(interpret, x, dt, a, b, c, first):
    """(The kernel's two outputs carry the name ``selscan``: a checkpoint
    whose policy keeps that name, as a Mamba-1 layer's does, runs the
    forward kernel once and not again in its recomputation.)"""
    y, kept = (checkpoint_name(t, "selscan") for t in _forward(interpret, x, dt, a, b, c, first))
    return y, (x, dt, a, b, c, first, kept)


def _walk_bwd(interpret, saved, dy):
    dx, ddt, da, db, dc = _backward(interpret, *saved, dy)
    shape = saved[3].shape
    return (dx, ddt, da.sum(0), db.sum(-1).reshape(shape), dc.sum(-1).reshape(shape), None)


_walk.defvjp(_walk_fwd, _walk_bwd)


def _walk_rows(x, dt, a, b, c, seg, interpret: bool):
    """:func:`selective_scan` through the kernel: the channels as [lane
    tiles, 128] so that a slot of a channel tile is whole registers, A with
    the state index first, B and C a row of scalars, a flag on every
    history's first slot."""
    bsz, length, ch = x.shape
    n, f32 = a.shape[-1], jnp.float32
    lay = lambda t: t.reshape(bsz, length, ch // _LANES, _LANES)  # noqa: E731
    y = _walk(interpret, lay(x), lay(dt.astype(f32)),
              a.astype(f32).T.reshape(n, ch // _LANES, _LANES),
              b.astype(f32).reshape(bsz, length * n), c.astype(f32).reshape(bsz, length * n),
              _first(seg).astype(jnp.int32))
    return y.reshape(bsz, length, ch)


def _chain(inner: int) -> Dict:
    """:func:`conv_chain`'s arguments: x~, the wide projection's first ``inner`` columns."""
    return dict(channels=inner, at=0, silu=True)


def forms(shapes: Dict, length: int, *, state: int, state_dtype=jnp.float32,
          gate_dtype=jnp.float32, **_) -> Dict[str, str]:
    """``selective_scan`` (:func:`scan_kind`) and ``conv`` ("pallas" or "xla"):
    what :func:`mamba1` runs over rows of ``length`` slots, ``shapes`` its
    parameters' and the keyword arguments its own."""
    inner = shapes["w_out"][0]
    return {"selective_scan": scan_kind(inner, state, length, state_dtype, gate_dtype),
            "conv": chain_kind(length, shapes["conv_w"][0], **_chain(inner))}


def mamba1(p: Dict, x, seg, *, state: int, dt_rank: int, chunk: int = 64,
           compute_dtype=jnp.float32, state_dtype=jnp.float32,
           gate_dtype=jnp.float32) -> Tuple[jax.Array, Dict]:
    """The mixer of a Mamba-1 layer: x [B, L, D] (normed) -> [B, L, D]
    float32. ``p``, with I the inner width: ``w_in`` [D, 2 I] (the columns
    ``[x~ | z]``), ``conv_w`` [K, I], ``conv_b`` [I], ``w_x`` [I, R + 2 N]
    (the columns ``[delta | B | C]``), ``w_dt`` [R, I], ``dt_bias`` [I],
    ``A_log`` [I, N], ``D`` [I], ``w_out`` [I, D].

    Also returns what the scan was given and what it gave, as this call
    computed them (``c`` [B, L, I], ``dt`` [B, L, I], ``B``, ``C`` [B, L, N],
    ``y`` [B, L, I] the scan's own output), and ``m = y + D * c`` [B, L, I],
    what the gate and the out-projection read and a gated memory unit above
    this layer reads too."""
    inner = p["w_out"].shape[0]
    cd, f32 = compute_dtype, jnp.float32
    with jax.named_scope("seq.mamba.proj"):
        # the wide projection is kept in the compute dtype, as in the other mixers
        xz = jnp.dot(x.astype(cd), p["w_in"].astype(cd), preferred_element_type=f32).astype(cd)
    with jax.named_scope("seq.mamba.conv"):
        c = conv_chain(xz, p["conv_w"], seg, bias=p["conv_b"], **_chain(inner)).astype(cd)
    with jax.named_scope("seq.mamba.proj"):
        dbc = jnp.dot(c, p["w_x"].astype(cd), preferred_element_type=f32)
        b, cc = (dbc[..., dt_rank + i * state: dt_rank + (i + 1) * state] for i in (0, 1))
        dt = jax.nn.softplus(jnp.dot(dbc[..., :dt_rank], p["w_dt"], precision=_HI) + p["dt_bias"])
    with jax.named_scope("seq.mamba.scan"):
        y = selective_scan(c, dt, -jnp.exp(p["A_log"]), b, cc, seg, chunk=chunk,
                           state_dtype=state_dtype, gate_dtype=gate_dtype)
        m = y + p["D"] * c.astype(f32)
    with jax.named_scope("seq.mamba.out"):
        gated = m * jax.nn.silu(xz[..., inner:].astype(f32))
        out = jnp.dot(gated.astype(cd), p["w_out"].astype(cd), preferred_element_type=f32)
    return out, {"c": c, "dt": dt, "B": b, "C": cc, "y": y, "m": m}


def gated_memory(p: Dict, x, m, *, compute_dtype=jnp.float32):
    """A gated memory unit: ``W_2 (m * silu(W_1 x))``, x [B, L, D] (normed),
    ``m`` [B, L, I] what a Mamba-1 layer below handed on (:func:`mamba1`),
    ``p``: ``w_1`` [D, I], ``w_2`` [I, D] -> [B, L, D] float32."""
    cd, f32 = compute_dtype, jnp.float32
    gate = jnp.dot(x.astype(cd), p["w_1"].astype(cd), preferred_element_type=f32)
    return jnp.dot((m.astype(f32) * jax.nn.silu(gate)).astype(cd), p["w_2"].astype(cd),
                   preferred_element_type=f32)
