"""The gated short convolution: a mixer whose only memory is the last few
slots of its own history.

Per slot, with ``u`` the normed residual stream::

    [B | C | x~] = u W_in;  z = B * x~;  c_t = sum_j k_j * z_(t-j);  out = (C * c) W_out

The convolution is depthwise and causal over ``K`` taps (``conv_L_cache``);
the two gates are the nonlinearity, there is no activation function.

Packed rows: ``seg`` gives each slot the id of its history; a tap that
would reach into the neighbouring history reads zero.

The chain around the taps (:func:`conv_chain`) is shared: the gated-DeltaNet
mixer runs four taps and a SiLU over its q, k, v, the Mamba-2 mixer four
taps, a bias and a SiLU over its ``[x | B | C]``, this mixer three taps
between its two gates. Where the backend is a TPU, the channels are whole
lane tiles and the arithmetic is float32 (:func:`conv_kind`) the chain is
ONE Pallas kernel each way: a tile of ``[slots, channels]`` is read once
from the wide projection the caller holds (by block index: no slice or cast
is copied out first), the ``taps - 1`` slots before it from a halo block,
and written once; the backward pass recomputes the pre-activation from the
inputs, the only residuals, and sums the taps' and the bias's cotangents in
VMEM. Inside a tile the kernels walk lane tile by lane tile and strip by
strip of 64 slots in loops, so a strip's chain of elementwise operations
stays in registers and the body is traced, lowered and compiled once
whatever the tile's shape. Everywhere else the same float32 operations run
as XLA's (:func:`causal_conv`).

Precision: the gates and the taps (``B * x~``, the sum over the taps,
``C * c``) are ``gate_dtype`` (float32); the two projections take
``compute_dtype`` inputs (bfloat16 on the chip) and accumulate in float32,
and ``[B | C | x~]`` is kept in ``compute_dtype`` between them, as the other
mixers keep their wide projections.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_HALO = 16  # slots of a halo block: a bfloat16 tile's sublanes; the nearest 8 are staged
_NEAR = 8  # a float32 tile's sublanes: the halo slots kept beside a tile; taps + 1 <= 8
_STRIP = 64  # slots the kernels work on at a time (the best of 16 … 256 on the chip: PERF.md section 6)
_TILE = 512 * 1024  # elements of one operand's tile
_WIDEST = 1024  # lanes of a tile where the channels allow a choice
_VMEM = 64 * 2**20


def causal_conv(x, w, seg):
    """Depthwise causal convolution, x [B, L, C], w [K, C] (tap K-1 is the
    current slot), seg [B, L]: a tap in another history reads zero."""
    taps, length = w.shape[0], x.shape[1]
    out = x * w[taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
        same = jnp.pad(seg, ((0, 0), (back, 0)), constant_values=-1)[:, :length] == seg
        out = out + jnp.where(same[..., None], shifted, 0) * w[taps - 1 - back]
    return out


class _Chain(NamedTuple):
    """What of the chain is no array: where in the wide array the
    convolution's input and the two gates start (``None``: no such gate),
    how many channels each has, whether a SiLU follows the taps."""
    channels: int
    at: int = 0
    gate_in: Optional[int] = None
    gate_out: Optional[int] = None
    silu: bool = False
    interpret: bool = False

    @property
    def parts(self) -> Tuple[int, ...]:
        """The offsets that are read (and, backwards, written), in the
        wide array's order."""
        return tuple(sorted(o for o in (self.at, self.gate_in, self.gate_out) if o is not None))


def conv_kind(channels: int, length: int, dtype, offsets=(0,), interpret: bool = False,
              taps: int = 4) -> str:
    """What runs the chain of :func:`conv_chain` at these shapes: "pallas"
    where the channels and their ``offsets`` in the wide array are whole
    lane tiles, a row is whole halo blocks, the taps and their bias fit a
    register's eight rows, the arithmetic is float32 and the backend is a
    TPU (``interpret``: or the kernel's interpreter, for tests), "xla"
    otherwise."""
    parts = sorted(offsets)
    tiles = (all(n % _LANES == 0 for n in (channels, *parts)) and length % _HALO == 0
             and taps < _NEAR
             # the parts are neighbours, and blocks of their own width where there are several
             and all(b - a == channels for a, b in zip(parts, parts[1:]))
             and (len(parts) == 1 or parts[0] % channels == 0))
    f32 = jnp.dtype(dtype) == jnp.float32
    return "pallas" if tiles and f32 and (interpret or jax.default_backend() == "tpu") else "xla"


def chain_kind(length: int, taps: int, *, dtype=jnp.float32, **chain) -> str:
    """:func:`conv_kind` of what :func:`conv_chain` runs with the keyword
    arguments ``chain`` (and ``dtype``) and ``taps`` taps over rows of ``length``
    slots: what the chain asks itself, and what a mixer's ``forms`` asks for it."""
    spec = _Chain(**chain)
    return conv_kind(spec.channels, length, dtype, spec.parts, spec.interpret, taps)


def conv_chain(src, w, seg, *, channels: int, at: int = 0, bias=None,
               gate_in: Optional[int] = None, gate_out: Optional[int] = None,
               silu: bool = False, dtype=jnp.float32, interpret: bool = False):
    """``g_out * act(conv(g_in * x) + bias)`` in ``dtype``, [B, L, channels]:
    x, g_in and g_out are the ``channels`` columns of ``src`` [B, L, W] from
    ``at``, ``gate_in`` and ``gate_out`` on (a gate that is ``None`` is
    left out, as are a ``bias`` [channels] that is ``None`` and the SiLU);
    w [K, channels], seg [B, L]. One kernel or XLA's chain: :func:`conv_kind`."""
    spec = _Chain(channels, at, gate_in, gate_out, silu, interpret)
    if chain_kind(src.shape[1], w.shape[0], dtype=dtype, **spec._asdict()) == "pallas":
        return _chain(spec, src, w.astype(dtype), bias, seg)

    def part(start):
        return src[..., start:start + channels].astype(dtype)

    x = part(at) if gate_in is None else part(gate_in) * part(at)
    y = causal_conv(x, w.astype(dtype), seg)
    if bias is not None:
        y = y + bias
    if silu:
        y = jax.nn.silu(y)
    return y if gate_out is None else part(gate_out) * y


def _tile(spec: _Chain, length: int, wide: bool) -> Tuple[int, int]:
    """Slots and lanes of a tile: the lanes divide the channels and every
    offset (``wide``: they are all the channels, so that the parts'
    cotangents lie side by side in one block); the slots divide the row."""
    lanes = spec.channels if wide else max(
        n for n in range(_LANES, min(spec.channels, _WIDEST) + 1, _LANES)
        if all(m % n == 0 for m in (spec.channels, *spec.parts)))
    rows = max(_HALO << n for n in range(8)
               if length % (_HALO << n) == 0 and ((_HALO << n) * lanes <= _TILE or n == 0))
    return rows, lanes


def _blocks(name: str, array, rows: int, lanes: int, height: int, sides, first: int = 0,
            across: bool = True):
    """(name, array, BlockSpec) of a tile of ``array`` [B, L, W] over a grid
    of (row, slot tile, channel tile), and of the ``height`` slots before it
    (side "prev", clamped at the row's first block) or after it ("next", at
    its last); ``first``: the channel block the part starts at; ``across``
    false: the same lanes for every channel tile."""
    per, blocks = rows // height, array.shape[1] // height
    near = {"prev": lambda i: jnp.maximum(i * per - 1, 0),
            "next": lambda i: jnp.minimum((i + 1) * per, blocks - 1)}
    lane = (lambda c: first + c) if across else (lambda c: 0)
    found = [(name, array, pl.BlockSpec((None, rows, lanes), lambda b, i, c: (b, i, lane(c))))]
    for side in sides:
        found.append((f"{name}_{side}", array, pl.BlockSpec(
            (None, height, lanes), lambda b, i, c, at=near[side]: (b, at(i), lane(c)))))
    return found


def _operands(spec: _Chain, src, seg, rows: int, lanes: int, sides):
    """The wide array as the kernels read it: every part's tile and halo
    blocks, then the ids of their slots, a lane tile wide."""
    found = []
    for name, start in (("x", spec.at), ("g_in", spec.gate_in), ("g_out", spec.gate_out)):
        if start is not None:
            found += _blocks(name, src, rows, lanes, _HALO, sides, start // lanes)
    ids = jnp.broadcast_to(seg[..., None].astype(jnp.int32), seg.shape + (_LANES,))
    return found + _blocks("seg", ids, rows, _LANES, _NEAR, sides, across=False)


def _ids(r: Dict, ids_ref):
    """The ids of the staged slots: the halo before the tile, the tile, the
    halo after it where the kernel has one."""
    rows = r["seg"].shape[0]
    ids_ref[:_NEAR] = r["seg_prev"][...]
    ids_ref[_NEAR:_NEAR + rows] = r["seg"][...]
    if "seg_next" in r:
        ids_ref[_NEAR + rows:] = r["seg_next"][...]


def _z(r: Dict, spec: _Chain, side: str, at, k: slice):
    """``z = g_in * x`` in float32, one lane tile of the slots ``at`` of the
    tile (``side`` ""), of the halo block before it ("_prev") or after."""
    z = r["x" + side][at, k].astype(jnp.float32)
    return z if spec.gate_in is None else r["g_in" + side][at, k].astype(jnp.float32) * z


def _halos(r: Dict, spec: _Chain, z_ref, rows: int, first, k):
    """The nearest slots of the halo blocks into ``z_ref`` (one lane tile of
    the staged slots), around where the tile goes: zero before a row's first
    slot (``first``: the tile is the row's first), as the XLA form pads."""
    z_ref[:_NEAR] = jnp.where(first, 0.0, _z(r, spec, "_prev", slice(None), k)[_HALO - _NEAR:])
    if "x_next" in r:
        z_ref[_NEAR + rows:] = _z(r, spec, "_next", slice(None), k)[:_NEAR]


def _taps(r: Dict, z_ref, ids_ref, taps: int, start, n: int, k: slice):
    """The convolution (and the bias) of the ``n`` slots from ``start`` on
    that ``z_ref`` stages, lane tile ``k``: the float32 operations of
    :func:`causal_conv` in its order. Also returns each tap's masked input,
    newest first."""
    here = ids_ref[pl.ds(_NEAR + start, n)]
    read = [z_ref[pl.ds(_NEAR + start, n)]]
    out = read[0] * r["w"][taps - 1:taps, k]
    for back in range(1, taps):
        same = ids_ref[pl.ds(_NEAR + start - back, n)] == here
        read.append(jnp.where(same, z_ref[pl.ds(_NEAR + start - back, n)], 0.0))
        out = out + read[back] * r["w"][taps - 1 - back:taps - back, k]
    if "bias" in r:
        out = out + r["bias"][:, k]
    return out, read


def _lane_tiles(lanes: int, body):
    """``body(k, first)`` for every lane tile of a tile: ``k`` its lanes in a
    block that starts where the tile does, ``first`` its first lane. A loop
    and not ``lanes / 128`` copies of the body: the body is traced, lowered
    and compiled once whatever the tile's width."""
    def tile(n, _):
        first = pl.multiple_of(n * _LANES, _LANES)
        body(pl.ds(first, _LANES), first)

    if lanes == _LANES:  # (a loop of one step is a call the interpreter does not know)
        body(slice(0, _LANES), 0)
    else:
        jax.lax.fori_loop(0, lanes // _LANES, tile, None)


def _strips(rows: int, size: int, body, carry=None):
    """``body(start, carry)`` over the tile's strips of ``size`` slots: a
    strip's arrays are a few registers each, so a chain of elementwise
    operations stays in them."""
    return jax.lax.fori_loop(
        0, rows // size, lambda j, c: body(pl.multiple_of(j * size, size), c), carry)


def _forward_kernel(spec: _Chain, names, taps, rows, lanes, *refs):
    r = dict(zip(names, refs))
    y_ref, z_ref, ids_ref = refs[len(names):]
    first_tile = pl.program_id(1) == 0  # read here: the interpreter knows no grid inside a loop
    size = min(_STRIP, rows)
    _ids(r, ids_ref)

    def lane_tile(k, _):
        _halos(r, spec, z_ref, rows, first_tile, k)

        def strip(start, _):
            at = pl.ds(start, size)
            z_ref[pl.ds(_NEAR + start, size)] = _z(r, spec, "", at, k)
            y, _ = _taps(r, z_ref, ids_ref, taps, start, size, k)
            if spec.silu:
                y = jax.nn.silu(y)
            if spec.gate_out is not None:
                y = r["g_out"][at, k].astype(jnp.float32) * y
            y_ref[at, k] = y

        _strips(rows, size, strip)

    _lane_tiles(lanes, lane_tile)


def _weights(w, bias, lanes: int):
    found = [("w", w, pl.BlockSpec((w.shape[0], lanes), lambda b, i, c: (0, c)))]
    if bias is not None:
        found.append((
            "bias", bias.astype(jnp.float32)[None],
            # pio: lint-ok[mosaic-blockspec-tiling] the bias is a [1, C] array: a block of 1 row is its whole first dimension, which a block may be
            pl.BlockSpec((1, lanes), lambda b, i, c: (0, c))))
    return found


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM),
        interpret=interpret)


@functools.partial(jax.jit, static_argnums=(0,))
def _forward(spec: _Chain, src, w, bias, seg):
    """(A jitted function, as the backward pass is: a step calls each many
    times at one shape, and the kernel's body is traced and lowered once.)"""
    (bsz, length, _), taps = src.shape, w.shape[0]
    rows, lanes = _tile(spec, length, False)
    operands = _operands(spec, src, seg, rows, lanes, ("prev",)) + _weights(w, bias, lanes)
    names = tuple(name for name, _, _ in operands)
    return pl.pallas_call(
        functools.partial(_forward_kernel, spec, names, taps, rows, lanes),
        grid=(bsz, length // rows, spec.channels // lanes),
        in_specs=[block for _, _, block in operands],
        out_specs=pl.BlockSpec((None, rows, lanes), lambda b, i, c: (b, i, c)),
        out_shape=jax.ShapeDtypeStruct((bsz, length, spec.channels), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_NEAR + rows, _LANES), jnp.float32),
                        pltpu.VMEM((_NEAR + rows, _LANES), jnp.int32)],
        **_params(spec.interpret),
    )(*(array for _, array, _ in operands))


def _backward_kernel(spec: _Chain, names, taps, rows, lanes, *refs):
    """One tile's cotangents. The pre-activation is made again for the tile
    and for the ``taps - 1`` slots after it, whose cotangents reach back
    into the tile; the taps' and the bias's cotangents are summed over the
    grid into one block that stays in VMEM."""
    r = dict(zip(names, refs))
    d_ref, dw_ref, z_ref, ids_ref, dpre_ref = refs[len(names):]
    f32 = jnp.float32
    # read here: the interpreter knows no grid inside a loop
    tile, first_tile = pl.program_id(2), pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    size = min(_STRIP, rows)
    place = {start: n * lanes for n, start in enumerate(spec.parts)}  # in the block of cotangents

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (tile == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def d_pre(start, n, side, at, k):
        """The cotangent of the taps' sum over ``n`` staged slots (``at`` in
        their own block), the taps' inputs there, the output gate's cotangent."""
        pre, read = _taps(r, z_ref, ids_ref, taps, start, n, k)
        d_act = r["dy" + side][at, k][:n]
        d_gate = None
        if spec.silu:
            s = jax.nn.sigmoid(pre)
            act, slope = pre * s, s * (1.0 + pre * (1.0 - s))
        else:
            act, slope = pre, None
        if spec.gate_out is not None:
            d_gate, d_act = d_act * act, d_act * r["g_out" + side][at, k].astype(f32)[:n]
        return (d_act if slope is None else d_act * slope), read, d_gate

    def folded(a):  # a strip [size, 128] -> [_NEAR, 128]: sums of whole registers
        return sum(a[n:n + _NEAR] for n in range(0, size, _NEAR))

    _ids(r, ids_ref)

    def lane_tile(k, first):
        _halos(r, spec, z_ref, rows, first_tile, k)

        def into(part):  # the part's lanes of this lane tile in the block of cotangents
            return pl.ds(pl.multiple_of(place[part] + first, _LANES), _LANES)

        def through(start, sums):
            """The taps' sum again and its cotangent, strip by strip."""
            at = pl.ds(start, size)
            z_ref[pl.ds(_NEAR + start, size)] = _z(r, spec, "", at, k)
            d, read, d_gate = d_pre(start, size, "", at, k)
            dpre_ref[at] = d
            if d_gate is not None:
                d_ref[at, into(spec.gate_out)] = d_gate.astype(d_ref.dtype)
            return tuple(acc + folded(d * z) for acc, z in zip(sums, read + [1.0]))

        sums = _strips(rows, size, through, (jnp.zeros((_NEAR, _LANES), f32),) * (taps + 1))
        # tap K-1 is the current slot; the row after the taps is the bias's
        for row, acc in zip((*range(taps - 1, -1, -1), taps), sums):
            dw_ref[tile, row:row + 1, k] += jnp.sum(acc, axis=0, keepdims=True)
        dpre_ref[rows:] = jnp.where(last, 0.0, d_pre(rows, _NEAR, "_next", slice(None), k)[0])

        def back_into(start, _):
            """What the cotangents of the slots from here on give this strip's z."""
            at = pl.ds(start, size)
            here = ids_ref[pl.ds(_NEAR + start, size)]
            dz = dpre_ref[at] * r["w"][taps - 1:taps, k]
            for back in range(1, taps):
                same = ids_ref[pl.ds(_NEAR + start + back, size)] == here
                dz = dz + jnp.where(same, dpre_ref[pl.ds(start + back, size)], 0.0) * r["w"][
                    taps - 1 - back:taps - back, k]
            gave = {spec.at: dz}
            if spec.gate_in is not None:
                gave = {spec.at: dz * r["g_in"][at, k].astype(f32),
                        spec.gate_in: dz * r["x"][at, k].astype(f32)}
            for part, d in gave.items():
                d_ref[at, into(part)] = d.astype(d_ref.dtype)

        _strips(rows, size, back_into)

    _lane_tiles(lanes, lane_tile)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(spec: _Chain, src, w, bias, seg, dy):
    """-> the cotangents of the parts of ``src`` side by side in its order
    [B, L, parts * channels], of w and of the bias."""
    (bsz, length, _), taps = src.shape, w.shape[0]
    order = spec.parts
    rows, lanes = _tile(spec, length, len(order) > 1)
    tiles = spec.channels // lanes
    operands = (_operands(spec, src, seg, rows, lanes, ("prev", "next"))
                + _weights(w, bias, lanes) + _blocks("dy", dy, rows, lanes, _NEAR, ("next",)))
    names = tuple(name for name, _, _ in operands)
    d_parts, d_taps = pl.pallas_call(
        functools.partial(_backward_kernel, spec, names, taps, rows, lanes),
        grid=(bsz, length // rows, tiles),
        in_specs=[block for _, _, block in operands],
        out_specs=[pl.BlockSpec((None, rows, len(order) * lanes), lambda b, i, c: (b, i, c)),
                   pl.BlockSpec((tiles, _NEAR, lanes), lambda b, i, c: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bsz, length, len(order) * spec.channels), src.dtype),
                   jax.ShapeDtypeStruct((tiles, _NEAR, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2 * _NEAR + rows, _LANES), jnp.float32),
                        pltpu.VMEM((2 * _NEAR + rows, _LANES), jnp.int32),
                        pltpu.VMEM((rows + _NEAR, _LANES), jnp.float32)],
        **_params(spec.interpret),
    )(*(array for _, array, _ in operands))
    d_taps = jnp.moveaxis(d_taps, 0, 1).reshape(_NEAR, spec.channels)
    return d_parts, d_taps[:taps], d_taps[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chain(spec: _Chain, src, w, bias, seg):
    return _forward(spec, src, w, bias, seg)


def _chain_fwd(spec, src, w, bias, seg):
    return _forward(spec, src, w, bias, seg), (src, w, bias, seg)


def _chain_bwd(spec, kept, dy):
    src, w, bias, seg = kept
    d_parts, d_w, d_bias = _backward(spec, src, w, bias, seg, dy)
    # the parts are neighbours in the wide array: what lies beside them gets zero
    first = spec.parts[0]
    beside = (first, src.shape[-1] - first - d_parts.shape[-1])
    d_src = d_parts if beside == (0, 0) else jnp.pad(d_parts, ((0, 0), (0, 0), beside))
    return d_src, d_w, (None if bias is None else d_bias.astype(bias.dtype)), None


_chain.defvjp(_chain_fwd, _chain_bwd)


def _gated(width: int, gate_dtype) -> Dict:
    """:func:`conv_chain`'s arguments for [B | C | x~], ``width`` columns."""
    d = width // 3
    return dict(channels=d, at=2 * d, gate_in=0, gate_out=d, dtype=gate_dtype)


def gated_conv(bcx, conv_w, seg, gate_dtype=jnp.float32, interpret: bool = False):
    """``C * conv(B * x~)`` of ``bcx`` = [B | C | x~] [rows, L, 3 D], in
    ``gate_dtype``; recomputed in the backward pass from ``bcx`` alone."""
    @jax.checkpoint
    def chain(bcx, conv_w):
        with jax.named_scope("seq.shortconv.conv"):
            return conv_chain(bcx, conv_w, seg, interpret=interpret,
                              **_gated(bcx.shape[-1], gate_dtype))

    return chain(bcx, conv_w)


def forms(shapes: Dict, length: int, *, gate_dtype=jnp.float32, **_) -> Dict[str, str]:
    """``conv``: what runs the chain of :func:`short_conv` ("pallas" or "xla")
    over rows of ``length`` slots, ``shapes`` its parameters' and the keyword
    arguments its own."""
    return {"conv": chain_kind(length, shapes["conv_w"][0],
                               **_gated(shapes["w_in"][1], gate_dtype))}


def short_conv(p: Dict, x, seg, *, compute_dtype=jnp.float32,
               gate_dtype=jnp.float32) -> Tuple[jax.Array, Dict]:
    """The mixer of a gated short-convolution layer: x [B, L, D] (normed)
    -> [B, L, D] float32. ``p``: ``w_in`` [D, 3 D], ``conv_w`` [K, D],
    ``w_out`` [D, D]. Also returns what the gate-taps-gate chain was given
    and what it gave, as this call computed them (``bcx`` [B, L, 3 D] in
    ``compute_dtype``, ``y`` [B, L, D] in ``gate_dtype``): a caller that
    holds the chain against a reference reads them."""
    cd, f32 = compute_dtype, jnp.float32
    with jax.named_scope("seq.shortconv.proj"):
        bcx = jnp.dot(x.astype(cd), p["w_in"].astype(cd), preferred_element_type=f32).astype(cd)
    y = gated_conv(bcx, p["conv_w"], seg, gate_dtype)
    with jax.named_scope("seq.shortconv.out"):
        out = jnp.dot(y.astype(cd), p["w_out"].astype(cd), preferred_element_type=f32)
    return out, {"bcx": bcx, "y": y}
