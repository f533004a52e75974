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

:func:`selective_scan` walks a row in blocks of ``block`` slots, the state
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
channel forgets.

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

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .deltanet import _HI
from .shortconv import conv_chain

#: slots of a block: what the backward pass keeps of one is its slots' states,
#: block x C x N floats (168 MB at 512 x 5,120 x 16)
BLOCK = 512


def selective_scan(x, dt, a, b, c, seg, *, chunk: int = 64, block: int = BLOCK,
                   state_dtype=jnp.float32, gate_dtype=jnp.float32):
    """x [B, L, C], dt [B, L, C] (after the softplus), a [C, N] (negative),
    b, c [B, L, N], seg [B, L] -> y [B, L, C] float32 (without the skip
    ``D * x``)."""
    bsz, length, ch = x.shape
    n = a.shape[-1]
    chunk = min(chunk, length)
    block = max(chunk, min(block, length) // chunk * chunk)
    pad = -length % block
    if pad:  # slots of a history of their own, which write nothing (dt 0, x 0)
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt, b, c))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    blocks, k = (length + pad) // block, block // chunk
    first = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
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


def scan_kind() -> str:
    """What implements :func:`selective_scan`: "xla" (loops over a chunk's
    slots and a row's blocks that the compiler schedules; there is no Pallas
    walk yet)."""
    return "xla"


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
        c = conv_chain(xz, p["conv_w"], seg, channels=inner, at=0, bias=p["conv_b"],
                       silu=True).astype(cd)
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
