"""Mamba-2: a state-space mixer whose state is a matrix per head, decayed by
a scalar a head and slot and written by an outer product.

Per head, with state ``S`` [P, N] (head width x state width), zero entering
a history's first slot, ``A = -exp(A_log)`` and ``dt`` the softplus'd step::

    S_t = exp(dt_t A) S_(t-1) + dt_t u_t (x) B_t;   y_t = S_t C_t

``B_t`` and ``C_t`` [N] are shared by all heads (one group). No correction
of the state by what it already holds: no triangular system, unlike the
delta rule (:mod:`.deltanet`).

:func:`ssd_scan` computes that in chunks of ``chunk`` slots with no loop
over slots and none over chunks (the state-space duality form), in three
phases after a preparation (``seq.ssm.scan.prep``: the layouts into chunks,
``dt u`` and the running sums). Local (``seq.ssm.scan.local``): inside a
chunk slot i reads slot j <= i of its own history through ``exp(cum_i - cum_j) * (C_i . B_j)``, cum
the running sum of ``dt A`` inside the chunk; the scores ``C B^T`` are made
once for all heads, the decay matrix a head, and the result is one batch
product with ``dt u``. The decay is always the exponential of a DIFFERENCE
(never ``exp(cum_i) * exp(-cum_j)``), so nothing overflows however fast a
head forgets. State (``seq.ssm.scan.state``): every chunk's own
contribution to the state at its last slot, one batch product; then every
chunk's incoming state as the decayed sum of the contributions before it,
a product with a [chunks, chunks] matrix a head whose entry is zero where a
history ended in between. Out (``seq.ssm.scan.out``): what the incoming
state adds to the slots of the history it belongs to.

Packed rows: ``seg`` gives each slot the id of its history (one contiguous
run per id). The first slot of a history starts from a zero state: every
mask above compares ids, so a chunk may hold any number of boundaries, on
its first slot, its last, or anywhere between.

:func:`mamba2` is the mixer around the scan: in-projection to ``[z | x B
C]`` and ``dt``, a depthwise causal convolution with a bias over ``x B C``
(a tap in another history reads zero: :func:`.shortconv.conv_chain`),
SiLU, the scan, the skip ``D * u``, the gated RMS norm ``rms(y *
silu(z)) * w`` over the whole inner width, out-projection.

Everything is differentiated as it stands; the local phase is recomputed
in the backward pass from what it was given (its [C, C] matrices a head and
chunk are the mixer's largest arrays).

Precision: ``dt``, ``dt A`` and its running sums are ``gate_dtype``
(float32: they feed exponentials), the state ``state_dtype`` (float32) and
read as float32 at ``Precision.HIGHEST`` (:func:`.deltanet._with_state`);
the other products take ``compute_dtype`` inputs (bfloat16 on the chip) and
accumulate in float32.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .deltanet import _HI, _with_state
from .shortconv import conv_chain


def _segsum(x):
    """x [..., T] -> [..., T, T]: entry (i, m) is ``sum_(k = m+1 .. i)
    x_k`` for i >= m, each sum made of its own terms alone (no difference
    of running sums that grow with T); 0 above the diagonal."""
    t = x.shape[-1]
    rows = jnp.broadcast_to(x[..., :, None], x.shape + (t,))  # [.., k, m] = x_k
    below = jnp.tril(jnp.ones((t, t), bool), -1)  # k > m
    return jnp.cumsum(jnp.where(below, rows, 0), axis=-2)


def ssd_scan(u, dt, a, b, c, seg, *, chunk: int = 256, compute_dtype=jnp.float32,
             state_dtype=jnp.float32, gate_dtype=jnp.float32):
    """u [B, L, H, P], dt [B, L, H] (after the softplus), a [H] (negative),
    b, c [B, L, N], seg [B, L] -> y [B, L, H, P] float32 (without the skip
    ``D * u``)."""
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
        uc = u.reshape(bsz, n, chunk, heads, p)
        bc, cc = (t.astype(cd).reshape(bsz, n, chunk, -1) for t in (b, c))
        dtc = dt.astype(gate_dtype).reshape(bsz, n, chunk, heads)
        # log decay a slot, and its running sum inside the chunk (inclusive), [B, n, H, C]
        cum = jnp.cumsum(jnp.moveaxis(dtc * a.astype(gate_dtype), 2, 3), axis=-1)
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
            return jnp.einsum("bnhij,bnjhp->bnihp", m, x.astype(cd), preferred_element_type=f32)

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
        whole = (breaks[..., :, None] == breaks[..., None, :]) & jnp.tril(jnp.ones((n, n), bool))
        total = jnp.moveaxis(cum[..., -1], 1, 2)  # [B, H, n]: a chunk's whole log decay
        carry = jnp.exp(jnp.where(whole[:, None], _segsum(total).astype(f32), -jnp.inf))
        after = _with_state("bhim,bmhps->bihps", carry, wrote).astype(state_dtype)
        incoming = jnp.pad(after[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)  # [B, n, H, P, N]

    with jax.named_scope("seq.ssm.scan.out"):
        carried = (sc == prev_last[..., None])[:, :, None]  # [B, n, 1, C]: sees the incoming state
        e_in = jnp.exp(jnp.where(carried, cum.astype(f32), -jnp.inf))  # [B, n, H, C]
        read = _with_state("bnis,bnhps->bnihp", cc, incoming)
        y = y + read * jnp.moveaxis(e_in, 2, 3)[..., None]
    return y.reshape(bsz, length + pad, heads, p)[:, :length]


def scan_kind() -> str:
    """What implements :func:`ssd_scan`: "xla" (batch products the compiler
    schedules; there is no Pallas walk yet)."""
    return "xla"


def mamba2(p: Dict, x, seg, *, heads: int, head_dim: int, state: int, eps: float,
           chunk: int = 256, compute_dtype=jnp.float32, state_dtype=jnp.float32,
           gate_dtype=jnp.float32) -> Tuple[jax.Array, Dict]:
    """The mixer of a Mamba-2 layer: x [B, L, D] (normed) -> [B, L, D]
    float32. ``p``, with I = heads * head_dim: ``w_in`` [D, 2 I + 2 N] (the
    columns ``[z | x | B | C]``), ``w_dt`` [D, H] (the published in-projection's
    last H columns, a leaf of their own: they feed an exponential and are
    multiplied in float32), ``conv_w`` [K, I + 2 N], ``conv_b`` [I + 2 N],
    ``A_log``, ``dt_bias``, ``D`` [H], ``norm`` [I], ``w_out`` [I, D].

    Also returns what the scan was given and what it gave, as this call
    computed them (``u`` [B, L, H, P], ``B``, ``C`` [B, L, N], ``dt`` [B, L,
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
        xbc = conv_chain(zxbc, p["conv_w"], seg, channels=zxbc.shape[-1] - inner, at=inner,
                         bias=p["conv_b"], silu=True)
        u = xbc[..., :inner].reshape(bsz, length, heads, head_dim).astype(cd)
        b, c = (xbc[..., inner + i * state: inner + (i + 1) * state].astype(cd) for i in (0, 1))
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    with jax.named_scope("seq.ssm.scan"):
        y = ssd_scan(u, dt, -jnp.exp(p["A_log"]), b, c, seg, chunk=chunk, compute_dtype=cd,
                     state_dtype=state_dtype, gate_dtype=gate_dtype)
    with jax.named_scope("seq.ssm.norm"):
        skipped = y + p["D"][:, None] * u.astype(f32)
        gated = skipped.reshape(bsz, length, inner) * jax.nn.silu(zxbc[..., :inner].astype(f32))
        o = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True) + eps) * p["norm"]
    with jax.named_scope("seq.ssm.out"):
        out = jnp.dot(o.astype(cd), p["w_out"].astype(cd), preferred_element_type=f32)
    return out, {"u": u, "B": b, "C": c, "dt": dt, "y": y}
