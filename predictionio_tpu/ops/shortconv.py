"""The gated short convolution: a mixer whose only memory is the last few
slots of its own history.

Per slot, with ``u`` the normed residual stream::

    [B | C | x~] = u W_in;  z = B * x~;  c_t = sum_j k_j * z_(t-j);  out = (C * c) W_out

The convolution is depthwise and causal over ``K`` taps (``conv_L_cache``);
the two gates are the nonlinearity, there is no activation function.

Packed rows: ``seg`` gives each slot the id of its history; a tap that
would reach into the neighbouring history reads zero
(:func:`causal_conv`, which the gated-DeltaNet mixer runs at four taps too).

Precision: the gates and the taps (``B * x~``, the sum over the taps,
``C * c``) are ``gate_dtype`` (float32); the two projections take
``compute_dtype`` inputs (bfloat16 on the chip) and accumulate in float32,
and ``[B | C | x~]`` is kept in ``compute_dtype`` between them, as the other
mixers keep their wide projections.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


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


def gated_conv(bcx, conv_w, seg, gate_dtype=jnp.float32):
    """``C * conv(B * x~)`` of ``bcx`` = [B | C | x~] [rows, L, 3 D], in
    ``gate_dtype``; recomputed in the backward pass from ``bcx`` alone."""

    @jax.checkpoint
    def chain(bcx, conv_w):
        with jax.named_scope("seq.shortconv.conv"):
            b, c, x = jnp.split(bcx.astype(gate_dtype), 3, axis=-1)
            return c * causal_conv(b * x, conv_w.astype(gate_dtype), seg)

    return chain(bcx, conv_w)


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
