"""The least work one optimizer step of the sequence backbone needs when
its layers are gated short convolutions beside grouped-query attention and
its sparse layers have no shared expert (``layer_types`` says which layer
is which), counted from the configuration and the shapes of a run. Forward
plus backward is three times the forward's products (recomputed layers do
not count), so a share above 100 % is a wrong count here, not a fast
program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2,
``held`` = assignments that fell on this chip's experts in that step, one
number per sparse layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32, moe_experts


def _layers(cfg: Dict, kind: str) -> int:
    return cfg["layer_types"].count(kind)


def shortconv_chain(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The gate-taps-gate chain of all convolution layers, ``C * conv(B *
    x~)``: per slot and channel one product for each gate and a product and
    a sum for each tap. Bytes, the bound: ``[B | C | x~]`` read and the
    product written once forward; they and their cotangents once backward;
    all in bfloat16, what the program keeps between its projections."""
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    per_slot = _layers(cfg, "conv") * shape["tokens"] * d
    return 3.0 * per_slot * (2.0 * taps + 1.0), per_slot * (4.0 + 8.0) * BF16


def gqa_core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """Scores and values of all attention layers: per causal pair inside a
    history and query head one product over the head for the score and one
    for the value. Bytes: q and o of every query head, k and v of every
    key/value head once in bfloat16, and their four cotangents once."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    layers = _layers(cfg, "full_attention")
    flops = 3.0 * layers * 2.0 * shape["pair_sum"] * h * (hd + hd)
    hbm = layers * shape["tokens"] * 2.0 * (2 * h * hd + 2 * hkv * hd) * BF16
    return flops, hbm


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token (the convolution mixers' two projections, the
    attention's four, the leading layers' SwiGLU, the router of every sparse
    layer, one pass over the tied head), the attention cores, the
    convolutions' chains, the held assignments; times three for the backward
    pass. Bytes: AdamW's own traffic, the least a step can move."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    leading = cfg["num_dense_layers"]
    dense = (_layers(cfg, "conv") * (d * 3 * d + d * d)
             + _layers(cfg, "full_attention") * (2 * d * h * hd + 2 * d * hkv * hd)
             + leading * 3 * d * cfg["intermediate_size"]
             + (cfg["num_hidden_layers"] - leading) * d * cfg["router_width"]
             + cfg["vocab_size"] * d)
    flops = 3.0 * 2.0 * shape["tokens"] * dense
    flops += gqa_core(cfg, shape)[0] + shortconv_chain(cfg, shape)[0] + moe_experts(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
