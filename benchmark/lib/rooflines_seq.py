"""The least work one optimizer step of the sequence backbone needs,
counted from the configuration and the shapes of a run: floating-point
operations and bytes to and from HBM. Forward plus backward is three times
the forward's products (recomputed layers do not count), so a share above
100 % is a wrong count here, not a fast program.

``shape``: ``tokens`` in a step, ``pair_sum`` = sum over the step's
histories of len * (len + 1) / 2 (the causal pairs attention has to score),
``held`` = assignments that fell on this chip's experts in that step, one
number per layer (the router moves during a job, so a job is counted step
by step: ``readers/seq_roofline.py``).
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16, F32 = 2.0, 4.0


def deltanet_scan(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The delta rule of all DeltaNet layers, as the token-by-token
    recurrence counts it: per token and value head three products of a
    vector with the [dk, dv] state (S^T k, k u^T, S^T q), 6 dk dv. Bytes:
    q, k (key heads) and v read in bfloat16, o written in float32, gates
    read; the backward pass reads those and the cotangent of o and writes
    three cotangents."""
    layers = cfg["num_hidden_layers"] - cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    t = shape["tokens"]
    flops = 3.0 * layers * t * hv * 6.0 * dk * dv
    qkv = 2 * hk * dk * BF16 + hv * dv * BF16
    forward = qkv + hv * dv * F32 + 2 * hv * F32
    backward = forward + hv * dv * F32 + qkv
    return flops, layers * t * (forward + backward)


def moe_experts(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The grouped products of the held experts: three matrices of
    [D, F] an assignment, forward and twice that backward. Bytes: the held
    experts' weights read in bfloat16 forward and backward, their float32
    gradients written, and every assignment's row in and out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    count = cfg["experts_held"][1]
    flops = hbm = 0.0
    for rows in shape["held"]:
        flops += 3.0 * rows * 3.0 * 2.0 * d * f
        hbm += 3.0 * count * d * f * (2 * BF16 + F32) + 3.0 * rows * (d * BF16 + d * F32)
    return flops, hbm


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two
    per parameter and token (projections, shared expert, router, head), the
    attention scores and values of the causal pairs inside histories, the
    delta rule, the held assignments; times three for the backward pass.
    Bytes: AdamW's own traffic (parameter, gradient and two moments read,
    parameter and moments written, float32), the least a step can move."""
    t = shape["tokens"]
    d = cfg["hidden_size"]
    n_layers = cfg["num_hidden_layers"]
    n_full = n_layers // cfg["full_attention_interval"]
    n_lin = n_layers - n_full
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lin = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    full = d * 2 * h * hd + 2 * d * hkv * hd + h * hd * d
    ffn = d * cfg["router_width"] + 3 * d * cfg["shared_expert_intermediate_size"]
    dense = n_lin * lin + n_full * full + n_layers * ffn + cfg["vocab_size"] * d
    flops = 3.0 * 2.0 * t * dense
    flops += 3.0 * n_full * 4.0 * shape["pair_sum"] * h * hd
    flops += deltanet_scan(cfg, shape)[0] + moe_experts(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
