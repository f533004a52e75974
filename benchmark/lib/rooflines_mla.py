"""The least work one optimizer step of the sequence backbone needs when
its mixers are latent attention and a multi-token-prediction module
follows the last layer, counted from the configuration and the shapes of a
run. Forward plus backward is three times the forward's products
(recomputed layers do not count), so a share above 100 % is a wrong count
here, not a fast program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2,
``held`` = assignments that fell on this chip's experts in that step, one
number per expert layer (the trunk's sparse layers, then the module's).
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32, moe_experts


def _mixers(cfg: Dict) -> int:
    """Latent-attention mixers a step runs: every layer's and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def mla_core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """Scores and values of all mixers: per causal pair inside a history and
    head one product over the score width (nope + rope) and one over the
    value width. Bytes: q, k, v and o of every head once in bfloat16, and
    their four cotangents once."""
    h = cfg["num_attention_heads"]
    dqk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    flops = 3.0 * _mixers(cfg) * 2.0 * shape["pair_sum"] * h * (dqk + dv)
    hbm = _mixers(cfg) * shape["tokens"] * h * 2.0 * (2 * dqk + 2 * dv) * BF16
    return flops, hbm


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token (the mixers' five projections, the leading layers'
    SwiGLU, router and shared expert of every expert layer, the module's
    joint projection, two passes over the head), the attention cores, the
    held assignments; times three for the backward pass. Bytes: AdamW's own
    traffic, the least a step can move."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mixer = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d
    leading = cfg["first_k_dense_replace"]
    module = cfg["num_nextn_predict_layers"]
    sparse = cfg["num_hidden_layers"] - leading + module
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    dense = (_mixers(cfg) * mixer + leading * 3 * d * cfg["intermediate_size"]
             + sparse * (d * cfg["router_width"] + 3 * d * shared)
             + module * 2 * d * d + (1 + module) * cfg["vocab_size"] * d)
    flops = 3.0 * 2.0 * shape["tokens"] * dense
    flops += mla_core(cfg, shape)[0] + moe_experts(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
