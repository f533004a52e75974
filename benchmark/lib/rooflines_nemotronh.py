"""The least work one optimizer step of the sequence backbone needs when
every layer is ONE part by ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer
whose heads read B and C by group, ``*`` grouped-query attention, ``E`` ungated
experts beside a shared one), counted from the configuration and the shapes
of a run. Forward plus backward is three times the forward's products
(recomputed layers do not count), so a share above 100 % is a wrong count
here, not a fast program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2,
``held`` = assignments that fell on this chip's experts in that step, one
number per expert layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32


def _layers(cfg: Dict, letter: str) -> int:
    return cfg["hybrid_override_pattern"].count(letter)


def ssd_scan(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The grouped state-space recurrence of all Mamba-2 layers as the
    slot-by-slot form counts it, whatever implements it: per slot and head the
    state update ``S <- decay S + (dt u) (x) B_g`` at 2 P N and the read-out
    ``S C_g`` at as much (a head's group changes which B and C, not how many
    operations). Bytes: ``u`` [H P], ``B`` and ``C`` of every group [G N] read in
    bfloat16 and ``Delta`` [H] in float32, ``y`` [H P] written in float32; the
    backward pass reads those and the cotangent of ``y`` and writes four
    cotangents."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    slots = _layers(cfg, "M") * shape["tokens"]
    given = h * p * BF16 + 2 * g * n * BF16 + h * F32
    gave = h * p * F32
    return 3.0 * slots * h * 4.0 * p * n, slots * (3.0 * given + 2.0 * gave)


def gqa_core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """Scores and values of all attention layers: per causal pair inside a
    history and query head one product over the head for the score and one
    for the value. Bytes: q and o of every query head, k and v of every
    key/value head once in bfloat16, and their four cotangents once."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers = _layers(cfg, "*")
    flops = 3.0 * layers * 2.0 * shape["pair_sum"] * h * (hd + hd)
    hbm = layers * shape["tokens"] * 2.0 * (2 * h * hd + 2 * hkv * hd) * BF16
    return flops, hbm


def moe_experts(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The grouped products of the held experts: TWO matrices of [D, F] an
    assignment (no gate), forward and twice that backward. Bytes: the held
    experts' weights read in bfloat16 forward and backward, their float32
    gradients written, and every assignment's row in and out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    count = cfg["experts_held"][1]
    flops = hbm = 0.0
    for rows in shape["held"]:
        flops += 3.0 * rows * 2.0 * 2.0 * d * f
        hbm += 2.0 * count * d * f * (2 * BF16 + F32) + 3.0 * rows * (d * BF16 + d * F32)
    return flops, hbm


def dense_parameters(cfg: Dict) -> float:
    """The parameters a token is multiplied with: the Mamba-2 mixers' two
    projections (the in-projection's ``dt`` columns too), the attention's
    four, every expert layer's router and shared expert (two matrices), one
    pass over the untied head."""
    d, h, hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    mh, n, g = cfg["mamba_num_heads"], cfg["ssm_state_size"], cfg["n_groups"]
    inner = mh * cfg["mamba_head_dim"]
    return float(
        _layers(cfg, "M") * (d * (2 * inner + 2 * g * n + mh) + inner * d)
        + _layers(cfg, "*") * (2 * d * h * hd + 2 * d * hkv * hd)
        + _layers(cfg, "E") * (d * cfg["router_width"]
                               + 2 * d * cfg["moe_shared_expert_intermediate_size"])
        + cfg["vocab_size"] * d)


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token, the attention cores, the state-space recurrence, the
    held assignments; times three for the backward pass. Bytes: AdamW's own
    traffic, the least a step can move."""
    flops = 3.0 * 2.0 * shape["tokens"] * dense_parameters(cfg)
    flops += gqa_core(cfg, shape)[0] + ssd_scan(cfg, shape)[0] + moe_experts(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
