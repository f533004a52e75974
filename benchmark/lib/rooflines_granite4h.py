"""The least work one optimizer step of the sequence backbone needs when
its layers are Mamba-2 state-space mixers beside grouped-query attention
and every layer's feed-forward is a dense SwiGLU (``layer_types`` says which
layer is which), counted from the configuration and the shapes of a run.
Forward plus backward is three times the forward's products (recomputed
layers do not count), so a share above 100 % is a wrong count here, not a
fast program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2;
``held`` (assignments on held experts) is taken and not read: there is no
expert layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32


def _layers(cfg: Dict, kind: str) -> int:
    return cfg["layer_types"].count(kind)


def ssd_scan(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The state-space recurrence of all Mamba-2 layers as the slot-by-slot
    form counts it, whatever implements it: per slot and head the state
    update ``S <- decay S + (dt u) (x) B`` at 2 P N and the read-out ``S C``
    at as much. Bytes: ``u`` [H P], ``B`` and ``C`` [N] read in bfloat16 and
    ``Delta`` [H] in float32, ``y`` [H P] written in float32; the backward
    pass reads those and the cotangent of ``y`` and writes four cotangents."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    slots = _layers(cfg, "mamba") * shape["tokens"]
    given = h * p * BF16 + 2 * n * BF16 + h * F32
    gave = h * p * F32
    return 3.0 * slots * h * 4.0 * p * n, slots * (3.0 * given + 2.0 * gave)


def gqa_core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """Scores and values of all attention layers: per causal pair inside a
    history and query head one product over the head for the score and one
    for the value. Bytes: q and o of every query head, k and v of every
    key/value head once in bfloat16, and their four cotangents once."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    layers = _layers(cfg, "attention")
    flops = 3.0 * layers * 2.0 * shape["pair_sum"] * h * (hd + hd)
    hbm = layers * shape["tokens"] * 2.0 * (2 * h * hd + 2 * hkv * hd) * BF16
    return flops, hbm


def dense_parameters(cfg: Dict) -> float:
    """The parameters a token is multiplied with: the Mamba-2 mixers' two
    projections (the in-projection's ``dt`` columns too), the attention's
    four, every layer's SwiGLU, one pass over the tied head."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    mh, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = mh * cfg["mamba_d_head"]
    return float(
        _layers(cfg, "mamba") * (d * (2 * inner + 2 * n + mh) + inner * d)
        + _layers(cfg, "attention") * (2 * d * h * hd + 2 * d * hkv * hd)
        + cfg["num_hidden_layers"] * 3 * d * cfg["shared_intermediate_size"]
        + cfg["vocab_size"] * d)


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token, the attention cores, the state-space recurrence;
    times three for the backward pass. Bytes: AdamW's own traffic, the least
    a step can move."""
    flops = 3.0 * 2.0 * shape["tokens"] * dense_parameters(cfg)
    flops += gqa_core(cfg, shape)[0] + ssd_scan(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
