"""The least work one optimizer step of the sequence backbone needs when its
layers are a decoder-hybrid-decoder's: Mamba-1 selective scans, differential
attention inside a sliding window, in full and as cross-attention onto a
layer's keys and values below, gated memory units, and a dense SwiGLU in
every layer (``layer_types`` says which layer is which), counted from the
configuration and the shapes of a run. Forward plus backward is three times
the forward's operations (recomputed layers do not count), so a share above
100 % is a wrong count here, not a fast program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2, and
``swa_pair_sum`` = the same sum with every slot's count cut at the window
(the pairs inside window AND history); ``held`` (assignments on held experts)
is taken and not read: there is no expert layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32


def _layers(cfg: Dict, kind: str) -> int:
    return cfg["layer_types"].count(kind)


def _inner(cfg: Dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def pairs_in_window(lengths, window: int) -> float:
    """Causal pairs inside window and history over histories of these
    lengths: slot t of a history keeps ``min(t + 1, window)`` slots."""
    total = 0.0
    for n in lengths:
        n, w = float(n), float(min(n, window))
        total += w * (w + 1.0) / 2.0 + (n - w) * w
    return total


def selective_scan(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The selective recurrence of all Mamba-1 layers as the slot-by-slot
    form counts it, whatever implements it: per slot, channel and state index
    the decay ``exp(Delta A)`` (a product and an exponential), the update ``S
    <- decay S + (Delta c) B`` (three) and the read-out ``C S`` (two): seven.
    Bytes: ``c`` [I] read in bfloat16, ``Delta`` [I], ``B`` and ``C`` [N] in
    float32, ``y`` [I] written in float32; the backward pass reads those and
    the cotangent of ``y`` and writes four cotangents in float32."""
    inner, n = _inner(cfg), cfg["mamba_d_state"]
    slots = _layers(cfg, "mamba1") * shape["tokens"]
    given = inner * BF16 + inner * F32 + 2 * n * F32
    gave = inner * F32
    cotangents = 2 * inner * F32 + 2 * n * F32
    return 3.0 * slots * inner * n * 7.0, slots * (2.0 * given + 2.0 * gave + cotangents)


def _core(cfg: Dict, layers: int, pairs: float, tokens: float) -> Tuple[float, float]:
    """Differential attention's cores: per kept pair of slots and query head
    (both members of every pair of heads) one product over the head for the
    score and one over the value of twice the head. Bytes: q of every query
    head, k of every key head and v of every key pair once in bfloat16 (a
    cross-attention layer reads the layer's below), the difference [pairs, 2
    head] written once, and their cotangents once."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    flops = 3.0 * layers * 2.0 * pairs * h * (hd + 2 * hd)
    moved = h * hd + hkv * hd + (hkv // 2) * 2 * hd + (h // 2) * 2 * hd
    return flops, layers * tokens * 2.0 * moved * BF16


def swa_core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The cores of the sliding-window layers: the pairs inside window and
    history only."""
    return _core(cfg, _layers(cfg, "sliding_attention"), shape["swa_pair_sum"], shape["tokens"])


def full_cores(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """The cores of the full and the cross-attention layers: every causal
    pair inside a history."""
    layers = _layers(cfg, "full_attention") + _layers(cfg, "cross_attention")
    return _core(cfg, layers, shape["pair_sum"], shape["tokens"])


def dense_parameters(cfg: Dict) -> float:
    """The parameters a token is multiplied with: a Mamba-1 mixer's four
    projections, differential attention's four (a cross-attention layer's
    two), a gated memory unit's two, every layer's SwiGLU, one pass over the
    tied head."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, inner = d // h, _inner(cfg)
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    own = _layers(cfg, "sliding_attention") + _layers(cfg, "full_attention")
    return float(
        _layers(cfg, "mamba1") * (d * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * d)
        + own * (2 * d * h * hd + 2 * d * hkv * hd)
        + _layers(cfg, "cross_attention") * 2 * d * h * hd
        + _layers(cfg, "gmu") * 2 * d * inner
        + cfg["num_hidden_layers"] * 3 * d * cfg["intermediate_size"]
        + cfg["vocab_size"] * d)


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token, the attention cores (the sliding layers' inside
    their window), the selective recurrence; times three for the backward
    pass. Bytes: AdamW's own traffic, the least a step can move."""
    flops = 3.0 * 2.0 * shape["tokens"] * dense_parameters(cfg)
    flops += swa_core(cfg, shape)[0] + full_cores(cfg, shape)[0] + selective_scan(cfg, shape)[0]
    return flops, n_params * 7.0 * F32
