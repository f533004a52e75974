"""The least work one optimizer step of the sequence backbone needs when its
layers are learned sparse attention (grouped-query heads that read only the
``sa_config.topk`` keys a lightning indexer picks) over routed experts without
a shared one, counted from the configuration and the shapes of a run. Forward
plus backward is three times the forward's products (recomputed layers do not
count), so a share above 100 % is a wrong count here, not a fast program.

``shape`` is what ``lib/rooflines_seq.py`` takes: ``tokens`` in a step,
``pair_sum`` = sum over the step's histories of len * (len + 1) / 2 (the causal
pairs: the indexer has to score every one to choose), ``kept_pair_sum`` = the
same sum with every slot's count cut at ``topk`` (the pairs the choice keeps:
all the core has to read, whatever implements it), ``held`` = assignments that
fell on this chip's experts in that step, one number per layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .rooflines_seq import BF16, F32, moe_experts


def _indexer_widths(cfg: Dict) -> Tuple[int, int]:
    return cfg["sa_config"]["indexer_num_heads"], cfg["sa_config"]["indexer_head_dim"]


def index(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """What the choice needs of the indexer, all layers: its three projections
    of every token (forward and backward: 3 x 2 a parameter and token), and ONE
    score of every causal pair inside a history (J products over d, a ReLU, a
    weight and a sum a head), forward only: the choice has no gradient. (The
    indexer's loss makes the kept pairs' scores again, and their backward: that
    is counted in :func:`step`, not here, as it runs under another scope.)
    Bytes: the normed input read and the three products written in bfloat16,
    their cotangents once, and the chosen mask written a bit a pair."""
    heads, d = _indexer_widths(cfg)
    layers, wide = cfg["num_hidden_layers"], heads * d + d + heads
    flops = layers * (3.0 * 2.0 * shape["tokens"] * cfg["hidden_size"] * wide
                      + shape["pair_sum"] * heads * (2.0 * d + 3.0))
    hbm = layers * (shape["tokens"] * 2.0 * (cfg["hidden_size"] + wide) * BF16
                    + shape["pair_sum"] / 8.0)
    return flops, hbm


def core(cfg: Dict, shape: Dict) -> Tuple[float, float]:
    """Scores and values of all layers over the KEPT pairs alone: per kept pair
    and query head one product over the head for the score and one for the
    value, times three for the backward pass. Bytes: q and o of every query
    head, k and v of every key/value head once in bfloat16, and their four
    cotangents once. A core that computes whole tiles and masks inside them
    reads low here, by as much as the tiles hold pairs that were not kept."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    flops = 3.0 * layers * 2.0 * shape["kept_pair_sum"] * h * (hd + hd)
    hbm = layers * shape["tokens"] * 2.0 * (2 * h * hd + 2 * hkv * hd) * BF16
    return flops, hbm


def step(cfg: Dict, shape: Dict, n_params: float) -> Tuple[float, float]:
    """One whole optimizer step. Operations: every dense product at two per
    parameter and token (attention's four projections, the indexer's three,
    the router, one pass over the untied head), times three for the backward
    pass; the cores over the kept pairs; the indexer's scores of every causal
    pair once and, for its loss, the kept pairs' scores backward (twice a
    forward); the held assignments. The main heads' weights that the loss
    holds constant are the core's own and cost nothing again. Bytes: AdamW's
    own traffic, the least a step can move."""
    d, h, hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    heads, di = _indexer_widths(cfg)
    layers = cfg["num_hidden_layers"]
    dense = layers * (2 * d * h * hd + 2 * d * hkv * hd + d * cfg["router_width"]) + cfg["vocab_size"] * d
    flops = 3.0 * 2.0 * shape["tokens"] * dense
    flops += index(cfg, shape)[0] + core(cfg, shape)[0] + moe_experts(cfg, shape)[0]
    flops += layers * 2.0 * shape["kept_pair_sum"] * heads * (2.0 * di + 3.0)
    return flops, n_params * 7.0 * F32
