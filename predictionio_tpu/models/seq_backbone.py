"""The sequence recommender's backbone: a decoder block that is a function
of a configuration.

A configuration gives the layer pattern (``layer_types``: a mixer a layer,
``full_attention`` or ``attention``, ``linear_attention`` = gated DeltaNet,
``conv`` = the gated short convolution, ``mamba`` = the Mamba-2
state-space mixer, ``ssm`` here, ``mamba1`` = the Mamba-1 selective scan,
``sliding_attention`` = attention inside ``sliding_window`` slots, ``gmu`` =
a gated memory unit that reads the scan output of the last ``mamba1`` layer
below it, or ``cross_attention`` = queries of its own onto the keys and
values of the last ``full_attention`` layer below it; or
``full_attention_interval``: every n-th layer is softmax attention, the
others gated DeltaNet; 1 = all attention),
the attention (``gqa``: grouped heads of one width; ``mla``:
latent attention, queries and keys/values projected down, normed and up
again, a rotary part of the key that all heads share; ``differential``:
grouped heads in pairs, two softmaxes a pair and their difference), the head and
feed-forward widths, the norm (``rms`` with scale ``1 + w``, or ``layer``),
the positions (``rotary`` on part of a head, a ``learned`` table, or
``none``: the mixers' own order is all the model knows of it), Granite's four
multipliers (on the embedding, on both residual additions of a layer, on the
attention scores in place of ``1 / sqrt(head)``, and the divisor of the
logits; all 1 or absent elsewhere), the feed-forward kind (``moe``: routed
experts of which this share holds a range, plus a shared expert where the
file gives one; ``swiglu``; or ``gelu``), how many leading layers are dense
instead (``first_k_dense_replace``, ``num_dense_layers``), whether a
multi-token-prediction module follows the last layer, and whether the head
is the embedding. The keys are those of the public models' ``config.json``;
what such a file does not state (norm, positions, the range of experts
held, precision) sits in its ``backbone`` group.

Parameters are stacked by period (the shortest run of mixers that the layers
after the leading dense ones repeat) and the periods run in a ``lax.scan``,
a model of one period too; the leading dense layers (one kind of mixer,
whichever the pattern gives them) and the prediction module lie outside it;
each layer is recomputed in the backward pass. Rows are packed: ``seg`` gives
each slot its history's id (0 = padding), positions count from a history's
start, and neither the convolutions, the delta-rule state, the state-space
state nor attention crosses a boundary.

Precision: parameters, residual stream, norms, router, softmax, gates,
delta-rule state, the router's bias and loss in float32; matrix products
take ``compute_dtype`` inputs (bfloat16 on the chip) and accumulate in
float32. ``state_dtype`` is also what latent attention keeps its running
softmax statistics in between tiles.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import attention, tiles_skipped_by_window
from ..ops.deltanet import gated_deltanet
from ..ops.moe import expert_layer, swiglu
from ..ops.selscan import gated_memory, mamba1
from ..ops.shortconv import conv_kind, short_conv
from ..ops.ssd import mamba2

#: a public file's word for a layer's mixer -> the kind the parameters are
#: stacked under
_KINDS = {"full_attention": "full", "attention": "full", "linear_attention": "linear",
          "conv": "conv", "mamba": "ssm", "mamba1": "mamba1", "sliding_attention": "swa",
          "gmu": "gmu", "cross_attention": "cross"}
#: what a layer of a kind hands to the layers above it in its period (of what
#: its mixer returns beside its output), and what a layer of a kind reads of
#: that: the newest below it
_HANDS = {"mamba1": ("m",), "full": ("k", "v")}
_READS = {"gmu": ("m",), "cross": ("k", "v")}
#: the kinds of layer a decoder-hybrid-decoder adds (``_hybrid_mixer`` runs them)
_HYBRID = ("mamba1", "swa", "gmu", "cross")
#: a reading kind -> the kind that hands it what it reads, in a file's word, and what that is
_PRODUCERS = {"gmu": ("mamba1", "mamba1", "a scan output"),
              "cross": ("full", "full_attention", "keys and values")}

CONF_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "conf", "backbones")


@dataclasses.dataclass(frozen=True)
class BackboneConfig:
    hidden_size: int = 64
    num_hidden_layers: int = 2
    full_attention_interval: int = 1
    #: the mixer of every layer in a public file's words (``_KINDS``); empty
    #: = ``full_attention_interval`` decides, and leading dense layers are
    #: full attention
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    #: the gated attention of Qwen3-Next: an output gate beside the query
    #: and a norm on every head of q and k
    attn_gate: bool = False
    #: the norm on every head of q and k without the gate
    qk_norm: bool = False
    #: "gqa", or "mla": latent attention at the five widths below
    attention: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: differential attention (arXiv:2410.05258) on grouped heads: query and
    #: key heads in pairs, a softmax a member, the second taken from the
    #: first ``lambda`` times (four learned vectors a layer and a start that
    #: follows the layer's depth), an RMS norm over a pair's value of twice
    #: the head; the form of ``sliding_attention`` and ``cross_attention``
    differential: bool = False
    #: slots a ``sliding_attention`` layer sees: itself and the ones before
    sliding_window: int = 0
    #: the depth of this file's first layer in the published model
    layer_index_offset: int = 0
    #: the single device's attention kernel: "xla" (the blockwise loop,
    #: which skips the tiles between histories) or "splash" (JAX's Pallas
    #: kernel where it can run: ``ops.attention.attention``)
    attn_kernel: str = "xla"
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e4
    positions: str = "learned"  # "learned" | "rotary" | "none"
    norm: str = "layer"  # "layer" | "rms"
    rms_norm_eps: float = 1e-6
    layer_norm_eps: float = 1e-6
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    #: taps of the gated short convolution (``layer_types`` ``conv``)
    conv_L_cache: int = 3
    #: the Mamba-2 mixer (``layer_types`` ``mamba``): heads, their width, the
    #: state's width a head, the taps of its convolution, and the groups
    #: that share B and C (one: nothing else runs here). 0 = not given.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_n_groups: int = 0
    #: the Mamba-1 mixer (``layer_types`` ``mamba1``) has ``mamba_expand`` x
    #: ``hidden_size`` channels with ``mamba_d_state`` numbers of state each,
    #: ``mamba_d_conv`` taps and a step projected through ``mamba_dt_rank``
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    #: what the attention scores are multiplied by; None = 1 / sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    #: on the embedding; on what a mixer and a feed-forward add to the
    #: residual stream; what the logits are divided by
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    ffn: str = "gelu"  # "gelu" | "swiglu" | "moe"
    #: leading layers, outside the periods, whose feed-forward is a SwiGLU
    #: of ``intermediate_size`` whatever ``ffn`` says
    first_k_dense_replace: int = 0
    intermediate_size: int = 256
    router_width: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    scoring_func: str = "softmax"  # "softmax" | "sigmoid" (``ops.moe.route``)
    routed_scaling_factor: float = 1.0
    #: what the sigmoid router adds to the chosen scores' sum before it
    #: divides by it (DeepSeek-V3's 1e-20; LFM2's 1e-6)
    norm_topk_eps: float = 1e-20
    #: a bias [router_width] that chooses the experts and does not weigh
    #: them (``topk_method`` ``noaux_tc``); no gradient moves it: every
    #: step adds ``router_bias_rate * sign(mean load - load)``
    router_bias: bool = False
    router_bias_rate: float = 0.001
    #: False = an optimizer step leaves every router's matrix where it was
    #: (its gradient is computed all the same). For a lone share of an
    #: expert-parallel group (``experts_held`` a part of ``router_width``):
    #: the gradient it has comes through its own experts alone, and steps
    #: along it pull every token onto them; the group's sum would step it
    router_trains: bool = True
    #: a sigmoid gate on the shared expert (Qwen3-Next has one)
    shared_expert_gate: bool = True
    #: (first, count): the contiguous range of routed experts held here
    experts_held: Tuple[int, int] = (0, 0)
    #: 1 = a multi-token-prediction module after the last layer: the id one
    #: slot on is embedded beside the last hidden state, one more block,
    #: the shared head, the id two slots on as target; the loss adds
    #: ``mtp_loss_weight`` times its cross entropy. Serving ignores it.
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    tie_word_embeddings: bool = True
    #: std of the normal the matrices are drawn from; None = 1/sqrt(fan_in)
    init_std: Optional[float] = None
    compute_dtype: str = "float32"
    state_dtype: str = "float32"
    gate_dtype: str = "float32"
    #: slots a chunk of the delta rule's and of the state-space scan
    #: (``mamba_chunk_size`` where the ``backbone`` group gives none)
    chunk: int = 64
    attn_block: int = 512
    loss_block: int = 2048

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of every layer: ``full``, ``linear``, ``conv``, ``ssm``,
        ``mamba1``, ``swa``, ``gmu`` or ``cross``."""
        if self.layer_types:
            return tuple(_KINDS[t] for t in self.layer_types)
        p = self.full_attention_interval
        one = ("linear",) * (p - 1) + ("full",)
        k = self.first_k_dense_replace
        return ("full",) * k + one * ((self.num_hidden_layers - k) // p)

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        """One period: the shortest run of mixers that the layers after the
        leading dense ones are whole repeats of."""
        rest = self.kinds[self.first_k_dense_replace:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                return rest[:p]
        return rest

    @property
    def period(self) -> int:
        return len(self.period_kinds)

    @property
    def n_periods(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace) // self.period

    def stacked(self, kind: str) -> int:
        """The layers of ``kind`` in a period as its parameters are stacked
        behind the period's own axis; 0 = no second axis: a period's one
        full layer (the layout of every model trained before there were
        patterns with more)."""
        held = self.period_kinds.count(kind)
        return 0 if (kind, held) == ("full", 1) else held

    def mixers(self) -> Dict[str, int]:
        """Layers by the mixer they run: ``deltanet``, ``shortconv``,
        ``mamba2``, ``mamba1``, ``swa``, ``gmu``, ``cross``, and ``gqa`` or
        ``mla`` (the prediction module's block counts too)."""
        names = {"linear": "deltanet", "conv": "shortconv", "ssm": "mamba2",
                 "full": self.attention}
        found = [names.get(k, k) for k in self.kinds] + [self.attention] * self.num_nextn_predict_layers
        return {name: found.count(name) for name in sorted(set(found))}

    @classmethod
    def toy(cls, d_model: int, n_heads: int, n_layers: int) -> "BackboneConfig":
        """The preset the template shipped with: pre-LayerNorm attention
        and a GELU feed-forward of 4x, learned positions, tied head."""
        return cls(
            hidden_size=d_model, num_hidden_layers=n_layers,
            num_attention_heads=n_heads, num_key_value_heads=n_heads,
            head_dim=d_model // n_heads, intermediate_size=4 * d_model,
        )

    @classmethod
    def from_dict(cls, d: Dict) -> "BackboneConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        merged = {**d, **d.get("backbone", {})}
        values = {k: v for k, v in merged.items() if k in names}
        for name in ("experts_held", "layer_types"):
            if name in values:
                values[name] = tuple(values[name])
        # what a public file says in its own words
        if merged.get("topk_method") == "noaux_tc" or merged.get("use_expert_bias"):
            values.setdefault("router_bias", True)
        for theirs, ours in (("num_dense_layers", "first_k_dense_replace"),
                             ("norm_eps", "rms_norm_eps")):
            if theirs in merged:
                values.setdefault(ours, merged[theirs])
        if "chunk" not in merged.get("backbone", {}) and "mamba_chunk_size" in merged:
            values["chunk"] = merged["mamba_chunk_size"]
        if merged.get("position_embedding_type") == "nope":
            values.setdefault("positions", "none")
        # a dense Granite's feed-forward is its "shared" SwiGLU
        if "shared_intermediate_size" in merged and not merged.get("num_local_experts"):
            values["intermediate_size"] = merged["shared_intermediate_size"]
        if "rope_theta" in merged.get("rope_parameters", {}):
            values.setdefault("rope_theta", merged["rope_parameters"]["rope_theta"])
        if "n_shared_experts" in merged:
            values.setdefault(
                "shared_expert_intermediate_size",
                merged["n_shared_experts"] * merged["moe_intermediate_size"])
        cfg = cls(**values)
        if cfg.layer_types:
            unknown = sorted(set(cfg.layer_types) - set(_KINDS))
            if unknown:
                raise ValueError(f"layer_types names mixers unknown here: {unknown}")
            if len(cfg.layer_types) != cfg.num_hidden_layers:
                raise ValueError(f"layer_types names {len(cfg.layer_types)} layers "
                                 f"for {cfg.num_hidden_layers}")
            if len(set(cfg.kinds[:cfg.first_k_dense_replace])) > 1:
                raise ValueError("the leading dense layers are stacked: one kind of mixer for all")
        elif (cfg.num_hidden_layers - cfg.first_k_dense_replace) % cfg.full_attention_interval:
            raise ValueError(
                f"{cfg.num_hidden_layers} layers less {cfg.first_k_dense_replace} dense ones "
                f"are not whole periods of {cfg.full_attention_interval}")
        if merged.get("conv_bias"):
            raise ValueError("the short convolution and its projections carry no bias here")
        if "ssm" in cfg.kinds:
            sizes = ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv")
            missing = [name for name in sizes if not getattr(cfg, name)]
            if missing:
                raise ValueError(f"mamba layers need {', '.join(missing)}: no default is assumed")
            if cfg.mamba_n_groups != 1:
                raise ValueError(f"mamba_n_groups is {cfg.mamba_n_groups or 'not given'}: the "
                                 "state-space scan here shares B and C among all heads (one group)")
            if "mamba_expand" in merged and (
                    merged["mamba_expand"] * cfg.hidden_size != cfg.mamba_n_heads * cfg.mamba_d_head):
                raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head")
            if not merged.get("mamba_conv_bias", True) or merged.get("mamba_proj_bias"):
                raise ValueError("the Mamba-2 mixer here has a bias on its convolution "
                                 "and none on its projections")
        if merged.get("attention_bias"):
            raise ValueError("the attention projections carry no bias here")
        _check_hybrid(cfg, merged)
        if cfg.positions not in ("learned", "rotary", "none"):
            raise ValueError(f"positions {cfg.positions!r}: learned, rotary or none")
        if cfg.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")
        if cfg.attention == "mla" and not (
                (cfg.norm, cfg.positions) == ("rms", "rotary") and merged.get("rope_interleave", True)):
            raise ValueError("latent attention comes with RMS norms and rotary positions "
                             "on neighbouring pairs (rope_interleave)")
        return cfg

    @classmethod
    def load(cls, name: str) -> "BackboneConfig":
        """``name``: a JSON file (absolute, or relative to the working
        directory), or the name of one under ``conf/backbones/``."""
        candidates = [name, os.path.join(CONF_DIR, name + ".json")]
        for path in candidates:
            if os.path.isfile(path):
                with open(path) as f:
                    return cls.from_dict(json.load(f))
        raise FileNotFoundError(
            f"no backbone configuration {name!r} (looked at {candidates})")


def _dt(name: str):
    return jnp.dtype(name)


# -- parameters -------------------------------------------------------------
def _is_spec(x) -> bool:
    """A leaf of ``_shapes``: (shape, kind)."""
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _shapes(cfg: BackboneConfig, vocab: int, max_positions: int) -> Dict:
    """name -> (shape, kind): 'w' a matrix (fan-in = second-to-last axis),
    'zero', 'one', 'embed', or a kind of its own. A period's mixers are
    stacked by kind, [periods, layers of that kind in a period, ...]
    (``BackboneConfig.stacked``)."""
    d, p, n = cfg.hidden_size, cfg.period, cfg.n_periods
    norm = {"w": ((d,), "zero")} if cfg.norm == "rms" else {
        "g": ((d,), "one"), "b": ((d,), "zero")}

    def lead(tree, *axes):
        return jax.tree_util.tree_map(
            lambda leaf: (tuple(axes) + leaf[0], leaf[1]), tree, is_leaf=_is_spec)

    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    if cfg.attention == "mla":
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        full = {
            "w_qa": ((d, rq), "w"), "q_norm": ((rq,), "zero"), "w_qb": ((rq, h * (dn + dr)), "w"),
            "w_kva": ((d, rkv + dr), "w"), "kv_norm": ((rkv,), "zero"),
            "w_kvb": ((rkv, h * (dn + dv)), "w"), "w_o": ((h * dv, d), "w"),
        }
    else:
        full = {
            "w_q": ((d, h * hd * (2 if cfg.attn_gate else 1)), "w"),
            "w_k": ((d, hkv * hd), "w"), "w_v": ((d, hkv * hd), "w"),
            "w_o": ((h * hd, d), "w"),
        }
        if cfg.attn_gate or cfg.qk_norm:
            full.update(q_norm=((hd,), "zero"), k_norm=((hd,), "zero"))
        if cfg.differential:
            full.update({name: ((hd,), "lambda") for name in _LAMBDAS}, subln=((2 * hd,), "one"))
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    mh, ns = cfg.mamba_n_heads, cfg.mamba_d_state
    inner = mh * cfg.mamba_d_head
    wide, rank = cfg.mamba_expand * d, cfg.mamba_dt_rank
    mixers = {
        "full": full,
        "swa": full,
        "cross": {name: spec for name, spec in full.items() if name not in ("w_k", "w_v")},
        "mamba1": {
            "w_in": ((d, 2 * wide), "w"), "conv_w": ((cfg.mamba_d_conv, wide), "w"),
            "conv_b": ((wide,), "zero"), "w_x": ((wide, rank + 2 * ns), "w"),
            "w_dt": ((rank, wide), "w"), "dt_bias": ((wide,), "dt_bias"),
            "A_log": ((wide, ns), "a_log_range"), "D": ((wide,), "one"),
            "w_out": ((wide, d), "w"),
        },
        "gmu": {"w_1": ((d, wide), "w"), "w_2": ((wide, d), "w")},
        "linear": {
            "w_qkvz": ((d, 2 * hk * dk + 2 * hv * dv), "w"), "w_ba": ((d, 2 * hv), "w"),
            "conv_w": ((cfg.linear_conv_kernel_dim, 2 * hk * dk + hv * dv), "w"),
            "A_log": ((hv,), "a_log"), "dt_bias": ((hv,), "dt_bias"),
            "o_norm": ((dv,), "one"), "w_out": ((hv * dv, d), "w"),
        },
        "conv": {"w_in": ((d, 3 * d), "w"), "conv_w": ((cfg.conv_L_cache, d), "w"),
                 "w_out": ((d, d), "w")},
        "ssm": {
            "w_in": ((d, 2 * inner + 2 * ns), "w"), "w_dt": ((d, mh), "w"),
            "conv_w": ((cfg.mamba_d_conv, inner + 2 * ns), "w"),
            "conv_b": ((inner + 2 * ns,), "zero"),
            "A_log": ((mh,), "a_log"), "dt_bias": ((mh,), "dt_bias"), "D": ((mh,), "one"),
            "norm": ((inner,), "one"), "w_out": ((inner, d), "w"),
        },
    }
    m = cfg.intermediate_size
    gated = {"wg": ((d, m), "w"), "wu": ((d, m), "w"), "wd": ((m, d), "w")}
    if cfg.ffn == "moe":
        f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        count = cfg.experts_held[1]
        ffn = {
            "router": ((d, cfg.router_width), "w"),
            "experts": {"wg": ((count, d, f), "w"), "wu": ((count, d, f), "w"),
                        "wd": ((count, f, d), "w")},
        }
        if fs:
            ffn["shared"] = {"wg": ((d, fs), "w"), "wu": ((d, fs), "w"), "wd": ((fs, d), "w")}
            if cfg.shared_expert_gate:
                ffn["shared_gate"] = ((d,), "w_vec")
        if cfg.router_bias:
            ffn["router_bias"] = ((cfg.router_width,), "zero")
    elif cfg.ffn == "swiglu":
        ffn = gated
    else:
        ffn = {"mlp_in": ((d, m), "w"), "mlp_out": ((m, d), "w")}
    periods = {
        "norm_in": lead(norm, n, p), "norm_post": lead(norm, n, p),
        "ffn": lead(ffn, n, p),
    }
    for kind in set(cfg.period_kinds):
        held = cfg.stacked(kind)
        periods[kind] = lead(mixers[kind], n, held) if held else lead(mixers[kind], n)
    shapes = {"embed": ((vocab, d), "embed"), "final_norm": norm, "periods": periods}
    if cfg.first_k_dense_replace:
        kind = cfg.kinds[0]
        shapes["dense"] = lead(
            {kind: mixers[kind], "norm_in": norm, "norm_post": norm, "ffn": gated},
            cfg.first_k_dense_replace)
    if cfg.num_nextn_predict_layers:
        shapes["mtp"] = {
            "enorm": norm, "hnorm": norm, "eh_proj": ((2 * d, d), "w"), "norm": norm,
            "block": {"full": full, "norm_in": norm, "norm_post": norm, "ffn": ffn},
        }
    if not cfg.tie_word_embeddings:
        shapes["head"] = ((vocab, d), "embed")
    if cfg.positions == "learned":
        shapes["pos"] = ((max_positions, d), "embed")
    return shapes


def init_params(cfg: BackboneConfig, vocab: int, max_positions: int, seed: int) -> Dict:
    """The parameters, drawn on the device by one jitted program: nothing
    is made on the host and uploaded."""
    return _draw_program(cfg, vocab, max_positions)(jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=8)
def _draw_program(cfg: BackboneConfig, vocab: int, max_positions: int):
    shapes = _shapes(cfg, vocab, max_positions)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_spec)

    def draw(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if kind == "zero":
                leaf = jnp.zeros(shape, jnp.float32)
            elif kind == "one":
                leaf = jnp.ones(shape, jnp.float32)
            elif kind == "embed":
                leaf = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif kind == "a_log":  # A uniform in [1, 16), as the public implementation has it
                leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif kind == "a_log_range":  # A = 1 ... N along the state of every channel (Mamba-1)
                leaf = jnp.log(jnp.broadcast_to(
                    jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape))
            elif kind == "lambda":  # the four vectors of a differential layer's lambda
                leaf = 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif kind == "dt_bias":  # softplus^-1 of dt, dt log-uniform in [1e-3, 1e-1]
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            else:
                fan_in = shape[-1] if kind == "w_vec" else shape[-2]
                std = cfg.init_std if cfg.init_std is not None else fan_in ** -0.5
                leaf = std * jax.random.normal(k, shape, jnp.float32)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)


def layers_of(params: Dict, cfg: BackboneConfig) -> Dict:
    """The parameters unstacked into a list of per-layer dicts, in the
    layout of the plain references (``testing/qwen3_next_reference.py``;
    with latent attention ``testing/joyai_flash_reference.py``, whose
    mixer is ``attn``, whose dense layers carry ``mlp`` and whose
    prediction module is ``mtp``; ``testing/lfm2_moe_reference.py``, whose
    mixers are ``conv`` and ``full`` and whose head is its embedding;
    ``testing/granite4h_reference.py``, whose mixers are ``ssm`` and
    ``full`` and whose every layer carries ``mlp``;
    ``testing/phi4flash_reference.py``, whose mixers are ``mamba1``, ``swa``,
    ``full``, ``gmu`` and ``cross`` and whose norms are ``{"g", "b"}``).
    Works on any pytree of the parameters' structure: gradients too."""
    full_key = "attn" if cfg.attention == "mla" else "full"
    ffn_key = "moe" if cfg.ffn == "moe" else "mlp"

    def norm(p):  # an RMS norm is its one leaf, a LayerNorm its two
        return p["w"] if "w" in p else p

    def block(blk, kind="full", ffn_key=ffn_key):
        return {"input_norm": norm(blk["norm_in"]), "post_norm": norm(blk["norm_post"]),
                ffn_key: blk["ffn"], full_key if kind == "full" else kind: blk[kind]}

    layers = []
    for j in range(cfg.first_k_dense_replace):
        dense = jax.tree_util.tree_map(lambda leaf, j=j: leaf[j], params["dense"])
        layers.append(block(dense, cfg.kinds[0], ffn_key="mlp"))
    per = params["periods"]
    for n in range(cfg.n_periods):
        at_n = jax.tree_util.tree_map(lambda leaf, n=n: leaf[n], per)
        for j, kind in enumerate(cfg.period_kinds):
            blk = {name: jax.tree_util.tree_map(lambda leaf, j=j: leaf[j], at_n[name])
                   for name in ("norm_in", "norm_post", "ffn")}
            blk[kind] = _mixer_of(cfg, at_n, j)
            layers.append(block(blk, kind))
    out = {"embed": params["embed"], "final_norm": norm(params["final_norm"]), "layers": layers}
    if "head" in params:
        out["head"] = params["head"]
    if "mtp" in params:
        m = params["mtp"]
        out["mtp"] = {
            "enorm": m["enorm"]["w"], "hnorm": m["hnorm"]["w"], "eh_proj": m["eh_proj"],
            "norm": m["norm"]["w"], "block": block(m["block"]),
        }
    return out


def _mixer_of(cfg: BackboneConfig, per: Dict, j: int):
    """The mixer's parameters of layer ``j`` of ONE period ``per`` (the
    stacked parameters at one index of their leading axis)."""
    kind = cfg.period_kinds[j]
    if not cfg.stacked(kind):
        return per[kind]
    nth = cfg.period_kinds[:j].count(kind)
    return jax.tree_util.tree_map(lambda a: a[nth], per[kind])


# -- the block --------------------------------------------------------------
def _norm(cfg: BackboneConfig, p: Dict, x):
    if cfg.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * (
            1.0 + p["w"])
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg.layer_norm_eps) * p["g"] + p["b"]


def positions_of(seg):
    """Position of each slot counted from the start of its history."""
    idx = jnp.arange(seg.shape[-1])
    start = jnp.concatenate(
        [jnp.ones_like(seg[..., :1], bool), seg[..., 1:] != seg[..., :-1]], -1)
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=seg.ndim - 1)


def _rope(t, pos, rot: int, theta: float):
    """t [B, L, H, hd]; the first ``rot`` dimensions turn with position."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    r, rest = t[..., :rot], t[..., rot:]
    half = jnp.concatenate([-r[..., rot // 2:], r[..., : rot // 2]], -1)
    return jnp.concatenate([r * cos + half * sin, rest], -1)


def _rope_pairs(t, pos, theta: float):
    """t [B, L, H, r]: every pair of neighbours (2j, 2j + 1) turns by
    ``pos * theta ** (-2j / r)`` (the interleaved layout)."""
    r = t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = t[..., 0::2], t[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(t.shape)


def _rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _latent_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, mesh, schedule):
    """Latent attention. Queries: down to ``q_lora_rank``, RMS norm, up to
    H x (nope | rope). Keys and values: down to ``kv_lora_rank`` + rope,
    RMS norm of the latent part, up to H x (nope | value); the rope part
    is ONE vector a slot that every head's key ends with. Scores over nope
    + rope, values of their own width. Also returns the q, k, v it handed
    the attention core (keys and values less their means over the row) and
    the o that gave, [B, H, L, .]."""
    b, l, _ = x.shape
    h, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cd, f32, eps = _dt(cfg.compute_dtype), jnp.float32, cfg.rms_norm_eps

    def dot(t, w):
        return jnp.dot(t.astype(cd), w.astype(cd), preferred_element_type=f32)

    with jax.named_scope("seq.attn.latent"):
        # the wide projections are kept in the compute dtype, as in the other mixers
        q = dot(_rms(dot(x, p["w_qa"]), p["q_norm"], eps), p["w_qb"]).astype(cd)
        q = q.reshape(b, l, h, dn + dr)
        kva = dot(x, p["w_kva"])
        k_rope = _rope_pairs(kva[:, :, None, rkv:], pos, cfg.rope_theta)
        kv = dot(_rms(kva[..., :rkv], p["kv_norm"], eps), p["w_kvb"]).astype(cd)
        kv = kv.reshape(b, l, h, dn + dv).astype(f32)
        q_rope = _rope_pairs(q[..., dn:].astype(f32), pos, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope.astype(cd)], -1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, l, h, dr))], -1)
        # The core is handed what differs between a row's slots: a softmax
        # does not see a constant added to every key, and a constant added
        # to every value comes out as itself. What the slots share would
        # else cost the bfloat16 core its digits, worst in its backward
        # pass, whose row sums cancel only as far as o is exact.
        k = k - k.mean(1, keepdims=True)
        v_mean = kv[..., dn:].mean(1, keepdims=True)
        v = kv[..., dn:] - v_mean
        q, k, v = (t.astype(cd).transpose(0, 2, 1, 3) for t in (q, k, v))
    with jax.named_scope("seq.attn.core"):
        o = attention(q, k, v, mesh=mesh, causal=True, schedule=schedule, segment_ids=seg,
                      block=cfg.attn_block, stats_dtype=cfg.state_dtype, kernel=cfg.attn_kernel)
    out = dot(o.transpose(0, 2, 1, 3).reshape(b, l, h * dv), p["w_o"])
    return out + dot(v_mean.reshape(b, 1, h * dv), p["w_o"]), {"q": q, "k": k, "v": v, "o": o}


def _attention_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, mesh, schedule):
    b, l, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    xc = x.astype(cd)
    # the wide projections are kept in the compute dtype, as in the DeltaNet mixer
    qg = jnp.dot(xc, p["w_q"].astype(cd), preferred_element_type=f32).astype(cd)
    q = qg[..., : h * hd].reshape(b, l, h, hd).astype(f32)
    k = jnp.dot(xc, p["w_k"].astype(cd), preferred_element_type=f32).reshape(b, l, hkv, hd)
    v = jnp.dot(xc, p["w_v"].astype(cd), preferred_element_type=f32).reshape(b, l, hkv, hd)
    if "q_norm" in p:
        eps = cfg.rms_norm_eps

        def head_norm(t, w):
            return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * (1.0 + w)

        q, k = head_norm(q, p["q_norm"]), head_norm(k, p["k_norm"])
    if cfg.positions == "rotary":
        rot = int(cfg.partial_rotary_factor * hd)
        q, k = _rope(q, pos, rot, cfg.rope_theta), _rope(k, pos, rot, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        # the core scales by 1 / sqrt(hd): q carries the rest
        q = q * (cfg.attention_multiplier * hd ** 0.5)
    with jax.named_scope("seq.attn.core"):
        o = attention(
            q.astype(cd).transpose(0, 2, 1, 3), k.astype(cd).transpose(0, 2, 1, 3),
            v.astype(cd).transpose(0, 2, 1, 3), mesh=mesh, causal=True,
            schedule=schedule, segment_ids=seg, block=cfg.attn_block,
        )
    o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd).astype(f32)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(qg[..., h * hd:].astype(f32))
    return jnp.dot(o.astype(cd), p["w_o"].astype(cd), preferred_element_type=f32)


def _ffn(cfg: BackboneConfig, p: Dict, x):
    """The feed-forward its parameters describe: routed experts, a SwiGLU
    or the GELU pair."""
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    if "router" in p:
        b, l, d = x.shape
        with jax.named_scope("seq.moe"):
            y, counters = expert_layer(
                p, x.reshape(b * l, d), first=cfg.experts_held[0],
                top_k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
                compute_dtype=cd, scoring=cfg.scoring_func, scale=cfg.routed_scaling_factor,
                norm_eps=cfg.norm_topk_eps)
        return y.reshape(b, l, d), counters
    if "wg" in p:
        with jax.named_scope("seq.ffn"):
            return swiglu(p, x, cd), {}
    hidden = jax.nn.gelu(jnp.dot(x.astype(cd), p["mlp_in"].astype(cd), preferred_element_type=f32))
    return jnp.dot(hidden.astype(cd), p["mlp_out"].astype(cd), preferred_element_type=f32), {}


def _add(cfg: BackboneConfig, x, y):
    """The residual stream ``x`` after a mixer or a feed-forward gave ``y``."""
    return x + y if cfg.residual_multiplier == 1.0 else x + cfg.residual_multiplier * y


def _layer(cfg: BackboneConfig, kind: str, mesh, schedule, x, seg, pos,
           norm_in, mixer, norm_post, ffn, given=None, *, depth: int = 0):
    h = _norm(cfg, norm_in, x)
    ran = {}
    if kind in _HYBRID or (kind == "full" and cfg.differential):
        mixed, ran = _hybrid_mixer(cfg, kind, mixer, h, seg, mesh, schedule, given, depth)
        x = _add(cfg, x, mixed)
    elif kind == "full" and cfg.attention == "mla":
        with jax.named_scope("seq.attn"):
            mixed, ran = _latent_mixer(cfg, mixer, h, seg, pos, mesh, schedule)
            x = _add(cfg, x, mixed)
    elif kind == "full":
        with jax.named_scope("seq.attn"):
            x = _add(cfg, x, _attention_mixer(cfg, mixer, h, seg, pos, mesh, schedule))
    elif kind == "conv":
        with jax.named_scope("seq.shortconv"):
            mixed, ran = short_conv(mixer, h, seg, compute_dtype=_dt(cfg.compute_dtype),
                                    gate_dtype=_dt(cfg.gate_dtype))
            x = _add(cfg, x, mixed)
    elif kind == "ssm":
        with jax.named_scope("seq.ssm"):
            mixed, ran = mamba2(
                mixer, h, seg, heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
                state=cfg.mamba_d_state, eps=cfg.rms_norm_eps, chunk=cfg.chunk,
                compute_dtype=_dt(cfg.compute_dtype), state_dtype=_dt(cfg.state_dtype),
                gate_dtype=_dt(cfg.gate_dtype))
            x = _add(cfg, x, mixed)
    else:
        with jax.named_scope("seq.deltanet"):
            mixed, ran = gated_deltanet(
                mixer, h, seg, key_heads=cfg.linear_num_key_heads,
                value_heads=cfg.linear_num_value_heads, key_dim=cfg.linear_key_head_dim,
                value_dim=cfg.linear_value_head_dim, eps=cfg.rms_norm_eps, chunk=cfg.chunk,
                compute_dtype=_dt(cfg.compute_dtype), state_dtype=_dt(cfg.state_dtype),
                gate_dtype=_dt(cfg.gate_dtype))
            x = _add(cfg, x, mixed)
    y, counters = _ffn(cfg, ffn, _norm(cfg, norm_post, x))
    return _add(cfg, x, y), counters, ran


def _layer_fn(cfg: BackboneConfig, kind: str, mesh, schedule, depth: int = 0):
    """One layer whose mixer is of ``kind`` (``BackboneConfig.kinds``) as ``(x,
    seg, pos, norm_in, mixer, norm_post, ffn) -> x, counters, ran``, recomputed
    in the backward pass; a layer that reads what a layer below handed on
    (``_READS``) takes that, a dict, as one more argument. ``depth``: the
    layer's index in the published model, which a differential layer's
    ``lambda`` starts from."""
    return jax.checkpoint(lambda *a: _layer(cfg, kind, mesh, schedule, *a, depth=depth), policy=_KEPT.get(kind))


def hidden_states(cfg: BackboneConfig, params: Dict, tokens, seg, mesh=None,
                  schedule: str = "auto"):
    """tokens, seg [B, L] -> the residual stream after the last layer
    [B, L, D] (float32, before the final norm); the expert layers'
    counters, stacked [periods, layers of a period, ...]; and what the
    first mixer of each period that says so handed its inner kernel and got
    back (the delta rule's q, k, v, g, beta and o:
    ``ops.deltanet.gated_deltanet``; latent attention's q, k, v and o; the
    short convolution's ``bcx`` and ``y``: ``ops.shortconv.short_conv``; the
    state-space scan's ``u``, ``B``, ``C``, ``dt`` and ``y``: ``ops.ssd.mamba2``;
    the selective scan's ``c``, ``dt``, ``B``, ``C``, ``y`` and ``m``:
    ``ops.selscan.mamba1``, and beside them the first differential layer's
    ``q``, ``k``, ``v``, ``lam`` and ``o``: ``_differential_mixer``;
    stacked [periods, B, ...]; empty where no mixer of a period does)."""
    pos = positions_of(seg)
    with jax.named_scope("seq.embed"):
        x = params["embed"][tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.positions == "learned":
            table = params["pos"]
            if tokens.shape[1] > table.shape[0]:
                raise ValueError(
                    f"sequence length {tokens.shape[1]} exceeds the model's positional "
                    f"table ({table.shape[0]} positions: trained with a shorter seq_len)")
            x = x + table[pos]

    layer_of = {kind: _layer_fn(cfg, kind, mesh, schedule) for kind in set(cfg.kinds)}
    for j in range(cfg.first_k_dense_replace):
        kind = cfg.kinds[0]
        d = jax.tree_util.tree_map(lambda a, j=j: a[j], params["dense"])
        x, _, _ = layer_of[kind](x, seg, pos, d["norm_in"], d[kind], d["norm_post"], d["ffn"])

    def one_period(x, per):
        counters, first_ran, given = [], {}, {}
        for j, kind in enumerate(cfg.period_kinds):
            pick = lambda tree, j=j: jax.tree_util.tree_map(lambda a: a[j], tree)  # noqa: E731
            mixer = _mixer_of(cfg, per, j)
            layer = layer_of[kind] if not cfg.differential else _layer_fn(
                cfg, kind, mesh, schedule, cfg.layer_index_offset + cfg.first_k_dense_replace + j)
            reads = ({name: given[name] for name in _READS[kind]},) if kind in _READS else ()
            x, c, ran = layer(x, seg, pos, pick(per["norm_in"]), mixer, pick(per["norm_post"]),
                              pick(per["ffn"]), *reads)
            given.update({name: ran[name] for name in _HANDS.get(kind, ()) if name in ran})
            counters.append(c)
            # every name from the first mixer of the period that gives it
            first_ran = {**ran, **first_ran}
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *counters)
        return x, (stacked, first_ran)

    x, (counters, ran) = jax.lax.scan(one_period, x, params["periods"])
    return x, counters, ran


def head_of(params: Dict):
    return params["head"] if "head" in params else params["embed"]


def logits_of(cfg: BackboneConfig, params: Dict, hidden, norm: Optional[Dict] = None):
    """hidden [..., D] (before the final norm, or before ``norm``, the
    prediction module's own) -> logits [..., V], float32."""
    cd = _dt(cfg.compute_dtype)
    with jax.named_scope("seq.head"):
        h = _norm(cfg, params["final_norm"] if norm is None else norm, hidden)
        logits = jnp.dot(h.astype(cd), head_of(params).T.astype(cd),
                         preferred_element_type=jnp.float32)
        return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def next_item_loss(cfg: BackboneConfig, params: Dict, hidden, targets, valid,
                   norm: Optional[Dict] = None):
    """Mean cross entropy of the real targets; logits are made a block of
    tokens at a time and made again in the backward pass."""
    d = hidden.shape[-1]
    h, t, m = hidden.reshape(-1, d), targets.reshape(-1), valid.reshape(-1)
    blk = cfg.loss_block if h.shape[0] % cfg.loss_block == 0 else h.shape[0]

    @jax.checkpoint
    def block(total, xs):
        hb, tb, mb = xs
        logits = logits_of(cfg, params, hb, norm)
        with jax.named_scope("seq.head"):
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
            return total + jnp.sum(jnp.where(mb, logz - picked, 0.0)), None

    xs = (h.reshape(-1, blk, d), t.reshape(-1, blk), m.reshape(-1, blk))
    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), xs)
    return total / jnp.maximum(m.sum(), 1)


def split_rows(rows, segs):
    """Packed rows [B, L + 1] -> inputs, their segments, targets and which
    targets count: the next slot of the same history."""
    valid = (segs[:, 1:] == segs[:, :-1]) & (segs[:, :-1] > 0)
    return rows[:, :-1], segs[:, :-1], rows[:, 1:], valid


def split_rows_mtp(rows, segs):
    """Packed rows [B, L + 1] -> what the prediction module is given and
    asked at slot i: the id at i + 1, the id at i + 2 as target (the last
    slot has none), and which targets count: i, i + 1 and i + 2 lie in one
    history."""
    same = (segs[:, :-2] == segs[:, 1:-1]) & (segs[:, 1:-1] == segs[:, 2:]) & (segs[:, :-2] > 0)
    last = ((0, 0), (0, 1))
    return rows[:, 1:], jnp.pad(rows[:, 2:], last), jnp.pad(same, last)


def mtp_hidden(cfg: BackboneConfig, params: Dict, hidden, next_tokens, seg, mesh=None,
               schedule: str = "auto"):
    """The multi-token-prediction module: ``[rms(embed(t_i+1)) | rms(h_i)]
    W_eh``, then one block of the model's own kind (its routed experts
    too). hidden [B, L, D] as :func:`hidden_states` gives it -> [B, L, D]
    (before the module's own last norm) and the block's counters."""
    m, cd = params["mtp"], _dt(cfg.compute_dtype)
    both = jnp.concatenate([_norm(cfg, m["enorm"], params["embed"][next_tokens]),
                            _norm(cfg, m["hnorm"], hidden)], -1)
    x = jnp.dot(both.astype(cd), m["eh_proj"].astype(cd), preferred_element_type=jnp.float32)
    blk = m["block"]
    x, counters, _ = _layer_fn(cfg, "full", mesh, schedule)(
        x, seg, positions_of(seg), blk["norm_in"], blk["full"], blk["norm_post"], blk["ffn"])
    return x, counters


def loss_fn(cfg: BackboneConfig, params: Dict, rows, segs, mesh=None,
            schedule: str = "auto"):
    """The training loss of one batch of packed rows, and (aux) the final
    hidden states, the counters and what the first mixers ran on. With a
    prediction module the loss is next-item + ``mtp_loss_weight`` x the
    module's; the counters then carry ``mtp_loss`` and its block's own as
    ``mtp_<name>``, and the third aux ``mtp_hidden``."""
    tokens, seg, targets, valid = split_rows(rows, segs)
    hidden, counters, ran = hidden_states(cfg, params, tokens, seg, mesh, schedule)
    loss = next_item_loss(cfg, params, hidden, targets, valid)
    if cfg.num_nextn_predict_layers:
        with jax.named_scope("seq.mtp"):
            next_tokens, targets, valid = split_rows_mtp(rows, segs)
            x, mtp_counters = mtp_hidden(cfg, params, hidden, next_tokens, seg, mesh, schedule)
            mtp_loss = next_item_loss(cfg, params, x, targets, valid, params["mtp"]["norm"])
        loss = loss + cfg.mtp_loss_weight * mtp_loss
        counters = {**counters, "mtp_loss": mtp_loss,
                    **{f"mtp_{name}": value for name, value in mtp_counters.items()}}
        ran = {**ran, "mtp_hidden": x}
    return loss, (hidden, counters, ran)


def step_routers(cfg: BackboneConfig, before: Dict, after: Dict, counters: Dict) -> Dict:
    """``after`` (the parameters an optimizer step made of ``before``) with
    what that step does not decide about the routers put right. Every
    router's bias is set to ``b + router_bias_rate * sign(mean load -
    load)``, b from ``before`` (the optimizer's weight decay does not reach
    it), the loads the step's own ``router_tokens`` over all experts; an
    expert at exactly the mean is left where it is (sign 0). Where
    ``router_trains`` is off every router's matrix is ``before``'s. With
    neither, ``after`` as it is."""
    if cfg.ffn != "moe" or (cfg.router_trains and not cfg.router_bias):
        return after

    def stepped(bias, tokens):
        load = tokens.astype(jnp.float32)
        return bias + cfg.router_bias_rate * jnp.sign(load.mean(-1, keepdims=True) - load)

    def put(tree, path, leaf):
        return leaf if not path else {**tree, path[0]: put(tree[path[0]], path[1:], leaf)}

    sites = [(("periods", "ffn"), "router_tokens")]
    if "mtp" in after:
        sites.append((("mtp", "block", "ffn"), "mtp_router_tokens"))
    with jax.named_scope("seq.router_bias"):
        for path, counted in sites:
            ffn = functools.reduce(lambda tree, key: tree[key], path, before)
            if cfg.router_bias:
                after = put(after, path + ("router_bias",),
                            stepped(ffn["router_bias"], counters[counted]))
            if not cfg.router_trains:
                after = put(after, path + ("router",), ffn["router"])
    return after


def conv_kinds(cfg: BackboneConfig, length: int) -> Dict[str, str]:
    """What runs the short convolutions of the mixers over rows of
    ``length`` slots, as ``{"conv": "pallas"}`` or ``"xla"``
    (``ops.shortconv.conv_kind`` at the channels each mixer convolves, where
    they start in its wide projection and the dtype of its taps); nothing for
    a backbone without one."""
    d, qk = cfg.hidden_size, cfg.linear_num_key_heads * cfg.linear_key_head_dim
    inner = cfg.mamba_n_heads * cfg.mamba_d_head
    chains = {  # channels, taps, their dtype, where the parts start
        "linear": (2 * qk + cfg.linear_num_value_heads * cfg.linear_value_head_dim,
                   cfg.linear_conv_kernel_dim, "float32", (0,)),
        "ssm": (inner + 2 * cfg.mamba_d_state, cfg.mamba_d_conv, "float32", (inner,)),
        "mamba1": (cfg.mamba_expand * d, cfg.mamba_d_conv, "float32", (0,)),
        "conv": (d, cfg.conv_L_cache, cfg.gate_dtype, (0, d, 2 * d)),
    }
    ran = {conv_kind(channels, length, dtype, offsets, taps=taps)
           for kind, (channels, taps, dtype, offsets) in chains.items() if kind in cfg.kinds}
    return {"conv": "+".join(sorted(ran))} if ran else {}


# -- the decoder-hybrid-decoder's mixers (below the frames the Pallas kernels
# record: PERF.md section 7) --------------------------------------------------
#: the four learned vectors of a differential layer's lambda
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _check_hybrid(cfg: BackboneConfig, merged: Dict) -> None:
    """What ``from_dict`` asks of a configuration with ``mamba1``,
    ``sliding_attention``, ``gmu`` or ``cross_attention`` layers or with
    differential attention, each refusal in words."""
    kinds = cfg.kinds
    words = {kind: word for word, kind in _KINDS.items()}
    for i, kind in enumerate(cfg.period_kinds):
        if kind in _PRODUCERS:
            producer, word, product = _PRODUCERS[kind]
            if producer not in cfg.period_kinds[:i]:
                raise ValueError(
                    f"layer {cfg.first_k_dense_replace + i} is a {words[kind]} layer and no "
                    f"{word} layer below it in its period hands it {product}")
    hybrid = set(_HYBRID) & set(kinds)
    if hybrid & set(kinds[:cfg.first_k_dense_replace]):
        raise ValueError("the leading dense layers hand nothing on: mamba1, sliding_attention, "
                         "gmu and cross_attention layers belong to the periods")
    if {"swa", "cross"} & hybrid and not cfg.differential:
        raise ValueError("sliding_attention and cross_attention layers run differential "
                         "attention here: the backbone group has to say differential")
    if cfg.differential:
        if cfg.attention != "gqa" or cfg.attn_gate or cfg.qk_norm or cfg.positions == "rotary":
            raise ValueError("differential attention here is grouped-query attention without "
                             "a gate, a norm on q and k or rotary positions")
        if cfg.num_attention_heads % 2 or cfg.num_key_value_heads % 2 or (
                cfg.num_attention_heads % cfg.num_key_value_heads):
            raise ValueError("differential attention pairs up query heads and key heads: "
                             "both counts even, the first a multiple of the second")
        if cfg.n_periods != 1 or cfg.num_nextn_predict_layers:
            raise ValueError("a differential layer's lambda starts from its depth: the layers "
                             "after the dense ones have to be ONE period, with no prediction module")
    if "swa" in kinds and cfg.sliding_window <= 0:
        raise ValueError("sliding_attention layers need sliding_window: no default is assumed")
    if "mamba1" in kinds:
        sizes = ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")
        missing = [name for name in sizes if not getattr(cfg, name)]
        if missing:
            raise ValueError(f"mamba1 layers need {', '.join(missing)}: no default is assumed")
        if not merged.get("mamba_conv_bias", True) or merged.get("mamba_proj_bias"):
            raise ValueError("the Mamba-1 mixer here has a bias on its convolution "
                             "and none on its projections")


def lambda_init(depth: int) -> float:
    """Where a differential layer's lambda starts, by the layer's depth."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * depth))


def _differential_mixer(cfg: BackboneConfig, p: Dict, x, seg, mesh, schedule, depth: int,
                        window: int = 0, given: Optional[Dict] = None):
    """Differential attention on grouped heads: query heads (2p, 2p + 1) are
    pair p, key heads (2c, 2c + 1) key pair c, value heads (2c, 2c + 1) side
    by side ONE value of twice the head; query pair p reads key pair ``p //
    (pairs a key pair)``. Per pair ``(softmax(q1 k1) - lambda softmax(q2 k2))
    v``, an RMS norm over the value's width, ``1 - lambda_init``, ``W_o``.
    Both softmaxes are ONE call of the attention core: the members lie along
    its head axis, first of all pairs, then second, over the values twice.
    ``given`` (a cross-attention layer): the ``k`` and ``v`` of the full
    layer below, as that layer's call of this function returned them. Also
    returns the ``q``, ``k``, ``v`` the core was handed [B, 2 pairs, L, .],
    ``lam`` and ``o``, the difference before the norm [B, pairs, L, 2 hd]."""
    b, l, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    xc = x.astype(cd)

    def members_first(t, heads):  # [B, L, heads * hd] -> [B, member, pair, L, hd] -> [B, heads, L, hd]
        return t.reshape(b, l, heads // 2, 2, hd).transpose(0, 3, 2, 1, 4).reshape(b, heads, l, hd)

    q = members_first(jnp.dot(xc, p["w_q"].astype(cd), preferred_element_type=f32).astype(cd), h)
    if given is None:
        k = members_first(
            jnp.dot(xc, p["w_k"].astype(cd), preferred_element_type=f32).astype(cd), hkv)
        v = jnp.dot(xc, p["w_v"].astype(cd), preferred_element_type=f32).astype(cd)
        v = v.reshape(b, l, hkv // 2, 2 * hd).transpose(0, 2, 1, 3)
        v = jnp.concatenate([v, v], axis=1)  # each member's softmax over the pair's one value
    else:
        k, v = given["k"], given["v"]
    start = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    with jax.named_scope("seq.attn.swa.core" if window else "seq.attn.core"):
        both = attention(q, k, v, mesh=mesh, causal=True, schedule=schedule, segment_ids=seg,
                         block=cfg.attn_block, window=window).astype(f32)
        o = both[:, : h // 2] - lam * both[:, h // 2:]  # [B, pairs, L, 2 hd]
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps) * (
        p["subln"] * (1.0 - start))
    out = jnp.dot(normed.transpose(0, 2, 1, 3).reshape(b, l, h * hd).astype(cd),
                  p["w_o"].astype(cd), preferred_element_type=f32)
    return out, {"q": q, "k": k, "v": v, "lam": lam, "o": o}


def _hybrid_mixer(cfg: BackboneConfig, kind: str, p: Dict, h, seg, mesh, schedule,
                  given: Optional[Dict], depth: int):
    """The mixers of a decoder-hybrid-decoder, ``h`` the layer's normed
    input: a Mamba-1 layer, a gated memory unit on the scan output ``m`` a
    Mamba-1 layer below handed on, or differential attention (full; inside a
    window: ``seq.attn.swa`` around the layer, its core ``seq.attn.swa.core``;
    or queries alone onto the ``k``, ``v`` a full layer below handed on)."""
    if kind == "mamba1":
        with jax.named_scope("seq.mamba"):
            return mamba1(
                p, h, seg, state=cfg.mamba_d_state, dt_rank=cfg.mamba_dt_rank, chunk=cfg.chunk,
                compute_dtype=_dt(cfg.compute_dtype), state_dtype=_dt(cfg.state_dtype),
                gate_dtype=_dt(cfg.gate_dtype))
    if kind == "gmu":
        with jax.named_scope("seq.gmu"):
            return gated_memory(p, h, given["m"], compute_dtype=_dt(cfg.compute_dtype)), {}
    with jax.named_scope("seq.attn"):
        if kind == "swa":
            with jax.named_scope("seq.attn.swa"):
                return _differential_mixer(cfg, p, h, seg, mesh, schedule, depth,
                                           window=cfg.sliding_window)
        return _differential_mixer(cfg, p, h, seg, mesh, schedule, depth,
                                   given=given if kind == "cross" else None)


def window_tiles(cfg: BackboneConfig, length: int) -> Dict[str, int]:
    """``attn_tiles_skipped_by_window``: the tiles of the blockwise attention
    loop that the window alone leaves out, over the sliding layers of one
    forward pass of a row of ``length`` slots; nothing for a backbone without
    such a layer."""
    layers = cfg.kinds.count("swa")
    if not layers:
        return {}
    return {"attn_tiles_skipped_by_window":
            layers * tiles_skipped_by_window(length, cfg.attn_block, cfg.sliding_window)}


#: what a layer's recomputation does not make again, by the layer's kind: the
#: selective scan's output and the states its backward pass starts from
#: (``ops.selscan``), the state-space scan's likewise where its kernel runs
#: (``ops.ssd``); every other layer keeps nothing, as before
_KEPT = {"mamba1": jax.checkpoint_policies.save_only_these_names("selscan"),
         "ssm": jax.checkpoint_policies.save_only_these_names("ssd")}


def ssd_shape(cfg: BackboneConfig, length: int):
    """What ``ops.ssd.scan_kind`` asks of a Mamba-2 layer over rows of
    ``length`` slots: heads, head and state widths, length, chunk, the
    state's and the gates' dtypes."""
    return (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, length, cfg.chunk,
            _dt(cfg.state_dtype), _dt(cfg.gate_dtype))
