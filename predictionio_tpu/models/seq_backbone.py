"""The sequence recommender's backbone: a decoder block that is a function
of a configuration.

A configuration gives the layer pattern (``layer_types``: a mixer a layer, in
the ``words`` of the table ``_MIXERS`` below, the one place that knows a kind
of mixer: its words, parameters, op, scope, counters and refusals; or
``hybrid_override_pattern``: a letter a layer, each layer ONE part, ``x <- x +
part(norm(x))``, the part a mixer or the expert layer, which is then a record
of the table like any mixer (``ffn`` is ``none``: no second norm, no
feed-forward behind the mixer); or
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
file gives one, each a SwiGLU or, ``expert_act`` ``relu2``, the ungated pair
``W_d relu(W_u h)^2``; ``swiglu``; ``gelu``; or ``none``), how many leading
layers are dense instead (``first_k_dense_replace``, ``num_dense_layers``), whether a
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

import contextlib
import dataclasses
import functools
import json
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import chosen_core, deltanet, dsa, indexer_kl, selscan, shortconv, ssd
from ..ops.attention import attention, chosen_attention, tiles_skipped_by_window
from ..ops.deltanet import gated_deltanet
from ..ops.moe import expert_layer, swiglu
from ..ops.selscan import gated_memory, mamba1
from ..ops.shortconv import short_conv
from ..ops.ssd import mamba2

CONF_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "conf", "backbones")


@dataclasses.dataclass(frozen=True)
class BackboneConfig:
    hidden_size: int = 64
    num_hidden_layers: int = 2
    full_attention_interval: int = 1
    #: the mixer of every layer in a public file's words (``_MIXERS``); empty
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
    #: learned sparse attention (``layer_types`` ``sparse_attention``; a public
    #: file's ``sa_config``): a lightning indexer of ``index_n_heads`` heads of
    #: ``index_head_dim`` on ONE index key a slot scores every (query, key)
    #: pair, and a query's grouped heads read the ``index_topk`` causal keys of
    #: its history with the largest scores (``ops.dsa``). The indexer reads the
    #: layer's normed input behind a ``stop_gradient`` and learns from its own
    #: loss alone, the KL of the main heads' attention over the chosen keys
    #: from the scores' softmax over them, summed over the layers with weight
    #: 1; rotary on the first half of its head; its head-weighted sum in
    #: ``index_dtype``. 0 = not given.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_dtype: str = "float32"
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
    #: state's width a head, the taps of its convolution, and the groups of
    #: neighbouring heads that share B and C, each with a gated norm of its
    #: own (one: all heads share them). 0 = not given.
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
    #: the feed-forward behind every mixer; "none": a layer is ONE part (a
    #: ``hybrid_override_pattern``), its one norm ``norm_in``
    ffn: str = "gelu"  # "gelu" | "swiglu" | "moe" | "none"
    #: an expert's feed-forward, routed and shared alike (``ops.moe.ACTS``)
    expert_act: str = "swiglu"  # "swiglu" | "relu2"
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
        """The mixer of every layer: a key of ``_MIXERS``."""
        if self.layer_types:
            return tuple(_KIND_OF[t] for t in self.layer_types)
        p = self.full_attention_interval
        one = ("linear",) * (p - 1) + ("full",)
        k = self.first_k_dense_replace
        return ("full",) * k + one * ((self.num_hidden_layers - k) // p)

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        """One period: the shortest run of mixers that the layers after the
        leading dense ones are whole repeats of (all of them, where they
        repeat nothing: a cut of a pattern that is not periodic is one period)."""
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
        """Layers by the ``name`` of the mixer they run (``_MIXERS``; softmax
        attention counts as ``gqa`` or ``mla``, the prediction module's block
        too)."""
        found = [_MIXERS[k].name or self.attention for k in self.kinds]
        found += [self.attention] * self.num_nextn_predict_layers
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
        # (a public file's null says nothing: the default stands)
        values = {k: v for k, v in merged.items() if k in names and v is not None}
        for name in ("experts_held", "layer_types"):
            if name in values:
                values[name] = tuple(values[name])
        # what a public file says in its own words
        if merged.get("topk_method") == "noaux_tc" or merged.get("use_expert_bias"):
            values.setdefault("router_bias", True)
        for theirs, ours in (("num_dense_layers", "first_k_dense_replace"),
                             ("norm_eps", "rms_norm_eps"),
                             # a ``nemotron_h`` file's words
                             ("layer_norm_epsilon", "rms_norm_eps"),
                             ("mamba_num_heads", "mamba_n_heads"),
                             ("mamba_head_dim", "mamba_d_head"),
                             ("ssm_state_size", "mamba_d_state"), ("conv_kernel", "mamba_d_conv"),
                             ("n_groups", "mamba_n_groups"),
                             ("moe_shared_expert_intermediate_size",
                              "shared_expert_intermediate_size")):
            if theirs in merged:
                values.setdefault(ours, merged[theirs])
        for theirs in ("mamba_chunk_size", "chunk_size"):
            if "chunk" not in merged.get("backbone", {}) and theirs in merged:
                values["chunk"] = merged[theirs]
        if merged.get("mlp_hidden_act") == "relu2":
            values.setdefault("expert_act", "relu2")
        if "hybrid_override_pattern" in merged:  # a letter a layer, each layer one part
            pattern = merged["hybrid_override_pattern"]
            if "-" in pattern:
                raise ValueError("hybrid_override_pattern has '-', a dense MLP as a layer of its "
                                 "own: the layers here are mixers and expert layers")
            values.setdefault("layer_types", tuple(pattern))
            values.setdefault("ffn", "none")
        if merged.get("position_embedding_type") == "nope":
            values.setdefault("positions", "none")
        # a dense Granite's feed-forward is its "shared" SwiGLU
        if "shared_intermediate_size" in merged and not merged.get("num_local_experts"):
            values["intermediate_size"] = merged["shared_intermediate_size"]
        if "rope_theta" in merged.get("rope_parameters", {}):
            values.setdefault("rope_theta", merged["rope_parameters"]["rope_theta"])
        for theirs, ours in (("indexer_num_heads", "index_n_heads"),
                             ("indexer_head_dim", "index_head_dim"), ("topk", "index_topk")):
            if theirs in merged.get("sa_config", {}):
                values.setdefault(ours, merged["sa_config"][theirs])
        if "sa_config" in merged:  # every layer of such a file is a sparse-attention layer
            values.setdefault("layer_types",
                              ("sparse_attention",) * values.get("num_hidden_layers", 0))
        if "n_shared_experts" in merged:
            values.setdefault(
                "shared_expert_intermediate_size",
                merged["n_shared_experts"] * merged["moe_intermediate_size"])
        cfg = cls(**values)
        if cfg.layer_types:
            unknown = sorted(set(cfg.layer_types) - set(_KIND_OF))
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
        for kind, mixer in _MIXERS.items():  # each mixer's own refusals
            if kind in cfg.kinds:
                mixer.check(cfg, merged)
        if cfg.positions not in ("learned", "rotary", "none"):
            raise ValueError(f"positions {cfg.positions!r}: learned, rotary or none")
        if cfg.ffn == "none" and (cfg.first_k_dense_replace or cfg.num_nextn_predict_layers):
            raise ValueError("layers of one part have no dense feed-forward to lead with "
                             "and no prediction module")
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
    'zero', 'one', 'embed', or a kind of its own. A mixer's are its record's
    (``_MIXERS``); a period's mixers are stacked by kind, [periods, layers of
    that kind in a period, ...] (``BackboneConfig.stacked``)."""
    d, p, n = cfg.hidden_size, cfg.period, cfg.n_periods
    norm = {"w": ((d,), "zero")} if cfg.norm == "rms" else {
        "g": ((d,), "one"), "b": ((d,), "zero")}

    def lead(tree, *axes):
        return jax.tree_util.tree_map(
            lambda leaf: (tuple(axes) + leaf[0], leaf[1]), tree, is_leaf=_is_spec)

    m = cfg.intermediate_size
    gated = {"wg": ((d, m), "w"), "wu": ((d, m), "w"), "wd": ((m, d), "w")}
    if cfg.ffn == "moe":
        ffn = _experts_shapes(cfg)
    elif cfg.ffn == "swiglu":
        ffn = gated
    else:
        ffn = {"mlp_in": ((d, m), "w"), "mlp_out": ((m, d), "w")}
    periods = {"norm_in": lead(norm, n, p)}
    if cfg.ffn != "none":
        periods.update(norm_post=lead(norm, n, p), ffn=lead(ffn, n, p))
    for kind in set(cfg.period_kinds):
        held, mixer = cfg.stacked(kind), _MIXERS[kind].shapes(cfg)
        periods[kind] = lead(mixer, n, held) if held else lead(mixer, n)
    shapes = {"embed": ((vocab, d), "embed"), "final_norm": norm, "periods": periods}
    if cfg.first_k_dense_replace:
        kind = cfg.kinds[0]
        shapes["dense"] = lead(
            {kind: _MIXERS[kind].shapes(cfg), "norm_in": norm, "norm_post": norm, "ffn": gated},
            cfg.first_k_dense_replace)
    if cfg.num_nextn_predict_layers:
        shapes["mtp"] = {
            "enorm": norm, "hnorm": norm, "eh_proj": ((2 * d, d), "w"), "norm": norm,
            "block": {"full": _MIXERS["full"].shapes(cfg), "norm_in": norm, "norm_post": norm,
                      "ffn": ffn},
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
    layout of the plain references (``testing/*_reference.py``): a layer's
    mixer under its kind (a key of ``_MIXERS``; latent attention under
    ``attn``), its feed-forward under ``moe`` or ``mlp`` (a leading dense
    layer's always ``mlp``), its norms under ``input_norm`` and ``post_norm``
    (a layer of one part: its part under its kind, its one norm under
    ``norm``), an RMS norm as its one leaf and a LayerNorm as
    ``{"g", "b"}``, the prediction module under ``mtp``. Works on any pytree
    of the parameters' structure: gradients too."""
    full_key = "attn" if cfg.attention == "mla" else "full"
    ffn_key = "moe" if cfg.ffn == "moe" else "mlp"

    def norm(p):  # an RMS norm is its one leaf, a LayerNorm its two
        return p["w"] if "w" in p else p

    def block(blk, kind="full", ffn_key=ffn_key):
        mixer = {full_key if kind == "full" else kind: blk[kind]}
        if "ffn" not in blk:  # a layer of one part
            return {"norm": norm(blk["norm_in"]), **mixer}
        return {"input_norm": norm(blk["norm_in"]), "post_norm": norm(blk["norm_post"]),
                ffn_key: blk["ffn"], **mixer}

    layers = []
    for j in range(cfg.first_k_dense_replace):
        dense = jax.tree_util.tree_map(lambda leaf, j=j: leaf[j], params["dense"])
        layers.append(block(dense, cfg.kinds[0], ffn_key="mlp"))
    per = params["periods"]
    for n in range(cfg.n_periods):
        at_n = jax.tree_util.tree_map(lambda leaf, n=n: leaf[n], per)
        for j, kind in enumerate(cfg.period_kinds):
            blk = {name: jax.tree_util.tree_map(lambda leaf, j=j: leaf[j], at_n[name])
                   for name in ("norm_in", "norm_post", "ffn") if name in at_n}
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


# -- the mixers -------------------------------------------------------------
def _unit(x, eps: float):
    """x over its root mean square along the last axis: an RMS norm before its scale."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _layer_norm(x, g, b, eps: float):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _norm(cfg: BackboneConfig, p: Dict, x):
    if cfg.norm == "rms":
        return _unit(x, cfg.rms_norm_eps) * (1.0 + p["w"])
    return _layer_norm(x, p["g"], p["b"], cfg.layer_norm_eps)


def _dot(cfg: BackboneConfig, x, w):
    """x w: both in ``compute_dtype``, the sum in float32."""
    cd = _dt(cfg.compute_dtype)
    return jnp.dot(x.astype(cd), w.astype(cd), preferred_element_type=jnp.float32)


def _dtypes(cfg: BackboneConfig) -> Dict:
    return dict(compute_dtype=_dt(cfg.compute_dtype), state_dtype=_dt(cfg.state_dtype),
                gate_dtype=_dt(cfg.gate_dtype))


# gated DeltaNet (``ops.deltanet``)
def _deltanet_shapes(cfg: BackboneConfig) -> Dict:
    d, hk, hv = cfg.hidden_size, cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return {
        "w_qkvz": ((d, 2 * hk * dk + 2 * hv * dv), "w"), "w_ba": ((d, 2 * hv), "w"),
        "conv_w": ((cfg.linear_conv_kernel_dim, 2 * hk * dk + hv * dv), "w"),
        "A_log": ((hv,), "a_log"), "dt_bias": ((hv,), "dt_bias"),
        "o_norm": ((dv,), "one"), "w_out": ((hv * dv, d), "w"),
    }


def _deltanet_widths(cfg: BackboneConfig) -> Dict:
    return dict(key_heads=cfg.linear_num_key_heads, value_heads=cfg.linear_num_value_heads,
                key_dim=cfg.linear_key_head_dim, value_dim=cfg.linear_value_head_dim,
                eps=cfg.rms_norm_eps, chunk=cfg.chunk, **_dtypes(cfg))


# the gated short convolution (``ops.shortconv``)
def _shortconv_shapes(cfg: BackboneConfig) -> Dict:
    d = cfg.hidden_size
    return {"w_in": ((d, 3 * d), "w"), "conv_w": ((cfg.conv_L_cache, d), "w"),
            "w_out": ((d, d), "w")}


def _shortconv_widths(cfg: BackboneConfig) -> Dict:
    return dict(compute_dtype=_dt(cfg.compute_dtype), gate_dtype=_dt(cfg.gate_dtype))


def _shortconv_check(cfg: BackboneConfig, merged: Dict) -> None:
    if merged.get("conv_bias"):
        raise ValueError("the short convolution and its projections carry no bias here")


# Mamba-2 (``ops.ssd``) and Mamba-1 (``ops.selscan``)
def _mamba_check(cfg: BackboneConfig, merged: Dict, word: str, name: str, sizes) -> None:
    missing = [size for size in sizes if not getattr(cfg, size)]
    if missing:
        raise ValueError(f"{word} layers need {', '.join(missing)}: no default is assumed")
    if (not merged.get("mamba_conv_bias", True) or not merged.get("use_conv_bias", True)
            or merged.get("mamba_proj_bias") or merged.get("use_bias")):
        raise ValueError(f"the {name} mixer here has a bias on its convolution "
                         "and none on its projections")


def _mamba2_shapes(cfg: BackboneConfig) -> Dict:
    d, mh = cfg.hidden_size, cfg.mamba_n_heads
    ns = cfg.mamba_n_groups * cfg.mamba_d_state  # B's and C's columns: a group after the other
    inner = mh * cfg.mamba_d_head
    return {
        "w_in": ((d, 2 * inner + 2 * ns), "w"), "w_dt": ((d, mh), "w"),
        "conv_w": ((cfg.mamba_d_conv, inner + 2 * ns), "w"),
        "conv_b": ((inner + 2 * ns,), "zero"),
        "A_log": ((mh,), "a_log"), "dt_bias": ((mh,), "dt_bias"), "D": ((mh,), "one"),
        "norm": ((inner,), "one"), "w_out": ((inner, d), "w"),
    }


def _mamba2_widths(cfg: BackboneConfig) -> Dict:
    return dict(heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head, state=cfg.mamba_d_state,
                eps=cfg.rms_norm_eps, chunk=cfg.chunk, groups=cfg.mamba_n_groups, **_dtypes(cfg))


def _mamba2_check(cfg: BackboneConfig, merged: Dict) -> None:
    _mamba_check(cfg, merged, "mamba", "Mamba-2",
                 ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv"))
    if cfg.mamba_n_groups < 1 or cfg.mamba_n_heads % cfg.mamba_n_groups:
        raise ValueError(f"mamba_n_groups is {cfg.mamba_n_groups or 'not given'}: the groups "
                         f"that share B and C are equal runs of the {cfg.mamba_n_heads} heads")
    # (a ``granitemoehybrid`` file's key, whose inner width the public code makes from it;
    # a ``nemotron_h`` file's ``expand`` is not read: there the inner width is heads x
    # head width whatever ``expand`` x hidden says)
    if "mamba_expand" in merged and (
            merged["mamba_expand"] * cfg.hidden_size != cfg.mamba_n_heads * cfg.mamba_d_head):
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head")


def _mamba1_shapes(cfg: BackboneConfig) -> Dict:
    d, ns, rank = cfg.hidden_size, cfg.mamba_d_state, cfg.mamba_dt_rank
    wide = cfg.mamba_expand * d
    return {
        "w_in": ((d, 2 * wide), "w"), "conv_w": ((cfg.mamba_d_conv, wide), "w"),
        "conv_b": ((wide,), "zero"), "w_x": ((wide, rank + 2 * ns), "w"),
        "w_dt": ((rank, wide), "w"), "dt_bias": ((wide,), "dt_bias"),
        "A_log": ((wide, ns), "a_log_range"), "D": ((wide,), "one"),
        "w_out": ((wide, d), "w"),
    }


def _mamba1_widths(cfg: BackboneConfig) -> Dict:
    return dict(state=cfg.mamba_d_state, dt_rank=cfg.mamba_dt_rank, chunk=cfg.chunk,
                **_dtypes(cfg))


def _mamba1_check(cfg: BackboneConfig, merged: Dict) -> None:
    _in_a_period(cfg, "mamba1")
    _mamba_check(cfg, merged, "mamba1", "Mamba-1",
                 ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"))


# what a decoder-hybrid-decoder's upper layers read of the layers below them
def _in_a_period(cfg: BackboneConfig, kind: str) -> None:
    if kind in cfg.kinds[:cfg.first_k_dense_replace]:
        raise ValueError("the leading dense layers hand nothing on: mamba1, sliding_attention, "
                         "gmu and cross_attention layers belong to the periods")


def _handed(cfg: BackboneConfig, kind: str, product: str) -> None:
    """No layer of ``kind`` is in a leading dense layer's place, and each has,
    below it in its period, a layer of the kind that hands on what it reads
    (``product``, in words)."""
    mine = _MIXERS[kind]
    by = next(k for k, m in _MIXERS.items() if set(mine.reads) <= set(m.hands))
    for i, k in enumerate(cfg.period_kinds):
        if k == kind and by not in cfg.period_kinds[:i]:
            raise ValueError(
                f"layer {cfg.first_k_dense_replace + i} is a {mine.words[0]} layer and no "
                f"{_MIXERS[by].words[0]} layer below it in its period hands it {product}")
    _in_a_period(cfg, kind)


def _gmu(cfg: BackboneConfig, p: Dict, h, seg, pos, given, depth, mesh, schedule):
    """A gated memory unit on the scan output ``m`` that the last Mamba-1 layer
    below it handed on."""
    return gated_memory(p, h, given["m"], compute_dtype=_dt(cfg.compute_dtype)), {}


# softmax attention (``ops.attention``): grouped, latent or differential; whole,
# inside a window, or onto the keys and values of the full layer below
#: the four learned vectors of a differential layer's lambda
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _attention_shapes(cfg: BackboneConfig) -> Dict:
    d, h, hkv, hd = (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    if cfg.attention == "mla":
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        return {
            "w_qa": ((d, rq), "w"), "q_norm": ((rq,), "zero"), "w_qb": ((rq, h * (dn + dr)), "w"),
            "w_kva": ((d, rkv + dr), "w"), "kv_norm": ((rkv,), "zero"),
            "w_kvb": ((rkv, h * (dn + dv)), "w"), "w_o": ((h * dv, d), "w"),
        }
    full = {
        "w_q": ((d, h * hd * (2 if cfg.attn_gate else 1)), "w"),
        "w_k": ((d, hkv * hd), "w"), "w_v": ((d, hkv * hd), "w"),
        "w_o": ((h * hd, d), "w"),
    }
    if cfg.attn_gate or cfg.qk_norm:
        full.update(q_norm=((hd,), "zero"), k_norm=((hd,), "zero"))
    if cfg.differential:
        full.update({name: ((hd,), "lambda") for name in _LAMBDAS}, subln=((2 * hd,), "one"))
    return full


def _cross_shapes(cfg: BackboneConfig) -> Dict:
    return {name: spec for name, spec in _attention_shapes(cfg).items()
            if name not in ("w_k", "w_v")}


def _window_widths(cfg: BackboneConfig) -> Dict:
    return dict(window=cfg.sliding_window)


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


def _latent_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, given, depth, mesh, schedule):
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
    with jax.named_scope("seq.attn.latent"):
        # the wide projections are kept in the compute dtype, as in the other mixers
        q = _dot(cfg, _unit(_dot(cfg, x, p["w_qa"]), eps) * (1.0 + p["q_norm"]), p["w_qb"])
        q = q.astype(cd).reshape(b, l, h, dn + dr)
        kva = _dot(cfg, x, p["w_kva"])
        k_rope = _rope_pairs(kva[:, :, None, rkv:], pos, cfg.rope_theta)
        kv = _dot(cfg, _unit(kva[..., :rkv], eps) * (1.0 + p["kv_norm"]), p["w_kvb"]).astype(cd)
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
    out = _dot(cfg, o.transpose(0, 2, 1, 3).reshape(b, l, h * dv), p["w_o"])
    return out + _dot(cfg, v_mean.reshape(b, 1, h * dv), p["w_o"]), {"q": q, "k": k, "v": v, "o": o}


def _grouped_heads(cfg: BackboneConfig, p: Dict, x, pos):
    """The grouped heads of a layer's normed input ``x``: q [B, L, H, hd], k, v
    [B, L, Hkv, hd] float32 (projected, normed a head where the layer has the
    norms, turned by position; :func:`_for_the_core` makes them the core's), and
    what ``w_q`` gave [B, L, .], whose second half is the gate where there is one."""
    b, l, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    xc = x.astype(cd)
    # the wide projections are kept in the compute dtype, as in the DeltaNet mixer
    qg = _dot(cfg, xc, p["w_q"]).astype(cd)
    q = qg[..., : h * hd].reshape(b, l, h, hd).astype(f32)
    k = _dot(cfg, xc, p["w_k"]).reshape(b, l, hkv, hd)
    v = _dot(cfg, xc, p["w_v"]).reshape(b, l, hkv, hd)
    if "q_norm" in p:
        q = _unit(q, cfg.rms_norm_eps) * (1.0 + p["q_norm"])
        k = _unit(k, cfg.rms_norm_eps) * (1.0 + p["k_norm"])
    if cfg.positions == "rotary":
        rot = int(cfg.partial_rotary_factor * hd)
        q, k = _rope(q, pos, rot, cfg.rope_theta), _rope(k, pos, rot, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        # the core scales by 1 / sqrt(hd): q carries the rest
        q = q * (cfg.attention_multiplier * hd ** 0.5)
    return q, k, v, qg


def _for_the_core(cfg: BackboneConfig, *heads):
    """[B, L, H, hd] -> [B, H, L, hd] in the compute dtype (inside the core's
    scope: the cast and the transpose are seconds of the core's)."""
    return tuple(t.astype(_dt(cfg.compute_dtype)).transpose(0, 2, 1, 3) for t in heads)


def _attention_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, given, depth, mesh, schedule):
    b, l, _ = x.shape
    h, hd = cfg.num_attention_heads, cfg.head_dim
    q, k, v, qg = _grouped_heads(cfg, p, x, pos)
    with jax.named_scope("seq.attn.core"):
        o = attention(*_for_the_core(cfg, q, k, v), mesh=mesh, causal=True, schedule=schedule,
                      segment_ids=seg, block=cfg.attn_block)
    o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd).astype(jnp.float32)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(qg[..., h * hd:].astype(jnp.float32))
    return _dot(cfg, o, p["w_o"]), {}


def _sparse_shapes(cfg: BackboneConfig) -> Dict:
    d, j, di = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
    return {**_attention_shapes(cfg),  # and the indexer's: three projections, its key's LayerNorm
            "w_iq": ((d, j * di), "w"), "w_ik": ((d, di), "w"), "w_iw": ((d, j), "w"),
            "ik_g": ((di,), "one"), "ik_b": ((di,), "zero")}


def _sparse_widths(cfg: BackboneConfig) -> Dict:
    return dict(topk=cfg.index_topk, block=cfg.attn_block, sum_dtype=_dt(cfg.index_dtype))


def _index_heads(cfg: BackboneConfig, p: Dict, x, pos):
    """The lightning indexer's inputs from a layer's normed input ``x``: J
    query heads iq [B, L, J, d] and ONE key a slot ik [B, L, d] (a LayerNorm
    over it), both turned by position on the first half of the head (the
    DeepSeek-V3.2 report's proportion), in the compute dtype; and the heads' weights iw [B, L, J]
    float32, ``J ** -0.5 * d ** -0.5`` folded in."""
    b, l, _ = x.shape
    j, di = cfg.index_n_heads, cfg.index_head_dim
    cd = _dt(cfg.compute_dtype)
    rot = di // 2
    iq = _dot(cfg, x, p["w_iq"]).reshape(b, l, j, di)
    ik = _layer_norm(_dot(cfg, x, p["w_ik"]), p["ik_g"], p["ik_b"], cfg.layer_norm_eps)
    iq = _rope(iq, pos, rot, cfg.rope_theta)
    ik = _rope(ik[:, :, None, :], pos, rot, cfg.rope_theta)[:, :, 0]
    iw = _dot(cfg, x, p["w_iw"]) * (j ** -0.5 * di ** -0.5)
    return iq.astype(cd), ik.astype(cd), iw


def _sparse_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, given, depth, mesh, schedule):
    """Grouped-query attention that reads only the keys a learned indexer
    picks (``ops.dsa``). The indexer sees the layer's normed input behind a
    ``stop_gradient``; the choice has no gradient; the indexer's loss holds the
    main heads' weights constant. Also returns, beside the layer's counters
    (``index_loss``, the mean KL of the real slots, and the ``kept_pairs`` among
    their ``causal_pairs`` inside histories), what a check of the choice
    needs: the indexer's inputs ``iq``, ``ik``, ``iw``, one strip of scores as
    the choice saw them (``index``, strip ``index_at``), the chosen mask packed
    8 keys a byte (``chosen`` [B, L, ceil(L / 8)] uint8, key ``8 w + bit`` at
    bit ``bit``), and the first key head's group as the core was handed it and what
    it gave: ``q``, ``o`` [B, G, L, hd], ``k``, ``v`` [B, 1, L, hd]."""
    if mesh is not None:
        raise ValueError("sparse attention runs on a single device: no sharded schedule "
                         "takes a mask that the step computes")
    b, l, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v, _ = _grouped_heads(cfg, p, x, pos)
    widths = _sparse_widths(cfg)
    topk = widths.pop("topk")
    with jax.named_scope("seq.attn.index"):
        iq, ik, iw = _index_heads(cfg, p, jax.lax.stop_gradient(x), pos)
    # the scopes of the choice are the op's own: seq.attn.index around a
    # strip's scores, seq.attn.select around its threshold and mask
    chosen, kept, causal, sample, at = dsa.select(
        *jax.lax.stop_gradient((iq, ik, iw)), seg, topk=topk, **widths)
    with jax.named_scope("seq.attn.core"):
        q, k, v = _for_the_core(cfg, q, k, v)
        o, lse = chosen_attention(q, k, v, chosen, seg, block=cfg.attn_block)
    with jax.named_scope("seq.attn.index_loss"):
        loss, slots = dsa.index_loss(iq, ik, iw, q, k, lse, seg, chosen, **widths)
    out = _dot(cfg, o.transpose(0, 2, 1, 3).reshape(b, l, h * hd).astype(jnp.float32), p["w_o"])
    g = h // hkv
    return out, {
        "index_loss": loss / jnp.maximum(slots, 1), "kept_pairs": kept.sum(),
        "causal_pairs": causal.sum(), "iq": iq, "ik": ik, "iw": iw, "index": sample,
        "index_at": at, "q": q[:, :g], "k": k[:, :1], "v": v[:, :1], "o": o[:, :g],
        "chosen": jnp.packbits(chosen, axis=-1, bitorder="little"),
    }


def _sparse_forms(cfg: BackboneConfig, length: int) -> Dict:
    heads = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
    return {**chosen_core.forms(*heads, length),
            **indexer_kl.forms(*heads, cfg.index_n_heads, cfg.index_head_dim, length,
                               _dt(cfg.index_dtype))}


def _sparse_check(cfg: BackboneConfig, merged: Dict) -> None:
    _in_a_period(cfg, "dsa")
    _attention_check(cfg, merged)
    missing = [size for size in ("index_n_heads", "index_head_dim", "index_topk")
               if not getattr(cfg, size)]
    if missing:
        raise ValueError(f"sparse_attention layers need {', '.join(missing)} "
                         "(a public file's sa_config): no default is assumed")
    if merged.get("sa_config", {}).get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer here has ONE index key a slot (indexer_num_kv_heads 1)")
    if cfg.attention != "gqa" or cfg.differential or cfg.attn_gate:
        raise ValueError("sparse attention here is grouped-query attention without a gate")


def lambda_init(depth: int) -> float:
    """Where a differential layer's lambda starts, by the layer's depth."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * depth))


def _differential_mixer(cfg: BackboneConfig, p: Dict, x, seg, pos, given, depth, mesh, schedule,
                        *, window: int = 0):
    """Differential attention on grouped heads: query heads (2p, 2p + 1) are
    pair p, key heads (2c, 2c + 1) key pair c, value heads (2c, 2c + 1) side
    by side ONE value of twice the head; query pair p reads key pair ``p //
    (pairs a key pair)``. Per pair ``(softmax(q1 k1) - lambda softmax(q2 k2))
    v``, an RMS norm over the value's width, ``1 - lambda_init``, ``W_o``.
    Both softmaxes are ONE call of the attention core: the members lie along
    its head axis, first of all pairs, then second, over the values twice.
    ``given`` (a cross-attention layer's, else empty): the ``k`` and ``v`` of
    the full layer below, as that layer's call of this function returned them.
    ``window``: a sliding layer's (``_window_widths``).
    Also returns the ``q``, ``k``, ``v`` the core was handed [B, 2 pairs, L, .],
    ``lam`` and ``o``, the difference before the norm [B, pairs, L, 2 hd]."""
    b, l, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    xc = x.astype(cd)

    def members_first(t, heads):  # [B, L, heads * hd] -> [B, member, pair, L, hd] -> [B, heads, L, hd]
        return t.reshape(b, l, heads // 2, 2, hd).transpose(0, 3, 2, 1, 4).reshape(b, heads, l, hd)

    q = members_first(_dot(cfg, xc, p["w_q"]).astype(cd), h)
    if not given:
        k = members_first(_dot(cfg, xc, p["w_k"]).astype(cd), hkv)
        v = _dot(cfg, xc, p["w_v"]).astype(cd)
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
    normed = _unit(o, cfg.rms_norm_eps) * (p["subln"] * (1.0 - start))
    out = _dot(cfg, normed.transpose(0, 2, 1, 3).reshape(b, l, h * hd), p["w_o"])
    return out, {"q": q, "k": k, "v": v, "lam": lam, "o": o}


def _full(cfg: BackboneConfig, *layer):
    """The attention the configuration names: differential, latent or grouped."""
    mixer = _differential_mixer if cfg.differential else (
        _latent_mixer if cfg.attention == "mla" else _attention_mixer)
    return mixer(cfg, *layer)


def _swa(cfg: BackboneConfig, *layer):
    return _differential_mixer(cfg, *layer, **_window_widths(cfg))


def _window_forms(cfg: BackboneConfig, length: int) -> Dict:
    """The tiles of the blockwise attention loop that the window alone leaves
    out, over the sliding layers of one forward pass of a row of ``length`` slots."""
    return {"attn_tiles_skipped_by_window": cfg.kinds.count("swa") * tiles_skipped_by_window(
        length, cfg.attn_block, **_window_widths(cfg))}


def _attention_check(cfg: BackboneConfig, merged: Dict) -> None:
    if merged.get("attention_bias"):
        raise ValueError("the attention projections carry no bias here")
    if not cfg.differential:
        return
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


def _differential_check(cfg: BackboneConfig, merged: Dict) -> None:
    if not cfg.differential:
        raise ValueError("sliding_attention and cross_attention layers run differential "
                         "attention here: the backbone group has to say differential")
    _attention_check(cfg, merged)


def _swa_check(cfg: BackboneConfig, merged: Dict) -> None:
    _in_a_period(cfg, "swa")
    _differential_check(cfg, merged)
    if cfg.sliding_window <= 0:
        raise ValueError("sliding_attention layers need sliding_window: no default is assumed")


def _cross_check(cfg: BackboneConfig, merged: Dict) -> None:
    _handed(cfg, "cross", "keys and values")
    _differential_check(cfg, merged)


# the expert layer (``ops.moe``): a layer's feed-forward, or a layer of its own
def _experts_shapes(cfg: BackboneConfig) -> Dict:
    d, f, fs = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    count = cfg.experts_held[1]

    def expert(width, *lead):  # an expert's matrices: the gate's only where it has a gate
        mats = {"wg": (lead + (d, width), "w"), "wu": (lead + (d, width), "w"),
                "wd": (lead + (width, d), "w")}
        return mats if cfg.expert_act == "swiglu" else {k: mats[k] for k in ("wu", "wd")}

    ffn = {"router": ((d, cfg.router_width), "w"), "experts": expert(f, count)}
    if fs:
        ffn["shared"] = expert(fs)
        if cfg.shared_expert_gate:
            ffn["shared_gate"] = ((d,), "w_vec")
    if cfg.router_bias:
        ffn["router_bias"] = ((cfg.router_width,), "zero")
    return ffn


def _experts_widths(cfg: BackboneConfig) -> Dict:
    return dict(first=cfg.experts_held[0], top_k=cfg.num_experts_per_tok,
                norm_topk=cfg.norm_topk_prob, compute_dtype=_dt(cfg.compute_dtype),
                scoring=cfg.scoring_func, scale=cfg.routed_scaling_factor,
                norm_eps=cfg.norm_topk_eps, act=cfg.expert_act)


#: what an expert layer's step counts (``ops.moe.expert_layer``)
_EXPERT_COUNTS = ("expert_tokens", "absent_weight", "dropped", "passes", "router_tokens")


def _experts(cfg: BackboneConfig, p: Dict, h, seg, pos, given, depth, mesh, schedule):
    """The expert layer as a layer of its own. Also returns, beside the step's
    counters, what it was handed and gave: ``moe_in``, ``moe_out`` [B, L, D]."""
    b, l, d = h.shape
    y, counters = expert_layer(p, h.reshape(b * l, d), **_experts_widths(cfg))
    y = y.reshape(b, l, d)
    return y, {**counters, "moe_in": h, "moe_out": y}


def _experts_check(cfg: BackboneConfig, merged: Dict) -> None:
    if cfg.ffn != "none":
        raise ValueError("an expert layer is a layer of its own only where every layer is one "
                         "part (a hybrid_override_pattern); elsewhere it is the layers' ffn")
    if merged.get("mlp_bias"):
        raise ValueError("the experts' projections carry no bias here")
    if merged.get("n_group", 1) != 1 or merged.get("topk_group", 1) != 1:
        raise ValueError("the router here keeps the top k of all experts: no limit by "
                         "groups of experts (n_group, topk_group)")
    missing = [size for size in ("router_width", "num_experts_per_tok", "moe_intermediate_size")
               if not getattr(cfg, size)] + ([] if cfg.experts_held[1] else ["experts_held"])
    if missing:
        raise ValueError(f"expert layers need {', '.join(missing)}: no default is assumed")


# -- the table ----------------------------------------------------------------
class _Mixer(NamedTuple):
    """Everything the backbone knows about one kind of mixer. ``words``: the
    public files' words for it in ``layer_types``, and its letter in a
    ``hybrid_override_pattern``. ``name``: what
    ``BackboneConfig.mixers`` counts it as; empty = ``cfg.attention``.
    ``scope``: the named scopes around the mixer and its residual addition,
    outermost first; the benchmark's per-layer metrics read these names.
    ``shapes(cfg)``: its parameters' ``(shape, kind)`` tree (``_shapes``).
    ``run(cfg, p, h, seg, pos, given, depth, mesh, schedule) -> (y, ran)``: ``h``
    the layer's normed input, ``given`` what it ``reads``, ``depth`` the layer's
    index in the published model, ``ran`` what its inner loops were handed and
    gave; a function of this module that finds its op in the module's globals
    when it is called, so a test that replaces the op there runs the
    replacement. ``widths(cfg)``: the keyword arguments its op takes from the
    configuration; what ``run`` hands the op is what ``forms`` asks the op's
    module about. ``forms(cfg, length)``: the counters that say which form of
    its inner loops runs over rows of ``length`` slots. ``hands``: of ``ran``,
    what the layers above it in its period may read; ``reads``: what it reads
    of that, the newest below it. ``kept``: what its layer's recomputation
    keeps and does not make again, a name that ``checkpoint_name`` gave: the
    scan's output and the states its backward pass starts from, where the
    scan's kernel runs; the two numbers a query of the indexers' loss, where
    its kernel does. ``counts``: of ``ran``, the layer's own counters (those of
    them that this step counted), which leave the layer beside its
    feed-forward's (a loss of the mixer's own among them: ``loss_fn`` adds
    it). ``check(cfg, merged)``: raises its refusals of
    a configuration in words (``merged``: the public file's keys and the
    ``backbone`` group's)."""
    words: Tuple[str, ...]
    name: str
    scope: Tuple[str, ...]
    shapes: Callable
    run: Callable
    widths: Callable = lambda cfg: {}
    forms: Callable = lambda cfg, length: {}
    hands: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()
    kept: Optional[str] = None
    counts: Tuple[str, ...] = ()
    check: Callable = lambda cfg, merged: None


def _of_op(op: Callable, forms: Callable, shapes: Callable, widths: Callable, **fields) -> _Mixer:
    """The record of a mixer that is one op of ``ops/``: ``op()(p, h, seg,
    **widths(cfg))`` (``op`` a thunk: the op is looked up when a layer is
    traced), and the ``forms`` of the op's own module, asked with the
    parameters' shapes and the same keyword arguments."""
    def run(cfg, p, h, seg, pos, given, depth, mesh, schedule):
        return op()(p, h, seg, **widths(cfg))

    def forms_of(cfg, length):
        plain = {name: shape for name, (shape, _) in shapes(cfg).items()}
        return forms(plain, length, **widths(cfg))

    return _Mixer(shapes=shapes, widths=widths, run=run, forms=forms_of, **fields)


#: kind (what the parameters are stacked under) -> its record
_MIXERS: Dict[str, _Mixer] = {
    # gated DeltaNet
    "linear": _of_op(
        lambda: gated_deltanet, deltanet.forms, _deltanet_shapes, _deltanet_widths,
        words=("linear_attention",), name="deltanet", scope=("seq.deltanet",)),
    # the gated short convolution
    "conv": _of_op(
        lambda: short_conv, shortconv.forms, _shortconv_shapes, _shortconv_widths,
        words=("conv",), name="shortconv", scope=("seq.shortconv",), check=_shortconv_check),
    # the Mamba-2 state-space mixer
    "ssm": _of_op(
        lambda: mamba2, ssd.forms, _mamba2_shapes, _mamba2_widths,
        words=("mamba", "M"), name="mamba2", scope=("seq.ssm",), kept="ssd",
        check=_mamba2_check),
    # the Mamba-1 selective scan
    "mamba1": _of_op(
        lambda: mamba1, selscan.forms, _mamba1_shapes, _mamba1_widths,
        words=("mamba1",), name="mamba1", scope=("seq.mamba",), hands=("m",), kept="selscan",
        check=_mamba1_check),
    # a gated memory unit that reads the scan output of the last ``mamba1`` layer below it
    "gmu": _Mixer(
        words=("gmu",), name="gmu", scope=("seq.gmu",), run=_gmu, reads=("m",),
        shapes=lambda cfg: {"w_1": ((cfg.hidden_size, cfg.mamba_expand * cfg.hidden_size), "w"),
                            "w_2": ((cfg.mamba_expand * cfg.hidden_size, cfg.hidden_size), "w")},
        check=lambda cfg, merged: _handed(cfg, "gmu", "a scan output")),
    # softmax attention over the whole history: grouped, latent or differential
    "full": _Mixer(
        words=("full_attention", "attention", "*"), name="", scope=("seq.attn",),
        shapes=_attention_shapes, run=_full, hands=("k", "v"), check=_attention_check),
    # grouped-query attention over the keys a lightning indexer picks
    "dsa": _Mixer(
        words=("sparse_attention",), name="dsa", scope=("seq.attn",), shapes=_sparse_shapes,
        widths=_sparse_widths, run=_sparse_mixer, forms=_sparse_forms, kept=indexer_kl.KEPT,
        counts=("index_loss", "kept_pairs", "causal_pairs"), check=_sparse_check),
    # differential attention inside ``sliding_window`` slots: itself and the ones before
    "swa": _Mixer(
        words=("sliding_attention",), name="swa", scope=("seq.attn", "seq.attn.swa"),
        shapes=_attention_shapes, widths=_window_widths, run=_swa, forms=_window_forms,
        check=_swa_check),
    # queries of its own onto the keys and values of the last ``full`` layer below it
    "cross": _Mixer(
        words=("cross_attention",), name="cross", scope=("seq.attn",), shapes=_cross_shapes,
        run=_differential_mixer, reads=("k", "v"), check=_cross_check),
    # the expert layer where it is a layer of its own (every layer one part) and no mixer's ffn
    "moe": _Mixer(
        words=("E",), name="moe", scope=("seq.moe",), shapes=_experts_shapes,
        widths=_experts_widths, run=_experts, counts=_EXPERT_COUNTS, check=_experts_check,
        forms=lambda cfg, length: {"expert_act": cfg.expert_act}),
}
_KIND_OF = {word: kind for kind, mixer in _MIXERS.items() for word in mixer.words}
#: the recomputation policy that keeps a name, ONE object a name: layers whose
#: policies are two objects share none of their inner functions in the lowered step
_POLICIES = {mixer.kept: jax.checkpoint_policies.save_only_these_names(mixer.kept)
             for mixer in _MIXERS.values() if mixer.kept}


def mechanisms(cfg: BackboneConfig, length: int) -> Dict:
    """The counters that say which form of its mixers' inner loops a job over
    rows of ``length`` slots runs: every record's ``forms`` over the kinds the
    backbone has, ``conv`` the forms its mixers' short convolutions run in,
    sorted and joined by ``+``."""
    found = [mixer.forms(cfg, length) for kind, mixer in _MIXERS.items() if kind in cfg.kinds]
    convs = {forms.pop("conv") for forms in found if "conv" in forms}
    merged = {name: form for forms in found for name, form in forms.items()}
    return {**merged, **({"conv": "+".join(sorted(convs))} if convs else {})}


# -- the block --------------------------------------------------------------
def _ffn(cfg: BackboneConfig, p: Dict, x):
    """The feed-forward its parameters describe: routed experts, a SwiGLU
    or the GELU pair."""
    if "router" in p:
        b, l, d = x.shape
        with jax.named_scope("seq.moe"):
            y, counters = expert_layer(p, x.reshape(b * l, d), **_experts_widths(cfg))
        return y.reshape(b, l, d), counters
    if "wg" in p:
        with jax.named_scope("seq.ffn"):
            return swiglu(p, x, _dt(cfg.compute_dtype)), {}
    return _dot(cfg, jax.nn.gelu(_dot(cfg, x, p["mlp_in"])), p["mlp_out"]), {}


def _add(cfg: BackboneConfig, x, y):
    """The residual stream ``x`` after a mixer or a feed-forward gave ``y``."""
    return x + y if cfg.residual_multiplier == 1.0 else x + cfg.residual_multiplier * y


def _layer(cfg: BackboneConfig, kind: str, mesh, schedule, x, seg, pos,
           norm_in, mixer, norm_post, ffn, given=None, *, depth: int = 0):
    record = _MIXERS[kind]
    h = _norm(cfg, norm_in, x)
    with contextlib.ExitStack() as scopes:
        for name in record.scope:
            scopes.enter_context(jax.named_scope(name))
        mixed, ran = record.run(cfg, mixer, h, seg, pos, given, depth, mesh, schedule)
        x = _add(cfg, x, mixed)
    counters = {}
    if cfg.ffn != "none":  # the layer's second part
        y, counters = _ffn(cfg, ffn, _norm(cfg, norm_post, x))
        x = _add(cfg, x, y)
    if record.counts:
        counters = {**counters, **{name: ran.pop(name) for name in record.counts if name in ran}}
    return x, counters, ran


def _layer_fn(cfg: BackboneConfig, kind: str, mesh, schedule, depth: int = 0):
    """One layer whose mixer is of ``kind`` (a key of ``_MIXERS``) as ``(x, seg,
    pos, norm_in, mixer, norm_post, ffn, given) -> x, counters, ran`` (``norm_post``
    and ``ffn``: None where a layer is one part), recomputed
    in the backward pass but for what its record says is ``kept``; ``given``, a
    dict: what the layer reads of the layers below it. ``depth``: the layer's
    index in the published model, which a differential layer's ``lambda``
    starts from."""
    return jax.checkpoint(lambda *a: _layer(cfg, kind, mesh, schedule, *a, depth=depth),
                          policy=_POLICIES.get(_MIXERS[kind].kept))


def hidden_states(cfg: BackboneConfig, params: Dict, tokens, seg, mesh=None,
                  schedule: str = "auto"):
    """tokens, seg [B, L] -> the residual stream after the last layer
    [B, L, D] (float32, before the final norm); the expert layers'
    counters, stacked [periods, layers of a period, ...]; and ``ran``: what
    the first mixer of each period that gives a name handed its inner loops
    and got back, as its op or mixer function documents it (the ``run`` of
    its record in ``_MIXERS``), stacked [periods, B, ...]; empty where no
    mixer of a period gives any."""
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

    # A layer knows its place in the published model only where a mixer starts
    # from it (a differential layer's lambda); layers of one kind that do not
    # are ONE function, traced and lowered once.
    @functools.cache
    def layer_of(kind, depth):
        return _layer_fn(cfg, kind, mesh, schedule, depth)

    def depth(j):
        return cfg.layer_index_offset + j if cfg.differential else 0

    for j in range(cfg.first_k_dense_replace):
        kind = cfg.kinds[0]
        d = jax.tree_util.tree_map(lambda a, j=j: a[j], params["dense"])
        x, _, _ = layer_of(kind, depth(j))(
            x, seg, pos, d["norm_in"], d[kind], d["norm_post"], d["ffn"])

    def one_period(x, per):
        counters, first_ran, given = [], {}, {}
        for j, kind in enumerate(cfg.period_kinds):
            # (a part the layers do not have: None)
            pick = lambda name, j=j: jax.tree_util.tree_map(lambda a: a[j], per.get(name))  # noqa: E731
            record, mixer = _MIXERS[kind], _mixer_of(cfg, per, j)
            layer = layer_of(kind, depth(cfg.first_k_dense_replace + j))
            x, c, ran = layer(x, seg, pos, pick("norm_in"), mixer, pick("norm_post"),
                              pick("ffn"), {name: given[name] for name in record.reads})
            given.update({name: ran[name] for name in record.hands if name in ran})
            counters.append(c)
            # every name from the first mixer of the period that gives it
            first_ran = {**ran, **first_ran}
        # a counter by the layers of the period that count it, in their order
        names = sorted({name for c in counters for name in c})
        stacked = {name: jnp.stack([c[name] for c in counters if name in c]) for name in names}
        return x, (stacked, first_ran)

    x, (counters, ran) = jax.lax.scan(one_period, x, params["periods"])
    return x, counters, ran


# -- the loss ---------------------------------------------------------------
def head_of(params: Dict):
    return params["head"] if "head" in params else params["embed"]


def logits_of(cfg: BackboneConfig, params: Dict, hidden, norm: Optional[Dict] = None):
    """hidden [..., D] (before the final norm, or before ``norm``, the
    prediction module's own) -> logits [..., V], float32."""
    with jax.named_scope("seq.head"):
        h = _norm(cfg, params["final_norm"] if norm is None else norm, hidden)
        logits = _dot(cfg, h.astype(_dt(cfg.compute_dtype)), head_of(params).T)
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
    m = params["mtp"]
    both = jnp.concatenate([_norm(cfg, m["enorm"], params["embed"][next_tokens]),
                            _norm(cfg, m["hnorm"], hidden)], -1)
    x, blk = _dot(cfg, both, m["eh_proj"]), m["block"]
    x, counters, _ = _layer_fn(cfg, "full", mesh, schedule)(
        x, seg, positions_of(seg), blk["norm_in"], blk["full"], blk["norm_post"], blk["ffn"])
    return x, counters


def loss_fn(cfg: BackboneConfig, params: Dict, rows, segs, mesh=None,
            schedule: str = "auto"):
    """The training loss of one batch of packed rows, and (aux) the final
    hidden states, the counters and what the first mixers ran on. With
    sparse-attention layers the loss is next-item + the sum of the layers'
    ``index_loss`` (the counters carry it a layer). With a
    prediction module the loss is next-item + ``mtp_loss_weight`` x the
    module's; the counters then carry ``mtp_loss`` and its block's own as
    ``mtp_<name>``, and the third aux ``mtp_hidden``."""
    tokens, seg, targets, valid = split_rows(rows, segs)
    hidden, counters, ran = hidden_states(cfg, params, tokens, seg, mesh, schedule)
    loss = next_item_loss(cfg, params, hidden, targets, valid)
    if "index_loss" in counters:  # the indexers' own loss, every sparse-attention layer's
        loss = loss + counters["index_loss"].sum()
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


# -- the routers' step --------------------------------------------------------
def expert_sites(cfg: BackboneConfig, params: Dict):
    """Where the expert layers' parameters lie in ``params``, each path with
    the counter that carries its routers' loads: the periods' feed-forwards
    (and the prediction module's block's), or the periods' expert layers where
    they are layers of their own; none without experts."""
    if cfg.ffn == "moe":
        return [(("periods", "ffn"), "router_tokens")] + (
            [(("mtp", "block", "ffn"), "mtp_router_tokens")] if "mtp" in params else [])
    return [(("periods", "moe"), "router_tokens")] if "moe" in cfg.period_kinds else []


def at_path(tree: Dict, path):
    return functools.reduce(lambda sub, key: sub[key], path, tree)


def step_routers(cfg: BackboneConfig, before: Dict, after: Dict, counters: Dict) -> Dict:
    """``after`` (the parameters an optimizer step made of ``before``) with
    what that step does not decide about the routers put right. Every
    router's bias is set to ``b + router_bias_rate * sign(mean load -
    load)``, b from ``before`` (the optimizer's weight decay does not reach
    it), the loads the step's own ``router_tokens`` over all experts; an
    expert at exactly the mean is left where it is (sign 0). Where
    ``router_trains`` is off every router's matrix is ``before``'s. With
    neither, ``after`` as it is."""
    sites = expert_sites(cfg, after)
    if not sites or (cfg.router_trains and not cfg.router_bias):
        return after

    def stepped(bias, tokens):
        load = tokens.astype(jnp.float32)
        return bias + cfg.router_bias_rate * jnp.sign(load.mean(-1, keepdims=True) - load)

    def put(tree, path, leaf):
        return leaf if not path else {**tree, path[0]: put(tree[path[0]], path[1:], leaf)}

    with jax.named_scope("seq.router_bias"):
        for path, counted in sites:
            ffn = at_path(before, path)
            if cfg.router_bias:
                after = put(after, path + ("router_bias",),
                            stepped(ffn["router_bias"], counters[counted]))
            if not cfg.router_trains:
                after = put(after, path + ("router",), ffn["router"])
    return after
