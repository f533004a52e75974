"""The sequence recommender's backbone: a decoder block that is a function
of a configuration.

A configuration gives the layer pattern (``full_attention_interval``: every
n-th layer is softmax attention, the others gated DeltaNet; 1 = all
attention), the head and feed-forward widths, the norm (``rms`` with scale
``1 + w``, or ``layer``), the positions (``rotary`` on part of a head, or a
``learned`` table), the feed-forward kind (``moe``: routed experts of which
this share holds a range, plus a shared expert; or ``gelu``) and whether the
head is the embedding. The keys are those of the public models'
``config.json``; what such a file does not state (norm, positions, the
range of experts held, precision) sits in its ``backbone`` group.

Parameters are stacked by period (``full_attention_interval`` layers) and
the periods run in a ``lax.scan``, a model of one period too; each layer is
recomputed in the backward pass. Rows are packed: ``seg`` gives each slot its history's
id (0 = padding), positions count from a history's start, and neither the
convolution, the delta-rule state nor attention crosses a boundary.

Precision: parameters, residual stream, norms, router, softmax, gates,
delta-rule state and loss in float32; matrix products take
``compute_dtype`` inputs (bfloat16 on the chip) and accumulate in float32.
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

from ..ops.attention import attention
from ..ops.deltanet import gated_deltanet
from ..ops.moe import expert_layer

CONF_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "conf", "backbones")


@dataclasses.dataclass(frozen=True)
class BackboneConfig:
    hidden_size: int = 64
    num_hidden_layers: int = 2
    full_attention_interval: int = 1
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    #: the gated attention of Qwen3-Next: an output gate beside the query
    #: and a norm on every head of q and k
    attn_gate: bool = False
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e4
    positions: str = "learned"  # "learned" | "rotary"
    norm: str = "layer"  # "layer" | "rms"
    rms_norm_eps: float = 1e-6
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    ffn: str = "gelu"  # "gelu" | "moe"
    intermediate_size: int = 256
    router_width: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    #: (first, count): the contiguous range of routed experts held here
    experts_held: Tuple[int, int] = (0, 0)
    tie_word_embeddings: bool = True
    #: std of the normal the matrices are drawn from; None = 1/sqrt(fan_in)
    init_std: Optional[float] = None
    compute_dtype: str = "float32"
    state_dtype: str = "float32"
    gate_dtype: str = "float32"
    chunk: int = 64
    attn_block: int = 512
    loss_block: int = 2048

    @property
    def period(self) -> int:
        return self.full_attention_interval

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.period

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
        if "experts_held" in values:
            values["experts_held"] = tuple(values["experts_held"])
        cfg = cls(**values)
        if cfg.num_hidden_layers % cfg.period:
            raise ValueError(
                f"{cfg.num_hidden_layers} layers are not whole periods of {cfg.period}")
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
    'zero', 'one', 'embed', or a kind of its own."""
    d, p, n = cfg.hidden_size, cfg.period, cfg.n_periods
    norm = {"w": ((d,), "zero")} if cfg.norm == "rms" else {
        "g": ((d,), "one"), "b": ((d,), "zero")}

    def lead(tree, *axes):
        return jax.tree_util.tree_map(
            lambda leaf: (tuple(axes) + leaf[0], leaf[1]), tree, is_leaf=_is_spec)

    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    full = {
        "w_q": ((d, h * hd * (2 if cfg.attn_gate else 1)), "w"),
        "w_k": ((d, hkv * hd), "w"), "w_v": ((d, hkv * hd), "w"),
        "w_o": ((h * hd, d), "w"),
    }
    if cfg.attn_gate:
        full.update(q_norm=((hd,), "zero"), k_norm=((hd,), "zero"))
    if cfg.ffn == "moe":
        f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        count = cfg.experts_held[1]
        ffn = {
            "router": ((d, cfg.router_width), "w"), "shared_gate": ((d,), "w_vec"),
            "shared": {"wg": ((d, fs), "w"), "wu": ((d, fs), "w"), "wd": ((fs, d), "w")},
            "experts": {"wg": ((count, d, f), "w"), "wu": ((count, d, f), "w"),
                        "wd": ((count, f, d), "w")},
        }
    else:
        m = cfg.intermediate_size
        ffn = {"mlp_in": ((d, m), "w"), "mlp_out": ((m, d), "w")}
    periods = {
        "full": lead(full, n),
        "norm_in": lead(norm, n, p), "norm_post": lead(norm, n, p),
        "ffn": lead(ffn, n, p),
    }
    if p > 1:
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        periods["linear"] = lead({
            "w_qkvz": ((d, 2 * hk * dk + 2 * hv * dv), "w"), "w_ba": ((d, 2 * hv), "w"),
            "conv_w": ((cfg.linear_conv_kernel_dim, 2 * hk * dk + hv * dv), "w"),
            "A_log": ((hv,), "a_log"), "dt_bias": ((hv,), "dt_bias"),
            "o_norm": ((dv,), "one"), "w_out": ((hv * dv, d), "w"),
        }, n, p - 1)
    shapes = {"embed": ((vocab, d), "embed"), "final_norm": norm, "periods": periods}
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
    layout of ``testing/qwen3_next_reference.py`` (works on any pytree of
    the parameters' structure: gradients too)."""
    per = params["periods"]
    layers = []
    for n in range(cfg.n_periods):
        for j in range(cfg.period):
            take = lambda leaf, n=n, j=j: leaf[n, j]  # noqa: E731
            layer = {
                "input_norm": per["norm_in"]["w"][n, j], "post_norm": per["norm_post"]["w"][n, j],
                "moe": jax.tree_util.tree_map(take, per["ffn"]),
            }
            if j == cfg.period - 1:
                layer["full"] = jax.tree_util.tree_map(lambda leaf, n=n: leaf[n], per["full"])
            else:
                layer["linear"] = jax.tree_util.tree_map(take, per["linear"])
            layers.append(layer)
    return {"embed": params["embed"], "head": params["head"],
            "final_norm": params["final_norm"]["w"], "layers": layers}


# -- the block --------------------------------------------------------------
def _norm(cfg: BackboneConfig, p: Dict, x):
    if cfg.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * (
            1.0 + p["w"])
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["g"] + p["b"]


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
    if cfg.attn_gate:
        eps = cfg.rms_norm_eps

        def head_norm(t, w):
            return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * (1.0 + w)

        q, k = head_norm(q, p["q_norm"]), head_norm(k, p["k_norm"])
    if cfg.positions == "rotary":
        rot = int(cfg.partial_rotary_factor * hd)
        q, k = _rope(q, pos, rot, cfg.rope_theta), _rope(k, pos, rot, cfg.rope_theta)
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
    cd, f32 = _dt(cfg.compute_dtype), jnp.float32
    if cfg.ffn == "moe":
        b, l, d = x.shape
        with jax.named_scope("seq.moe"):
            y, counters = expert_layer(
                p, x.reshape(b * l, d), first=cfg.experts_held[0],
                top_k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
                compute_dtype=cd)
        return y.reshape(b, l, d), counters
    hidden = jax.nn.gelu(jnp.dot(x.astype(cd), p["mlp_in"].astype(cd), preferred_element_type=f32))
    return jnp.dot(hidden.astype(cd), p["mlp_out"].astype(cd), preferred_element_type=f32), {}


def _layer(cfg: BackboneConfig, full: bool, mesh, schedule, x, seg, pos,
           norm_in, mixer, norm_post, ffn):
    h = _norm(cfg, norm_in, x)
    ran = {}
    if full:
        with jax.named_scope("seq.attn"):
            x = x + _attention_mixer(cfg, mixer, h, seg, pos, mesh, schedule)
    else:
        with jax.named_scope("seq.deltanet"):
            mixed, ran = gated_deltanet(
                mixer, h, seg, key_heads=cfg.linear_num_key_heads,
                value_heads=cfg.linear_num_value_heads, key_dim=cfg.linear_key_head_dim,
                value_dim=cfg.linear_value_head_dim, eps=cfg.rms_norm_eps, chunk=cfg.chunk,
                compute_dtype=_dt(cfg.compute_dtype), state_dtype=_dt(cfg.state_dtype),
                gate_dtype=_dt(cfg.gate_dtype))
            x = x + mixed
    y, counters = _ffn(cfg, ffn, _norm(cfg, norm_post, x))
    return x + y, counters, ran


def hidden_states(cfg: BackboneConfig, params: Dict, tokens, seg, mesh=None,
                  schedule: str = "auto"):
    """tokens, seg [B, L] -> the residual stream after the last layer
    [B, L, D] (float32, before the final norm); the expert layers'
    counters, stacked [periods, layers of a period, ...]; and what the
    delta rule of each period's first layer was given and gave
    (``ops.deltanet.gated_deltanet``; stacked [periods, B, L, ...]; empty
    without such a layer)."""
    pos = positions_of(seg)
    with jax.named_scope("seq.embed"):
        x = params["embed"][tokens]
        if cfg.positions == "learned":
            table = params["pos"]
            if tokens.shape[1] > table.shape[0]:
                raise ValueError(
                    f"sequence length {tokens.shape[1]} exceeds the model's positional "
                    f"table ({table.shape[0]} positions: trained with a shorter seq_len)")
            x = x + table[pos]

    def layer_fn(full):
        fn = lambda *a: _layer(cfg, full, mesh, schedule, *a)  # noqa: E731
        return jax.checkpoint(fn)

    linear_layer, full_layer = layer_fn(False), layer_fn(True)
    p = cfg.period

    def one_period(x, per):
        counters, first_ran = [], {}
        for j in range(p):
            pick = lambda tree, j=j: jax.tree_util.tree_map(lambda a: a[j], tree)  # noqa: E731
            full = j == p - 1
            mixer = per["full"] if full else pick(per["linear"])
            x, c, ran = (full_layer if full else linear_layer)(
                x, seg, pos, pick(per["norm_in"]), mixer, pick(per["norm_post"]),
                pick(per["ffn"]))
            counters.append(c)
            if j == 0:
                first_ran = ran
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *counters)
        return x, (stacked, first_ran)

    x, (counters, ran) = jax.lax.scan(one_period, x, params["periods"])
    return x, counters, ran


def head_of(params: Dict):
    return params["head"] if "head" in params else params["embed"]


def logits_of(cfg: BackboneConfig, params: Dict, hidden):
    """hidden [..., D] (before the final norm) -> logits [..., V], float32."""
    cd = _dt(cfg.compute_dtype)
    with jax.named_scope("seq.head"):
        h = _norm(cfg, params["final_norm"], hidden)
        return jnp.dot(h.astype(cd), head_of(params).T.astype(cd),
                       preferred_element_type=jnp.float32)


def next_item_loss(cfg: BackboneConfig, params: Dict, hidden, targets, valid):
    """Mean cross entropy of the real targets; logits are made a block of
    tokens at a time and made again in the backward pass."""
    d = hidden.shape[-1]
    h, t, m = hidden.reshape(-1, d), targets.reshape(-1), valid.reshape(-1)
    blk = cfg.loss_block if h.shape[0] % cfg.loss_block == 0 else h.shape[0]

    @jax.checkpoint
    def block(total, xs):
        hb, tb, mb = xs
        logits = logits_of(cfg, params, hb)
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


def loss_fn(cfg: BackboneConfig, params: Dict, rows, segs, mesh=None,
            schedule: str = "auto"):
    """The training loss of one batch of packed rows, and (aux) the final
    hidden states, the counters and what the first delta rule ran on."""
    tokens, seg, targets, valid = split_rows(rows, segs)
    hidden, counters, ran = hidden_states(cfg, params, tokens, seg, mesh, schedule)
    return next_item_loss(cfg, params, hidden, targets, valid), (hidden, counters, ran)
