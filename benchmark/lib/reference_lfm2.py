"""Plain reference of the LFM2-MoE block (``model_type`` ``lfm2_moe``:
LFM2-24B-A2B, LFM2-8B-A1B) as the sequence recommender runs it: forward,
loss and gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``:
the convolution is three shifted adds; attention is a full masked score
matrix, a few heads at a time; the experts are a dense loop over the held
set (every token through every held expert, weighted by its routing weight
or 0); rotary positions are written out half by half; packed rows are
handled by a segment-id mask alone; the optimizer step and the router-bias
rule are numpy. ``benchmark/lib/reference_lfm2.py`` is a copy of this file
(a test holds the two to the same text).

``cfg`` is the configuration as its JSON file states it (the model's own
``config.json`` keys: ``layer_types``, ``num_dense_layers``, ``norm_eps``,
``rope_parameters``, ...) plus ``experts_held`` = [first, count], the
contiguous range of routed experts this share computes (what the absent
experts would add is left out). ``params`` is::

    {"embed": [V, D], "final_norm": [D], "layers": [layer]}      (the head is the embedding)

    layer = {"input_norm": [D], "post_norm": [D], a mixer, and either
             "mlp": ffn (the leading ``num_dense_layers``) or "moe": moe}
    mixer = "conv": {"w_in": [D, 3 D], "conv_w": [K, D], "w_out": [D, D]}, or
            "full": {"w_q": [D, H * hd], "w_k", "w_v": [D, Hkv * hd],
                     "q_norm", "k_norm": [hd], "w_o": [H * hd, D]}
    moe = {"router": [D, E], "router_bias": [E], "experts": ffn with a
           leading [count] axis}                                  (no shared expert)
    ffn = {"wg": [D, F], "wu": [D, F], "wd": [F, D]}

Per token x (the residual stream), as the public implementation has it:

- every layer: ``h = x + mixer(rms(x))``, ``y = h + ffn(rms(h))``; after the
  last a norm and the head.
- ``conv`` (the gated short convolution): ``[B | C | x~] = u W_in``, ``z = B *
  x~``, ``c_t = sum_j k_j * z_(t-j)`` over ``conv_L_cache`` taps (depthwise,
  causal, no bias; ``conv_w[K - 1]`` is the current slot's tap), ``(C * c)
  W_out``. No activation function.
- ``full_attention``: q of H heads, k and v of Hkv; an RMS norm with a
  learned weight over each head of q and of k; rotary over the whole head
  (rotate-half, ``rope_parameters.rope_theta``, positions restart with each
  history); softmax of ``q . k / sqrt(hd)`` over the earlier slots of the
  same history, H / Hkv query heads a key/value head; ``W_o``. No gate.
- feed-forward: the first ``num_dense_layers`` layers a SwiGLU; after them
  ``s = sigmoid(u W_r)`` over ALL experts, the ``num_experts_per_tok``
  largest of ``s + b`` chosen (b: ``use_expert_bias``), weights ``s_i / (sum
  of the chosen s + 1e-6) * routed_scaling_factor`` (b chooses, it does not
  weigh), the held experts' part of ``sum w_i E_i(u)``.
- loss = mean cross entropy of the next id. The bias b has no gradient.

Departures from the public implementation, each the same function or stated
in the configuration's ``assumed``: RMS norms have scale ``1 + w`` with w
starting at 0 (there: ``w`` starting at 1); a tap that would reach into the
neighbouring history of a packed row reads zero (there: one history a row,
the same thing); the bias's rule is DeepSeek-V3's ``b + rate * sign(mean
load - load)`` from the step's own counts (:func:`bias_step`; the config
names the bias and no rule).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def rms_norm(x, w, eps):
    """RMSNorm with scale ``1 + w`` (w starts at 0)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def positions_of(seg):
    """Position of each slot counted from the start of its history."""
    idx = jnp.arange(seg.shape[-1])
    start = jnp.concatenate([jnp.ones_like(seg[..., :1], bool), seg[..., 1:] != seg[..., :-1]], -1)
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=seg.ndim - 1)


# -- the gated short convolution --------------------------------------------
def gated_conv(bcx, conv_w, seg):
    """``C * conv(B * x~)`` of ``bcx`` = [B | C | x~] [L, 3 D]: the taps as
    shifted adds, ``conv_w[K - 1 - j]`` on the slot j back, a slot of
    another history read as zero."""
    d = bcx.shape[-1] // 3
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    taps, length = conv_w.shape[0], z.shape[0]
    total = jnp.zeros_like(z)
    for back in range(taps):
        shifted = jnp.concatenate([jnp.zeros((back, d), z.dtype), z[: length - back]], 0)
        before = jnp.concatenate([jnp.full((back,), -1, seg.dtype), seg[: length - back]], 0)
        total = total + jnp.where((before == seg)[:, None], shifted, 0.0) * conv_w[taps - 1 - back]
    return c * total


@_highest
def gated_conv_of(bcx, conv_w, seg):
    """The gate-taps-gate chain alone on a given ``[B | C | x~]`` of one row
    (any float type) -> float32: what a program that ran on those very
    numbers has to give."""
    return jax.jit(gated_conv)(
        jnp.asarray(bcx, jnp.float32), jnp.asarray(conv_w, jnp.float32), jnp.asarray(seg))


def conv_mixer(p, x, seg):
    """x [L, D] (already normed) -> [L, D]."""
    return gated_conv(x @ p["w_in"], p["conv_w"], seg) @ p["w_out"]


# -- grouped-query attention ------------------------------------------------
def rope_half(t, pos, theta):
    """t [L, H, hd]: dimension j and j + hd / 2 turn together by the angle
    ``pos * theta ** (-2j / hd)`` (rotate-half, over the whole head)."""
    hd = t.shape[-1]
    half = hd // 2
    inv = jnp.asarray([1.0 / theta ** (2.0 * j / hd) for j in range(half)], jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv  # [L, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def softmax_attention(q, k, v, seg, heads_at_once: int = 4):
    """q [H, L, hd], k, v [Hkv, L, hd], seg [L] -> [H, L, hd]: each slot
    over the slots before it, itself included, of its own history; query
    head h reads key/value head ``h // (H / Hkv)``. The full [L, L] score
    matrix of ``heads_at_once`` heads at a time, made again in the backward
    pass."""
    h, length, hd = q.shape
    k, v = (jnp.repeat(t, h // t.shape[0], axis=0) for t in (k, v))
    idx = jnp.arange(length)
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])

    @jax.checkpoint
    def some(qkv):
        q_, k_, v_ = qkv
        s = jnp.einsum("hqd,hkd->hqk", q_, k_) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(keep[None], s, _NEG), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", w, v_)

    n = heads_at_once if h % heads_at_once == 0 else 1
    grouped = tuple(t.reshape((h // n, n) + t.shape[1:]) for t in (q, k, v))
    return jax.lax.map(some, grouped).reshape(h, length, hd)


def attention_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D]."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    length, pos = x.shape[0], positions_of(seg)
    q = rms_norm((x @ p["w_q"]).reshape(length, h, hd), p["q_norm"], eps)
    k = rms_norm((x @ p["w_k"]).reshape(length, hkv, hd), p["k_norm"], eps)
    v = (x @ p["w_v"]).reshape(length, hkv, hd)
    q, k = rope_half(q, pos, theta), rope_half(k, pos, theta)
    o = softmax_attention(*(t.transpose(1, 0, 2) for t in (q, k, v)), seg)
    return o.transpose(1, 0, 2).reshape(length, h * hd) @ p["w_o"]


# -- experts ----------------------------------------------------------------
def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def routing(p, x, cfg):
    """Weights [L, E]: sigmoid scores of ALL experts; the
    ``num_experts_per_tok`` largest of score + bias are chosen; their
    weights are the scores alone, over (their sum + 1e-6), times
    ``routed_scaling_factor``; 0 elsewhere."""
    scores = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-6)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top * cfg["routed_scaling_factor"])


def moe_block(p, x, cfg):
    first, count = cfg["experts_held"]
    weights = routing(p, x, cfg)[:, first: first + count]  # [L, count]

    @jax.checkpoint
    def one(acc, ew):
        w, col = ew
        return acc + col[:, None] * _swiglu(w, x), None

    return jax.lax.scan(one, jnp.zeros_like(x), (p["experts"], weights.T))[0]


def router_counts(p, x, cfg) -> jnp.ndarray:
    """Tokens of every expert [E] among ``x`` [L, D] (already normed)."""
    return (routing(p, x, cfg) > 0).sum(0)


def bias_step(bias, counts, rate):
    """The router's bias after a step that counted ``counts`` [..., E]
    tokens an expert: ``b + rate * sign(mean - count)``; an expert at
    exactly the mean keeps its bias. numpy."""
    counts = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float32) + np.float32(rate) * np.sign(
        counts.mean(-1, keepdims=True) - counts).astype(np.float32)


# -- the model --------------------------------------------------------------
def layer_forward(p, x, seg, cfg):
    eps = cfg["norm_eps"]
    u = rms_norm(x, p["input_norm"], eps)
    x = x + (conv_mixer(p["conv"], u, seg) if "conv" in p else attention_mixer(p["full"], u, seg, cfg))
    h = rms_norm(x, p["post_norm"], eps)
    return x + (moe_block(p["moe"], h, cfg) if "moe" in p else _swiglu(p["mlp"], h))


def split_row(row, seg_row):
    """A packed row of L + 1 slots -> inputs, their segments, targets and
    which targets count: the next slot of the same history (segment 0 is
    padding)."""
    valid = (seg_row[1:] == seg_row[:-1]) & (seg_row[:-1] > 0)
    return row[:-1], seg_row[:-1], row[1:], valid


def _head_loss(norm, head, x, targets, valid, eps):
    """Summed cross entropy of one row's real targets."""
    logits = rms_norm(x, norm, eps) @ head.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def _freeze(cfg: Dict) -> Tuple:
    """The numbers of ``cfg`` the layers read, hashable."""
    flat = {**cfg, "rope_theta": cfg["rope_parameters"]["rope_theta"]}
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in flat.items()
                        if k != "layer_types" and isinstance(v, (int, float, bool, list))))


def _thaw(cfg_items: Tuple) -> Dict:
    cfg = dict(cfg_items)
    return {**cfg, "rope_parameters": {"rope_theta": cfg["rope_theta"]}}


@functools.partial(jax.jit, static_argnums=(3,))
def _layer_jit(p, x, seg, cfg_items):
    return layer_forward(p, x, seg, _thaw(cfg_items))


@functools.partial(jax.jit, static_argnums=(4,))
def _layer_vjp(p, x, seg, dx, cfg_items):
    _, vjp = jax.vjp(lambda p_, x_: layer_forward(p_, x_, seg, _thaw(cfg_items)), p, x)
    return vjp(dx)


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(norm, head, x, targets, valid, eps):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(norm, head, x, targets, valid, eps)


@_highest
def hidden_states(params, tokens, seg, cfg) -> jnp.ndarray:
    """Final hidden states (before the last norm) of one row: [L, D]."""
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = _layer_jit(p, x, seg, _freeze(cfg))
    return x


@_highest
def loss_and_grads(params, rows, segs, cfg, sample=None):
    """Of the packed rows [B, L + 1]: the loss (mean over the real
    targets), its gradient in the layout of ``params`` (``router_bias``:
    zeros, nothing flows to it; ``embed``: the embedding's and the head's
    parts summed) and the logits [len(sample[b]), V] at the slots
    ``sample[b]`` of each row (an empty list without ``sample``). One row,
    then one layer, at a time; gradients are summed on the host."""
    frozen, eps = _freeze(cfg), cfg["norm_eps"]
    rows, segs = np.asarray(rows), np.asarray(segs)
    n_real = max(sum(int(split_row(r, s)[3].sum()) for r, s in zip(rows, segs)), 1)
    grads = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, np.float32), params)
    total = 0.0
    logits: List[np.ndarray] = []

    def add(into, delta, scale):
        for leaf, d in zip(jax.tree_util.tree_leaves(into), jax.tree_util.tree_leaves(delta)):
            leaf += scale * np.asarray(d)

    for b, (row, seg_row) in enumerate(zip(rows, segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        xs = [params["embed"][tokens]]
        for p in params["layers"]:
            xs.append(_layer_jit(p, xs[-1], seg, frozen))
        if sample is not None:
            at = jnp.asarray(sample[b])
            logits.append(np.asarray(
                rms_norm(xs[-1][at], params["final_norm"], eps) @ params["embed"].T))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["embed"], xs[-1], targets, valid, eps)
        total += float(loss)
        add(grads["final_norm"], d_norm, 1.0 / n_real)
        add(grads["embed"], d_head, 1.0 / n_real)
        dx = dx / n_real
        for i in reversed(range(len(params["layers"]))):
            dp, dx = _layer_vjp(params["layers"][i], xs[i], seg, dx, frozen)
            add(grads["layers"][i], dp, 1.0)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    return total / n_real, grads, logits


@_highest
def loss(params, rows, segs, cfg) -> float:
    """The training loss alone."""
    eps = cfg["norm_eps"]
    total, count = 0.0, 0
    for row, seg_row in zip(np.asarray(rows), np.asarray(segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        x = hidden_states(params, tokens, seg, cfg)
        total += float(_head_loss(params["final_norm"], params["embed"], x, targets, valid, eps))
        count += int(valid.sum())
    return total / max(count, 1)


def adamw_first_step(params, grads, learning_rate, b1, b2, eps, weight_decay):
    """The change plain AdamW makes to ``params`` in its first step, from
    moments that start at zero: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``,
    both corrected for their start (``/ (1 - b1)``, ``/ (1 - b2)``),
    ``-lr (m / (sqrt(v) + eps) + wd p)``. numpy, leaf by leaf, float32."""
    def change(p, g):
        p, g = np.asarray(p, np.float32), np.asarray(g, np.float32)
        m = np.float32((1.0 - b1) / (1.0 - b1 ** 1)) * g  # corrected for step 1
        v = np.float32((1.0 - b2) / (1.0 - b2 ** 1)) * g * g
        np.sqrt(v, out=v)
        v += np.float32(eps)
        np.divide(m, v, out=m)
        m += np.float32(weight_decay) * p
        m *= np.float32(-learning_rate)
        return m

    return jax.tree_util.tree_map(change, params, grads)
