"""Plain reference of the Qwen3-Next block as the sequence recommender runs
it: forward, loss and gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``:
the delta rule is the token-by-token recurrence in a ``lax.scan``, the
experts are a dense loop over the held set, attention is a full masked
score matrix per head, and packed rows are handled by a segment-id mask
alone. ``benchmark/lib/reference_qwen3next.py`` is a copy of this file (a
test holds the two to the same text).

``cfg`` is the configuration as its JSON file states it (the model's own
``config.json`` keys) plus ``experts_held`` = [first, count]: the
contiguous range of routed experts this share computes. What the absent
experts would add is left out. ``params`` is::

    {"embed": [V, D], "head": [V, D], "final_norm": [D], "layers": [layer]}

    layer = {"input_norm": [D], "post_norm": [D], "moe": moe, and either
             "linear": {...} or "full": {...}}
    linear = {"w_qkvz": [D, 2*Hk*dk + 2*Hv*dv], "w_ba": [D, 2*Hv],
              "conv_w": [K, 2*Hk*dk + Hv*dv], "A_log": [Hv], "dt_bias": [Hv],
              "o_norm": [dv], "w_out": [Hv*dv, D]}
    full = {"w_q": [D, 2*H*hd], "w_k": [D, Hkv*hd], "w_v": [D, Hkv*hd],
            "q_norm": [hd], "k_norm": [hd], "w_o": [H*hd, D]}
    moe = {"router": [D, E], "shared_gate": [D], "shared": ffn,
           "experts": ffn with a leading [count] axis}
    ffn = {"wg": [D, F], "wu": [D, F], "wd": [F, D]}

Column order inside the fused projections is plain concatenation
([q, k, v, z], [b, a], [q, gate]); the published checkpoint interleaves
them by head, which random weights cannot tell apart.

Departures from the published model: no auxiliary balance loss, no
multi-token-prediction head.
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


def is_full_attention(cfg: Dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def rms_norm(x, w, eps):
    """RMSNorm with scale ``1 + w`` (w starts at 0)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def positions_of(seg):
    """Position of each slot counted from the start of its history."""
    idx = jnp.arange(seg.shape[-1])
    start = jnp.concatenate([jnp.ones_like(seg[..., :1], bool), seg[..., 1:] != seg[..., :-1]], -1)
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=seg.ndim - 1)


# -- gated DeltaNet ---------------------------------------------------------
def _conv_silu(x, w, seg):
    """Depthwise causal conv of ``len(w)`` taps, then SiLU; a tap that
    would reach into another history reads zero. x [L, C], w [K, C]."""
    taps = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[: x.shape[0]]
        same = jnp.pad(seg, (back, 0), constant_values=-1)[: x.shape[0]] == seg
        out = out + jnp.where(same[:, None], shifted, 0.0) * w[j]
    return jax.nn.silu(out)


def delta_rule(q, k, v, alpha, beta, start, block: int = 64):
    """Token by token, per head: ``S <- alpha_t S``; ``u_t = beta_t (v_t -
    S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``; ``S`` is zero at
    every ``start``. q, k [L, H, dk]; v [L, H, dv]; alpha, beta [L, H]."""
    length, heads, dk = q.shape
    dv = v.shape[-1]

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t, s_t = xs
        S = jnp.where(s_t, 0.0, S) * a_t[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    xs = (q, k, v, alpha, beta, start)
    S0 = jnp.zeros((heads, dk, dv), jnp.float32)
    if length % block:
        return jax.lax.scan(token, S0, xs)[1]

    # the same recurrence in two levels, so that its gradient keeps one
    # state per block and not one per token
    @jax.checkpoint
    def chunk(S, xs_block):
        return jax.lax.scan(token, S, xs_block)

    blocked = jax.tree_util.tree_map(
        lambda a: a.reshape((length // block, block) + a.shape[1:]), xs)
    return jax.lax.scan(chunk, S0, blocked)[1].reshape(length, heads, dv)


def delta_rule_inputs(p, x, seg, cfg):
    """What the delta rule of one layer is given: q, k [L, Hv, dk] (after
    the convolution, normalised, repeated to the value heads, q scaled), v
    [L, Hv, dv], g = log alpha and beta [L, Hv], and where a history
    starts; and z [L, Hv, dv], the output gate's input."""
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    length = x.shape[0]
    qkvz = x @ p["w_qkvz"]
    ba = x @ p["w_ba"]
    qkv, z = qkvz[:, : 2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    qkv = _conv_silu(qkv, p["conv_w"], seg)
    q = qkv[:, : hk * dk].reshape(length, hk, dk)
    k = qkv[:, hk * dk: 2 * hk * dk].reshape(length, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(length, hv, dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q), hv // hk, axis=1) * dk ** -0.5
    k = jnp.repeat(l2(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return q, k, v, g, beta, start, z.reshape(length, hv, dv)


def deltanet_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D]."""
    q, k, v, g, beta, start, z = delta_rule_inputs(p, x, seg, cfg)
    o = delta_rule(q, k, v, jnp.exp(g), beta, start)
    eps = cfg["rms_norm_eps"]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["o_norm"]
    o = o * jax.nn.silu(z)
    return o.reshape(x.shape[0], -1) @ p["w_out"]


@_highest
def delta_rule_of(q, k, v, g, beta, seg):
    """The recurrence alone on given inputs of one row (q, k [L, H, dk], v
    [L, H, dv], g = log alpha and beta [L, H], in any float type; seg [L])
    -> o [L, H, dv] float32: what a chunked scan that ran on those very
    numbers has to give."""
    seg = jnp.asarray(seg)
    q, k, v, g, beta = (jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))
    start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return jax.jit(delta_rule)(q, k, v, jnp.exp(g), beta, start)


# -- gated attention --------------------------------------------------------
def _rope(t, pos, rot, theta):
    """Rotary positions on the first ``rot`` dimensions (halves rotated
    against each other). t [L, H, hd]."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    r, rest = t[..., :rot], t[..., rot:]
    half = jnp.concatenate([-r[..., rot // 2:], r[..., : rot // 2]], -1)
    return jnp.concatenate([r * cos + half * sin, rest], -1)


def attention_mixer(p, x, seg, cfg):
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    length = x.shape[0]
    eps = cfg["rms_norm_eps"]
    qg = x @ p["w_q"]
    q = qg[:, : h * hd].reshape(length, h, hd)
    gate = qg[:, h * hd:]
    k = (x @ p["w_k"]).reshape(length, hkv, hd)
    v = (x @ p["w_v"]).reshape(length, hkv, hd)
    pos = positions_of(seg)
    rot = int(cfg["partial_rotary_factor"] * hd)
    q = _rope(rms_norm(q, p["q_norm"], eps), pos, rot, cfg["rope_theta"])
    k = _rope(rms_norm(k, p["k_norm"], eps), pos, rot, cfg["rope_theta"])
    idx = jnp.arange(length)
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])

    @jax.checkpoint
    def head(i):
        kv = i // (h // hkv)
        s = (q[:, i] @ k[:, kv].T) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(keep, s, _NEG), axis=-1)
        return w @ v[:, kv]

    o = jax.lax.map(head, jnp.arange(h))  # [H, L, hd]
    o = o.transpose(1, 0, 2).reshape(length, h * hd) * jax.nn.sigmoid(gate)
    return o @ p["w_o"]


# -- experts ----------------------------------------------------------------
def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def routing(p, x, cfg):
    """Weights [L, E] of the ``num_experts_per_tok`` largest of the
    softmax over ALL experts, renormalised to sum 1; 0 elsewhere."""
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def moe_block(p, x, cfg):
    first, count = cfg["experts_held"]
    weights = routing(p, x, cfg)[:, first: first + count]  # [L, count]
    y = jax.nn.sigmoid(x @ p["shared_gate"])[:, None] * _swiglu(p["shared"], x)

    def one(acc, ew):
        w, col = ew
        return acc + col[:, None] * _swiglu(w, x), None

    return jax.lax.scan(one, y, (p["experts"], weights.T))[0]


# -- the model --------------------------------------------------------------
def layer_forward(p, x, seg, cfg, full: bool):
    eps = cfg["rms_norm_eps"]
    mixer = attention_mixer(p["full"], rms_norm(x, p["input_norm"], eps), seg, cfg) if full \
        else deltanet_mixer(p["linear"], rms_norm(x, p["input_norm"], eps), seg, cfg)
    x = x + mixer
    return x + moe_block(p["moe"], rms_norm(x, p["post_norm"], eps), cfg)


def split_row(row, seg_row):
    """A packed row of L + 1 slots -> inputs, their segments, targets and
    which targets count: the next slot of the same history (segment 0 is
    padding)."""
    valid = (seg_row[1:] == seg_row[:-1]) & (seg_row[:-1] > 0)
    return row[:-1], seg_row[:-1], row[1:], valid


def _head_loss(final_norm, head, x, targets, valid, eps):
    """Summed cross entropy of one row's real targets."""
    logits = rms_norm(x, final_norm, eps) @ head.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


@_highest
def hidden_states(params, tokens, seg, cfg) -> jnp.ndarray:
    """Final hidden states (before the last norm) of one row: [L, D]."""
    x = params["embed"][tokens]
    for i, p in enumerate(params["layers"]):
        x = _layer_jit(p, x, seg, _freeze(cfg), is_full_attention(cfg, i))
    return x


@_highest
def logits_at(params, tokens, seg, cfg, positions) -> jnp.ndarray:
    """Logits [len(positions), V] of one row at the given slots."""
    x = hidden_states(params, tokens, seg, cfg)[positions]
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"].T


def _freeze(cfg: Dict) -> Tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, list))))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_jit(p, x, seg, cfg_items, full):
    return layer_forward(p, x, seg, dict(cfg_items), full)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer_vjp(p, x, seg, dx, cfg_items, full):
    _, vjp = jax.vjp(lambda p_, x_: layer_forward(p_, x_, seg, dict(cfg_items), full), p, x)
    return vjp(dx)


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(final_norm, head, x, targets, valid, eps):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(
        final_norm, head, x, targets, valid, eps)


@_highest
def loss_and_grads(params, rows, segs, cfg, sample=None):
    """Mean next-item cross entropy over the real targets of the packed
    rows [B, L + 1], its gradient in the layout of ``params``, and the
    logits [len(sample[b]), V] of each row at the slots ``sample[b]`` (an
    empty list without ``sample``). One row, then one layer, at a time;
    gradients are summed on the host."""
    frozen = _freeze(cfg)
    eps = cfg["rms_norm_eps"]
    rows, segs = np.asarray(rows), np.asarray(segs)
    n_targets = sum(int(np.asarray(split_row(r, s)[3]).sum()) for r, s in zip(rows, segs))
    grads = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, np.float32), params)
    total, logits = 0.0, []

    def add(into, delta):
        for leaf, d in zip(jax.tree_util.tree_leaves(into), jax.tree_util.tree_leaves(delta)):
            leaf += np.asarray(d)

    for b, (row, seg_row) in enumerate(zip(rows, segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        xs: List = [params["embed"][tokens]]
        for i, p in enumerate(params["layers"]):
            xs.append(_layer_jit(p, xs[-1], seg, frozen, is_full_attention(cfg, i)))
        if sample is not None:
            at = rms_norm(xs[-1][jnp.asarray(sample[b])], params["final_norm"], eps)
            logits.append(np.asarray(at @ params["head"].T))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["head"], xs[-1], targets, valid, eps)
        total += float(loss)
        add(grads["final_norm"], d_norm)
        add(grads["head"], d_head)
        for i in reversed(range(len(params["layers"]))):
            dp, dx = _layer_vjp(params["layers"][i], xs[i], seg, dx, frozen,
                                is_full_attention(cfg, i))
            add(grads["layers"][i], dp)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    scale = 1.0 / max(n_targets, 1)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    return total * scale, grads, logits


@_highest
def loss(params, rows, segs, cfg) -> float:
    eps = cfg["rms_norm_eps"]
    total, count = 0.0, 0
    for row, seg_row in zip(np.asarray(rows), np.asarray(segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        x = hidden_states(params, tokens, seg, cfg)
        total += float(_head_loss(params["final_norm"], params["head"], x, targets, valid, eps))
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
