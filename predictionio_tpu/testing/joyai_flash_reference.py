"""Plain reference of the JoyAI-LLM-Flash block (the published DeepSeek-V3
block at other sizes) as the sequence recommender runs it: forward, both
losses and gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``:
attention is a full masked score matrix, one head at a time; the experts
are a dense loop over the held set (every token through every held expert,
weighted by its routing weight or 0); rotary positions are written out pair
by pair; packed rows are handled by a segment-id mask alone; the optimizer
step and the router-bias rule are numpy.
``benchmark/lib/reference_joyai.py`` is a copy of this file (a test holds
the two to the same text).

``cfg`` is the configuration as its JSON file states it (the model's own
``config.json`` keys) plus ``experts_held`` = [first, count], the contiguous
range of routed experts this share computes (what the absent experts would
add is left out), and ``mtp_loss_weight``. ``params`` is::

    {"embed": [V, D], "head": [V, D], "final_norm": [D], "layers": [layer],
     "mtp": {"enorm": [D], "hnorm": [D], "eh_proj": [2 D, D], "norm": [D],
             "block": layer}}

    layer = {"input_norm": [D], "post_norm": [D], "attn": attn, and either
             "mlp": ffn (the leading dense layers) or "moe": moe}
    attn = {"w_qa": [D, Rq], "q_norm": [Rq], "w_qb": [Rq, H * (dn + dr)],
            "w_kva": [D, Rkv + dr], "kv_norm": [Rkv],
            "w_kvb": [Rkv, H * (dn + dv)], "w_o": [H * dv, D]}
    moe = {"router": [D, E], "router_bias": [E], "shared": ffn,
           "experts": ffn with a leading [count] axis}
    ffn = {"wg": [D, F], "wu": [D, F], "wd": [F, D]}

Per token x (the residual stream), as the public implementation has it:

- mixer: ``c_q = rms(x W_qa)``, ``q = c_q W_qb`` -> H x (nope | rope);
  ``[c_kv | k_r] = x W_kva``, ``c_kv = rms(c_kv)``, ``[k_n | v] = c_kv
  W_kvb`` -> H x (nope | value); rotary on q's rope part and on k_r
  (neighbouring pairs (2j, 2j + 1) turn by ``pos * theta ** (-2j / dr)``,
  positions restart with each history); ``k = [k_n | k_r]``, k_r the same
  for every head; softmax of ``q . k / sqrt(dn + dr)`` over the earlier
  slots of the same history; ``o = softmax . v`` -> ``W_o``.
- feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU; after
  them ``s = sigmoid(x W_r)`` over ALL experts, the ``num_experts_per_tok``
  largest of ``s + b`` chosen, weights ``s_i / (sum of the chosen s + 1e-20)
  * routed_scaling_factor`` (b chooses, it does not weigh), the held
  experts' part of ``sum w_i E_i(x)`` plus the shared expert, ungated.
- prediction module (one): ``h' = [rms(embed(t_i+1)) | rms(h_i)] W_eh``, h_i
  the residual stream after the last layer (before the final norm), one
  more block with routed experts, a norm of its own, the shared head;
  target ``t_i+2`` where i, i + 1, i + 2 lie in one history.
- loss = mean cross entropy of the next id + ``mtp_loss_weight`` x that of
  the module. The bias b has no gradient: after a step, ``b + rate *
  sign(mean load - load)`` from the step's counts (:func:`bias_step`).

RMS norms have scale ``1 + w`` (w starts at 0).
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


# -- latent attention -------------------------------------------------------
def rope_pairs(t, pos, theta):
    """t [L, H, r]: the pair (t[2j], t[2j + 1]) turns by the angle
    ``pos * theta ** (-2j / r)``."""
    r = t.shape[-1]
    out = []
    for j in range(r // 2):
        ang = pos.astype(jnp.float32) * (1.0 / theta ** (2.0 * j / r))
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a, b = t[..., 2 * j], t[..., 2 * j + 1]
        out += [a * cos - b * sin, b * cos + a * sin]
    return jnp.stack(out, -1)


def softmax_attention(q, k, v, seg, heads_at_once: int = 4):
    """q, k [H, L, dqk], v [H, L, dv], seg [L] -> [H, L, dv]: each slot
    over the slots before it, itself included, of its own history. The
    full [L, L] score matrix of ``heads_at_once`` heads at a time, made
    again in the backward pass."""
    h, length, dqk = q.shape
    idx = jnp.arange(length)
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])

    @jax.checkpoint
    def some(qkv):
        q_, k_, v_ = qkv
        s = jnp.einsum("hqd,hkd->hqk", q_, k_) * dqk ** -0.5
        w = jax.nn.softmax(jnp.where(keep[None], s, _NEG), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", w, v_)

    n = heads_at_once if h % heads_at_once == 0 else 1
    grouped = tuple(t.reshape((h // n, n) + t.shape[1:]) for t in (q, k, v))
    return jax.lax.map(some, grouped).reshape(h, length, v.shape[-1])


@_highest
def softmax_attention_of(q, k, v, seg):
    """The softmax alone on given inputs of one row (q, k [H, L, dqk], v
    [H, L, dv] in any float type; seg [L]) -> o [H, L, dv] float32: what a
    blockwise kernel that ran on those very numbers has to give."""
    q, k, v = (jnp.asarray(a, jnp.float32) for a in (q, k, v))
    return jax.jit(softmax_attention)(q, k, v, jnp.asarray(seg))


def attention_inputs(p, x, seg, cfg):
    """q, k [H, L, dn + dr] and v [H, L, dv] of one row, x already normed."""
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rkv, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    length = x.shape[0]
    pos = positions_of(seg)
    q = (rms_norm(x @ p["w_qa"], p["q_norm"], eps) @ p["w_qb"]).reshape(length, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], pos, theta)], -1)
    kva = x @ p["w_kva"]
    k_rope = rope_pairs(kva[:, None, rkv:], pos, theta)  # one vector a slot
    kv = (rms_norm(kva[:, :rkv], p["kv_norm"], eps) @ p["w_kvb"]).reshape(length, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (length, h, dr))], -1)
    return tuple(t.transpose(1, 0, 2) for t in (q, k, kv[..., dn:]))


def attention_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D]."""
    q, k, v = attention_inputs(p, x, seg, cfg)
    o = softmax_attention(q, k, v, seg)
    return o.transpose(1, 0, 2).reshape(x.shape[0], -1) @ p["w_o"]


# -- experts ----------------------------------------------------------------
def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def routing(p, x, cfg):
    """Weights [L, E]: sigmoid scores of ALL experts; the
    ``num_experts_per_tok`` largest of score + bias are chosen; their
    weights are the scores alone, renormalised over the chosen and times
    ``routed_scaling_factor``; 0 elsewhere."""
    scores = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top * cfg["routed_scaling_factor"])


def moe_block(p, x, cfg):
    first, count = cfg["experts_held"]
    weights = routing(p, x, cfg)[:, first: first + count]  # [L, count]

    @jax.checkpoint
    def one(acc, ew):
        w, col = ew
        return acc + col[:, None] * _swiglu(w, x), None

    return jax.lax.scan(one, _swiglu(p["shared"], x), (p["experts"], weights.T))[0]


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
    eps = cfg["rms_norm_eps"]
    x = x + attention_mixer(p["attn"], rms_norm(x, p["input_norm"], eps), seg, cfg)
    h = rms_norm(x, p["post_norm"], eps)
    return x + (moe_block(p["moe"], h, cfg) if "moe" in p else _swiglu(p["mlp"], h))


def split_row(row, seg_row):
    """A packed row of L + 1 slots -> inputs, their segments, targets and
    which targets count: the next slot of the same history (segment 0 is
    padding)."""
    valid = (seg_row[1:] == seg_row[:-1]) & (seg_row[:-1] > 0)
    return row[:-1], seg_row[:-1], row[1:], valid


def split_row_mtp(row, seg_row):
    """What the prediction module is given and asked at slot i of a packed
    row: the id at i + 1, the id at i + 2 (none at the last slot: 0) and
    whether it counts: i, i + 1, i + 2 in one history."""
    same = (seg_row[:-2] == seg_row[1:-1]) & (seg_row[1:-1] == seg_row[2:]) & (seg_row[:-2] > 0)
    return row[1:], np.append(row[2:], 0), np.append(same, False)


def _head_loss(norm, head, x, targets, valid, eps):
    """Summed cross entropy of one row's real targets."""
    logits = rms_norm(x, norm, eps) @ head.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def mtp_forward(mtp, embed, x, next_tokens, seg, cfg):
    """The module's hidden states [L, D] (before its own norm) from the
    residual stream x [L, D] after the last layer."""
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate([rms_norm(embed[next_tokens], mtp["enorm"], eps),
                            rms_norm(x, mtp["hnorm"], eps)], -1)
    return layer_forward(mtp["block"], both @ mtp["eh_proj"], seg, cfg)


def _mtp_loss(mtp, embed, head, x, next_tokens, seg, targets, valid, cfg):
    hidden = mtp_forward(mtp, embed, x, next_tokens, seg, cfg)
    return _head_loss(mtp["norm"], head, hidden, targets, valid, cfg["rms_norm_eps"])


def _freeze(cfg: Dict) -> Tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, list))))


@functools.partial(jax.jit, static_argnums=(3,))
def _layer_jit(p, x, seg, cfg_items):
    return layer_forward(p, x, seg, dict(cfg_items))


@functools.partial(jax.jit, static_argnums=(4,))
def _layer_vjp(p, x, seg, dx, cfg_items):
    _, vjp = jax.vjp(lambda p_, x_: layer_forward(p_, x_, seg, dict(cfg_items)), p, x)
    return vjp(dx)


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(norm, head, x, targets, valid, eps):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(norm, head, x, targets, valid, eps)


@functools.partial(jax.jit, static_argnums=(8,))
def _mtp_vjp(mtp, embed, head, x, next_tokens, seg, targets, valid, cfg_items):
    return jax.value_and_grad(_mtp_loss, argnums=(0, 1, 2, 3))(
        mtp, embed, head, x, next_tokens, seg, targets, valid, dict(cfg_items))


@functools.partial(jax.jit, static_argnums=(5,))
def _mtp_hidden_jit(mtp, embed, x, next_tokens, seg, cfg_items):
    return mtp_forward(mtp, embed, x, next_tokens, seg, dict(cfg_items))


@_highest
def hidden_states(params, tokens, seg, cfg) -> jnp.ndarray:
    """Final hidden states (before the last norm) of one row: [L, D]."""
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = _layer_jit(p, x, seg, _freeze(cfg))
    return x


@_highest
def loss_and_grads(params, rows, segs, cfg, sample=None):
    """Of the packed rows [B, L + 1]: the loss (next id + ``mtp_loss_weight``
    x the module's, each a mean over its own real targets), the two means,
    the gradient of the loss in the layout of ``params`` (``router_bias``:
    zeros, nothing flows to it), and the logits [len(sample[b]), V] of both
    heads at the slots ``sample[b]`` of each row (empty lists without
    ``sample``). One row, then one layer, at a time; gradients are summed
    on the host."""
    frozen = _freeze(cfg)
    eps, weight = cfg["rms_norm_eps"], cfg["mtp_loss_weight"]
    rows, segs = np.asarray(rows), np.asarray(segs)
    n_main = max(sum(int(split_row(r, s)[3].sum()) for r, s in zip(rows, segs)), 1)
    n_mtp = max(sum(int(split_row_mtp(r, s)[2].sum()) for r, s in zip(rows, segs)), 1)
    grads = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, np.float32), params)
    main = mtp = 0.0
    logits, mtp_logits = [], []

    def add(into, delta, scale):
        for leaf, d in zip(jax.tree_util.tree_leaves(into), jax.tree_util.tree_leaves(delta)):
            leaf += scale * np.asarray(d)

    for b, (row, seg_row) in enumerate(zip(rows, segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        next_tokens, targets2, valid2 = (jnp.asarray(a) for a in split_row_mtp(row, seg_row))
        xs: List = [params["embed"][tokens]]
        for p in params["layers"]:
            xs.append(_layer_jit(p, xs[-1], seg, frozen))
        if sample is not None:
            at = jnp.asarray(sample[b])
            logits.append(np.asarray(
                rms_norm(xs[-1][at], params["final_norm"], eps) @ params["head"].T))
            hidden = _mtp_hidden_jit(params["mtp"], params["embed"], xs[-1], next_tokens, seg, frozen)
            mtp_logits.append(np.asarray(
                rms_norm(hidden[at], params["mtp"]["norm"], eps) @ params["head"].T))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["head"], xs[-1], targets, valid, eps)
        main += float(loss)
        add(grads["final_norm"], d_norm, 1.0 / n_main)
        add(grads["head"], d_head, 1.0 / n_main)
        loss2, (d_mtp, d_embed, d_head2, dx2) = _mtp_vjp(
            params["mtp"], params["embed"], params["head"], xs[-1], next_tokens, seg,
            targets2, valid2, frozen)
        mtp += float(loss2)
        add(grads["mtp"], d_mtp, weight / n_mtp)
        add(grads["embed"], d_embed, weight / n_mtp)
        add(grads["head"], d_head2, weight / n_mtp)
        dx = dx / n_main + dx2 * (weight / n_mtp)
        for i in reversed(range(len(params["layers"]))):
            dp, dx = _layer_vjp(params["layers"][i], xs[i], seg, dx, frozen)
            add(grads["layers"][i], dp, 1.0)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    main, mtp = main / n_main, mtp / n_mtp
    return main + weight * mtp, main, mtp, grads, logits, mtp_logits


@_highest
def loss(params, rows, segs, cfg) -> float:
    """The training loss alone."""
    frozen, eps = _freeze(cfg), cfg["rms_norm_eps"]
    sums, counts = [0.0, 0.0], [0, 0]
    for row, seg_row in zip(np.asarray(rows), np.asarray(segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        next_tokens, targets2, valid2 = (jnp.asarray(a) for a in split_row_mtp(row, seg_row))
        x = hidden_states(params, tokens, seg, cfg)
        sums[0] += float(_head_loss(params["final_norm"], params["head"], x, targets, valid, eps))
        sums[1] += float(_mtp_loss(params["mtp"], params["embed"], params["head"], x,
                                   next_tokens, seg, targets2, valid2, dict(frozen)))
        counts[0] += int(valid.sum())
        counts[1] += int(valid2.sum())
    return sums[0] / max(counts[0], 1) + cfg["mtp_loss_weight"] * sums[1] / max(counts[1], 1)


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
