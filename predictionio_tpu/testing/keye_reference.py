"""Plain reference of Keye-VL-2.0's language block (``model_type`` ``KeyeVL2``:
Keye-VL-2.0-30B-A3B) as the sequence recommender runs it: forward, both losses
and gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``: the
indexer's scores are full rows, a strip of queries at a time; the choice is a
sort; attention is a masked softmax over the full row, a key head's group at a
time; the experts are a dense loop over the held set (every token through
every held expert, weighted by its routing weight or 0); rotary positions are
written out half by half; packed rows are handled by a segment-id mask alone;
the optimizer step is numpy. ``benchmark/lib/reference_keye.py`` is a copy of
this file (a test holds the two to the same text).

``cfg`` is the configuration as its JSON file states it (the model's own
``config.json`` keys: ``sa_config``, ``rope_theta``, ``rms_norm_eps``, ...) plus
``experts_held`` = [first, count], the contiguous range of routed experts this
share computes (what the absent experts would add is left out). ``params`` is::

    {"embed": [V, D], "head": [V, D], "final_norm": [D], "layers": [layer]}

    layer = {"input_norm": [D], "post_norm": [D], "dsa": mixer, "moe": moe}
    mixer = {"w_q": [D, H * hd], "w_k", "w_v": [D, Hkv * hd], "q_norm", "k_norm": [hd],
             "w_o": [H * hd, D],
             and the indexer's (``INDEXER``): "w_iq": [D, J * d], "w_ik": [D, d],
             "w_iw": [D, J], "ik_g", "ik_b": [d] (its key's LayerNorm)}
    moe = {"router": [D, E], "experts": ffn with a leading [count] axis}   (no shared expert)
    ffn = {"wg": [D, F], "wu": [D, F], "wd": [F, D]}

Per slot t of one packed row (x the residual stream; "causal keys of t": the
slots s <= t of t's own history):

1. ``h = rms(x)``. Main heads: ``q = rope(rms_head(h W_q))`` [H x hd], ``k =
   rope(rms_head(h W_k))`` [Hkv x hd], ``v = h W_v``; rotary over the whole
   head (rotate-half, ``rope_theta``, positions restart with each history).
2. Indexer, on ``h^ = stop_gradient(h)``: ``qI[t, j] = (h^ W_iq)[t, j]`` in
   ``R^d`` for j < J (``sa_config.indexer_num_heads``, ``indexer_head_dim``),
   ``kI[s] = LayerNorm(h^ W_ik)[s]`` (ONE index key a slot), both rotary on the
   first half of the head; ``w[t, j] = (h^ W_iw)[t, j] * J ** -0.5 * d ** -0.5``.
   ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``.
3. ``S_t`` = the ``sa_config.topk`` causal keys of t with the largest ``I[t,
   s]``, all of them where t has no more: exactly ``min(topk, causal keys)``.
   Among equal scores (-0.0 equals 0.0) the most recent key comes first. By a
   stable sort. The choice has no gradient.
4. ``o_t`` (a head) = ``sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(hd))
   v_s``, query head i on key head ``i // (H / Hkv)``; ``x <- x + o W_o``.
5. ``x <- x + MoE(rms(x))``: softmax over ALL router logits, the
   ``num_experts_per_tok`` largest, renormalised (``norm_topk_prob``), the held
   experts' part of ``sum w_i E_i(u)``; no shared expert, no bias.
6. loss = mean cross entropy of the next id over the (untied) head + the sum
   over layers of ``L_I = mean_t KL(p[t, S_t] || softmax_{s in S_t} I[t, s])``,
   p the main heads' attention weights over ``S_t`` summed over the H heads and
   divided by H, held constant; the mean is over the real slots of all rows.
   ``L_I`` reaches the indexer's five leaves alone (step 2's
   ``stop_gradient``); the next-item loss reaches nothing of the indexer.

Departures from the public description, each stated in the configuration's
``assumed``: steps 2, 3 and 6 follow the DeepSeek-V3.2 report's sparse
attention (the config sizes an indexer and names no equations); the rotary
half of the index head, ``L_I``'s weight 1 and its mean over slots are set
here; RMS norms have scale ``1 + w`` with w starting at 0; M-RoPE's three
position axes all carry the slot's position in its history (ids alone have no
image grid), which is plain rotary; no vision tower.

``chosen`` (:func:`loss_and_grads`): the sets ``S_t`` given from outside, a
[L, L] bool (query, key) a layer and row: the reference then runs on THOSE
keys (what a check of everything after the choice needs, a rounding having
flipped a key at the threshold); its own choice is then not made.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30
#: the leaves of a mixer that are the indexer's
INDEXER = ("w_iq", "w_ik", "w_iw", "ik_g", "ik_b")
#: queries a strip: scores, choice, softmax and KL are made for so many at a time
STRIP = 512


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def rms_norm(x, w, eps):
    """RMSNorm with scale ``1 + w`` (w starts at 0)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def layer_norm(x, g, b, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def positions_of(seg):
    """Position of each slot counted from the start of its history."""
    idx = jnp.arange(seg.shape[-1])
    start = jnp.concatenate([jnp.ones_like(seg[..., :1], bool), seg[..., 1:] != seg[..., :-1]], -1)
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=seg.ndim - 1)


def rope_half(t, pos, theta, rot=None):
    """t [L, H, hd]: of the first ``rot`` dimensions (all without ``rot``),
    dimension j and j + rot / 2 turn together by the angle ``pos * theta ** (-2j
    / rot)`` (rotate-half); the rest pass."""
    rot = t.shape[-1] if rot is None else rot
    half = rot // 2
    inv = jnp.asarray([1.0 / theta ** (2.0 * j / rot) for j in range(half)], jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv  # [L, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = t[..., :half], t[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, t[..., rot:]], -1)


# -- the indexer and the choice ---------------------------------------------
def index_scores(iq, ik, iw):
    """iq [S, J, d], ik [T, d], iw [S, J] -> I [S, T]: ``sum_j iw[., j] *
    relu(iq[., j] . ik)``."""
    return (iw[:, :, None] * jax.nn.relu(jnp.einsum("sjd,td->sjt", iq, ik))).sum(1)


@_highest
def index_scores_of(iq, ik, iw):
    """The scores alone on given indexer inputs (any float type) -> float32:
    what a program that ran on those very numbers has to give."""
    return jax.jit(index_scores)(*(jnp.asarray(t, jnp.float32) for t in (iq, ik, iw)))


def chosen_by_sort(scores, valid, topk: int):
    """scores, valid [S, T] -> the mask [S, T] of the row's ``topk`` valid keys
    with the largest scores (all valid keys where there are no more than
    ``topk``), among equal scores the last keys (the most recent) first: the
    row turned round and sorted stably, largest first; the first ``topk`` kept
    (-0.0 is 0.0)."""
    turned = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)[:, ::-1]
    order = jnp.argsort(-turned, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)  # where each key stands in that order
    return valid & (rank < topk)[:, ::-1]


def valid_pairs(seg, rows):
    """[len(rows), L]: key s is a causal key of query ``rows[t]``."""
    idx = jnp.arange(seg.shape[0])
    return (rows[:, None] >= idx[None, :]) & (seg[rows][:, None] == seg[None, :])


def attend(q, k, v, keep):
    """Step 4 for some queries: q [H, S, hd], k, v [Hkv, T, hd], keep [S, T] ->
    o [H, S, hd] and the heads' weights summed and divided by H [S, T] (held
    constant). A masked softmax over the full row, a key head's group at a time."""
    h, hd = q.shape[0], q.shape[-1]
    g = h // k.shape[0]
    out, p = [], jnp.zeros(keep.shape, jnp.float32)
    for c in range(k.shape[0]):
        s = jnp.einsum("gsd,td->gst", q[c * g:(c + 1) * g], k[c]) * hd ** -0.5
        w = jnp.where(keep[None], jax.nn.softmax(jnp.where(keep[None], s, _NEG), axis=-1), 0.0)
        out.append(jnp.einsum("gst,td->gsd", w, v[c]))
        p = p + jax.lax.stop_gradient(w).sum(0)
    return jnp.concatenate(out, 0), p / h


def _strips(length: int):
    """Slot numbers by strips [n, strip]; the last strip repeats the last slot
    where ``length`` is no whole number of them (cut again by the caller)."""
    strip = min(STRIP, length)
    return jnp.minimum(jnp.arange(length + -length % strip), length - 1).reshape(-1, strip)


def sparse_attention(q, k, v, iq, ik, iw, seg, topk: int, chosen=None):
    """q [H, L, hd], k, v [Hkv, L, hd]; iq [L, J, d], ik [L, d], iw [L, J];
    seg [L] -> o [H, L, hd], the KL of every slot [L] and the sets ``S_t`` as a
    mask [L, L]. A strip of queries at a time, made again in the backward
    pass; within it the full rows of scores, a sort (without ``chosen``), a
    masked softmax a key head's group at a time."""
    h, length, hd = q.shape

    @jax.checkpoint
    def one(at):
        valid = valid_pairs(seg, at)
        scores = index_scores(iq[at], ik, iw[at])
        keep = (chosen_by_sort(jax.lax.stop_gradient(scores), valid, topk) if chosen is None
                else chosen[at] & valid)
        out, p = attend(q[:, at], k, v, keep)
        log_soft = jax.nn.log_softmax(jnp.where(keep, scores, _NEG), axis=-1)
        inside = keep & (p > 0)
        kl = jnp.where(inside, p * (jnp.log(jnp.where(inside, p, 1.0)) - log_soft), 0.0).sum(-1)
        return out, kl, keep

    o, kl, keep = jax.lax.map(one, _strips(length))  # [n, H, strip, hd], [n, strip], [n, strip, L]
    o = o.transpose(1, 0, 2, 3).reshape(h, -1, hd)[:, :length]
    return o, kl.reshape(-1)[:length], keep.reshape(-1, length)[:length]


def indexer_inputs(p, h, pos, cfg):
    """Step 2 on ``h`` [L, D] (the caller stops the gradient): iq [L, J, d],
    ik [L, d], iw [L, J]."""
    sa = cfg["sa_config"]
    j, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, length = cfg["rope_theta"], h.shape[0]
    iq = rope_half((h @ p["w_iq"]).reshape(length, j, d), pos, theta, d // 2)
    ik = layer_norm(h @ p["w_ik"], p["ik_g"], p["ik_b"])
    ik = rope_half(ik[:, None, :], pos, theta, d // 2)[:, 0]
    return iq, ik, (h @ p["w_iw"]) * (j ** -0.5 * d ** -0.5)


def main_heads(p, h, pos, cfg):
    """Step 1 on ``h`` [L, D]: q [H, L, hd], k, v [Hkv, L, hd]."""
    heads, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta, length = cfg["rms_norm_eps"], cfg["rope_theta"], h.shape[0]
    q = rms_norm((h @ p["w_q"]).reshape(length, heads, hd), p["q_norm"], eps)
    k = rms_norm((h @ p["w_k"]).reshape(length, hkv, hd), p["k_norm"], eps)
    v = (h @ p["w_v"]).reshape(length, hkv, hd)
    q, k = rope_half(q, pos, theta), rope_half(k, pos, theta)
    return tuple(t.transpose(1, 0, 2) for t in (q, k, v))


def sparse_mixer(p, h, seg, cfg, chosen=None):
    """h [L, D] (already normed) -> what the mixer adds [L, D], the KL of every
    slot [L] and the chosen mask [L, L]."""
    pos = positions_of(seg)
    q, k, v = main_heads(p, h, pos, cfg)
    iq, ik, iw = indexer_inputs(p, jax.lax.stop_gradient(h), pos, cfg)
    o, kl, keep = sparse_attention(q, k, v, iq, ik, iw, seg, cfg["sa_config"]["topk"], chosen)
    return o.transpose(1, 0, 2).reshape(h.shape[0], -1) @ p["w_o"], kl, keep


@_highest
def sparse_core_of(q, k, v, seg, chosen):
    """Step 4 alone for ONE key head's group on given q [G, L, hd], k, v [L,
    hd] (any float type) and given sets [L, L] -> o [G, L, hd] float32."""
    def core(q, k, v, seg, chosen):
        o = jax.lax.map(lambda at: attend(
            q[:, at], k[None], v[None], chosen[at] & valid_pairs(seg, at))[0], _strips(seg.shape[0]))
        return o.transpose(1, 0, 2, 3).reshape(q.shape[0], -1, q.shape[-1])[:, :seg.shape[0]]

    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    return jax.jit(core)(q, k, v, jnp.asarray(seg), jnp.asarray(chosen))


# -- experts ----------------------------------------------------------------
def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def routing(p, x, cfg):
    """Weights [L, E] of the ``num_experts_per_tok`` largest of the softmax
    over ALL experts, renormalised to sum 1 (``norm_topk_prob``); 0 elsewhere."""
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def moe_block(p, x, cfg, held=None):
    """The part of ``sum w_i E_i(x)`` that the experts ``held`` = [first, count]
    (``cfg["experts_held"]`` without it) give; ``p["experts"]`` holds those."""
    first, count = cfg["experts_held"] if held is None else held
    weights = routing(p, x, cfg)[:, first: first + count]  # [L, count]

    @jax.checkpoint
    def one(acc, ew):
        w, col = ew
        return acc + col[:, None] * _swiglu(w, x), None

    return jax.lax.scan(one, jnp.zeros_like(x), (p["experts"], weights.T))[0]


# -- the model --------------------------------------------------------------
def layer_forward(p, x, seg, cfg, chosen=None):
    """x [L, D] -> x after the layer, the KL of every slot [L], the sets [L, L]."""
    eps = cfg["rms_norm_eps"]
    mixed, kl, keep = sparse_mixer(p["dsa"], rms_norm(x, p["input_norm"], eps), seg, cfg, chosen)
    x = x + mixed
    return x + moe_block(p["moe"], rms_norm(x, p["post_norm"], eps), cfg), kl, keep


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
    flat = {**cfg, **cfg["sa_config"]}
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in flat.items()
                        if isinstance(v, (int, float, bool, list)) and k != "mlp_only_layers"))


def _thaw(cfg_items: Tuple) -> Dict:
    cfg = dict(cfg_items)
    return {**cfg, "sa_config": cfg}


def _kl_sum(kl, seg):
    return jnp.sum(jnp.where(seg > 0, kl, 0.0))


@functools.partial(jax.jit, static_argnums=(4,))
def _layer_jit(p, x, seg, chosen, cfg_items):
    x, kl, keep = layer_forward(p, x, seg, _thaw(cfg_items), chosen)
    return x, _kl_sum(kl, seg), keep


@functools.partial(jax.jit, static_argnums=(6,))
def _layer_vjp(p, x, seg, chosen, dx, dkl, cfg_items):
    def run(p_, x_):
        out, kl, _ = layer_forward(p_, x_, seg, _thaw(cfg_items), chosen)
        return out, _kl_sum(kl, seg)

    _, vjp = jax.vjp(run, p, x)
    return vjp((dx, dkl))


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(norm, head, x, targets, valid, eps):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(norm, head, x, targets, valid, eps)


@_highest
def hidden_states(params, tokens, seg, cfg) -> jnp.ndarray:
    """Final hidden states (before the last norm) of one row: [L, D]."""
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = _layer_jit(p, x, seg, None, _freeze(cfg))[0]
    return x


@_highest
def loss_and_grads(params, rows, segs, cfg, sample=None, chosen=None):
    """Of the packed rows [B, L + 1]: ``{"loss", "next_item", "index_loss"}``
    (the whole loss, the mean cross entropy over the real targets, and the sum
    over layers of the mean KL over the real slots), the whole loss's gradient
    in the layout of ``params`` and the logits [len(sample[b]), V] at the slots
    ``sample[b]`` of each row (an empty list without ``sample``).
    ``chosen[layer][b]``: the sets to run on ([L, L] bool), else the
    reference's own. One row, then one layer, at a time; gradients are summed
    on the host."""
    frozen, eps = _freeze(cfg), cfg["rms_norm_eps"]
    rows, segs = np.asarray(rows), np.asarray(segs)
    n_real = max(sum(int(split_row(r, s)[3].sum()) for r, s in zip(rows, segs)), 1)
    n_slots = max(int((segs[:, :-1] > 0).sum()), 1)
    grads = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, np.float32), params)
    next_item = index_loss = 0.0
    logits: List[np.ndarray] = []

    def add(into, delta, scale):
        for leaf, d in zip(jax.tree_util.tree_leaves(into), jax.tree_util.tree_leaves(delta)):
            leaf += scale * np.asarray(d)

    def sets(i, b) -> Optional[jnp.ndarray]:
        return None if chosen is None else jnp.asarray(chosen[i][b])

    for b, (row, seg_row) in enumerate(zip(rows, segs)):
        tokens, seg, targets, valid = (jnp.asarray(a) for a in split_row(row, seg_row))
        xs = [params["embed"][tokens]]
        for i, p in enumerate(params["layers"]):
            x, kl, _ = _layer_jit(p, xs[-1], seg, sets(i, b), frozen)
            xs.append(x)
            index_loss += float(kl) / n_slots
        if sample is not None:
            at = jnp.asarray(sample[b])
            logits.append(np.asarray(
                rms_norm(xs[-1][at], params["final_norm"], eps) @ params["head"].T))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["head"], xs[-1], targets, valid, eps)
        next_item += float(loss) / n_real
        add(grads["final_norm"], d_norm, 1.0 / n_real)
        add(grads["head"], d_head, 1.0 / n_real)
        dx = dx / n_real
        for i in reversed(range(len(params["layers"]))):
            dp, dx = _layer_vjp(params["layers"][i], xs[i], seg, sets(i, b), dx,
                                jnp.float32(1.0 / n_slots), frozen)
            add(grads["layers"][i], dp, 1.0)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    losses = {"loss": next_item + index_loss, "next_item": next_item, "index_loss": index_loss}
    return losses, grads, logits


@_highest
def chosen_sets(params, rows, segs, cfg) -> List[List[np.ndarray]]:
    """The reference's own sets ``[layer][b]`` [L, L] bool on these rows."""
    frozen = _freeze(cfg)
    out: List[List[np.ndarray]] = [[] for _ in params["layers"]]
    for row, seg_row in zip(np.asarray(rows), np.asarray(segs)):
        tokens, seg, _, _ = (jnp.asarray(a) for a in split_row(row, seg_row))
        x = params["embed"][tokens]
        for i, p in enumerate(params["layers"]):
            x, _, keep = _layer_jit(p, x, seg, None, frozen)
            out[i].append(np.asarray(keep))
    return out


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
