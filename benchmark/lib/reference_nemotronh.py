"""Plain reference of the Nemotron-H block (``model_type`` ``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B) as the sequence recommender runs it: forward,
loss and gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``:
the state-space recurrence goes slot by slot (a ``lax.scan`` over slots, in
blocks that are made again in the backward pass so that a row of 8,192
slots fits), every head reading its group's ``B`` and ``C``; the convolution
is four shifted adds and a bias; attention is a full masked score matrix, a
few heads at a time; the experts are a dense loop over the held set (every
token through every held expert, weighted by its routing weight or 0);
packed rows are handled by comparing segment ids alone; the optimizer step
and the router-bias rule are numpy. ``benchmark/lib/reference_nemotronh.py``
is a copy of this file (a test holds the two to the same text).

``cfg`` is the configuration as its JSON file states it (the model's own
``config.json`` keys: ``hybrid_override_pattern``, ``mamba_num_heads``,
``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``head_dim``,
``layer_norm_epsilon``, ``num_experts_per_tok``, ``routed_scaling_factor``,
...) plus ``experts_held`` = [first, count], the contiguous range of routed
experts this share computes (what the absent experts would add is left out).
``params`` is::

    {"embed": [V, D], "head": [V, D], "final_norm": [D], "layers": [layer]}

    layer = {"norm": [D], and ONE part}
    part = "ssm": {"w_in": [D, 2 I + 2 G N] (columns [z | x | B | C], B and C a group after
                   the other), "w_dt": [D, H], "conv_w": [K, I + 2 G N], "conv_b": [I + 2 G N],
                   "A_log", "dt_bias", "D": [H], "norm": [I], "w_out": [I, D]}   (I = H * P), or
           "full": {"w_q": [D, Hq * hd], "w_k", "w_v": [D, Hkv * hd], "w_o": [Hq * hd, D]}, or
           "moe": {"router": [D, E], "router_bias": [E], "experts": ffn with a leading
                   [count] axis, "shared": ffn}
    ffn = {"wu": [D, F], "wd": [F, D]}                             (no gate)

Per token x (the residual stream), as the public implementation has it:

- every layer ``x <- x + part(rms(x))``, the part by the pattern's letter
  (``M``, ``*``, ``E``); after the last a norm and the untied head.
- ``M`` (Mamba-2): ``[z | xBC] = h W_in``, ``dt = h W_dt`` (the published
  in-projection's last H columns); ``xBC <- silu(conv(xBC) + b)``, depthwise,
  causal over ``K`` taps (``conv_w[K - 1]`` is the current slot's tap),
  reading zero before a history's first slot; ``xBC`` splits into ``u`` [H,
  P], ``B`` [G, N], ``C`` [G, N]; head i reads group ``i // (H / G)``;
  ``Delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state a head
  ``S_t = exp(Delta_t A) S_(t-1) + Delta_t u_t (x) B_t`` with ``S = 0``
  entering a history's first slot; ``y_t = S_t C_t + D * u_t``; the gate
  before the norm, ``y * silu(z)``, then an RMS norm over each group's I / G
  channels times ``w`` [I]; ``W_out``.
- ``*`` (grouped-query attention): q of Hq heads, k and v of Hkv, no norm, no
  gate, NO positions (the public modelling code applies no rotary); softmax
  of ``q . k / sqrt(hd)`` over the earlier slots of the same history, query
  head i on key head ``i // (Hq / Hkv)``; ``W_o``.
- ``E`` (experts): ``s = sigmoid(h W_r)`` over ALL experts in float32, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen (b: the correction
  bias), weights ``s_e / (sum of the chosen s + 1e-20) *
  routed_scaling_factor`` (b chooses, it does not weigh), the held experts'
  part of ``sum w_e W_down,e relu(W_up,e h)^2`` plus the shared expert
  ``W_down,s relu(W_up,s h)^2``, no gate on either.
- loss = mean cross entropy of the next id. The bias b has no gradient.

Departures from the public implementation, each the same function or stated
in the configuration's ``assumed``: the residual stream's RMS norms have
scale ``1 + w`` with w starting at 0 (there: ``w`` starting at 1; the mixer's
gated norm keeps the plain ``w`` starting at 1); the in-projection's ``dt``
columns are a leaf of their own; a tap or a state that would reach into the
neighbouring history of a packed row reads zero (there: one history a row,
the same thing); the bias's rule is DeepSeek-V3's ``b + rate * sign(mean
load - load)`` from the step's own counts (:func:`bias_step`; the config
names no rule).
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


# -- the Mamba-2 mixer -------------------------------------------------------
def conv_taps(x, conv_w, conv_b, seg):
    """Depthwise causal convolution of x [L, C] as shifted adds, then the
    bias: ``conv_w[K - 1 - j]`` on the slot j back, a slot of another
    history (or before the row) read as zero."""
    taps, length = conv_w.shape[0], x.shape[0]
    total = jnp.zeros_like(x)
    for back in range(taps):
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype), x[: length - back]], 0)
        before = jnp.concatenate([jnp.full((back,), -1, seg.dtype), seg[: length - back]], 0)
        total = total + jnp.where((before == seg)[:, None], shifted, 0.0) * conv_w[taps - 1 - back]
    return total + conv_b


def ssd_recurrence(u, b, c, dt, a_log, seg, block: int = 64):
    """The state-space recurrence slot by slot: u [L, H, P], b, c [L, G, N],
    dt [L, H] (after the softplus), a_log [H], seg [L] -> y [L, H, P]
    (without the skip ``D * u``); head i reads ``b`` and ``c`` of group ``i //
    (H / G)``. The state [H, P, N] is zero entering a history's first slot.
    Blocks of ``block`` slots are made again in the backward pass: only a
    block's incoming state is kept."""
    length, heads = u.shape[:2]
    a = -jnp.exp(a_log)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    pad = -length % block
    per_group = heads // b.shape[1]

    def blocks(t, fill=0):
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1), constant_values=fill)
        return t.reshape((-1, block) + t.shape[1:])

    def slot(state, at):
        u_t, b_t, c_t, dt_t, first_t = at
        b_t, c_t = (jnp.repeat(t, per_group, axis=0) for t in (b_t, c_t))  # [H, N]: a head's own
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (
            (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def one_block(state, ats):
        return jax.lax.scan(slot, state, ats)

    start = jnp.zeros(u.shape[1:] + (b.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(one_block, start, tuple(blocks(t) for t in (u, b, c, dt, first)))
    return y.reshape((-1,) + u.shape[1:])[:length]


@_highest
def ssd_of(u, b, c, dt, a_log, seg, groups: int):
    """The recurrence alone on given ``u``, ``B``, ``C`` [L, G N], ``Delta`` of
    one row (any float type) -> float32: what a scan that ran on those very
    numbers has to give."""
    f32 = jnp.float32
    b, c = (jnp.asarray(t, f32).reshape(t.shape[0], groups, -1) for t in (b, c))
    return jax.jit(ssd_recurrence)(
        jnp.asarray(u, f32), b, c, jnp.asarray(dt, f32), jnp.asarray(a_log, f32),
        jnp.asarray(seg))


def ssm_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D]."""
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, groups = cfg["ssm_state_size"], cfg["n_groups"]
    inner, length = heads * width, x.shape[0]
    zxbc = x @ p["w_in"]
    z = zxbc[:, :inner]
    xbc = jax.nn.silu(conv_taps(zxbc[:, inner:], p["conv_w"], p["conv_b"], seg))
    u = xbc[:, :inner].reshape(length, heads, width)
    b = xbc[:, inner: inner + groups * n].reshape(length, groups, n)
    c = xbc[:, inner + groups * n:].reshape(length, groups, n)
    dt = jax.nn.softplus(x @ p["w_dt"] + p["dt_bias"])
    y = ssd_recurrence(u, b, c, dt, p["A_log"], seg) + p["D"][:, None] * u
    gated = (y.reshape(length, inner) * jax.nn.silu(z)).reshape(length, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["layer_norm_epsilon"])
    return (normed.reshape(length, inner) * p["norm"]) @ p["w_out"]


# -- grouped-query attention without positions --------------------------------
def softmax_attention(q, k, v, seg, scale, heads_at_once: int = 4):
    """q [H, L, hd], k, v [Hkv, L, hd], seg [L] -> [H, L, hd]: each slot
    over the slots before it, itself included, of its own history, scores
    ``scale * q . k``; query head h reads key/value head ``h // (H / Hkv)``.
    The full [L, L] score matrix of ``heads_at_once`` heads at a time, made
    again in the backward pass."""
    h, length, hd = q.shape
    k, v = (jnp.repeat(t, h // t.shape[0], axis=0) for t in (k, v))
    idx = jnp.arange(length)
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])

    @jax.checkpoint
    def some(qkv):
        q_, k_, v_ = qkv
        s = jnp.einsum("hqd,hkd->hqk", q_, k_) * scale
        w = jax.nn.softmax(jnp.where(keep[None], s, _NEG), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", w, v_)

    n = heads_at_once if h % heads_at_once == 0 else 1
    grouped = tuple(t.reshape((h // n, n) + t.shape[1:]) for t in (q, k, v))
    return jax.lax.map(some, grouped).reshape(h, length, hd)


def attention_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D]."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    length = x.shape[0]
    q = (x @ p["w_q"]).reshape(length, h, hd)
    k = (x @ p["w_k"]).reshape(length, hkv, hd)
    v = (x @ p["w_v"]).reshape(length, hkv, hd)
    o = softmax_attention(*(t.transpose(1, 0, 2) for t in (q, k, v)), seg, hd ** -0.5)
    return o.transpose(1, 0, 2).reshape(length, h * hd) @ p["w_o"]


# -- the expert layer ----------------------------------------------------------
def _relu2(w, x):
    return jnp.square(jax.nn.relu(x @ w["wu"])) @ w["wd"]


def routing(p, x, cfg):
    """Weights [L, E]: sigmoid scores of ALL experts; the
    ``num_experts_per_tok`` largest of score + bias are chosen; their
    weights are the scores alone, over (their sum + 1e-20), times
    ``routed_scaling_factor``; 0 elsewhere."""
    scores = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top * cfg["routed_scaling_factor"])


def routed_part(p, x, cfg):
    """What this share's held experts give the tokens ``x`` [L, D]."""
    first, count = cfg["experts_held"]
    weights = routing(p, x, cfg)[:, first: first + count]  # [L, count]

    @jax.checkpoint
    def one(acc, ew):
        w, col = ew
        return acc + col[:, None] * _relu2(w, x), None

    return jax.lax.scan(one, jnp.zeros_like(x), (p["experts"], weights.T))[0]


def moe_block(p, x, cfg):
    """x [L, D] (already normed) -> [L, D]: the held experts' part and the
    shared expert, which every share computes alike."""
    return routed_part(p, x, cfg) + _relu2(p["shared"], x)


@_highest
def moe_of(p, x, cfg):
    """The expert layer alone on a given normed input ``x`` [L, D] (any float
    type) -> float32: what a layer that ran on those very numbers has to give."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    return jax.jit(lambda p_, x_: moe_block(p_, x_, dict(_freeze(cfg))))(
        p, jnp.asarray(x, jnp.float32))


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
_PARTS = {"ssm": ssm_mixer, "full": attention_mixer}


def layer_forward(p, x, seg, cfg):
    h = rms_norm(x, p["norm"], cfg["layer_norm_epsilon"])
    if "moe" in p:
        return x + moe_block(p["moe"], h, cfg)
    (kind,) = set(p) & set(_PARTS)
    return x + _PARTS[kind](p[kind], h, seg, cfg)


def split_row(row, seg_row):
    """A packed row of L + 1 slots -> inputs, their segments, targets and
    which targets count: the next slot of the same history (segment 0 is
    padding)."""
    valid = (seg_row[1:] == seg_row[:-1]) & (seg_row[:-1] > 0)
    return row[:-1], seg_row[:-1], row[1:], valid


def logits_of(norm, head, x, eps):
    return rms_norm(x, norm, eps) @ head.T


def _head_loss(norm, head, x, targets, valid, eps):
    """Summed cross entropy of one row's real targets."""
    logits = logits_of(norm, head, x, eps)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def _freeze(cfg: Dict) -> Tuple:
    """The numbers of ``cfg`` the layers read (lists of numbers too), hashable."""
    def number(v):
        return isinstance(v, (int, float, bool))

    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if number(v) or (isinstance(v, list) and all(number(e) for e in v))))


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
    zeros, nothing flows to it) and the logits [len(sample[b]), V] at the
    slots ``sample[b]`` of each row (an empty list without ``sample``). One
    row, then one layer, at a time; gradients are summed on the host."""
    frozen, eps = _freeze(cfg), cfg["layer_norm_epsilon"]
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
                logits_of(params["final_norm"], params["head"], xs[-1][at], eps)))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["head"], xs[-1], targets, valid, eps)
        total += float(loss)
        add(grads["final_norm"], d_norm, 1.0 / n_real)
        add(grads["head"], d_head, 1.0 / n_real)
        dx = dx / n_real
        for i in reversed(range(len(params["layers"]))):
            dp, dx = _layer_vjp(params["layers"][i], xs[i], seg, dx, frozen)
            add(grads["layers"][i], dp, 1.0)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    return total / n_real, grads, logits


@_highest
def loss(params, rows, segs, cfg) -> float:
    """The training loss alone."""
    eps = cfg["layer_norm_epsilon"]
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
