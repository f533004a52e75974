"""Plain reference of the Phi-4-mini-flash block (``model_type``
``phi4flash``: SambaY, arXiv:2507.06607, with Differential Attention,
arXiv:2410.05258) as the sequence recommender runs it: forward, loss and
gradients in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Nothing here is fast and nothing is imported from ``ops/`` or ``models/``:
the selective scan goes slot by slot (a ``lax.scan`` over slots, in blocks
that are made again in the backward pass so that a row of 8,192 slots fits);
the convolution is four shifted adds and a bias; attention is two full
masked score matrices a head pair, a few pairs at a time, the three masks
(causal, same history, window) written out; packed rows are handled by
comparing segment ids alone; the optimizer step is numpy.
``benchmark/lib/reference_phi4flash.py`` is a copy of this file (a test
holds the two to the same text).

``cfg`` is the configuration as its JSON file states it (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``layer_norm_eps``, ``mamba_d_state``, ``mamba_dt_rank``, ``layer_types``, and
in its ``backbone`` group ``layer_index_offset`` and ``rms_norm_eps``).
``params`` is::

    {"embed": [V, D], "final_norm": norm, "layers": [layer]}      (the head is the embedding)

    norm = {"g": [D], "b": [D]}
    layer = {"input_norm": norm, "post_norm": norm, "mlp": ffn, and a mixer}
    mixer = "mamba1": {"w_in": [D, 2 I] (columns [x~ | z]), "conv_w": [K, I], "conv_b": [I],
                       "w_x": [I, R + 2 N] (columns [delta | B | C]), "w_dt": [R, I],
                       "dt_bias": [I], "A_log": [I, N], "D": [I], "w_out": [I, D]}, or
            "swa" | "full": {"w_q": [D, Hq * hd], "w_k", "w_v": [D, Hkv * hd], "w_o": [Hq * hd, D],
                             "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2": [hd],
                             "subln": [2 hd]}, or
            "cross": the same without "w_k" and "w_v", or
            "gmu": {"w_1": [D, I], "w_2": [I, D]}
    ffn = {"wg": [D, F], "wu": [D, F], "wd": [F, D]}

Per token x (the residual stream), LN a LayerNorm with scale and bias:

- ``x0 = E[token]`` (no positions anywhere); every layer ``x <- x +
  mixer(LN(x))``, then ``x <- x + mlp(LN'(x))``; logits ``= LN(x_L) E^T``.
- ``mlp(h) = (silu(h W_g) * (h W_u)) W_d`` (the published ``gate_up_proj`` is
  ``[W_g | W_u]``).
- ``mamba1``: ``[x~ | z] = h W_in``; ``c = silu(conv(x~) + b_c)``, depthwise,
  causal over ``K`` taps (``conv_w[K - 1]`` is the current slot's tap),
  reading zero before a history's first slot; ``[delta | B | C] = c W_x``;
  ``Delta = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; the state
  ``S_t[c, n] = exp(Delta_t[c] A[c, n]) S_(t-1)[c, n] + Delta_t[c] B_t[n]
  c_t[c]`` with ``S = 0`` entering a history's first slot; ``m_t[c] = sum_n
  C_t[n] S_t[c, n] + D[c] c_t[c]``; ``y = (m * silu(z)) W_out``. The layer
  hands ``m`` on.
- ``swa`` and ``full`` (differential attention): q of Hq heads, k and v of
  Hkv; query heads (2p, 2p + 1) are pair p, key heads (2c, 2c + 1) key pair c,
  value heads (2c, 2c + 1) side by side the ONE value of key pair c, twice
  the head wide; query pair p reads key pair ``p // (Hq / Hkv)``. ``A^i =
  softmax(q^i k^i^T / sqrt(hd) + mask)`` over the earlier slots of the same
  history, in ``swa`` of those the slot itself and the ``sliding_window - 1``
  before it; ``lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 .
  lambda_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at the
  layer's depth l in the published model; ``o = (1 - lambda_init) rms((A^1 -
  lambda A^2) v) * subln`` over the value's width; ``W_o``. A ``full`` layer
  hands its ``k`` and ``v`` on.
- ``cross``: q alone from this layer; ``k`` and ``v`` the ones the ``full``
  layer below handed on; the same form with this layer's own lambda, causal
  inside the history, no window.
- ``gmu``: ``(m * silu(h W_1)) W_2``, ``m`` what the last ``mamba1`` layer
  below handed on.
- loss = mean cross entropy of the next id.

Departures from the public implementation, each the same function or stated
in the configuration's ``assumed``: no bias on the attention projections
(there: one on the fused q, k, v and on the output projection, 7,680 numbers
a layer; the keys' part is invisible to a softmax); the in-projection's and
the x-projection's column orders and which heads pair up are layouts; a tap,
a state or a score that would reach into the neighbouring history of a packed
row reads zero (there: one history a row, the same thing); the key/value
cache a serving deployment shares between the cross-attention layers is, in
training, the full layer's ``k`` and ``v`` themselves.
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


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


# -- the Mamba-1 mixer -------------------------------------------------------
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


def selective_recurrence(c, dt, b, cc, a_log, seg, block: int = 64):
    """The selective scan slot by slot: c [L, I], dt [L, I] (after the
    softplus), b, cc [L, N], a_log [I, N], seg [L] -> y [L, I] (without the
    skip ``D * c``). The state [I, N] is zero entering a history's first
    slot. Blocks of ``block`` slots are made again in the backward pass:
    only a block's incoming state is kept."""
    length = c.shape[0]
    a = -jnp.exp(a_log)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    pad = -length % block

    def blocks(t, fill=0):
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1), constant_values=fill)
        return t.reshape((-1, block) + t.shape[1:])

    def slot(state, at):
        c_t, dt_t, b_t, cc_t, first_t = at
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * c_t)[:, None] * b_t[None, :]
        return state, state @ cc_t

    @jax.checkpoint
    def one_block(state, ats):
        return jax.lax.scan(slot, state, ats)

    start = jnp.zeros(a.shape, jnp.float32)
    _, y = jax.lax.scan(one_block, start, tuple(blocks(t) for t in (c, dt, b, cc, first)))
    return y.reshape(-1, c.shape[1])[:length]


@_highest
def selective_scan_of(c, dt, b, cc, a_log, seg):
    """The recurrence alone on given ``c``, ``Delta``, ``B``, ``C`` of one
    row (any float type) -> float32: what a scan that ran on those very
    numbers has to give."""
    f32 = jnp.float32
    return jax.jit(selective_recurrence)(
        jnp.asarray(c, f32), jnp.asarray(dt, f32), jnp.asarray(b, f32), jnp.asarray(cc, f32),
        jnp.asarray(a_log, f32), jnp.asarray(seg))


def mamba1_mixer(p, x, seg, cfg):
    """x [L, D] (already normed) -> [L, D] and ``m`` [L, I], handed on."""
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    inner = p["w_out"].shape[0]
    xz = x @ p["w_in"]
    c = jax.nn.silu(conv_taps(xz[:, :inner], p["conv_w"], p["conv_b"], seg))
    dbc = c @ p["w_x"]
    dt = jax.nn.softplus(dbc[:, :rank] @ p["w_dt"] + p["dt_bias"])
    b, cc = dbc[:, rank: rank + n], dbc[:, rank + n:]
    m = selective_recurrence(c, dt, b, cc, p["A_log"], seg) + p["D"] * c
    return (m * jax.nn.silu(xz[:, inner:])) @ p["w_out"], m


def gmu_mixer(p, x, m):
    return (m * jax.nn.silu(x @ p["w_1"])) @ p["w_2"]


# -- differential attention ---------------------------------------------------
def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * depth))


def kept_pairs(seg, window: int = 0):
    """[L, L] bool: slot i keeps slot j. The three masks written out: j is
    not after i; both lie in one history; under a window, j is i or one of
    the ``window - 1`` slots before it."""
    idx = jnp.arange(seg.shape[0])
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])
    if window:
        keep = keep & (idx[:, None] - idx[None, :] < window)
    return keep


def differential_core(q, k, v, lam, seg, window: int = 0, pairs_at_once: int = 2):
    """q [P, 2, L, hd] (query pairs, their two members), k [Pk, 2, L, hd], v
    [Pk, L, 2 hd], lam a scalar, seg [L] -> ``(A^1 - lam A^2) v`` [P, L, 2
    hd]: each member's softmax over the kept slots of its own scores, scaled
    by ``1 / sqrt(hd)``; query pair p reads key pair ``p // (P / Pk)``. Two
    full [L, L] score matrices a pair, ``pairs_at_once`` pairs at a time,
    made again in the backward pass."""
    pairs, _, length, hd = q.shape
    k, v = (jnp.repeat(t, pairs // t.shape[0], axis=0) for t in (k, v))
    keep = kept_pairs(seg, window)

    @jax.checkpoint
    def some(qkv):
        q_, k_, v_ = qkv
        s = jnp.einsum("pmqd,pmkd->pmqk", q_, k_) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(keep[None, None], s, _NEG), axis=-1)
        return jnp.einsum("pqk,pkd->pqd", w[:, 0] - lam * w[:, 1], v_)

    n = pairs_at_once if pairs % pairs_at_once == 0 else 1
    grouped = tuple(t.reshape((pairs // n, n) + t.shape[1:]) for t in (q, k, v))
    return jax.lax.map(some, grouped).reshape(pairs, length, 2 * hd)


@_highest
def differential_core_of(q, k, v, lam, seg, window: int = 0):
    """The core alone on given q, k, v (any float type) of one row ->
    float32: what a core that ran on those very numbers has to give."""
    f32 = jnp.float32
    return jax.jit(differential_core, static_argnums=(5,))(
        jnp.asarray(q, f32), jnp.asarray(k, f32), jnp.asarray(v, f32), jnp.asarray(lam, f32),
        jnp.asarray(seg), window)


def keys_and_values(p, x, cfg):
    """x [L, D] -> k [Pk, 2, L, hd], v [Pk, L, 2 hd]."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, length = cfg["hidden_size"] // h, x.shape[0]
    k = (x @ p["w_k"]).reshape(length, hkv // 2, 2, hd).transpose(1, 2, 0, 3)
    v = (x @ p["w_v"]).reshape(length, hkv // 2, 2 * hd).transpose(1, 0, 2)
    return k, v


def differential_mixer(p, x, seg, cfg, depth: int, k, v, window: int = 0):
    """x [L, D] (already normed), k and v as :func:`keys_and_values` gives
    them (this layer's own or a layer's below) -> [L, D]."""
    h = cfg["num_attention_heads"]
    hd, length = cfg["hidden_size"] // h, x.shape[0]
    q = (x @ p["w_q"]).reshape(length, h // 2, 2, hd).transpose(1, 2, 0, 3)
    start = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    o = differential_core(q, k, v, lam, seg, window)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["backbone"]["rms_norm_eps"])
    o = o * p["subln"] * (1.0 - start)
    return o.transpose(1, 0, 2).reshape(length, h * hd) @ p["w_o"]


# -- the model --------------------------------------------------------------
def _swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def layer_forward(p, x, seg, given, cfg, depth: int):
    """One layer: the residual stream after it, and what it hands to the
    layers above (``m`` from a ``mamba1`` layer, ``k`` and ``v`` from a
    ``full`` one; nothing from the others). ``given``: what the layers below
    handed on that this one reads (``m`` for ``gmu``, ``k`` and ``v`` for
    ``cross``)."""
    eps = cfg["layer_norm_eps"]
    h = layer_norm(x, p["input_norm"], eps)
    handed = {}
    if "mamba1" in p:
        mixed, m = mamba1_mixer(p["mamba1"], h, seg, cfg)
        handed = {"m": m}
    elif "gmu" in p:
        mixed = gmu_mixer(p["gmu"], h, given["m"])
    elif "cross" in p:
        mixed = differential_mixer(p["cross"], h, seg, cfg, depth, given["k"], given["v"])
    else:
        name = "swa" if "swa" in p else "full"
        k, v = keys_and_values(p[name], h, cfg)
        mixed = differential_mixer(p[name], h, seg, cfg, depth, k, v,
                                   cfg["sliding_window"] if name == "swa" else 0)
        if name == "full":
            handed = {"k": k, "v": v}
    x = x + mixed
    return x + _swiglu(p["mlp"], layer_norm(x, p["post_norm"], eps)), handed


#: what a layer of a kind reads of what the layers below it handed on
READS = {"gmu": ("m",), "cross": ("k", "v")}


def _kind(p) -> str:
    return next(name for name in ("mamba1", "swa", "full", "gmu", "cross") if name in p)


def _reads(p, available):
    return {name: available[name] for name in READS.get(_kind(p), ())}


def split_row(row, seg_row):
    """A packed row of L + 1 slots -> inputs, their segments, targets and
    which targets count: the next slot of the same history (segment 0 is
    padding)."""
    valid = (seg_row[1:] == seg_row[:-1]) & (seg_row[:-1] > 0)
    return row[:-1], seg_row[:-1], row[1:], valid


def logits_of(norm, head, x, eps):
    return layer_norm(x, norm, eps) @ head.T


def _head_loss(norm, head, x, targets, valid, eps):
    """Summed cross entropy of one row's real targets."""
    logits = logits_of(norm, head, x, eps)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def _freeze(cfg: Dict) -> Tuple:
    """The numbers of ``cfg`` the layers read, hashable."""
    flat = {**cfg, **{f"backbone.{k}": v for k, v in cfg.get("backbone", {}).items()}}
    return tuple(sorted((k, v) for k, v in flat.items() if isinstance(v, (int, float, bool))))


def _thaw(cfg_items: Tuple) -> Dict:
    cfg = {k: v for k, v in cfg_items if not k.startswith("backbone.")}
    cfg["backbone"] = {k[9:]: v for k, v in cfg_items if k.startswith("backbone.")}
    return cfg


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer_jit(p, x, seg, given, cfg_items, depth):
    return layer_forward(p, x, seg, given, _thaw(cfg_items), depth)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _layer_vjp(p, x, seg, given, dx, d_handed, cfg_items, depth):
    _, vjp = jax.vjp(
        lambda p_, x_, g_: layer_forward(p_, x_, seg, g_, _thaw(cfg_items), depth), p, x, given)
    return vjp((dx, d_handed))


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(norm, head, x, targets, valid, eps):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(norm, head, x, targets, valid, eps)


def _depth(cfg: Dict, i: int) -> int:
    return cfg.get("backbone", {}).get("layer_index_offset", 0) + i


def _forward(params, tokens, seg, cfg):
    """Every layer's input, what it was given and what it handed on."""
    frozen = _freeze(cfg)
    xs, givens, handeds, available = [params["embed"][tokens]], [], [], {}
    for i, p in enumerate(params["layers"]):
        givens.append(_reads(p, available))
        x, handed = _layer_jit(p, xs[-1], seg, givens[-1], frozen, _depth(cfg, i))
        xs.append(x)
        handeds.append(handed)
        available = {**available, **handed}
    return xs, givens, handeds


@_highest
def hidden_states(params, tokens, seg, cfg) -> jnp.ndarray:
    """Final hidden states (before the last norm) of one row: [L, D]."""
    return _forward(params, tokens, seg, cfg)[0][-1]


@_highest
def loss_and_grads(params, rows, segs, cfg, sample=None):
    """Of the packed rows [B, L + 1]: the loss (mean over the real
    targets), its gradient in the layout of ``params`` (``embed``: the
    embedding's and the head's parts summed) and the logits
    [len(sample[b]), V] at the slots ``sample[b]`` of each row (an empty
    list without ``sample``). One row, then one layer, at a time; gradients
    are summed on the host. What a layer handed on gets its cotangent from
    every layer that read it, summed, before the layer is pulled back."""
    frozen, eps = _freeze(cfg), cfg["layer_norm_eps"]
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
        xs, givens, handeds = _forward(params, tokens, seg, cfg)
        if sample is not None:
            at = jnp.asarray(sample[b])
            logits.append(np.asarray(
                logits_of(params["final_norm"], params["embed"], xs[-1][at], eps)))
        loss, (d_norm, d_head, dx) = _head_vjp(
            params["final_norm"], params["embed"], xs[-1], targets, valid, eps)
        total += float(loss)
        add(grads["final_norm"], d_norm, 1.0 / n_real)
        add(grads["embed"], d_head, 1.0 / n_real)
        dx = dx / n_real
        owed: Dict = {}  # the cotangents of what the layers still to come handed on
        for i in reversed(range(len(params["layers"]))):
            d_handed = {name: owed.pop(name, jnp.zeros_like(value))
                        for name, value in handeds[i].items()}
            dp, dx, d_given = _layer_vjp(
                params["layers"][i], xs[i], seg, givens[i], dx, d_handed, frozen, _depth(cfg, i))
            for name, d in d_given.items():
                owed[name] = owed[name] + d if name in owed else d
            add(grads["layers"][i], dp, 1.0)
        np.add.at(grads["embed"], np.asarray(tokens), np.asarray(dx))
    return total / n_real, grads, logits


@_highest
def loss(params, rows, segs, cfg) -> float:
    """The training loss alone."""
    eps = cfg["layer_norm_eps"]
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
