"""A sparse expert layer that is told which experts it holds.

The router scores ALL ``n_experts`` in float32 and keeps ``top_k`` of them
a token (:func:`route`). ``softmax``: a softmax over all experts, the
``top_k`` largest, renormalised over those ``top_k`` (``norm_topk``).
``sigmoid``: a sigmoid of every logit on its own; the ``top_k`` largest of
score + ``router_bias`` are chosen, a bias that no gradient moves and the
trainer steps from the experts' loads; their weights are the scores WITHOUT
the bias, renormalised over the ``top_k`` and times ``scale``. This share
holds the contiguous range ``[first, first + count)``: it computes, for
every token, the part of the result that its own experts give (a grouped
matrix product over the assignments sorted by expert) plus, where the layer
has one, the shared expert (behind a sigmoid gate where the layer has a
``shared_gate``), which every share computes alike. What the absent experts
would add is left out; no code stands in for the chips that hold them.

An expert, routed or shared, is one of two feed-forwards (``act``):
``swiglu``, three matrices, ``W_d (silu(W_g h) * W_u h)``; or ``relu2``, two,
``W_d relu(W_u h)^2``, no gate (the square is float32's, before the cast).

No assignment is dropped. Shapes are static, so the sorted assignments are
taken ``pass_rows`` at a time (default: twice this share's mean load), in
as many passes as all of a step's assignments could need. The usual step
needs one, and that one runs in line: its sum starts the result and its
gradient is one backward of one pass. The passes after it stand behind ONE
``cond`` on whether the held assignments overflow a pass (decided on the
device from the count the router just produced), and inside it a pass past
the last held assignment is skipped. The passes' sum has a VJP of its own
(:func:`_passes`): the gradient is the sum of the passes' gradients, each
pass recomputed where it is pulled back, accumulated inside the same
``cond``; so a step that needs one pass fills, carries and adds nothing for
the passes it does not run, forward or backward. ``dropped`` in the
counters is the held assignments less the rows the passes that ran
combined into the result (each pass, the one in line too, counts the rows
its own mask let through), so a pass that is skipped, short or cut wrongly
shows there; ``passes`` is how many the step needed.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def swiglu(w: Dict, x, cd):
    f32 = jnp.float32
    xc = x.astype(cd)
    gate = jnp.dot(xc, w["wg"].astype(cd), preferred_element_type=f32)
    up = jnp.dot(xc, w["wu"].astype(cd), preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(cd), w["wd"].astype(cd),
                   preferred_element_type=f32)


def relu2(w: Dict, x, cd):
    """``W_d relu(W_u x)^2``: the ungated feed-forward (``wu``, ``wd``)."""
    f32 = jnp.float32
    up = jnp.dot(x.astype(cd), w["wu"].astype(cd), preferred_element_type=f32)
    return jnp.dot(jnp.square(jax.nn.relu(up)).astype(cd), w["wd"].astype(cd),
                   preferred_element_type=f32)


#: an expert's feed-forward by its name; the routed experts' passes run the
#: same arithmetic as grouped products (:func:`_held_pass`)
ACTS = {"swiglu": swiglu, "relu2": relu2}


def route(x, router, top_k: int, norm_topk: bool = True, scoring: str = "softmax",
          bias=None, scale: float = 1.0, norm_eps: float = 1e-20):
    """x [T, D], router [D, E] -> expert ids [T, k] and weights [T, k]
    (float32). ``softmax``: over all E, the k largest, renormalised.
    ``sigmoid``: the k largest of sigmoid + ``bias`` [E]; the weights are
    the sigmoids alone, renormalised (over their sum + ``norm_eps``, the
    public implementation's own constant), times ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), router, precision=_HI)
    if scoring == "softmax":
        top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if norm_topk:
            top = top / top.sum(-1, keepdims=True)
        return idx, top
    if scoring != "sigmoid":
        raise ValueError(f"unknown scoring function {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        top = top / (top.sum(-1, keepdims=True) + norm_eps)
    return idx, top * scale


def _held_pass(x, experts: Dict, take, group_sizes, weights, top_k: int, cd, act: str):
    """``take``: sorted assignments (token * k + slot) of one pass, the
    first ``group_sizes.sum()`` of them held. The rows past those lie past
    the last group, where the chip's grouped product writes NOTHING,
    forward or backward: what it leaves there is whatever the buffer held.
    So those rows are masked where they enter (their cotangent must not be
    scattered onto a token) and where they leave. Returns the tokens' sums
    and how many rows the mask let through."""
    f32 = jnp.float32
    tok = take // top_k
    live = (jnp.arange(take.shape[0]) < group_sizes.sum())[:, None]
    with jax.named_scope("seq.moe.dispatch"):
        xs = jnp.where(live, x.astype(cd)[tok], 0)
    with jax.named_scope("seq.moe.experts"):
        if act == "swiglu":
            gate = jax.lax.ragged_dot(xs, experts["wg"].astype(cd), group_sizes,
                                      preferred_element_type=f32)
        up = jax.lax.ragged_dot(xs, experts["wu"].astype(cd), group_sizes,
                                preferred_element_type=f32)
        hidden = jnp.where(
            live, jax.nn.silu(gate) * up if act == "swiglu" else jnp.square(jax.nn.relu(up)), 0.0)
        ys = jax.lax.ragged_dot(hidden.astype(cd), experts["wd"].astype(cd),
                                group_sizes, preferred_element_type=f32)
    with jax.named_scope("seq.moe.combine"):
        ys = jnp.where(live, ys * weights[take][:, None], 0.0)
        return jax.ops.segment_sum(ys, tok, num_segments=x.shape[0]), live.sum(dtype=jnp.int32)


def _passes_for(all_rows: int, rows: int) -> int:
    """Passes of ``rows`` sorted assignments that cover ``all_rows``."""
    return -(-all_rows // rows)


@functools.partial(jax.jit, static_argnums=0)
def _one_pass(static, x, experts, flat_w, order, group_sizes, start):
    """The pass over the ``rows`` sorted assignments from ``start`` on: the
    tokens' sums and how many rows its mask let through. A jitted function
    (as its pull-back below), so that it is traced and lowered once for a
    shape, whichever layer, branch or direction asks for it."""
    rows, _, top_k, cd, act = static
    take = jax.lax.dynamic_slice_in_dim(order, start, rows)
    # what of every group lies inside [start, start + rows)
    ends = jnp.cumsum(group_sizes)
    inside = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - group_sizes - start, 0, rows)
    return _held_pass(x, experts, take, inside, flat_w, top_k, cd, act)


def _over_passes(static, n_held, one):
    """``one(start)``, a tree of arrays, summed over the passes that hold an
    assignment. The first pass runs in line and starts the sum; the passes
    after it, which only a step whose held assignments overflow ``rows``
    needs, stand behind one ``cond``, and there a pass past the last held
    assignment is skipped."""
    rows, passes = static[:2]
    total = one(jnp.int32(0))
    if passes == 1:
        return total

    def one_more(total, start):
        return jax.lax.cond(
            start < n_held, lambda total: jax.tree_util.tree_map(jnp.add, total, one(start)),
            lambda total: total, total), None

    starts = jnp.arange(1, passes, dtype=jnp.int32) * rows
    return jax.lax.cond(n_held > rows, lambda total: jax.lax.scan(one_more, total, starts)[0],
                        lambda total: total, total)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _passes(static, x, experts, flat_w, order, group_sizes):
    """Every held assignment through its experts, ``rows`` sorted
    assignments a pass: the tokens' sums [T, D] and the rows the passes'
    masks let through. ``static``: ``rows``, ``passes``, ``top_k``, the
    compute dtype and ``act``. Its gradient is the sum of the passes' own, each
    recomputed where it is pulled back: nothing is kept from the forward
    pass but the arguments, and no cotangent is carried through a pass that
    does not run."""
    return _over_passes(static, group_sizes.sum(), lambda start: _one_pass(
        static, x, experts, flat_w, order, group_sizes, start))


def _passes_fwd(static, *args):
    return _passes(static, *args), args


@functools.partial(jax.jit, static_argnums=0)
def _pull_pass(static, x, experts, flat_w, order, group_sizes, start, ct):
    """The cotangents of ``x``, ``experts`` and ``flat_w`` through the pass
    from ``start`` on, the pass recomputed."""
    _, vjp = jax.vjp(lambda *inputs: _one_pass(static, *inputs, order, group_sizes, start)[0],
                     x, experts, flat_w)
    return vjp(ct)


def _passes_bwd(static, args, cotangents):
    group_sizes = args[-1]
    pulled = _over_passes(static, group_sizes.sum(), lambda start: _pull_pass(
        static, *args, start, cotangents[0]))
    return (*pulled, None, None)  # nothing for ``order`` and ``group_sizes``


_passes.defvjp(_passes_fwd, _passes_bwd)


def expert_layer(p: Dict, x, *, first: int, top_k: int, norm_topk: bool = True,
                 pass_rows: int = 0, compute_dtype=jnp.float32, scoring: str = "softmax",
                 scale: float = 1.0, norm_eps: float = 1e-20,
                 act: str = "swiglu") -> Tuple[jax.Array, Dict]:
    """x [T, D] (normed) -> y [T, D] float32 and the step's counters.
    ``p``: ``router`` [D, E] and ``experts`` (``wg`` unless ``act`` is
    ``relu2``, ``wu`` [.., D, F], ``wd`` [.., F, D], with a leading [count]
    axis: the experts ``first .. first + count - 1``); where the layer has
    them, ``shared`` (one expert every token goes through), ``shared_gate``
    [D] and ``router_bias`` [E] (then the counters also give ``router_tokens`` [E]: the tokens of every
    expert, held or not)."""
    if act not in ACTS:
        raise ValueError(f"unknown expert activation {act!r}: {' or '.join(ACTS)}")
    x = jnp.asarray(x)
    tokens = x.shape[0]
    count = p["experts"]["wu"].shape[0]
    n_experts = p["router"].shape[1]
    cd = compute_dtype
    all_rows = tokens * min(top_k, count)  # a token's k experts are distinct
    rows = min(all_rows, pass_rows or max(8, 2 * tokens * top_k * count // n_experts))
    passes = _passes_for(all_rows, rows)
    with jax.named_scope("seq.moe.route"):
        bias = p.get("router_bias")
        idx, weights = route(x, p["router"], top_k, norm_topk, scoring, bias, scale, norm_eps)
        local = idx - first
        held = (local >= 0) & (local < count)
        flat = jnp.where(held, local, count).reshape(-1)  # absent experts sort last
        order = jnp.argsort(flat, stable=True)
        order = jnp.pad(order, (0, max(0, passes * rows - order.shape[0])))
        group_sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
        n_held = group_sizes.sum()
        flat_w = weights.reshape(-1)

    y, combined = _passes((rows, passes, top_k, cd, act), x, p["experts"], flat_w, order, group_sizes)
    if "shared" in p:
        with jax.named_scope("seq.moe.shared"):
            if "shared_gate" in p:
                gate = jax.nn.sigmoid(
                    jnp.dot(x.astype(jnp.float32), p["shared_gate"], precision=_HI))
                y = y + gate[:, None] * ACTS[act](p["shared"], x, cd)
            else:
                y = y + ACTS[act](p["shared"], x, cd)
    counters = {
        "expert_tokens": group_sizes,
        "absent_weight": jnp.where(held, 0.0, weights).sum() / (tokens * scale),
        "dropped": n_held - combined,
        "passes": -(-n_held // rows),
    }
    if bias is not None:
        counters["router_tokens"] = jnp.bincount(idx.reshape(-1), length=n_experts).astype(jnp.int32)
    return y, counters
