"""The sequence backbone with latent attention, a sigmoid router balanced
by a bias, a leading dense layer and a multi-token-prediction module,
against its plain reference, at a small size on the CPU: hidden 64, 4 heads
(keys of 16 + 8 rotary, values of 12), 8 routed experts of which 3 are held,
one dense layer + two sparse ones + the module, rows of 64 slots.

The program computes in float32 here (``compute_dtype`` of the tiny
configuration), so the distances are those of the mathematics: summation
order and nothing else.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import attention, flash_attention, splash_attention
from predictionio_tpu.testing import joyai_flash_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "joyai-flash-tiny.json")) as f:
    TINY = json.load(f)
REF_CFG = {**TINY, **TINY["backbone"]}
VOCAB, L = 50, 64


def rel(got, want):
    got = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


@pytest.fixture(scope="module")
def cfg():
    return bb.BackboneConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with every norm scale and router bias moved off its
    starting value, so that none of them drops out of a comparison."""
    drawn = bb.init_params(cfg, VOCAB, L, 0)
    leaves, treedef = jax.tree_util.tree_flatten(drawn)
    rng = np.random.default_rng(1)
    leaves = [leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32)
              if leaf.shape[-1] in (64, 24, 16, 8) else leaf for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of L + 1 slots: three histories and padding in the
    first, one history that fills the second."""
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    segs[0, :20], segs[0, 20:57], segs[0, 57:62] = 1, 2, 3
    segs[1, :] = 1
    return rows, segs


@pytest.fixture(scope="module")
def both(cfg, params, batch):
    """Program and reference on the same weights and batch."""
    rows, segs = batch
    program = jax.jit(jax.value_and_grad(
        lambda mp, r, s: bb.loss_fn(cfg, mp, r, s), has_aux=True))
    (loss, (hidden, counters, ran)), grads = program(params, rows, segs)
    slots = [np.arange(0, 60, 7), np.arange(3, 64, 5)]
    want = ref.loss_and_grads(bb.layers_of(params, cfg), rows, segs, REF_CFG, sample=slots)
    logits = [bb.logits_of(cfg, params, hidden[b][at]) for b, at in enumerate(slots)]
    mtp_logits = [bb.logits_of(cfg, params, ran["mtp_hidden"][b][at], params["mtp"]["norm"])
                  for b, at in enumerate(slots)]
    return {
        "loss": float(loss), "mtp_loss": float(counters["mtp_loss"]),
        "grads": bb.layers_of(grads, cfg), "logits": logits, "mtp_logits": mtp_logits,
        "counters": counters, "ran": ran,
        "want": dict(zip(("loss", "main", "mtp_loss", "grads", "logits", "mtp_logits"), want)),
    }


def test_both_losses_match_reference(both):
    want = both["want"]
    assert abs(both["loss"] - want["loss"]) / want["loss"] < 1e-6
    assert abs(both["mtp_loss"] - want["mtp_loss"]) / want["mtp_loss"] < 1e-6
    weight = TINY["backbone"]["mtp_loss_weight"]
    assert want["loss"] == pytest.approx(want["main"] + weight * want["mtp_loss"], rel=1e-12)


@pytest.mark.parametrize("head", ["logits", "mtp_logits"])
def test_logits_of_both_heads_match_reference(both, head):
    for got, want in zip(both[head], both["want"][head]):
        assert rel(got, want) < 1e-4


GROUPS = {
    "latent": lambda layer: layer["attn"],
    "router": lambda layer: layer["moe"]["router"] if "moe" in layer else None,
    "experts": lambda layer: layer["moe"]["experts"] if "moe" in layer else None,
    "shared": lambda layer: layer["moe"]["shared"] if "moe" in layer else None,
    "dense": lambda layer: layer.get("mlp"),
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}
MTP_PARTS = ["enorm", "hnorm", "eh_proj", "norm", "block"]


@pytest.mark.parametrize(
    "group", sorted(GROUPS) + ["embed", "head", "final_norm"] + [f"mtp.{p}" for p in MTP_PARTS])
def test_gradient_group_matches_reference(both, group):
    got, want = both["grads"], both["want"]["grads"]
    if group in GROUPS:
        pick = GROUPS[group]
        got = [pick(layer) for layer in got["layers"]]
        want = [pick(layer) for layer in want["layers"]]
        assert any(w is not None for w in want)
    elif group.startswith("mtp."):
        got, want = got["mtp"][group[4:]], want["mtp"][group[4:]]
    else:
        got, want = got[group], want[group]
    pairs = list(zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    assert pairs
    for a, b in pairs:
        if np.any(b):  # nothing flows to a router's bias
            assert rel(a, b) < 5e-4, group
        else:
            assert not np.any(a), group


def test_the_layout_is_one_dense_layer_two_sparse_and_the_module(cfg, both):
    layers = both["grads"]["layers"]
    assert ["mlp" in layer for layer in layers] == [True, False, False]
    assert all("attn" in layer for layer in layers)
    assert (cfg.n_periods, cfg.first_k_dense_replace, cfg.router_bias) == (2, 1, True)
    assert cfg.shared_expert_intermediate_size == 32  # n_shared_experts x moe_intermediate_size
    counters = both["counters"]
    assert int(np.sum(counters["dropped"])) + int(counters["mtp_dropped"]) == 0
    assert np.asarray(counters["expert_tokens"]).shape == (2, 1, 3)
    assert np.asarray(counters["router_tokens"]).shape == (2, 1, 8)
    # every slot of the batch chooses num_experts_per_tok experts, in each layer
    assert (np.asarray(counters["router_tokens"]).sum(-1) == 2 * L * 3).all()
    assert int(np.asarray(counters["mtp_router_tokens"]).sum()) == 2 * L * 3


def test_the_first_sparse_layers_core_is_the_softmax_of_what_it_was_handed(both, batch):
    """The aux carries q, k, v and o of every period's attention core:
    o is the reference's softmax on those very q, k, v."""
    ran, segs = both["ran"], batch[1]
    assert ran["q"].shape == (2, 2, 4, L, 24) and ran["v"].shape == (2, 2, 4, L, 12)
    for b in range(2):
        want = ref.softmax_attention_of(ran["q"][0, b], ran["k"][0, b], ran["v"][0, b], segs[b, :-1])
        assert rel(ran["o"][0, b], want) < 1e-5
    # the rotary part of the key is one vector a slot, shared by the heads
    k_rope = np.asarray(ran["k"][0, 0, :, :, 16:])
    assert (k_rope == k_rope[:1]).all()


# -- packing ----------------------------------------------------------------
def test_a_packed_row_is_its_histories_one_by_one(cfg, params, batch):
    """Rotary positions restart with each history, attention does not cross
    a boundary, and neither does the prediction module: the hidden states of
    a history inside a packed row, the trunk's and the module's, are those
    of the history alone in a row; the module's targets stop two slots
    before a history's end."""
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, *_ = bb.hidden_states(cfg, params, tokens, seg)
    next_tokens, _, valid = bb.split_rows_mtp(rows[:1], segs[:1])
    packed_mtp, _ = bb.mtp_hidden(cfg, params, packed, next_tokens, seg)
    for sid, (lo, hi) in {1: (0, 20), 2: (20, 57), 3: (57, 62)}.items():
        n = hi - lo
        alone, alone_seg = np.zeros((1, L + 1), np.int32), np.zeros((1, L + 1), np.int32)
        alone[0, :n], alone_seg[0, :n] = rows[0, lo:hi], 1
        single, *_ = bb.hidden_states(cfg, params, alone[:, :-1], alone_seg[:, :-1])
        assert rel(packed[0, lo:hi], single[0, :n]) < 1e-5, sid
        single_next, _, single_valid = bb.split_rows_mtp(alone, alone_seg)
        single_mtp, _ = bb.mtp_hidden(cfg, params, single, single_next, alone_seg[:, :-1])
        # the last slot of a history is handed its neighbour's first id (or
        # padding): it counts for nothing and nothing reads it
        assert rel(packed_mtp[0, lo:hi - 1], single_mtp[0, :n - 1]) < 1e-5, sid
        assert np.asarray(valid)[0, lo:hi].tolist() == [True] * (n - 2) + [False] * 2
        assert np.asarray(single_valid)[0, :n].tolist() == [True] * (n - 2) + [False] * 2
    assert not np.asarray(valid)[0, 62:].any()


def test_a_neighbour_let_in_changes_the_row(cfg, params, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, *_ = bb.hidden_states(cfg, params, tokens, seg)
    merged, *_ = bb.hidden_states(cfg, params, tokens, np.minimum(seg, 1))
    assert rel(merged[0, 20:57], packed[0, 20:57]) > 1e-3


def test_the_module_mask_is_the_references(batch):
    rows, segs = batch
    got = [np.asarray(a) for a in bb.split_rows_mtp(rows, segs)]
    for b in range(2):
        want = ref.split_row_mtp(rows[b], segs[b])
        for a, w in zip(got, want):
            assert (a[b] == w).all()


# -- the router -------------------------------------------------------------
def _moe_params(rng, d=16, e=8, f=8):
    w = lambda *shape: (rng.normal(size=shape) * 0.3).astype(np.float32)  # noqa: E731
    return {
        "router": rng.normal(size=(d, e)).astype(np.float32),
        "router_bias": (0.2 * rng.normal(size=(e,))).astype(np.float32),
        "shared": {"wg": w(d, f), "wu": w(d, f), "wd": w(f, d)},
        "experts": {"wg": w(e, d, f), "wu": w(e, d, f), "wd": w(e, f, d)},
    }


def _share(full, first, count):
    held = jax.tree_util.tree_map(lambda a: a[first:first + count], full["experts"])
    return {**full, "experts": held}


def _ref_cfg(first, count, top_k=3):
    return {"experts_held": [first, count], "num_experts_per_tok": top_k,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5}


SIGMOID = dict(top_k=3, scoring="sigmoid", scale=2.5)


def test_the_shares_add_up_to_the_uncut_layer():
    """What all shares give, the shared expert counted once, is what the
    uncut reference gives for the whole layer (model-configs guide, section
    4), under the sigmoid router with a bias that is not zero."""
    rng = np.random.default_rng(6)
    full = _moe_params(rng)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_block(full, jnp.asarray(x), _ref_cfg(0, 8))
        shared_once = np.asarray(ref._swiglu(full["shared"], x))
    total = np.zeros_like(x)
    counted = np.zeros(8, np.int64)
    for first in range(0, 8, 2):
        y, counters = moe.expert_layer(_share(full, first, 2), x, first=first, **SIGMOID)
        total += np.asarray(y) - shared_once
        counted[first:first + 2] = np.asarray(counters["expert_tokens"])
        assert int(counters["dropped"]) == 0
        # every share counts the same tokens over the router's whole width
        assert (np.asarray(counters["router_tokens"])[first:first + 2] == counted[first:first + 2]).all()
    assert counted.sum() == 40 * 3
    assert rel(total + shared_once, whole) < 1e-5


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3), (6, 2)])
def test_expert_layer_matches_reference_share(first, count):
    rng = np.random.default_rng(7)
    full = _moe_params(rng)
    x = rng.normal(size=(33, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_block(_share(full, first, count), jnp.asarray(x), _ref_cfg(first, count))
    got, _ = moe.expert_layer(_share(full, first, count), x, first=first, **SIGMOID)
    assert rel(got, want) < 1e-5


def test_the_bias_picks_but_does_not_weigh():
    rng = np.random.default_rng(8)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    router = rng.normal(size=(16, 8)).astype(np.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    plain_idx, plain_w = moe.route(x, router, 3, scoring="sigmoid", scale=2.5)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0  # expert 5 is picked by every token, whatever it scores
    idx, w = moe.route(x, router, 3, scoring="sigmoid", bias=bias, scale=2.5)
    idx, w = np.asarray(idx), np.asarray(w)
    assert (idx == 5).any(axis=1).all() and not (np.asarray(plain_idx) == 5).any(axis=1).all()
    # the weights are the chosen experts' own scores, renormalised, times the scale
    chosen = np.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(w, 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(plain_w).sum(1), 2.5, rtol=1e-5)
    # and no gradient reaches the bias
    grad = jax.grad(lambda b: moe.route(x, router, 3, scoring="sigmoid", bias=b)[1].sum())(bias)
    assert not np.asarray(grad).any()


def test_softmax_routing_is_what_it_was():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(32, 16)).astype(np.float32)
    router = rng.normal(size=(16, 8)).astype(np.float32)
    idx, w = moe.route(x, router, 3)
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    top = np.sort(probs, axis=1)[:, ::-1][:, :3]
    np.testing.assert_allclose(w, top / top.sum(1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError):
        moe.route(x, router, 3, scoring="tanh")


def test_the_bias_step_is_the_rule(cfg, params):
    """b + rate * sign(mean load - load) from the step's own counts, every
    expert layer its own; an expert at exactly the mean stays; what the
    optimizer did to the leaf (weight decay) does not reach it."""
    rng = np.random.default_rng(10)
    counts = rng.integers(0, 97, size=(2, 1, 8)).astype(np.int32)
    counts[0, 0] = [48, 48, 40, 56, 48, 0, 96, 48]  # mean 48: four ties
    mtp_counts = rng.integers(0, 97, size=(8,)).astype(np.int32)
    decayed = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    after = bb.step_routers(
        cfg, params, decayed, {"router_tokens": counts, "mtp_router_tokens": mtp_counts})
    before = params["periods"]["ffn"]["router_bias"]
    got = np.asarray(after["periods"]["ffn"]["router_bias"])
    np.testing.assert_array_equal(got, ref.bias_step(before, counts, cfg.router_bias_rate))
    moved = (got - np.asarray(before))[0, 0] / cfg.router_bias_rate
    np.testing.assert_allclose(moved, [0, 0, 1, -1, 0, 1, -1, 0], atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(after["mtp"]["block"]["ffn"]["router_bias"]),
        ref.bias_step(params["mtp"]["block"]["ffn"]["router_bias"], mtp_counts, cfg.router_bias_rate))
    # everything else is the optimizer's
    np.testing.assert_array_equal(after["embed"], decayed["embed"])
    np.testing.assert_array_equal(
        after["periods"]["ffn"]["router"], decayed["periods"]["ffn"]["router"])


def test_a_held_router_stays_where_it_was(cfg, params):
    """``router_trains`` off: the optimizer's step does not reach any
    router's matrix, the module's neither; every other leaf is the
    optimizer's and the bias is stepped as ever."""
    import dataclasses

    held = dataclasses.replace(cfg, router_trains=False)
    counts = {"router_tokens": np.ones((2, 1, 8), np.int32),
              "mtp_router_tokens": np.ones((8,), np.int32)}
    decayed = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    after = bb.step_routers(held, params, decayed, counts)
    for pick in (lambda t: t["periods"]["ffn"], lambda t: t["mtp"]["block"]["ffn"]):
        np.testing.assert_array_equal(pick(after)["router"], pick(params)["router"])
        np.testing.assert_array_equal(pick(after)["router_bias"], pick(params)["router_bias"])
        np.testing.assert_array_equal(pick(after)["shared"]["wg"], pick(decayed)["shared"]["wg"])
    np.testing.assert_array_equal(after["embed"], decayed["embed"])


@pytest.mark.parametrize("trains", [True, False])
def test_a_job_steps_its_routers_only_where_they_train(cfg, batch, trains):
    """Three steps of the job's own step program: with ``router_trains``
    off the routers are the drawn ones bit for bit; the experts beside
    them and the bias have moved either way."""
    import dataclasses

    from predictionio_tpu.models import sequencerec

    this = dataclasses.replace(cfg, router_trains=trains)
    opt_init, step, _ = sequencerec._programs(this, 1e-2, None, "auto")
    drawn = bb.init_params(this, VOCAB, L, 0)
    start = jax.tree_util.tree_map(np.asarray, drawn)
    state, losses = opt_init(drawn), []
    for _ in range(3):
        drawn, state, loss, _ = step(drawn, state, *batch)
        losses.append(float(loss))
    routers = [(t["periods"]["ffn"], t["mtp"]["block"]["ffn"]) for t in (start, drawn)]
    for before, after in zip(*routers):
        assert np.array_equal(before["router"], after["router"]) != trains
        assert not np.array_equal(before["experts"]["wg"], after["experts"]["wg"])
        assert not np.array_equal(before["router_bias"], after["router_bias"])
    # the first step's loss is the drawn parameters', whatever is held
    assert losses[0] == pytest.approx(float(bb.loss_fn(this, start, *batch)[0]), rel=1e-5)
    assert np.isfinite(losses).all()


def test_a_backbone_without_a_bias_is_not_touched():
    toy = bb.BackboneConfig.toy(32, 2, 2)
    marker = {"anything": 1}
    assert bb.step_routers(toy, marker, marker, {}) is marker


# -- attention with a value width of its own --------------------------------
def _naive_attention(q, k, v, seg):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    idx = jnp.arange(q.shape[2])
    keep = (idx[:, None] >= idx[None, :])[None, None] & (
        seg[:, None, :, None] == seg[:, None, None, :])
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)


@pytest.fixture(scope="module")
def qkv_seg():
    rng = np.random.default_rng(11)
    q = rng.normal(size=(2, 4, 70, 24)).astype(np.float32)
    k = rng.normal(size=(2, 4, 70, 24)).astype(np.float32)
    v = rng.normal(size=(2, 4, 70, 12)).astype(np.float32)
    seg = np.sort(rng.integers(1, 5, size=(2, 70)), axis=1).astype(np.int32)
    return q, k, v, seg


@pytest.mark.parametrize("block", [16, 32, 128])
def test_flash_with_a_value_width_of_its_own(qkv_seg, block):
    q, k, v, seg = qkv_seg
    got = flash_attention(q, k, v, causal=True, block_k=block, segment_ids=seg)
    assert got.shape == (2, 4, 70, 12)
    assert rel(got, _naive_attention(q, k, v, seg)) < 1e-5


def test_flash_backward_with_a_value_width_of_its_own(qkv_seg):
    q, k, v, seg = qkv_seg
    got = jax.grad(lambda *a: (flash_attention(*a, block_k=16, segment_ids=seg) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_naive_attention(*a, seg) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_a_values_columns_do_not_see_each_other(qkv_seg):
    """The first columns of a wider value come out as they do alone, forward
    and backward (to rounding: the products tile by width)."""
    q, k, v, seg = qkv_seg
    rng = np.random.default_rng(12)
    wide = rng.normal(size=(2, 4, 70, 24)).astype(np.float32)
    narrow = wide[..., :12]
    a = flash_attention(q, k, wide, block_k=16, segment_ids=seg)
    b = flash_attention(q, k, narrow, block_k=16, segment_ids=seg)
    assert rel(np.asarray(a)[..., :12], b) < 1e-6
    ga = jax.grad(lambda v_: (flash_attention(q, k, v_, block_k=16, segment_ids=seg)[..., :12] ** 2).sum())(wide)
    gb = jax.grad(lambda v_: (flash_attention(q, k, v_, block_k=16, segment_ids=seg) ** 2).sum())(narrow)
    assert rel(np.asarray(ga)[..., :12], gb) < 1e-6 and not np.asarray(ga)[..., 12:].any()


def test_softmax_statistics_in_bfloat16_are_seen(qkv_seg):
    """The control of the benchmark's cell: the running maximum, sum and
    output kept in bfloat16 between tiles is another result."""
    q, k, v, seg = qkv_seg
    sound = flash_attention(q, k, v, block_k=16, segment_ids=seg)
    low = flash_attention(q, k, v, block_k=16, segment_ids=seg, stats_dtype="bfloat16")
    assert low.dtype == sound.dtype and 1e-4 < rel(low, sound) < 5e-2


def test_the_pallas_kernel_interpreted_is_the_xla_loop():
    """JAX's splash attention, the kernel the chip runs for this backbone,
    interpreted off the chip: keys of 24, values of 12, two blocks of 128."""
    rng = np.random.default_rng(13)
    q = rng.normal(size=(2, 2, 256, 24)).astype(np.float32)
    k = rng.normal(size=(2, 2, 256, 24)).astype(np.float32)
    v = rng.normal(size=(2, 2, 256, 12)).astype(np.float32)
    seg = np.sort(rng.integers(1, 4, size=(2, 256)), axis=1).astype(np.int32)
    got = splash_attention(q, k, v, seg, block=128, interpret=True)
    assert rel(got, _naive_attention(q, k, v, seg)) < 1e-5
    grads = jax.grad(lambda *a: (splash_attention(*a, seg, block=128, interpret=True) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_naive_attention(*a, seg) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        assert rel(a, b) < 1e-5


def test_off_the_chip_the_kernel_asked_for_is_the_xla_loop(qkv_seg):
    q, k, v, seg = qkv_seg
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v, segment_ids=seg, block=32, kernel="splash")),
        np.asarray(attention(q, k, v, segment_ids=seg, block=32)))
    with pytest.raises(ValueError):
        attention(q, k, v, segment_ids=seg, kernel="mosaic")


# -- the normal path --------------------------------------------------------
def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming this configuration
    trains and answers through the same workflow as every template; the
    job's counters carry the module's loss and the routers' loads and bias;
    ``predict`` does not need the module."""
    import datetime as dt

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.sequencerec import (
        Query, SeqDataSourceParams, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, engine_factory)
    from predictionio_tpu.storage import Event, get_registry
    from predictionio_tpu.workflow.context import WorkflowContext

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    get_registry(refresh=True)
    store = get_registry().get_events()
    store.init(11)
    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(8):
        for t in range(5 + 3 * u):
            store.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}", target_entity_type="item",
                target_entity_id=f"i{(u + t) % 7}", event_time=t0 + dt.timedelta(minutes=t)), 11)
    algo_params = SeqRecAlgorithmParams(
        backbone="joyai-flash-tiny", steps=30, batch_size=2, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=11)),
        preparator_params=("", SeqPreparatorParams(seq_len=32)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.config.attention == "mla" and model.config.experts_held == (2, 3)
    assert model.losses[-1] < model.losses[0]
    stats = model.stats
    assert "conv" not in stats and "delta_rule_walk" not in stats  # no mixer with a convolution
    assert stats["mtp_loss_by_step"].shape == (30,) and stats["mtp_loss_by_step"][-1] < stats["mtp_loss_by_step"][0]
    assert stats["router_tokens_by_step"].shape == (30, 2, 1, 8)
    assert stats["mtp_router_tokens_by_step"].shape == (30, 8)
    # the module's block is an expert layer too: 30 steps x (2 sparse layers + the module's)
    assert stats["mtp_passes_by_step"].shape == (30,) and stats["layer_steps"] == 90
    assert stats["overflow_layer_steps"] == int((stats["passes_by_step"] > 1).sum()
                                                + (stats["mtp_passes_by_step"] > 1).sum())
    # the bias the job ends with is the rule applied to every step's counts
    bias = np.zeros((2, 1, 8), np.float32)
    for counts in stats["router_tokens_by_step"]:
        bias = ref.bias_step(bias, counts, model.config.router_bias_rate)
    np.testing.assert_allclose(model.params["periods"]["ffn"]["router_bias"], bias, atol=1e-6)
    assert 0.0 < stats["router_bias_abs_max"] <= 30 * model.config.router_bias_rate + 1e-6
    algo = SeqRecAlgorithm(algo_params)
    query = Query(recent_items=("i0", "i1", "i2"), num=3)
    answer = algo.predict(model, query)
    assert len(answer.item_scores) == 3
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    # serving ignores the prediction module
    model.params = {k: v for k, v in model.params.items() if k != "mtp"}
    model.__dict__.pop("_device_params", None)
    assert algo.predict(model, query) == answer
    get_registry(refresh=True)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "joyai_flash_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_joyai.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "joyai_flash_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "joyai-flash-48b-a3b-ep16.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "seqrec-joyai-flash-48b-a3b-ep16.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    published = {
        "hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "num_attention_heads": 32, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 32000000, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "router_width": 256}
    for key, value in published.items():
        assert conf[key] == value, key
    assert bench["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280}
    assert bench["reduced"] == sorted(bench["published"], key=list(bench["published"]).index)
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.attention, cfg.router_bias, cfg.shared_expert_gate) == ("mla", True, False)
    assert (cfg.n_periods, cfg.period, cfg.experts_held) == (4, 1, (0, 16))
    assert cfg.shared_expert_intermediate_size == 768
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 8192, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(shapes["dense"]["full"]) == 26_347_520
    assert count(shapes["dense"]) == 70_391_808
    assert count(shapes["periods"]) == 4 * (107_091_968 + 256)
    assert count(shapes["mtp"]) == 115_486_720 + 256
    assert count(shapes) == 680_441_088


@pytest.mark.parametrize("bad", [
    {"num_nextn_predict_layers": 2}, {"backbone": {"attention": "mla", "norm": "layer"}},
    {"num_hidden_layers": 4, "full_attention_interval": 2, "first_k_dense_replace": 1}])
def test_configurations_the_backbone_cannot_run_are_refused(bad):
    merged = {**TINY, **{k: v for k, v in bad.items() if k != "backbone"}}
    merged["backbone"] = {**TINY["backbone"], **bad.get("backbone", {})}
    with pytest.raises(ValueError):
        bb.BackboneConfig.from_dict(merged)
