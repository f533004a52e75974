"""The sequence backbone with gated short-convolution layers beside
grouped-query attention (a norm on q and k, no gate), a sigmoid router
balanced by a bias, no shared expert and a leading dense layer whose mixer
is a convolution, against its plain reference, at a small size on the CPU:
hidden 64, 4 query heads on 2 key/value heads of 16, 8 routed experts of
which 3 are held, one dense layer + two periods of (attention, three
convolutions), rows of 64 slots.

The program computes in float32 here (``compute_dtype`` of the tiny
configuration), so the distances are those of the mathematics: summation
order and nothing else.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.shortconv import causal_conv, gated_conv, short_conv
from predictionio_tpu.testing import lfm2_moe_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "lfm2-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L = 50, 64
HISTORIES = {1: (0, 20), 2: (20, 57), 3: (57, 62)}


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
              if leaf.shape[-1] in (64, 16, 8) else leaf for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of L + 1 slots: three histories and padding in the
    first, one history that fills the second."""
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    for sid, (lo, hi) in HISTORIES.items():
        segs[0, lo:hi] = sid
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
    want = ref.loss_and_grads(bb.layers_of(params, cfg), rows, segs, TINY, sample=slots)
    logits = [bb.logits_of(cfg, params, hidden[b][at]) for b, at in enumerate(slots)]
    return {"loss": float(loss), "grads": bb.layers_of(grads, cfg), "logits": logits,
            "counters": counters, "ran": ran, "want": dict(zip(("loss", "grads", "logits"), want))}


def test_loss_and_logits_match_reference(both):
    want = both["want"]
    assert abs(both["loss"] - want["loss"]) / want["loss"] < 1e-6
    for got, expected in zip(both["logits"], want["logits"]):
        assert rel(got, expected) < 1e-4


GROUPS = {
    "shortconv": lambda layer: layer.get("conv"),
    "attention": lambda layer: layer.get("full"),
    "dense": lambda layer: layer.get("mlp"),
    "router": lambda layer: layer["moe"]["router"] if "moe" in layer else None,
    "experts": lambda layer: layer["moe"]["experts"] if "moe" in layer else None,
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    got, want = both["grads"], both["want"]["grads"]
    if group in GROUPS:
        pick = GROUPS[group]
        got = [pick(layer) for layer in got["layers"]]
        want = [pick(layer) for layer in want["layers"]]
        assert any(w is not None for w in want)
    else:
        got, want = got[group], want[group]
    pairs = list(zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    assert pairs
    for a, b in pairs:
        assert np.any(b) and rel(a, b) < 5e-4, group


def test_nothing_flows_to_the_bias_and_the_head_is_the_embedding(both, params):
    for got, want in zip(both["grads"]["layers"], both["want"]["grads"]["layers"]):
        if "moe" in want:
            assert not np.any(got["moe"]["router_bias"]) and not np.any(want["moe"]["router_bias"])
            assert "shared" not in got["moe"]
    assert "head" not in params and "head" not in both["grads"]


def test_the_layout_comes_from_layer_types(cfg, both):
    """One leading dense layer whose mixer is a convolution, then two
    periods whose full layer comes FIRST."""
    layers = both["grads"]["layers"]
    assert ["mlp" in layer for layer in layers] == [True] + [False] * 8
    assert ["conv" in layer for layer in layers] == [t == "conv" for t in TINY["layer_types"]]
    assert (cfg.period_kinds, cfg.n_periods, cfg.first_k_dense_replace) == (
        ("full", "conv", "conv", "conv"), 2, 1)
    assert cfg.mixers() == {"gqa": 2, "shortconv": 7}
    assert (cfg.router_bias, cfg.rms_norm_eps, cfg.rope_theta, cfg.conv_L_cache) == (True, 1e-5, 1e6, 3)
    counters = both["counters"]
    assert int(np.sum(counters["dropped"])) == 0
    assert np.asarray(counters["expert_tokens"]).shape == (2, 4, 3)
    assert (np.asarray(counters["router_tokens"]).sum(-1) == 2 * L * 3).all()


def test_the_chain_is_the_references_on_what_it_was_handed(both, params, batch):
    """The aux carries ``[B | C | x~]`` and ``C * conv(B * x~)`` of every
    period's first convolution layer: the second is the reference's chain
    on the first."""
    ran, segs = both["ran"], batch[1]
    assert ran["bcx"].shape == (2, 2, L, 192) and ran["y"].shape == (2, 2, L, 64)
    for period in range(2):
        taps = params["periods"]["conv"]["conv_w"][period, 0]
        for b in range(2):
            want = ref.gated_conv_of(ran["bcx"][period, b], taps, segs[b, :-1])
            assert rel(ran["y"][period, b], want) < 1e-6


@pytest.mark.parametrize("build,passes", [("float32", True), ("bfloat16", False)])
def test_the_control_build_fails_shortconv_err_and_the_sound_build_passes(build, passes):
    """The benchmark's control: gates and taps in bfloat16 on the same
    ``[B | C | x~]`` is another result, by three orders of magnitude."""
    rng = np.random.default_rng(3)
    bcx = rng.normal(size=(1, 48, 96)).astype(np.float32)
    taps = rng.normal(size=(3, 32)).astype(np.float32)
    seg = np.sort(rng.integers(1, 4, size=(1, 48)), axis=1).astype(np.int32)
    got = gated_conv(jnp.asarray(bcx), taps, seg, jnp.dtype(build))
    err = rel(np.asarray(got, np.float32), ref.gated_conv_of(bcx[0], taps, seg[0])[None])
    assert (err < 1e-5) == passes and (passes or err > 1e-3)


# -- packing ----------------------------------------------------------------
def test_a_packed_row_is_its_histories_one_by_one(cfg, params, batch):
    """No tap, key or position crosses a boundary: the hidden states of a
    history inside a packed row are those of the history alone in a row."""
    rows, segs = batch
    packed, *_ = bb.hidden_states(cfg, params, rows[:1, :-1], segs[:1, :-1])
    for sid, (lo, hi) in HISTORIES.items():
        n = hi - lo
        alone, alone_seg = np.zeros((1, L), np.int32), np.zeros((1, L), np.int32)
        alone[0, :n], alone_seg[0, :n] = rows[0, lo:hi], 1
        single, *_ = bb.hidden_states(cfg, params, alone, alone_seg)
        assert rel(packed[0, lo:hi], single[0, :n]) < 1e-5, sid


def test_a_neighbour_let_in_changes_the_row(cfg, params, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, *_ = bb.hidden_states(cfg, params, tokens, seg)
    merged, *_ = bb.hidden_states(cfg, params, tokens, np.minimum(seg, 1))
    assert rel(merged[0, 20:57], packed[0, 20:57]) > 1e-3


def test_a_tap_that_would_reach_the_neighbour_reads_zero():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 10, 4)).astype(np.float32)
    w = rng.normal(size=(3, 4)).astype(np.float32)
    seg = np.asarray([[1, 1, 1, 1, 2, 2, 2, 0, 0, 3]], np.int32)
    got = np.asarray(causal_conv(x, w, seg))
    for t in range(10):
        want = sum(w[2 - j] * x[0, t - j] for j in range(3) if t - j >= 0 and seg[0, t - j] == seg[0, t])
        np.testing.assert_allclose(got[0, t], want, rtol=1e-6, atol=1e-6)


def test_the_mixer_is_projection_chain_projection():
    rng = np.random.default_rng(5)
    p = {"w_in": rng.normal(size=(8, 24)).astype(np.float32) * 0.3,
         "conv_w": rng.normal(size=(3, 8)).astype(np.float32),
         "w_out": rng.normal(size=(8, 8)).astype(np.float32) * 0.3}
    x = rng.normal(size=(2, 12, 8)).astype(np.float32)
    seg = np.sort(rng.integers(1, 3, size=(2, 12)), axis=1).astype(np.int32)
    got, ran = short_conv(p, x, seg)
    with jax.default_matmul_precision("highest"):
        want = [ref.conv_mixer(p, jnp.asarray(x[b]), jnp.asarray(seg[b])) for b in range(2)]
    assert rel(got, np.stack(want)) < 1e-5 and set(ran) == {"bcx", "y"}


# -- the router and the expert layer without a shared expert ----------------
def _moe_params(rng, d=16, e=8, f=8):
    w = lambda *shape: (rng.normal(size=shape) * 0.3).astype(np.float32)  # noqa: E731
    return {"router": rng.normal(size=(d, e)).astype(np.float32),
            "router_bias": (0.2 * rng.normal(size=(e,))).astype(np.float32),
            "experts": {"wg": w(e, d, f), "wu": w(e, d, f), "wd": w(e, f, d)}}


def _share(full, first, count):
    held = jax.tree_util.tree_map(lambda a: a[first:first + count], full["experts"])
    return {**full, "experts": held}


def _ref_cfg(first, count):
    return {"experts_held": [first, count], "num_experts_per_tok": 4,
            "norm_topk_prob": True, "routed_scaling_factor": 1}


LFM2 = dict(top_k=4, scoring="sigmoid", scale=1, norm_eps=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """What the 8 shares of one expert each give, nothing counted twice
    since there is no shared expert, is what the uncut reference gives for
    the whole layer (model-configs guide, section 4)."""
    rng = np.random.default_rng(6)
    full = _moe_params(rng)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_block(full, jnp.asarray(x), _ref_cfg(0, 8))
    total, counted = np.zeros_like(x), np.zeros(8, np.int64)
    for first in range(8):
        y, counters = moe.expert_layer(_share(full, first, 1), x, first=first, **LFM2)
        total += np.asarray(y)
        counted[first] = int(counters["expert_tokens"][0])
        assert int(counters["dropped"]) == 0
        assert int(counters["router_tokens"][first]) == counted[first]
    assert counted.sum() == 40 * 4
    assert rel(total, whole) < 1e-5


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3), (6, 2)])
def test_expert_layer_matches_reference_share(first, count):
    rng = np.random.default_rng(7)
    full = _moe_params(rng)
    x = rng.normal(size=(33, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_block(_share(full, first, count), jnp.asarray(x), _ref_cfg(first, count))
    got, _ = moe.expert_layer(_share(full, first, count), x, first=first, **LFM2)
    assert rel(got, want) < 1e-5


def test_the_weights_are_over_their_sum_and_the_constant():
    """``norm_eps`` is what the chosen scores' sum gains before it divides
    (the configuration hands the published 1e-6 down: the test of the
    shipped file; here a constant large enough to see in float32)."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    router = rng.normal(size=(16, 8)).astype(np.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    idx, w = moe.route(x, router, 4, scoring="sigmoid", norm_eps=0.5)
    chosen = np.take_along_axis(scores, np.asarray(idx), axis=1)
    np.testing.assert_allclose(w, chosen / (chosen.sum(1, keepdims=True) + 0.5), rtol=1e-5)
    _, plain = moe.route(x, router, 4, scoring="sigmoid")
    np.testing.assert_allclose(plain, chosen / chosen.sum(1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("turned,reads", [(False, 0.0), (True, 2.0)])
def test_bias_err_reads_zero_and_the_rule_turned_round_reads_two(cfg, params, turned, reads, monkeypatch):
    """``bias_err`` as the benchmark's kind reads it: the bias's change in
    one step against ``b + rate * sign(mean - count)``, in units of the
    rate; what the optimizer did to the leaf (weight decay) does not reach it."""
    rng = np.random.default_rng(10)
    counts = rng.integers(0, 97, size=(2, 4, 8)).astype(np.int32)
    counts[0, 0] = [48, 48, 40, 56, 48, 0, 96, 48]  # mean 48: four ties
    if turned:
        real = jnp.sign
        monkeypatch.setattr(jnp, "sign", lambda a: -real(a))
    decayed = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    after = bb.step_routers(cfg, params, decayed, {"router_tokens": counts})
    before = np.asarray(params["periods"]["ffn"]["router_bias"])
    moved = np.asarray(after["periods"]["ffn"]["router_bias"]) - before
    want = ref.bias_step(before, counts, cfg.router_bias_rate) - before
    assert np.abs(moved - want).max() / cfg.router_bias_rate == pytest.approx(reads, abs=1e-3)
    np.testing.assert_array_equal(after["embed"], decayed["embed"])


def test_a_held_router_stays_where_it_was(cfg, params):
    held = dataclasses.replace(cfg, router_trains=False)
    decayed = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    after = bb.step_routers(held, params, decayed, {"router_tokens": np.ones((2, 4, 8), np.int32)})
    np.testing.assert_array_equal(after["periods"]["ffn"]["router"], params["periods"]["ffn"]["router"])
    np.testing.assert_array_equal(
        after["periods"]["ffn"]["experts"]["wg"], decayed["periods"]["ffn"]["experts"]["wg"])


# -- what the accepted configurations still are ------------------------------
@pytest.mark.parametrize("name,kinds,period,stacked", [
    ("qwen3next-tiny", ("linear",) * 3 + ("full",), 4, {"full": (2,), "linear": (2, 3)}),
    ("joyai-flash-tiny", ("full",), 1, {"full": (2,)}),
    ("lfm2-tiny", ("full", "conv", "conv", "conv"), 4, {"full": (2,), "conv": (2, 3)}),
])
def test_a_period_is_the_shortest_run_the_layers_repeat(name, kinds, period, stacked):
    cfg = bb.BackboneConfig.load(name)
    assert (cfg.period_kinds, cfg.period) == (kinds, period)
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, VOCAB, L, 0))
    for kind, lead in stacked.items():
        leaf = jax.tree_util.tree_leaves(shapes["periods"][kind])[0]
        assert leaf.shape[:len(lead)] == lead, kind
    assert set(stacked) == set(shapes["periods"]) - {"norm_in", "norm_post", "ffn"}


def test_a_pattern_that_does_not_repeat_is_one_period():
    """The published 40 layers: after the two dense ones the pattern ends on
    half a period, so the whole rest is scanned as one."""
    types = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
             + ["full_attention", "conv"])
    cfg = bb.BackboneConfig.from_dict(
        {**TINY, "num_hidden_layers": 40, "num_dense_layers": 2, "layer_types": types})
    assert (cfg.period, cfg.n_periods) == (38, 1)
    assert cfg.mixers() == {"gqa": 10, "shortconv": 30}


@pytest.mark.parametrize("bad", [
    {"conv_bias": True}, {"layer_types": ["conv", "full_attention"]},
    {"layer_types": ["conv"] * 8 + ["window_attention"]},
    {"num_dense_layers": 2}])
def test_configurations_the_backbone_cannot_run_are_refused(bad):
    with pytest.raises(ValueError):
        bb.BackboneConfig.from_dict({**TINY, **bad})


# -- the normal path --------------------------------------------------------
def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming this configuration
    trains and answers through the same workflow as every template; the
    job's counters say which mixers ran and carry the routers' loads and bias."""
    import datetime as dt

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.sequencerec import (
        Query, SeqDataSourceParams, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, engine_factory)
    from predictionio_tpu.obs.trace import default_tracer
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
        backbone="lfm2-tiny", steps=30, batch_size=2, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=11)),
        preparator_params=("", SeqPreparatorParams(seq_len=32)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.config.layer_types == tuple(TINY["layer_types"])
    assert model.losses[-1] < model.losses[0]
    stats = model.stats
    assert stats["mixers"] == {"gqa": 2, "shortconv": 7} and "delta_rule_walk" not in stats
    assert stats["conv"] == "xla"  # the CPU: the XLA form of the chain
    assert stats["router_tokens_by_step"].shape == (30, 2, 4, 8)
    bias = np.zeros((2, 4, 8), np.float32)
    for counts in stats["router_tokens_by_step"]:
        bias = ref.bias_step(bias, counts, model.config.router_bias_rate)
    np.testing.assert_allclose(model.params["periods"]["ffn"]["router_bias"], bias, atol=1e-6)
    roots = [s for s in default_tracer().store.dump() if s["name"] == "train" and s["parentId"] is None]
    assert roots[-1]["tags"]["mixers"] == "gqa:2 shortconv:7" and roots[-1]["tags"]["conv"] == "xla"
    answer = SeqRecAlgorithm(algo_params).predict(model, Query(recent_items=("i0", "i1", "i2"), num=3))
    scores = [s.score for s in answer.item_scores]
    assert len(scores) == 3 and scores == sorted(scores, reverse=True)
    get_registry(refresh=True)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "lfm2_moe_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_lfm2.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "lfm2_moe_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "lfm2-24b-a2b-ep8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "seqrec-lfm2-24b-a2b-ep8.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    published = {
        "hidden_size": 2048, "intermediate_size": 11776, "moe_intermediate_size": 1536,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 64, "conv_L_cache": 3,
        "conv_bias": False, "num_experts_per_tok": 4, "norm_eps": 1e-5, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "use_expert_bias": True, "max_position_embeddings": 128000,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "model_type": "lfm2_moe", "router_width": 64}
    for key, value in published.items():
        assert conf[key] == value, key
    assert {k: v for k, v in bench["published"].items() if k != "layer_types"} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64, "vocab_size": 65536}
    assert bench["reduced"] == list(bench["published"])
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.partial_rotary_factor, cfg.attn_kernel) == (True, False, 1.0, "xla")
    assert (cfg.scoring_func, cfg.router_bias, cfg.router_trains, cfg.norm_topk_eps) == (
        "sigmoid", True, False, 1e-6)
    assert (cfg.n_periods, cfg.period_kinds, cfg.experts_held) == (1, ("full", "conv", "conv", "conv"), (0, 8))
    assert cfg.shared_expert_intermediate_size == 0 and cfg.tie_word_embeddings
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 8192, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(shapes["dense"]["conv"]) == 16_783_360
    assert count(shapes["dense"]) == 89_139_200
    assert count(shapes["periods"]["full"]) == 10_485_888
    assert count(shapes["periods"]["ffn"]) == 4 * (131_072 + 64 + 8 * 9_437_184)
    assert count(shapes["periods"]) == 363_366_784
    assert "shared" not in shapes["periods"]["ffn"] and "head" not in shapes
    assert count(shapes) == 469_285_248
