"""The sequence backbone whose every layer is ONE part (a Mamba-2 mixer with
groups of B and C, grouped-query attention without positions, or ungated
ReLU^2 experts beside a shared one behind a sigmoid router with a bias),
against its plain reference, at a small size on the CPU: hidden 64, the nine
layers ``MEMEM*EME``, 8 state-space heads of 16 on a state of 16 in 2 groups,
8 query heads on 2 key/value heads of 16, 3 of 8 routed experts held (top 3,
scale 2.5) of width 48 and a shared one of 96, chunks of 16 slots, rows of 64
slots.

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
from predictionio_tpu.models import sequencerec
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.ssd import mamba2
from predictionio_tpu.testing import nemotronh_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "nemotron3-nano-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L = 50, 64
HISTORIES = {1: (0, 20), 2: (20, 57), 3: (57, 62)}


def rel(got, want):
    got = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def seeded(cfg, seed=0):
    """Seeded weights with every norm scale, convolution bias, skip and
    router bias moved off its starting value, so that none of them drops out
    of a comparison."""
    drawn = bb.init_params(cfg, VOCAB, L, seed)
    leaves, treedef = jax.tree_util.tree_flatten(drawn)
    rng = np.random.default_rng(1)
    leaves = [leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32)
              if leaf.ndim <= 3 else leaf for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def cfg():
    return bb.BackboneConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


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
            "hidden": hidden, "counters": counters, "ran": ran,
            "want": dict(zip(("loss", "grads", "logits"), want))}


def test_loss_logits_and_hidden_states_match_reference(both, cfg, params, batch):
    want = both["want"]
    # float32 sums in another order over 128 targets
    assert abs(both["loss"] - want["loss"]) / want["loss"] < 1e-6
    for got, expected in zip(both["logits"], want["logits"]):
        assert rel(got, expected) < 1e-4
    layers = bb.layers_of(params, cfg)
    for b, (row, seg) in enumerate(zip(*batch)):
        assert rel(both["hidden"][b], ref.hidden_states(layers, row[:-1], seg[:-1], TINY)) < 1e-5


GROUPS = {
    "ssm": lambda layer: layer.get("ssm"),
    "attention": lambda layer: layer.get("full"),
    "router": lambda layer: layer["moe"]["router"] if "moe" in layer else None,
    "experts": lambda layer: layer["moe"]["experts"] if "moe" in layer else None,
    "shared": lambda layer: layer["moe"]["shared"] if "moe" in layer else None,
    "norms": lambda layer: layer["norm"],
}


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "head", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    """Leaf by leaf, none of them zero: 5e-4 is a hundred times what
    float32 in another order reads here and a thousandth of what a state let
    across a boundary does (test below)."""
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


def test_no_gradient_reaches_the_routers_bias(both):
    for layer in both["grads"]["layers"]:
        if "moe" in layer:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))


@pytest.mark.parametrize("router_trains", [True, False])
def test_one_optimizer_step_is_plain_adamw_and_the_bias_rule(cfg, params, batch, both, router_trains):
    """The job's own donated step from fresh moments against numpy AdamW on
    the REFERENCE's gradient, and every router's bias stepped by the rule from
    the step's own counts; a router the configuration holds has not moved."""
    held = dataclasses.replace(cfg, router_trains=router_trains)
    opt_init, step, _ = sequencerec._programs(held, 1e-2, None, "auto")
    copy = jax.tree_util.tree_map(jnp.array, params)
    new, _, loss, counters = step(copy, opt_init(copy), *batch)
    assert abs(float(loss) - both["loss"]) < 1e-6
    assert counters["router_tokens"].shape == (1, 4, 8) and int(counters["dropped"].sum()) == 0
    change = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), new, params)
    bias = np.asarray(params["periods"]["moe"]["router_bias"])
    want_bias = ref.bias_step(bias, counters["router_tokens"], cfg.router_bias_rate) - bias
    np.testing.assert_allclose(change["periods"]["moe"]["router_bias"], want_bias, atol=1e-7)
    assert np.any(want_bias)
    moved = np.abs(change["periods"]["moe"]["router"]).max()
    assert (moved > 0) == router_trains
    # the optimizer's leaves: all but the bias (and the routers it leaves alone)
    want = ref.adamw_first_step(
        bb.layers_of(params, cfg), both["want"]["grads"], 1e-2, 0.9, 0.999, 1e-8, 1e-4)
    got = bb.layers_of(change, cfg)
    for tree in (got, want):
        for layer in tree["layers"]:
            if "moe" in layer:
                layer["moe"] = {k: v for k, v in layer["moe"].items()
                                if k != "router_bias" and (router_trains or k != "router")}
    assert rel(got, want) < 1e-3


def test_the_layout_comes_from_the_pattern(cfg, both, params):
    layers = both["grads"]["layers"]
    assert [next(k for k in ("ssm", "moe", "full") if k in layer) for layer in layers] == [
        {"M": "ssm", "E": "moe", "*": "full"}[letter] for letter in TINY["hybrid_override_pattern"]]
    assert all(set(layer) - {"ssm", "moe", "full"} == {"norm"} for layer in layers)  # one norm, one part
    assert (cfg.period_kinds, cfg.n_periods, cfg.first_k_dense_replace, cfg.ffn) == (
        ("ssm", "moe", "ssm", "moe", "ssm", "full", "moe", "ssm", "moe"), 1, 0, "none")
    assert (cfg.stacked("ssm"), cfg.stacked("moe"), cfg.stacked("full")) == (4, 4, 0)
    assert cfg.mixers() == {"gqa": 1, "mamba2": 4, "moe": 4}
    assert (cfg.positions, cfg.chunk, cfg.mamba_n_groups, cfg.expert_act) == ("none", 16, 2, "relu2")
    assert (cfg.rms_norm_eps, cfg.shared_expert_intermediate_size, cfg.head_dim) == (1e-5, 96, 16)
    periods = params["periods"]
    assert set(periods) == {"norm_in", "ssm", "moe", "full"} and "pos" not in params
    assert periods["norm_in"]["w"].shape == (1, 9, 64) and params["head"].shape == (VOCAB, 64)
    assert periods["ssm"]["w_in"].shape == (1, 4, 64, 2 * 128 + 2 * 2 * 16)
    assert periods["ssm"]["conv_w"].shape == (1, 4, 4, 128 + 2 * 2 * 16)
    assert periods["full"]["w_q"].shape == (1, 64, 128) and periods["full"]["w_k"].shape == (1, 64, 32)
    assert set(periods["moe"]["experts"]) == set(periods["moe"]["shared"]) == {"wu", "wd"}  # no gate
    assert periods["moe"]["experts"]["wu"].shape == (1, 4, 3, 64, 48)
    assert periods["moe"]["shared"]["wd"].shape == (1, 4, 96, 64)
    assert "shared_gate" not in periods["moe"]
    assert set(both["counters"]) == set(bb._EXPERT_COUNTS)
    assert both["counters"]["expert_tokens"].shape == (1, 4, 3)
    assert bb.mechanisms(cfg, L) == {
        "ssd_scan": "xla", "ssd_groups": 2, "expert_act": "relu2", "conv": "xla"}


def test_the_scan_and_the_expert_layer_are_the_references_on_what_they_were_handed(
        both, params, batch, cfg):
    """The aux carries u, B, C, Delta and y of the first Mamba-2 layer, and
    the first expert layer's normed input and output: y is the reference's
    slot-by-slot recurrence on the other four with every head on its group's
    B and C, and the output the reference's dense loop over the held experts
    plus the shared one."""
    ran, segs = both["ran"], batch[1]
    assert ran["u"].shape == ran["y"].shape == (1, 2, L, 8, 16)
    assert ran["B"].shape == ran["C"].shape == (1, 2, L, 2 * 16) and ran["dt"].shape == (1, 2, L, 8)
    assert ran["moe_in"].shape == ran["moe_out"].shape == (1, 2, L, 64)
    a_log = params["periods"]["ssm"]["A_log"][0, 0]
    first_moe = jax.tree_util.tree_map(lambda a: a[0, 0], params["periods"]["moe"])
    for b in range(2):
        want = ref.ssd_of(*(ran[name][0, b] for name in ("u", "B", "C", "dt")), a_log,
                          segs[b, :-1], groups=2)
        assert rel(ran["y"][0, b], want) < 1e-5
        assert rel(ran["moe_out"][0, b], ref.moe_of(first_moe, ran["moe_in"][0, b], TINY)) < 1e-5
    # a scan that read ONE group's B and C for all heads is another result
    wrong = ref.ssd_of(ran["u"][0, 0], *(np.tile(ran[n][0, 0][:, :16], (1, 2)) for n in ("B", "C")),
                       ran["dt"][0, 0], a_log, segs[0, :-1], groups=2)
    assert rel(ran["y"][0, 0], wrong) > 0.3


def _runs(*lengths):
    return np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])


def test_the_mixer_is_the_references_with_a_gated_norm_a_group(cfg):
    """``mamba2`` with three groups of two heads against the reference's
    mixer, on histories shorter than the taps; a norm over the whole inner
    width (one group's rule) is another result."""
    rng = np.random.default_rng(5)
    d, heads, width, state, groups = 12, 6, 4, 5, 3
    inner, wide = heads * width, 3 * 5
    w = lambda *shape: (0.4 * rng.normal(size=shape)).astype(np.float32)  # noqa: E731
    p = {"w_in": w(d, 2 * inner + 2 * wide), "w_dt": w(d, heads),
         "conv_w": w(4, inner + 2 * wide), "conv_b": w(inner + 2 * wide),
         "A_log": np.log(rng.uniform(1, 16, heads)).astype(np.float32), "dt_bias": w(heads),
         "D": 1 + w(heads), "norm": 1 + w(inner), "w_out": w(inner, d)}
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    seg = np.stack([_runs(1, 2, 3, 2, 1, 15), _runs(3, 21)]).astype(np.int32)
    widths = dict(heads=heads, head_dim=width, state=state, eps=1e-5, chunk=8)
    got, ran = mamba2(p, x, seg, groups=groups, **widths)
    conf = {"mamba_num_heads": heads, "mamba_head_dim": width, "ssm_state_size": state,
            "n_groups": groups, "layer_norm_epsilon": 1e-5}
    with jax.default_matmul_precision("highest"):
        want = [ref.ssm_mixer(p, jnp.asarray(x[b]), jnp.asarray(seg[b]), conf) for b in range(2)]
    assert rel(got, np.stack(want)) < 1e-5 and ran["B"].shape == (2, 24, wide)
    one_norm = {**p, "w_in": p["w_in"][:, :2 * inner + 2 * state],
                "conv_w": p["conv_w"][:, :inner + 2 * state], "conv_b": p["conv_b"][:inner + 2 * state]}
    assert rel(mamba2(one_norm, x, seg, **widths)[0], np.stack(want)) > 0.1


# -- the ungated experts' passes ------------------------------------------------
def _expert_layer_inputs(rng, tokens=96, d=16, f=24, experts=8, held=(2, 4)):
    w = lambda *shape: (0.5 * rng.normal(size=shape)).astype(np.float32)  # noqa: E731
    p = {"router": w(d, experts), "router_bias": 0.3 * w(experts),
         "experts": {"wu": w(held[1], d, f), "wd": w(held[1], f, d)},
         "shared": {"wu": w(d, 2 * f), "wd": w(2 * f, d)}}
    conf = {"num_experts_per_tok": 3, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
            "experts_held": list(held)}
    return p, w(tokens, d), conf


@pytest.mark.parametrize("pass_rows,overflows", [(0, False), (40, True), (16, True)])
def test_the_ungated_passes_are_the_dense_loop_in_line_and_behind_the_cond(pass_rows, overflows):
    """``expert_layer`` with ``act`` ``relu2`` against the reference's dense
    loop, value and every gradient: in one pass in line, and with rows so few
    that the held assignments overflow into the passes behind the ``cond``
    (which recompute a pass where they pull it back); nothing is dropped."""
    p, x, conf = _expert_layer_inputs(np.random.default_rng(4))
    widths = dict(first=2, top_k=3, scoring="sigmoid", scale=2.5, act="relu2", pass_rows=pass_rows)

    def program(p, x):
        y, counters = moe.expert_layer(p, x, **widths)
        return jnp.sum(y * jnp.cos(y)), (y, counters)

    def reference(p, x):
        y = ref.moe_block(p, x, conf)
        return jnp.sum(y * jnp.cos(y)), y

    (_, (got, counters)), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(p, x)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(p, x)
    assert int(counters["dropped"]) == 0 and (int(counters["passes"]) > 1) == overflows
    assert int(counters["expert_tokens"].sum()) == int(
        np.asarray(ref.router_counts(p, x, conf))[2:6].sum())
    assert rel(got, want) < 1e-5
    want_grads[0]["router_bias"] = grads[0]["router_bias"]  # the reference's scatter gives it none either
    assert not np.any(np.asarray(grads[0]["router_bias"]))
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        assert rel(a, b) < 1e-4 if np.any(np.asarray(b)) else not np.any(np.asarray(a))


def test_the_square_is_taken_in_float32_before_the_cast():
    """``relu(up)^2`` of bfloat16 products: squared as float32, cast once."""
    rng = np.random.default_rng(6)
    w = {"wu": rng.normal(size=(8, 16)).astype(np.float32), "wd": np.eye(16, dtype=np.float32)}
    x = rng.normal(size=(4, 8)).astype(np.float32)
    up = jnp.dot(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w["wu"], jnp.bfloat16),
                 preferred_element_type=jnp.float32)
    want = jnp.square(jax.nn.relu(up)).astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(moe.relu2(w, x, jnp.bfloat16), want)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """The share test: the routed parts that the 4 shares of 2 experts each
    give, plus the shared expert counted ONCE, are the uncut reference's
    expert layer (all 8 experts held)."""
    p, x, conf = _expert_layer_inputs(np.random.default_rng(8), held=(0, 8))
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_block(p, x, conf)
        shared = ref._relu2(p["shared"], x)
    total, tokens = np.zeros_like(whole), 0
    for first in range(0, 8, 2):
        share = {**p, "experts": jax.tree_util.tree_map(lambda a: a[first:first + 2], p["experts"])}
        y, counters = moe.expert_layer(share, x, first=first, top_k=3, scoring="sigmoid",
                                       scale=2.5, act="relu2")
        total += np.asarray(y) - np.asarray(shared)  # what every share computes alike: once
        tokens += int(counters["expert_tokens"].sum())
        assert int(counters["dropped"]) == 0
    assert tokens == 3 * x.shape[0]  # every assignment lies in exactly one share
    assert rel(total + np.asarray(shared), whole) < 1e-5


# -- packing ----------------------------------------------------------------
@pytest.fixture(scope="module")
def hidden_of(cfg, params):
    return jax.jit(lambda tokens, seg: bb.hidden_states(cfg, params, tokens, seg)[0])


def test_a_packed_row_is_its_histories_one_by_one(hidden_of, batch):
    """Neither the convolution, the grouped state nor attention crosses a
    boundary; the expert layer is a function of one slot."""
    rows, segs = batch
    packed = hidden_of(rows[:1, :-1], segs[:1, :-1])[0]
    for sid, (lo, hi) in HISTORIES.items():
        alone = np.zeros((1, L), np.int32)
        alone[0, :hi - lo] = rows[0, lo:hi]
        seg = np.zeros((1, L), np.int32)
        seg[0, :hi - lo] = 1
        assert rel(packed[lo:hi], hidden_of(alone, seg)[0, :hi - lo]) < 1e-5


def test_a_neighbour_let_in_changes_the_row(hidden_of, batch):
    rows, segs = batch
    merged = np.where(segs[:1, :-1] > 0, 1, 0).astype(np.int32)
    lo, hi = HISTORIES[2]
    assert rel(hidden_of(rows[:1, :-1], merged)[0, lo:hi],
               hidden_of(rows[:1, :-1], segs[:1, :-1])[0, lo:hi]) > 0.05


# -- what the configuration refuses -------------------------------------------
@pytest.mark.parametrize("bad,says", [
    ({"hybrid_override_pattern": "MEMEM-EME"}, "'-', a dense MLP as a layer of its own"),
    ({"hybrid_override_pattern": "MEMEMXEME"}, "unknown"),
    ({"hybrid_override_pattern": "MEMEM*EM"}, "names 8 layers for 9"),
    ({"mlp_bias": True}, "experts' projections carry no bias"),
    ({"use_bias": True}, "bias"), ({"mamba_proj_bias": True}, "bias"),
    ({"use_conv_bias": False}, "bias"), ({"attention_bias": True}, "bias"),
    ({"n_group": 2}, "no limit by groups of experts"), ({"topk_group": 2}, "groups of experts"),
    ({"n_groups": 3}, "mamba_n_groups is 3"), ({"n_groups": None}, "mamba_n_groups is not given"),
    ({"mamba_num_heads": None}, "mamba_n_heads"), ({"ssm_state_size": None}, "mamba_d_state"),
    ({"router_width": None}, "router_width"), ({"experts_held": None}, "experts_held"),
    ({"backbone": {**TINY["backbone"], "ffn": "swiglu"}}, "a layer of its own only where"),
    ({"backbone": {**TINY["backbone"], "num_nextn_predict_layers": 1}}, "one part"),
])
def test_configurations_the_backbone_cannot_run_are_refused_with_a_message(bad, says):
    conf = {k: v for k, v in {**TINY, **bad}.items() if v is not None}
    with pytest.raises(ValueError, match=says):
        bb.BackboneConfig.from_dict(conf)


def test_expand_is_not_read_and_an_unknown_activation_is_refused():
    assert bb.BackboneConfig.from_dict({**TINY, "expand": 7}) == bb.BackboneConfig.from_dict(TINY)
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.expert_layer({"router": np.zeros((4, 2), np.float32),
                          "experts": {"wu": np.zeros((1, 4, 4), np.float32)}},
                         np.zeros((2, 4), np.float32), first=0, top_k=1, act="gelu")


# -- the normal path --------------------------------------------------------
def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming this configuration
    trains and answers through the same workflow as every template; the
    job's counters say which parts ran, in which form, and what the experts saw."""
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
    store.init(12)
    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(8):
        for t in range(5 + 3 * u):
            store.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}", target_entity_type="item",
                target_entity_id=f"i{(u + t) % 7}", event_time=t0 + dt.timedelta(minutes=t)), 12)
    algo_params = SeqRecAlgorithmParams(
        backbone="nemotron3-nano-tiny", steps=20, batch_size=1, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=12)),
        preparator_params=("", SeqPreparatorParams(seq_len=32)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.config.layer_types == tuple(TINY["hybrid_override_pattern"])
    assert model.losses[-1] < model.losses[0]
    stats = model.stats
    assert stats["mixers"] == {"gqa": 1, "mamba2": 4, "moe": 4}
    assert (stats["ssd_scan"], stats["ssd_groups"], stats["expert_act"], stats["conv"]) == (
        "xla", 2, "relu2", "xla")
    assert stats["expert_tokens_by_step"].shape == (20, 1, 4, 3)
    assert stats["router_tokens_by_step"].shape == (20, 1, 4, 8)
    assert int(np.sum(stats["dropped"])) == 0 and stats["layer_steps"] == 80
    assert 0 < stats["router_bias_abs_max"] <= 20 * 0.001 + 1e-6
    roots = [s for s in default_tracer().store.dump() if s["name"] == "train" and s["parentId"] is None]
    tags = roots[-1]["tags"]
    assert tags["mixers"] == "gqa:1 mamba2:4 moe:4" and tags["ssd_scan"] == "xla"
    assert (tags["ssd_groups"], tags["expert_act"]) == (2, "relu2") and "passes_by_step" in tags
    answer = SeqRecAlgorithm(algo_params).predict(model, Query(recent_items=("i0", "i1", "i2"), num=3))
    scores = [s.score for s in answer.item_scores]
    assert len(scores) == 3 and scores == sorted(scores, reverse=True)
    get_registry(refresh=True)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "nemotronh_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_nemotronh.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "nemotronh_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "nemotron3-nano-30b-a3b-ep16.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "seqrec-nemotron3-nano-30b-a3b-ep16.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    published = {
        "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 2,
        "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
        "conv_kernel": 4, "chunk_size": 128, "expand": 2, "intermediate_size": 1856,
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
        "n_shared_experts": 1, "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "layer_norm_epsilon": 1e-5,
        "norm_eps": 1e-5, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "attention_bias": False, "mlp_bias": False, "use_bias": False, "mamba_proj_bias": False,
        "use_conv_bias": True, "tie_word_embeddings": False, "model_type": "nemotron_h",
        "rope_theta": 10000, "partial_rotary_factor": 1, "max_position_embeddings": 262144,
        "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001}
    for key, value in published.items():
        assert conf[key] == value, key
    assert bench["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert bench["published"]["hybrid_override_pattern"][:9] == conf["hybrid_override_pattern"]
    assert bench["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
                                "vocab_size"]
    assert (conf["router_width"], conf["experts_held"], conf["n_routed_experts"]) == (128, [0, 8], 8)
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.positions, cfg.ffn, cfg.norm, cfg.attention, cfg.attn_kernel, cfg.expert_act) == (
        "none", "none", "rms", "gqa", "xla", "relu2")
    assert (cfg.n_periods, "".join({"ssm": "M", "moe": "E", "full": "*"}[k] for k in cfg.period_kinds),
            cfg.head_dim, cfg.mamba_n_groups) == (1, "MEMEM*EME", 128, 8)
    assert (cfg.scoring_func, cfg.router_bias, cfg.router_trains, cfg.shared_expert_gate,
            cfg.tie_word_embeddings) == ("sigmoid", True, False, False, False)
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 8192, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    periods = shapes["periods"]
    assert periods["ssm"]["w_in"].shape == (1, 4, 2688, 2 * 4096 + 2 * 8 * 128)
    assert periods["ssm"]["conv_w"].shape == (1, 4, 4, 6144)
    assert count(periods["ssm"]) + 4 * 2688 == 4 * 38_744_896  # with each layer's own norm
    assert count(periods["full"]) + 2688 == 23_399_040
    assert count(periods["moe"]) + 4 * 2688 == 4 * (100_125_312 + 128)  # and the routers' bias
    assert count(shapes["embed"]) + count(shapes["head"]) == 88_080_384
    assert set(periods) == {"norm_in", "ssm", "moe", "full"} and "pos" not in shapes
    assert count(shapes) == 666_962_944 + 4 * 128
