"""The sequence backbone with Mamba-2 state-space layers on both sides of
grouped-query attention without positions, a dense SwiGLU in every layer
and Granite's four multipliers, against its plain reference, at a small
size on the CPU: hidden 64, 8 state-space heads of 16 on a state of 16, 4
query heads on 2 key/value heads of 16, two periods of four layers (two
Mamba-2 layers, the attention layer, one more), chunks of 16 slots, rows of
64 slots.

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
from predictionio_tpu.ops.ssd import mamba2, ssd_scan
from predictionio_tpu.testing import granite4h_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "granite4h-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L = 50, 64
HISTORIES = {1: (0, 20), 2: (20, 57), 3: (57, 62)}


def rel(got, want):
    got = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def seeded(cfg, seed=0):
    """Seeded weights with every norm scale, convolution bias, skip and gate
    parameter moved off its starting value, so that none of them drops out
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
    return {"loss": float(loss), "grads": bb.layers_of(grads, cfg), "logits": logits, "counters": counters, "ran": ran,
            "want": dict(zip(("loss", "grads", "logits"), want))}


def test_loss_and_logits_match_reference(both):
    want = both["want"]
    # float32 sums in another order over 128 targets
    assert abs(both["loss"] - want["loss"]) / want["loss"] < 1e-6
    for got, expected in zip(both["logits"], want["logits"]):
        assert rel(got, expected) < 1e-4


GROUPS = {
    "ssm": lambda layer: layer.get("ssm"),
    "attention": lambda layer: layer.get("full"),
    "mlp": lambda layer: layer["mlp"],
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    """Leaf by leaf, none of them zero: 5e-4 is a hundred times what
    float32 in another order reads here (2e-6) and a thousandth of what a
    state let across a boundary does (test below)."""
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


def test_one_optimizer_step_is_plain_adamw_on_the_references_gradient(cfg, params, batch, both):
    """The job's own donated step from fresh moments against numpy AdamW on
    the REFERENCE's gradient: Adam's first step is lr * sign(g) nearly
    everywhere, so the two agree to float32 wherever the gradients do."""
    opt_init, step, _ = sequencerec._programs(cfg, 1e-2, None, "auto")
    copy = jax.tree_util.tree_map(jnp.array, params)
    new, _, loss, counters = step(copy, opt_init(copy), *batch)
    assert counters == {} and abs(float(loss) - both["loss"]) < 1e-6
    change = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), new, params)
    want = ref.adamw_first_step(
        bb.layers_of(params, cfg), both["want"]["grads"], 1e-2, 0.9, 0.999, 1e-8, 1e-4)
    assert rel(bb.layers_of(change, cfg), want) < 1e-3


def test_the_layout_comes_from_layer_types(cfg, both, params):
    layers = both["grads"]["layers"]
    assert ["ssm" in layer for layer in layers] == [t == "mamba" for t in TINY["layer_types"]]
    assert all("mlp" in layer for layer in layers)
    assert (cfg.period_kinds, cfg.n_periods, cfg.first_k_dense_replace) == (
        ("ssm", "ssm", "full", "ssm"), 2, 0)
    assert cfg.mixers() == {"gqa": 2, "mamba2": 6}
    assert (cfg.positions, cfg.chunk, cfg.intermediate_size) == ("none", 16, 96)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (0.0625, 12, 0.22, 8)
    assert "pos" not in params and "head" not in params and "dense" not in params
    assert params["periods"]["ssm"]["w_in"].shape == (2, 3, 64, 2 * 128 + 2 * 16)
    assert params["periods"]["full"]["w_q"].shape == (2, 64, 64)
    assert both["counters"] == {}  # no router anywhere: nothing is counted


def test_the_scan_is_the_recurrence_on_what_it_was_handed(both, params, batch):
    """The aux carries u, B, C, Delta and y of every period's first Mamba-2
    layer: y is the reference's slot-by-slot recurrence on the other four."""
    ran, segs = both["ran"], batch[1]
    assert ran["u"].shape == (2, 2, L, 8, 16) and ran["y"].shape == (2, 2, L, 8, 16)
    assert ran["B"].shape == ran["C"].shape == (2, 2, L, 16) and ran["dt"].shape == (2, 2, L, 8)
    for period in range(2):
        a_log = params["periods"]["ssm"]["A_log"][period, 0]
        for b in range(2):
            want = ref.ssd_of(*(ran[name][period, b] for name in ("u", "B", "C", "dt")),
                              a_log, segs[b, :-1])
            assert rel(ran["y"][period, b], want) < 1e-5


# -- the chunked scan against the recurrence, boundary by boundary -----------
def _scan_inputs(rng, seg, heads=4, width=8, state=8):
    length = len(seg)
    u = rng.normal(size=(1, length, heads, width)).astype(np.float32)
    b, c = (rng.normal(size=(1, length, state)).astype(np.float32) for _ in range(2))
    # decays from nearly none to a state forgotten within a few slots
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=(1, length, heads))).astype(np.float32)
    a_log = np.log(rng.uniform(1.0, 16.0, size=heads)).astype(np.float32)
    return u, dt, a_log, b, c, np.asarray(seg, np.int32)[None]


def _runs(*lengths):
    """Segment ids of histories of these lengths, one after another."""
    return np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])


BOUNDARIES = {
    "one history": _runs(64),
    "on a chunk's first slot": _runs(16, 32, 16),
    "on a chunk's last slot": _runs(15, 33, 16),
    "mid-chunk": _runs(7, 30, 27),
    "three in one chunk": _runs(18, 3, 4, 5, 34),
    "histories shorter than the four taps": _runs(1, 2, 3, 1, 1, 2, 22, 3, 29),
    "every slot its own history": _runs(*[1] * 64),
    "a row that is no whole number of chunks": _runs(9, 20, 11),
    "padding (id 0) behind the histories": np.concatenate([_runs(20, 30), np.zeros(14, int)]),
}


def _weighted(scan):
    """``scan`` -> its value and every gradient of a weighted sum of it, jitted
    once for all cases of one length."""
    def total(u, dt, a_log, b, c, segs, weight):
        y = scan(u, dt, a_log, b, c, segs)
        return jnp.sum(y * weight), y

    return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4), has_aux=True))


_PROGRAM = _weighted(lambda u, dt, a_log, b, c, segs: ssd_scan(
    u, dt, -jnp.exp(a_log), b, c, segs, chunk=16))
_RECURRENCE = _weighted(lambda u, dt, a_log, b, c, segs: ref.ssd_recurrence(
    u[0], b[0], c[0], dt[0], a_log, segs[0], block=8)[None])


@pytest.mark.parametrize("case", sorted(BOUNDARIES))
def test_the_chunked_scan_is_the_recurrence(case):
    """Value and every gradient; chunks of 16. float32 in another order:
    1e-5 is ten times the largest reading of the values; a gradient is a sum
    over every slot of terms of both signs (A_log's: four numbers in all)
    and reads up to 3e-5, so 1e-4."""
    seg = BOUNDARIES[case]
    inputs = _scan_inputs(np.random.default_rng(len(seg) + len(case)), seg)
    weight = np.random.default_rng(9).normal(size=inputs[0].shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = _RECURRENCE(*inputs, weight)
    (_, got), grads = _PROGRAM(*inputs, weight)
    assert rel(got, want) < 1e-5
    for name, a, w in zip(("u", "dt", "A_log", "B", "C"), grads, want_grads):
        assert np.isfinite(np.asarray(a)).all() and rel(a, w) < 1e-4, name


@pytest.mark.parametrize("chunk", [4, 16, 64, 256])
def test_the_chunk_is_no_part_of_the_result(chunk):
    u, dt, a_log, b, c, segs = _scan_inputs(np.random.default_rng(11), BOUNDARIES["mid-chunk"])
    want = ref.ssd_of(u[0], b[0], c[0], dt[0], a_log, segs[0])
    assert rel(ssd_scan(u, dt, -jnp.exp(a_log), b, c, segs, chunk=chunk)[0], want) < 1e-5


def test_a_fast_decay_overflows_nothing():
    """Every decay is the exponential of a difference: a head that forgets
    within a slot (dt A = -400 a slot) gives finite values and gradients."""
    u, dt, a_log, b, c, segs = _scan_inputs(np.random.default_rng(12), _runs(40, 24))
    dt = np.full_like(dt, 25.0)

    def total(u, dt):
        return jnp.sum(ssd_scan(u, dt, -jnp.exp(a_log), b, c, segs, chunk=16) ** 2)

    value, grads = jax.value_and_grad(total, argnums=(0, 1))(u, dt)
    assert np.isfinite(float(value)) and all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert rel(ssd_scan(u, dt, -jnp.exp(a_log), b, c, segs, chunk=16)[0],
               ref.ssd_of(u[0], b[0], c[0], dt[0], a_log, segs[0])) < 1e-5


@pytest.mark.parametrize("build,passes", [("float32", True), ("bfloat16", False)])
def test_the_control_build_fails_ssd_err_and_the_sound_build_passes(build, passes):
    """The benchmark's control: state, Delta and the decay's running sums in
    bfloat16 on the same bfloat16 u, B, C is another result, ten times
    further from the recurrence than the sound build (on the CPU at the
    published widths: 0.0018 against 0.02 to 0.08)."""
    rng = np.random.default_rng(3)
    u, dt, a_log, b, c, segs = _scan_inputs(rng, _runs(50, 14, 64), heads=8, width=16, state=32)
    low = [jnp.asarray(t, jnp.bfloat16) for t in (u, b, c)]
    got = ssd_scan(low[0], dt, -jnp.exp(a_log), low[1], low[2], segs, chunk=32,
                   compute_dtype=jnp.bfloat16, state_dtype=jnp.dtype(build),
                   gate_dtype=jnp.dtype(build))
    err = rel(got[0], ref.ssd_of(low[0][0], low[1][0], low[2][0], dt[0], a_log, segs[0]))
    assert (err < 6e-3) == passes and (passes or err > 1.2e-2), err


@pytest.mark.parametrize("build,form", [("float32", "pallas"), ("bfloat16", "xla")])
def test_on_a_tpu_the_control_builds_call_is_the_xla_form_and_the_sound_builds_the_kernel(
        monkeypatch, build, form):
    """The same call at the cell's widths with the backend answered as a
    TPU: what the control build hands over (bfloat16 state and gates) takes
    XLA's batch products, so it goes on failing ``ssd_err`` whatever the
    kernel does."""
    from predictionio_tpu.ops.ssd import scan_kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert scan_kind(64, 64, 128, 8192, 256, jnp.dtype(build), jnp.dtype(build)) == form


def test_the_mixer_is_the_references_on_histories_shorter_than_its_taps():
    rng = np.random.default_rng(5)
    d, heads, width, state = 12, 3, 4, 5
    inner = heads * width
    w = lambda *shape: (0.4 * rng.normal(size=shape)).astype(np.float32)  # noqa: E731
    p = {"w_in": w(d, 2 * inner + 2 * state), "w_dt": w(d, heads),
         "conv_w": w(4, inner + 2 * state), "conv_b": w(inner + 2 * state),
         "A_log": np.log(rng.uniform(1, 16, heads)).astype(np.float32), "dt_bias": w(heads),
         "D": 1 + w(heads), "norm": 1 + w(inner), "w_out": w(inner, d)}
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    seg = np.stack([_runs(1, 2, 3, 2, 1, 15), _runs(3, 21)]).astype(np.int32)
    got, ran = mamba2(p, x, seg, heads=heads, head_dim=width, state=state, eps=1e-5, chunk=8)
    cfg = {"mamba_n_heads": heads, "mamba_d_head": width, "mamba_d_state": state, "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        want = [ref.ssm_mixer(p, jnp.asarray(x[b]), jnp.asarray(seg[b]), cfg) for b in range(2)]
    assert rel(got, np.stack(want)) < 1e-5 and set(ran) == {"u", "B", "C", "dt", "y"}


# -- packing ----------------------------------------------------------------
@pytest.fixture(scope="module")
def hidden_of(cfg, params):
    """tokens, seg [1, L] -> the residual stream after the last layer."""
    program = jax.jit(lambda tokens, seg: bb.hidden_states(cfg, params, tokens, seg)[0])
    return lambda tokens, seg: np.asarray(program(np.asarray(tokens), np.asarray(seg)))


def test_a_packed_row_is_its_histories_one_by_one(hidden_of, batch):
    """No tap, state or key crosses a boundary: the hidden states of a
    history inside a packed row are those of the history alone in a row."""
    rows, segs = batch
    packed = hidden_of(rows[:1, :-1], segs[:1, :-1])
    for sid, (lo, hi) in HISTORIES.items():
        n = hi - lo
        alone, alone_seg = np.zeros((1, L), np.int32), np.zeros((1, L), np.int32)
        alone[0, :n], alone_seg[0, :n] = rows[0, lo:hi], 1
        single = hidden_of(alone, alone_seg)
        assert rel(packed[0, lo:hi], single[0, :n]) < 1e-5, sid


def test_a_neighbour_let_in_changes_the_row(hidden_of, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, merged = hidden_of(tokens, seg), hidden_of(tokens, np.minimum(seg, 1))
    assert rel(merged[0, 20:57], packed[0, 20:57]) > 1e-2


def test_without_positions_only_the_mixers_know_the_order(hidden_of, batch):
    """``positions: none``: nothing is added to the embedding and nothing
    turns q or k; a history moved along its row reads the same."""
    rows, _ = batch
    tokens, seg = np.zeros((2, 1, L), np.int32), np.zeros((2, 1, L), np.int32)
    tokens[0, 0, :30], seg[0, 0, :30] = rows[0, :30], 1
    tokens[1, 0, 17:47], seg[1, 0, 17:47] = rows[0, :30], 1
    assert rel(hidden_of(tokens[1], seg[1])[0, 17:47], hidden_of(tokens[0], seg[0])[0, :30]) < 1e-5


# -- the cut, tied to the model ------------------------------------------------
def test_the_cut_is_the_first_period_of_the_uncut_model_and_a_slice_of_its_head(cfg, params, batch):
    """The benchmark's configuration keeps the first whole period of the
    published layers and an eighth of the tied head: at the tiny size, the
    cut model's hidden states are those of the uncut REFERENCE after its
    first period, and the sliced head's logits are the whole head's columns
    of the slice."""
    rows, segs = batch
    cut = bb.BackboneConfig.from_dict(
        {**TINY, "num_hidden_layers": 4, "layer_types": TINY["layer_types"][:4]})
    assert (cut.n_periods, cut.period_kinds) == (1, cfg.period_kinds)
    held = {**params, "periods": jax.tree_util.tree_map(lambda a: a[:1], params["periods"])}
    hidden, *_ = bb.hidden_states(cut, held, rows[:, :-1], segs[:, :-1])
    whole = bb.layers_of(params, cfg)
    first_period = {**whole, "layers": whole["layers"][:4]}
    for b in range(2):
        want = ref.hidden_states(first_period, jnp.asarray(rows[b, :-1]), jnp.asarray(segs[b, :-1]), TINY)
        assert rel(hidden[b], want) < 1e-4
    first, count = 8, 20  # this share's rows of the embedding and head
    share = {**held, "embed": params["embed"][first:first + count]}
    np.testing.assert_allclose(
        bb.logits_of(cut, share, hidden[0]), bb.logits_of(cut, held, hidden[0])[:, first:first + count],
        rtol=1e-5, atol=1e-6)


# -- what the configuration refuses, and what no router means -----------------
@pytest.mark.parametrize("bad,says", [
    ({"mamba_n_heads": None}, "mamba_n_heads"), ({"mamba_d_head": None}, "mamba_d_head"),
    ({"mamba_d_state": None}, "mamba_d_state"), ({"mamba_d_conv": None}, "mamba_d_conv"),
    ({"mamba_n_groups": None}, "mamba_n_groups"), ({"mamba_n_groups": 3}, "mamba_n_groups is 3"),
    ({"mamba_expand": 4}, "mamba_expand"), ({"mamba_conv_bias": False}, "bias"),
    ({"mamba_proj_bias": True}, "bias"), ({"attention_bias": True}, "bias"),
    ({"backbone": {**TINY["backbone"], "positions": "alibi"}}, "positions"),
    ({"layer_types": ["mamba"] * 7 + ["chunked_attention"]}, "unknown"),
    # known since the fifth backbone, and differential attention's alone
    ({"layer_types": ["mamba"] * 7 + ["sliding_attention"]}, "differential"),
])
def test_configurations_the_backbone_cannot_run_are_refused_with_a_message(bad, says):
    conf = {k: v for k, v in {**TINY, **bad}.items() if v is not None}
    with pytest.raises(ValueError, match=says):
        bb.BackboneConfig.from_dict(conf)


def test_a_backbone_without_a_router_steps_and_counts_nothing_of_one(cfg, params):
    """``step_routers`` hands the optimizer's parameters back as they are,
    and the job's counters know no expert: no ``expert_tokens``, no
    ``passes``, no ``router_bias_abs_max``."""
    stepped = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    assert bb.step_routers(cfg, params, stepped, {}) is stepped
    assert bb.step_routers(dataclasses.replace(cfg, router_trains=False, router_bias=True),
                           params, stepped, {}) is stepped
    assert sequencerec._pass_counts({"fill": 1.0}) == {}
    assert bb.mechanisms(cfg, 1)["ssd_scan"] == "xla"
    assert "delta_rule_walk" not in bb.mechanisms(cfg, 1)
    assert "ssd_scan" not in bb.mechanisms(bb.BackboneConfig.load("lfm2-tiny"), 1)


# -- the normal path --------------------------------------------------------
def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming this configuration
    trains and answers through the same workflow as every template; the
    job's counters say which mixers ran and what ran the scan."""
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
        backbone="granite4h-tiny", steps=20, batch_size=1, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=12)),
        preparator_params=("", SeqPreparatorParams(seq_len=32)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.config.layer_types == tuple(TINY["layer_types"])
    assert model.losses[-1] < model.losses[0]
    stats = model.stats
    assert stats["mixers"] == {"gqa": 2, "mamba2": 6} and stats["ssd_scan"] == "xla"
    assert stats["tokens_per_step"] == 32 and "delta_rule_walk" not in stats
    assert stats["conv"] == "xla"  # the CPU: the XLA form of the chain
    assert not [name for name in stats if "expert" in name or "router" in name or "passes" in name]
    roots = [s for s in default_tracer().store.dump() if s["name"] == "train" and s["parentId"] is None]
    assert roots[-1]["tags"]["mixers"] == "gqa:2 mamba2:6" and roots[-1]["tags"]["ssd_scan"] == "xla"
    assert roots[-1]["tags"]["conv"] == "xla"
    answer = SeqRecAlgorithm(algo_params).predict(model, Query(recent_items=("i0", "i1", "i2"), num=3))
    scores = [s.score for s in answer.item_scores]
    assert len(scores) == 3 and scores == sorted(scores, reverse=True)
    get_registry(refresh=True)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "granite4h_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_granite4h.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "granite4h_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "granite4h-micro-vp8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "seqrec-granite4h-micro-vp8.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "shared_intermediate_size": 8192,
        "num_attention_heads": 32, "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
        "position_embedding_type": "nope", "num_local_experts": 0, "num_experts_per_tok": 0,
        "tie_word_embeddings": True, "model_type": "granitemoehybrid", "hidden_act": "silu",
        "max_position_embeddings": 131072, "rope_theta": 10000}
    for key, value in published.items():
        assert conf[key] == value, key
    assert bench["published"]["layer_types"].count("attention") == 4
    assert bench["published"]["layer_types"][:10] == conf["layer_types"]
    assert {k: v for k, v in bench["published"].items() if k != "layer_types"} == {
        "num_hidden_layers": 40, "vocab_size": 100352}
    assert bench["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.positions, cfg.ffn, cfg.norm, cfg.attention, cfg.attn_kernel) == (
        "none", "swiglu", "rms", "gqa", "xla")
    assert (cfg.n_periods, cfg.period_kinds, cfg.head_dim) == (
        1, ("ssm",) * 5 + ("full",) + ("ssm",) * 4, 64)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.first_k_dense_replace, cfg.tie_word_embeddings) == (
        False, False, 0, True)
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 8192, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(shapes["periods"]["ssm"]) == 9 * 25_847_232
    assert count(shapes["periods"]["ffn"]) == 10 * 50_331_648
    assert count(shapes["periods"]["full"]) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert count(shapes["periods"]) == 9 * 76_182_976 + 60_821_504
    assert "pos" not in shapes and "head" not in shapes and "dense" not in shapes
    assert count(shapes) == 772_160_448
