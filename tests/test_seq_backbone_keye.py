"""The sequence backbone as Keye-VL-2.0's language block (grouped-query
attention that reads only the keys a lightning indexer picks, trained with the
indexer's own loss; routed experts behind a softmax router, no shared expert;
untied head) against its plain reference, at a small size on the CPU: hidden
64, 4 query heads on 2 key heads of 16, an indexer of 4 heads of 8 that keeps
24 keys, 4 of 8 experts held, two layers, tiles of 16 slots, rows of 64 slots
that hold three histories (one shorter than ``topk``) or one.

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
from predictionio_tpu.models import sequencerec
from predictionio_tpu.ops import dsa, moe
from predictionio_tpu.testing import keye_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "keye-vl2-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L, TOPK = 50, 64, 24
#: a history shorter than topk, one longer, a short one, and padding
HISTORIES = {1: (0, 20), 2: (20, 57), 3: (57, 62)}
GROUPS = {
    "attn": lambda layer: {k: v for k, v in layer["dsa"].items() if k not in ref.INDEXER},
    "indexer": lambda layer: {k: layer["dsa"][k] for k in ref.INDEXER},
    "router": lambda layer: layer["moe"]["router"],
    "experts": lambda layer: layer["moe"]["experts"],
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


def rel(got, want):
    got = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def seeded(cfg, seed=0):
    """Seeded weights with every norm scale and bias moved off its starting
    value and the indexer's matrices ten times their draw, so that none of
    them drops out of a comparison and the scores differ enough to choose."""
    drawn = bb.init_params(cfg, VOCAB, L, seed)
    rng = np.random.default_rng(1)

    def moved(path, leaf):
        if any(f"'{name}'" in jax.tree_util.keystr(path) for name in ("w_iq", "w_ik", "w_iw")):
            return 10.0 * leaf
        return leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32) if leaf.ndim <= 3 else leaf

    return jax.tree_util.tree_map_with_path(moved, drawn)


def packed_batch():
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    for sid, (lo, hi) in HISTORIES.items():
        segs[0, lo:hi] = sid
    segs[1, :] = 1
    return rows, segs


def unpacked(packed):
    return np.unpackbits(np.asarray(packed), axis=-1, count=L, bitorder="little").astype(bool)


@pytest.fixture(scope="module")
def cfg():
    return bb.BackboneConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


@pytest.fixture(scope="module")
def batch():
    return packed_batch()


@pytest.fixture(scope="module")
def both(cfg, params, batch):
    rows, segs = batch
    program = jax.jit(jax.value_and_grad(
        lambda mp, r, s: bb.loss_fn(cfg, mp, r, s), has_aux=True))
    (loss, (hidden, counters, ran)), grads = program(params, rows, segs)
    slots = [np.arange(0, 60, 7), np.arange(3, 64, 5)]
    want = ref.loss_and_grads(bb.layers_of(params, cfg), rows, segs, TINY, sample=slots)
    logits = [bb.logits_of(cfg, params, hidden[b][at]) for b, at in enumerate(slots)]
    return {"loss": float(loss), "grads": bb.layers_of(grads, cfg), "logits": logits,
            "counters": counters, "ran": ran, "want": dict(zip(("losses", "grads", "logits"), want))}


def test_both_losses_and_logits_match_reference(both):
    want = both["want"]["losses"]
    assert both["loss"] == pytest.approx(want["loss"], rel=1e-5)
    assert float(both["counters"]["index_loss"].sum()) == pytest.approx(want["index_loss"], rel=1e-4)
    assert want["index_loss"] > 0.01  # the indexer has something to learn
    assert rel(both["logits"], both["want"]["logits"]) < 1e-5


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "head", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    got, want = both["grads"], both["want"]["grads"]
    if group in GROUPS:
        got = [GROUPS[group](layer) for layer in got["layers"]]
        want = [GROUPS[group](layer) for layer in want["layers"]]
    else:
        got, want = got[group], want[group]
    assert rel(got, want) < 2e-4
    assert max(float(np.abs(leaf).max()) for leaf in jax.tree_util.tree_leaves(want)) > 0


def test_the_choice_is_the_references_sort(cfg, params, batch, both):
    """Every layer's chosen sets, as the program hands them out a bit a pair,
    are the sets the reference's sort gives; a history no longer than topk keeps
    every causal key, a longer one topk a slot from slot topk on."""
    rows, segs = batch
    want = ref.chosen_sets(bb.layers_of(params, cfg), rows, segs, TINY)
    got = unpacked(both["ran"]["chosen"])  # [layers, B, L, L]
    for i in range(cfg.num_hidden_layers):
        for b in range(2):
            assert (got[i, b] == want[i][b]).all()
    per_slot = got[0].sum(-1)
    # (a score is exactly 0 where no head's product is positive, so a threshold
    # of 0 keeps every such key: more than topk)
    assert (per_slot[1] >= np.minimum(np.arange(L) + 1, TOPK)).all()
    assert (per_slot[1][:TOPK] == np.arange(TOPK) + 1).all()
    assert (per_slot[0][:20] == np.arange(20) + 1).all()
    assert (per_slot[0][20 + TOPK:57] >= TOPK).all() and per_slot[0][20 + TOPK:57].mean() < TOPK + 3
    kept, causal = (int(np.asarray(both["counters"][name])[0, 0])
                    for name in ("kept_pairs", "causal_pairs"))
    real = np.asarray(segs[:, :-1] > 0)
    assert kept == int(got[0].sum(-1)[real].sum())
    assert causal == sum(n * (n + 1) // 2 for n in (20, 37, 5, 64))


@pytest.mark.parametrize("scores,topk,want", [
    ([0.0, -0.0, 0.0, -0.0, 1.0, -1.0], 3, [0, 0, 1, 1, 1, 0]),  # -0.0 ties with 0.0
    ([2.0, 2.0, 2.0, 2.0, 2.0, 2.0], 2, [0, 0, 0, 0, 1, 1]),  # the most recent of equals
    ([-3.0, 5.0, -3.0, 5.0, -7.0, -3.0], 4, [0, 1, 1, 1, 0, 1]),
    ([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], 9, [1, 1, 1, 1, 1, 1]),  # no more than topk: all
])
def test_the_rule_among_equal_scores(scores, topk, want):
    """One query's row by hand, the last key not valid: what ``ops.dsa`` and the
    reference's stable sort keep of it."""
    row = jnp.asarray(scores + [9.0], jnp.float32)[None, None]
    valid = jnp.asarray([True] * len(scores) + [False])[None, None]
    assert np.asarray(dsa._strip_choice(row, valid, topk))[0, 0].tolist() == [bool(w) for w in want] + [False]
    assert np.asarray(ref.chosen_by_sort(row[0], valid[0], topk))[0].tolist() == [bool(w) for w in want] + [False]


@pytest.mark.parametrize("topk", [7, 3])
@pytest.mark.parametrize("length,block", [(64, 16), (50, 16), (40, 64)])
def test_the_choice_against_a_sort_ties_included(topk, length, block):
    """``ops.dsa.select`` on seeded scores with many ties (the index products
    rounded to a few values) keeps EXACTLY min(topk,
    causal keys) a query: the largest scores, and of the keys that tie at the
    last place the most recent ones, as a stable sort of the same scores does."""
    rng = np.random.default_rng(length)
    heads, d = 2, 4
    iq = np.round(rng.normal(size=(2, length, heads, d))).astype(np.float32)
    ik = np.round(rng.normal(size=(2, length, d))).astype(np.float32)
    iw = np.round(2 * rng.normal(size=(2, length, heads))).astype(np.float32)
    seg = np.ones((2, length), np.int32)
    seg[0, length // 3:] = 2
    seg[0, -3:] = 0
    chosen, kept, causal, sample, at = dsa.select(
        jnp.asarray(iq), jnp.asarray(ik), jnp.asarray(iw), jnp.asarray(seg), topk=topk,
        block=block)
    broken = 0
    for b in range(2):
        at_rows = jnp.arange(length)
        valid = np.asarray(ref.valid_pairs(jnp.asarray(seg[b]), at_rows))
        scores = np.asarray(ref.index_scores(jnp.asarray(iq[b]), jnp.asarray(ik[b]), jnp.asarray(iw[b])))
        want = np.asarray(ref.chosen_by_sort(jnp.asarray(scores), jnp.asarray(valid), topk))
        got = np.asarray(chosen[b])
        assert (got == want).all()
        assert (got.sum(-1) == np.minimum(valid.sum(-1), topk)).all() and not (got & ~valid).any()
        for t in np.flatnonzero(valid.sum(-1) > topk):
            # nothing left out beats anything kept; of the keys that equal the
            # smallest kept score, the kept ones are the last
            low = scores[t][got[t]].min()
            assert scores[t][valid[t] & ~got[t]].max() <= low
            tied = np.flatnonzero(valid[t] & (scores[t] == low))
            n_kept = int(got[t][tied].sum())
            assert (np.flatnonzero(got[t][tied]) == np.arange(len(tied) - n_kept, len(tied))).all()
            broken += n_kept < len(tied)
        real = seg[b] > 0
        assert int(kept[b]) == int(want[real].sum()) and int(causal[b]) == int(valid[real].sum())
    assert broken > 0  # a tie at the last place did have to be broken
    blk = min(block, length)
    strip = slice(int(at) * blk, min((int(at) + 1) * blk, length))
    assert np.asarray(sample).shape == (2, blk, length)
    for b in range(2):
        valid = np.asarray(ref.valid_pairs(jnp.asarray(seg[b]), jnp.arange(length)))[strip]
        scores = np.asarray(ref.index_scores(jnp.asarray(iq[b]), jnp.asarray(ik[b]), jnp.asarray(iw[b])))[strip]
        np.testing.assert_allclose(np.where(valid, np.asarray(sample[b])[: valid.shape[0]], 0),
                                   np.where(valid, scores, 0), atol=1e-5)


@pytest.mark.parametrize("k", [1, 5, 64, 100])
@pytest.mark.parametrize("bits", [1, 2, 4])
def test_the_bisection_finds_the_kth_largest_bit_pattern(k, bits):
    rng = np.random.default_rng(k)
    x = np.concatenate([rng.normal(size=(3, 60)), [[-np.inf, 0.0, -0.0, np.inf]] * 3], 1).astype(np.float32)
    keys = dsa._sortable(jnp.asarray(x))
    got = np.asarray(dsa.kth_largest_key(keys, k, bits=bits))
    want = np.sort(np.asarray(keys), axis=-1)[:, -k] if k <= 64 else np.zeros(3, np.uint32)
    assert (got == want).all()
    # small keys (places among tied keys) need only their own width, and k may differ a row
    places = rng.integers(0, 200, size=(3, 64)).astype(np.uint32)
    each = np.asarray([1, min(k, 64), 7], np.int32)
    got = np.asarray(dsa.kth_largest_key(jnp.asarray(places), jnp.asarray(each), 8, bits))
    assert (got == [np.sort(row)[-n] for row, n in zip(places, each)]).all()
    order = np.argsort(np.asarray(keys[0]))
    assert (np.diff(x[0][order]) >= 0).all()  # the keys order as the floats do


def test_no_gradient_crosses_between_the_indexer_and_the_next_item_loss(cfg, params, batch):
    """The next-item loss alone gives the indexer nothing (the choice has no
    gradient, its input is behind a stop_gradient); the indexers' loss alone
    gives nothing but the indexer anything."""
    rows, segs = batch

    def parts(mp):
        tokens, seg, targets, valid = bb.split_rows(rows, segs)
        hidden, counters, _ = bb.hidden_states(cfg, mp, tokens, seg)
        return bb.next_item_loss(cfg, mp, hidden, targets, valid), counters["index_loss"].sum()

    next_item = bb.layers_of(jax.grad(lambda mp: parts(mp)[0])(params), cfg)
    own = bb.layers_of(jax.grad(lambda mp: parts(mp)[1])(params), cfg)
    biggest = lambda tree: max(float(np.abs(a).max()) for a in jax.tree_util.tree_leaves(tree))  # noqa: E731
    for layer in next_item["layers"]:
        assert biggest(GROUPS["indexer"](layer)) == 0.0
        assert biggest(GROUPS["attn"](layer)) > 0
    for layer in own["layers"]:
        assert biggest(GROUPS["indexer"](layer)) > 0
        assert biggest({**layer, "dsa": GROUPS["attn"](layer)}) == 0.0
    assert biggest((own["embed"], own["head"], own["final_norm"])) == 0.0


def test_the_core_is_the_references_on_what_it_was_handed(both, batch):
    rows, segs = batch
    ran = both["ran"]
    sets = unpacked(ran["chosen"][0])
    for b in range(2):
        want = ref.sparse_core_of(ran["q"][0][b], ran["k"][0][b][0], ran["v"][0][b][0],
                                  segs[b, :-1], sets[b])
        assert rel(ran["o"][0][b], want) < 1e-5


def test_the_reference_runs_on_the_sets_it_is_given(cfg, params, batch, both):
    """Given the program's own sets the reference gives what it gives with its
    own (they are the same sets here); given other sets it gives another loss."""
    rows, segs = batch
    layout = bb.layers_of(params, cfg)
    sets = unpacked(both["ran"]["chosen"])
    given = ref.loss_and_grads(layout, rows, segs, TINY, chosen=[list(layer) for layer in sets])
    assert given[0]["loss"] == pytest.approx(both["want"]["losses"]["loss"], rel=1e-6)
    causal = [[np.tril(np.ones((L, L), bool))] * 2] * 2  # every causal key: no choice at all
    dense = ref.loss_and_grads(layout, rows, segs, TINY, chosen=causal)
    assert abs(dense[0]["loss"] - given[0]["loss"]) > 1e-4


@pytest.mark.parametrize("build,passes", [("float32", True), ("bfloat16", False)])
def test_the_control_build_fails_index_err_and_the_sound_build_passes(build, passes):
    """The scores with their head-weighted sum in bfloat16 (the benchmark's
    control) read about 3e-3 from the reference on the same inputs; the sound
    build reads summation order."""
    rng = np.random.default_rng(5)
    iq = rng.normal(size=(1, 32, 4, 8)).astype(np.float32)
    ik = rng.normal(size=(1, 48, 8)).astype(np.float32)
    iw = rng.normal(size=(1, 32, 4)).astype(np.float32)
    got = dsa.index_scores(jnp.asarray(iq), jnp.asarray(ik), jnp.asarray(iw), jnp.dtype(build))
    want = ref.index_scores_of(iq[0], ik[0], iw[0])
    assert (rel(got[0], want) < 1e-4) == passes


def test_a_packed_row_is_its_histories_one_by_one(cfg, params, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    whole = np.asarray(bb.hidden_states(cfg, params, tokens, seg)[0][0])
    for sid, (lo, hi) in HISTORIES.items():
        alone = np.zeros_like(tokens), np.zeros_like(seg)
        alone[0][0, : hi - lo], alone[1][0, : hi - lo] = tokens[0, lo:hi], 1
        got = np.asarray(bb.hidden_states(cfg, params, *alone)[0][0, : hi - lo])
        assert rel(got, whole[lo:hi]) < 1e-4, sid


def test_the_shares_add_up_to_the_uncut_layer():
    """What the 8 shares of 16 of 128 experts give, nothing counted twice since
    there is no shared expert, is what the uncut reference gives for the whole
    layer (model-configs guide, section 4), at the router's published width and
    its 8 experts a token."""
    rng = np.random.default_rng(6)
    d, f, experts, top_k = 16, 8, 128, 8
    full = {"router": rng.normal(size=(d, experts)).astype(np.float32),
            "experts": {"wg": 0.3 * rng.normal(size=(experts, d, f)).astype(np.float32),
                        "wu": 0.3 * rng.normal(size=(experts, d, f)).astype(np.float32),
                        "wd": 0.3 * rng.normal(size=(experts, f, d)).astype(np.float32)}}
    x = rng.normal(size=(40, d)).astype(np.float32)
    ref_cfg = {"num_experts_per_tok": top_k, "norm_topk_prob": True, "experts_held": [0, experts]}
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_block(jax.tree_util.tree_map(jnp.asarray, full), jnp.asarray(x), ref_cfg)
    total, counted = np.zeros_like(x), 0
    for first in range(0, experts, 16):
        share = {"router": full["router"], "experts": {
            name: w[first: first + 16] for name, w in full["experts"].items()}}
        y, counters = moe.expert_layer(share, x, first=first, top_k=top_k, norm_topk=True,
                                       scoring="softmax")
        total += np.asarray(y)
        counted += int(counters["expert_tokens"].sum())
        assert int(counters["dropped"]) == 0
        with jax.default_matmul_precision("highest"):
            alone = ref.moe_block(jax.tree_util.tree_map(jnp.asarray, share), jnp.asarray(x),
                                  ref_cfg, held=[first, 16])
        assert rel(y, alone) < 1e-5
    assert counted == 40 * top_k
    assert rel(total, whole) < 1e-5


def test_a_held_router_stays_where_it_was(cfg, params, batch):
    held = bb.BackboneConfig.from_dict({**TINY, "backbone": {**TINY["backbone"], "router_trains": False}})
    opt_init, step, _ = sequencerec._programs(held, 1e-2, None, "auto")
    before = jax.tree_util.tree_map(np.asarray, params)
    after, *_ = step(jax.tree_util.tree_map(jnp.asarray, before), opt_init(params), *batch)
    assert (np.asarray(after["periods"]["ffn"]["router"]) == before["periods"]["ffn"]["router"]).all()
    assert not (np.asarray(after["periods"]["dsa"]["w_iq"])
                == before["periods"]["dsa"]["w_iq"]).all()


@pytest.mark.parametrize("bad,says", [
    ({"sa_config": {**TINY["sa_config"], "indexer_num_kv_heads": 2}}, "ONE index key"),
    ({"sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8}}, "index_topk"),
    ({"attention_bias": True}, "no bias"),
    ({"layer_types": ["sparse_attention", "full_attention", "sparse_attention"],
      "num_hidden_layers": 3, "first_k_dense_replace": 1}, "belong to the periods"),
])
def test_configurations_the_backbone_cannot_run_are_refused_with_a_message(bad, says):
    with pytest.raises(ValueError, match=says):
        bb.BackboneConfig.from_dict({**TINY, **bad})


def test_the_layout_comes_from_sa_config(cfg, both):
    assert cfg.layer_types == ("sparse_attention",) * 2 and cfg.period_kinds == ("dsa",)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (4, 8, TOPK)
    assert cfg.mixers() == {"dsa": 2}
    # the choice has one form; the core and the indexers' loss two: toy heads on the CPU take
    # the XLA loops
    assert bb.mechanisms(cfg, L) == {"chosen_core": "xla", "index_kl": "xla"}
    layer = both["grads"]["layers"][0]
    assert sorted(layer) == ["dsa", "input_norm", "moe", "post_norm"]
    assert set(ref.INDEXER) < set(layer["dsa"]) and len(layer["dsa"]) == 6 + len(ref.INDEXER)
    assert "shared" not in layer["moe"] and "router_bias" not in layer["moe"]


def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming this configuration
    trains and answers through the same workflow as every template; the job's
    counters say which mixer ran, how the choice is made, what it kept and the
    indexers' loss, on the job's stats and on the spans' tags."""
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
        for t in range(20 + 6 * u):
            store.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}", target_entity_type="item",
                target_entity_id=f"i{(u + t) % 7}", event_time=t0 + dt.timedelta(minutes=t)), 11)
    algo_params = SeqRecAlgorithmParams(
        backbone="keye-vl2-tiny", steps=30, batch_size=2, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=11)),
        preparator_params=("", SeqPreparatorParams(seq_len=64)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.losses[-1] < model.losses[0]
    stats = model.stats
    assert stats["mixers"] == {"dsa": 2} and stats["chosen_core"] == "xla"
    assert 50.0 < stats["dsa_kept_pairs_pct"] < 100.0  # the whole job: histories of up to 62 ids, 24 kept
    assert stats["index_loss_by_step"].shape == (30, 2, 1)
    assert stats["index_loss"] == pytest.approx(float(stats["index_loss_by_step"][-1].sum()), rel=1e-6)
    assert np.isfinite(stats["index_loss_by_step"]).all() and stats["index_loss_by_step"].min() > 0
    spans = default_tracer().store.dump()
    roots = [s for s in spans if s["name"] == "train" and s["parentId"] is None]
    assert roots[-1]["tags"]["mixers"] == "dsa:2"
    steps = [s for s in spans if s["name"] == "seqrec.step" and s["traceId"] == roots[-1]["traceId"]]
    # a step's span waits for the step before it, and carries what that one counted
    tagged = {s["tags"]["i"]: s["tags"] for s in steps}
    assert "index_loss" not in tagged[0]
    assert tagged[29]["index_loss"] == pytest.approx(float(stats["index_loss_by_step"][28].sum()), rel=1e-5)
    assert 50.0 < tagged[29]["dsa_kept_pairs_pct"] < 100.0
    answer = SeqRecAlgorithm(algo_params).predict(model, Query(recent_items=("i0", "i1", "i2"), num=3))
    scores = [s.score for s in answer.item_scores]
    assert len(scores) == 3 and scores == sorted(scores, reverse=True)
    get_registry(refresh=True)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "keye_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_keye.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "keye_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "keye-vl2-30b-a3b-ep8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "seqrec-keye-vl2-30b-a3b-ep8.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "router_width": 128}
    for key, value in published.items():
        assert conf[key] == value, key
    assert bench["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert bench["reduced"] == list(bench["published"])
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (6, 16, 18992)
    assert bench["algorithm"]["seq_len"] == 16384 and bench["algorithm"]["batch_size"] == 1
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.partial_rotary_factor, cfg.rope_theta) == (True, False, 1.0, 1e7)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert (cfg.scoring_func, cfg.router_bias, cfg.router_trains, cfg.norm_topk_prob) == (
        "softmax", False, False, True)
    assert (cfg.n_periods, cfg.period_kinds, cfg.experts_held, cfg.sliding_window) == (
        6, ("dsa",), (0, 16), 0)
    assert cfg.shared_expert_intermediate_size == 0 and not cfg.tie_word_embeddings
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 16384, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count({k: shapes["periods"]["dsa"][k] for k in ref.INDEXER}) == 6 * (
        2_097_152 + 131_072 + 32_768 + 128)
    assert count(shapes["periods"]["dsa"]) == 6 * (2 * 8_388_608 + 2 * 1_048_576 + 256 + 2_261_120)
    assert count(shapes["periods"]["ffn"]) == 6 * (262_144 + 16 * 4_718_592)
    assert count(shapes["periods"]) == 6 * 96_899_456
    assert count(shapes["embed"]) + count(shapes["head"]) == 77_791_232
    assert count(shapes) == 659_190_016
