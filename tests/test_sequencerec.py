"""Sequence-recommendation engine: transformer next-item prediction.

Toy data with a deterministic transition pattern (item i is always followed
by item i+1 mod V) — the trained model must put the correct next item in its
top predictions, and the whole DASE chain must run through the Engine.
"""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.models.sequencerec import (
    PreparedData,
    Query,
    SeqDataSource,
    SeqDataSourceParams,
    SeqPreparator,
    SeqPreparatorParams,
    SeqRecAlgorithm,
    SeqRecAlgorithmParams,
    TrainingData,
    engine_factory,
)
from predictionio_tpu.storage import Event, get_registry
from predictionio_tpu.workflow.context import WorkflowContext


V = 12  # vocabulary of items i0..i11


def cyclic_training_data(n_users=30, length=40, seed=0):
    rng = np.random.default_rng(seed)
    users, seqs = [], []
    for u in range(n_users):
        start = int(rng.integers(0, V))
        seqs.append([f"i{(start + t) % V}" for t in range(length)])
        users.append(f"u{u}")
    return TrainingData(user_ids=users, sequences=seqs)


@pytest.fixture(scope="module")
def trained():
    td = cyclic_training_data()
    pd = SeqPreparator(SeqPreparatorParams(seq_len=16, window_stride=8)).prepare(
        None, td
    )
    algo = SeqRecAlgorithm(
        SeqRecAlgorithmParams(
            d_model=32, n_heads=2, n_layers=2, steps=250, batch_size=32,
            learning_rate=3e-3, seed=0,
        )
    )
    model = algo.train(None, pd)
    return algo, model


class TestPreparator:
    def test_windows_and_padding(self):
        td = TrainingData(
            user_ids=["a", "b"],
            sequences=[["x", "y", "z"], ["y"]],
        )
        pd = SeqPreparator(SeqPreparatorParams(seq_len=4)).prepare(None, td)
        assert pd.windows.shape[1] == 5
        # rows are packed, not left-padded: the history fills the first
        # slots under one segment id, and the slots no history fills are
        # segment 0 (PR 26: packed rows with segment ids took the place of
        # one left-padded window a history)
        assert pd.segments[0].tolist() == [1, 1, 1, 0, 0]
        assert pd.windows[0, :3].tolist() == [pd.item_map[i] for i in "xyz"]
        # single-item user contributes recents but no window
        assert pd.user_recent["b"] == [pd.item_map["y"]]

    def test_empty_histories_rejected(self):
        td = TrainingData(user_ids=["a"], sequences=[["x"]])
        with pytest.raises(ValueError):
            SeqPreparator().prepare(None, td)


class TestModelQuality:
    def test_learns_cycle(self, trained):
        algo, model = trained
        hits = 0
        for start in range(V):
            recent = tuple(f"i{(start + t) % V}" for t in range(8))
            res = algo.predict(model, Query(recent_items=recent, num=3))
            want = f"i{(start + 8) % V}"
            got = [s.item for s in res.item_scores]
            hits += want in got
        assert hits >= 10, f"only {hits}/12 cycle continuations in top-3"

    def test_user_history_query(self, trained):
        algo, model = trained
        res = algo.predict(model, Query(user="u0", num=5))
        assert len(res.item_scores) == 5
        # never recommends items in the user's recent window context? at
        # minimum: scores are finite and sorted descending
        scores = [s.score for s in res.item_scores]
        assert scores == sorted(scores, reverse=True)

    def test_unknown_user_empty(self, trained):
        algo, model = trained
        assert algo.predict(model, Query(user="nobody")).item_scores == ()

    def test_sanity_check(self, trained):
        _, model = trained
        model.sanity_check()


class TestSequenceParallelTraining:
    def test_ring_schedule_trains(self):
        from predictionio_tpu.parallel.mesh import MeshConfig

        td = cyclic_training_data(n_users=8, length=20)
        pd = SeqPreparator(SeqPreparatorParams(seq_len=8)).prepare(None, td)
        ctx = WorkflowContext(mesh_config=MeshConfig((("seq", 8),)))
        algo = SeqRecAlgorithm(
            SeqRecAlgorithmParams(
                d_model=16, n_heads=2, n_layers=1, steps=5, schedule="ring"
            )
        )
        model = algo.train(ctx, pd)
        model.sanity_check()


class TestEngineIntegration:
    def test_datasource_orders_by_time(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
        get_registry(refresh=True)
        store = get_registry().get_events()
        store.init(7)
        t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
        # insert out of order; sequence must come back time-ordered
        for i in [2, 0, 1]:
            store.insert(
                Event(event="view", entity_type="user", entity_id="u1",
                      target_entity_type="item", target_entity_id=f"i{i}",
                      event_time=t0 + dt.timedelta(minutes=i)),
                7,
            )
        td = SeqDataSource(SeqDataSourceParams(app_id=7)).read_training(None)
        assert td.sequences[td.user_ids.index("u1")] == ["i0", "i1", "i2"]
        get_registry(refresh=True)

    def test_engine_train_and_eval_chain(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
        get_registry(refresh=True)
        store = get_registry().get_events()
        store.init(9)
        t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
        for u in range(6):
            for t in range(12):
                store.insert(
                    Event(event="view", entity_type="user",
                          entity_id=f"u{u}",
                          target_entity_type="item",
                          target_entity_id=f"i{(u + t) % 6}",
                          event_time=t0 + dt.timedelta(minutes=t)),
                    9,
                )
        engine = engine_factory()
        algo_params = SeqRecAlgorithmParams(
            d_model=16, n_heads=2, n_layers=1, steps=10)
        ep = EngineParams(
            data_source_params=("", SeqDataSourceParams(app_id=9)),
            preparator_params=("", SeqPreparatorParams(seq_len=8)),
            algorithm_params_list=[("", algo_params)],
        )
        ctx = WorkflowContext()
        models = engine.train(ctx, ep)
        assert len(models) == 1
        algo = SeqRecAlgorithm(algo_params)
        preds = algo.predict(
            models[0], Query(recent_items=("i0", "i1"), num=3)
        )
        assert len(preds.item_scores) <= 3
        get_registry(refresh=True)


class TestWindowTail:
    def test_tail_window_anchored(self):
        # stride not dividing the history: newest items must appear
        td = TrainingData(
            user_ids=["a"],
            sequences=[[f"x{i}" for i in range(96)]],
        )
        pd = SeqPreparator(
            SeqPreparatorParams(seq_len=64, window_stride=32)
        ).prepare(None, td)
        last = pd.item_map["x95"]
        assert (pd.windows == last).any(), "newest interaction not in any window"

    def test_device_params_not_pickled(self, trained):
        import pickle

        _, model = trained
        model.device_params()  # populate cache
        blob = pickle.dumps(model)
        clone = pickle.loads(blob)
        assert "_device_params" not in clone.__dict__


def test_predicted_result_wire_shape():
    """Serving JSON must be the reference's camelCase itemScores — shared
    with every recommender template via models.wire."""
    from predictionio_tpu.models.sequencerec import ItemScore, PredictedResult
    from predictionio_tpu.workflow.serving import encode_result

    r = PredictedResult(item_scores=(ItemScore(item="i1", score=0.5),))
    assert encode_result(r) == {
        "itemScores": [{"item": "i1", "score": 0.5}]
    }


def test_a_second_job_reuses_the_programs_and_trains_the_same():
    """The jitted programs are made once per configuration
    (``_programs``): a second job of the same shape builds none, and from
    the same seed it ends on the same parameters."""
    import numpy as np

    from predictionio_tpu.models import sequencerec
    from predictionio_tpu.models.sequencerec import (
        SeqPreparator,
        SeqPreparatorParams,
        SeqRecAlgorithm,
        SeqRecAlgorithmParams,
        TrainingData,
    )

    seqs = [[f"i{(u + j) % 9}" for j in range(12)] for u in range(6)]
    td = TrainingData(
        user_ids=[f"u{u}" for u in range(6)], sequences=seqs
    )
    pd = SeqPreparator(SeqPreparatorParams(seq_len=8)).prepare(None, td)
    algo = SeqRecAlgorithm(SeqRecAlgorithmParams(
        d_model=16, n_heads=2, n_layers=1, steps=3, batch_size=4, seed=5))
    first = algo.train(None, pd).params
    made = sequencerec._programs.cache_info().misses
    second = algo.train(None, pd).params
    assert sequencerec._programs.cache_info().misses == made
    cfg = algo.params.backbone_config()
    assert algo.programs(cfg)[1] is sequencerec._programs(
        cfg, algo.params.learning_rate, None, "auto")[1]
    for key in ("embed", "pos"):
        np.testing.assert_array_equal(np.asarray(first[key]), np.asarray(second[key]))


@pytest.mark.parametrize("warm", [4, 1, 0])
def test_a_warm_up_raises_the_rate_in_a_line(warm):
    """``warmup_steps`` W: step k of a job (from 0) runs at (k + 1) / W of
    ``learning_rate`` and every step from W - 1 on at all of it. AdamW's step is
    its rate times what the moments and the parameters give, so from the same
    state the first step moves every leaf by 1 / W of what it moves without."""
    import jax
    import numpy as np
    import optax

    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models.sequencerec import SeqRecAlgorithm, SeqRecAlgorithmParams

    def first_move(**kw):
        algo = SeqRecAlgorithm(SeqRecAlgorithmParams(
            d_model=16, n_heads=2, n_layers=1, learning_rate=1e-2, **kw))
        cfg = algo.params.backbone_config()
        opt_init, step, _ = algo.programs(cfg)
        params = bb.init_params(cfg, 9, 8, 5)
        rows = np.arange(18, dtype=np.int32).reshape(2, 9) % 9
        before = jax.tree_util.tree_map(np.asarray, params)
        state = opt_init(params)
        after, state, *_ = step(params, state, rows, np.ones_like(rows))
        moved = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b, after, before)
        return moved, state

    plain, _ = first_move()
    warmed, state = first_move(warmup_steps=warm)
    for a, b in zip(jax.tree_util.tree_leaves(warmed), jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(a, b / max(warm, 1), rtol=1e-4, atol=1e-9)
    if warm > 1:
        rate = optax.linear_schedule(1e-2 / warm, 1e-2, warm - 1)
        assert [float(rate(k)) for k in (0, 1, warm - 1, warm + 5)] == pytest.approx(
            [1e-2 / warm, 2e-2 / warm, 1e-2, 1e-2])


@pytest.mark.parametrize("backbone,backend,want", [
    ("qwen3next-80b-a3b-ep16", "tpu", {"delta_rule_walk": "pallas", "conv": "pallas"}),
    ("lfm2-24b-a2b-ep8", "tpu", {"conv": "pallas"}),
    ("granite4h-micro-vp8", "tpu", {"ssd_scan": "pallas", "conv": "pallas"}),
    ("joyai-flash-48b-a3b-ep16", "tpu", {}),
    ("qwen3next-80b-a3b-ep16", "cpu", {"delta_rule_walk": "scan", "conv": "xla"}),
    ("lfm2-24b-a2b-ep8", "cpu", {"conv": "xla"}),
    ("granite4h-micro-vp8", "cpu", {"ssd_scan": "xla", "conv": "xla"}),
    ("qwen3next-tiny", "tpu", {"delta_rule_walk": "scan", "conv": "pallas"}),  # 128 channels
    ("lfm2-tiny", "tpu", {"conv": "xla"}),
    ("granite4h-tiny", "tpu", {"ssd_scan": "xla", "conv": "xla"}),
    ("phi4-mini-flash-vp8", "tpu",
     {"selective_scan": "pallas", "conv": "pallas", "attn_tiles_skipped_by_window": 105}),
    ("phi4-mini-flash-vp8", "cpu",
     {"selective_scan": "xla", "conv": "xla", "attn_tiles_skipped_by_window": 105}),
    ("phi4-mini-flash-tiny", "tpu",  # 128 channels on a state of 4; rows of 8,192 in tiles of 16 under a window of 16
     {"selective_scan": "xla", "conv": "pallas", "attn_tiles_skipped_by_window": 512 * 513 // 2 - 1023}),
])
def test_the_counters_that_say_which_form_of_a_mixer_runs(monkeypatch, backbone, backend, want):
    """``delta_rule_walk``, ``ssd_scan`` and ``conv`` in ``SeqRecModel.stats``
    and on the job's ``train`` span: read from the widths, the row's length
    and the backend, each only where the backbone has such a mixer; the
    cells' configurations run the convolution's kernel on a TPU, channels
    that are no lane tiles (64 and 160 of the toy widths) and the CPU the XLA
    form."""
    import jax

    from predictionio_tpu.models import seq_backbone as bb

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = bb.BackboneConfig.load(backbone)
    assert bb.mechanisms(cfg, 8192) == want
    # and every convolving mixer's own record says the form ``conv`` joins
    convs = {bb._MIXERS[kind].forms(cfg, 8192).get("conv") for kind in set(cfg.kinds)} - {None}
    assert "+".join(sorted(convs)) == want.get("conv", "")


def test_a_row_that_is_no_whole_halo_blocks_runs_the_xla_form(monkeypatch):
    import jax

    from predictionio_tpu.models import seq_backbone as bb

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from predictionio_tpu.ops import shortconv

    cfg = bb.BackboneConfig.load("lfm2-24b-a2b-ep8")
    assert bb.mechanisms(cfg, 8192) == {"conv": "pallas"}
    assert bb.mechanisms(cfg, 8200) == {"conv": "xla"}
    import dataclasses

    assert bb.mechanisms(dataclasses.replace(cfg, gate_dtype="bfloat16"), 8192) == {"conv": "xla"}
    # the op's own answer, from its parameters' shapes and its keyword arguments
    shapes = {"w_in": (2048, 6144), "conv_w": (3, 2048), "w_out": (2048, 2048)}
    assert shortconv.forms(shapes, 8192, gate_dtype="float32") == {"conv": "pallas"}
    assert shortconv.forms(shapes, 8200, gate_dtype="float32") == {"conv": "xla"}
    assert shortconv.forms(shapes, 8192, gate_dtype="bfloat16") == {"conv": "xla"}


@pytest.mark.parametrize("change,length,want", [
    ({}, 8192, "pallas"),
    ({"state_dtype": "bfloat16", "gate_dtype": "bfloat16"}, 8192, "xla"),  # the cell's control build
    ({"gate_dtype": "bfloat16"}, 8192, "xla"),
    ({}, 8200, "xla"),  # no whole strips
])
def test_the_selective_scan_counter_follows_the_dtypes_and_the_row(monkeypatch, change, length, want):
    import dataclasses

    import jax

    from predictionio_tpu.models import seq_backbone as bb

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(bb.BackboneConfig.load("phi4-mini-flash-vp8"), **change)
    assert bb.mechanisms(cfg, length)["selective_scan"] == want
