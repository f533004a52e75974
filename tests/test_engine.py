"""Engine/controller tests — the analogue of ``EngineTest.scala``,
``EngineWorkflowTest``, ``EvaluationWorkflowTest`` and
``FastEvalEngineTest.scala`` (memoization-count asserts)."""

import dataclasses
import pickle

import pytest

from predictionio_tpu.controller import (
    RETRAIN,
    EmptyParams,
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    FastEvalEngine,
    FirstServing,
    IdentityPreparator,
    Metric,
    MetricEvaluator,
    ParamsError,
    PersistentModelManifest,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    WorkflowParams,
    extract_params,
)
from predictionio_tpu.workflow.context import WorkflowContext

from sample_engine import (
    Algo0,
    Algo1,
    Actual,
    DataSource0,
    DSParams,
    IdParams,
    NonPersistentAlgo,
    PersistentAlgo,
    PersistableModel,
    Prediction,
    Preparator0,
    Query,
    SampleModel,
    Serving0,
    reset_all_counts,
)


@pytest.fixture(autouse=True)
def _reset():
    reset_all_counts()


@pytest.fixture()
def ctx():
    return WorkflowContext(mode="Training", batch="test")


def make_engine(algo_map=None):
    return Engine(
        {"": DataSource0},
        {"": Preparator0},
        algo_map or {"": Algo0},
        {"": Serving0},
    )


def make_params(ds_id=3, prep_id=7, algo_ids=(11,), n_eval_sets=2):
    return EngineParams(
        data_source_params=("", DSParams(id=ds_id, n_eval_sets=n_eval_sets)),
        preparator_params=("", IdParams(id=prep_id)),
        algorithm_params_list=[("", IdParams(id=a)) for a in algo_ids],
        serving_params=("", IdParams(id=0)),
    )


class TestTrain:
    def test_dataflow_composition(self, ctx):
        engine = make_engine()
        models = engine.train(ctx, make_params(ds_id=3, prep_id=7, algo_ids=(11, 13)))
        assert models == [
            SampleModel(algo_id=11, pd_id=7),
            SampleModel(algo_id=13, pd_id=7),
        ]

    def test_read_error_wrapped(self, ctx):
        engine = make_engine()
        params = make_params()

        class BoomDS(DataSource0):
            def read_training(self, c):
                raise IOError("backend down")

        eng = Engine({"": BoomDS}, {"": Preparator0}, {"": Algo0}, {"": Serving0})
        with pytest.raises(RuntimeError, match="Data is incomplete"):
            eng.train(ctx, params)

    def test_sanity_check_failure_propagates(self, ctx):
        engine = make_engine()
        params = make_params()
        params = params.copy(
            data_source_params=("", DSParams(id=1, error=True))
        )
        with pytest.raises(ValueError, match="sanity check"):
            engine.train(ctx, params)
        # --skip-sanity-check suppresses it (Engine.scala:526-543)
        models = engine.train(
            ctx, params, WorkflowParams(skip_sanity_check=True)
        )
        assert len(models) == 1

    def test_stop_after_read_and_prepare(self, ctx):
        engine = make_engine()
        with pytest.raises(StopAfterReadInterruption):
            engine.train(ctx, make_params(), WorkflowParams(stop_after_read=True))
        with pytest.raises(StopAfterPrepareInterruption):
            engine.train(ctx, make_params(), WorkflowParams(stop_after_prepare=True))

    def test_unknown_component_name(self, ctx):
        engine = make_engine()
        bad = make_params().copy(data_source_params=("nope", EmptyParams()))
        with pytest.raises(KeyError):
            engine.train(ctx, bad)


class TestPersistence:
    def test_plain_model_passthrough_pickle(self, ctx):
        engine = make_engine()
        params = make_params()
        models = engine.train(ctx, params)
        persisted = engine.make_serializable_models(ctx, params, "I1", models)
        roundtrip = pickle.loads(pickle.dumps(persisted))
        live = engine.prepare_deploy(ctx, params, "I1", roundtrip)
        assert live == models

    def test_persistent_model_manifest(self, ctx):
        engine = Engine(
            {"": DataSource0}, {"": Preparator0}, {"": PersistentAlgo}, {"": Serving0}
        )
        params = make_params(algo_ids=(5,))
        models = engine.train(ctx, params)
        persisted = engine.make_serializable_models(ctx, params, "I2", models)
        assert isinstance(persisted[0], PersistentModelManifest)
        live = engine.prepare_deploy(
            ctx, params, "I2", pickle.loads(pickle.dumps(persisted))
        )
        assert isinstance(live[0], PersistableModel)
        assert live[0].algo_id == 5

    def test_retrain_at_deploy(self, ctx):
        engine = Engine(
            {"": DataSource0}, {"": Preparator0}, {"": NonPersistentAlgo}, {"": Serving0}
        )
        params = make_params(algo_ids=(9,))
        models = engine.train(ctx, params)
        assert NonPersistentAlgo.count == 1
        persisted = engine.make_serializable_models(ctx, params, "I3", models)
        assert persisted[0] is RETRAIN
        # RETRAIN survives pickling as the same sentinel
        unpickled = pickle.loads(pickle.dumps(persisted))
        assert unpickled[0] is RETRAIN
        live = engine.prepare_deploy(ctx, params, "I3", unpickled)
        assert NonPersistentAlgo.count == 2  # retrained
        assert live[0] == SampleModel(algo_id=9, pd_id=7)

    def test_mixed_persistence(self, ctx):
        engine = Engine(
            {"": DataSource0},
            {"": Preparator0},
            {"plain": Algo0, "npa": NonPersistentAlgo, "pa": PersistentAlgo},
            {"": Serving0},
        )
        params = make_params().copy(
            algorithm_params_list=[
                ("plain", IdParams(id=1)),
                ("npa", IdParams(id=2)),
                ("pa", IdParams(id=3)),
            ]
        )
        models = engine.train(ctx, params)
        persisted = engine.make_serializable_models(ctx, params, "I4", models)
        live = engine.prepare_deploy(
            ctx, params, "I4", pickle.loads(pickle.dumps(persisted))
        )
        assert live[0] == SampleModel(algo_id=1, pd_id=7)
        assert live[1] == SampleModel(algo_id=2, pd_id=7)
        assert isinstance(live[2], PersistableModel)


class TestEval:
    def test_eval_dataflow(self, ctx):
        engine = make_engine({"a0": Algo0, "a1": Algo1})
        params = make_params(n_eval_sets=2).copy(
            algorithm_params_list=[("a0", IdParams(id=1)), ("a1", IdParams(id=2))]
        )
        results = engine.eval(ctx, params)
        assert len(results) == 2  # two folds
        ei, qpa = results[0]
        assert ei.id == 3
        assert len(qpa) == 2
        q, p, a = qpa[0]
        assert isinstance(q, Query) and isinstance(a, Actual)
        # serving combined both algos in order
        assert p.combined == (1, 2)
        assert p.algo_id == 1  # first algo's prediction is the base

    def test_batch_eval_returns_all_params(self, ctx):
        engine = make_engine()
        eps = [make_params(algo_ids=(i,)) for i in range(3)]
        results = engine.batch_eval(ctx, eps)
        assert [ep for ep, _ in results] == eps


class TestJsonToEngineParams:
    def test_full_variant(self):
        engine = make_engine({"a0": Algo0, "a1": Algo1})
        variant = {
            "id": "default",
            "engineFactory": "tests.Factory",
            "datasource": {"params": {"id": 4, "n_eval_sets": 1}},
            "preparator": {"params": {"id": 5}},
            "algorithms": [
                {"name": "a0", "params": {"id": 6}},
                {"name": "a1", "params": {"id": 7}},
            ],
            "serving": {"params": {"id": 8}},
        }
        ep = engine.json_to_engine_params(variant)
        assert ep.data_source_params == ("", DSParams(id=4, n_eval_sets=1))
        assert ep.preparator_params == ("", IdParams(id=5))
        assert ep.algorithm_params_list == (
            ("a0", IdParams(id=6)),
            ("a1", IdParams(id=7)),
        )
        assert ep.serving_params == ("", IdParams(id=8))

    def test_missing_fields_use_component_defaults(self):
        # An absent params block yields the component's declared default
        # Params (its params_class()), not EmptyParams — a component with
        # meaningful defaults (e.g. a preparator's seq_len) must still work
        # when the variant omits the block.
        engine = make_engine()
        ep = engine.json_to_engine_params({"engineFactory": "f"})
        assert ep.data_source_params == ("", DSParams())
        assert ep.algorithm_params_list == (("", IdParams()),)

    def test_unknown_algorithm_name_rejected(self):
        engine = make_engine()
        with pytest.raises(ParamsError):
            engine.json_to_engine_params(
                {"algorithms": [{"name": "ghost", "params": {}}]}
            )

    def test_params_extraction_errors(self):
        with pytest.raises(ParamsError, match="unknown fields"):
            extract_params(IdParams, {"id": 1, "bogus": 2})
        with pytest.raises(ParamsError, match="expected an integer"):
            extract_params(IdParams, {"id": "x"})

    def test_engine_instance_roundtrip(self):
        from predictionio_tpu.controller import serialize_engine_params

        engine = make_engine({"a0": Algo0})
        ep = make_params().copy(
            algorithm_params_list=[("a0", IdParams(id=42))]
        )
        cols = serialize_engine_params(ep)

        class FakeInstance:
            data_source_params = cols["data_source_params"]
            preparator_params = cols["preparator_params"]
            algorithms_params = cols["algorithms_params"]
            serving_params = cols["serving_params"]

        ep2 = engine.engine_instance_to_engine_params(FakeInstance())
        assert ep2 == ep


class IdSumMetric(Metric):
    """Sums prediction algo ids over all folds (deterministic check)."""

    def calculate(self, ctx, eval_data_set):
        return sum(
            p.algo_id for _, qpa in eval_data_set for _, p, _ in qpa
        )


class TestMetricEvaluator:
    def test_best_params_selection(self, ctx):
        engine = make_engine()
        eps = [make_params(algo_ids=(i,)) for i in (1, 5, 3)]
        data = engine.batch_eval(ctx, eps)
        result = MetricEvaluator(IdSumMetric()).evaluate_base(ctx, None, data)
        assert result.best_idx == 1
        assert result.best_engine_params == eps[1]
        assert result.best_score.score == 5 * 4  # 2 folds x 2 queries
        assert len(result.engine_params_scores) == 3

    def test_tie_keeps_earliest(self, ctx):
        engine = make_engine()
        eps = [make_params(algo_ids=(2,)), make_params(algo_ids=(2,))]
        data = engine.batch_eval(ctx, eps)
        result = MetricEvaluator(IdSumMetric()).evaluate_base(ctx, None, data)
        assert result.best_idx == 0

    def test_output_path_writes_variant(self, ctx, tmp_path):
        engine = make_engine()
        data = engine.batch_eval(ctx, [make_params(algo_ids=(4,))])
        out = tmp_path / "best.json"
        MetricEvaluator(IdSumMetric(), output_path=str(out)).evaluate_base(
            ctx, None, data
        )
        import json

        best = json.loads(out.read_text())
        assert best["algorithms"][0]["params"]["id"] == 4


class TestFastEvalMemoization:
    """FastEvalEngineTest.scala:30-146 — invocation-count asserts."""

    def fast_engine(self):
        return FastEvalEngine(
            {"": DataSource0}, {"": Preparator0}, {"": Algo0}, {"": Serving0}
        )

    def test_algo_sweep_reads_once(self, ctx):
        engine = self.fast_engine()
        eps = [make_params(algo_ids=(i,), n_eval_sets=1) for i in range(4)]
        results = engine.batch_eval(ctx, eps)
        assert len(results) == 4
        assert DataSource0.count == 1  # read once across the sweep
        assert Preparator0.count == 1  # prepared once
        assert Algo0.count == 4  # trained per algo params

    def test_ds_sweep_reads_per_params(self, ctx):
        engine = self.fast_engine()
        eps = [make_params(ds_id=i, n_eval_sets=1) for i in range(3)]
        engine.batch_eval(ctx, eps)
        assert DataSource0.count == 3
        assert Preparator0.count == 3

    def test_duplicate_params_fully_cached(self, ctx):
        engine = self.fast_engine()
        ep = make_params(n_eval_sets=1)
        engine.batch_eval(ctx, [ep, ep, ep])
        assert DataSource0.count == 1
        assert Algo0.count == 1
        assert Serving0.count == 2  # 1 fold x 2 queries, computed once

    def test_serving_sweep_caches_predictions(self, ctx):
        engine = self.fast_engine()
        base = make_params(n_eval_sets=1)
        eps = [
            base.copy(serving_params=("", IdParams(id=i))) for i in range(3)
        ]
        engine.batch_eval(ctx, eps)
        assert Algo0.count == 1  # predictions cached across serving sweep
        assert DataSource0.count == 1

    def test_non_value_eq_params_not_cached(self, ctx):
        """Params without value equality never hit the cache
        (FastEvalEngineTest.scala:146)."""

        class RawParams:  # not a dataclass: identity equality
            def __init__(self, id=0):
                self.id = id

        engine = self.fast_engine()
        eps = [
            make_params(n_eval_sets=1).copy(
                data_source_params=("", RawParams())
            )
            for _ in range(2)
        ]

        class RawDS(DataSource0):
            count = 0

            def __init__(self, params=None):
                self.params = params or DSParams()

            def read_eval(self, c):
                type(self).bump()
                from sample_engine import TrainingData, EvalInfo

                return [(TrainingData(id=1), EvalInfo(id=1), [(Query(0), Actual(0))])]

        eng = FastEvalEngine(
            {"": RawDS}, {"": Preparator0}, {"": Algo0}, {"": Serving0}
        )
        eng.batch_eval(ctx, eps)
        assert RawDS.count == 2  # two distinct instances, no cache hits


class TestEvaluationWiring:
    def test_evaluation_engine_metric(self, ctx):
        ev = Evaluation()
        ev.engine_metric = (make_engine(), IdSumMetric())
        engine, evaluator = ev.engine_evaluator
        assert isinstance(evaluator, MetricEvaluator)
        assert isinstance(evaluator.metric, IdSumMetric)

    def test_unset_evaluation_raises(self):
        with pytest.raises(ValueError):
            Evaluation().engine_evaluator

    def test_generator(self):
        g = EngineParamsGenerator()
        with pytest.raises(ValueError):
            g.engine_params_list
        g.engine_params_list = [make_params()]
        assert len(g.engine_params_list) == 1


# -- fields a Params class has retired ---------------------------------------
def _sequence_events(store, app_id):
    import datetime as dt

    from predictionio_tpu.storage import Event

    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    store.write([
        Event(event="view", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{(u + t) % 6}",
              event_time=t0 + dt.timedelta(minutes=t))
        for u in range(6) for t in range(12)
    ], app_id)


def _rating_events(store, app_id):
    from predictionio_tpu.storage import DataMap, Event

    store.write([
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": 5.0 if (u + i) % 2 == 0 else 1.0}))
        for u in range(8) for i in range(6) if (u * 7 + i) % 5
    ], app_id)


def _sequencerec_case():
    from predictionio_tpu.models import sequencerec as seq

    params = EngineParams(
        data_source_params=("", seq.SeqDataSourceParams(app_id=1)),
        preparator_params=("", seq.SeqPreparatorParams(seq_len=8)),
        algorithm_params_list=[("", seq.SeqRecAlgorithmParams(
            d_model=16, n_heads=2, n_layers=1, steps=4))],
    )
    return (seq.engine_factory(), params, _sequence_events,
            seq.Query(recent_items=("i0", "i1"), num=3))


def _recommendation_case():
    from predictionio_tpu.models import recommendation as rec

    params = EngineParams(
        data_source_params=("", rec.RecDataSourceParams(app_id=1)),
        algorithm_params_list=[("als", rec.ALSAlgorithmParams(
            rank=4, num_iterations=2, lambda_=0.05))],
    )
    return rec.engine_factory(), params, _rating_events, rec.Query(user="u0", num=3)


@pytest.mark.parametrize("case, field, stored_value", [
    (_sequencerec_case, "flash_impl", "xla"),
    (_recommendation_case, "fused_gather", None),
    (_recommendation_case, "sort_gather_indices", False),
])
def test_a_stored_instance_with_a_retired_field_deploys(
        case, field, stored_value, tmp_path, monkeypatch):
    """An engine instance trained before a Params class lost a field
    carries it (``params_to_json`` writes every field): the deploy path
    drops it and the instance serves; the same key in a user's
    ``engine.json`` is an unknown field like any other."""
    import json

    import predictionio_tpu.storage.registry as regmod
    from predictionio_tpu.storage import StorageRegistry
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.serving import ServerConfig, prepare_deployment

    registry = StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)})
    monkeypatch.setattr(regmod, "_default_registry", registry)
    engine, params, write_events, query = case()
    store = registry.get_events()
    store.init(1)
    write_events(store, 1)
    instance_id = run_train(engine, params, registry, engine_id="retired")
    md = registry.get_metadata()
    instance = md.engine_instance_get(instance_id)
    blocks = json.loads(instance.algorithms_params)
    assert field not in blocks[0]["params"]
    blocks[0]["params"][field] = stored_value
    md.engine_instance_update(
        dataclasses.replace(instance, algorithms_params=json.dumps(blocks)))

    deployment = prepare_deployment(
        engine, registry, ServerConfig(engine_instance_id=instance_id))
    assert deployment.engine_params == params
    answer = deployment.algorithms[0].predict(deployment.models[0], query)
    assert 0 < len(answer.item_scores) <= 3

    variant = {
        "datasource": {"params": {"app_id": 1}},
        "algorithms": [{"name": blocks[0]["name"], "params": {field: stored_value}}],
    }
    with pytest.raises(ParamsError, match=f"unknown fields.*{field}"):
        engine.json_to_engine_params(variant)
