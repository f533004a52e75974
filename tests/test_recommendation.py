"""End-to-end recommendation template test: events → train → persist →
deploy → predict (the "one model" milestone of SURVEY §7 step 5)."""

import numpy as np
import pytest

from predictionio_tpu.controller import EngineParams
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams,
    Query,
    RecDataSourceParams,
    engine_factory,
)
from predictionio_tpu.storage import DataMap, Event, StorageRegistry
from predictionio_tpu.workflow import load_models, run_train
from predictionio_tpu.workflow.context import WorkflowContext


@pytest.fixture()
def registry(tmp_path, monkeypatch):
    reg = StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)})
    # route the module-level get_registry() to this test's registry
    import predictionio_tpu.storage.registry as regmod

    monkeypatch.setattr(regmod, "_default_registry", reg)
    return reg


def ingest_ratings(reg, app_id=1, n_users=12, n_items=8, seed=0):
    """Two-cohort preference structure so recommendations are predictable:
    even users love even items, odd users love odd items."""
    rng = np.random.default_rng(seed)
    ev = reg.get_events()
    ev.init(app_id)
    events = []
    for u in range(n_users):
        for i in range(n_items):
            aligned = (u % 2) == (i % 2)
            if rng.random() < 0.8:
                rating = 5.0 if aligned else 1.0
                events.append(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap({"rating": rating}),
                    )
                )
    # a few buy events (implicit rating 4.0)
    events.append(
        Event(event="buy", entity_type="user", entity_id="u0",
              target_entity_type="item", target_entity_id="i2")
    )
    ev.write(events, app_id)
    return len(events)


def engine_params(rank=4, iters=6):
    return EngineParams(
        data_source_params=("", RecDataSourceParams(app_id=1)),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=rank, num_iterations=iters,
                                       lambda_=0.05))
        ],
    )


class TestEndToEnd:
    def test_train_persist_deploy_predict(self, registry):
        n = ingest_ratings(registry)
        assert n > 50
        engine = engine_factory()
        iid = run_train(
            engine, engine_params(), registry,
            engine_id="rec", engine_factory="predictionio_tpu.models.recommendation:engine_factory",
        )
        # deploy path: reload from blobs
        ctx = WorkflowContext("Serving")
        ep = engine.engine_instance_to_engine_params(
            registry.get_metadata().engine_instance_get(iid)
        )
        models = engine.prepare_deploy(ctx, ep, iid, load_models(registry, iid))
        algo = engine._algorithms(ep)[0]

        result = algo.predict(models[0], Query(user="u0", num=3))
        assert len(result.item_scores) == 3
        # even user should prefer even items
        top = result.item_scores[0].item
        assert int(top[1:]) % 2 == 0, f"u0 got odd item {top}"
        # scores descending
        scores = [s.score for s in result.item_scores]
        assert scores == sorted(scores, reverse=True)

    def test_unknown_user_empty_result(self, registry):
        ingest_ratings(registry)
        engine = engine_factory()
        ctx = WorkflowContext("Training")
        models = engine.train(ctx, engine_params())
        algo = engine._algorithms(engine_params())[0]
        result = algo.predict(models[0], Query(user="ghost", num=3))
        assert result.item_scores == ()

    def test_batch_predict_matches_single(self, registry):
        ingest_ratings(registry)
        engine = engine_factory()
        ctx = WorkflowContext("Training")
        models = engine.train(ctx, engine_params())
        algo = engine._algorithms(engine_params())[0]
        queries = [(i, Query(user=f"u{i}", num=2)) for i in range(4)]
        batch = dict(algo.batch_predict(models[0], queries))
        for i, q in queries:
            single = algo.predict(models[0], q)
            # same items; scores equal up to matmul tiling noise
            assert [s.item for s in batch[i].item_scores] == [
                s.item for s in single.item_scores
            ]
            np.testing.assert_allclose(
                [s.score for s in batch[i].item_scores],
                [s.score for s in single.item_scores],
                rtol=1e-5,
            )

    def test_json_query_roundtrip(self, registry):
        """Wire-format compatibility of the predicted result."""
        ingest_ratings(registry)
        engine = engine_factory()
        ctx = WorkflowContext("Training")
        models = engine.train(ctx, engine_params())
        algo = engine._algorithms(engine_params())[0]
        result = algo.predict(models[0], Query(user="u1", num=2))
        js = result.to_json_dict()
        assert set(js) == {"itemScores"}
        assert all(set(s) == {"item", "score"} for s in js["itemScores"])

    def test_eval_split(self, registry):
        ingest_ratings(registry)
        engine = engine_factory()
        ctx = WorkflowContext("Evaluation")
        results = engine.eval(ctx, engine_params())
        assert len(results) == 1
        _, qpa = results[0]
        assert len(qpa) > 5
        q, p, a = qpa[0]
        assert isinstance(q, Query)

    def test_eval_train_split_excludes_test_only_entities(self, registry):
        """A user whose every rating fell in the test split must be absent
        from the train-split maps, so predict() returns the unknown-user
        empty result instead of scoring a never-solved zero factor row."""
        ingest_ratings(registry)
        from predictionio_tpu.models.recommendation import RecDataSource

        ds = RecDataSource(RecDataSourceParams(app_id=1))
        [(train_td, _, qa)] = ds.read_eval(None)
        # maps contain exactly the train split's entities
        full = ds.read_training(None)
        test_mask = np.arange(len(full.users)) % 4 == 0
        u_inv = full.user_map.inverse
        train_users = {u_inv[int(u)] for u in full.users[~test_mask]}
        assert set(train_td.user_map) == train_users
        # indices are dense and consistent with the arrays
        assert train_td.users.max() == len(train_td.user_map) - 1
        assert train_td.items.max() == len(train_td.item_map) - 1

    def test_empty_events_fails_sanity(self, registry):
        registry.get_events().init(1)
        engine = engine_factory()
        ctx = WorkflowContext("Training")
        with pytest.raises(ValueError, match="No rating events"):
            engine.train(ctx, engine_params())


class TestStreamingTopKServing:
    """The streaming serving path must produce the same results as the
    dense path (forced via streaming_top_k="always"; on CPU the kernel
    runs in interpret mode)."""

    def test_streaming_matches_dense(self, registry):
        """One trained model served through both paths — streaming_top_k
        is serving-only, so the model is shared."""
        from predictionio_tpu.models.recommendation import ALSAlgorithm

        ingest_ratings(registry)
        engine = engine_factory()
        params = EngineParams(
            data_source_params=("", RecDataSourceParams(app_id=1)),
            algorithm_params_list=[
                ("als", ALSAlgorithmParams(rank=4, num_iterations=4,
                                           lambda_=0.05))
            ],
        )
        iid = run_train(engine, params, registry, engine_id="stream")
        model = load_models(registry, iid)[0]
        results = {}
        for mode in ("never", "always"):
            algo = ALSAlgorithm(
                ALSAlgorithmParams(rank=4, streaming_top_k=mode)
            )
            out = algo.batch_predict(
                model,
                [(0, Query(user="u0", num=4)), (1, Query(user="u3", num=4))],
            )
            results[mode] = {
                i: [s.item for s in r.item_scores] for i, r in out
            }
        assert results["never"] == results["always"]

    def test_bad_mode_fails_loudly_at_train_time(self, registry):
        ingest_ratings(registry)
        engine = engine_factory()
        params = EngineParams(
            data_source_params=("", RecDataSourceParams(app_id=1)),
            algorithm_params_list=[
                ("als", ALSAlgorithmParams(streaming_top_k="bogus"))
            ],
        )
        with pytest.raises(ValueError, match="streaming_top_k"):
            run_train(engine, params, registry, engine_id="bad-mode")


class TestGatherLeverParams:
    """The training levers (solve_mode, gather_dtype) must be reachable
    from engine.json via ALSAlgorithmParams and reproduce the default
    path's factors."""

    @pytest.mark.slow  # ~90 s: three full trainings; outside tier-1 budget
    def test_levers_reproduce_default_model(self, registry):
        ingest_ratings(registry)
        engine = engine_factory()

        def params(**kw):
            return EngineParams(
                data_source_params=("", RecDataSourceParams(app_id=1)),
                algorithm_params_list=[
                    ("als", ALSAlgorithmParams(
                        rank=4, num_iterations=4, lambda_=0.05, **kw
                    ))
                ],
            )

        base = run_train(engine, params(), registry, engine_id="lv0")
        levered = run_train(
            engine,
            params(solve_mode="pallas"),
            registry, engine_id="lv1",
        )
        m0 = load_models(registry, base)[0]
        m1 = load_models(registry, levered)[0]
        np.testing.assert_allclose(
            m0.user_factors, m1.user_factors, rtol=5e-3, atol=5e-4
        )
        np.testing.assert_allclose(
            m0.item_factors, m1.item_factors, rtol=5e-3, atol=5e-4
        )
