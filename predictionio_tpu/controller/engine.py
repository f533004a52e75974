"""Engine: chains DASE components, orchestrates train/eval/deploy.

Rebuild of ``core/src/main/scala/io/prediction/controller/Engine.scala``:
component class maps keyed by name, ``EngineParams`` naming one variant of
each stage, static train (``Engine.scala:499-586``) and eval
(``Engine.scala:588-672``) dataflows, deploy-time model preparation
(``prepareDeploy``, ``Engine.scala:168-237``) and engine-variant JSON parsing
(``jValueToEngineParams``, ``Engine.scala:313-370``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
from collections import defaultdict
from typing import (
    Any,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
    Type,
    Union,
)

from .dase import (
    RETRAIN,
    Algorithm,
    DataSource,
    FirstServing,
    IdentityPreparator,
    PersistentModelManifest,
    Preparator,
    Serving,
    doer,
    run_sanity_check,
)
from .params import EmptyParams, Params, ParamsError, extract_params, params_to_json

logger = logging.getLogger(__name__)


def _null_phase(name: str):
    return contextlib.nullcontext()

ClassMap = Dict[str, Type]


def _as_class_map(spec: Union[Type, Mapping[str, Type]]) -> ClassMap:
    if isinstance(spec, Mapping):
        return dict(spec)
    return {"": spec}


@dataclasses.dataclass(frozen=True)
class WorkflowParams:
    """Per-run workflow knobs (``workflow/WorkflowParams.scala``; surfaced as
    CLI flags in ``CreateWorkflow.scala:87-140``)."""

    batch: str = ""
    verbose: int = 0
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    #: hyperparameter-sweep parallelism: 0 = auto (one slice per candidate
    #: up to the mesh data-axis size), 1 = serial, N = N mesh slices
    eval_parallelism: int = 0
    #: per-run checkpoint cadence override (``pio train
    #: --checkpoint-every``; docs/checkpoint.md): None defers to the
    #: engine params / ``PIO_CKPT_EVERY`` tri-state
    checkpoint_every: Optional[int] = None


class StopAfterReadInterruption(Exception):
    """``--stop-after-read`` (``Engine.scala:530-536``)."""


class StopAfterPrepareInterruption(Exception):
    """``--stop-after-prepare`` (``Engine.scala:548-554``)."""


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Named (component-name, Params) bindings for one engine variant
    (``controller/EngineParams.scala:56-144``)."""

    data_source_params: Tuple[str, Params] = ("", EmptyParams())
    preparator_params: Tuple[str, Params] = ("", EmptyParams())
    algorithm_params_list: Sequence[Tuple[str, Params]] = (("", EmptyParams()),)
    serving_params: Tuple[str, Params] = ("", EmptyParams())

    def __post_init__(self):
        object.__setattr__(
            self, "algorithm_params_list", tuple(self.algorithm_params_list)
        )

    def copy(self, **updates) -> "EngineParams":
        return dataclasses.replace(self, **updates)


class Engine:
    """The DASE engine (``Engine.scala:81-128``)."""

    def __init__(
        self,
        data_source_class_map: Union[Type, Mapping[str, Type]],
        preparator_class_map: Union[Type, Mapping[str, Type]],
        algorithm_class_map: Union[Type, Mapping[str, Type]],
        serving_class_map: Union[Type, Mapping[str, Type]],
    ):
        self.data_source_class_map = _as_class_map(data_source_class_map)
        self.preparator_class_map = _as_class_map(preparator_class_map)
        self.algorithm_class_map = _as_class_map(algorithm_class_map)
        self.serving_class_map = _as_class_map(serving_class_map)

    # -- component instantiation (Engine.scala:136-145) -------------------
    def _data_source(self, ep: EngineParams) -> DataSource:
        name, params = ep.data_source_params
        if name not in self.data_source_class_map:
            raise KeyError(f"Unknown datasource name {name!r}")
        return doer(self.data_source_class_map[name], params)

    def _preparator(self, ep: EngineParams) -> Preparator:
        name, params = ep.preparator_params
        if name not in self.preparator_class_map:
            raise KeyError(f"Unknown preparator name {name!r}")
        return doer(self.preparator_class_map[name], params)

    def _algorithms(self, ep: EngineParams) -> List[Algorithm]:
        algos = []
        for name, params in ep.algorithm_params_list:
            if name not in self.algorithm_class_map:
                raise KeyError(f"Unknown algorithm name {name!r}")
            algos.append(doer(self.algorithm_class_map[name], params))
        return algos

    def _serving(self, ep: EngineParams) -> Serving:
        name, params = ep.serving_params
        if name not in self.serving_class_map:
            raise KeyError(f"Unknown serving name {name!r}")
        return doer(self.serving_class_map[name], params)

    # -- train (Engine.train instance :130-166 + static :499-586) ---------
    def train(
        self,
        ctx,
        engine_params: EngineParams,
        workflow_params: WorkflowParams = WorkflowParams(),
    ) -> List[Any]:
        """Run read → sanity → prepare → sanity → train(each algo) → sanity;
        returns one trained model per algorithm."""
        data_source = self._data_source(engine_params)
        preparator = self._preparator(engine_params)
        algorithms = self._algorithms(engine_params)
        timer = getattr(ctx, "timer", None)
        timed = timer.time if timer is not None else _null_phase

        try:
            with timed("read"):
                training_data = data_source.read_training(ctx)
        except Exception as exc:
            # Engine.scala:517-524 wraps read errors with a storage hint.
            raise RuntimeError(
                "Data is incomplete or data source reported an error. "
                f"(reading training data failed: {exc})"
            ) from exc
        if not workflow_params.skip_sanity_check:
            run_sanity_check(training_data, "training data")
        if workflow_params.stop_after_read:
            raise StopAfterReadInterruption()

        with timed("prepare"):
            prepared_data = preparator.prepare(ctx, training_data)
        if not workflow_params.skip_sanity_check:
            run_sanity_check(prepared_data, "prepared data")
        if workflow_params.stop_after_prepare:
            raise StopAfterPrepareInterruption()

        models = []
        for i, algo in enumerate(algorithms):
            if ctx is not None:
                # lets algorithms namespace per-run resources (checkpoints)
                ctx.algorithm_index = i
            with timed(f"train[{i}]"):
                model = algo.train(ctx, prepared_data)
            if not workflow_params.skip_sanity_check:
                run_sanity_check(model, "model")
            models.append(model)
        return models

    # -- persistence (Engine.makeSerializableModels :254-272) -------------
    def make_serializable_models(
        self, ctx, engine_params: EngineParams, instance_id: str, models: Sequence[Any]
    ) -> List[Any]:
        """Per algorithm: PersistentModelManifest | RETRAIN | blobbable model."""
        algorithms = self._algorithms(engine_params)
        return [
            algo.make_persistent(instance_id, model, ctx)
            for algo, model in zip(algorithms, models)
        ]

    # -- deploy (Engine.prepareDeploy :168-237) ----------------------------
    def prepare_deploy(
        self,
        ctx,
        engine_params: EngineParams,
        instance_id: str,
        persisted_models: Sequence[Any],
    ) -> List[Any]:
        """Turn persisted models back into live ones: load self-persisted
        models, retrain RETRAIN entries (``Engine.scala:180-198``), pass
        blobbed models through."""
        algorithms = self._algorithms(engine_params)
        needs_retrain = any(m is RETRAIN for m in persisted_models)
        retrained: Optional[List[Any]] = None
        if needs_retrain:
            logger.info(
                "Some persisted models require retraining at deploy "
                "(reference behavior for non-persistable models)"
            )
            retrained = self.train(ctx, engine_params)
        live = []
        for i, (algo, pm) in enumerate(zip(algorithms, persisted_models)):
            if isinstance(pm, PersistentModelManifest):
                cls = pm.resolve()
                live.append(cls.load(instance_id, algo.params, ctx))
            elif pm is RETRAIN:
                assert retrained is not None
                live.append(retrained[i])
            else:
                live.append(pm)
        return live

    # -- eval (Engine.eval static :588-672) --------------------------------
    def eval(
        self,
        ctx,
        engine_params: EngineParams,
        workflow_params: WorkflowParams = WorkflowParams(),
    ) -> List[Tuple[Any, List[Tuple[Any, Any, Any]]]]:
        """Per eval fold: train on the split, batch-predict all algorithms,
        combine per query through serving → (eval info, [(q, p, a)])."""
        data_source = self._data_source(engine_params)
        preparator = self._preparator(engine_params)
        algorithms = self._algorithms(engine_params)
        serving = self._serving(engine_params)

        eval_sets = data_source.read_eval(ctx)
        results = []
        for training_data, eval_info, qa_pairs in eval_sets:
            prepared_data = preparator.prepare(ctx, training_data)
            models = [algo.train(ctx, prepared_data) for algo in algorithms]

            # Note: serving.supplement is a serve-time hook (query server
            # path) and is intentionally not applied during evaluation,
            # matching the reference's eval dataflow and keeping
            # FastEvalEngine's prediction caches equivalent to this path.
            indexed = list(enumerate(q for q, _ in qa_pairs))
            # Union of per-algo batch predictions grouped by query index
            # (Engine.scala:636-660).
            by_query: Dict[int, Dict[int, Any]] = defaultdict(dict)
            for ai, (algo, model) in enumerate(zip(algorithms, models)):
                for qi, p in algo.batch_predict(model, indexed):
                    by_query[qi][ai] = p
            qpa = []
            for qi, (q, a) in enumerate(qa_pairs):
                preds = by_query.get(qi, {})
                ordered = [preds[ai] for ai in sorted(preds)]
                p = serving.serve(q, ordered)
                qpa.append((q, p, a))
            results.append((eval_info, qpa))
        return results

    def batch_eval(
        self,
        ctx,
        engine_params_list: Sequence[EngineParams],
        workflow_params: WorkflowParams = WorkflowParams(),
        parallelism: int = 1,
    ) -> List[Tuple[EngineParams, List[Tuple[Any, List[Tuple[Any, Any, Any]]]]]]:
        """Evaluate every EngineParams (``BaseEngine.batchEval``,
        ``core/BaseEngine.scala:47-55``); FastEvalEngine overrides with
        prefix memoization.

        ``parallelism > 1`` runs candidates concurrently on independent
        mesh slices (``WorkflowContext.slices``; SURVEY §2.8 row 5 — the
        TPU-native form of the reference's ``.par`` sweep): each
        candidate's training dispatches onto a disjoint device subset, so
        an 8-device mesh evaluates a 4-way grid as 4 concurrent 2-device
        trainings."""
        if parallelism > 1 and len(engine_params_list) > 1:
            from ..parallel.sweep import run_sliced

            tasks = [
                (lambda sliced, ep=ep: self.eval(sliced, ep, workflow_params))
                for ep in engine_params_list
            ]
            results = run_sliced(ctx, tasks, parallelism)
            return list(zip(engine_params_list, results))
        return [
            (ep, self.eval(ctx, ep, workflow_params))
            for ep in engine_params_list
        ]

    # -- engine.json parsing (Engine.scala:313-370) ------------------------
    def json_to_engine_params(self, variant: Mapping[str, Any]) -> EngineParams:
        """Parse an engine-variant JSON object into typed EngineParams."""
        ds = _named_params(variant, "datasource", self.data_source_class_map)
        prep = _named_params(variant, "preparator", self.preparator_class_map)
        serv = _named_params(variant, "serving", self.serving_class_map)

        algorithms = variant.get("algorithms")
        if algorithms is None:
            algo_list: List[Tuple[str, Params]] = [
                ("", _default_params(self.algorithm_class_map, ""))
            ]
        else:
            algo_list = []
            for block in algorithms:
                name = block.get("name", "")
                if name not in self.algorithm_class_map:
                    raise ParamsError(
                        f"Unable to find algorithm class with name {name!r} "
                        "defined in Engine."
                    )
                cls = self.algorithm_class_map[name]
                params_cls = _component_params_class(cls)
                algo_list.append(
                    (name, extract_params(params_cls, block.get("params")))
                )
        return EngineParams(
            data_source_params=ds,
            preparator_params=prep,
            algorithm_params_list=algo_list,
            serving_params=serv,
        )

    def engine_instance_to_engine_params(self, instance) -> EngineParams:
        """Rebuild EngineParams from a stored EngineInstance row
        (``Engine.scala:372-425``) — the deploy path's parameter source."""
        def stored(params_cls, raw) -> Params:
            """A stored block's params, less the fields its class has
            retired since the instance was trained."""
            retired = set(raw or ()) & set(getattr(params_cls, "retired_fields", ()))
            if retired:
                logger.info(
                    "engine instance %s: dropping retired %s fields %s",
                    instance.id, params_cls.__name__, sorted(retired),
                )
                raw = {k: v for k, v in raw.items() if k not in retired}
            return extract_params(params_cls, raw)

        def parse(text: str, class_map: ClassMap, stage: str) -> Tuple[str, Params]:
            if not text:
                return ("", _default_params(class_map, ""))
            obj = json.loads(text)
            name = obj.get("name", "")
            if name not in class_map:
                raise ParamsError(
                    f"Unable to find {stage} class with name {name!r} defined "
                    "in Engine (stored engine instance refers to a renamed or "
                    "removed component)."
                )
            cls = class_map[name]
            return (name, stored(_component_params_class(cls), obj.get("params")))

        algo_list: List[Tuple[str, Params]] = []
        if instance.algorithms_params:
            for block in json.loads(instance.algorithms_params):
                name = block.get("name", "")
                if name not in self.algorithm_class_map:
                    raise ParamsError(
                        f"Unable to find algorithm class with name {name!r} "
                        "defined in Engine (stored engine instance refers to "
                        "a renamed or removed component)."
                    )
                cls = self.algorithm_class_map[name]
                algo_list.append(
                    (name, stored(_component_params_class(cls), block.get("params")))
                )
        else:
            algo_list = [("", _default_params(self.algorithm_class_map, ""))]
        return EngineParams(
            data_source_params=parse(
                instance.data_source_params, self.data_source_class_map, "datasource"
            ),
            preparator_params=parse(
                instance.preparator_params, self.preparator_class_map, "preparator"
            ),
            algorithm_params_list=algo_list,
            serving_params=parse(
                instance.serving_params, self.serving_class_map, "serving"
            ),
        )


def serialize_engine_params(ep: EngineParams) -> Dict[str, str]:
    """EngineParams → the four JSON-text columns of an EngineInstance row
    (``CreateWorkflow.scala:245-253``)."""
    def enc(pair: Tuple[str, Params]) -> str:
        return json.dumps({"name": pair[0], "params": params_to_json(pair[1])})

    return {
        "data_source_params": enc(ep.data_source_params),
        "preparator_params": enc(ep.preparator_params),
        "algorithms_params": json.dumps(
            [
                {"name": name, "params": params_to_json(params)}
                for name, params in ep.algorithm_params_list
            ]
        ),
        "serving_params": enc(ep.serving_params),
    }


def _component_params_class(component_cls: Type) -> Type:
    """Find a component's Params dataclass.

    Replacement for ctor-signature reflection: the component declares
    ``params_class`` or defaults to EmptyParams.
    """
    return getattr(component_cls, "params_class", EmptyParams)


def _named_params(
    variant: Mapping[str, Any], field: str, class_map: ClassMap
) -> Tuple[str, Params]:
    """``WorkflowUtils.getParamsFromJsonByFieldAndClass``
    (``WorkflowUtils.scala:169-209``)."""
    block = variant.get(field)
    if block is None:
        return ("", _default_params(class_map, ""))
    name = block.get("name", "")
    if name not in class_map:
        raise ParamsError(
            f"Unable to find {field} class with name {name!r} defined in Engine."
        )
    params_json = block.get("params")
    if params_json is None:
        return (name, _default_params(class_map, name))
    cls = class_map[name]
    return (name, extract_params(_component_params_class(cls), params_json))


def _default_params(class_map: ClassMap, name: str) -> Params:
    """An absent params block means "the component's declared defaults", not
    EmptyParams — otherwise a component whose ``params_class`` has required
    behavior (e.g. SeqPreparator's seq_len) breaks when the variant omits
    the block."""
    cls = class_map.get(name)
    if cls is None:
        return EmptyParams()
    params_cls = _component_params_class(cls)
    try:
        return params_cls()
    except TypeError:  # params class with required fields: caller must supply
        return EmptyParams()


class SimpleEngine(Engine):
    """Single DataSource + identity preparator + single algorithm + first
    serving (``Engine.scala:677-696``)."""

    def __init__(self, data_source_class: Type, algorithm_class: Type):
        super().__init__(
            data_source_class,
            IdentityPreparator,
            algorithm_class,
            FirstServing,
        )
