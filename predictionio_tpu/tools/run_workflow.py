"""Train/eval driver process — the ``CreateWorkflow`` analogue.

Rebuild of ``core/src/main/scala/io/prediction/workflow/CreateWorkflow.scala``:
the ``main`` of every ``pio train`` / ``pio eval``.  The reference is spawned
via spark-submit (``RunWorkflow.scala:103-169``); here the console either
invokes :func:`run` in-process or spawns
``python -m predictionio_tpu.tools.run_workflow`` to preserve the process
boundary (CLI process ↔ training driver process) with the same
metadata-store handshake.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import Optional, Sequence

from ..controller.engine import WorkflowParams
from ..storage import StorageRegistry, get_registry
from ..workflow import loader
from ..workflow.core_workflow import run_evaluation, run_train
from .register import load_engine_dir

logger = logging.getLogger(__name__)


def build_parser() -> argparse.ArgumentParser:
    """Flag grammar (``CreateWorkflow.scala:87-140``)."""
    p = argparse.ArgumentParser(prog="run_workflow")
    p.add_argument("--engine-dir", default=".", help="engine project directory")
    p.add_argument("--engine-id", default=None)
    p.add_argument("--engine-version", default=None)
    p.add_argument("--engine-variant", default="engine.json")
    p.add_argument("--engine-factory", default=None)
    p.add_argument("--engine-params-key", default=None)
    p.add_argument("--evaluation-class", default=None)
    p.add_argument("--engine-params-generator-class", default=None)
    p.add_argument("--batch", default="")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--skip-sanity-check", action="store_true")
    p.add_argument("--stop-after-read", action="store_true")
    p.add_argument("--stop-after-prepare", action="store_true")
    p.add_argument("--verbosity", type=int, default=0)
    p.add_argument(
        "--eval-parallelism", type=int, default=0,
        help="sweep parallelism over mesh slices (0 = auto, 1 = serial)",
    )
    p.add_argument(
        "--shards", type=int, default=None, metavar="N",
        help="train with both factor tables sharded over N devices "
             "(ALX-style shard_map trainer, docs/distributed_training.md); "
             "sets PIO_TRAIN_SHARDS, which the algorithm's `shards` "
             "tri-state resolves from — an explicit engine.json value "
             "still wins",
    )
    p.add_argument(
        "--checkpoint-every", type=int, default=None, metavar="N",
        help="checkpoint factor tables every N iterations "
             "(docs/checkpoint.md); the run's override in the "
             "checkpoint_every tri-state — an explicit engine.json "
             "value still wins, PIO_CKPT_EVERY is the fleet default",
    )
    p.add_argument(
        "--resume", default=None, action=argparse.BooleanOptionalAction,
        help="resume from the newest valid checkpoint (default; a "
             "mismatched recipe refuses loudly). --no-resume clears "
             "existing checkpoints and trains fresh. Env default: "
             "PIO_CKPT_RESUME",
    )
    return p


def run(
    args: argparse.Namespace, registry: Optional[StorageRegistry] = None
) -> str:
    """Execute one train or eval run; returns the instance id
    (``CreateWorkflow.main``, ``CreateWorkflow.scala:142-279``)."""
    loader.modify_logging(args.verbose)
    fn = lambda: _run_inner(args, registry)  # noqa: E731
    if getattr(args, "resume", None) is not None:
        # env-driven like --shards below, so --spawn and in-process runs
        # behave identically; scoped to this run
        from ..ckpt import RESUME_ENV

        fn = (lambda inner: lambda: _with_env(
            RESUME_ENV, "1" if args.resume else "0", inner
        ))(fn)
    if getattr(args, "shards", None) is not None:
        # an explicit 0 must reach resolve_shards and fail loudly there
        # — a falsy check would silently train single-device
        # the tri-state env the algorithm's `shards=None` resolves from
        # (ops.als_sharded.resolve_shards) — env-driven like every other
        # config tier, so --spawn and in-process runs behave identically.
        # Scoped to this run: an in-process console must not leak the
        # flag into a later train in the same process.
        from ..ops.als_sharded import SHARDS_ENV

        fn = (lambda inner: lambda: _with_env(
            SHARDS_ENV, str(args.shards), inner
        ))(fn)
    return fn()


def _with_env(key: str, value: str, fn):
    prior = os.environ.get(key)
    os.environ[key] = value
    try:
        return fn()
    finally:
        if prior is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prior


def _run_inner(
    args: argparse.Namespace, registry: Optional[StorageRegistry] = None
) -> str:
    registry = registry or get_registry()
    wp = WorkflowParams(
        batch=args.batch,
        verbose=args.verbosity,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
        eval_parallelism=args.eval_parallelism,
        checkpoint_every=getattr(args, "checkpoint_every", None),
    )

    # runtimeConf binds to every workflow run, train AND eval — the
    # reference applies embedded sparkConf to all SparkContext creations
    # (WorkflowUtils.scala:321-339). Eval runs may lack an engine.json
    # (evaluation classes can carry their own engines): absent = no-op,
    # but a PRESENT-yet-broken engine dir must not silently drop config.
    from .register import ENGINE_JSON

    ed = None
    if args.evaluation_class and not os.path.exists(
        os.path.join(args.engine_dir, ENGINE_JSON)
    ):
        pass  # eval without an engine.json: nothing to apply
    else:
        ed = load_engine_dir(args.engine_dir)
        loader.apply_runtime_conf(ed.variant)

    if args.evaluation_class:
        # Eval path (``CreateWorkflow.scala:180-199,264-277``).
        evaluation = loader.get_evaluation(args.evaluation_class, args.engine_dir)
        if args.engine_params_generator_class:
            generator = loader.get_engine_params_generator(
                args.engine_params_generator_class, args.engine_dir
            )
        else:
            # An Evaluation may itself carry the params list
            # (``Evaluation.scala:59-124`` couples engine+params).
            from ..controller.evaluation import EngineParamsGenerator

            generator = EngineParamsGenerator(
                [evaluation.engine.default_engine_params()]
                if hasattr(evaluation.engine, "default_engine_params")
                else []
            )
        return run_evaluation(evaluation, generator, registry, workflow_params=wp)

    # Train path (``CreateWorkflow.scala:219-263``). ``ed`` was loaded
    # above (train always has an engine dir).
    factory = args.engine_factory or ed.engine_factory
    engine = loader.get_engine(factory, search_dir=ed.path)
    if args.engine_params_key:
        # Programmatic params: factory object exposes engine_params(key)
        # (``CreateWorkflow.scala:227-231``).
        factory_obj = loader.load_object(factory, ed.path)
        engine_params = factory_obj.engine_params(args.engine_params_key)
    else:
        engine_params = engine.json_to_engine_params(ed.variant)
    return run_train(
        engine,
        engine_params,
        registry,
        engine_id=args.engine_id or ed.manifest.id,
        engine_version=args.engine_version or ed.manifest.version,
        engine_variant=args.engine_variant,
        engine_factory=factory,
        workflow_params=wp,
    )


#: The ALSConfig fields an algorithm's params may carry under the same
#: name — what :func:`result_line` needs to resolve the levers.
_ALS_LEVER_FIELDS = ("rank", "solve_mode", "gather_dtype")


def resolved_levers(
    registry: StorageRegistry, instance_id: str, shards: Optional[int] = None
) -> dict:
    """``{algorithm name: resolved ALS levers}`` for a trained instance:
    ``ALSConfig.resolve_levers()`` (or, at more than one shard, the
    sharded trainer's resolution) over the params the instance stored,
    on the backend this process runs on. Empty for an evaluation
    instance and for algorithms that carry no ALS levers."""
    from ..ops.als import ALSConfig
    from ..ops.als_sharded import resolve_sharded_levers, resolve_shards

    instance = registry.get_metadata().engine_instance_get(instance_id)
    if instance is None:
        return {}
    out = {}
    for algo in json.loads(instance.algorithms_params or "[]"):
        params = algo.get("params", {})
        if "solve_mode" not in params:
            continue
        cfg = ALSConfig(
            **{k: params[k] for k in _ALS_LEVER_FIELDS if k in params}
        )
        n = resolve_shards(
            params.get("shards") if params.get("shards") is not None
            else shards
        )
        levers = (
            resolve_sharded_levers(cfg) if n > 1 else cfg.resolve_levers()
        )
        out[algo.get("name", "")] = dict(levers, shards=n)
    return out


def result_line(
    instance_id: str,
    registry: Optional[StorageRegistry] = None,
    shards: Optional[int] = None,
) -> dict:
    """What a finished run reports on stdout: the instance id, the device
    JAX gave this process, the ALS levers as they resolve on it, and the
    compile cache's directory and hit/miss counts. Reading, not
    choosing: a run that came up on the wrong backend says so here."""
    from ..obs.profile import default_telemetry
    from ..utils.jax_cache import compilation_cache_dir
    from ..utils.platform import device_info

    cache = default_telemetry().snapshot()["cache"]
    return {
        "engineInstanceId": instance_id,
        "device": device_info(),
        "levers": resolved_levers(
            registry or get_registry(), instance_id, shards
        ),
        "compileCache": {
            "dir": compilation_cache_dir(),
            "hits": cache["hits"],
            "misses": cache["misses"],
        },
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    # Tests pin the CPU backend through the environment; make that pin
    # this process's jax config before any backend init (the spark-submit
    # env-propagation analogue, RunWorkflow.scala:37-40).
    from ..utils.jax_cache import enable_compilation_cache
    from ..utils.platform import apply_env_platform

    apply_env_platform()
    enable_compilation_cache()
    args = build_parser().parse_args(argv)
    instance_id = run(args)
    print(json.dumps(result_line(instance_id, shards=args.shards)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
