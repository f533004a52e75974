"""Recommendation engine template (ALS).

Rebuild of the reference's quickstart template
``examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/``:
``DataSource.scala:25-55`` reads "rate"/"buy" events from the event store,
``ALSAlgorithm.scala:27-70`` trains MLlib ALS over BiMap-translated indices,
``ALSAlgorithm.scala:72-86`` predicts via ``recommendProducts``. Here the
train step is the TPU ALS kernel (:mod:`predictionio_tpu.ops.als`) and
predict is the batched gather-dot top-k kernel
(:mod:`predictionio_tpu.ops.scoring`).
"""

from __future__ import annotations

import dataclasses
import logging
import weakref
from typing import List, Optional, Sequence, Tuple

import numpy as np

_logger = logging.getLogger(__name__)

from ..controller import (
    Algorithm,
    DataSource,
    Engine,
    EngineParams,
    Evaluation,
    EngineParamsGenerator,
    FirstServing,
    OptionAverageMetric,
    Params,
    Preparator,
)
from ..obs.trace import span
from ..ops.als import ALSConfig, als_train_coo
from ..ops.scoring import (
    pad_pow2,
    resolve_topk_path,
    top_k_for_users_fused,
    use_streaming_topk,
)
from ..storage import BiMap, get_registry
from ..workflow.infeed import stream_ratings


# -- queries / results (template's Query.scala / PredictedResult) -----------
@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: Tuple[ItemScore, ...]

    def to_json_dict(self) -> dict:
        from .wire import item_scores_json

        return item_scores_json(self.item_scores)


# -- training data ----------------------------------------------------------
@dataclasses.dataclass
class TrainingData:
    """Streamed, pre-indexed ratings.

    The reference's TrainingData carries ``RDD[Rating]`` with *string* ids,
    translated later by the preparator (``DataSource.scala:25-55``). Here
    translation happens during the streaming read (12 bytes retained per
    rating instead of three Python strings), so TrainingData already holds
    dense indices plus the BiMaps to decode them — the host-memory contract
    of SURVEY §7 ("no triple materialization").
    """

    users: np.ndarray  # int32 [nnz]
    items: np.ndarray  # int32 [nnz]
    ratings: np.ndarray  # float32 [nnz]
    user_map: BiMap
    item_map: BiMap

    def sanity_check(self):
        if len(self.users) == 0:
            raise ValueError(
                "No rating events found; check app id and event names."
            )


@dataclasses.dataclass
class PreparedData:
    user_map: BiMap
    item_map: BiMap
    users: np.ndarray  # int32 [nnz]
    items: np.ndarray  # int32 [nnz]
    ratings: np.ndarray  # float32 [nnz]


# -- DASE components --------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RecDataSourceParams(Params):
    app_id: int = 1
    event_names: Tuple[str, ...] = ("rate", "buy")
    buy_rating: float = 4.0  # implicit "buy" mapped to a rating, as in the
    # template's DataSource ("buy" treated as rate 4)


class RecDataSource(DataSource):
    """Reads rate/buy events via the columnar scan fast path
    (reference ``DataSource.scala:25-55`` via ``Storage.getPEvents().find``)."""

    params_class = RecDataSourceParams

    def __init__(self, params: RecDataSourceParams = RecDataSourceParams()):
        self.params = params

    def _value_rules(self) -> dict:
        """Per-event value rule (the template's rate/buy pattern-match):
        'rate' reads the required 'rating' property, 'buy' maps to a fixed
        implicit rating. Unsupported names fail in stream_ratings' rule
        lookup rather than pattern-match crash."""
        rules: dict = {}
        for name in self.params.event_names:
            if name == "rate":
                rules[name] = "rating"
            elif name == "buy":
                rules[name] = self.params.buy_rating
            else:
                raise ValueError(
                    f"Unsupported event {name!r} in recommendation "
                    "DataSource (supported: 'rate', 'buy')"
                )
        return rules

    def read_training(self, ctx) -> TrainingData:
        store = get_registry().get_events()
        batch = stream_ratings(
            store, self.params.app_id, self._value_rules()
        )
        return TrainingData(
            users=batch.users,
            items=batch.items,
            ratings=batch.ratings,
            user_map=batch.user_map,
            item_map=batch.item_map,
        )

    def read_eval(self, ctx):
        """K-fold by event index parity — mirrors the evaluation example's
        random splits but deterministic."""
        td = self.read_training(ctx)
        n = len(td.users)
        idx = np.arange(n)
        test = idx % 4 == 0
        u_inv, i_inv = td.user_map.inverse, td.item_map.inverse
        # Rebuild maps from the TRAIN split only: a user/item whose every
        # rating landed in the test split must be absent from the model's
        # maps so predict() takes the unknown-user path (empty result)
        # instead of scoring its never-solved zero factor row.
        tr_users, tr_items = td.users[~test], td.items[~test]
        uniq_u = np.unique(tr_users)
        uniq_i = np.unique(tr_items)
        u_remap = np.full(len(td.user_map), -1, dtype=np.int32)
        u_remap[uniq_u] = np.arange(len(uniq_u), dtype=np.int32)
        i_remap = np.full(len(td.item_map), -1, dtype=np.int32)
        i_remap[uniq_i] = np.arange(len(uniq_i), dtype=np.int32)
        train_td = TrainingData(
            users=u_remap[tr_users],
            items=i_remap[tr_items],
            ratings=td.ratings[~test],
            user_map=BiMap(
                {u_inv[int(old)]: new for new, old in enumerate(uniq_u)}
            ),
            item_map=BiMap(
                {i_inv[int(old)]: new for new, old in enumerate(uniq_i)}
            ),
        )
        qa = [
            (Query(user=u_inv[int(td.users[i])], num=10),
             ItemScore(item=i_inv[int(td.items[i])],
                       score=float(td.ratings[i])))
            for i in idx[test]
        ]
        return [(train_td, None, qa)]


class RecPreparator(Preparator):
    """Hands the streamed, pre-indexed ratings to the algorithm (reference
    custom-preparator variant, ``BiMap.stringInt`` usage — the string→index
    translation it performed now happens inside the streaming read, so
    preparation is a re-shape, not a copy)."""

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(
            user_map=td.user_map,
            item_map=td.item_map,
            users=td.users,
            items=td.items,
            ratings=td.ratings,
        )


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    seed: int = 3
    implicit_prefs: bool = False
    alpha: float = 1.0
    #: Shard the training run over the workflow context's device mesh
    #: (solve rows on the ``data`` axis); "replicated" or "model" controls
    #: the factor-table layout (see :func:`ops.als.als_train`).
    distributed: bool = False
    factor_sharding: str = "replicated"
    #: Train with BOTH factor tables sharded over N devices via the
    #: ALX-style shard_map trainer (ops.als_sharded.als_train_sharded,
    #: docs/distributed_training.md). Tri-state per the PR-12 lever
    #: discipline: an explicit N wins, None resolves from
    #: ``PIO_TRAIN_SHARDS`` (what ``pio train --shards N`` sets), else 1 —
    #: the single-device trainer, byte-identical config resolution to
    #: today's path. Mutually exclusive with ``distributed`` (the
    #: pjit-annotation path) — conflicts fail loudly at train time,
    #: never silently pick one.
    shards: Optional[int] = None
    #: checkpoint factor tables every N iterations; a rerun of the same
    #: workflow resumes from the newest valid step. Tri-state
    #: (ckpt.resolve_every): explicit N (0 = explicitly off) wins, None
    #: resolves from the workflow run (``pio train --checkpoint-every``),
    #: else ``PIO_CKPT_EVERY``, else off. With ``shards > 1`` the
    #: sharded trainer snapshots canonical row order, so the resume
    #: shard count is free to differ (docs/checkpoint.md).
    checkpoint_every: Optional[int] = None
    #: "auto" | "chunked" | "pallas" — see
    #: ops.als.ALSConfig.solve_mode ("auto" picks the fused pallas
    #: Cholesky kernel on a single-chip TPU run, "chunked" elsewhere)
    solve_mode: str = "auto"
    #: "f32" | "bf16" — gathered-factor precision for the normal-equation
    #: einsums (see ops.als.ALSConfig.gather_dtype; the bench's RMSE gate
    #: — docs/performance.md#levers — bounds the drift before adopting
    #: bf16)
    gather_dtype: str = "f32"
    #: Serving top-k path: "auto" (default) streams item blocks through
    #: the fused Pallas score+select kernel — never materializing the
    #: [batch, n_items] score matrix in HBM — when on TPU and that
    #: matrix would exceed 64 MB (ops.scoring.STREAMING_TOPK_BYTES);
    #: "always"/"never" force the choice. Serving dispatches through
    #: ops.scoring.top_k_for_users_fused (XLA lax.top_k fallback
    #: off-TPU) and /status.json reports the resolved path (topkPath).
    streaming_top_k: str = "auto"
    #: Serve top-k from an int8-quantized item table (per-row scales,
    #: docs/quantization.md) — ~4x less serving memory and item-table
    #: read traffic. Tri-state per the PR-12 lever discipline: explicit
    #: True/False wins, None resolves from ``PIO_SERVE_QUANT``
    #: ("1"/"0"), else OFF. Enabling runs the exactness gate at model
    #: attach (train / fold-in / first serve of a loaded model): the
    #: quantized top-k ids must match the f32 top-k on a probe set or
    #: the attach REFUSES loudly (quant.QuantGateError + counted
    #: metric) — never a silent quality slide. /status.json reports
    #: dtype, bytes, compression and the gate verdict (quantServing).
    quantized_serving: Optional[bool] = None
    #: Exactness-gate bound: minimum fraction of probe users whose
    #: quantized top-k id set must equal the f32 set. The default (1.0)
    #: demands identity; lowering it is an explicit operator decision
    #: (recorded in the gate status), the analogue of the bench's
    #: BENCH_BF16_RMSE_GATE override.
    quant_gate_min_match: float = 1.0

    retired_fields = ("fused_gather", "sort_gather_indices")


@dataclasses.dataclass
class ALSModel:
    """Factor tables + id maps (the ``MatrixFactorizationModel`` +
    ``IPersistentModel`` analogue, reference ``ALSModel.scala:1-63``).
    Plain numpy arrays so the workflow blob-persists it."""

    rank: int
    user_factors: np.ndarray  # [U, rank] float32
    item_factors: np.ndarray  # [I, rank] float32
    user_map: BiMap
    item_map: BiMap

    def sanity_check(self):
        if not np.isfinite(self.user_factors).all():
            raise ValueError("ALS produced non-finite user factors")
        if not np.isfinite(self.item_factors).all():
            raise ValueError("ALS produced non-finite item factors")


def _fetch_factors(factors) -> Tuple[np.ndarray, np.ndarray]:
    """Both tables on the host. The wait is a span of its own: the
    ``np.asarray`` would block there anyway, so nothing is fenced that
    was not, and ``train.fetch`` times the copy alone."""
    import jax

    with span("train.wait_device"):
        jax.block_until_ready((factors.user_factors, factors.item_factors))
    with span("train.fetch"):
        return (
            np.asarray(factors.user_factors),
            np.asarray(factors.item_factors),
        )


class ALSAlgorithm(Algorithm):
    """TPU ALS (reference ``ALSAlgorithm.scala:27-86``)."""

    params_class = ALSAlgorithmParams

    def __init__(self, params: ALSAlgorithmParams = ALSAlgorithmParams()):
        self.params = params
        #: the top-k path the LAST batch actually took ("streaming" |
        #: "dense" | "quant"; None before the first query) — the
        #: resolved serving lever, read by the query server's
        #: /status.json
        self._topk_path: Optional[str] = None
        # quantized-serving state: the gated table for the attached
        # model (weakref identity — a fold-in's new model re-gates) and
        # the gate status /status.json surfaces
        self._quant = None
        self._quant_model_ref = None
        self._quant_status: Optional[dict] = None

    @property
    def topk_path(self) -> Optional[str]:
        return self._topk_path

    @property
    def quant_status(self) -> Optional[dict]:
        """The quantized-serving gate status for the attached model
        (dtype, bytes, compression, matchRate) — None while the lever
        is off. Read by /status.json (quantServing)."""
        return self._quant_status

    def _attach_quant(self, model: ALSModel) -> None:
        """Resolve the quantized_serving lever against THIS model.

        Runs the exactness gate once per attached model — at train and
        fold-in return, and on the first serve of a model loaded from
        the blob store — always BEFORE any quantized answer is
        produced. A gate refusal propagates (loud + counted, the
        docs/quantization.md#gate contract); it never falls back to
        f32 silently."""
        from ..quant import quantize_serving_table, resolve_quantized_serving

        if not resolve_quantized_serving(self.params.quantized_serving):
            self._quant = None
            self._quant_model_ref = None
            self._quant_status = None
            return
        if (
            self._quant is not None
            and self._quant_model_ref is not None
            and self._quant_model_ref() is model
        ):
            return
        qtable, status = quantize_serving_table(
            model.item_factors,
            model.user_factors,
            min_match=self.params.quant_gate_min_match,
        )
        status["minMatch"] = self.params.quant_gate_min_match
        self._quant = qtable
        self._quant_model_ref = weakref.ref(model)
        self._quant_status = status

    def train(self, ctx, pd: PreparedData) -> ALSModel:
        from ..ops.als_sharded import resolve_shards

        p = self.params
        # a config typo must fail the training run, not the first serving
        # query after deploy (use_streaming_topk raises on unknown modes)
        use_streaming_topk(p.streaming_top_k, 1, 1)
        shards = resolve_shards(p.shards)
        # the job's root span: under no server it starts a trace of its
        # own, so one training job is one trace (docs/observability.md)
        tags = {
            "rank": p.rank, "iterations": p.num_iterations, "shards": shards,
        }
        with span("train", tags):
            return self._train(ctx, pd, shards)

    def _train(self, ctx, pd: PreparedData, shards: int) -> ALSModel:
        p = self.params
        cfg = ALSConfig(
            rank=p.rank,
            iterations=p.num_iterations,
            lambda_=p.lambda_,
            seed=p.seed,
            implicit_prefs=p.implicit_prefs,
            alpha=p.alpha,
            solve_mode=p.solve_mode,
            gather_dtype=p.gather_dtype,
        )
        from ..ckpt import resolve_every, resolve_resume
        from ..ops.als_sharded import als_train_sharded

        # checkpoint cadence: params > workflow run (--checkpoint-every /
        # the continuous retrain config) > PIO_CKPT_EVERY > off; an
        # invalid value refuses here, at train time
        every = resolve_every(
            p.checkpoint_every,
            workflow=getattr(ctx, "checkpoint_every", None),
        )
        if shards > 1:
            # the ALX-style sharded data plane (docs/distributed_training
            # .md): both factor tables sharded over the mesh data axis.
            # Conflicting levers fail loudly — a silently ignored flag
            # would corrupt the hardware A/B (the PR-12 discipline).
            if p.distributed:
                raise ValueError(
                    "shards > 1 and distributed=True are mutually "
                    "exclusive: the sharded trainer builds its own mesh "
                    "(pass one or the other)"
                )
            store = None
            if every > 0 and ctx is not None:
                store_factory = getattr(ctx, "checkpoint_store", None)
                if store_factory:
                    # one namespace per algorithm slot, disjoint from the
                    # single-device manager's: the canonical-row store
                    # and the pytree manager must never read each other
                    store = store_factory(
                        subdir="algo_"
                        f"{getattr(ctx, 'algorithm_index', 0)}_sharded"
                    )
                if store is not None and not resolve_resume():
                    store.clear()  # --no-resume: train fresh
            factors = als_train_sharded(
                pd.users,
                pd.items,
                pd.ratings,
                n_users=len(pd.user_map),
                n_items=len(pd.item_map),
                cfg=cfg,
                shards=shards,
                checkpoint=store,
                checkpoint_every=every if store is not None else 0,
            )
            user_factors, item_factors = _fetch_factors(factors)
            model = ALSModel(
                rank=p.rank,
                user_factors=user_factors,
                item_factors=item_factors,
                user_map=pd.user_map,
                item_map=pd.item_map,
            )
            self._attach_quant(model)
            return model
        mesh = ctx.mesh if (p.distributed and ctx is not None) else None
        checkpoint = None
        if every > 0 and ctx is not None:
            manager_factory = getattr(ctx, "checkpoint_manager", None)
            if manager_factory:
                # one namespace per algorithm slot: a second ALS block in the
                # same engine must never resume from this one's factors
                checkpoint = manager_factory(
                    subdir=f"algo_{getattr(ctx, 'algorithm_index', 0)}"
                )
                if checkpoint is not None and not resolve_resume():
                    import os
                    import shutil

                    # --no-resume: train fresh (the manager recreates
                    # the empty dir it expects to list)
                    shutil.rmtree(checkpoint.directory, ignore_errors=True)
                    os.makedirs(checkpoint.directory, exist_ok=True)
        factors = als_train_coo(
            pd.users,
            pd.items,
            pd.ratings,
            n_users=len(pd.user_map),
            n_items=len(pd.item_map),
            cfg=cfg,
            mesh=mesh,
            factor_sharding=p.factor_sharding,
            checkpoint=checkpoint,
            checkpoint_every=every,
        )
        user_factors, item_factors = _fetch_factors(factors)
        model = ALSModel(
            rank=p.rank,
            user_factors=user_factors,
            item_factors=item_factors,
            user_map=pd.user_map,
            item_map=pd.item_map,
        )
        # quantized-serving gate at train time (a refusal must surface
        # here, not on the first query after deploy — the same reasoning
        # as the use_streaming_topk validation above)
        self._attach_quant(model)
        return model

    @property
    def fold_in_supported(self) -> bool:
        """Fold-in solves the EXPLICIT normal equations; an
        implicit-prefs model (Hu-Koren confidence weighting,
        ``_system_implicit``) would get mathematically wrong row updates
        — the continuous controller escalates those engines to a full
        retrain instead (docs/continuous.md)."""
        return not self.params.implicit_prefs

    def _fold_base(self, model: ALSModel, pd: PreparedData) -> dict:
        """The fold prologue shared by :meth:`fold_in` and
        :meth:`fold_in_partitioned`: extend the model's id maps with
        pd's universe (stable indices), translate pd's COO into the
        combined space, and seed rows for new entities. Deterministic in
        (model, pd) — every concurrent partition fold starts from this
        SAME extended base, which is what makes their results
        mergeable."""
        from ..continuous.foldin import extend_bimap_indexing, seeded_rows

        p = self.params
        rank = model.user_factors.shape[1]
        old_u, old_i = len(model.user_map), len(model.item_map)
        # pd's maps are freshly built in arrival order — append the ids
        # the baseline has never seen, preserving every existing index
        pd_u_ids = [pd.user_map.inverse[i] for i in range(len(pd.user_map))]
        pd_i_ids = [pd.item_map.inverse[i] for i in range(len(pd.item_map))]
        comb_u, new_u = extend_bimap_indexing(model.user_map.to_dict(), pd_u_ids)
        comb_i, new_i = extend_bimap_indexing(model.item_map.to_dict(), pd_i_ids)
        # translate pd's index space into the combined space via id strings
        t_u = np.asarray([comb_u[k] for k in pd_u_ids], dtype=np.int32)
        t_i = np.asarray([comb_i[k] for k in pd_i_ids], dtype=np.int32)
        uf = np.concatenate(
            [
                np.asarray(model.user_factors, dtype=np.float32),
                seeded_rows(new_u, rank, p.seed, offset=old_u),
            ]
        )
        itf = np.concatenate(
            [
                np.asarray(model.item_factors, dtype=np.float32),
                seeded_rows(new_i, rank, p.seed + 1, offset=old_i),
            ]
        )
        return {
            "rank": rank,
            "old_u": old_u,
            "old_i": old_i,
            "new_u": new_u,
            "new_i": new_i,
            "comb_u": comb_u,
            "comb_i": comb_i,
            "users": t_u[pd.users],
            "items": t_i[pd.items],
            "uf": uf,
            "itf": itf,
        }

    def fold_in(
        self,
        ctx,
        model: ALSModel,
        pd: PreparedData,
        changed_user_ids: Sequence[str],
        changed_item_ids: Sequence[str],
        policy=None,
    ):
        """ALX-style incremental update (``docs/continuous.md``): re-solve
        only the changed/new user and item rows against fixed counterpart
        factors, over the full current data ``pd``. Existing entities keep
        their indices (untouched rows stay byte-identical); new entities
        get appended, seeded rows. Returns ``(ALSModel, FoldInStats)``.
        """
        from ..continuous.foldin import (
            FoldInPolicy,
            FoldInStats,
            fold_in_factors,
        )
        from ..ops.als import ALSFactors, rmse

        if not self.fold_in_supported:
            raise ValueError(
                "fold_in solves explicit normal equations; "
                "implicit_prefs=True models must retrain fully"
            )
        policy = policy or FoldInPolicy()
        base = self._fold_base(model, pd)
        rank, users, items = base["rank"], base["users"], base["items"]
        uf, itf = base["uf"], base["itf"]
        comb_u, comb_i = base["comb_u"], base["comb_i"]
        changed_u = sorted(
            {comb_u[k] for k in changed_user_ids if k in comb_u}
            | set(range(base["old_u"], base["old_u"] + base["new_u"]))
        )
        changed_i = sorted(
            {comb_i[k] for k in changed_item_ids if k in comb_i}
            | set(range(base["old_i"], base["old_i"] + base["new_i"]))
        )
        before = rmse(ALSFactors(uf, itf, rank), users, items, pd.ratings)
        uf, itf, counts = fold_in_factors(
            uf, itf, users, items, pd.ratings,
            changed_u, changed_i, self.params.lambda_, policy=policy,
        )
        after = rmse(ALSFactors(uf, itf, rank), users, items, pd.ratings)
        folded = ALSModel(
            rank=model.rank,
            user_factors=uf,
            item_factors=itf,
            user_map=BiMap(comb_u),
            item_map=BiMap(comb_i),
        )
        stats = FoldInStats(
            folded_users=counts["solved_users"],
            folded_items=counts["solved_items"],
            new_users=base["new_u"],
            new_items=base["new_i"],
            rmse_before=before,
            rmse_after=after,
        )
        # re-gate the folded table: fold-in moved item rows, so the old
        # quantized table (if any) is stale and the new one must prove
        # exactness again before it serves
        self._attach_quant(folded)
        return folded, stats

    def fold_in_partitioned(
        self,
        ctx,
        model: ALSModel,
        pd: PreparedData,
        parts,
        policy=None,
        max_workers: int = 2,
        timeout_s: float = 0.0,
        clock=None,
    ):
        """Fold per-partition deltas CONCURRENTLY on a bounded pool
        (docs/continuous.md#partitioned-folds).

        ``parts`` maps partition index → ``(user_ids, item_ids)`` — the
        per-keyspace deltas ``PartitionedFeedWatcher.take_batches``
        yields. Every partition's fold runs :func:`fold_in_factors` over
        the SAME extended base tables (so results merge by row copy):
        the write-path hash partitions users, making the per-partition
        changed-user row sets disjoint; changed-item rows may overlap and
        merge last-partition-wins — both solves read the full rating
        corpus against the same base, so the difference is bounded by the
        user-row deltas and the RMSE drift gate guards the composition.

        ``timeout_s > 0`` bounds the wait: a partition whose fold has not
        finished by the deadline (or raised) is SKIPPED — excluded from
        the merge and from the returned ``completed`` list, so the
        controller never commits its cursor and its delta re-folds next
        cycle (convergent, the watcher's replay contract). A slow
        partition therefore never blocks another partition's commit.
        ``timeout_s == 0`` waits for every partition.

        Returns ``(ALSModel, FoldInStats, completed)`` — stats measured
        on the MERGED model. Raises ``RuntimeError`` when no partition
        completed (nothing to commit)."""
        import concurrent.futures
        import time as _time

        from ..continuous.foldin import (
            FoldInPolicy,
            FoldInStats,
            fold_in_factors,
        )
        from ..ops.als import ALSFactors, rmse

        if not self.fold_in_supported:
            raise ValueError(
                "fold_in solves explicit normal equations; "
                "implicit_prefs=True models must retrain fully"
            )
        policy = policy or FoldInPolicy()
        clock = clock or _time.monotonic
        base = self._fold_base(model, pd)
        rank, users, items = base["rank"], base["users"], base["items"]
        comb_u, comb_i = base["comb_u"], base["comb_i"]
        new_u_rows = set(range(base["old_u"], base["old_u"] + base["new_u"]))
        new_i_rows = set(range(base["old_i"], base["old_i"] + base["new_i"]))
        changed: dict = {}
        claimed_u: set = set()
        claimed_i: set = set()
        for idx in sorted(parts):
            user_ids, item_ids = parts[idx]
            cu = {comb_u[k] for k in user_ids if k in comb_u}
            ci = {comb_i[k] for k in item_ids if k in comb_i}
            changed[idx] = (cu, ci)
            claimed_u |= cu
            claimed_i |= ci
        # new entities nobody's delta named (races between the batch
        # snapshot and the pd read) go to EVERY partition: identical
        # inputs solve to identical rows, so whichever folds complete
        # cover them and the merge copies are byte-equal
        orphan_u = new_u_rows - claimed_u
        orphan_i = new_i_rows - claimed_i
        for idx, (cu, ci) in changed.items():
            cu |= orphan_u
            ci |= orphan_i

        before = rmse(
            ALSFactors(base["uf"], base["itf"], rank),
            users, items, pd.ratings,
        )
        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, min(len(changed), int(max_workers))),
            thread_name_prefix="fold-part",
        )
        futures = {
            idx: pool.submit(
                fold_in_factors,
                base["uf"], base["itf"], users, items, pd.ratings,
                sorted(cu), sorted(ci), self.params.lambda_, policy=policy,
            )
            for idx, (cu, ci) in sorted(changed.items())
        }
        deadline = clock() + timeout_s if timeout_s > 0 else None
        concurrent.futures.wait(
            futures.values(),
            timeout=None if deadline is None else max(0.0, deadline - clock()),
        )
        # a wedged fold thread keeps running past the deadline (threads
        # cannot be killed) but is bounded by the pool size and holds
        # only the shared read-only base arrays; never join on it —
        # queued-but-unstarted folds ARE cancellable and must not burn
        # the next cycle's CPU on thrown-away results
        pool.shutdown(wait=False, cancel_futures=True)
        uf = np.array(base["uf"], dtype=np.float32, copy=True)
        itf = np.array(base["itf"], dtype=np.float32, copy=True)
        completed = []
        folded_users = folded_items = 0
        for idx in sorted(futures):
            fut = futures[idx]
            if not fut.done() or fut.cancelled():
                # timed out (or cancelled while queued): cursor stays
                # put, delta re-folds next cycle
                _logger.warning(
                    "fold_in_partitioned: partition %d missed the "
                    "%.1fs deadline; skipped (delta re-folds)",
                    idx, timeout_s,
                )
                continue
            if fut.exception() is not None:
                # a failing partition must be DIAGNOSABLE, not a bare
                # skip counter: the error is logged here, the cursor
                # stays put, and the delta re-folds (a deterministic
                # failure keeps logging every cycle — loud by design)
                _logger.warning(
                    "fold_in_partitioned: partition %d fold failed; "
                    "skipped (delta re-folds)",
                    idx, exc_info=fut.exception(),
                )
                continue
            uf_p, itf_p, counts = fut.result()
            cu, ci = changed[idx]
            cu_rows = np.asarray(sorted(cu), dtype=np.int64)
            ci_rows = np.asarray(sorted(ci), dtype=np.int64)
            if len(cu_rows):
                uf[cu_rows] = uf_p[cu_rows]
            if len(ci_rows):
                itf[ci_rows] = itf_p[ci_rows]
            completed.append(idx)
            folded_users += counts["solved_users"]
            folded_items += counts["solved_items"]
        if not completed:
            raise RuntimeError(
                f"no partition fold completed within {timeout_s}s "
                f"(partitions {sorted(futures)}) — nothing to commit"
            )
        after = rmse(ALSFactors(uf, itf, rank), users, items, pd.ratings)
        folded = ALSModel(
            rank=model.rank,
            user_factors=uf,
            item_factors=itf,
            user_map=BiMap(comb_u),
            item_map=BiMap(comb_i),
        )
        stats = FoldInStats(
            folded_users=folded_users,
            folded_items=folded_items,
            new_users=base["new_u"],
            new_items=base["new_i"],
            rmse_before=before,
            rmse_after=after,
        )
        self._attach_quant(folded)  # merged table re-gates (see fold_in)
        return folded, stats, completed

    def shard_model(
        self, model: ALSModel, shard_index: int, shard_count: int
    ) -> ALSModel:
        """One item-factor partition for sharded serving
        (``docs/fleet.md``; the serving-side analogue of ALX's sharded
        factor layout). Item row ``i`` lives on shard ``i % shard_count``
        — round-robin, so power-law-popular head items spread across
        shards instead of piling onto shard 0. User factors stay whole
        (queries score a full user row against the local partition), the
        item map is rebuilt over the kept rows, and the union of all
        shards' local top-ks provably contains the global top-k the
        router merge reconstructs exactly."""
        keep = np.arange(
            shard_index, model.item_factors.shape[0], shard_count
        )
        inv = model.item_map.inverse
        return ALSModel(
            rank=model.rank,
            user_factors=model.user_factors,
            item_factors=np.ascontiguousarray(model.item_factors[keep]),
            user_map=model.user_map,
            item_map=BiMap(
                {inv[int(old)]: new for new, old in enumerate(keep)}
            ),
        )

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        results = self.batch_predict(model, [(0, query)])
        return results[0][1]

    def batch_predict(
        self, model: ALSModel, indexed_queries: Sequence[Tuple[int, Query]]
    ) -> List[Tuple[int, PredictedResult]]:
        """One device call for the whole batch (reference batchPredict is a
        per-query cartesian; here it's a single gather-dot top-k)."""
        known = [
            (i, q) for i, q in indexed_queries if model.user_map.get(q.user) is not None
        ]
        out: List[Tuple[int, PredictedResult]] = [
            (i, PredictedResult(item_scores=()))
            for i, q in indexed_queries
            if model.user_map.get(q.user) is None
        ]
        if known:
            n_items = model.item_factors.shape[0]
            max_k = min(max(q.num for _, q in known), n_items)
            user_idx = np.asarray(
                [model.user_map[q.user] for _, q in known], dtype=np.int32
            )
            # Shape bucketing (ops/scoring.pad_pow2): micro-batched serving
            # produces every batch size — pad B and k to powers of two so
            # the device program set stays O(log^2), then slice on host.
            b = len(user_idx)
            b_pad = pad_pow2(b)
            k_pad = min(pad_pow2(max_k, lo=8), n_items)
            padded_idx = np.pad(user_idx, (0, b_pad - b))
            # gate-or-refuse BEFORE any answer when the quantized lever
            # is on and this model (e.g. loaded from the blob store)
            # has not been gated yet — a query must never be served
            # from ungated codes
            self._attach_quant(model)
            # one span per batch each, on the profiler's clock too: the
            # dispatch (which uploads the host tables), the fetch, and
            # the Python result objects
            with span("predict.dispatch", {"b": b_pad}):
                if self._quant is not None:
                    # quantized serving: scores from int8 codes + per-row
                    # scales (quant.top_k_quantized) — licensed by the
                    # exactness gate _attach_quant just ran/cached
                    from ..quant import top_k_quantized

                    self._topk_path = "quant"
                    scores, items = top_k_quantized(
                        model.user_factors, self._quant, padded_idx, k=k_pad
                    )
                else:
                    # the fused score+select entry dispatches: Pallas
                    # streaming on TPU past the use_streaming_topk bar
                    # (the [B, I] score matrix never exists), XLA score +
                    # lax.top_k below it — record which path serves
                    # (resolve_topk_path is the ONE decision home the
                    # entry itself dispatches on, same (mode, b, n)
                    # inputs), surfaced at /status.json
                    self._topk_path = resolve_topk_path(
                        self.params.streaming_top_k, b_pad, n_items
                    )
                    scores, items = top_k_for_users_fused(
                        model.user_factors, model.item_factors, padded_idx,
                        k=k_pad, mode=self.params.streaming_top_k,
                    )
            # one fetch for both arrays: each device_get is a full host↔
            # device round trip
            import jax

            with span("predict.fetch", {"b": b_pad}):
                scores, items = jax.device_get((scores, items))
            with span("predict.results", {"b": b_pad}):
                # bulk ndarray→python conversion: one C call instead of
                # 2×B×k scalar __float__/__int__ calls on the hot path
                scores = scores[:b, :max_k].tolist()
                items = items[:b, :max_k].tolist()
                inv = model.item_map.inverse
                for row, (i, q) in enumerate(known):
                    k = min(q.num, max_k)
                    s_row, i_row = scores[row], items[row]
                    out.append(
                        (
                            i,
                            PredictedResult(
                                item_scores=tuple(
                                    ItemScore(item=inv[i_row[j]], score=s_row[j])
                                    for j in range(k)
                                )
                            ),
                        )
                    )
        return out

    def query_class(self):
        return Query


def engine_factory() -> Engine:
    """The template's EngineFactory (reference ``Engine.scala`` of the
    template: ``RecommendationEngine``)."""
    return Engine(
        {"": RecDataSource},
        {"": RecPreparator},
        {"als": ALSAlgorithm, "": ALSAlgorithm},
        {"": FirstServing},
    )


# -- evaluation (reference evaluation example: Precision@K on MovieLens,
#    examples/experimental/scala-local-movielens-evaluation/src/main/scala/
#    Evaluation.scala:83,115) --------------------------------------------
class PrecisionAtK(OptionAverageMetric):
    """Fraction of relevant held-out interactions recovered in the top-k.

    A held-out (query, actual) row counts only when the actual rating meets
    ``rating_threshold`` (irrelevant rows are skipped — the Option part);
    the point score is 1.0 when the actual item appears in the predicted
    top-k."""

    def __init__(self, k: int = 10, rating_threshold: float = 4.0):
        self.k = k
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"Precision@{self.k} (threshold={self.rating_threshold})"

    def calculate_point(self, q, p, a) -> Optional[float]:
        if a.score < self.rating_threshold:
            return None
        top = [s.item for s in p.item_scores[: self.k]]
        return 1.0 if a.item in top else 0.0


class RecEvaluation(Evaluation):
    """``pio eval`` target for this template."""

    def __init__(self, k: int = 10, rating_threshold: float = 4.0):
        super().__init__()
        self.engine_metric = (
            engine_factory(),
            PrecisionAtK(k=k, rating_threshold=rating_threshold),
        )


class RecParamsGenerator(EngineParamsGenerator):
    """Hyperparameter grid over rank x lambda (the reference example's
    EngineParamsGenerator pattern)."""

    def __init__(
        self,
        app_id: int = 1,
        ranks: Sequence[int] = (8, 16),
        lambdas: Sequence[float] = (0.01, 0.1),
    ):
        base_ds = RecDataSourceParams(app_id=app_id)
        grid = [
            EngineParams(
                data_source_params=("", base_ds),
                algorithm_params_list=[
                    ("als", ALSAlgorithmParams(rank=r, lambda_=lam)),
                ],
            )
            for r in ranks
            for lam in lambdas
        ]
        super().__init__(grid)
