"""Sequence-recommendation engine: transformer next-item prediction.

The long-context upgrade of the reference's sequence machinery: where
briandamage/PredictionIO offers only a first-order ``MarkovChain`` over item
transitions (``e2/src/main/scala/io/prediction/e2/engine/MarkovChain.scala``),
this engine models whole interaction histories with a causal transformer —
same DASE shape as every other template (DataSource reads view/buy events,
Preparator indexes items and builds windows, Algorithm trains, Serving
answers ``queries.json``), but the context window is a first-class scaling
axis: attention dispatches to ring or Ulysses sequence parallelism over the
mesh ``seq`` axis for histories too long for one chip
(:mod:`predictionio_tpu.ops.attention`).

The backbone is a function of a configuration
(:mod:`predictionio_tpu.models.seq_backbone`): the shipped preset is a small
pre-LayerNorm transformer, and ``SeqRecAlgorithmParams.backbone`` names any
other (a JSON file with the keys of a public model's ``config.json``, e.g.
``conf/backbones/qwen3next-80b-a3b-ep16.json``: gated DeltaNet and gated
attention layers with sparse experts, of which this chip holds a range).
It stays framework-light (pure jax + optax pytrees), so the model pytree
persists through the standard model store like any other template's model.

Training runs on packed rows: ragged histories laid first-fit into rows of
``seq_len + 1`` slots with the id of its history beside every slot, a
background thread that keeps the next batch a step ahead on the device, and
one donated jitted optimizer step.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
)
from ..obs.trace import span
from ..ops.scoring import top_k_for_vectors
from ..storage import BiMap, EventFilter, get_registry
from . import seq_backbone as bb


# -- query / result ---------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Query:
    """Next-item query: by user history (``user``) or explicit recent items."""

    user: Optional[str] = None
    recent_items: Tuple[str, ...] = ()
    num: int = 10


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: Tuple[ItemScore, ...] = ()

    def to_json_dict(self) -> dict:
        # same camelCase wire shape as the recommender templates
        from .wire import item_scores_json

        return item_scores_json(self.item_scores)


# -- training data ----------------------------------------------------------
@dataclasses.dataclass
class TrainingData:
    """Per-user, time-ordered item-id sequences."""

    user_ids: List[str]
    sequences: List[List[str]]

    def sanity_check(self):
        if not self.sequences:
            raise ValueError("No interaction sequences found; check app id "
                             "and event names.")


@dataclasses.dataclass
class PreparedData:
    item_map: BiMap
    #: packed rows [R, seq_len + 1] int32 (0 in the slots no history fills)
    windows: np.ndarray
    #: the id of the history in every slot, counted from 1 in each row; 0 =
    #: padding. A slot's target is the next slot where the id is the same.
    segments: np.ndarray
    user_recent: Dict[str, List[int]]  # tail of each user's history
    seq_len: int

    @property
    def fill(self) -> float:
        """Real tokens over slots."""
        return float((self.segments > 0).mean())


def pack_first_fit(pieces: List[np.ndarray], slots: int) -> Tuple[np.ndarray, np.ndarray]:
    """Lay ``pieces`` (int arrays of at most ``slots`` ids), in the order
    they arrive, each into the first row that still has room for it.
    Returns rows and segment ids, [R, slots] int32 each."""
    free: List[int] = []  # room left in each row
    place: List[Tuple[int, int]] = []
    # rows with room, kept as a list scanned from the front: first fit
    open_rows: List[int] = []
    for piece in pieces:
        n = len(piece)
        for at, r in enumerate(open_rows):
            if free[r] >= n:
                break
        else:
            r, at = len(free), len(open_rows)
            free.append(slots)
            open_rows.append(r)
        place.append((r, slots - free[r]))
        free[r] -= n
        if free[r] < 2:  # no history of two ids fits any more
            open_rows.pop(at)
    rows = np.zeros((len(free), slots), np.int32)
    segs = np.zeros((len(free), slots), np.int32)
    count = [0] * len(free)
    for piece, (r, start) in zip(pieces, place):
        count[r] += 1
        rows[r, start:start + len(piece)] = piece
        segs[r, start:start + len(piece)] = count[r]
    return rows, segs


# -- DASE components --------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SeqDataSourceParams(Params):
    app_id: int = 1
    event_names: Tuple[str, ...] = ("view", "buy")


class SeqDataSource(DataSource):
    """Orders each user's view/buy events by event time into one sequence."""

    params_class = SeqDataSourceParams

    def __init__(self, params: SeqDataSourceParams = SeqDataSourceParams()):
        self.params = params

    def read_training(self, ctx) -> TrainingData:
        store = get_registry().get_events()
        cols = store.scan_columnar(
            self.params.app_id,
            EventFilter(event_names=list(self.params.event_names)),
        )
        by_user: Dict[str, List[Tuple[int, str]]] = {}
        for uid, tid, tms in zip(
            cols["entity_id"], cols["target_entity_id"],
            cols["event_time_ms"].tolist(),
        ):
            if tid is None:
                continue
            by_user.setdefault(uid, []).append((tms, tid))
        users, seqs = [], []
        for uid, pairs in by_user.items():
            pairs.sort(key=lambda p: p[0])
            users.append(uid)
            seqs.append([tid for _, tid in pairs])
        return TrainingData(user_ids=users, sequences=seqs)

    def read_eval(self, ctx):
        """Leave-one-out: last item of each ≥2-length sequence is the label."""
        td = self.read_training(ctx)
        train_seqs, qa = [], []
        users = []
        for uid, seq in zip(td.user_ids, td.sequences):
            if len(seq) >= 2:
                train_seqs.append(seq[:-1])
                users.append(uid)
                qa.append(
                    (Query(recent_items=tuple(seq[:-1]), num=10),
                     ItemScore(item=seq[-1], score=1.0))
                )
            else:
                train_seqs.append(seq)
                users.append(uid)
        return [(TrainingData(user_ids=users, sequences=train_seqs), None, qa)]


@dataclasses.dataclass(frozen=True)
class SeqPreparatorParams(Params):
    #: positions the model sees in one row; a row holds seq_len + 1 ids
    seq_len: int = 64
    #: slide stride when a history is longer than seq_len + 1
    window_stride: int = 32


class SeqPreparator(Preparator):
    """Item indexing + packed training rows: each history of two ids or
    more goes whole into a row of ``seq_len + 1`` slots beside others
    (first fit, in the order the histories arrive), with the id of its
    history in every slot — the static-shape layout XLA needs without
    spending a row on every short history. A history longer than a row
    is cut into windows ``window_stride`` apart, the last one anchored on
    its newest ids."""

    params_class = SeqPreparatorParams

    def __init__(self, params: SeqPreparatorParams = SeqPreparatorParams()):
        self.params = params

    def pack(self, pieces: List[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
        """Indexed histories (each of at most ``seq_len + 1`` ids) -> packed
        rows and their segment ids."""
        with span("seqrec.pack", {"histories": len(pieces)}):
            return pack_first_fit(pieces, self.params.seq_len + 1)

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        L = self.params.seq_len
        item_map = BiMap.string_int(
            [i for seq in td.sequences for i in seq]
        )
        pieces: List[np.ndarray] = []
        user_recent: Dict[str, List[int]] = {}
        span_ = L + 1
        for uid, seq in zip(td.user_ids, td.sequences):
            idx = np.fromiter((item_map[i] for i in seq), np.int32, len(seq))
            user_recent[uid] = idx[-L:].tolist()
            if len(idx) < 2:
                continue
            starts = list(range(0, max(1, len(idx) - span_ + 1),
                                self.params.window_stride))
            # anchor a final window on the newest interactions — a stride
            # that doesn't divide the history must not drop the tail
            if len(idx) > span_ and starts[-1] != len(idx) - span_:
                starts.append(len(idx) - span_)
            pieces.extend(idx[s: s + span_] for s in starts)
        if not pieces:
            raise ValueError("No training windows (all histories length < 2)")
        rows, segs = self.pack(pieces)
        return PreparedData(
            item_map=item_map, windows=rows, segments=segs,
            user_recent=user_recent, seq_len=L,
        )


# -- the model and its trainer ---------------------------------------------
@dataclasses.dataclass(frozen=True)
class SeqRecAlgorithmParams(Params):
    #: the backbone configuration: a JSON file (absolute, or relative to
    #: the engine's directory) or the name of one under ``conf/backbones/``.
    #: Empty = the shipped preset, sized by the three knobs below.
    backbone: str = ""
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    steps: int = 300
    #: packed rows of seq_len positions in one optimizer step
    batch_size: int = 64
    learning_rate: float = 1e-3
    #: optimizer steps over which the rate rises in a line to ``learning_rate``:
    #: step k of them (from 0) runs at (k + 1) / warmup_steps of it. 0 = none
    warmup_steps: int = 0
    seed: int = 0
    #: attention schedule: "flash" (single device), "ring", "ulysses",
    #: or "auto" (ring when the ctx mesh has a seq axis of size > 1)
    schedule: str = "flash"

    retired_fields = ("flash_impl",)

    def backbone_config(self) -> bb.BackboneConfig:
        if self.backbone:
            return bb.BackboneConfig.load(self.backbone)
        return bb.BackboneConfig.toy(self.d_model, self.n_heads, self.n_layers)


@dataclasses.dataclass
class SeqRecModel:
    """Trained backbone + id maps + per-user recent histories."""

    params: dict  # numpy pytree
    item_map: BiMap
    user_recent: Dict[str, List[int]]
    seq_len: int
    config: bb.BackboneConfig
    #: the loss of every optimizer step, in order
    losses: Optional[np.ndarray] = None
    #: what the job counted: slots filled, tokens per held expert, ...
    stats: Optional[dict] = None

    def sanity_check(self):
        # one reduction on the device: no host copy of every leaf
        leaves = jax.tree_util.tree_leaves(self.device_params())
        finite = jax.jit(lambda ls: jnp.all(jnp.stack([jnp.isfinite(x).all() for x in ls])))
        if not bool(finite(leaves)):
            raise ValueError("sequencerec produced non-finite weights")

    def device_params(self):
        """Device-resident weight pytree, uploaded once per model — serving
        must not pay a full host→device weight transfer per query."""
        cache = self.__dict__.get("_device_params")
        if cache is None:
            cache = jax.tree_util.tree_map(jnp.asarray, self.params)
            self.__dict__["_device_params"] = cache
        return cache

    def __getstate__(self):
        # never pickle the device cache (model blobs stay pure numpy)
        state = dict(self.__dict__)
        state.pop("_device_params", None)
        return state


def batch_order(n_rows: int, batch: int, steps: int, seed: int):
    """Which packed rows each step of a job takes: seeded epochs without
    replacement, one array of ``batch`` row numbers a step. A function of
    its arguments alone, so whoever holds the rows can name a job's
    batches without the trainer keeping them."""
    rng = np.random.default_rng(seed)
    order = np.empty(0, np.int64)
    for _ in range(steps):
        while len(order) < batch:
            order = np.concatenate([order, rng.permutation(n_rows)])
        take, order = order[:batch], order[batch:]
        yield take


class _Batches:
    """The input pipeline: a thread that takes the rows of each step
    (:func:`batch_order`), stacks them and puts them on the device while
    the step before is still running. ``depth`` batches wait at most;
    ``next()`` is the wait the trainer sees."""

    def __init__(self, pd: PreparedData, batch: int, steps: int, seed: int, depth: int = 2):
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(pd, batch, steps, seed), daemon=True)
        self._thread.start()

    def close(self) -> None:
        """Stop the thread (a trainer that failed mid-job leaves batches
        undrawn) and wait for it."""
        self._stop.set()
        while self._thread.is_alive():
            try:  # make room for a put that is waiting
                self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)

    def _run(self, pd, batch, steps, seed):
        try:
            for take in batch_order(pd.windows.shape[0], batch, steps, seed):
                if self._stop.is_set():
                    return
                self._queue.put(jax.device_put((pd.windows[take], pd.segments[take])))
        except BaseException as exc:  # the trainer re-raises it
            self._queue.put(exc)

    def next(self):
        item = self._queue.get()
        if isinstance(item, BaseException):
            raise item
        return item


def make_loss_and_grad(cfg: bb.BackboneConfig, mesh=None, schedule: str = "auto"):
    """``(params, rows, segs) -> ((loss, (hidden, counters, ran)), grads)``
    (``seq_backbone.loss_fn``): the function the optimizer step is built
    from, jitted."""
    return jax.jit(jax.value_and_grad(
        lambda mp, rows, segs: bb.loss_fn(cfg, mp, rows, segs, mesh, schedule),
        has_aux=True))


#: counters of a step that the job keeps from every step: tokens per held
#: expert [periods, layers of a period, held], per expert of the router's
#: whole width where a bias balances them [periods, layers, experts], the
#: same two of the prediction module's block, the module's loss, and the
#: passes an expert layer's step needed [periods, layers] (the module's
#: block: a scalar; more than one: it took the overflow branch, ``ops.moe``)
_BY_STEP = ("expert_tokens", "router_tokens", "mtp_expert_tokens", "mtp_router_tokens",
            "mtp_loss", "passes", "mtp_passes", "index_loss", "kept_pairs", "causal_pairs")


def _choice_counts(counters: Dict) -> Dict[str, float]:
    """From a step's counters where the backbone has sparse-attention layers:
    ``index_loss`` (the indexers' loss summed over the layers) and
    ``dsa_kept_pairs_pct`` (the pairs the choice kept over the causal pairs
    inside histories, all layers); nothing elsewhere. Counters stacked over a
    job's steps give the last step's loss and the whole job's share (a step's
    own follows its rows: a history that fills a row keeps a quarter, eight
    short ones keep all)."""
    if "kept_pairs" not in counters:
        return {}
    loss, kept, causal = (np.asarray(a, np.float64) for a in jax.device_get(
        [counters[name] for name in ("index_loss", "kept_pairs", "causal_pairs")]))
    last = loss[-1] if loss.ndim > 2 else loss
    return {"index_loss": float(last.sum()),
            "dsa_kept_pairs_pct": float(100.0 * kept.sum() / max(causal.sum(), 1.0))}


def _pass_counts(stats: Dict) -> Dict[str, object]:
    """From ``passes_by_step`` (and the module's): the job's layer-steps,
    how many of them took the overflow branch, and every step's largest
    ``passes`` as one string (a span's tag); nothing without expert layers."""
    kept = [stats[name].reshape(len(stats[name]), -1)
            for name in ("passes_by_step", "mtp_passes_by_step") if name in stats]
    if not kept:
        return {}
    passes = np.concatenate(kept, axis=1)  # [steps, expert layers]
    return {"layer_steps": int(passes.size), "overflow_layer_steps": int((passes > 1).sum()),
            "passes_by_step": " ".join(str(n) for n in passes.max(axis=1))}


@functools.lru_cache(maxsize=8)
def _programs(cfg: bb.BackboneConfig, learning_rate: float, mesh, schedule: str,
              warmup_steps: int = 0):
    """The jitted programs of a job, made once per configuration: a second
    job of the same shape compiles nothing."""
    import optax

    if warmup_steps > 1:
        learning_rate = optax.linear_schedule(
            learning_rate / warmup_steps, learning_rate, warmup_steps - 1)
    opt = optax.adamw(learning_rate)
    loss_and_grad = make_loss_and_grad(cfg, mesh, schedule)

    def step(mp, os_, rows, segs):
        (loss, (_, counters, _)), grads = loss_and_grad(mp, rows, segs)
        with jax.named_scope("seq.optimizer"):
            updates, os_ = opt.update(grads, os_, mp)
            stepped = optax.apply_updates(mp, updates)
        # what no gradient moves is stepped from the step's own counts
        stepped = bb.step_routers(cfg, mp, stepped, counters)
        return stepped, os_, loss, counters

    return jax.jit(opt.init), jax.jit(step, donate_argnums=(0, 1)), loss_and_grad


class SeqRecAlgorithm(Algorithm):
    """Next-item trainer over packed histories (optax AdamW)."""

    params_class = SeqRecAlgorithmParams

    def __init__(self, params: SeqRecAlgorithmParams = SeqRecAlgorithmParams()):
        self.params = params

    def programs(self, cfg: bb.BackboneConfig):
        """The jitted programs of this algorithm's single-device job, the
        objects ``train`` runs and no copies: the optimizer's ``init``
        (params -> state), the donated ``step`` ((params, state, rows,
        segs) -> params, state, loss, counters) and the loss-and-gradient
        function the step is built from (:func:`make_loss_and_grad`)."""
        return self._programs_of(cfg, None, "auto")

    def _programs_of(self, cfg: bb.BackboneConfig, mesh, schedule: str):
        # (the cache's key is the call as written: no warm-up, no fifth argument)
        warm = (self.params.warmup_steps,) if self.params.warmup_steps else ()
        return _programs(cfg, self.params.learning_rate, mesh, schedule, *warm)

    def train(self, ctx, pd: PreparedData) -> SeqRecModel:
        p = self.params
        cfg = p.backbone_config()
        tags = {"backbone": p.backbone or "toy", "steps": p.steps,
                "layers": cfg.num_hidden_layers, **bb.mechanisms(cfg, pd.seq_len),
                "mixers": " ".join(f"{name}:{n}" for name, n in cfg.mixers().items())}
        # the job's root span: under no server it starts a trace of its own
        with span("train", tags):
            model = self._train(ctx, pd, cfg)
            tags.update(_pass_counts(model.stats))  # the store reads the tags when the span ends
            return model

    def _train(self, ctx, pd: PreparedData, cfg: bb.BackboneConfig) -> SeqRecModel:
        p = self.params
        mesh = ctx.mesh if (ctx is not None and p.schedule != "flash") else None
        schedule = p.schedule if p.schedule != "flash" else "auto"
        batch = min(p.batch_size, pd.windows.shape[0])
        batches = _Batches(pd, batch, p.steps, p.seed)
        try:
            return self._run_steps(pd, cfg, batches, batch, mesh, schedule)
        finally:
            batches.close()

    def _run_steps(self, pd, cfg, batches, batch, mesh, schedule) -> SeqRecModel:
        p = self.params
        vocab = len(pd.item_map)
        opt_init, step, _ = self._programs_of(cfg, mesh, schedule)
        with span("seqrec.init"):
            model_params = bb.init_params(cfg, vocab, pd.seq_len, p.seed)
            opt_state = opt_init(model_params)
        losses, by_step, counters, before = [], {}, None, None
        for i in range(p.steps):
            with span("seqrec.input", {"i": i}):
                rows, segs = batches.next()
            tags = {"i": i}
            with span("seqrec.step", tags):
                model_params, opt_state, loss, counters = step(
                    model_params, opt_state, rows, segs)
                losses.append(loss)
                for name in _BY_STEP:
                    if name in counters:
                        by_step.setdefault(name, []).append(counters[name])
                # one step behind: the device already has step i when the
                # host waits for step i - 1, so the span is a step long and
                # the device is never left waiting for the host
                if before is not None:
                    jax.block_until_ready(before[0])
                    # what the step the span waited for counted of its choice
                    tags.update(_choice_counts(before[1]))
                before = loss, counters
        with span("train.wait_device"):
            jax.block_until_ready(model_params)
        stats = {"fill": pd.fill, "steps": p.steps, "tokens_per_step": batch * pd.seq_len,
                 "mixers": cfg.mixers(), **bb.mechanisms(cfg, pd.seq_len)}
        if counters:
            stats.update(jax.tree_util.tree_map(np.asarray, counters))
        with span("train.fetch"):
            host_params = jax.tree_util.tree_map(np.asarray, model_params)
            host_losses = np.asarray(jax.device_get(losses), np.float32)
            for name, values in by_step.items():  # [steps, ...]
                stats[name + "_by_step"] = np.stack(jax.device_get(values))
            # the last step's loss, the whole job's kept share
            stats.update(_choice_counts({name: stats[name + "_by_step"] for name in by_step}))
            counts = _pass_counts(stats)
            stats.update({name: counts[name] for name in counts if name != "passes_by_step"})
            sites = bb.expert_sites(cfg, host_params)
            if cfg.router_bias and sites:
                stats["router_bias_abs_max"] = max(
                    float(np.abs(bb.at_path(host_params, path)["router_bias"]).max())
                    for path, _ in sites)
        return SeqRecModel(
            params=host_params, item_map=pd.item_map, user_recent=pd.user_recent,
            seq_len=pd.seq_len, config=cfg, losses=host_losses, stats=stats,
        )

    # -- serving ----------------------------------------------------------
    def _tokens_for(self, model: SeqRecModel, query: Query) -> Optional[List[int]]:
        if query.recent_items:
            idx = [
                model.item_map[i]
                for i in query.recent_items
                if model.item_map.get(i) is not None
            ]
            return idx[-model.seq_len:] or None
        if query.user is not None:
            return model.user_recent.get(query.user)
        return None

    def predict(self, model: SeqRecModel, query: Query) -> PredictedResult:
        recent = self._tokens_for(model, query)
        if not recent:
            return PredictedResult(item_scores=())
        # the window is encoded anew for every query, left-padded to the
        # training context length: one compiled shape for every query. The
        # padding is a history of its own (id 0), which the recent items
        # neither attend to nor inherit state from.
        pad = model.seq_len - len(recent)
        tokens = np.asarray([0] * pad + list(recent), np.int32)[None, :]
        seg = np.asarray([0] * pad + [1] * len(recent), np.int32)[None, :]
        k = min(query.num, len(model.item_map))
        top_s, top_i = _encode_and_select(
            model.config, k, model.device_params(), jnp.asarray(tokens), jnp.asarray(seg))
        # Next-item prediction keeps previously-seen items eligible (Markov
        # semantics: the next state may be a revisit). Scores are logits;
        # score-and-select on the device, one round trip.
        top_s, top_i = jax.device_get((top_s[0], top_i[0]))
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.item_map.inverse[int(i)], score=float(s))
                for s, i in zip(top_s, top_i)
                if np.isfinite(s)
            )
        )

    def query_class(self):
        return Query


@functools.partial(jax.jit, static_argnums=(0, 1))
def _encode_and_select(cfg: bb.BackboneConfig, k: int, params, tokens, seg):
    hidden, *_ = bb.hidden_states(cfg, params, tokens, seg)
    last = bb._norm(cfg, params["final_norm"], hidden[:, -1])
    return top_k_for_vectors(last, bb.head_of(params), k)


def engine_factory() -> Engine:
    """EngineFactory for the sequence-recommendation template."""
    return Engine(
        {"": SeqDataSource},
        {"": SeqPreparator},
        {"transformer": SeqRecAlgorithm, "": SeqRecAlgorithm},
        {"": FirstServing},
    )
