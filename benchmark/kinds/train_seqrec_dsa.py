"""A sequence-training cell whose backbone is Keye-VL-2.0's language model:
grouped-query attention that reads only the keys a lightning indexer picks
(learned sparse attention), trained with the indexer's own loss, then routed
experts behind a softmax router with no shared expert. Whole jobs of
``SeqRecAlgorithm.train`` back to back, as ``kinds/train_seqrec.py`` runs them
(its packing, its jobs, its window, its counters and its distances are used as
they stand). Where the cell's traffic says ``length_draw`` ``stratified`` the
job's history lengths are the law's quantiles, one a stratum
(``lib/synth_seq_strata.py``), sized for the rows the job trains on.

``correct`` is decided after the window, on the device the window ran on and at
its shapes, from the last whole job (in a traced run the warm-up job), all from
the objects the job ran (``SeqRecAlgorithm.programs``): the jitted
loss-and-gradient function on the job's last batch and final parameters, whose
aux carries, a layer, the indexer's inputs, one strip of index scores as the
choice saw them, the chosen sets (a bit a pair) and a key head's group of the
core; and one donated optimizer step from fresh moments. Against
``lib/reference_keye.py`` (float32 at ``highest`` precision, full rows of
scores, the choice by a sort, a masked softmax, dense experts, one row and one
layer at a time, plain AdamW in numpy). A choice is discrete and a rounding
flips keys at the threshold, so the comparison is in three parts:

(a) ``index_err``: the first layer's strip of scores against the reference's
    scores on the very indexer inputs the timed call made;
(b) ``select_err``: of that strip, the share of chosen (slot, key) pairs that a
    sort of the program's OWN scores does not choose (0: the rule among equal
    scores is the same) plus the share of pairs on which the program and a sort of the
    REFERENCE's scores disagree although the reference's score lies further
    from its threshold than ``index_err``'s limit (times the strip's largest
    score) allows;
(c) everything after the choice against the reference run on the program's own
    chosen sets, every layer's: ``dsa_core_err`` (the first layer's core on that
    call's own q, k, v), both losses, sampled logits, gradient groups, the step
    (a router the configuration holds must not have moved).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict, List

import numpy as np

from ..lib import reference, reference_keye, scopes, synth_seq, synth_seq_strata
from ..lib.idmaps import id_map
from ..lib.rooflines_phi4flash import pairs_in_window
from ..lib.spans import compiles_in
from .train_seqrec import _backbone_file, _counters, _distance, _leaves, update_distance
from .train_seqrec_mla import worst_leaves

#: which leaves of a layer (reference layout) belong to which gradient group
_GROUPS = {
    "attn": lambda layer: {k: v for k, v in layer["dsa"].items() if k not in reference_keye.INDEXER},
    "indexer": lambda layer: {k: layer["dsa"][k] for k in reference_keye.INDEXER},
    "router": lambda layer: layer["moe"]["router"],
    "experts": lambda layer: layer["moe"]["experts"],
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


def gradient_distances(got: Dict, want: Dict) -> Dict[str, float]:
    """``grad_err.<group>``: the program's gradient against the
    reference's, both in the reference's layout."""
    out = {}
    for name, pick in _GROUPS.items():
        out[f"grad_err.{name}"] = _distance(
            [pick(a) for a in got["layers"]], [pick(b) for b in want["layers"]])
    out["grad_err.norms"] = max(
        out["grad_err.norms"], _distance(got["final_norm"], want["final_norm"]))
    out["grad_err.embed"] = _distance(got["embed"], want["embed"])
    out["grad_err.head"] = _distance(got["head"], want["head"])
    return out


def _without_routers(layout: Dict) -> Dict:
    """A tree in the reference's layout with every router's matrix at zero:
    the optimizer's step is compared without the leaves it does not move."""
    return {**layout, "layers": [
        {**layer, "moe": {**layer["moe"], "router": np.zeros_like(layer["moe"]["router"])}}
        for layer in layout["layers"]]}


def unpacked(packed, length: int) -> np.ndarray:
    """The program's chosen sets [..., L, ceil(L / 8)] uint8 (key ``8 w + bit``
    at bit ``bit`` of byte w) -> [..., L, L] bool."""
    return np.unpackbits(np.asarray(packed), axis=-1, count=length, bitorder="little").astype(bool)


def choice_distances(ran: Dict, segs, topk: int, allowed: float) -> Dict[str, float]:
    """(a) and (b) of the module's text from the first layer's ``ran`` (every
    entry [B, ...]): ``index_err`` and ``select_err``."""
    import jax.numpy as jnp

    at, sample = int(ran["index_at"]), np.asarray(ran["index"], np.float32)
    blk = sample.shape[1]
    strip = np.arange(at * blk, (at + 1) * blk)
    got, want, wrong, kept = [], [], 0, 0
    for b in range(sample.shape[0]):
        seg = jnp.asarray(segs[b, :-1])
        valid = np.asarray(reference_keye.valid_pairs(seg, jnp.asarray(strip)))
        mine = unpacked(ran["chosen"][b][strip], segs.shape[1] - 1)
        scores = np.asarray(reference_keye.index_scores_of(
            ran["iq"][b][strip], ran["ik"][b], ran["iw"][b][strip]))
        got.append(np.where(valid, sample[b], 0.0))
        want.append(np.where(valid, scores, 0.0))
        by_own = np.asarray(reference_keye.chosen_by_sort(jnp.asarray(sample[b]), jnp.asarray(valid), topk))
        by_ref = np.asarray(reference_keye.chosen_by_sort(jnp.asarray(scores), jnp.asarray(valid), topk))
        # the reference's threshold a query: its smallest chosen score
        threshold = np.where(by_ref, scores, np.inf).min(axis=1, keepdims=True)
        far = np.abs(scores - threshold) > allowed * np.abs(want[-1]).max()
        wrong += int((mine != by_own).sum()) + int(((mine != by_ref) & far).sum())
        kept += int(mine.sum())
    return {"index_err": _distance(got, want), "select_err": wrong / max(kept, 1)}


def _compare(ctx, algo, model, batch, check: Dict) -> Dict[str, float]:
    """The readings of ``correct`` that need the reference."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import seq_backbone as bb

    cfg = model.config
    rows, segs = batch
    opt_init, step, loss_and_grad = algo.programs(cfg)
    t_start = time.monotonic()
    on_device = jnp.asarray(rows), jnp.asarray(segs)
    # the step first, on a device that holds what a job's first step finds
    # there (it donates the parameters, so they are put there twice)
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    new_params = step(params, opt_init(params), *on_device)[0]
    change = jax.tree_util.tree_map(lambda new, old: np.asarray(new) - old, new_params, model.params)
    del new_params
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    (loss, (hidden, counted, ran)), grads = loss_and_grad(params, *on_device)
    valid = np.asarray(bb.split_rows(rows, segs)[3])
    slots = synth_seq.sampled_slots(ctx.seed, valid, check["sampled_positions"])
    logits = np.concatenate([np.asarray(bb.logits_of(cfg, params, hidden[b][jnp.asarray(at)]))
                             for b, at in enumerate(slots)])
    loss, index_loss = float(loss), float(np.asarray(counted["index_loss"]).sum())
    # every layer's chosen sets; of the first layer what its choice and core ran on
    chosen = [list(unpacked(layer, rows.shape[1] - 1)) for layer in np.asarray(ran["chosen"])]
    first = {name: np.asarray(a[0]) for name, a in ran.items() if name != "chosen"}
    first["chosen"] = np.asarray(ran["chosen"][0])
    grads = jax.tree_util.tree_map(np.asarray, grads)
    del hidden, params, ran
    t0 = time.monotonic()
    limits = ctx.config["limits"]["train"]
    readings = choice_distances(first, segs, cfg.index_topk, limits["index_err"])
    each = range(len(rows))
    want_o = [np.asarray(reference_keye.sparse_core_of(
        first["q"][b], first["k"][b][0], first["v"][b][0], segs[b, :-1], chosen[0][b])) for b in each]
    readings["dsa_core_err"] = _distance([np.asarray(first["o"][b], np.float32) for b in each], want_o)
    del first, want_o
    host_params = bb.layers_of(model.params, cfg)
    want_losses, want_grads, want_logits = reference_keye.loss_and_grads(
        jax.tree_util.tree_map(jnp.asarray, host_params), rows, segs, ctx.config,
        sample=slots, chosen=chosen)
    t1 = time.monotonic()
    algorithm = ctx.config["algorithm"]
    # a job's first step runs at the first rate of its warm-up
    adamw = (algorithm["learning_rate"] / max(algorithm.get("warmup_steps", 0), 1), *(
        algorithm["adamw"][name] for name in ("b1", "b2", "eps", "weight_decay")))
    held = not cfg.router_trains
    moved = bb.layers_of(change, cfg)
    still = _without_routers if held else (lambda layout: layout)
    want = np.concatenate(want_logits)
    in_layers = bb.layers_of(grads, cfg)
    readings.update({
        "loss_err": abs(loss - want_losses["loss"]) / abs(want_losses["loss"]),
        "index_loss_err": abs(index_loss - want_losses["index_loss"]) / abs(want_losses["index_loss"]),
        "logit_err": float(np.linalg.norm(logits - want) / np.linalg.norm(want)),
        **gradient_distances(in_layers, want_grads),
        "update_err": update_distance(
            still(moved),
            still(reference_keye.adamw_first_step(host_params, want_grads, *adamw)), want_grads),
        # a router that the configuration holds must not have moved at all
        "router_moved": max(float(np.abs(layer["moe"]["router"]).max())
                            for layer in moved["layers"]) if held else 0.0,
    })
    ctx.say(f"losses of the checked batch: program {loss:.5f} (indexers' {index_loss:.5f}), "
            f"reference {json.dumps(want_losses)}")
    ctx.say("leaves that carry most of the gradient's squared distance, each with its own "
            "relative distance: " + worst_leaves(in_layers, want_grads))
    finite = np.isfinite(loss) and all(np.isfinite(a).all() for a in _leaves(grads))
    readings["finite"] = 0.0 if finite and np.isfinite(model.losses).all() else 1.0
    ctx.say(f"check of {rows.shape[0]} row(s): the program's loss, gradients and one step "
            f"{t0 - t_start:.1f} s, the reference's {t1 - t0:.1f} s, distances "
            f"{time.monotonic() - t1:.1f} s")
    return readings


def _pieces(traffic: Dict, n_items: int, rows: int, slots: int, seed: int) -> List[np.ndarray]:
    """One epoch of a job: as many histories as its ``rows`` rows hold."""
    if traffic.get("length_draw") == "stratified":
        return synth_seq_strata.histories(traffic, n_items, rows, slots, seed)
    return synth_seq.histories(traffic, n_items, rows * slots, seed)


def run(ctx) -> Dict:
    import jax

    from predictionio_tpu.models.sequencerec import (
        PreparedData, SeqPreparator, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, batch_order)
    from predictionio_tpu.models.seq_backbone import BackboneConfig
    from predictionio_tpu.obs.profile import default_telemetry

    if "index_topk" not in {f.name for f in dataclasses.fields(BackboneConfig)}:
        # a program from before this backbone knows no sparse-attention layer
        sys.exit("benchmark: this program's backbone has no lightning indexer "
                 "(no index_topk among its keys); the cell cannot run on it")
    cfg, traffic, seed = ctx.config, ctx.workload["traffic_params"], ctx.seed
    algorithm = cfg["algorithm"]
    n_items, seq_len = cfg["vocab_size"], algorithm["seq_len"]
    rows_per_step, steps = traffic["rows_per_step"], algorithm["steps"]
    t_in = time.monotonic()
    pieces = _pieces(traffic, n_items, steps * rows_per_step, seq_len + 1, seed)
    rows, segs = SeqPreparator(SeqPreparatorParams(seq_len=seq_len)).pack(pieces)
    data = PreparedData(
        item_map=id_map("i", n_items), windows=rows, segments=segs,
        user_recent={}, seq_len=seq_len)
    t_data = time.monotonic()
    control = cfg["control"]["train"][ctx.control] if ctx.control else {}
    backbone = _backbone_file(ctx, control)

    def algo_of(n_steps: int):
        return SeqRecAlgorithm(SeqRecAlgorithmParams(
            backbone=backbone, steps=n_steps, batch_size=rows_per_step,
            learning_rate=algorithm["learning_rate"],
            warmup_steps=algorithm.get("warmup_steps", 0), seed=algorithm["seed"]))

    algo = algo_of(steps)

    def job(which=algo):
        start = time.monotonic()
        model = which.train(None, data)
        return model, time.monotonic() - start

    # every program of a job at the window's own shapes; a traced run's job
    # is cut to ``trace_steps``, too few for the loss to fall, so there the
    # warm-up is a whole job and ``correct`` is decided from it
    checked, _ = job(algo_of(steps if ctx.trace else traffic["warm_steps"]))
    telemetry = default_telemetry()
    jit_before = telemetry.snapshot()
    window_start = time.monotonic()
    setup_s = window_start - ctx.t0
    cache = jit_before["cache"]
    ctx.say(
        f"set-up {setup_s:.1f} s: {t_in - ctx.t0:.1f} s to reach the chip, "
        f"{t_data - t_in:.1f} s for {len(pieces)} histories packed into {rows.shape[0]} rows, "
        f"{window_start - t_data:.1f} s for the warm-up job; compile cache "
        f"{cache['hits']} hits, {cache['misses']} misses")
    jobs, model = [], None
    if ctx.trace:
        from ..lib.spans import traced_window

        algo = algo_of(traffic.get("trace_steps", steps))
        with traced_window(ctx.trace_dir):
            model, seconds = job(algo)
        jobs.append(seconds)
    else:
        while time.monotonic() - window_start < ctx.seconds:
            model, seconds = job()
            jobs.append(seconds)
        checked = model
    window_s = time.monotonic() - window_start
    ctx.say(f"window {window_s:.1f} s: {len(jobs)} job(s) of "
            + ", ".join(f"{j:.2f}" for j in jobs) + f" s, {algo.params.steps} steps each")
    compiles = telemetry.delta_since(jit_before)

    stats = model.stats
    counters = {**_counters(model), **{
        name: stats[name] for name in ("dsa_kept_pairs_pct", "index_loss")}}
    step_ms = [s["durationMs"] for s in scopes.job_spans() if s["name"] == "seqrec.step"]
    takes = list(batch_order(rows.shape[0], rows_per_step, algo.params.steps, algorithm["seed"]))
    lengths = [np.bincount(s[:-1][s[:-1] > 0])[1:] for s in segs]
    row_pairs = np.asarray([(n * (n + 1) / 2.0).sum() for n in lengths])
    # the pairs the choice keeps, from the lengths alone: a slot keeps its
    # ``topk`` best causal keys, all of them where it has no more
    row_kept = np.asarray([pairs_in_window(n, cfg["sa_config"]["topk"]) for n in lengths])
    obs: Dict = {
        "setup_s": setup_s,
        "attempted": len(jobs),
        "jobs_s": jobs,
        "job_mean_s": window_s / len(jobs),
        "window_compiles": compiles_in(compiles),
        # the first two spans do not wait for a step before them
        "step_ms": step_ms[2:],
        "counters": counters,
        "seq_shape": {
            "config": cfg, "tokens": rows_per_step * seq_len, "steps": algo.params.steps,
            "pair_sum": float(np.mean([row_pairs[take].sum() for take in takes])),
            "kept_pair_sum": float(np.mean([row_kept[take].sum() for take in takes])),
            "held_by_step": counters["held_by_step"],
            "n_params": float(sum(a.size for a in jax.tree_util.tree_leaves(model.params))),
        },
    }
    shape = obs["seq_shape"]
    ctx.say("counters: " + json.dumps(
        {k: v for k, v in counters.items() if k != "held_by_step"}))
    ctx.say(f"mixers: {json.dumps(stats['mixers'])}; {len(pieces)} histories of "
            f"{int(np.concatenate(lengths).mean())} ids at the mean, "
            f"{shape['pair_sum'] / shape['tokens']:.0f} causal pairs a slot, "
            f"{shape['kept_pair_sum'] / shape['tokens']:.0f} of them kept by the lengths' count "
            f"({100 * shape['kept_pair_sum'] / shape['pair_sum']:.1f} %)")
    ctx.say("step ms by step (the last job): " + " ".join(f"{v:.0f}" for v in step_ms))
    # the indexers' loss follows the row (a history that fills it reads tens of
    # times a short one's): whether the job learns is read off the next-item part
    indexers = np.asarray(checked.stats["index_loss_by_step"]).reshape(len(checked.losses), -1).sum(1)
    next_item = np.asarray(checked.losses) - indexers
    ctx.say("next-item loss by step: " + " ".join(f"{v:.3f}" for v in next_item))
    ctx.say("indexers' loss by step (summed over layers): " + " ".join(f"{v:.3f}" for v in indexers))
    held = cfg["experts_held"][1]
    ctx.say("tokens a held expert by step (mean over the layers): "
            + " ".join(f"{np.mean(v) / held:.0f}" for v in counters["held_by_step"]))

    # -- correct: the last whole job's parameters and last batch, after the window
    check = traffic["check"]
    tail = check["loss_tail_steps"]
    last = list(batch_order(rows.shape[0], rows_per_step, steps, algorithm["seed"]))[-1]
    readings = {
        "window_compiles": float(obs["window_compiles"]),
        "dropped": max(counters["dropped"], float(np.sum(checked.stats["dropped"]))),
        "loss_last_over_first": float(np.mean(next_item[-tail:]) / next_item[0]),
    }
    readings.update(_compare(ctx, algo, checked, (rows[last], segs[last]), check))
    ctx.say("readings: " + json.dumps(readings))
    verdict = reference.verdict(
        readings, {**cfg["limits"]["train"], "window_compiles": 0.0, "dropped": 0.0,
                   "finite": 0.0, "router_moved": 0.0})
    obs["verdict"] = verdict
    obs["failed"] = 0 if all(v["ok"] for v in verdict) else len(jobs)
    return obs
