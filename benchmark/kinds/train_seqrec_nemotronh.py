"""A sequence-training cell whose backbone is Nemotron-H: every layer ONE
part behind one norm (``hybrid_override_pattern``), a Mamba-2 mixer whose
heads read B and C by group, grouped-query attention without positions, or
ungated ReLU^2 experts beside a shared one behind a sigmoid router balanced
by a bias. Whole jobs of ``SeqRecAlgorithm.train`` back to back, as
``kinds/train_seqrec.py`` runs them (its packing, its jobs, its window, its
counters and its distances are used as they stand), on that cell's traffic
with the catalogue drawn once for all runs (``lib/synth_seq_catalogue.py``).

``correct`` is decided after the window, on the device the window ran on and
at its shapes, from the last whole job (in a traced run the warm-up job),
all from the objects the job ran (``SeqRecAlgorithm.programs``): the jitted
loss-and-gradient function on the job's last batch and final parameters,
whose aux carries what the first Mamba-2 layer handed its scan and what that
gave, and the first expert layer's normed input and output; and one donated
optimizer step from fresh moments, which also steps the routers' bias.
Against ``lib/reference_nemotronh.py`` (float32 at ``highest`` precision, the
recurrence slot by slot with every head on its group's B and C, the
convolution as four shifted adds and a bias, a full score matrix, dense
experts, one row and one layer at a time, plain AdamW and the bias rule in
numpy): the loss, sampled logits, gradient groups, the step (a router the
configuration holds must not have moved), the bias step on the step's own
counts, ``ssd_err`` (the scan's ``y`` of that layer against the reference's
recurrence on the very ``u``, ``B``, ``C``, ``Delta`` the timed function made)
and ``moe_err`` (that expert layer's output against the reference's dense
loop on the very input it was handed).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict

import numpy as np

from ..lib import reference, reference_nemotronh, scopes, synth_seq, synth_seq_catalogue
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in
from .train_seqrec import _backbone_file, _distance, _leaves, update_distance
from .train_seqrec_lfm2 import _lfm2_counters, _optimizer_leaves
from .train_seqrec_mla import worst_leaves

#: which leaves of a layer (reference layout) belong to which gradient group
_GROUPS = {
    "ssm": lambda layer: layer.get("ssm"),
    "attention": lambda layer: layer.get("full"),
    "router": lambda layer: layer["moe"]["router"] if "moe" in layer else None,
    "experts": lambda layer: layer["moe"]["experts"] if "moe" in layer else None,
    "shared": lambda layer: layer["moe"]["shared"] if "moe" in layer else None,
    "norms": lambda layer: layer["norm"],
}


def gradient_distances(got: Dict, want: Dict) -> Dict[str, float]:
    """``grad_err.<group>``: the program's gradient against the
    reference's, both in the reference's layout."""
    out = {}
    for name, pick in _GROUPS.items():
        pairs = [(pick(a), pick(b)) for a, b in zip(got["layers"], want["layers"])]
        pairs = [(a, b) for a, b in pairs if b is not None]
        out[f"grad_err.{name}"] = _distance([a for a, _ in pairs], [b for _, b in pairs])
    out["grad_err.norms"] = max(
        out["grad_err.norms"], _distance(got["final_norm"], want["final_norm"]))
    out["grad_err.embed"] = _distance(got["embed"], want["embed"])
    out["grad_err.head"] = _distance(got["head"], want["head"])
    return out


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
    stepped = step(params, opt_init(params), *on_device)
    new_params, counted = stepped[0], np.asarray(stepped[3]["router_tokens"])
    del stepped
    change = jax.tree_util.tree_map(lambda new, old: np.asarray(new) - old, new_params, model.params)
    del new_params
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    (loss, (hidden, _, ran)), grads = loss_and_grad(params, *on_device)
    valid = np.asarray(bb.split_rows(rows, segs)[3])
    slots = synth_seq.sampled_slots(ctx.seed, valid, check["sampled_positions"])
    logits = np.concatenate([np.asarray(bb.logits_of(cfg, params, hidden[b][jnp.asarray(at)]))
                             for b, at in enumerate(slots)])
    loss = float(loss)
    # the first Mamba-2 layer's scan and the first expert layer: handed and gave
    seen = {name: np.asarray(ran[name][0]) for name in
            ("u", "B", "C", "dt", "y", "moe_in", "moe_out")}
    grads = jax.tree_util.tree_map(np.asarray, grads)
    del hidden, params, ran
    t0 = time.monotonic()
    a_log = model.params["periods"]["ssm"]["A_log"][0, 0]
    first_moe = jax.tree_util.tree_map(lambda a: a[0, 0], model.params["periods"]["moe"])
    each = range(len(rows))
    want_y = [np.asarray(reference_nemotronh.ssd_of(
        *(seen[name][b] for name in ("u", "B", "C", "dt")), a_log, segs[b, :-1],
        groups=cfg.mamba_n_groups)) for b in each]
    ssd_err = _distance([np.asarray(seen["y"][b], np.float32) for b in each], want_y)
    want_out = [np.asarray(reference_nemotronh.moe_of(first_moe, seen["moe_in"][b], ctx.config))
                for b in each]
    moe_err = _distance([seen["moe_out"][b] for b in each], want_out)
    del seen, want_y, want_out
    host_params = bb.layers_of(model.params, cfg)
    want_loss, want_grads, want_logits = reference_nemotronh.loss_and_grads(
        jax.tree_util.tree_map(jnp.asarray, host_params), rows, segs, ctx.config, sample=slots)
    t1 = time.monotonic()
    algorithm = ctx.config["algorithm"]
    # (a job's first step runs at the warm-up's first rate)
    adamw = (algorithm["learning_rate"] / max(algorithm.get("warmup_steps", 0), 1), *(
        algorithm["adamw"][name] for name in ("b1", "b2", "eps", "weight_decay")))
    rate, held = cfg.router_bias_rate, not cfg.router_trains
    moved = bb.layers_of(change, cfg)
    bias = model.params["periods"]["moe"]["router_bias"]
    want = np.concatenate(want_logits)
    in_layers = bb.layers_of(grads, cfg)
    readings = {
        "loss_err": abs(loss - want_loss) / abs(want_loss),
        "logit_err": float(np.linalg.norm(logits - want) / np.linalg.norm(want)),
        **gradient_distances(in_layers, want_grads),
        "ssd_err": ssd_err,
        "moe_err": moe_err,
        "update_err": update_distance(
            _optimizer_leaves(moved, held),
            _optimizer_leaves(
                reference_nemotronh.adamw_first_step(host_params, want_grads, *adamw), held),
            want_grads),
        # in units of the bias's rate
        "bias_err": float(np.abs(change["periods"]["moe"]["router_bias"] - (
            reference_nemotronh.bias_step(bias, counted, rate) - bias)).max()) / rate,
        # a router that the configuration holds must not have moved at all
        "router_moved": max(float(np.abs(layer["moe"]["router"]).max())
                            for layer in moved["layers"] if "moe" in layer) if held else 0.0,
    }
    ctx.say("leaves that carry most of the gradient's squared distance, each with its own "
            "relative distance: " + worst_leaves(in_layers, want_grads))
    finite = np.isfinite(loss) and all(np.isfinite(a).all() for a in _leaves(grads))
    readings["finite"] = 0.0 if finite and np.isfinite(model.losses).all() else 1.0
    ctx.say(f"check of {rows.shape[0]} row(s): the program's loss, gradients and one step "
            f"{t0 - t_start:.1f} s, the reference's {t1 - t0:.1f} s, distances "
            f"{time.monotonic() - t1:.1f} s")
    return readings


def run(ctx) -> Dict:
    import jax

    from predictionio_tpu.models.sequencerec import (
        PreparedData, SeqPreparator, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, batch_order)
    from predictionio_tpu.models.seq_backbone import BackboneConfig
    from predictionio_tpu.obs.profile import default_telemetry

    if "expert_act" not in {f.name for f in dataclasses.fields(BackboneConfig)}:
        # a program from before this backbone reads no ``hybrid_override_pattern``: it
        # would build another model from the keys it knows and train that
        sys.exit("benchmark: this program's backbone has no layer of one part and no ungated "
                 "expert (no expert_act among its keys); the cell cannot run on it")
    cfg, traffic, seed = ctx.config, ctx.workload["traffic_params"], ctx.seed
    algorithm = cfg["algorithm"]
    n_items, seq_len = cfg["vocab_size"], algorithm["seq_len"]
    rows_per_step, steps = traffic["rows_per_step"], algorithm["steps"]
    t_in = time.monotonic()
    # one epoch of a job: as many ids as its steps consume, from ONE catalogue (which ids
    # are popular decides the held experts' load: ``lib/synth_seq_catalogue.py``)
    pieces = synth_seq_catalogue.histories(
        traffic, n_items, steps * rows_per_step * (seq_len + 1), seed)
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
    counters = {**_lfm2_counters(model), **{
        name: stats[name] for name in ("ssd_scan", "ssd_groups", "expert_act", "conv")}}
    step_ms = [s["durationMs"] for s in scopes.job_spans() if s["name"] == "seqrec.step"]
    takes = list(batch_order(rows.shape[0], rows_per_step, algo.params.steps, algorithm["seed"]))
    lengths = [np.bincount(s[:-1][s[:-1] > 0])[1:] for s in segs]
    row_pairs = np.asarray([(n * (n + 1) / 2.0).sum() for n in lengths])
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
            "held_by_step": counters["held_by_step"],
            "n_params": float(sum(a.size for a in jax.tree_util.tree_leaves(model.params))),
        },
    }
    ctx.say("counters: " + json.dumps(
        {k: v for k, v in counters.items() if k != "held_by_step"}))
    ctx.say(f"mixers: {json.dumps(stats['mixers'])}; histories of "
            f"{int(np.concatenate(lengths).mean())} ids at the mean, "
            f"{obs['seq_shape']['pair_sum'] / obs['seq_shape']['tokens']:.0f} causal pairs a slot, "
            f"{float(np.mean([(np.diff(s[:-1]) != 0).sum() for s in segs])):.0f} boundaries a row")
    ctx.say("step ms by step (the last job): " + " ".join(f"{v:.0f}" for v in step_ms))
    ctx.say("loss by step: " + " ".join(f"{v:.3f}" for v in checked.losses))
    held = cfg["experts_held"][1]
    ctx.say("tokens a held expert by step (mean over the expert layers): "
            + " ".join(f"{np.mean(v) / held:.0f}" for v in counters["held_by_step"]))

    # -- correct: the last whole job's parameters and last batch, after the window
    check = traffic["check"]
    tail = check["loss_tail_steps"]
    last = list(batch_order(rows.shape[0], rows_per_step, steps, algorithm["seed"]))[-1]
    readings = {
        "window_compiles": float(obs["window_compiles"]),
        "dropped": max(counters["dropped"], float(np.sum(checked.stats["dropped"]))),
        "loss_last_over_first": float(np.mean(checked.losses[-tail:]) / checked.losses[0]),
    }
    readings.update(_compare(ctx, algo, checked, (rows[last], segs[last]), check))
    ctx.say("readings: " + json.dumps(readings))
    verdict = reference.verdict(
        readings, {**cfg["limits"]["train"], "window_compiles": 0.0, "dropped": 0.0,
                   "finite": 0.0, "router_moved": 0.0})
    obs["verdict"] = verdict
    obs["failed"] = 0 if all(v["ok"] for v in verdict) else len(jobs)
    return obs
