"""A sequence-training cell whose backbone is Phi-4-mini-flash's: a
decoder-hybrid-decoder of Mamba-1 selective scans, differential attention
inside a sliding window and in full, then a gated memory unit on the last
scan's output and cross-attention onto the full layer's keys and values;
LayerNorm with a bias, a dense SwiGLU in every layer, no positions, no router
anywhere. Whole jobs of ``SeqRecAlgorithm.train`` back to back, as
``kinds/train_seqrec.py`` runs them (its packing, its jobs, its window and
its distances are used as they stand), on ``train-joyai-long8k``'s traffic at
one row a step.

``correct`` is decided after the window, on the device the window ran on and
at its shapes, from the last whole job (in a traced run the warm-up job),
all from the objects the job ran (``SeqRecAlgorithm.programs``): the jitted
loss-and-gradient function on the job's last batch and final parameters,
whose aux carries what the first Mamba-1 layer handed its scan and what that
gave, and the q, k, v and the difference of the sliding layer's core; and one
donated optimizer step from fresh moments. Against
``lib/reference_phi4flash.py`` (float32 at ``highest`` precision, the
recurrence slot by slot, the convolution as four shifted adds and a bias,
two full score matrices a head pair with the three masks written out, one row
and one layer at a time, plain AdamW in numpy): the loss, sampled logits,
gradient groups, the step, ``selscan_err`` (the scan's ``y`` of that layer
against the reference's recurrence on the very ``c``, ``Delta``, ``B``, ``C``
the timed function made) and ``swa_core_err`` (the sliding layer's ``(A1 -
lambda A2) v`` against the reference's two softmaxes on that call's own q, k,
v, inside window and history).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict

import numpy as np

from ..lib import reference, reference_phi4flash, rooflines_phi4flash, scopes, synth_seq
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in
from .train_seqrec import _backbone_file, _distance, _leaves, update_distance
from .train_seqrec_mla import worst_leaves

#: which leaves of a layer (reference layout) belong to which gradient group
_GROUPS = {
    **{name: (lambda layer, name=name: layer.get(name))
       for name in ("mamba1", "swa", "full", "cross", "gmu")},
    "mlp": lambda layer: layer["mlp"],
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


def gradient_distances(got: Dict, want: Dict) -> Dict[str, float]:
    """``grad_err.<group>``: the program's gradient against the
    reference's, both in the reference's layout. The head is the embedding:
    its part is in ``embed``."""
    out = {}
    for name, pick in _GROUPS.items():
        pairs = [(pick(a), pick(b)) for a, b in zip(got["layers"], want["layers"])]
        pairs = [(a, b) for a, b in pairs if b is not None]
        out[f"grad_err.{name}"] = _distance([a for a, _ in pairs], [b for _, b in pairs])
    out["grad_err.norms"] = max(
        out["grad_err.norms"], _distance(got["final_norm"], want["final_norm"]))
    out["grad_err.embed"] = _distance(got["embed"], want["embed"])
    return out


def pairs_first(t):
    """The program's q or k of one row [members x pairs, L, hd] (all first
    members, then all second) as the reference has them: [pairs, 2, L, hd]."""
    t = np.asarray(t, np.float32)
    return t.reshape((2, t.shape[0] // 2) + t.shape[1:]).transpose(1, 0, 2, 3)


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
    (loss, (hidden, _, ran)), grads = loss_and_grad(params, *on_device)
    valid = np.asarray(bb.split_rows(rows, segs)[3])
    slots = synth_seq.sampled_slots(ctx.seed, valid, check["sampled_positions"])
    logits = np.concatenate([np.asarray(bb.logits_of(cfg, params, hidden[b][jnp.asarray(at)]))
                             for b, at in enumerate(slots)])
    loss = float(loss)
    # the first Mamba-1 layer: what its scan was handed and gave; the sliding
    # layer: what its core was handed (the values twice: once is the pairs') and gave
    scan = {name: np.asarray(ran[name][0]) for name in ("c", "dt", "B", "C", "y")}
    core = {name: np.asarray(ran[name][0]) for name in ("q", "k", "v", "lam", "o")}
    a_log = model.params["periods"]["mamba1"]["A_log"][0, 0]
    grads = jax.tree_util.tree_map(np.asarray, grads)
    del hidden, params, ran
    t0 = time.monotonic()
    each = range(len(rows))
    want_y = [np.asarray(reference_phi4flash.selective_scan_of(
        *(scan[name][b] for name in ("c", "dt", "B", "C")), a_log, segs[b, :-1])) for b in each]
    selscan_err = _distance([np.asarray(scan["y"][b], np.float32) for b in each], want_y)
    want_o = [np.asarray(reference_phi4flash.differential_core_of(
        pairs_first(core["q"][b]), pairs_first(core["k"][b]),
        core["v"][b][: core["v"].shape[1] // 2], core["lam"], segs[b, :-1],
        ctx.config["sliding_window"])) for b in each]
    swa_core_err = _distance([np.asarray(core["o"][b], np.float32) for b in each], want_o)
    del scan, core, want_y, want_o
    host_params = bb.layers_of(model.params, cfg)
    want_loss, want_grads, want_logits = reference_phi4flash.loss_and_grads(
        jax.tree_util.tree_map(jnp.asarray, host_params), rows, segs, ctx.config, sample=slots)
    t1 = time.monotonic()
    adamw = (ctx.config["algorithm"]["learning_rate"], *(
        ctx.config["algorithm"]["adamw"][name] for name in ("b1", "b2", "eps", "weight_decay")))
    want = np.concatenate(want_logits)
    in_layers = bb.layers_of(grads, cfg)
    readings = {
        "loss_err": abs(loss - want_loss) / abs(want_loss),
        "logit_err": float(np.linalg.norm(logits - want) / np.linalg.norm(want)),
        **gradient_distances(in_layers, want_grads),
        "selscan_err": selscan_err,
        "swa_core_err": swa_core_err,
        "update_err": update_distance(
            bb.layers_of(change, cfg),
            reference_phi4flash.adamw_first_step(host_params, want_grads, *adamw), want_grads),
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

    if "mamba_dt_rank" not in {f.name for f in dataclasses.fields(BackboneConfig)}:
        # a program from before this backbone knows no ``mamba1`` layer and no window
        sys.exit("benchmark: this program's backbone has no Mamba-1 selective scan "
                 "(no mamba_dt_rank among its keys); the cell cannot run on it")
    cfg, traffic, seed = ctx.config, ctx.workload["traffic_params"], ctx.seed
    algorithm = cfg["algorithm"]
    n_items, seq_len = cfg["vocab_size"], algorithm["seq_len"]
    rows_per_step, steps = traffic["rows_per_step"], algorithm["steps"]
    t_in = time.monotonic()
    # one epoch of a job: as many ids as its steps consume
    pieces = synth_seq.histories(traffic, n_items, steps * rows_per_step * (seq_len + 1), seed)
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
            learning_rate=algorithm["learning_rate"], seed=algorithm["seed"]))

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
    routers = sorted(name for name in stats if "expert" in name or "router" in name)
    counters = {"pack_fill_pct": 100.0 * stats["fill"], **{
        name: stats[name] for name in ("selective_scan", "conv", "attn_tiles_skipped_by_window")}}
    step_ms = [s["durationMs"] for s in scopes.job_spans() if s["name"] == "seqrec.step"]
    takes = list(batch_order(rows.shape[0], rows_per_step, algo.params.steps, algorithm["seed"]))
    lengths = [np.bincount(s[:-1][s[:-1] > 0])[1:] for s in segs]
    row_pairs = np.asarray([(n * (n + 1) / 2.0).sum() for n in lengths])
    in_window = np.asarray([rooflines_phi4flash.pairs_in_window(n, cfg["sliding_window"])
                            for n in lengths])
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
            "swa_pair_sum": float(np.mean([in_window[take].sum() for take in takes])),
            # no expert layer anywhere: every step held nothing
            "held_by_step": [[] for _ in range(algo.params.steps)],
            "n_params": float(sum(a.size for a in jax.tree_util.tree_leaves(model.params))),
        },
    }
    ctx.say("counters: " + json.dumps(counters))
    ctx.say(f"mixers: {json.dumps(stats['mixers'])}; histories of "
            f"{int(np.concatenate(lengths).mean())} ids at the mean, "
            f"{obs['seq_shape']['pair_sum'] / obs['seq_shape']['tokens']:.0f} causal pairs a slot, "
            f"{obs['seq_shape']['swa_pair_sum'] / obs['seq_shape']['tokens']:.0f} of them inside the window, "
            f"{float(np.mean([(np.diff(s[:-1]) != 0).sum() for s in segs])):.0f} boundaries a row")
    ctx.say("step ms by step (the last job): " + " ".join(f"{v:.0f}" for v in step_ms))
    ctx.say("loss by step: " + " ".join(f"{v:.3f}" for v in checked.losses))

    # -- correct: the last whole job's parameters and last batch, after the window
    check = traffic["check"]
    tail = check["loss_tail_steps"]
    last = list(batch_order(rows.shape[0], rows_per_step, steps, algorithm["seed"]))[-1]
    readings = {
        "window_compiles": float(obs["window_compiles"]),
        # a backbone without a router must count nothing of one
        "router_counters": float(len(routers)),
        "loss_last_over_first": float(np.mean(checked.losses[-tail:]) / checked.losses[0]),
    }
    readings.update(_compare(ctx, algo, checked, (rows[last], segs[last]), check))
    ctx.say("readings: " + json.dumps(readings))
    verdict = reference.verdict(
        readings, {**cfg["limits"]["train"], "window_compiles": 0.0, "router_counters": 0.0,
                   "finite": 0.0})
    obs["verdict"] = verdict
    obs["failed"] = 0 if all(v["ok"] for v in verdict) else len(jobs)
    return obs
