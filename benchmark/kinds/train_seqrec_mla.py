"""A sequence-training cell whose backbone has latent attention, a sigmoid
router balanced by a bias and a multi-token-prediction module: whole jobs
of ``SeqRecAlgorithm.train`` back to back, as ``kinds/train_seqrec.py`` runs
them (its packing, its jobs, its window, its counters, and its distances are
used as they stand), on long histories.

``correct`` is decided after the window, on the device the window ran on and
at its shapes, from the last whole job (in a traced run the warm-up job),
all from the objects the job ran (``SeqRecAlgorithm.programs``): the jitted
loss-and-gradient function on the job's last batch and final parameters,
whose aux carries the module's hidden states and what the first sparse
layer's attention was handed and gave; and one donated optimizer step from
fresh moments, which also steps the routers' bias. Against
``lib/reference_joyai.py`` (float32 at ``highest`` precision, a full score
matrix a head, dense experts, one row and one layer at a time, plain AdamW
and the bias rule in numpy): both losses, sampled logits of both heads,
gradient groups, the step (a router the configuration holds,
``router_trains`` off, must not have moved), the bias step on the step's own
counts, and ``attn_core_err``: the attention output of the first sparse layer against
the reference's softmax on the very q, k, v the timed function made, in
units of what rounding that softmax to the core's output type costs (a
sound core reads about 1: its distance is its output's rounding).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict

import numpy as np

from ..lib import reference, reference_joyai, scopes, synth_seq
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in
from .train_seqrec import _backbone_file, _counters, _distance, _leaves, update_distance

#: which leaves of a trunk layer (reference layout) belong to which
#: gradient group; the prediction module is a group of its own
_GROUPS = {
    "latent": lambda layer: layer["attn"],
    "router": lambda layer: layer["moe"]["router"] if "moe" in layer else None,
    "experts": lambda layer: layer["moe"]["experts"] if "moe" in layer else None,
    "shared": lambda layer: layer["moe"]["shared"] if "moe" in layer else None,
    "dense": lambda layer: layer.get("mlp"),
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}
_BIASES = (("router_tokens", lambda tree: tree["periods"]["ffn"]["router_bias"]),
           ("mtp_router_tokens", lambda tree: tree["mtp"]["block"]["ffn"]["router_bias"]))


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
    for name in ("embed", "head", "mtp"):
        out[f"grad_err.{name}"] = _distance(got[name], want[name])
    return out


def worst_leaves(got: Dict, want: Dict, top: int = 6) -> str:
    """The leaves (reference layout) that carry most of the squared distance
    of the whole gradient, each with its own relative distance: where a
    group's reading comes from."""
    import jax

    rows = []
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32).ravel(), np.asarray(b, np.float32).ravel()
        d = a - b
        rows.append((float(np.dot(d, d)), float(np.dot(b, b)), jax.tree_util.keystr(path)))
    total = sum(r[0] for r in rows) or 1.0
    rows.sort(reverse=True)
    return "; ".join(f"{name} {np.sqrt(err / max(ref, 1e-300)):.4f} ({100 * err / total:.0f} %)"
                     for err, ref, name in rows[:top])


def _optimizer_leaves(layout: Dict, routers_too: bool) -> Dict:
    """A tree in the reference's layout with every router's bias at zero,
    and every router's matrix where the configuration holds it
    (``router_trains`` off): the optimizer's step is compared without the
    leaves it does not move."""
    def block(layer):
        if "moe" not in layer:
            return layer
        still = {"router_bias": np.zeros_like(layer["moe"]["router_bias"])}
        if routers_too:
            still["router"] = np.zeros_like(layer["moe"]["router"])
        return {**layer, "moe": {**layer["moe"], **still}}

    return {**layout, "layers": [block(layer) for layer in layout["layers"]],
            "mtp": {**layout["mtp"], "block": block(layout["mtp"]["block"])}}


def _routers(layout: Dict):
    """Every router's matrix of a tree in the reference's layout."""
    blocks = layout["layers"] + [layout["mtp"]["block"]]
    return [np.asarray(b["moe"]["router"]) for b in blocks if "moe" in b]


def _compare(ctx, algo, model, batch, check: Dict) -> Dict[str, float]:
    """The readings of ``correct`` that need the reference."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import seq_backbone as bb

    from predictionio_tpu.obs.profile import default_telemetry

    def compile_s():
        return default_telemetry().snapshot()["cache"]["backend_compile_s"]

    cfg = model.config
    # the configuration as its file states it, and the one number the
    # reference needs from the ``backbone`` group
    ref_cfg = {**ctx.config, "mtp_loss_weight": cfg.mtp_loss_weight}
    rows, segs = batch
    opt_init, step, loss_and_grad = algo.programs(cfg)
    t_start, c_start = time.monotonic(), compile_s()
    on_device = jnp.asarray(rows), jnp.asarray(segs)
    # the step first, on a device that holds what a job's first step finds
    # there (it donates the parameters, so they are put there twice)
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    stepped = step(params, opt_init(params), *on_device)
    new_params, counted = stepped[0], {name: np.asarray(stepped[3][name]) for name, _ in _BIASES}
    del stepped
    change = jax.tree_util.tree_map(lambda new, old: np.asarray(new) - old, new_params, model.params)
    del new_params
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    (loss, (hidden, counters, ran)), grads = loss_and_grad(params, *on_device)
    valid = np.asarray(bb.split_rows(rows, segs)[3])
    slots = synth_seq.sampled_slots(ctx.seed, valid, check["sampled_positions"])
    logits = np.concatenate([np.asarray(bb.logits_of(cfg, params, hidden[b][jnp.asarray(at)]))
                             for b, at in enumerate(slots)])
    mtp_logits = np.concatenate([np.asarray(bb.logits_of(
        cfg, params, ran["mtp_hidden"][b][jnp.asarray(at)], params["mtp"]["norm"]))
        for b, at in enumerate(slots)])
    loss, mtp_loss = float(loss), float(counters["mtp_loss"])
    core = {name: ran[name][0] for name in ("q", "k", "v", "o")}  # the first sparse layer's
    grads = jax.tree_util.tree_map(np.asarray, grads)
    del hidden, params, ran
    t0, c0 = time.monotonic(), compile_s()
    want_o = [np.asarray(reference_joyai.softmax_attention_of(
        core["q"][b], core["k"][b], core["v"][b], segs[b, :-1])) for b in range(len(rows))]
    got_o = [np.asarray(core["o"][b], np.float32) for b in range(len(rows))]
    # what the output's own type costs: the reference rounded to it (never
    # under float32's half unit, which a float32 core would divide by)
    rounded = [np.asarray(w.astype(core["o"].dtype), np.float32) for w in want_o]
    core_err, rounding = _distance(got_o, want_o), max(_distance(rounded, want_o), 2.0 ** -24)
    del core, rounded
    host_params = bb.layers_of(model.params, cfg)
    want_loss, _, want_mtp_loss, want_grads, want_logits, want_mtp_logits = (
        reference_joyai.loss_and_grads(
            jax.tree_util.tree_map(jnp.asarray, host_params), rows, segs, ref_cfg, sample=slots))
    t1, c1 = time.monotonic(), compile_s()
    adamw = (ctx.config["algorithm"]["learning_rate"], *(
        ctx.config["algorithm"]["adamw"][name] for name in ("b1", "b2", "eps", "weight_decay")))
    rate, held = cfg.router_bias_rate, not cfg.router_trains
    moved = bb.layers_of(change, cfg)
    bias_err = max(
        float(np.abs(pick(change) - (reference_joyai.bias_step(
            pick(model.params), counted[name], rate) - pick(model.params))).max()) / rate
        for name, pick in _BIASES)
    want, want_mtp = np.concatenate(want_logits), np.concatenate(want_mtp_logits)
    readings = {
        "loss_err": abs(loss - want_loss) / abs(want_loss),
        "mtp_loss_err": abs(mtp_loss - want_mtp_loss) / abs(want_mtp_loss),
        "logit_err": float(np.linalg.norm(logits - want) / np.linalg.norm(want)),
        "mtp_logit_err": float(np.linalg.norm(mtp_logits - want_mtp) / np.linalg.norm(want_mtp)),
        **gradient_distances(bb.layers_of(grads, cfg), want_grads),
        "attn_core_err": core_err / rounding,
        "update_err": update_distance(
            _optimizer_leaves(moved, held),
            _optimizer_leaves(reference_joyai.adamw_first_step(host_params, want_grads, *adamw), held),
            want_grads),
        "bias_err": bias_err,
        # a router that the configuration holds must not have moved at all
        "router_moved": max(float(np.abs(r).max()) for r in _routers(moved)) if held else 0.0,
    }
    ctx.say(f"attention core of the first sparse layer: distance to the reference's softmax "
            f"{core_err:.3g}, the output type's rounding {rounding:.3g}")
    ctx.say("leaves that carry most of the gradient's squared distance, each with its own "
            "relative distance: " + worst_leaves(bb.layers_of(grads, cfg), want_grads))
    finite = np.isfinite(loss) and all(np.isfinite(a).all() for a in _leaves(grads))
    readings["finite"] = 0.0 if finite and np.isfinite(model.losses).all() else 1.0
    ctx.say(f"check of {rows.shape[0]} row(s): the program's loss, gradients and one step "
            f"{t0 - t_start:.1f} s ({c0 - c_start:.1f} s of it compiling), the reference's "
            f"{t1 - t0:.1f} s ({c1 - c0:.1f} s compiling), distances {time.monotonic() - t1:.1f} s")
    return readings


def _mla_counters(model) -> Dict:
    """The other kind's counters, and what this backbone adds to them: the
    prediction module's block among the held experts, the load over the
    router's whole width (what the bias balances), the bias, the module's
    loss."""
    stats = model.stats
    counters = _counters(model)
    mtp_held = np.asarray(stats["mtp_expert_tokens_by_step"], np.float64).sum(axis=1)
    counters["held_by_step"] = [
        trunk + [float(module)] for trunk, module in zip(counters["held_by_step"], mtp_held)]
    counters["dropped"] += float(stats["mtp_dropped"])
    wide = np.asarray(stats["router_tokens"], np.float64)
    wide = np.concatenate([wide.reshape(-1, wide.shape[-1]),
                           np.asarray(stats["mtp_router_tokens"], np.float64)[None]])
    counters.update({
        "router_tokens_least": float(wide.min()),
        "router_tokens_mean": float(wide.mean()),
        "router_tokens_most": float(wide.max()),
        "router_load_max_over_mean": float((wide.max(axis=1) / wide.mean(axis=1)).max()),
        "router_bias_abs_max": float(stats["router_bias_abs_max"]),
        "mtp_loss_last": float(np.asarray(stats["mtp_loss_by_step"])[-1]),
    })
    return counters


def run(ctx) -> Dict:
    import jax

    from predictionio_tpu.models.sequencerec import (
        PreparedData, SeqPreparator, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, batch_order)
    from predictionio_tpu.models.seq_backbone import BackboneConfig
    from predictionio_tpu.obs.profile import default_telemetry

    if "attention" not in {f.name for f in dataclasses.fields(BackboneConfig)}:
        # a program from before this backbone would build another model
        # from the keys it knows and train that
        sys.exit("benchmark: this program's backbone has no latent attention; "
                 "the cell cannot run on it")
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

    counters = _mla_counters(model)
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
    ctx.say(f"histories of {int(np.concatenate(lengths).mean())} ids at the mean, "
            f"{obs['seq_shape']['pair_sum'] / obs['seq_shape']['tokens']:.0f} causal pairs a slot")
    ctx.say("step ms by step (the last job): " + " ".join(f"{v:.0f}" for v in step_ms))
    ctx.say("loss by step: " + " ".join(f"{v:.3f}" for v in checked.losses))
    ctx.say("the module's loss by step: "
            + " ".join(f"{v:.3f}" for v in checked.stats["mtp_loss_by_step"]))
    held = cfg["experts_held"][1]
    ctx.say("tokens a held expert by step (mean over the expert layers, the module's too): "
            + " ".join(f"{np.mean(v) / held:.0f}" for v in counters["held_by_step"]))

    # -- correct: the last whole job's parameters and last batch, after the window
    check = traffic["check"]
    tail = check["loss_tail_steps"]
    last = list(batch_order(rows.shape[0], rows_per_step, steps, algorithm["seed"]))[-1]
    readings = {
        "window_compiles": float(obs["window_compiles"]),
        "dropped": max(counters["dropped"], float(
            np.sum(checked.stats["dropped"]) + checked.stats["mtp_dropped"])),
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
