"""A sequence-training cell: whole jobs of the sequencerec template's
``SeqRecAlgorithm.train`` (packed rows in host memory -> parameters drawn on
the device -> N optimizer steps -> a ``SeqRecModel`` whose parameters are on
the host: what ``pio train`` does after the event read and the packing),
back to back on the same rows until the window closes. A job in flight at
the close runs to its end and counts.

``correct`` is decided after the window, on the device the window ran on and
at its shapes, from the last whole job (in a traced run the warm-up job,
because the traced one is cut short): the program's own jitted loss-and-gradient
function (the one its optimizer step is built from) on the job's last batch
and final parameters, and one optimizer step of its own from there, against
``lib/reference_qwen3next.py`` (float32 at ``highest`` precision,
token-by-token delta rule, dense experts, one row and one layer at a time,
plain AdamW). The job's batches are named from the cell's own rows
(``sequencerec.batch_order``): the trainer keeps none for the check.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

from ..lib import manifest, reference, reference_qwen3next, scopes, synth_seq
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in

#: which leaves of a layer (reference layout) belong to which gradient group
_GROUPS = {
    "deltanet": lambda layer: layer.get("linear"),
    "attention": lambda layer: layer.get("full"),
    "router": lambda layer: layer["moe"]["router"],
    "experts": lambda layer: layer["moe"]["experts"],
    "shared": lambda layer: (layer["moe"]["shared"], layer["moe"]["shared_gate"]),
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


def _backbone_file(ctx, overrides: Dict) -> str:
    """The configuration's own file, or a copy in the work directory with
    the control's entries laid over its ``backbone`` group."""
    path = os.path.join(manifest.ROOT, "configs", f"{ctx.workload['config']}.json")
    if not overrides:
        return path
    merged = dict(ctx.config)
    merged["backbone"] = {**ctx.config["backbone"], **overrides}
    path = os.path.join(manifest.WORK, "backbone-control.json")
    with open(path, "w") as f:
        json.dump(merged, f)
    return path


def _leaves(tree) -> List[np.ndarray]:
    import jax

    return [np.asarray(a, np.float32).ravel() for a in jax.tree_util.tree_leaves(tree)]


def _distance(got, want) -> float:
    """Relative L2 distance of two pytrees of one structure, all leaves as
    one vector (float32 products summed in float64 leaf by leaf: 626 M
    numbers are compared on the machine that holds the chip)."""
    num = den = 0.0
    for a, b in zip(_leaves(got), _leaves(want)):
        d = a - b
        num += float(np.dot(d, d))
        den += float(np.dot(b, b))
    return float(np.sqrt(num / max(den, 1e-300)))


def update_distance(change, want_change, want_grads) -> float:
    """``update_err``: the parameter change of one optimizer step against
    plain AdamW on the reference's gradient, as a relative L2 distance over
    the entries whose reference gradient is at least its leaf's root mean
    square. Adam's first step is lr * sign(g) nearly everywhere, so where g
    is small beside the bfloat16 products' rounding the step's direction is
    a coin's toss in either build; where g is large a sound step agrees and
    a wrong rule (rate, sign, moments, their correction) does not."""
    num = den = 0.0
    for got, want, g in zip(_leaves(change), _leaves(want_change), _leaves(want_grads)):
        keep = np.abs(g) >= np.sqrt(float(np.dot(g, g)) / max(g.size, 1))
        d = np.where(keep, got - want, np.float32(0))
        w = np.where(keep, want, np.float32(0))
        num += float(np.dot(d, d))
        den += float(np.dot(w, w))
    return float(np.sqrt(num / max(den, 1e-300)))


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
    """The readings of ``correct`` that need the reference. Everything the
    program gives here comes from the objects the timed job ran
    (``SeqRecAlgorithm.programs``): its loss-and-gradient function, whose
    aux carries what the first layer's delta rule ran on and gave, and one
    donated optimizer step from fresh moments."""
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
    logits = [np.asarray(bb.logits_of(cfg, params, hidden[b][jnp.asarray(at)]))
              for b, at in enumerate(slots)]
    loss = float(loss)
    ran = {name: np.asarray(a[0]) for name, a in ran.items()}  # the first period's
    grads = jax.tree_util.tree_map(np.asarray, grads)
    del hidden, params
    t0 = time.monotonic()
    host_params = bb.layers_of(model.params, cfg)
    want_loss, want_grads, want_logits = reference_qwen3next.loss_and_grads(
        jax.tree_util.tree_map(jnp.asarray, host_params), rows, segs, ctx.config, sample=slots)
    want_o = [reference_qwen3next.delta_rule_of(
        *(ran[name][b] for name in ("q", "k", "v", "g", "beta")), segs[b, :-1])
        for b in range(len(rows))]
    t1 = time.monotonic()
    adamw = (ctx.config["algorithm"]["learning_rate"], *(
        ctx.config["algorithm"]["adamw"][name] for name in ("b1", "b2", "eps", "weight_decay")))
    got, want = np.concatenate(logits), np.concatenate(want_logits)
    in_layers = bb.layers_of(grads, cfg)
    readings = {
        "loss_err": abs(loss - want_loss) / abs(want_loss),
        "logit_err": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
        **gradient_distances(in_layers, want_grads),
        "delta_rule_err": _distance(list(ran["o"]), want_o),
        "update_err": update_distance(
            bb.layers_of(change, cfg),
            reference_qwen3next.adamw_first_step(host_params, want_grads, *adamw), want_grads),
    }
    gates = [_distance([a["linear"][name] for name in ("A_log", "dt_bias")],
                       [b["linear"][name] for name in ("A_log", "dt_bias")])
             for a, b in zip(in_layers["layers"], want_grads["layers"]) if "linear" in b]
    ctx.say("gradient of the gates' own parameters (A_log, dt_bias) by DeltaNet layer, "
            "relative distance: " + " ".join(f"{d:.4f}" for d in gates))
    finite = np.isfinite(loss) and all(np.isfinite(a).all() for a in _leaves(grads))
    readings["finite"] = 0.0 if finite and np.isfinite(model.losses).all() else 1.0
    ctx.say(f"check of {rows.shape[0]} row(s): the program's loss, gradients and one step "
            f"{t0 - t_start:.1f} s, the reference's {t1 - t0:.1f} s, distances "
            f"{time.monotonic() - t1:.1f} s")
    return readings


def _counters(model) -> Dict:
    stats = model.stats
    tokens = np.asarray(stats["expert_tokens"], np.float64)  # [periods, layers, held]
    per_layer = tokens.reshape(-1, tokens.shape[-1])
    # [steps, periods, layers of a period, held] -> [steps, layers, held]
    by_step = np.asarray(stats["expert_tokens_by_step"], np.float64)
    by_step = by_step.reshape(len(by_step), -1, by_step.shape[-1])
    return {
        "pack_fill_pct": 100.0 * stats["fill"],
        "expert_tokens_least": float(per_layer.min()),
        "expert_tokens_mean": float(per_layer.mean()),
        "expert_tokens_most": float(per_layer.max()),
        # over every step of the job, not its last one
        "expert_tokens_job_mean": float(by_step.mean()),
        "expert_load_max_over_mean": float(
            (per_layer.max(axis=1) / np.maximum(per_layer.mean(axis=1), 1e-9)).max()),
        "absent_weight_pct": 100.0 * float(np.mean(stats["absent_weight"])),
        "dropped": float(np.sum(stats["dropped"])),
        "held_by_step": by_step.sum(axis=2).tolist(),
    }


def run(ctx) -> Dict:
    import jax

    from predictionio_tpu.models.sequencerec import (
        PreparedData, SeqPreparator, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, batch_order)
    from predictionio_tpu.obs.profile import default_telemetry

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

    # every program of a job at the window's own shapes: the draw of the
    # parameters, the optimizer's state, the step, the way back. A traced
    # run's job is cut to ``trace_steps``, too few for the loss to fall, so
    # there the warm-up is a whole job and ``correct`` is decided from it.
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

        # one whole job under the profiler, of ``trace_steps`` steps: a
        # step is tens of thousands of device operations, and the trace of
        # a full job would be too large to bring back
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

    counters = _counters(model)
    step_ms = [s["durationMs"] for s in scopes.job_spans() if s["name"] == "seqrec.step"]
    # the job's batches, named from this cell's own rows
    takes = list(batch_order(rows.shape[0], rows_per_step, algo.params.steps, algorithm["seed"]))
    row_pairs = np.asarray([  # causal pairs inside the histories of a row's input slots
        (n * (n + 1) / 2.0).sum() for n in (np.bincount(s[:-1][s[:-1] > 0]) for s in segs)])
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
    ctx.say("step ms by step (the last job): " + " ".join(f"{v:.0f}" for v in step_ms))
    ctx.say("loss by step: " + " ".join(f"{v:.3f}" for v in checked.losses))
    held = cfg["experts_held"][1]
    ctx.say("tokens a held expert by step (mean over layers and experts): "
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
                   "finite": 0.0})
    obs["verdict"] = verdict
    obs["failed"] = 0 if all(v["ok"] for v in verdict) else len(jobs)
    return obs
