"""A training cell: whole jobs of the recommendation template's
``ALSAlgorithm.train`` (triplets in host memory -> an ``ALSModel`` whose
factors are on the host: what ``pio train`` does after the event read),
back to back on the same triplets until the window closes. A job in
flight at the close runs to its end and counts.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from ..lib import reference, synth
from ..lib.idmaps import id_map
from ..lib.spans import compiles_in


def _algorithm(config: Dict, control: str, iterations: int = None):
    from predictionio_tpu.models import recommendation as rec

    params = dict(config["algorithm"])
    if control:
        params.update(config["control"]["train"][control])
    if iterations is not None:
        params["num_iterations"] = iterations
    return rec.ALSAlgorithm(rec.ALSAlgorithmParams(**params))


def _instrument(ctx) -> Dict:
    """Spans around the functions ``als_train_coo`` reaches, and a
    ``profile`` dict handed to ``als_train`` so that it reports its own
    fenced per-iteration clock and its bucket shapes. Module attributes
    are replaced, not edited: ``als_train_coo`` looks both up by name."""
    from predictionio_tpu.ops import als

    profiles = []
    inner = als.als_train

    def als_train(*args, **kwargs):
        profile = kwargs.setdefault("profile", {})
        profiles.append(profile)
        return inner(*args, **kwargs)

    als.bucketize = ctx.spans.wrap("bucketize", als.bucketize)
    als.stage = ctx.spans.wrap("stage", als.stage)
    als.als_train = ctx.spans.wrap("als_train", als_train)
    return profiles


def run(ctx) -> Dict:
    from predictionio_tpu.models.recommendation import PreparedData
    from predictionio_tpu.obs.profile import default_telemetry
    cfg, traffic, seed = ctx.config, ctx.workload["traffic_params"], ctx.seed
    sizes = cfg["sizes"]
    t_in = time.monotonic()
    law = traffic["law"]
    users, items, vals, truth = synth.ratings(sizes, law, seed)
    data = PreparedData(
        user_map=id_map("u", sizes["n_users"]),
        item_map=id_map("i", sizes["n_items"]),
        users=users, items=items, ratings=vals,
    )
    t_data = time.monotonic()
    profiles = _instrument(ctx) if ctx.trace else []
    algo = _algorithm(cfg, ctx.control)

    def job():
        start = time.monotonic()
        model = algo.train(None, data)
        return model, time.monotonic() - start

    # every program of a job at this run's own bucket shapes: two
    # iterations run both half-step programs and the fused iteration
    _algorithm(cfg, ctx.control, iterations=2).train(None, data)
    del profiles[:]

    telemetry = default_telemetry()
    jit_before = telemetry.snapshot()
    window_start = time.monotonic()
    setup_s = window_start - ctx.t0
    cache = jit_before["cache"]
    ctx.say(
        f"set-up {setup_s:.1f} s: {t_in - ctx.t0:.1f} s to reach the chip, "
        f"{t_data - t_in:.1f} s for ratings and id maps, {window_start - t_data:.1f} s "
        f"for the warm-up job; compile cache {cache['hits']} hits, {cache['misses']} misses")
    jobs, model = [], None
    if ctx.trace:
        from ..lib.spans import traced_window

        # one whole job under the profiler; the window's other jobs
        # would only make the trace larger
        with traced_window(ctx.trace_dir):
            model, seconds = job()
        jobs.append(seconds)
    else:
        while time.monotonic() - window_start < ctx.seconds:
            model, seconds = job()
            jobs.append(seconds)
    window_s = time.monotonic() - window_start
    ctx.say(f"window {window_s:.1f} s: {len(jobs)} job(s) of "
            + ", ".join(f"{j:.2f}" for j in jobs) + " s")
    compiles = telemetry.delta_since(jit_before)

    obs: Dict = {
        "setup_s": setup_s,
        "attempted": len(jobs),
        "jobs_s": jobs,
        "job_mean_s": window_s / len(jobs) if not ctx.trace else jobs[0],
        "window_compiles": compiles_in(compiles),
    }
    if profiles:
        prof = profiles[-1]
        iters = prof["iteration_s"]
        obs["iteration_ms"] = [s * 1e3 for s in iters[1:]]
        obs["stage_s"] = jobs[-1] - sum(iters)
        obs["levers"] = {k: prof[k] for k in ("solve_mode", "gather_dtype", "fused_gather")}
        obs["bucket_shapes"] = prof["bucket_shapes"]
    cap = traffic["max_row_ratings"]
    obs["als_shape"] = {
        "user_degrees": np.minimum(np.bincount(users, minlength=sizes["n_users"]), cap),
        "item_degrees": np.minimum(np.bincount(items, minlength=sizes["n_items"]), cap),
        "n_users": sizes["n_users"], "n_items": sizes["n_items"], "rank": sizes["rank"],
    }

    # -- correct: the last job's model, after the window
    uf, itf = np.asarray(model.user_factors), np.asarray(model.item_factors)
    check = traffic["check"]
    readings: Dict[str, float] = {"window_compiles": float(obs["window_compiles"])}
    hu, hi, hr = synth.holdout(sizes, law, seed, truth, users, items, check["holdout_pairs"])
    degree = np.bincount(items, minlength=sizes["n_items"])
    least = check.get("holdout_min_ratings", 1)
    if least > 1:
        # the gate reads the pairs the model can know something about: a
        # user and an item with ``least`` training ratings or more each
        ctx.say(f"holdout over all {len(hu)} pairs: rmse "
                f"{reference.rmse(uf, itf, hu, hi, hr)!r} (not compared)")
        known = (np.bincount(users, minlength=sizes["n_users"])[hu] >= least) & (degree[hi] >= least)
        hu, hi, hr = hu[known], hi[known], hr[known]
    readings["holdout_pairs_short"] = float(max(0, check.get("holdout_pairs_least", 1) - len(hu)))
    readings["holdout_rmse"] = reference.rmse(uf, itf, hu, hi, hr)
    eligible = np.flatnonzero((degree >= check["min_row_ratings"]) & (degree <= cap))
    rows = synth.rng_for(seed, "rows").choice(
        eligible, size=min(check["half_step_rows"], len(eligible)), replace=False)
    readings.update(reference.half_step_errors(
        uf, itf, users, items, vals, np.sort(rows), cfg["algorithm"]["lambda_"]))
    readings["finite"] = 0.0 if (np.isfinite(uf).all() and np.isfinite(itf).all()) else 1.0
    verdict = reference.verdict(
        readings, {**cfg["limits"]["train"], "holdout_pairs_short": 0.0,
                   "window_compiles": 0.0, "finite": 0.0})
    obs["verdict"] = verdict
    obs["failed"] = 0 if all(v["ok"] for v in verdict) else len(jobs)
    return obs
