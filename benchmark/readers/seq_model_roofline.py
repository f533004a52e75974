"""A part of a sequence backbone's step against the chip's peaks, in %,
with the module that counts it a parameter: ``lib`` names the module under
``benchmark/lib`` and ``model`` the function in it. A count ``step`` is the
model's own operations of one optimizer step (the job's mean load on the
held experts) over the chip's peak rate times the median execution of the
program named ``program``: a share of the peak (MFU), not of a roofline.
Any other count is the least time for that part of the traced job's steps,
each with the assignments it counted itself, over the device seconds under
``scope``. ``needs`` names a key of the configuration without which the
count does not apply: the reader then finds nothing, as it does on a
program without the scope or the program."""

import importlib

import numpy as np

from ..lib import rooflines, scopes, seq_scopes
from ..lib import trace as tr


def read(obs, params):
    trace, peaks, shape = scopes.job_trace(obs), obs.get("peaks"), obs.get("seq_shape")
    if not trace or not peaks or not shape or params["needs"] not in shape["config"]:
        return None
    cfg = shape["config"]
    count = getattr(importlib.import_module(f"benchmark.lib.{params['lib']}"), params["model"])
    if params["model"] == "step":
        events = tr.program_events(trace, params["program"])
        if not events:
            return None
        held = np.mean(shape["held_by_step"], axis=0).tolist()
        flops, _ = count(cfg, {**shape, "held": held}, shape["n_params"])
        seconds = float(np.median([d for _, _, d in events]))
        return 100.0 * flops / peaks["flops_per_s"] / seconds
    seconds = seq_scopes.scope_seconds(trace, params["scope"])
    if not seconds:
        return None
    counts = [count(cfg, {**shape, "held": held}) for held in shape["held_by_step"]]
    flops, hbm = (sum(c[i] for c in counts) for i in (0, 1))
    least, bound = rooflines.least_time(flops, hbm, peaks)
    obs.setdefault("bounds", {})[params["scope"]] = bound
    return 100.0 * least / seconds
