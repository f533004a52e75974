"""A part of the sequence backbone's step against its roofline, in %: the
least time the chip needs for the work of the traced job's steps
(``lib/rooflines_seq.py``, against ``peaks.json``) over the device seconds
the trace shows for it. ``model`` says which count; with ``scope`` the time
is that of the ``seq.`` scope over the whole job, with ``program`` it is
the median execution of the program of that name against one step."""

import numpy as np

from ..lib import rooflines, rooflines_seq, scopes, seq_scopes
from ..lib import trace as tr


def read(obs, params):
    trace, peaks, shape = scopes.job_trace(obs), obs.get("peaks"), obs.get("seq_shape")
    if not trace or not peaks or not shape:
        return None
    cfg = shape["config"]
    if params["model"] == "step":
        events = tr.program_events(trace, params["program"])
        if not events:
            return None
        # the median execution against a step of the job's mean load
        held = np.mean(shape["held_by_step"], axis=0).tolist()
        flops, hbm = rooflines_seq.step(cfg, {**shape, "held": held}, shape["n_params"])
        seconds = float(np.median([d for _, _, d in events]))
    else:
        seconds = seq_scopes.scope_seconds(trace, params["scope"])
        if not seconds:
            return None
        # every step of the job with the assignments it counted itself
        counts = [getattr(rooflines_seq, params["model"])(cfg, {**shape, "held": held})
                  for held in shape["held_by_step"]]
        flops, hbm = (sum(c[i] for c in counts) for i in (0, 1))
    least, bound = rooflines.least_time(flops, hbm, peaks)
    obs.setdefault("bounds", {})[params.get("scope", params.get("program"))] = bound
    return 100.0 * least / seconds
