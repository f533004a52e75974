"""A part of the latent-attention backbone's step against the chip's peaks,
in % (``lib/rooflines_mla.py`` against ``peaks.json``). ``model``
``mla_core``: the least time for the attention cores of the traced job's
steps over the device seconds under ``scope``. ``model`` ``step``: the
model's own operations of one step (the job's mean load) over the chip's
peak rate times the median execution of the program named ``program``: a
share of the peak (MFU), not of a roofline."""

import numpy as np

from ..lib import rooflines, rooflines_mla, scopes, seq_scopes
from ..lib import trace as tr


def read(obs, params):
    trace, peaks, shape = scopes.job_trace(obs), obs.get("peaks"), obs.get("seq_shape")
    if not trace or not peaks or not shape or "q_lora_rank" not in shape["config"]:
        return None
    cfg = shape["config"]
    if params["model"] == "step":
        events = tr.program_events(trace, params["program"])
        if not events:
            return None
        held = np.mean(shape["held_by_step"], axis=0).tolist()
        flops, _ = rooflines_mla.step(cfg, {**shape, "held": held}, shape["n_params"])
        seconds = float(np.median([d for _, _, d in events]))
        return 100.0 * flops / peaks["flops_per_s"] / seconds
    seconds = seq_scopes.scope_seconds(trace, params["scope"])
    if not seconds:
        return None
    flops, hbm = rooflines_mla.mla_core(cfg, shape)
    least, bound = rooflines.least_time(shape["steps"] * flops, shape["steps"] * hbm, peaks)
    obs.setdefault("bounds", {})[params["scope"]] = bound
    return 100.0 * least / seconds
