"""A program's share of its roofline, in %: the least time the chip
needs for the work (``lib/rooflines.py``'s own count of bytes and
operations, against ``peaks.json``) over the device time the trace shows
for it. ``model`` says which count:

``als_iteration``: every execution of the iteration program does the same
work; the median execution is judged.
``topk``: each execution is judged at its own padded batch, read from the
``bench.batch_predict b=<n>`` span it started in, on the path the program
resolved for that batch at warm-up; the median share is reported.
"""

import re

import numpy as np

from ..lib import rooflines
from ..lib import trace as tr


def read(obs, params):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if not trace or not peaks:
        return None
    events = tr.program_events(trace, params["program"])
    if not events:
        return None
    if params["model"] == "als_iteration":
        flops, hbm = rooflines.als_iteration(obs["als_shape"])
        least, bound = rooflines.least_time(flops, hbm, peaks)
        obs.setdefault("bounds", {})[params["program"]] = bound
        return 100.0 * least / float(np.median([d for _, _, d in events]))
    if params["model"] == "topk":
        shape = obs["topk"]
        shares = []
        for (_, _, seconds), span in zip(
            events, tr.enclosing_span(trace, r"batch_predict b=\d+$", events)
        ):
            if span is None:
                continue
            b = int(re.search(r"b=(\d+)$", span).group(1))
            flops, hbm = rooflines.topk(
                b, shape["n_items"], shape["rank"], shape["k"], shape["paths"][b])
            shares.append(100.0 * rooflines.least_time(flops, hbm, peaks)[0] / seconds)
        return float(np.median(shares)) if shares else None
    raise ValueError(f"unknown roofline model {params['model']!r}")
