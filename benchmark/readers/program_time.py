"""Median device time, in ms, of one execution of the programs whose
name matches ``program`` in the traced window."""

import numpy as np

from ..lib import trace as tr


def read(obs, params):
    trace = obs.get("trace")
    if not trace:
        return None
    events = tr.program_events(trace, params["program"])
    if not events:
        return None
    return float(np.median([d for _, _, d in events])) * 1e3
