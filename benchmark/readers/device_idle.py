"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device's op intervals / window)."""

from ..lib import trace as tr


def read(obs, params):
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    busy, window = tr.busy_seconds(trace)
    return 100.0 * (1.0 - busy / window)
