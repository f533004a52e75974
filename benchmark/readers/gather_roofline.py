"""The XLA row gather's share of its roofline over the traced job, in %:
the least time for the bytes it has to move (``lib/gather_roofline.py``,
every iteration the trace shows, against the peak bytes/s of
``peaks.json``) over the device seconds under the scope ``als.gather``."""

from ..lib import gather_roofline, scopes
from ..lib import trace as tr


def read(obs, params):
    trace, peaks = scopes.job_trace(obs), obs.get("peaks")
    if not trace or not peaks or "bucket_shapes" not in obs:
        return None
    seconds = scopes.scope_seconds(trace, params["scope"])
    if not seconds:
        return None
    # the first iteration runs as two half programs, the others as one
    iterations = len(tr.program_events(trace, params["iteration"])) + len(
        tr.program_events(trace, params["half"])) / 2.0
    moved = iterations * gather_roofline.gather_bytes_per_iteration(
        obs["bucket_shapes"], obs["als_shape"]["rank"], obs["levers"])
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / seconds
