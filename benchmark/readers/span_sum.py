"""Seconds, summed, of the traced job's spans named ``span`` in the
program's own ``SpanStore`` (host clock): the spans ``ops/als.py`` and
``ALSAlgorithm.train`` open themselves, found by the job's trace id."""

from ..lib import scopes


def read(obs, params):
    found = [s for s in scopes.job_spans() if s["name"] == params["span"]]
    if not found:
        return None
    return sum(s["durationMs"] for s in found) / 1e3
