"""Device seconds of the traced job under one ``seq.`` scope of the
sequence backbone (forward, recomputation and backward alike): the union
of the intervals of the operations whose name stack holds the scope or a
scope inside it."""

from ..lib import scopes, seq_scopes


def read(obs, params):
    trace = scopes.job_trace(obs)
    if not trace:
        return None
    return seq_scopes.scope_seconds(trace, params["scope"])
