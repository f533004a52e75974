"""Device-idle seconds of the traced job by the program's own host span
(``pio.<name>`` annotations on the profiler's clock; gaps cut at span
edges, the innermost span wins, gaps under 20 us left out).

With ``spans``: the idle seconds under those spans, summed. Without: the
share, in %, of the idle seconds that fall under any ``pio.`` span other
than the root ``pio.train``."""

from ..lib import scopes


def read(obs, params):
    trace = scopes.job_trace(obs)
    if not trace:
        return None
    idle = scopes.idle_by_span(trace)
    named = {k: v for k, v in idle.items() if k.startswith(scopes.SPAN_PREFIX)}
    if not named:  # a program that writes no ``pio.`` span
        return None
    if "spans" in params:
        return sum(named.get(name, 0.0) for name in params["spans"])
    gaps = sum(v for k, v in idle.items() if k != "(between ops)")
    if gaps <= 0:
        return None
    under = sum(v for k, v in named.items() if k != scopes.ROOT_SPAN)
    return 100.0 * under / gaps
