"""What the readers of the program's own spans and scopes (PR 24) share:
the traced job's profile loaded once a run, device seconds under a
``jax.named_scope``, idle seconds under a ``pio.`` host span, and the
traced job's spans from the program's ``SpanStore``.

On a program without those spans and scopes (the parent of PR 24) every
function here finds nothing and returns ``None`` or an empty result; the
readers then report nothing.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from . import manifest, xplane
from . import trace as tr

#: The program's host annotations (``obs/trace.py``) start with this,
#: and its device scopes (``ops/als.py``) with ``als.``.
SPAN_PREFIX = "pio."
ROOT_SPAN = "pio.train"


def job_trace(obs: Dict) -> Optional[Dict]:
    """``lib/xplane.load`` of the run's newest profile, cached on
    ``obs``: ``run.py``'s own load keeps neither ``pio.`` host events nor
    the operations' name stacks."""
    if "pio_trace" not in obs:
        try:
            path = tr.newest_xplane(os.path.join(manifest.WORK, "trace"))
        except FileNotFoundError:
            obs["pio_trace"] = None
        else:
            obs["pio_trace"] = xplane.load(path, (tr.SPAN_PREFIX, SPAN_PREFIX))
    return obs["pio_trace"]


def first_device(trace: Dict) -> Optional[str]:
    """The first device plane that ran an operation."""
    for plane, lines in sorted(trace["devices"].items()):
        if lines.get(tr.OP_LINE):
            return plane
    return None


def scoped_ops(trace: Dict) -> List[Tuple[float, float, List[str]]]:
    """(start, end, the ``als.`` scopes of its name stack) of every
    operation of the first device inside the traced window. A fusion
    carries the stack of its root instruction; a ``while`` carries none
    (its body's operations do)."""
    if "scoped_ops" not in trace:  # six readers walk the same events
        plane = first_device(trace)
        lo, hi = tr.window_of(trace) if plane else (0.0, 0.0)
        trace["scoped_ops"] = [
            (start, start + seconds, xplane.scopes_of(stack, "als."))
            for (_, start, seconds), stack in zip(
                trace["devices"][plane][tr.OP_LINE], trace["stacks"][plane])
            if seconds > 0 and start >= lo and start + seconds <= hi
        ] if plane else []
    return trace["scoped_ops"]


def scope_seconds(trace: Dict, scope: str) -> Optional[float]:
    """Union of the intervals of the operations whose name stack holds
    ``scope``; ``None`` where no operation does."""
    spans = [(s, e) for s, e, scopes in scoped_ops(trace) if scope in scopes]
    if not spans:
        return None
    return sum(e - s for s, e in tr.union(spans))


def idle_by_span(trace: Dict) -> Dict[str, float]:
    """Idle seconds of the first device inside the window by the
    program's own host span: ``lib/trace.idle_gaps`` (gaps cut at span
    edges, innermost span wins, gaps under 20 us apart) over the
    ``pio.`` spans alone. Keys are span names without their tags, plus
    ``(no span)`` and ``(between ops)``."""
    view = dict(trace)
    view["host"] = [
        ev for ev in trace["host"]
        if ev[0].startswith(SPAN_PREFIX) or ev[0] == tr.SPAN_PREFIX + "window"
    ]
    totals: Dict[str, float] = {}
    for label, seconds in tr.idle_gaps(view, top=1 << 30):
        name = label.split(" ")[0] if label.startswith(SPAN_PREFIX) else label
        totals[name] = totals.get(name, 0.0) + seconds
    return totals


def job_spans() -> List[Dict]:
    """The spans of the newest ``train`` root in the program's default
    tracer: the job that ran last, which in a traced run is the traced
    one. Empty on a program without ``default_tracer``."""
    try:
        from predictionio_tpu.obs.trace import default_tracer
    except ImportError:
        return []
    store = default_tracer().store
    roots = [s for s in store.dump() if s["name"] == "train" and s["parentId"] is None]
    return store.for_trace(roots[-1]["traceId"]) if roots else []
