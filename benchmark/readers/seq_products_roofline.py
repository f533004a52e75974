"""A part of a sequence backbone's step whose products the compiler lifts
out of their named scope, against its roofline, in %: the least time for
that part of the traced job's steps (``lib/<lib>.<model>``, each step with
the assignments it counted itself) over the device seconds of the
operations under ``scope`` TOGETHER WITH those whose own name starts with
``events`` and whose name stack lies under ``outer``. The held experts'
grouped products are such: ``ragged_dot`` becomes a custom call named
``ragged-dot-...`` that keeps the stack of the jitted pass that called it
(``seq.moe``) and loses the inner ``seq.moe.experts``, which then holds only
the casts and the activation; ``readers/seq_roofline.py`` times those alone.
``needs`` names a key of the configuration without which the count does not
apply: the reader then finds nothing, as it does on a program without the
scopes."""

import importlib

from ..lib import rooflines, scopes, seq_scopes
from ..lib import trace as tr


def _under(names, scope: str) -> bool:
    return any(n == scope or n.startswith(scope + ".") for n in names)


def seconds_of(trace, scope: str, outer: str, events: str):
    """Union of the intervals, inside the traced window, of the first device's
    operations under ``scope`` and of those named ``events...`` under ``outer``."""
    plane = scopes.first_device(trace)
    if not plane:
        return None
    lo, hi = tr.window_of(trace)
    spans = []
    for (name, start, seconds), stack in zip(
            trace["devices"][plane][tr.OP_LINE], trace["stacks"][plane]):
        if seconds <= 0 or start < lo or start + seconds > hi:
            continue
        names = seq_scopes.names_in(stack)
        # (an event's name is its instruction's text: ``%ragged-dot-none.3 = f32[...] ...``)
        if _under(names, scope) or (
                name.lstrip("%").startswith(events) and _under(names, outer)):
            spans.append((start, start + seconds))
    return sum(e - s for s, e in tr.union(spans)) if spans else None


def read(obs, params):
    trace, peaks, shape = scopes.job_trace(obs), obs.get("peaks"), obs.get("seq_shape")
    if not trace or not peaks or not shape or params["needs"] not in shape["config"]:
        return None
    seconds = seconds_of(trace, params["scope"], params["outer"], params["events"])
    if not seconds:
        return None
    count = getattr(importlib.import_module(f"benchmark.lib.{params['lib']}"), params["model"])
    counts = [count(shape["config"], {**shape, "held": held}) for held in shape["held_by_step"]]
    flops, hbm = (sum(c[i] for c in counts) for i in (0, 1))
    least, bound = rooflines.least_time(flops, hbm, peaks)
    obs.setdefault("bounds", {})[params["scope"]] = bound
    return 100.0 * least / seconds
