"""Device seconds of the traced job under the ``seq.`` scopes of the
sequence backbone. The scopes are ``jax.named_scope`` names; in the
backward pass and in recomputed layers a name arrives wrapped
(``transpose(jvp(seq.moe))``, ``checkpoint/rematted_computation/seq.attn``),
so the names are found anywhere in an operation's name stack, not at the
start of a component as ``lib/scopes.py`` finds the ``als.`` ones.

On a program without these scopes every function here finds nothing.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Set, Tuple

from . import scopes
from . import trace as tr

_NAME = re.compile(r"seq\.[a-z_]+(?:\.[a-z_]+)*")


def names_in(stack: str) -> Set[str]:
    return set(_NAME.findall(stack))


def scoped_ops(trace: Dict) -> List[Tuple[float, float, Set[str]]]:
    """(start, end, the ``seq.`` names of its stack) of every operation of
    the first device inside the traced window."""
    if "seq_scoped_ops" not in trace:
        plane = scopes.first_device(trace)
        lo, hi = tr.window_of(trace) if plane else (0.0, 0.0)
        trace["seq_scoped_ops"] = [
            (start, start + seconds, names_in(stack))
            for (_, start, seconds), stack in zip(
                trace["devices"][plane][tr.OP_LINE], trace["stacks"][plane])
            if seconds > 0 and start >= lo and start + seconds <= hi
        ] if plane else []
    return trace["seq_scoped_ops"]


def scope_seconds(trace: Dict, scope: str) -> Optional[float]:
    """Union of the intervals of the operations under ``scope`` or a scope
    inside it (``seq.moe`` covers ``seq.moe.experts``); ``None`` where no
    operation is."""
    inner = scope + "."
    spans = [(s, e) for s, e, names in scoped_ops(trace)
             if any(n == scope or n.startswith(inner) for n in names)]
    if not spans:
        return None
    return sum(e - s for s, e in tr.union(spans))
