"""Spans the benchmark puts, from its own side, around the functions of
the program that a cell reaches. Each span is timed on the host clock
(kept in memory, read by the readers) and, while a profiler trace is
running, written into that trace as a ``jax.profiler.TraceAnnotation``
named ``bench.<name>``, so that idle gaps of the device can be put down
to what the host was doing. Nothing in the program is edited: a wrapper
replaces an attribute of a module or an object for the length of a run.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Tuple


class Spans:
    """name -> [(start, seconds)], appended from any thread."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._spans: Dict[str, List[Tuple[float, float]]] = {}

    def add(self, name: str, start: float, seconds: float) -> None:
        with self._lock:
            self._spans.setdefault(name, []).append((start, seconds))

    def durations(self, name: str, since: float = 0.0, until: float = float("inf")) -> List[float]:
        with self._lock:
            return [d for s, d in self._spans.get(name, []) if since <= s < until]

    def wrap(self, name: str, fn: Callable, label: Callable[..., str] = None) -> Callable:
        """``fn`` with a span around every call. ``label(*args, **kw)``
        may add a suffix to the annotation's name (the host-clock span
        keeps the plain name)."""
        from jax.profiler import TraceAnnotation

        def wrapped(*args: Any, **kwargs: Any):
            tag = "bench." + name + (label(*args, **kwargs) if label else "")
            start = time.monotonic()
            with TraceAnnotation(tag):
                try:
                    return fn(*args, **kwargs)
                finally:
                    self.add(name, start, time.monotonic() - start)

        wrapped.__wrapped__ = fn
        return wrapped


class traced_window:
    """The profiler over a block, with the block inside a ``bench.window``
    span. The Python tracer is off: it records every Python call of every
    thread, which slowed the server under trace to a twentieth of its
    rate (42 batches in 4 s, PR 23); host ``TraceAnnotation`` spans and
    the device planes do not need it."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir

    def __enter__(self):
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        options = ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._span = TraceAnnotation("bench.window")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        return False


def compiles_in(delta: Dict) -> int:
    """Compiles in a ``JitTelemetry.delta_since``: jit-cache growth of the
    instrumented functions plus backend compiles. A measured window has
    to show none."""
    return int(
        sum(fn["compiles"] for fn in delta.get("fns", {}).values())
        + delta.get("cache", {}).get("backend_compiles", 0)
    )
