"""Cross-process request tracing: ``X-PIO-Trace`` ids + in-process spans.

One online request touches three processes (query server → storage
server → replica) plus background delivery threads; when its tail
latency spikes, per-server histograms say *that* it was slow, not
*where*. A trace answers where:

- the client (or the first server to see the request) mints a **trace
  id** and sends it in the ``X-PIO-Trace`` header;
- every server creates a **server span** at admission carrying that id,
  and every instrumented stage inside the process (micro-batch queue
  wait, device dispatch, remote storage calls, feedback delivery) adds
  child spans;
- outbound calls (``storage/remote.py``, feedback POSTs) forward the
  header, so the downstream server's spans join the same trace;
- each process keeps its spans in a bounded in-memory ring buffer
  (:class:`SpanStore`) dumped via ``GET /traces.json``; ``pio trace
  <id>`` stitches the dumps from a node list back into one timeline.

This is deliberately *not* a distributed tracer with collectors and
sampling — it is the smallest thing that makes a single slow request
explainable across the fleet (the profiling-hooks-first philosophy of
the training side, ``utils/profiling.py``, applied to serving).

Ambient propagation mirrors ``utils/resilience.deadline_scope``: a
contextvar carries the live request's :class:`SpanContext` so deep call
sites (the remote storage client under an engine's ``supplement``) pick
it up without signature changes. Contextvars do not cross threads —
work handed to another thread (MicroBatcher workers, the feedback pool)
must capture :func:`current_context` at submit time and pass it
explicitly (``Tracer.span(..., parent=ctx)``).

Clocks are injectable (``Tracer(clock=..., wall=...)``): every trace
test runs with zero wall-clock sleeps.

One span, two sinks. A span opened with :meth:`Tracer.span` or
:meth:`Tracer.server_span` is also entered as a
``jax.profiler.TraceAnnotation`` named ``pio.<name>`` (its tags as a
`` k=v`` suffix), so that while a profiler session runs the same span
lies in the profiler's trace, on the device trace's clock, beside the
operations the chip ran under it; outside a session the annotation is a
flag test. A process that never imported JAX has no profiler to write
to and records to the store alone. Code that runs under no server (a
training job) opens its spans with the module-level :func:`span`, which
records into the ambient request's tracer or, with none, into
:func:`default_tracer`: a root span there starts a new trace id, so one
training job is one trace, as one request is.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import secrets
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional

__all__ = [
    "TRACE_HEADER",
    "SpanContext",
    "SpanStore",
    "Tracer",
    "current_context",
    "default_tracer",
    "new_trace_id",
    "span",
]

#: Wire header carrying the trace id. Value contract: an opaque token of
#: 1-64 URL-safe characters; anything longer/weirder is truncated and
#: sanitized at admission (a garbled header must degrade, never 500).
TRACE_HEADER = "X-PIO-Trace"

_MAX_ID_LEN = 64
_ID_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_."
)


def new_trace_id() -> str:
    """16 hex chars — unique enough for a per-fleet debugging session."""
    return secrets.token_hex(8)


def sanitize_trace_id(value: Optional[str]) -> Optional[str]:
    """Header value → usable trace id, or None when absent/empty."""
    if not value:
        return None
    cleaned = "".join(c for c in value.strip() if c in _ID_OK)[:_MAX_ID_LEN]
    return cleaned or None


@dataclasses.dataclass(frozen=True)
class SpanContext:
    """What a child span (possibly on another thread) needs of its
    parent: the ids and the tracer whose store it records into."""

    trace_id: str
    span_id: str
    tracer: "Tracer"


_ambient_span: contextvars.ContextVar = contextvars.ContextVar(
    "pio_span", default=None
)


def current_context() -> Optional[SpanContext]:
    """The span context of the request this thread is serving, if any."""
    return _ambient_span.get()


def _annotation(name: str, tags: Optional[Dict[str, object]]):
    """The profiler's half of a span: a ``TraceAnnotation`` named
    ``pio.<name>`` with the tags as a suffix (``pio.als.stage
    side=user``), or nothing to enter in a process that has not imported
    JAX (no import is made here: ``obs/`` works without it, and a
    process without JAX has no profiler session to write into)."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    label = "pio." + name
    if tags:
        label += "".join(f" {k}={v}" for k, v in tags.items())
    return profiler.TraceAnnotation(label)


class SpanStore:
    """Bounded ring buffer of finished spans (newest win; a busy server
    forgets old traces instead of growing without bound)."""

    def __init__(self, capacity: int = 2048):
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)

    @property
    def capacity(self) -> int:
        """Spans kept; a store this long has begun to forget."""
        return self._spans.maxlen

    def add(self, span: dict) -> None:
        with self._lock:
            self._spans.append(span)

    def dump(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def for_trace(self, trace_id: str) -> List[dict]:
        return [s for s in self.dump() if s.get("traceId") == trace_id]

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


class Tracer:
    """Per-process (per-server) span factory bound to one store.

    ``clock`` measures durations (monotonic); ``wall`` stamps span start
    times (epoch seconds) so cross-process dumps sort into one timeline.
    Both injectable for sleep-free tests.
    """

    def __init__(
        self,
        service: str,
        store: Optional[SpanStore] = None,
        clock: Callable[[], float] = time.monotonic,
        wall: Callable[[], float] = time.time,
    ):
        self.service = service
        self.store = store if store is not None else SpanStore()
        self.clock = clock
        self.wall = wall

    # -- span creation ----------------------------------------------------
    @contextlib.contextmanager
    def server_span(
        self,
        name: str,
        header_value: Optional[str] = None,
        tags: Optional[Dict[str, object]] = None,
    ) -> Iterator[SpanContext]:
        """The admission span: joins the trace named by an incoming
        ``X-PIO-Trace`` header, or roots a fresh one. Sets the ambient
        context for the request's dynamic extent."""
        trace_id = sanitize_trace_id(header_value) or new_trace_id()
        yield from self._run_span(name, trace_id, None, tags, kind="server")

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        tags: Optional[Dict[str, object]] = None,
        parent: Optional[SpanContext] = None,
    ) -> Iterator[SpanContext]:
        """A child of ``parent`` (default: the ambient context; with
        neither, roots a fresh trace). Use an explicit ``parent`` when
        crossing threads — the ambient contextvar does not follow."""
        parent = parent if parent is not None else current_context()
        trace_id = parent.trace_id if parent else new_trace_id()
        parent_id = parent.span_id if parent else None
        yield from self._run_span(name, trace_id, parent_id, tags)

    def _run_span(self, name, trace_id, parent_id, tags, kind="internal"):
        ctx = SpanContext(trace_id, secrets.token_hex(4), self)
        token = _ambient_span.set(ctx)
        start_wall = self.wall()
        t0 = self.clock()
        error: Optional[str] = None
        try:
            # entered and left on this thread, as an annotation has to
            # be: hand-timed ``record`` spans get none
            with _annotation(name, tags):
                yield ctx
        except BaseException as exc:
            error = type(exc).__name__
            raise
        finally:
            _ambient_span.reset(token)
            self.record(
                name=name,
                ctx=ctx,
                parent_id=parent_id,
                start_wall=start_wall,
                duration_s=self.clock() - t0,
                tags=tags,
                kind=kind,
                error=error,
            )

    def record(
        self,
        name: str,
        ctx: SpanContext,
        parent_id: Optional[str],
        start_wall: float,
        duration_s: float,
        tags: Optional[Dict[str, object]] = None,
        kind: str = "internal",
        error: Optional[str] = None,
    ) -> None:
        """Append one finished span (also the entry point for callers
        that measured timing themselves, e.g. the MicroBatcher's
        queue-wait span whose start predates the dispatch thread)."""
        span = {
            "traceId": ctx.trace_id,
            "spanId": ctx.span_id,
            "parentId": parent_id,
            "service": self.service,
            "kind": kind,
            "name": name,
            "startMs": round(start_wall * 1000.0, 3),
            "durationMs": round(max(0.0, duration_s) * 1000.0, 3),
        }
        if tags:
            span["tags"] = {k: v for k, v in tags.items()}
        if error:
            span["error"] = error
        self.store.add(span)

    def child_context(self, parent: Optional[SpanContext]) -> SpanContext:
        """A pre-minted context for a span whose lifetime is managed by
        hand (cross-thread timing); pair with :meth:`record`."""
        trace_id = parent.trace_id if parent else new_trace_id()
        return SpanContext(trace_id, secrets.token_hex(4), self)


_SINGLETON_LOCK = threading.Lock()
_default: Optional[Tracer] = None


def default_tracer() -> Tracer:
    """The process's tracer for code that runs under no server (a
    training job), beside ``obs.profile.default_telemetry``."""
    global _default
    with _SINGLETON_LOCK:
        if _default is None:
            _default = Tracer("process")
        return _default


def span(name: str, tags: Optional[Dict[str, object]] = None):
    """A span under the ambient context, in that context's tracer; with
    no ambient context, a root span in :func:`default_tracer`.

    The store reads ``tags`` when the span ends: what the block adds to
    the dict it passed (a count known only once the work is done) is
    recorded with the span. The profiler's label is made on entry."""
    ctx = current_context()
    tracer = ctx.tracer if ctx is not None else default_tracer()
    return tracer.span(name, tags=tags)
