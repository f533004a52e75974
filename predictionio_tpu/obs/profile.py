"""Performance observability: jit compile/retrace telemetry + phase profiling.

The ROADMAP's verdict on rounds 1–5 is that the control plane matured
while BENCH stayed flat — and nothing in the system could *say why*:
time lost to XLA compiles, silent per-request retraces, or gather-bound
solves all looked identical from outside. This module is the seeing
layer (docs/observability.md#profiling):

- :class:`JitTelemetry` — process-wide compile/retrace accounting at the
  jit boundary. Call sites (trainer solves in ``ops/als.py``, the
  serving top-k dispatch in ``ops/scoring.py``, continuous fold-in in
  ``continuous/foldin.py``) route jitted calls through
  :meth:`JitTelemetry.call` / :meth:`JitTelemetry.wrap`; a call that
  grows the jitted function's compilation cache is a compile, and any
  compile after a function's first is a **retrace** (a new signature —
  the silent 20-40 s tax ``ops/scoring.pad_pow2`` exists to bound).
  Bound registries expose ``pio_jit_compiles_total{fn}`` /
  ``pio_jit_retraces_total{fn}`` / ``pio_jit_compile_seconds{fn}`` on
  ``/metrics``; a live request's ambient trace context gets a
  ``jit.compile`` span so an unexpected compile is visible in
  ``pio trace`` timelines. ``attach_monitoring()`` additionally taps
  ``jax.monitoring`` (wired in by
  ``utils/jax_cache.enable_compilation_cache``) for backend-compile
  durations, persistent compilation-cache hit/miss counts, and the
  three phases that bring ANY program to the device, instrumented
  boundary or bare ``jax.jit``: each is a ``jit.trace`` / ``jit.lower``
  / ``jit.backend`` span under the ambient span and a total in
  ``snapshot()["cache"]``.
- :class:`PhaseProfiler` — ``utils/profiling.StepTimer`` grown device
  fences and roofline accounting: each phase records wall time, a
  fenced (``block_until_ready``) device-complete time, and optional
  FLOP/byte estimates from which MFU and HBM-bandwidth utilization are
  computed against the peaks of the device that ran
  (:data:`DEVICE_PEAKS`, keyed by ``device_kind``; a kind that is not in
  the table gets achieved rates only). Disabled (``PIO_PROFILE`` unset),
  a phase is a no-op
  context that never touches the clock or the device — hooks may stay
  in production paths.

Like the rest of ``obs/``, importing this module requires neither jax
nor numpy; everything device-facing is imported lazily inside the few
functions that need it.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional

from .metrics import MetricsRegistry
from .trace import current_context

__all__ = [
    "DEVICE_PEAKS",
    "JitTelemetry",
    "PhaseProfiler",
    "PROFILE_ENV",
    "default_telemetry",
    "profiling_enabled",
    "render_profile_report",
    "roofline",
]

#: Environment switch for the *deep* profiling hooks (device fences,
#: per-phase accounting). The cheap jit compile/retrace counters are
#: always on — an int compare per dispatch.
PROFILE_ENV = "PIO_PROFILE"

#: Device peaks for roofline estimates, keyed by ``device_kind`` as JAX
#: reports it. v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s
#: bf16 MXU → ~half attainable for f32 solves; 819 GB/s HBM. One home,
#: shared by bench.py and ``pio profile``. A kind that is not here has
#: no peaks: its roofline carries achieved rates and no utilization.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s_f32": 98.5e12, "hbm_bytes_per_s": 819e9},
}

#: compile-duration samples kept per function for replay-on-bind and
#: reports; compiles are rare, so a small cap loses nothing real
_MAX_SAMPLES = 256

#: ``jax.monitoring``'s names (``jax/_src/dispatch.py``) of the phases
#: that bring a program to the device, by the ``jit.<phase>`` span each
#: becomes. JAX raises each three times: a scalar carrying the start
#: time when the phase begins, a duration and a time span when it ends,
#: all on the thread that runs the phase, with the program's name as
#: ``fun_name``.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

#: A phase inside another one (tracing a step traces every jitted
#: function it calls: 1,255 of them in the toy Qwen3-Next step, 88 of a
#: millisecond or more) is a span only from here up; its seconds are the
#: enclosing span's either way. What it encloses is shorter still, so no
#: recorded span names a parent that was left out.
_NESTED_FLOOR_S = 0.01


class _OpenPhase:
    """A phase between JAX's event at its start and the one at its end:
    ``span`` is the open span where the phase is a real one (the
    outermost on its thread, under an ambient span), ``ctx`` that span's
    context or, for a phase inside another one, the context it is given
    when something has to name it (:func:`_context_of`); ``tags`` are
    read when the span is recorded."""

    __slots__ = ("phase", "fn", "span", "ctx", "tags")

    def __init__(self, phase, fn, span, ctx, tags):
        self.phase, self.fn, self.span, self.ctx, self.tags = (
            phase, fn, span, ctx, tags)


def _context_of(stack: List[_OpenPhase], at: int):
    """The span context of the open phase ``stack[at]``. A phase inside
    another one gets its context only when it is asked for: when it is
    recorded itself, or when a phase inside it is and has to name its
    parent (a step's trace opens thousands that never are)."""
    entry = stack[at]
    if entry.ctx is None and at > 0:
        outer = _context_of(stack, at - 1)
        if outer is not None:
            entry.ctx = outer.tracer.child_context(outer)
    return entry.ctx


def profiling_enabled(env: Optional[Dict[str, str]] = None) -> bool:
    """Is deep profiling (``PIO_PROFILE``) switched on?"""
    value = (env if env is not None else os.environ).get(PROFILE_ENV, "")
    return value not in ("", "0", "off", "false")


def roofline(
    flops: float,
    hbm_bytes: float,
    seconds: float,
    peaks: Optional[Dict[str, float]] = None,
) -> Dict[str, float]:
    """FLOP/byte/time → achieved TFLOP/s and GB/s, plus ``mfu`` and
    ``hbm_util`` against ``peaks``. ``peaks=None`` looks up the device
    that ran (``jax.devices()[0].device_kind`` in :data:`DEVICE_PEAKS`);
    for a kind that is not in the table the two utilization keys are
    absent — never a share of another chip's peak."""
    if peaks is None:
        import jax

        peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    per_s = 1.0 / seconds if seconds > 0.0 else 0.0
    out = {
        "tflops_per_s": flops * per_s / 1e12,
        "hbm_gb_per_s": hbm_bytes * per_s / 1e9,
    }
    if peaks is not None:
        out["mfu"] = flops * per_s / peaks["flops_per_s_f32"]
        out["hbm_util"] = hbm_bytes * per_s / peaks["hbm_bytes_per_s"]
    return out


class _InstrumentedJit:
    """Callable wrapper around one jitted function: every call routes
    through the telemetry's compile accounting; every other attribute
    (``.lower``, ``._cache_size``, …) forwards to the wrapped function
    so AOT tooling keeps working against the instrumented name."""

    __slots__ = ("_telemetry", "_name", "__wrapped__")

    def __init__(self, telemetry: "JitTelemetry", name: str, fn):
        self._telemetry = telemetry
        self._name = name
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        return self._telemetry.call(
            self._name, self.__wrapped__, *args, **kwargs
        )

    def __getattr__(self, item):
        return getattr(self.__wrapped__, item)


class JitTelemetry:
    """Process-wide compile/retrace accounting at the jit boundary.

    Internal state is the source of truth (training and bench read it
    without any server); bound :class:`MetricsRegistry` instances mirror
    it onto ``/metrics``. Binding replays current totals into the fresh
    registry's counters so a server created *after* its deploy-time
    compiles still exposes them. Registries are held weakly — a test
    suite creating hundreds of servers must not grow a permanent list.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        #: fn name -> {"compiles", "retraces", "samples": [seconds, ...]}
        self._fns: Dict[str, dict] = {}
        #: fn -> highest cache size already credited. Two threads racing
        #: the same first compile both see the cache grow (the loser
        #: waits on jax's compile lock, then reads after > before);
        #: crediting only growth BEYOND the recorded high-water mark
        #: keeps the count at one compile, no phantom retrace. Keyed by
        #: the fn itself, weakly: a GC'd jitted fn (lru_cache eviction)
        #: drops its mark instead of leaking it onto an id()-recycled
        #: successor, and the map cannot grow past the live fn set.
        self._seen_sizes: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )
        self._cache_hits = 0
        self._cache_misses = 0
        self._backend_compiles = 0
        self._backend_s = 0.0
        #: the first ``_MAX_SAMPLES`` of them, for replay-on-bind
        self._backend_samples: List[float] = []
        #: seconds by phase (``trace``, ``lower``: those that lie inside
        #: no other phase on their thread, so each second counts once;
        #: ``retrieval``: reading a program back from the persistent
        #: cache), the newest samples of each for replay-on-bind, and
        #: the compile seconds the cache's hits saved
        self._phase_s: Dict[str, float] = {
            "trace": 0.0, "lower": 0.0, "retrieval": 0.0,
        }
        self._phase_samples: Dict[str, List[float]] = {
            phase: [] for phase in self._phase_s
        }
        self._saved_s = 0.0
        #: the :class:`_OpenPhase` stack of each thread, innermost last
        self._open = threading.local()
        self._bound: List[weakref.ref] = []
        self._monitoring = False

    # -- the jit boundary --------------------------------------------------
    def call(self, name: str, fn, *args, **kwargs):
        """Call ``fn`` (a jitted callable), detecting whether THIS call
        compiled by probing its compilation-cache size around the call.
        A non-jitted callable (no ``_cache_size``) passes through
        untouched — callers never need to know which they hold."""
        size_fn = getattr(fn, "_cache_size", None)
        if size_fn is None:
            return fn(*args, **kwargs)
        try:
            before = size_fn()
        except Exception:
            return fn(*args, **kwargs)
        t0 = self._clock()
        out = fn(*args, **kwargs)
        try:
            after = size_fn()
        except Exception:
            after = before
        if after > before:
            with self._lock:
                try:
                    credited = self._seen_sizes.get(fn, 0)
                    fresh = after > max(before, credited)
                    if fresh:
                        self._seen_sizes[fn] = after
                except TypeError:
                    # unhashable/non-weakrefable callable: fall back to
                    # the raw probe (worst case: a racing first compile
                    # double-counts on such a fn)
                    fresh = True
            if fresh:
                self._record_compile(name, self._clock() - t0)
        return out

    def wrap(self, name: str, fn) -> _InstrumentedJit:
        """Permanently instrument a module-level jitted function."""
        return _InstrumentedJit(self, name, fn)

    def _record_compile(self, name: str, seconds: float) -> None:
        with self._lock:
            st = self._fns.setdefault(
                name, {"compiles": 0, "retraces": 0, "samples": []}
            )
            retrace = st["compiles"] >= 1
            st["compiles"] += 1
            if retrace:
                st["retraces"] += 1
            if len(st["samples"]) < _MAX_SAMPLES:
                st["samples"].append(float(seconds))
            bound = self._live_registries()
        for registry in bound:
            inst = self._instruments(registry)
            inst["compiles"].inc(1, fn=name)
            if retrace:
                inst["retraces"].inc(1, fn=name)
            inst["compile_s"].observe(seconds, fn=name)
        # a compile inside a live request is exactly the thing a trace
        # should show: record it against the ambient span, if any
        ctx = current_context()
        if ctx is not None:
            try:
                tracer = ctx.tracer
                tracer.record(
                    "jit.compile",
                    tracer.child_context(ctx),
                    ctx.span_id,
                    start_wall=tracer.wall() - seconds,
                    duration_s=seconds,
                    tags={"fn": name, "retrace": retrace},
                )
            except Exception:
                pass  # telemetry must never fail the traced call

    # -- jax.monitoring taps ----------------------------------------------
    def attach_monitoring(self) -> bool:
        """Tap ``jax.monitoring`` for what it tells of a program's way to
        the device: tracing, lowering and the backend's compile (or, on
        a hit in the persistent cache, the retrieval) of every program
        as ``jit.trace`` / ``jit.lower`` / ``jit.backend`` spans and as
        totals, backend-compile durations, and the persistent cache's
        hit/miss events. Idempotent, best-effort (False when jax is
        unavailable); listeners are process-global and registered at
        most once."""
        with self._lock:
            if self._monitoring:
                return True
            self._monitoring = True
        try:
            import jax.monitoring as monitoring
        except Exception:
            with self._lock:
                self._monitoring = False
            return False

        def on_event(name: str, **kwargs) -> None:
            if name.endswith("/cache_hits"):
                self._cache_event("hit")
            elif name.endswith("/cache_misses"):
                self._cache_event("miss")

        def on_duration(name: str, duration: float, **kwargs) -> None:
            if name.endswith("backend_compile_duration"):
                with self._lock:
                    self._backend_compiles += 1
                    self._backend_s += float(duration)
                    if len(self._backend_samples) < _MAX_SAMPLES:
                        self._backend_samples.append(float(duration))
                    bound = self._live_registries()
                for registry in bound:
                    self._instruments(registry)["backend_s"].observe(duration)
            elif name.endswith("/cache_retrieval_time_sec"):
                self._add_phase_seconds("retrieval", duration)
            elif name.endswith("/compile_time_saved_sec"):
                with self._lock:
                    self._saved_s += float(duration)

        def on_phase_start(name: str, value: float, **kwargs) -> None:
            phase = _PHASES.get(name)
            if phase is not None:
                self._phase_started(phase, str(kwargs.get("fun_name", "")))

        def on_phase_end(name: str, start: float, end: float, **kwargs) -> None:
            phase = _PHASES.get(name)
            if phase is not None:
                self._phase_ended(
                    phase, str(kwargs.get("fun_name", "")), start, end
                )

        try:
            monitoring.register_event_listener(on_event)
            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_scalar_listener(on_phase_start)
            monitoring.register_event_time_span_listener(on_phase_end)
        except Exception:
            # un-latch so a later call may retry; a half-registered set
            # (the first succeeded, a later one raised) at worst
            # re-registers a listener, double-counting being the lesser
            # evil than a silently-dead tap for the process lifetime
            with self._lock:
                self._monitoring = False
            return False
        return True

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _cache_event(self, outcome: str) -> None:
        """A hit or a miss of the persistent cache: JAX raises it on the
        compiling thread inside the backend phase, whose span it tags."""
        with self._lock:
            if outcome == "hit":
                self._cache_hits += 1
            else:
                self._cache_misses += 1
        stack = self._stack()
        if stack and stack[-1].phase == "backend":
            stack[-1].tags["cache"] = outcome

    def _add_phase_seconds(self, phase: str, seconds: float) -> None:
        seconds = float(seconds)
        with self._lock:
            self._phase_s[phase] += seconds
            samples = self._phase_samples[phase]
            if len(samples) < _MAX_SAMPLES:
                samples.append(seconds)
            bound = self._live_registries()
        for registry in bound:
            self._instruments(registry)["phase_s"].observe(seconds, phase=phase)

    def _phase_started(self, phase: str, fn: str) -> None:
        """A phase begins on this thread. The outermost one under an
        ambient span is a real span from here to :meth:`_phase_ended`
        (a profiler session holds it as ``pio.jit.<phase>``, on its own
        clock); one inside another phase, a function traced while
        another is, is only noted here and recorded when it ends, if it
        lasted. Under no ambient span only the totals grow:
        ``jit.compile``'s rule."""
        stack = self._stack()
        opened = ctx = None
        # until the cache says otherwise
        tags = {"fn": fn, "cache": "off"} if phase == "backend" else None
        if not stack:
            try:
                ambient = current_context()
                if ambient is not None:
                    tags = tags or {"fn": fn}
                    opened = ambient.tracer.span("jit." + phase, tags=tags)
                    ctx = opened.__enter__()
            except Exception:
                opened = ctx = None  # telemetry must never fail the traced call
        stack.append(_OpenPhase(phase, fn, opened, ctx, tags))

    def _phase_ended(self, phase: str, fn: str, start: float, end: float) -> None:
        stack = self._stack()
        # the phase that ends is the innermost open one; a start whose
        # end never came (the listeners were cleared meanwhile) is
        # dropped on the way, and an end without its start (the tap
        # attached from another thread meanwhile) counts without a span
        at = len(stack) - 1
        while at >= 0 and (stack[at].phase != phase or stack[at].fn != fn):
            at -= 1
        if at >= 0:
            ended = stack[at]
            try:
                if ended.span is not None:
                    ended.span.__exit__(None, None, None)
                elif at > 0 and end - start >= _NESTED_FLOOR_S:
                    ctx = _context_of(stack, at)
                    if ctx is not None:
                        # JAX's own time span, on ``time.time()``: the
                        # clock ``Tracer.wall`` stamps ``startMs`` with
                        ctx.tracer.record(
                            "jit." + phase, ctx, stack[at - 1].ctx.span_id,
                            start_wall=start, duration_s=end - start,
                            tags=ended.tags or {"fn": fn},
                        )
            except Exception:
                pass
            del stack[at:]
        if phase != "backend" and not stack:
            self._add_phase_seconds(phase, end - start)

    # -- registry mirroring ------------------------------------------------
    def _instruments(self, registry: MetricsRegistry) -> dict:
        """Idempotent instrument lookup on a bound registry (get-or-create
        is the registry's own contract)."""
        return {
            "compiles": registry.counter(
                "pio_jit_compiles_total",
                "XLA compiles observed at instrumented jit boundaries",
                labelnames=("fn",),
            ),
            "retraces": registry.counter(
                "pio_jit_retraces_total",
                "Compiles after a function's first — new-signature "
                "retraces",
                labelnames=("fn",),
            ),
            "compile_s": registry.histogram(
                "pio_jit_compile_seconds",
                "Wall time of jitted calls that triggered a compile",
                labelnames=("fn",),
            ),
            "backend_s": registry.histogram(
                "pio_jit_backend_compile_seconds",
                "XLA backend compile durations (jax.monitoring, whole "
                "process)",
            ),
            "phase_s": registry.histogram(
                "pio_jit_phase_seconds",
                "Seconds of jaxpr tracing, lowering to MLIR and "
                "retrieval from the persistent cache, one observation "
                "per program (jax.monitoring, whole process)",
                labelnames=("phase",),
            ),
        }

    def _live_registries(self) -> List[MetricsRegistry]:
        """Caller holds ``_lock``. Prunes dead weakrefs in passing."""
        live, refs = [], []
        for ref in self._bound:
            registry = ref()
            if registry is not None:
                live.append(registry)
                refs.append(ref)
        self._bound = refs
        return live

    def bind(self, registry: MetricsRegistry) -> None:
        """Mirror this telemetry onto ``registry`` (``/metrics``): create
        the instrument families, replay current totals (compiles that
        happened before the server existed — e.g. deploy-time serving
        warmup — must not vanish from exposition), and register the
        cache hit/miss gauges. Idempotent per registry."""
        with self._lock:
            if any(ref() is registry for ref in self._bound):
                return
            self._bound.append(weakref.ref(registry))
            fns = {
                name: (st["compiles"], st["retraces"], list(st["samples"]))
                for name, st in self._fns.items()
            }
            backend = list(self._backend_samples)
            phases = {
                phase: list(samples)
                for phase, samples in self._phase_samples.items()
            }
        inst = self._instruments(registry)
        for name, (compiles, retraces, samples) in fns.items():
            if compiles:
                inst["compiles"].inc(compiles, fn=name)
            if retraces:
                inst["retraces"].inc(retraces, fn=name)
            for seconds in samples:
                inst["compile_s"].observe(seconds, fn=name)
        for seconds in backend:
            inst["backend_s"].observe(seconds)
        for phase, samples in phases.items():
            for seconds in samples:
                inst["phase_s"].observe(seconds, phase=phase)
        registry.gauge_callback(
            "pio_jit_cache_hits",
            self._hits_locked,
            "Persistent compilation-cache hits (jax.monitoring)",
        )
        registry.gauge_callback(
            "pio_jit_cache_misses",
            self._misses_locked,
            "Persistent compilation-cache misses (jax.monitoring)",
        )

    def _hits_locked(self) -> int:
        with self._lock:
            return self._cache_hits

    def _misses_locked(self) -> int:
        with self._lock:
            return self._cache_misses

    # -- snapshots ---------------------------------------------------------
    def snapshot(self) -> dict:
        """Current totals, JSON-safe: ``{"fns": {name: {compiles,
        retraces, compile_s}}, "cache": {hits, misses, backend_compiles,
        backend_compile_s, trace_s, lower_s, retrieval_s,
        compile_time_saved_s}}``. ``trace_s`` and ``lower_s`` count the
        phases that lie inside no other one on their thread (a function
        traced inside another's trace is part of that one's seconds);
        ``retrieval_s`` lies inside ``backend_compile_s``, which on a
        hit of the persistent cache is the retrieval."""
        with self._lock:
            return {
                "fns": {
                    name: {
                        "compiles": st["compiles"],
                        "retraces": st["retraces"],
                        "compile_s": round(sum(st["samples"]), 4),
                    }
                    for name, st in self._fns.items()
                },
                "cache": {
                    "hits": self._cache_hits,
                    "misses": self._cache_misses,
                    "backend_compiles": self._backend_compiles,
                    "backend_compile_s": round(self._backend_s, 4),
                    "trace_s": round(self._phase_s["trace"], 4),
                    "lower_s": round(self._phase_s["lower"], 4),
                    "retrieval_s": round(self._phase_s["retrieval"], 4),
                    "compile_time_saved_s": round(self._saved_s, 4),
                },
            }

    def delta_since(self, before: dict) -> dict:
        """``snapshot() - before``: what happened during one run (the
        shape persisted into ``PIO_TRAIN_PROFILE``). Functions with a
        zero delta are dropped."""
        now = self.snapshot()
        fns = {}
        for name, st in now["fns"].items():
            prev = before.get("fns", {}).get(name, {})
            compiles = st["compiles"] - prev.get("compiles", 0)
            retraces = st["retraces"] - prev.get("retraces", 0)
            if compiles <= 0 and retraces <= 0:
                continue
            fns[name] = {
                "compiles": compiles,
                "retraces": retraces,
                "compile_s": round(
                    st["compile_s"] - prev.get("compile_s", 0.0), 4
                ),
            }
        prev_cache = before.get("cache", {})
        cache = {
            key: (
                round(now["cache"][key] - prev_cache.get(key, 0), 4)
                if isinstance(now["cache"][key], float)
                else now["cache"][key] - prev_cache.get(key, 0)
            )
            for key in now["cache"]
        }
        return {"fns": fns, "cache": cache}


_SINGLETON_LOCK = threading.Lock()
_default: Optional[JitTelemetry] = None


def default_telemetry() -> JitTelemetry:
    """The process-wide telemetry instance every instrumented boundary
    reports into (jit caches are process state, so is their telemetry)."""
    global _default
    with _SINGLETON_LOCK:
        if _default is None:
            _default = JitTelemetry()
        return _default


# -- phase profiling --------------------------------------------------------


class _NullPhase:
    """The disabled-path phase handle AND context manager: every method
    is a no-op so a production code path pays an attribute call and
    nothing else when ``PIO_PROFILE`` is off."""

    __slots__ = ()

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def fence(self, value=None):
        return value


_NULL_PHASE = _NullPhase()


class _Phase:
    """One enabled phase: wall time always; ``fence(value)`` blocks until
    ``value``'s device work completes and records the device-complete
    time (without a fence, device_s == wall_s — an *unfenced dispatch*
    measurement, which the report labels as such is not: callers that
    care fence)."""

    __slots__ = ("_profiler", "_t0", "device_s")

    def __init__(self, profiler: "PhaseProfiler", t0: float):
        self._profiler = profiler
        self._t0 = t0
        self.device_s: Optional[float] = None

    def fence(self, value=None):
        self._profiler._fence(value)
        self.device_s = self._profiler._clock() - self._t0
        return value


class _PhaseCtx:
    __slots__ = ("_profiler", "_name", "_flops", "_bytes", "_phase")

    def __init__(self, profiler, name, flops, hbm_bytes):
        self._profiler = profiler
        self._name = name
        self._flops = flops
        self._bytes = hbm_bytes
        self._phase: Optional[_Phase] = None

    def __enter__(self) -> _Phase:
        self._phase = _Phase(self._profiler, self._profiler._clock())
        return self._phase

    def __exit__(self, *exc) -> None:
        ph = self._phase
        wall = self._profiler._clock() - ph._t0
        self._profiler._record(
            self._name,
            wall_s=wall,
            device_s=ph.device_s if ph.device_s is not None else wall,
            flops=self._flops,
            hbm_bytes=self._bytes,
        )


def _default_fence(value) -> None:
    try:
        import jax

        jax.block_until_ready(value)
    except Exception:
        pass  # device-free host (or host values): nothing to fence


class PhaseProfiler:
    """``StepTimer`` extended with device fencing and roofline
    accounting (docs/observability.md#profiling).

    ::

        prof = PhaseProfiler(enabled=True)
        with prof.phase("solve", flops=F, hbm_bytes=B) as ph:
            out = jitted(x)
            ph.fence(out)          # device-complete, not dispatch, time
        prof.summary()["solve"].get("mfu")  # when the device's peaks are known

    ``enabled=None`` reads ``PIO_PROFILE``; disabled, :meth:`phase`
    returns a shared no-op context that never calls the clock or the
    fence — the near-zero-cost contract ``tests/test_perf.py`` pins.
    ``clock`` and ``fence`` are injectable for sleep-free, device-free
    tests.
    """

    def __init__(
        self,
        enabled: Optional[bool] = None,
        clock: Callable[[], float] = time.perf_counter,
        fence: Optional[Callable] = None,
        peaks: Optional[Dict[str, float]] = None,
    ):
        self.enabled = profiling_enabled() if enabled is None else enabled
        self._clock = clock
        self._fence = fence if fence is not None else _default_fence
        self._peaks = peaks
        self._lock = threading.Lock()
        self._phases: Dict[str, dict] = {}

    def phase(self, name: str, flops: float = 0.0, hbm_bytes: float = 0.0):
        if not self.enabled:
            return _NULL_PHASE
        return _PhaseCtx(self, name, float(flops), float(hbm_bytes))

    def _record(self, name, wall_s, device_s, flops, hbm_bytes) -> None:
        with self._lock:
            st = self._phases.setdefault(
                name,
                {
                    "count": 0,
                    "wall_s": 0.0,
                    "device_s": 0.0,
                    "flops": 0.0,
                    "hbm_bytes": 0.0,
                },
            )
            st["count"] += 1
            st["wall_s"] += wall_s
            st["device_s"] += device_s
            st["flops"] += flops
            st["hbm_bytes"] += hbm_bytes

    def record(
        self,
        name: str,
        wall_s: float,
        device_s: Optional[float] = None,
        flops: float = 0.0,
        hbm_bytes: float = 0.0,
    ) -> None:
        """Adopt an externally measured phase (e.g. ``ops/als.py``'s
        fenced per-iteration timings) into the same summary."""
        if not self.enabled:
            return
        self._record(
            name,
            wall_s=wall_s,
            device_s=device_s if device_s is not None else wall_s,
            flops=flops,
            hbm_bytes=hbm_bytes,
        )

    def summary(self) -> Dict[str, dict]:
        """Per-phase totals + roofline estimates (vs the peaks of the
        device that ran unless the profiler was built with explicit
        ``peaks``) — JSON-safe, the ``pio profile`` report's data."""
        with self._lock:
            phases = {
                name: dict(st) for name, st in self._phases.items()
            }
        for st in phases.values():
            st.update(
                {
                    key: round(value, 6)
                    for key, value in roofline(
                        st["flops"],
                        st["hbm_bytes"],
                        st["device_s"],
                        self._peaks,
                    ).items()
                }
            )
            st["wall_s"] = round(st["wall_s"], 6)
            st["device_s"] = round(st["device_s"], 6)
        return phases


# -- report rendering (pio profile) -----------------------------------------


def render_profile_report(
    title: str,
    phases: Optional[Dict[str, dict]] = None,
    jit: Optional[Dict[str, dict]] = None,
    cache: Optional[dict] = None,
    device: Optional[str] = None,
) -> str:
    """One-screen text report shared by every ``pio profile`` mode
    (smoke train, live-server scrape, completed instance). Inputs are
    plain dicts — the summary shapes of :class:`PhaseProfiler`,
    :meth:`JitTelemetry.snapshot` and the exposition scrape all fit."""
    lines = [f"pio profile — {title}" + (f" (device {device})" if device else "")]
    if phases:
        lines.append("")
        lines.append(
            f"{'phase':<24}{'count':>6}{'wall_s':>10}{'device_s':>10}"
            f"{'tflops/s':>10}{'mfu':>10}{'hbm_util':>10}"
        )
        for name in sorted(phases):
            st = phases[name]
            lines.append(
                f"{name:<24}{st.get('count', 1):>6}"
                f"{st.get('wall_s', 0.0):>10.3f}"
                f"{st.get('device_s', st.get('wall_s', 0.0)):>10.3f}"
                f"{st.get('tflops_per_s', 0.0):>10.3f}"
                + "".join(
                    f"{st[key]:>10.4f}" if key in st else f"{'-':>10}"
                    for key in ("mfu", "hbm_util")
                )
            )
        lines.append(
            "  (mfu/hbm_util are roofline estimates vs the peaks of the "
            "device that ran; '-' where its device_kind has no peaks in "
            "DEVICE_PEAKS)"
        )
    if jit:
        lines.append("")
        lines.append(
            f"{'jit fn':<24}{'compiles':>9}{'retraces':>9}"
            f"{'compile_s':>11}"
        )
        for name in sorted(jit):
            st = jit[name]
            lines.append(
                f"{name:<24}{st.get('compiles', 0):>9.0f}"
                f"{st.get('retraces', 0):>9.0f}"
                f"{st.get('compile_s', 0.0):>11.3f}"
            )
    if cache is not None:
        lines.append("")
        lines.append(
            "compilation cache: "
            f"hits={cache.get('hits', 0):.0f} "
            f"misses={cache.get('misses', 0):.0f} "
            f"backend_compiles={cache.get('backend_compiles', 0):.0f} "
            f"backend_compile_s={cache.get('backend_compile_s', 0.0):.3f}"
            # where the tap's totals came along (not from a scrape)
            + "".join(
                f" {key}={cache[key]:.3f}"
                for key in ("trace_s", "lower_s", "retrieval_s")
                if key in cache
            )
        )
    if not phases and not jit and cache is None:
        lines.append("(no profile data)")
    return "\n".join(lines)
