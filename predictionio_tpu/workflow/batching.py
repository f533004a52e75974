"""Micro-batching aggregator for the serving hot path.

The reference serves each query with its own ``predictBase`` call
(``core/src/main/scala/io/prediction/workflow/CreateServer.scala:479-485``)
— fine on a JVM thread pool doing CPU dot-products, fatal on an
accelerator: a batch-1 device dispatch per HTTP request leaves the MXU
idle and pays full dispatch latency per query. SURVEY §7 flags "batched
query aggregation into the gather-dot kernel without killing tail
latency" as the hard part of the ≥10k QPS target.

:class:`MicroBatcher` is the aggregator: concurrent request threads
``submit()`` work items; a single dispatcher thread collects whatever has
arrived within ``max_wait_ms`` (or up to ``max_batch``), hands the batch
to a worker thread, and immediately forms the next batch. Up to
``pipeline_depth`` batches are in flight at once: while batch *k*'s
results travel back from the device, batch *k+1* is already dispatched —
a single in-flight batch caps throughput at ``max_batch / round_trip``
with the device idle between batches. Pipelining multiplies that by the
depth until device compute (not the round trip) is the binding
resource. At low rates a lone query pays at most ``max_wait_ms`` extra
latency. This is the classic accelerator-serving pattern (cf. TF
Serving's batching layer), sized so tail latency stays bounded:
p99 <= pipeline_depth * device_time(max_batch) + max_wait_ms.

The processor must be thread-safe under ``pipeline_depth`` concurrent
calls (jitted JAX dispatch is; the serving processor is a pure function
of its items). Batches may COMPLETE out of order; per-item futures make
that invisible to callers.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ..obs.metrics import MetricsRegistry
from ..obs.trace import Tracer, current_context, default_tracer

__all__ = ["MicroBatcher"]


class MicroBatcher:
    """Aggregate concurrent ``submit()`` calls into batched processor runs.

    ``process``: callable taking a list of items and returning a list of
    results of the same length (index-aligned). It runs on the dispatcher
    thread. A result element that is an ``Exception`` instance fails only
    its own request; an exception *raised* by ``process`` fails every
    request in that batch (and only that batch).

    ``default_timeout_s`` bounds each ``submit()`` wait; size it to cover
    worst-case first-dispatch latency (an XLA compile for a fresh shape
    bucket can cost tens of seconds on TPU).

    ``pipeline_depth`` is the number of batches allowed in flight at once
    (>=1). Depth 1 reproduces the strictly serial round-2 behavior; depth
    >=2 overlaps device round trips and is the default.

    Observability (``docs/observability.md``): with a ``metrics``
    registry attached, every flush records batch size, the flush reason
    (``full`` / ``wait`` / ``close``) and per-item queue wait, and the
    live queue depth is exported as a gauge — the signals that say
    whether the aggregator is forming real batches or just adding
    ``max_wait_ms`` of latency. With a ``tracer`` attached, each item
    whose submitting thread carried a span context gets two child spans:
    ``batch.queue-wait`` (submit → dispatch) and ``batch.device`` (the
    processor call) — the queue-time-vs-device-time split that explains
    a slow query. Every executed batch is also one ``batch.execute`` span
    (tags ``b``, ``flush``) in that tracer, or in the process's default
    tracer without one. ``clock`` is injectable for sleep-free tests.
    """

    def __init__(
        self,
        process: Callable[[Sequence[Any]], Sequence[Any]],
        max_batch: int = 64,
        max_wait_ms: float = 1.0,
        name: str = "microbatch",
        default_timeout_s: float = 120.0,
        pipeline_depth: int = 2,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self._process = process
        self._max_batch = max_batch
        self._max_wait_s = max(0.0, max_wait_ms) / 1000.0
        self._default_timeout_s = default_timeout_s
        self._pipeline_depth = pipeline_depth
        self._clock = clock
        self._tracer = tracer
        self._obs_size = self._obs_wait = self._obs_flush = None
        self._obs_items = self._obs_failures = None
        if metrics is not None:
            self._obs_size = metrics.histogram(
                "pio_batch_size",
                "Queries per dispatched micro-batch",
                buckets=[2.0 ** i for i in range(11)],  # 1..1024
            )
            self._obs_wait = metrics.histogram(
                "pio_batch_queue_wait_seconds",
                "Per-item wait between submit and batch dispatch",
            )
            self._obs_flush = metrics.counter(
                "pio_batch_flush_total",
                "Batch flushes by trigger",
                labelnames=("reason",),
            )
            self._obs_items = metrics.counter(
                "pio_batch_items_total", "Items dispatched through batches"
            )
            self._obs_failures = metrics.counter(
                "pio_batch_failures_total",
                "Batches whose processor raised (all items failed)",
            )
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._items: List[Any] = []
        self._futures: List[Future] = []
        #: parallel to _items: (enqueue_ts, submitter SpanContext or None)
        self._meta: List[Tuple[float, Any]] = []
        self._closed = False
        if metrics is not None:
            # registered only now: the registry is shared, so a scrape
            # can fire the callback the instant it registers — the lock
            # and the queue it reads must already exist
            metrics.gauge_callback(
                "pio_batch_queue_depth",
                self._queue_depth,
                "Items waiting for the next batch",
            )
        self._batches = 0
        self._submitted = 0
        self._inflight_hwm = 0  # high-water mark of concurrent batches
        self._inflight = 0
        self._slots = threading.Semaphore(pipeline_depth)
        # Dedicated daemon workers (NOT a ThreadPoolExecutor: its threads
        # are joined at interpreter exit, so a batch hung on a dead device
        # would wedge process shutdown; daemons get left behind instead).
        self._work: "queue.Queue" = queue.Queue()
        self._workers = [
            threading.Thread(
                target=self._worker, name=f"{name}-exec-{i}", daemon=True
            )
            for i in range(pipeline_depth)
        ]
        for w in self._workers:
            w.start()
        self._dispatcher = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._dispatcher.start()

    def _queue_depth(self) -> int:
        """Scrape-thread gauge callback: reads the queue under the same
        lock the request/dispatcher threads mutate it under."""
        with self._lock:
            return len(self._items)

    # -- client side ------------------------------------------------------
    def submit(self, item: Any, timeout: Optional[float] = None) -> Any:
        """Block until the batched processor has handled ``item``; returns
        its index-aligned result (or raises that item's exception)."""
        fut: Future = Future()
        # capture the submitter's trace context OUTSIDE the lock: the
        # dispatcher/worker threads that emit this item's spans have no
        # access to the submitting thread's contextvars
        span_ctx = current_context() if self._tracer is not None else None
        with self._nonempty:
            if self._closed:
                raise RuntimeError("MicroBatcher is closed")
            self._items.append(item)
            self._futures.append(fut)
            self._meta.append((self._clock(), span_ctx))
            self._submitted += 1
            self._nonempty.notify()
        return fut.result(
            timeout=timeout if timeout is not None else self._default_timeout_s
        )

    # -- dispatcher -------------------------------------------------------
    def _take_batch(self) -> tuple:
        """Wait for at least one item, linger up to max_wait for more (or
        until the batch is full), then drain. Returns ((), (), (), "")
        on close."""
        with self._nonempty:
            while not self._items and not self._closed:
                self._nonempty.wait(0.1)
            if self._closed and not self._items:
                return (), (), (), ""
            if self._max_wait_s > 0:
                deadline = time.monotonic() + self._max_wait_s
                while len(self._items) < self._max_batch:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._nonempty.wait(remaining)
            # flush reason, for the metrics plane: a fleet of "wait"
            # flushes at size 1 means batching is pure added latency
            if len(self._items) >= self._max_batch:
                reason = "full"
            elif self._closed:
                reason = "close"
            else:
                reason = "wait"
            items = self._items[: self._max_batch]
            futures = self._futures[: self._max_batch]
            metas = self._meta[: self._max_batch]
            del self._items[: self._max_batch]
            del self._futures[: self._max_batch]
            del self._meta[: self._max_batch]
            return items, futures, metas, reason

    def _run(self) -> None:
        while True:
            # Acquire a pipeline slot BEFORE draining the queue: the batch
            # is formed as late as possible, so while all slots are busy
            # (device round trips in flight) arrivals keep topping up the
            # next batch to max_batch instead of dispatching undersized.
            self._slots.acquire()
            items, futures, metas, reason = self._take_batch()
            if not items:
                self._slots.release()
                with self._lock:
                    closed = self._closed
                if closed:
                    return
                continue
            with self._lock:
                self._inflight += 1
                self._inflight_hwm = max(self._inflight_hwm, self._inflight)
            self._work.put((items, futures, metas, reason))

    def _worker(self) -> None:
        while True:
            task = self._work.get()
            if task is None:  # close() sentinel
                return
            self._execute(*task)

    def _record_obs(
        self,
        metas: Sequence[Tuple[float, Any]],
        reason: str,
        dispatch_ts: float,
        device_s: float,
        batch_size: int,
    ) -> None:
        """Metrics + spans for one executed batch (see class docstring)."""
        if self._obs_size is not None:
            self._obs_size.observe(batch_size)
            self._obs_flush.inc(1, reason=reason)
            self._obs_items.inc(batch_size)
        for enqueue_ts, span_ctx in metas:
            wait_s = max(0.0, dispatch_ts - enqueue_ts)
            if self._obs_wait is not None:
                self._obs_wait.observe(wait_s)
            if self._tracer is not None and span_ctx is not None:
                wall = self._tracer.wall()
                tags = {"batch_size": batch_size, "flush": reason}
                self._tracer.record(
                    "batch.queue-wait",
                    self._tracer.child_context(span_ctx),
                    span_ctx.span_id,
                    start_wall=wall - wait_s - device_s,
                    duration_s=wait_s,
                    tags=tags,
                )
                self._tracer.record(
                    "batch.device",
                    self._tracer.child_context(span_ctx),
                    span_ctx.span_id,
                    start_wall=wall - device_s,
                    duration_s=device_s,
                    tags=tags,
                )

    def _execute(
        self,
        items: Sequence[Any],
        futures: Sequence[Future],
        metas: Sequence[Tuple[float, Any]] = (),
        reason: str = "",
    ) -> None:
        """Run one batch on an executor thread and fan results out. Runs
        concurrently with up to ``pipeline_depth - 1`` sibling batches."""
        dispatch_ts = self._clock()
        recorded = False

        def record() -> None:
            # Metrics/spans for every executed batch, FAILED ones
            # included — an erroring device is exactly when the batch
            # signals matter, so a raise must not zero the flush counts.
            # Swallowed on error: observability must never wedge the
            # pipeline slot or kill the worker thread.
            try:
                self._record_obs(
                    metas,
                    reason,
                    dispatch_ts,
                    self._clock() - dispatch_ts,
                    len(items),
                )
            except Exception:
                pass

        # Observability is recorded BEFORE the result fan-out on both
        # paths: set_result()/set_exception() unblocks the submitting
        # thread, which may answer its client — and a client (or an e2e
        # test) that then reads /traces.json must find this batch's
        # spans already there. Recording after the fan-out raced exactly
        # that read (the PR-8/9 batch-span flake).
        try:
            try:
                # one span per batch, entered and left on this executor
                # thread, so it is in the profiler's trace too; with no
                # ambient request it roots a trace of its own, and the
                # model step's spans (``predict.*``) become its children
                tracer = self._tracer or default_tracer()
                with tracer.span(
                    "batch.execute", {"b": len(items), "flush": reason}
                ):
                    results = self._process(items)
                if len(results) != len(items):
                    raise RuntimeError(
                        f"batch processor returned {len(results)} results "
                        f"for {len(items)} items"
                    )
            except Exception as exc:
                if self._obs_failures is not None:
                    self._obs_failures.inc(1)
                record()
                recorded = True
                for fut in futures:
                    if not fut.done():
                        fut.set_exception(exc)
                return
            with self._lock:
                self._batches += 1
            record()
            recorded = True
            for fut, result in zip(futures, results):
                if fut.done():
                    continue
                if isinstance(result, Exception):
                    fut.set_exception(result)  # per-item failure channel
                else:
                    fut.set_result(result)
        finally:
            if not recorded:  # a raise before the fan-out still records
                record()
            with self._lock:
                self._inflight -= 1
            self._slots.release()

    # -- lifecycle / stats ------------------------------------------------
    def close(self, grace_s: float = 5.0) -> None:
        # ONE deadline shared by the dispatcher join and the in-flight
        # wait: close() is bounded by grace_s total, not per phase.
        deadline = time.monotonic() + grace_s
        with self._nonempty:
            self._closed = True
            self._nonempty.notify_all()
        self._dispatcher.join(timeout=max(0.0, deadline - time.monotonic()))
        # Bounded wait for in-flight batches (their callers still block on
        # the results). A batch hung on a dead device must not hang /stop
        # or hot-swap forever: after the grace period the daemon workers
        # are left behind and hung submitters hit their submit() timeout.
        while time.monotonic() < deadline:
            with self._lock:
                if self._inflight == 0:
                    break
            time.sleep(0.005)
        for _ in self._workers:
            self._work.put(None)  # tidy exit for idle workers
        # fail anything still queued
        with self._nonempty:
            for fut in self._futures:
                if not fut.done():
                    fut.set_exception(RuntimeError("MicroBatcher closed"))
            self._items.clear()
            self._futures.clear()
            self._meta.clear()

    @property
    def stats(self) -> dict:
        with self._lock:
            return {
                "submitted": self._submitted,
                "batches": self._batches,
                "avg_batch": (
                    self._submitted / self._batches if self._batches else 0.0
                ),
                "pipeline_depth": self._pipeline_depth,
                "inflight_hwm": self._inflight_hwm,
            }
