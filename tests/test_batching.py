"""Micro-batching aggregator: correctness under concurrency, fan-out
alignment, error isolation, and the batched serving path end-to-end
(the accelerator replacement for per-request predictBase,
``CreateServer.scala:479-485``)."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest
import requests

from predictionio_tpu.workflow.batching import MicroBatcher


class TestMicroBatcher:
    def test_single_item_roundtrip(self):
        mb = MicroBatcher(lambda items: [x * 2 for x in items], max_wait_ms=1.0)
        try:
            assert mb.submit(21) == 42
        finally:
            mb.close()

    def test_results_index_aligned_under_concurrency(self):
        mb = MicroBatcher(
            lambda items: [x * 10 for x in items],
            max_batch=16,
            max_wait_ms=5.0,
        )
        try:
            with ThreadPoolExecutor(max_workers=32) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(200)]
                results = [f.result(timeout=30) for f in futs]
            assert results == [i * 10 for i in range(200)]
            # concurrency must actually aggregate: far fewer batches than items
            assert mb.stats["batches"] < 200
            assert mb.stats["avg_batch"] > 1.0
        finally:
            mb.close()

    def test_max_batch_respected(self):
        seen = []

        def process(items):
            seen.append(len(items))
            return list(items)

        mb = MicroBatcher(process, max_batch=8, max_wait_ms=20.0)
        try:
            with ThreadPoolExecutor(max_workers=24) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(64)]
                [f.result(timeout=30) for f in futs]
            assert max(seen) <= 8
        finally:
            mb.close()

    def test_processor_exception_fails_only_that_batch(self):
        calls = []

        def process(items):
            calls.append(list(items))
            if "boom" in items:
                raise ValueError("boom batch")
            return list(items)

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0)
        try:
            with pytest.raises(ValueError, match="boom batch"):
                mb.submit("boom")
            assert mb.submit("ok") == "ok"  # batcher still alive
        finally:
            mb.close()

    def test_length_mismatch_is_an_error(self):
        # a wait long enough that both submits share a batch on a loaded
        # machine too (at 5 ms each went alone under six test workers, and a
        # lone 1-item batch passes)
        mb = MicroBatcher(lambda items: [1], max_batch=4, max_wait_ms=250.0)
        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(2)]
                time.sleep(0.05)
                failures = 0
                for f in futs:
                    try:
                        f.result(timeout=10)
                    except RuntimeError:
                        failures += 1
                # at least the 2-item batch fails; a lone 1-item batch passes
                assert failures >= 1
        finally:
            mb.close()

    def test_submit_after_close_raises(self):
        mb = MicroBatcher(lambda items: list(items))
        mb.close()
        with pytest.raises(RuntimeError, match="closed"):
            mb.submit(1)


class TestPipelinedDispatch:
    """Round-3 pipelining: up to pipeline_depth batches in flight at once
    so the next batch dispatches while the previous one's results are
    still traveling back from the device (the round-2 single-in-flight
    dispatcher capped QPS at max_batch / round_trip)."""

    def test_batches_overlap_up_to_depth(self):
        """With a slow processor and depth 2, two batches must be observed
        running concurrently — the whole point of the pipeline."""
        running = []
        peak = []
        lock = threading.Lock()
        entered = threading.Barrier(2, timeout=10)

        def process(items):
            with lock:
                running.append(1)
                peak.append(len(running))
            try:
                entered.wait()  # both batches provably inside process()
            except threading.BrokenBarrierError:
                pass
            time.sleep(0.02)
            with lock:
                running.pop()
            return list(items)

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0,
                          pipeline_depth=2)
        try:
            with ThreadPoolExecutor(max_workers=4) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(4)]
                assert sorted(f.result(timeout=30) for f in futs) == [0, 1, 2, 3]
            assert max(peak) == 2
            assert mb.stats["inflight_hwm"] == 2
        finally:
            mb.close()

    def test_depth_bounds_concurrency(self):
        """Never more than pipeline_depth batches in process() at once,
        regardless of queue pressure."""
        concurrent = []
        count = [0]
        lock = threading.Lock()

        def process(items):
            with lock:
                count[0] += 1
                concurrent.append(count[0])
            time.sleep(0.005)
            with lock:
                count[0] -= 1
            return list(items)

        mb = MicroBatcher(process, max_batch=2, max_wait_ms=0.0,
                          pipeline_depth=3)
        try:
            with ThreadPoolExecutor(max_workers=24) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(96)]
                [f.result(timeout=30) for f in futs]
            assert max(concurrent) <= 3
        finally:
            mb.close()

    def test_depth_one_is_strictly_serial(self):
        """pipeline_depth=1 reproduces the round-2 contract: batches never
        overlap."""
        concurrent = []
        count = [0]
        lock = threading.Lock()

        def process(items):
            with lock:
                count[0] += 1
                concurrent.append(count[0])
            time.sleep(0.002)
            with lock:
                count[0] -= 1
            return list(items)

        mb = MicroBatcher(process, max_batch=4, max_wait_ms=0.0,
                          pipeline_depth=1)
        try:
            with ThreadPoolExecutor(max_workers=16) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(64)]
                [f.result(timeout=30) for f in futs]
            assert max(concurrent) == 1
        finally:
            mb.close()

    def test_out_of_order_completion_resolves_correct_futures(self):
        """A later batch finishing before an earlier one must deliver each
        item to its own submitter (futures are per-item, not positional
        across batches)."""
        first_batch_gate = threading.Event()
        batch_no = [0]
        batch_lock = threading.Lock()

        def process(items):
            with batch_lock:
                batch_no[0] += 1
                mine = batch_no[0]
            if mine == 1:
                # stall batch 1 until batch 2 has finished
                first_batch_gate.wait(timeout=10)
            return [x * 100 for x in items]

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0,
                          pipeline_depth=2)
        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                f1 = pool.submit(mb.submit, 1)
                time.sleep(0.05)  # ensure 1 is taken as its own batch first
                f2 = pool.submit(mb.submit, 2)
                assert f2.result(timeout=10) == 200  # batch 2 completes first
                assert not f1.done()
                first_batch_gate.set()
                assert f1.result(timeout=10) == 100
        finally:
            first_batch_gate.set()
            mb.close()

    def test_error_in_one_inflight_batch_spares_the_other(self):
        gate = threading.Event()

        def process(items):
            if "bad" in items:
                raise ValueError("bad batch")
            gate.wait(timeout=10)
            return list(items)

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0,
                          pipeline_depth=2)
        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                f_ok = pool.submit(mb.submit, "ok")
                time.sleep(0.05)
                f_bad = pool.submit(mb.submit, "bad")
                with pytest.raises(ValueError, match="bad batch"):
                    f_bad.result(timeout=10)
                gate.set()
                assert f_ok.result(timeout=10) == "ok"
        finally:
            gate.set()
            mb.close()

    def test_close_is_bounded_with_hung_batch(self):
        """A batch hung on a dead device must not hang close() (the /stop
        and hot-swap path) forever: close returns after its grace period,
        leaving the daemon worker behind."""
        hang = threading.Event()

        def process(items):
            hang.wait(timeout=60)  # simulates a wedged device dispatch
            return list(items)

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0,
                          pipeline_depth=2)
        try:
            with ThreadPoolExecutor(max_workers=1) as pool:
                fut = pool.submit(mb.submit, 1, 30)
                time.sleep(0.1)  # batch is in flight and hung
                t0 = time.monotonic()
                mb.close(grace_s=0.3)
                assert time.monotonic() - t0 < 5.0
                hang.set()  # release the "device"; submitter completes
                assert fut.result(timeout=10) == 1
        finally:
            hang.set()

    def test_close_with_inflight_batches_completes_them(self):
        """close() must let in-flight batches finish (their callers are
        blocked on the result), then fail whatever never dispatched."""
        release = threading.Event()

        def process(items):
            release.wait(timeout=10)
            return list(items)

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0,
                          pipeline_depth=2)
        try:
            with ThreadPoolExecutor(max_workers=3) as pool:
                futs = [pool.submit(mb.submit, i) for i in range(2)]
                time.sleep(0.1)  # both in flight
                closer = pool.submit(mb.close)
                time.sleep(0.05)
                release.set()
                closer.result(timeout=10)
                assert sorted(f.result(timeout=10) for f in futs) == [0, 1]
        finally:
            release.set()


class TestQueueDepthGauge:
    """Regression tests for the ISSUE-6 ``conc-unguarded-attr`` sweep
    finding: the queue-depth gauge callback read ``self._items`` from
    the scrape thread without the batcher lock."""

    def test_gauge_is_registered_and_reads_zero_when_idle(self):
        from predictionio_tpu.obs.metrics import MetricsRegistry

        metrics = MetricsRegistry()
        mb = MicroBatcher(
            lambda items: list(items), max_wait_ms=0.0, metrics=metrics
        )
        try:
            metrics.collect()  # refresh callback gauges
            assert metrics.gauge("pio_batch_queue_depth").value() == 0.0
        finally:
            mb.close()

    def test_queue_depth_reads_under_the_batcher_lock(self):
        mb = MicroBatcher(lambda items: list(items), max_wait_ms=0.0)
        try:
            got = []
            mb._lock.acquire()
            try:
                t = threading.Thread(
                    target=lambda: got.append(mb._queue_depth())
                )
                t.start()
                t.join(timeout=0.05)
                assert t.is_alive(), (
                    "queue-depth callback returned while the batcher "
                    "lock was held — it reads _items without the lock"
                )
            finally:
                mb._lock.release()
            t.join(timeout=30)
            assert got == [0]
        finally:
            mb.close()


class TestBatchedServing:
    def test_batched_and_unbatched_agree(self, registry):
        from predictionio_tpu.workflow.serving import QueryServer, ServerConfig
        from test_query_server import _train, _typed_engine

        engine = _typed_engine()
        _train(registry, engine, algo_ids=(11, 13))

        batched = QueryServer(
            ServerConfig(ip="127.0.0.1", port=0, batching=True,
                         batch_wait_ms=2.0),
            engine, registry,
        )
        unbatched = QueryServer(
            ServerConfig(ip="127.0.0.1", port=0, batching=False),
            engine, registry,
        )
        try:
            rb, sb = batched.handle_query({"id": 7})
            ru, su = unbatched.handle_query({"id": 7})
            assert sb == su == 200
            assert rb == ru
        finally:
            for s in (batched, unbatched):
                s.server_close()

    def test_poison_query_fails_alone(self, registry):
        """One bad query in a micro-batch must not 500 its batchmates."""
        from predictionio_tpu.controller import Engine
        from predictionio_tpu.workflow.serving import QueryServer, ServerConfig
        from sample_engine import Algo0, DataSource0, Preparator0, Serving0
        from test_query_server import _train, TypedQueryAlgoMixin

        class PoisonAlgo(TypedQueryAlgoMixin, Algo0):
            def predict(self, model, query):
                if query.id == 666:
                    raise ValueError("poison")
                return super().predict(model, query)

        engine = Engine(
            {"": DataSource0}, {"": Preparator0},
            {"": PoisonAlgo}, {"": Serving0},
        )
        _train(registry, engine, algo_ids=(11,))
        srv = QueryServer(
            ServerConfig(ip="127.0.0.1", port=0, batching=True,
                         batch_max=8, batch_wait_ms=30.0),
            engine, registry,
        )
        try:
            with ThreadPoolExecutor(max_workers=4) as pool:
                futs = {
                    qid: pool.submit(srv.handle_query, {"id": qid})
                    for qid in (1, 666, 2, 3)
                }
                for qid, fut in futs.items():
                    if qid == 666:
                        with pytest.raises(ValueError, match="poison"):
                            fut.result(timeout=30)
                    else:
                        _result, status = fut.result(timeout=30)
                        assert status == 200
        finally:
            srv.server_close()

    def test_concurrent_http_queries_aggregate(self, registry):
        from predictionio_tpu.workflow.serving import QueryServer, ServerConfig
        from test_query_server import _train, _typed_engine

        engine = _typed_engine()
        _train(registry, engine, algo_ids=(11,))
        srv = QueryServer(
            ServerConfig(ip="127.0.0.1", port=0, batching=True,
                         batch_max=32, batch_wait_ms=150.0),
            engine, registry,
        )
        srv.start_background()
        base = f"http://127.0.0.1:{srv.bound_port}"
        try:
            with ThreadPoolExecutor(max_workers=16) as pool:
                futs = [
                    pool.submit(
                        requests.post, f"{base}/queries.json",
                        json={"id": i}, timeout=30,
                    )
                    for i in range(64)
                ]
                codes = [f.result().status_code for f in futs]
            assert codes == [200] * 64
            stats = srv._batcher.stats
            assert stats["submitted"] == 64
            # fewer dispatches than requests = aggregation happened. The
            # bound is deliberately loose (48, not 32): on a loaded 1-core
            # CI host the 16 client threads can trickle in slowly enough
            # that several batches close near-empty despite the 150 ms
            # linger — the test proves aggregation, not a batching ratio.
            assert stats["batches"] <= 48
        finally:
            srv.shutdown()
            srv.server_close()


class TestObsRecordedBeforeFanout:
    """Regression pin for the PR-8/9 e2e batch-span flake: metrics and
    spans for a batch were recorded in ``_execute``'s finally block,
    AFTER ``set_result`` unblocked the submitting thread — so a client
    (or a test) that answered and immediately read ``/traces.json``
    raced the recording. The fix records before the fan-out on both the
    success and failure paths; these tests make the old ordering fail
    deterministically instead of flakily."""

    def test_obs_complete_when_submit_returns(self, monkeypatch):
        from predictionio_tpu.obs.metrics import MetricsRegistry

        recorded = threading.Event()
        orig = MicroBatcher._record_obs

        def slow_record(self, *args, **kwargs):
            # widen the historical race window: under the OLD ordering
            # the submitter returns while this sleeps, turning a
            # sometimes-flake into a certain failure
            time.sleep(0.05)
            orig(self, *args, **kwargs)
            recorded.set()

        monkeypatch.setattr(MicroBatcher, "_record_obs", slow_record)
        metrics = MetricsRegistry()
        mb = MicroBatcher(
            lambda items: list(items), max_batch=1, max_wait_ms=0.0,
            metrics=metrics,
        )
        flush = metrics.counter(
            "pio_batch_flush_total", "Batch flushes by trigger",
            labelnames=("reason",),
        )
        try:
            for i in range(3):
                recorded.clear()
                assert mb.submit(i) == i
                # the moment submit() returns, this batch's obs must
                # already be on the registry — no drain, no sleep
                assert recorded.is_set()
            assert flush.value(reason="full") == 3  # max_batch=1 fills
        finally:
            mb.close()

    def test_failed_batch_also_records_before_fanout(self, monkeypatch):
        recorded = threading.Event()
        orig = MicroBatcher._record_obs

        def slow_record(self, *args, **kwargs):
            time.sleep(0.05)
            orig(self, *args, **kwargs)
            recorded.set()

        monkeypatch.setattr(MicroBatcher, "_record_obs", slow_record)

        def process(items):
            raise ValueError("device died")

        mb = MicroBatcher(process, max_batch=1, max_wait_ms=0.0)
        try:
            with pytest.raises(ValueError, match="device died"):
                mb.submit("x")
            assert recorded.is_set()
        finally:
            mb.close()


@pytest.fixture()
def registry(tmp_path):
    from predictionio_tpu.storage import StorageRegistry

    return StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)})
