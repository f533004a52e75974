"""One span, two sinks (``obs/trace.py``, docs/observability.md): a
program span lands in the ``SpanStore`` on the host clock and, while a
profiler session runs, in the profiler's trace as ``pio.<name>``; the
training path opens its own spans under one ``train`` root; the ALS
programs carry stable device scopes and the Pallas kernels their names.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.obs.trace import (
    Tracer,
    current_context,
    default_tracer,
    span,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what the jit telemetry's jax.monitoring tap leaves of a program's way
#: to the device
JIT_PHASES = ("jit.trace", "jit.lower", "jit.backend")


def _host_spans_only():
    """Profiler options that keep ``TraceAnnotation`` spans and leave the
    Python tracer off."""
    from jax.profiler import ProfileOptions

    options = ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def _host_events(trace_dir, prefix="pio."):
    """(name, start_ns, end_ns) of the host events named ``prefix``…"""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(prefix)
    ]


class TestTwoSinks:
    def test_span_under_a_profiler_session_is_in_both(self, tmp_path):
        tracer = Tracer("svc")
        jax.profiler.start_trace(str(tmp_path), profiler_options=_host_spans_only())
        try:
            with tracer.span("outer", tags={"side": "user", "b": 64}) as o:
                with tracer.span("inner") as i:
                    jnp.ones(8).block_until_ready()
            with tracer.server_span("GET /x"):
                pass
        finally:
            jax.profiler.stop_trace()

        # (whatever ``jnp.ones`` brought to the device here for the
        # first time left its phases under ``inner``, in a process that
        # taps jax.monitoring: TestJitPhases)
        events = {
            name: (s, e) for name, s, e in _host_events(str(tmp_path))
            if not name.startswith("pio.jit.")
        }
        assert set(events) == {
            "pio.outer side=user b=64", "pio.inner", "pio.GET /x",
        }
        outer, inner = events["pio.outer side=user b=64"], events["pio.inner"]
        assert outer[0] <= inner[0] and inner[1] <= outer[1]  # nested

        stored = {
            s["name"]: s for s in tracer.store.dump()
            if s["name"] not in JIT_PHASES
        }
        assert set(stored) == {"outer", "inner", "GET /x"}
        assert stored["inner"]["parentId"] == stored["outer"]["spanId"]
        assert stored["inner"]["traceId"] == stored["outer"]["traceId"]
        assert (o.trace_id, i.trace_id) == (
            stored["outer"]["traceId"], stored["inner"]["traceId"],
        )
        assert stored["outer"]["parentId"] is None
        assert stored["outer"]["tags"] == {"side": "user", "b": 64}

    def test_profiler_idle_store_still_records(self, tmp_path):
        tracer = Tracer("svc")
        with tracer.span("quiet", tags={"k": 1}):
            pass
        assert [s["name"] for s in tracer.store.dump()] == ["quiet"]
        assert list(tmp_path.iterdir()) == []  # nothing written anywhere

    def test_hand_timed_record_gets_no_annotation(self, tmp_path):
        tracer = Tracer("svc")
        jax.profiler.start_trace(str(tmp_path))
        try:
            ctx = tracer.child_context(None)
            tracer.record("batch.device", ctx, None, 0.0, 0.5)
        finally:
            jax.profiler.stop_trace()
        assert _host_events(str(tmp_path)) == []
        assert tracer.store.dump()[0]["name"] == "batch.device"

    def test_obs_trace_works_without_jax(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None  # any import of jax now raises\n"
            "from predictionio_tpu.obs.trace import span, default_tracer\n"
            "with span('solo', {'k': 'v'}):\n"
            "    pass\n"
            "(s,) = default_tracer().store.dump()\n"
            "assert s['name'] == 'solo' and s['parentId'] is None, s\n"
            "assert 'jax.profiler' not in sys.modules\n"
        )
        done = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, capture_output=True,
            text=True, timeout=120,
        )
        assert done.returncode == 0, done.stderr


class TestDefaultTracer:
    def test_one_per_process(self):
        assert default_tracer() is default_tracer()

    def test_rootless_span_roots_a_trace_in_it(self):
        assert current_context() is None
        with span("job") as root:
            with span("phase") as child:
                assert current_context() is child
        assert child.trace_id == root.trace_id
        assert child.tracer is default_tracer()
        by_name = {
            s["name"]: s for s in default_tracer().store.for_trace(root.trace_id)
        }
        assert by_name["job"]["parentId"] is None
        assert by_name["phase"]["parentId"] == by_name["job"]["spanId"]

    def test_span_joins_the_ambient_tracer(self):
        server = Tracer("query")
        with server.server_span("POST /queries.json") as req:
            with span("predict.fetch"):
                pass
        names = [s["name"] for s in server.store.for_trace(req.trace_id)]
        assert names == ["predict.fetch", "POST /queries.json"]


def _toy_prepared(n_users=60, n_items=25, nnz=900, seed=0):
    from predictionio_tpu.models.recommendation import PreparedData
    from predictionio_tpu.storage import BiMap

    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    items = rng.integers(0, n_items, nnz).astype(np.int32)
    pairs = np.unique(np.stack([users, items], 1), axis=0)
    return PreparedData(
        user_map=BiMap.string_int([f"u{i}" for i in range(n_users)]),
        item_map=BiMap.string_int([f"i{i}" for i in range(n_items)]),
        users=pairs[:, 0], items=pairs[:, 1],
        ratings=rng.integers(1, 6, len(pairs)).astype(np.float32),
    )


class TestTrainingSpans:
    def test_one_train_root_with_the_paths_spans(self):
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSAlgorithmParams,
        )

        algo = ALSAlgorithm(
            ALSAlgorithmParams(rank=4, num_iterations=3, seed=1)
        )
        model = algo.train(None, _toy_prepared())
        assert model.user_factors.shape == (60, 4)
        # the newest root is this job's (the store is a ring buffer that
        # other tests of this process have written to)
        root = [
            s for s in default_tracer().store.dump() if s["name"] == "train"
        ][-1]
        assert root["parentId"] is None
        assert root["tags"] == {"rank": 4, "iterations": 3, "shards": 1}
        spans = default_tracer().store.for_trace(root["traceId"])
        assert [s for s in spans if s["name"] == "train"] == [root]
        children = [s for s in spans if s["parentId"] == root["spanId"]]
        # below the children only what JitTelemetry records: a
        # ``jit.compile`` under the enqueue that compiled and, in a
        # process that taps jax.monitoring, each program's phases under
        # the span that brought it to the device, or under the phase
        # that encloses them
        enqueues = {s["spanId"] for s in children if s["name"] == "als.enqueue"}
        by_id = {s["spanId"]: s for s in spans}
        for s in spans:
            if s is not root and s not in children:
                assert s["name"] in ("jit.compile",) + JIT_PHASES, s
                if s["name"] == "jit.compile":
                    assert s["parentId"] in enqueues
                else:
                    above = by_id[s["parentId"]]
                    assert above in children or above["name"] in JIT_PHASES

        def tagged(name, key):
            return sorted(
                s["tags"][key] for s in children if s["name"] == name
            )

        for name in ("als.bucketize", "als.stage"):
            assert tagged(name, "side") == ["item", "user"], name
        # the fill share of the job's padding: every rating once on
        # either side, over the slots the ladder padded them to
        from predictionio_tpu.ops import als

        pd = _toy_prepared()
        assert tagged("als.bucketize", "ratings") == [len(pd.users)] * 2
        assert tagged("als.bucketize", "slots") == sorted(
            sum(b.idx.size for b in als.bucketize(
                rows, cols, pd.ratings, n, m, pad_to_blocks=True).buckets)
            for rows, cols, n, m in (
                (pd.users, pd.items, 60, 25), (pd.items, pd.users, 25, 60))
        )
        assert tagged("als.enqueue", "program") == [
            "half_item", "half_user", "iteration", "iteration",
        ]
        assert tagged("als.enqueue", "i") == [0, 0, 1, 2]
        # how often the dual form engages: real rows by the form their
        # bucket is solved in (explicit, rank 4: widths 1 and 2 dual),
        # on the span of each program, by the side or sides it solves
        sides = {}
        for side, (rows, cols, n, m) in (
            ("user", (pd.users, pd.items, 60, 25)),
            ("item", (pd.items, pd.users, 25, 60)),
        ):
            buckets = als.bucketize(rows, cols, pd.ratings, n, m).buckets
            sides[side] = tuple(
                sum(len(b.rows) for b in buckets if (b.width < 4) == dual)
                for dual in (True, False)
            )
            assert sum(sides[side]) == len(np.unique(rows))
        sides["both"] = tuple(map(sum, zip(sides["user"], sides["item"])))
        by_program = {
            s["tags"]["program"]: (s["tags"]["dual_rows"], s["tags"]["primal_rows"])
            for s in children if s["name"] == "als.enqueue"
        }
        assert by_program == {
            "half_user": sides["user"], "half_item": sides["item"],
            "iteration": sides["both"],
        }
        counts = {}
        for s in children:
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        assert counts == {
            "als.bucketize": 2, "als.stage": 2,
            "als.init_factors": 1, "als.enqueue": 4,
            "train.wait_device": 1, "train.fetch": 1,
        }
        # children lie inside the root, in the order the work happens
        ends = [s["startMs"] + s["durationMs"] for s in children]
        assert max(ends) <= root["startMs"] + root["durationMs"] + 1.0
        assert [s["name"] for s in children][-2:] == [
            "train.wait_device", "train.fetch",
        ]

    @pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
    @pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
    def test_host_work_of_a_job_in_order(self, implicit, resumed, tmp_path):
        """What the host does before the first program, in the order it
        does it, whatever the feedback and whichever iteration the job
        starts at: both sides bucketized, both staged, then the two half
        programs of the first executed iteration and a fused program for
        each one after it. Nothing between ``als.bucketize`` and
        ``als.stage``: no pass over the rows' indices."""
        from predictionio_tpu.ops import als
        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        pd = _toy_prepared()

        def train(iterations, **kw):
            cfg = als.ALSConfig(
                rank=4, iterations=iterations, seed=1, implicit_prefs=implicit)
            return als.als_train_coo(
                pd.users, pd.items, pd.ratings, 60, 25, cfg, **kw)

        kw, first = {}, 0
        if resumed:
            kw = {"checkpoint": CheckpointManager(str(tmp_path / "ck")),
                  "checkpoint_every": 1}
            train(1, **kw)
            first = 1
        with span("train") as root:
            train(3, **kw)
        spans = [
            s for s in default_tracer().store.for_trace(root.trace_id)
            if s["name"].startswith("als.") and s["name"] != "als.init_factors"
        ]
        assert [
            (s["name"], s["tags"].get("side") or s["tags"]["program"])
            for s in spans
        ] == [
            ("als.bucketize", "user"), ("als.bucketize", "item"),
            ("als.stage", "user"), ("als.stage", "item"),
            ("als.enqueue", "half_user"), ("als.enqueue", "half_item"),
        ] + [("als.enqueue", "iteration")] * (2 - first)
        assert [s["tags"]["i"] for s in spans if s["name"] == "als.enqueue"] == (
            [first, first] + list(range(first + 1, 3)))

    def test_no_index_sort_span_in_a_traced_job(self, tmp_path):
        """Nothing sorts a row's indices (PERF.md §6, PR 29): a job under
        the profiler leaves no ``als.index_sort`` in either sink, beside
        the spans it does leave."""
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSAlgorithmParams,
        )

        algo = ALSAlgorithm(ALSAlgorithmParams(rank=4, num_iterations=2, seed=1))
        jax.profiler.start_trace(str(tmp_path), profiler_options=_host_spans_only())
        try:
            algo.train(None, _toy_prepared())
        finally:
            jax.profiler.stop_trace()
        root = [
            s for s in default_tracer().store.dump() if s["name"] == "train"
        ][-1]
        in_store = {
            s["name"] for s in default_tracer().store.for_trace(root["traceId"])
        }
        in_trace = {name.split()[0] for name, _, _ in _host_events(str(tmp_path))}
        assert {"als.bucketize", "als.stage", "als.enqueue"} <= in_store
        assert {"pio.als.bucketize", "pio.als.stage", "pio.als.enqueue"} <= in_trace
        assert "als.index_sort" not in in_store
        assert "pio.als.index_sort" not in in_trace

    def test_profile_fence_is_a_span_of_its_own(self):
        """``als_train(profile=...)`` fences every iteration (the
        benchmark's traced run passes it): that wait has a name, so the
        children of the root still cover the job."""
        from predictionio_tpu.ops import als

        pd = _toy_prepared()
        by_user = als.bucketize(pd.users, pd.items, pd.ratings, 60, 25)
        by_item = als.bucketize(pd.items, pd.users, pd.ratings, 25, 60)
        cfg = als.ALSConfig(rank=4, iterations=3, seed=1)
        profile = {}
        with span("train") as root:
            als.als_train(by_user, by_item, cfg, profile=profile)
        spans = default_tracer().store.for_trace(root.trace_id)
        waits = [s for s in spans if s["name"] == "als.wait_device"]
        assert [s["tags"]["i"] for s in waits] == [0, 1, 2]
        # the counter of the dual form, as the spans carry it
        enqueued = {s["tags"]["program"]: s["tags"] for s in spans
                    if s["name"] == "als.enqueue"}
        assert profile["solve_forms"] == {
            side: [enqueued[f"half_{side}"][k] for k in ("dual_rows", "primal_rows")]
            for side in ("user", "item")
        }
        assert sum(profile["solve_forms"]["user"]) == len(np.unique(pd.users))
        # and never without ``profile``: nothing is fenced that was not
        with span("train") as bare:
            als.als_train(by_user, by_item, cfg)
        names = {s["name"] for s in default_tracer().store.for_trace(bare.trace_id)}
        assert "als.wait_device" not in names and "als.enqueue" in names

    def test_serving_batch_spans(self):
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            Query,
        )
        from predictionio_tpu.workflow.batching import MicroBatcher

        algo = ALSAlgorithm(ALSAlgorithmParams(rank=4, num_iterations=2, seed=1))
        model = algo.train(None, _toy_prepared())
        tracer = Tracer("query")
        batcher = MicroBatcher(
            lambda qs: [r for _, r in algo.batch_predict(
                model, list(enumerate(qs)))],
            max_batch=4, max_wait_ms=20.0, tracer=tracer,
        )
        try:
            with ThreadPoolExecutor(3) as pool:
                results = list(pool.map(
                    lambda i: batcher.submit(Query(user=f"u{i}", num=3), 60.0),
                    range(3),
                ))
        finally:
            batcher.close()
        assert all(len(r.item_scores) == 3 for r in results)
        spans = tracer.store.dump()
        executes = [s for s in spans if s["name"] == "batch.execute"]
        assert executes and sum(s["tags"]["b"] for s in executes) == 3
        for ex in executes:
            assert ex["parentId"] is None
            kids = [s["name"] for s in spans if s["parentId"] == ex["spanId"]]
            assert kids == [
                "predict.dispatch", "predict.fetch", "predict.results",
            ]
        # one span per batch each, nothing per request
        per_batch = [s for s in spans if not s["name"].startswith("jit.")]
        assert len(per_batch) == 4 * len(executes)


@pytest.fixture
def tapped(tmp_path, monkeypatch):
    """The process's jit telemetry with its jax.monitoring tap on and a
    persistent compile cache of this test's own, so the first compile of
    a program is a miss and the next one after ``jax.clear_caches()`` a
    hit, whatever ran in the process before."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from predictionio_tpu.obs.profile import default_telemetry
    from predictionio_tpu.utils.jax_cache import enable_compilation_cache

    names = (
        "jax_enable_compilation_cache",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {name: getattr(jax.config, name) for name in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    try:
        assert enable_compilation_cache() == str(tmp_path / "cache")
        yield default_telemetry()
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        cc.reset_cache()


def _phases_of(trace_id):
    return [
        s for s in default_tracer().store.for_trace(trace_id)
        if s["name"] in JIT_PHASES
    ]


def _inside(span_, outer, slack_ms=250.0):
    """``span_`` within ``outer``, to a slack of the host's scale: a span's
    start is ``time.time()`` and its length ``perf_counter``'s, a nested
    phase's both ends JAX's own ``time.time()``, and under six workers two
    readings of one moment lie a scheduler's quantum apart."""
    return (
        outer["startMs"] - slack_ms <= span_["startMs"]
        and span_["startMs"] + span_["durationMs"]
        <= outer["startMs"] + outer["durationMs"] + slack_ms
    )


class TestJitPhases:
    """A program's way to the device as spans: ``JitTelemetry``'s
    jax.monitoring tap (docs/observability.md, "Spans and scopes")."""

    def test_first_call_then_cache_hit_then_nothing(self, tapped):
        @jax.jit
        def fresh(x):
            return jnp.tanh(x @ x).sum()

        x = np.ones((16, 16), np.float32)  # no program of its own
        before = tapped.snapshot()
        with span("train") as root:
            with span("seqrec.step", {"i": 0}) as step:
                fresh(x)
        spans = {s["spanId"]: s for s in default_tracer().store.for_trace(root.trace_id)}
        # the innermost ambient span is the parent, and holds them (a
        # function of jax.numpy's that took long to trace inside
        # ``fresh``, as one may in a young process, is below its trace)
        phases = [s for s in spans.values() if s["parentId"] == step.span_id]
        assert [s["name"] for s in phases] == list(JIT_PHASES)
        for s in spans.values():
            if s["name"] in JIT_PHASES:
                assert s in phases or spans[s["parentId"]]["name"] == "jit.trace"
                assert _inside(s, spans[step.span_id])
        assert [s["tags"]["fn"] for s in phases] == ["fresh", "jit(fresh)", "jit(fresh)"]
        assert [s["tags"].get("cache") for s in phases] == [None, None, "miss"]
        first = tapped.delta_since(before)["cache"]
        assert (first["misses"], first["hits"], first["backend_compiles"]) == (1, 0, 1)
        assert first["trace_s"] > 0 and first["lower_s"] > 0
        assert first["retrieval_s"] == 0 and first["backend_compile_s"] > 0

        # the process forgets the program, the persistent cache does not
        jax.clear_caches()
        before = tapped.snapshot()
        with span("train") as again:
            fresh(x)
        phases = [s for s in _phases_of(again.trace_id)
                  if s["parentId"] == again.span_id]
        assert [s["name"] for s in phases] == list(JIT_PHASES)
        assert phases[-1]["tags"] == {"fn": "jit(fresh)", "cache": "hit"}
        second = tapped.delta_since(before)["cache"]
        assert (second["misses"], second["hits"], second["backend_compiles"]) == (0, 1, 1)
        assert second["retrieval_s"] > 0 and second["trace_s"] > 0
        # what a hit costs is the retrieval, and the backend phase holds it
        assert second["retrieval_s"] <= second["backend_compile_s"] + 1e-3

        # a program the process holds comes with no phase at all
        before = tapped.snapshot()
        with span("train") as third:
            fresh(x)
        assert _phases_of(third.trace_id) == []
        assert tapped.delta_since(before)["cache"] == {
            key: 0 for key in second
        }

    def test_a_trace_inside_a_trace_is_its_child(self, tapped, monkeypatch):
        from predictionio_tpu.obs import profile

        monkeypatch.setattr(profile, "_NESTED_FLOOR_S", 0.0)

        @jax.jit
        def inner_fn(x):
            return jnp.sin(x) * 2.0

        @jax.jit
        def outer_fn(x):
            return inner_fn(x).sum() + inner_fn(x + 1.0).sum()

        before = tapped.snapshot()
        with span("train") as root:
            outer_fn(np.ones((4, 4), np.float32))
        phases = _phases_of(root.trace_id)
        top = [s for s in phases if s["parentId"] == root.span_id]
        assert [(s["name"], s["tags"]["fn"]) for s in top] == [
            ("jit.trace", "outer_fn"), ("jit.lower", "jit(outer_fn)"),
            ("jit.backend", "jit(outer_fn)"),
        ]
        outer = top[0]
        by_id = {s["spanId"]: s for s in phases}
        nested = [s for s in phases if s not in top]
        # ``inner_fn`` is traced inside the outer trace and as its child;
        # whatever jax.numpy traced inside either is below them, never beside
        inner = [s for s in nested if s["tags"]["fn"] == "inner_fn"]
        assert inner
        for s in inner:
            assert s["name"] == "jit.trace" and s["parentId"] == outer["spanId"]
            assert _inside(s, outer)
        for s in nested:
            assert s["name"] == "jit.trace" and by_id[s["parentId"]]["name"] == "jit.trace"
        # ONE clock from here on, JAX's own: a nested phase's start and length
        # and the total are all its time spans. Children of one phase come one
        # after the other and never overlap, and together last no longer than
        # the phase that holds them
        traced = tapped.delta_since(before)["cache"]["trace_s"]
        for holder in by_id.values():
            held = sorted((s for s in nested if s["parentId"] == holder["spanId"]),
                          key=lambda s: s["startMs"])
            for first, then in zip(held, held[1:]):
                assert first["startMs"] + first["durationMs"] <= then["startMs"] + 1e-3
            length = traced * 1e3 if holder is outer else holder["durationMs"]
            assert sum(s["durationMs"] for s in held) <= length + 1e-3
        # the total counts the outer trace alone: what lies inside it is part
        # of its seconds, so it is the outer span's own length (that one on
        # the tracer's clock: the same interval taken twice)
        assert 0 < traced and abs(traced - outer["durationMs"] / 1e3) <= 0.25
        assert sum(s["durationMs"] for s in nested) > 0

    def test_short_nested_traces_are_left_out_of_the_store(self, tapped):
        """Tracing a step traces every jitted function it calls: the
        ring of 2,048 is for the jobs, so a phase inside another one is
        a span only from ``_NESTED_FLOOR_S`` up."""
        @jax.jit
        def small(x):
            return x + 1.0

        @jax.jit
        def calls_small(x):
            return small(x) * small(x * 2.0)

        with span("train") as root:
            calls_small(np.ones(3, np.float32))
        phases = _phases_of(root.trace_id)
        assert [s["name"] for s in phases
                if s["parentId"] == root.span_id] == list(JIT_PHASES)
        from predictionio_tpu.obs.profile import _NESTED_FLOOR_S

        for s in phases:
            if s["parentId"] != root.span_id:
                assert s["durationMs"] >= _NESTED_FLOOR_S * 1e3

    def test_under_no_span_only_the_totals_grow(self, tapped):
        @jax.jit
        def unseen(x):
            return jnp.cos(x).sum()

        assert current_context() is None
        stored = len(default_tracer().store.dump())
        before = tapped.snapshot()
        unseen(np.ones(5, np.float32))
        assert len(default_tracer().store.dump()) == stored
        delta = tapped.delta_since(before)["cache"]
        assert delta["backend_compiles"] == 1 and delta["misses"] == 1
        assert delta["trace_s"] > 0 and delta["lower_s"] > 0

    def test_phase_spans_lie_in_the_profilers_trace(self, tapped, tmp_path):
        """The outermost phases are real spans, entered when JAX says the
        phase begins: a profiler session holds them as ``pio.jit.<phase>``
        inside the span they were opened under, on its own clock."""
        @jax.jit
        def profiled(x):
            return jnp.exp(x).sum()

        jax.profiler.start_trace(
            str(tmp_path / "profile"), profiler_options=_host_spans_only())
        try:
            with span("seqrec.init"):
                profiled(np.ones(7, np.float32))
        finally:
            jax.profiler.stop_trace()
        events = {
            name: (s, e) for name, s, e in _host_events(str(tmp_path / "profile"))
        }
        outer = events["pio.seqrec.init"]
        for name in ("pio.jit.trace fn=profiled", "pio.jit.lower fn=jit(profiled)",
                     "pio.jit.backend fn=jit(profiled) cache=off"):
            assert outer[0] <= events[name][0] and events[name][1] <= outer[1], name

    def test_wrapped_call_keeps_jit_compile_around_its_phases(self, tapped):
        """``jit.compile`` is the whole first call of a function that goes
        through ``JitTelemetry.call``; the phases of the same program lie
        inside its interval, under the same parent."""
        @jax.jit
        def solve(x):
            return jnp.linalg.inv(x + jnp.eye(3)).sum()

        with span("als.enqueue") as enqueue:
            tapped.call("test.solve", solve, np.ones((3, 3), np.float32))
        spans = default_tracer().store.for_trace(enqueue.trace_id)
        (compile_,) = [s for s in spans if s["name"] == "jit.compile"]
        assert compile_["tags"] == {"fn": "test.solve", "retrace": False}
        top = [s for s in spans if s["name"] in JIT_PHASES
               and s["parentId"] == enqueue.span_id]
        own = [s for s in top if s["tags"]["fn"] in ("solve", "jit(solve)")]
        assert [s["name"] for s in own] == list(JIT_PHASES)
        assert compile_["parentId"] == enqueue.span_id
        for s in own:
            assert _inside(s, compile_, slack_ms=5.0)

    def test_a_sequence_jobs_first_trace_shows_where_its_first_step_went(self, tapped):
        from predictionio_tpu.models import sequencerec
        from predictionio_tpu.models.sequencerec import (
            SeqPreparator,
            SeqPreparatorParams,
            SeqRecAlgorithm,
            SeqRecAlgorithmParams,
            TrainingData,
        )

        seqs = [[f"i{(u + j) % 9}" for j in range(12)] for u in range(6)]
        td = TrainingData(user_ids=[f"u{u}" for u in range(6)], sequences=seqs)
        pd = SeqPreparator(SeqPreparatorParams(seq_len=8)).prepare(None, td)
        algo = SeqRecAlgorithm(SeqRecAlgorithmParams(
            d_model=16, n_heads=2, n_layers=1, steps=3, batch_size=4, seed=5))
        # as a fresh process finds them: no program made, none compiled
        sequencerec._programs.cache_clear()
        jax.clear_caches()

        def job():
            algo.train(None, pd)
            root = [s for s in default_tracer().store.dump()
                    if s["name"] == "train" and s["parentId"] is None][-1]
            return default_tracer().store.for_trace(root["traceId"])

        first = job()
        by_id = {s["spanId"]: s for s in first}

        def under(s):
            while s["name"] in JIT_PHASES:
                s = by_id[s["parentId"]]
            return s["name"], s.get("tags", {}).get("i")

        phases = [s for s in first if s["name"] in JIT_PHASES]
        homes = {under(s) for s in phases}
        assert homes == {("seqrec.init", None), ("seqrec.step", 0)}
        step0 = [s for s in phases if under(s) == ("seqrec.step", 0)]
        top = [s for s in step0 if by_id[s["parentId"]]["name"] == "seqrec.step"]
        assert [(s["name"], s["tags"]["fn"]) for s in top] == [
            ("jit.trace", "step"), ("jit.lower", "jit(step)"),
            ("jit.backend", "jit(step)"),
        ]
        # what the step's trace enclosed is below it, never beside it
        for s in step0:
            if s not in top:
                assert by_id[s["parentId"]]["name"] in JIT_PHASES
        # the step's phases make up its first span: that is where it went
        (span0,) = [s for s in first
                    if s["name"] == "seqrec.step" and s["tags"]["i"] == 0]
        assert sum(s["durationMs"] for s in top) <= span0["durationMs"] + 1.0
        assert sum(s["durationMs"] for s in top) > 0.5 * span0["durationMs"]
        # a later job of the same shape brings nothing to the device
        assert [s for s in job() if s["name"] in JIT_PHASES] == []


SCOPES_PALLAS = (
    "als.user_side", "als.item_side", "als.gather", "als.gramian",
    "als.solve", "als.scatter",
)


def _staged(seed=0, n_u=400, n_i=60, nnz=6000):
    from predictionio_tpu.ops import als

    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_u + 1) ** 0.9
    users = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    items = rng.integers(0, n_i, nnz).astype(np.int32)
    vals = rng.integers(1, 6, nnz).astype(np.float32)
    by_user = als.stage(als.bucketize(users, items, vals, n_u, n_i))
    by_item = als.stage(als.bucketize(items, users, vals, n_i, n_u))
    return als, by_user, by_item, n_u, n_i


class TestDeviceScopes:
    @pytest.mark.parametrize("implicit", [False, True])
    def test_iteration_text_holds_every_scope(self, implicit):
        als, by_user, by_item, n_u, n_i = _staged()
        text = als._als_iteration.lower(
            als._bucket_tensors(by_user), als._bucket_tensors(by_item),
            jnp.zeros((n_i, 16)), jnp.float32(0.1), jnp.float32(1.0),
            rank=16, implicit=implicit, n_users=n_u, n_items=n_i,
            solve_mode="pallas", gather_dtype="f32", mesh=None,
        ).as_text(debug_info=True)
        widths = {b.idx.shape[-1] for s in (by_user, by_item) for b in s.buckets}
        assert {1, 2, 4, 8, 16, 32, 128} <= widths
        wanted = SCOPES_PALLAS + tuple(f"als.k{w}" for w in widths)
        if implicit:
            wanted += ("als.yty",)
        for scope in wanted:
            assert scope in text, scope
        assert ("als.yty" in text) == implicit
        # the name stack reads side / rung / own width / phase. The rung
        # is the bucket the rows sat in before the ladder grew its narrow
        # widths, so ``als.w8`` (``w8_device_s``) holds every row of at
        # most 8 ratings; no ``als.w`` scope is named after a narrow
        # width, or the per-bucket table would count it twice. Widths
        # under the rank gather in XLA (``als.gather``), the wider ones
        # inside ``gramian_fused``
        rungs = {1: 8, 2: 8, 4: 8, 8: 8, 16: 32, 32: 32}
        for side, staged in (("user", by_user), ("item", by_item)):
            for bucket in staged.buckets:
                k = bucket.idx.shape[-1]
                stack = f"als.{side}_side/als.w{rungs.get(k, k)}/als.k{k}/"
                assert stack in text, stack
        for narrow in (1, 2, 4, 16):
            assert f"als.w{narrow}/" not in text
        assert "als.user_side/als.w8/als.k2/als.scatter/scatter" in text
        # an explicit bucket under the rank (16) runs the dual body: its
        # two products carry the phase they are counted under, and an
        # implicit job traces neither
        for product in ("als.gramian/bkr,bjr->kjb", "als.solve/bkr,kb->br"):
            assert (product in text) == (not implicit)
        # (a chunk's phases are a called function in this text, so their
        # stack is whole only in the compiled program:
        # tests/test_chip_compile.py reads it there)

    @pytest.mark.parametrize("side", ["user", "item"])
    def test_half_text_names_the_side_it_solves(self, side):
        als, by_user, by_item, n_u, n_i = _staged()
        staged, n_rows, n_cols = (
            (by_user, n_u, n_i) if side == "user" else (by_item, n_i, n_u)
        )
        text = als._als_half.lower(
            jnp.zeros((n_cols, 8)), als._bucket_tensors(staged),
            jnp.float32(0.1), jnp.float32(1.0),
            rank=8, implicit=False, n_rows=n_rows, solve_mode="chunked",
            gather_dtype="f32", mesh=None, side=side,
        ).as_text(debug_info=True)
        other = "item" if side == "user" else "user"
        assert f"als.{side}_side" in text
        assert f"als.{other}_side" not in text
        # the XLA solve path: gather and Gramian in ``system``, the
        # Cholesky under ``als.solve``
        for scope in ("als.gather", "als.gramian", "als.solve", "als.scatter"):
            assert scope in text, scope

    def test_pallas_calls_carry_their_names(self):
        from predictionio_tpu.ops.pallas_kernels import (
            gramian_fused,
            spd_solve_t,
            top_k_streaming,
        )

        def kernel_names(fn, *args):
            found = []

            def walk(jaxpr):
                for eqn in jaxpr.eqns:
                    if eqn.primitive.name == "pallas_call":
                        found.append(eqn.params["name"])
                    for value in eqn.params.values():
                        inner = getattr(value, "jaxpr", value)
                        if hasattr(inner, "eqns"):
                            walk(inner)

            walk(jax.make_jaxpr(fn)(*args).jaxpr)
            return found

        f32 = jnp.float32
        assert kernel_names(
            spd_solve_t, jnp.zeros((8, 8, 128), f32), jnp.zeros((8, 128), f32)
        ) == ["spd_solve_t"]
        assert kernel_names(
            lambda y, idx, w, r, ridge: gramian_fused(y, idx, w, r, ridge),
            jnp.zeros((64, 128), f32), jnp.zeros((8, 8), jnp.int32),
            jnp.zeros((8, 8), f32), jnp.zeros((8, 8), f32), jnp.zeros((8,), f32),
        ) == ["gramian_fused"]
        assert kernel_names(
            lambda q, items: top_k_streaming(q, items, 4),
            jnp.zeros((8, 8), f32), jnp.zeros((512, 8), f32),
        ) == ["top_k_streaming"]
