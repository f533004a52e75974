"""Performance observability (ISSUE 8, docs/observability.md#profiling,
docs/performance.md#perf-ledger).

Five layers:

1. **Jit telemetry**: compile/retrace counting via cache-size probes on
   a fake jitted callable, replay-on-bind into a metrics registry,
   attribute-forwarding wrappers, and run deltas.
2. **Phase profiling**: the near-zero-cost contract when ``PIO_PROFILE``
   is off (the injected clock and fence are NEVER called), fenced
   device timing, and roofline math — all on injected clocks.
3. **Exposition round trip**: the new ``pio_jit_*`` metric families
   survive ``expo.render`` → ``expo.parse_text`` with values intact
   (the scrape path ``pio profile --node`` and ``pio top`` ride).
4. **Perf ledger**: append/load durability (torn lines skipped),
   bench-record normalization, comparability grouping (a CPU fallback
   never gates a TPU number), and the regression gate against the
   checked-in BENCH_r0*.json history — flat ⇒ clean, an injected
   20%-worse synthetic record ⇒ flagged (the ISSUE 8 acceptance).
5. **CLIs**: ``pio perf diff|trend`` and ``pio profile`` driven
   in-process through the console, including the smoke-train report
   (per-phase wall/device time, compile counts, retrace counts, a
   roofline estimate) and the fleet columns read through LIVE
   exposition (a real HTTP scrape of a server's ``/metrics``).

No wall-clock sleeps; the only waiting is loopback HTTP.
"""

from __future__ import annotations

import json
import os

import pytest

from predictionio_tpu.obs import expo
from predictionio_tpu.obs import perfledger
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.profile import (
    DEVICE_PEAKS,
    JitTelemetry,
    PhaseProfiler,
    render_profile_report,
    roofline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")
#: four flat bench rounds + one failed round, in the driver's file shape
HISTORY = os.path.join(REPO, "tests", "fixtures", "perf_history")


class FakeJit:
    """Mimics a jitted callable: ``_cache_size`` grows on every new
    'signature' (argument) — exactly the probe JitTelemetry reads."""

    def __init__(self):
        self._signatures = set()
        self.calls = 0

    def _cache_size(self) -> int:
        return len(self._signatures)

    def __call__(self, signature, **kwargs):
        self.calls += 1
        self._signatures.add((signature, tuple(sorted(kwargs.items()))))
        return signature

    def lower(self):  # AOT-surface stand-in for wrapper forwarding
        return "lowered"


# ---------------------------------------------------------------------------
# 1. Jit telemetry
# ---------------------------------------------------------------------------


class TestJitTelemetry:
    def test_compile_and_retrace_counting(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("toy", fn, "a")  # first compile: warmup
        tel.call("toy", fn, "a")  # cache hit: nothing
        tel.call("toy", fn, "b")  # second compile: retrace
        tel.call("toy", fn, "c")  # third compile: retrace
        snap = tel.snapshot()
        assert snap["fns"]["toy"]["compiles"] == 3
        assert snap["fns"]["toy"]["retraces"] == 2
        assert fn.calls == 4

    def test_non_jitted_callable_passes_through(self):
        tel = JitTelemetry()
        assert tel.call("plain", lambda x: x + 1, 41) == 42
        assert tel.snapshot()["fns"] == {}

    def test_bind_replays_totals_and_counts_live(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("solve", fn, "a")
        tel.call("solve", fn, "b")
        reg = MetricsRegistry()
        tel.bind(reg)  # after the fact: totals must replay
        text = expo.render(reg)
        assert 'pio_jit_compiles_total{fn="solve"} 2' in text
        assert 'pio_jit_retraces_total{fn="solve"} 1' in text
        tel.call("solve", fn, "c")  # live after bind
        text = expo.render(reg)
        assert 'pio_jit_compiles_total{fn="solve"} 3' in text
        assert 'pio_jit_retraces_total{fn="solve"} 2' in text
        # cache gauges exist even with monitoring unattached
        assert "pio_jit_cache_hits 0" in text

    def test_bind_is_idempotent(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("f", fn, "a")
        reg = MetricsRegistry()
        tel.bind(reg)
        tel.bind(reg)  # second bind must not double-replay
        assert 'pio_jit_compiles_total{fn="f"} 1' in expo.render(reg)

    def test_wrap_counts_and_forwards_attributes(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        wrapped = tel.wrap("w", FakeJit())
        wrapped("a")
        wrapped("b")
        assert tel.snapshot()["fns"]["w"]["compiles"] == 2
        # AOT tooling reaches through the wrapper
        assert wrapped.lower() == "lowered"
        assert wrapped._cache_size() == 2

    def test_racing_first_compile_counted_once(self):
        """Two threads racing the same first compile both observe cache
        growth (the loser waits on jax's compile lock, then reads
        after > before); the high-water mark must credit ONE compile and
        no phantom retrace. Reproduced deterministically by scripting
        the cache-size reads the loser thread would see."""

        class ScriptedSizes:
            def __init__(self, sizes):
                self._sizes = list(sizes)

            def _cache_size(self):
                return self._sizes.pop(0)

            def __call__(self):
                return None

        # winner: before=0 after=1; loser replays before=0 after=1
        fn = ScriptedSizes([0, 1, 0, 1])
        tel = JitTelemetry(clock=lambda: 0.0)
        tel.call("raced", fn)
        tel.call("raced", fn)
        snap = tel.snapshot()["fns"]["raced"]
        assert snap["compiles"] == 1
        assert snap["retraces"] == 0
        # a REAL later retrace (cache grows past the mark) still counts
        fn._sizes = [1, 2]
        tel.call("raced", fn)
        snap = tel.snapshot()["fns"]["raced"]
        assert snap["compiles"] == 2
        assert snap["retraces"] == 1

    def test_delta_since_isolates_one_run(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("f", fn, "a")
        before = tel.snapshot()
        tel.call("f", fn, "b")
        tel.call("g", FakeJit(), "x")
        delta = tel.delta_since(before)
        assert delta["fns"]["f"] == {
            "compiles": 1, "retraces": 1, "compile_s": 0.0,
        }
        assert delta["fns"]["g"]["compiles"] == 1
        assert "retraces" in delta["fns"]["g"]


# -- the phases of a program's way to the device ----------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_KEYS = {
    "hits", "misses", "backend_compiles", "backend_compile_s",
    "trace_s", "lower_s", "retrieval_s", "compile_time_saved_s",
}


class _Phase:
    """What JAX's ``dispatch.log_elapsed_time`` raises around a phase, on
    times of the test's choosing: a scalar carrying the start when it
    begins; a duration and a time span when it ends."""

    def __init__(self, event, fn, start, end):
        self.event, self.fn, self.start, self.end = event, fn, start, end

    def __enter__(self):
        from jax import monitoring

        monitoring.record_scalar(self.event, self.start, fun_name=self.fn)

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.record_event_duration_secs(
            self.event, self.end - self.start, fun_name=self.fn)
        monitoring.record_event_time_span(
            self.event, self.start, self.end, fun_name=self.fn)


@pytest.fixture
def tap():
    """The process's telemetry with its jax.monitoring tap on, a tracer
    on a clock that stands still, and what the test's phases changed."""
    from predictionio_tpu.obs.profile import default_telemetry
    from predictionio_tpu.obs.trace import Tracer

    tel = default_telemetry()
    assert tel.attach_monitoring() is True
    tracer = Tracer("test", clock=lambda: 0.0, wall=lambda: 50.0)
    before = tel.snapshot()
    return tel, tracer, lambda: tel.delta_since(before)["cache"]


class TestJitPhaseTotals:
    def test_snapshot_and_delta_keep_every_key(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        snap = tel.snapshot()
        assert set(snap) == {"fns", "cache"}
        assert set(snap["cache"]) == CACHE_KEYS
        assert all(value == 0 for value in snap["cache"].values())
        assert tel.delta_since(snap)["cache"] == snap["cache"]

    def test_report_line_carries_the_totals_where_it_is_given_them(self):
        cache = {**JitTelemetry().snapshot()["cache"], "trace_s": 7.6, "lower_s": 1.25}
        line = render_profile_report("t", cache=cache).splitlines()[-1]
        assert line.startswith("compilation cache: hits=0 misses=0")
        assert line.endswith("trace_s=7.600 lower_s=1.250 retrieval_s=0.000")
        # a scrape of /metrics brings the four older keys alone
        scraped = {k: cache[k] for k in ("hits", "misses", "backend_compiles", "backend_compile_s")}
        assert render_profile_report("t", cache=scraped).splitlines()[-1].endswith(
            "backend_compile_s=0.000")

    @pytest.mark.parametrize(
        "inner_s,recorded", [(0.5, True), (0.004, False)],
        ids=["long-nested-trace-is-a-child", "short-nested-trace-is-left-out"])
    def test_nested_trace_counts_once(self, tap, inner_s, recorded):
        from jax import monitoring

        tel, tracer, delta = tap
        with tracer.span("seqrec.step") as step:
            with _Phase(TRACE, "step", 100.0, 103.0):
                with _Phase(TRACE, "gated_delta_rule", 101.0, 101.0 + inner_s):
                    with _Phase(TRACE, "_where", 101.0, 101.0 + inner_s / 2):
                        pass
            with _Phase(LOWER, "jit(step)", 103.0, 104.0):
                pass
            with _Phase(BACKEND, "jit(step)", 104.0, 110.0):
                monitoring.record_event("/jax/compilation_cache/cache_misses")
        got = delta()
        assert got == {
            "hits": 0, "misses": 1, "backend_compiles": 1,
            "backend_compile_s": 6.0, "trace_s": 3.0, "lower_s": 1.0,
            "retrieval_s": 0.0, "compile_time_saved_s": 0.0,
        }
        spans = tracer.store.for_trace(step.trace_id)
        by_fn = {(s["name"], s["tags"]["fn"]): s for s in spans if "tags" in s}
        top = [by_fn[key] for key in (
            ("jit.trace", "step"), ("jit.lower", "jit(step)"),
            ("jit.backend", "jit(step)"))]
        assert all(s["parentId"] == step.span_id for s in top)
        assert top[2]["tags"] == {"fn": "jit(step)", "cache": "miss"}
        nested = [s for s in spans if s["name"] == "jit.trace" and s not in top]
        if not recorded:
            assert nested == []
            return
        inner, innermost = (
            by_fn["jit.trace", "gated_delta_rule"], by_fn["jit.trace", "_where"])
        assert sorted(nested, key=id) == sorted([inner, innermost], key=id)
        assert inner["parentId"] == top[0]["spanId"]
        assert innermost["parentId"] == inner["spanId"]
        # JAX's own time span, on the clock ``startMs`` is stamped with
        assert (inner["startMs"], inner["durationMs"]) == (101000.0, 500.0)
        assert (innermost["startMs"], innermost["durationMs"]) == (101000.0, 250.0)

    def test_cache_hit_carries_retrieval_and_saved_seconds(self, tap):
        from jax import monitoring

        tel, tracer, delta = tap
        with tracer.span("als.enqueue") as enqueue:
            with _Phase(BACKEND, "jit(_als_iteration_body)", 10.0, 14.0):
                monitoring.record_event("/jax/compilation_cache/cache_hits")
                monitoring.record_event_duration_secs(
                    "/jax/compilation_cache/compile_time_saved_sec", 23.5)
                monitoring.record_event_duration_secs(
                    "/jax/compilation_cache/cache_retrieval_time_sec", 3.75)
        got = delta()
        assert (got["hits"], got["misses"], got["backend_compiles"]) == (1, 0, 1)
        assert got["retrieval_s"] == 3.75 and got["compile_time_saved_s"] == 23.5
        assert got["backend_compile_s"] == 4.0
        assert got["trace_s"] == got["lower_s"] == 0.0
        (backend,) = [s for s in tracer.store.for_trace(enqueue.trace_id)
                      if s["name"] == "jit.backend"]
        assert backend["tags"]["cache"] == "hit"

    def test_backend_seconds_keep_counting_past_the_replay_samples(self, tap):
        """The samples kept for a registry bound later are capped; the
        total is not (it stood still after 256 compiles before PR 36,
        which a server or a test process passes in minutes)."""
        from predictionio_tpu.obs import profile

        tel, tracer, delta = tap
        for _ in range(profile._MAX_SAMPLES + 3):
            with _Phase(BACKEND, "jit(op)", 0.0, 0.5):
                pass
        got = delta()
        assert got["backend_compiles"] == profile._MAX_SAMPLES + 3
        assert got["backend_compile_s"] == 0.5 * (profile._MAX_SAMPLES + 3)

    def test_a_backend_phase_no_cache_event_reaches_says_off(self, tap):
        tel, tracer, delta = tap
        with tracer.span("predict.dispatch") as dispatch:
            with _Phase(BACKEND, "jit(top_k)", 1.0, 2.0):
                pass
        (backend,) = [s for s in tracer.store.for_trace(dispatch.trace_id)
                      if s["name"] == "jit.backend"]
        assert backend["tags"] == {"fn": "jit(top_k)", "cache": "off"}
        assert delta()["backend_compiles"] == 1

    def test_every_second_counts_once(self, tap):
        """A phase inside another kind of phase (lowering a rule that
        traces a jitted helper; an eager operation while tracing) is in
        the enclosing phase's total alone."""
        tel, tracer, delta = tap
        with _Phase(LOWER, "jit(step)", 0.0, 4.0):
            with _Phase(TRACE, "helper", 1.0, 2.0):
                pass
        with _Phase(TRACE, "step", 4.0, 9.0):
            with _Phase(LOWER, "jit(eager)", 5.0, 6.0):
                pass
            with _Phase(BACKEND, "jit(eager)", 6.0, 8.0):
                pass
        got = delta()
        assert (got["lower_s"], got["trace_s"]) == (4.0, 5.0)
        # every backend compile counts, as it always did
        assert (got["backend_compiles"], got["backend_compile_s"]) == (1, 2.0)
        assert tracer.store.dump() == []  # under no span nothing is stored

    def test_an_end_without_its_start_counts_without_a_span(self, tap):
        from jax import monitoring

        tel, tracer, delta = tap
        with tracer.span("train") as root:
            # the tap attached while another thread's phase was open
            monitoring.record_event_time_span(TRACE, 1.0, 3.0, fun_name="late")
            # and a start whose end never came: dropped when the phase
            # around it ends, which still counts
            with _Phase(TRACE, "outer", 10.0, 12.0):
                monitoring.record_scalar(TRACE, 11.0, fun_name="cut_short")
        assert delta()["trace_s"] == 4.0
        names = [s["tags"]["fn"] for s in tracer.store.for_trace(root.trace_id)
                 if s["name"] == "jit.trace"]
        assert names == ["outer"]
        with _Phase(TRACE, "next", 20.0, 21.0):  # nothing is left open
            pass
        assert delta()["trace_s"] == 5.0

    def test_a_failing_tracer_never_fails_the_phase(self, tap, monkeypatch):
        tel, tracer, delta = tap

        def broken(*args, **kwargs):
            raise RuntimeError("store is gone")

        with tracer.span("train"):
            monkeypatch.setattr(tracer, "span", broken)
            monkeypatch.setattr(tracer, "record", broken)
            with _Phase(TRACE, "step", 0.0, 2.0):
                with _Phase(TRACE, "inner", 0.5, 1.5):
                    pass
            monkeypatch.undo()
        assert delta()["trace_s"] == 2.0

    def test_phase_histogram_mirrors_and_replays(self, tap):
        tel, tracer, delta = tap
        live = MetricsRegistry()
        tel.bind(live)
        before = expo.parse_text(expo.render(live))

        def count(parsed, phase):
            return sum(
                value for labels, value in parsed.get("pio_jit_phase_seconds_count", [])
                if labels.get("phase") == phase)

        from jax import monitoring

        with _Phase(TRACE, "f", 0.0, 1.0):
            pass
        with _Phase(LOWER, "jit(f)", 1.0, 1.5):
            pass
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        after = expo.parse_text(expo.render(live))
        for phase in ("trace", "lower", "retrieval"):
            assert count(after, phase) == count(before, phase) + 1, phase
        # a registry bound later is told what happened before it was
        late = MetricsRegistry()
        tel.bind(late)
        replayed = expo.parse_text(expo.render(late))
        for phase in ("trace", "lower", "retrieval"):
            assert count(replayed, phase) >= 1, phase


# ---------------------------------------------------------------------------
# 2. Phase profiling
# ---------------------------------------------------------------------------


class TestPhaseProfiler:
    def test_disabled_hooks_are_free(self):
        """The PIO_PROFILE-off contract: neither the clock nor the fence
        is EVER called, and nothing is recorded — production paths keep
        the hooks at (near) zero cost."""
        calls = {"clock": 0, "fence": 0}

        def clock():
            calls["clock"] += 1
            return float(calls["clock"])

        def fence(value):
            calls["fence"] += 1

        prof = PhaseProfiler(enabled=False, clock=clock, fence=fence)
        for _ in range(100):
            with prof.phase("hot", flops=1e12) as ph:
                ph.fence("result")
        prof.record("adopted", wall_s=1.0)
        assert calls == {"clock": 0, "fence": 0}
        assert prof.summary() == {}

    def test_enabled_respects_env_default(self, monkeypatch):
        monkeypatch.delenv("PIO_PROFILE", raising=False)
        assert PhaseProfiler().enabled is False
        monkeypatch.setenv("PIO_PROFILE", "1")
        assert PhaseProfiler().enabled is True

    def test_fenced_device_time_and_roofline(self):
        # injected clock: each read advances 1s, so wall and device
        # times are exact integers
        ticks = {"n": 0}

        def clock():
            ticks["n"] += 1
            return float(ticks["n"])

        fenced = []
        prof = PhaseProfiler(
            enabled=True, clock=clock, fence=fenced.append,
            peaks=DEVICE_PEAKS["TPU v5 lite"],
        )
        with prof.phase("solve", flops=197e12, hbm_bytes=819e9) as ph:
            ph.fence("device-value")  # t0=1, fence read=2 → device 1s
        # exit read=3 → wall 2s
        summary = prof.summary()
        assert fenced == ["device-value"]
        st = summary["solve"]
        assert st["count"] == 1
        assert st["wall_s"] == pytest.approx(2.0)
        assert st["device_s"] == pytest.approx(1.0)
        # 197e12 flops over the 1s device time vs the 98.5e12 f32 peak
        assert st["mfu"] == pytest.approx(2.0)
        assert st["hbm_util"] == pytest.approx(1.0)
        assert st["tflops_per_s"] == pytest.approx(197.0)
        assert st["hbm_gb_per_s"] == pytest.approx(819.0)

    def test_unfenced_phase_device_equals_wall(self):
        ticks = {"n": 0}

        def clock():
            ticks["n"] += 1
            return float(ticks["n"])

        prof = PhaseProfiler(enabled=True, clock=clock, fence=lambda v: v)
        with prof.phase("host-only"):
            pass
        st = prof.summary()["host-only"]
        assert st["wall_s"] == st["device_s"] == pytest.approx(1.0)

    def test_roofline_zero_time(self):
        assert roofline(
            1e12, 1e9, 0.0, peaks=DEVICE_PEAKS["TPU v5 lite"]
        ) == {
            "tflops_per_s": 0.0, "hbm_gb_per_s": 0.0,
            "mfu": 0.0, "hbm_util": 0.0,
        }

    def test_roofline_unknown_device_kind_has_no_utilization(self):
        # tests run on the CPU backend, whose device_kind has no peaks:
        # achieved rates only — never a share of another chip's peak
        import jax

        assert jax.devices()[0].device_kind not in DEVICE_PEAKS
        assert roofline(2e12, 4e9, 2.0) == {
            "tflops_per_s": pytest.approx(1.0),
            "hbm_gb_per_s": pytest.approx(2.0),
        }
        prof = PhaseProfiler(enabled=True, fence=lambda v: v)
        with prof.phase("solve", flops=1.0, hbm_bytes=1.0):
            pass
        assert "mfu" not in prof.summary()["solve"]
        text = render_profile_report("unit", phases=prof.summary())
        assert "v5e" not in text and " -" in text

    def test_report_renders_all_sections(self):
        text = render_profile_report(
            "unit",
            phases={"train": {"count": 2, "wall_s": 3.0, "device_s": 2.5,
                              "tflops_per_s": 1.0, "mfu": 0.01,
                              "hbm_util": 0.02}},
            jit={"als_half": {"compiles": 2, "retraces": 1,
                              "compile_s": 3.5}},
            cache={"hits": 1, "misses": 2, "backend_compiles": 3,
                   "backend_compile_s": 4.0},
            device="TFRT_CPU_0",
        )
        for token in ("train", "als_half", "retraces", "mfu",
                      "hits=1", "TFRT_CPU_0"):
            assert token in text, text


# ---------------------------------------------------------------------------
# 3. Exposition round trip over the profile families
# ---------------------------------------------------------------------------


class TestProfileExpositionRoundTrip:
    def test_jit_families_survive_render_parse(self):
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("als_half", fn, "a")
        tel.call("als_half", fn, "b")
        tel.call("serving.topk_users", FakeJit(), "q")
        reg = MetricsRegistry()
        tel.bind(reg)
        parsed = expo.parse_text(expo.render(reg))
        compiles = dict(
            (labels["fn"], value)
            for labels, value in parsed["pio_jit_compiles_total"]
        )
        assert compiles == {"als_half": 2.0, "serving.topk_users": 1.0}
        retraces = dict(
            (labels["fn"], value)
            for labels, value in parsed["pio_jit_retraces_total"]
        )
        assert retraces["als_half"] == 1.0
        # histogram family: _bucket/_sum/_count all present and coherent
        assert "pio_jit_compile_seconds_bucket" in parsed
        counts = {
            labels["fn"]: value
            for labels, value in parsed["pio_jit_compile_seconds_count"]
        }
        assert counts["als_half"] == 2.0
        assert parsed["pio_jit_cache_hits"][0][1] == 0.0
        assert parsed["pio_jit_cache_misses"][0][1] == 0.0

    def test_scraped_report_reconstruction(self):
        """The pio profile --node path: scrape text → report inputs."""
        from predictionio_tpu.tools.perf import _report_from_metrics

        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("fold_in.solve_rows", fn, "a")
        tel.call("fold_in.solve_rows", fn, "b")
        reg = MetricsRegistry()
        tel.bind(reg)
        reg.gauge(
            "pio_train_phase_seconds", labelnames=("phase",)
        ).set(4.5, phase="train[0]")
        data = _report_from_metrics(expo.parse_text(expo.render(reg)))
        assert data["jit"]["fold_in.solve_rows"]["compiles"] == 2.0
        assert data["jit"]["fold_in.solve_rows"]["retraces"] == 1.0
        assert data["phases"]["train[0]"]["wall_s"] == 4.5
        text = render_profile_report("node", **data)
        assert "fold_in.solve_rows" in text


# ---------------------------------------------------------------------------
# 4. Perf ledger + regression gate
# ---------------------------------------------------------------------------


def _bench_like(value: float, source: str = "bench", **over) -> dict:
    base = {
        "metric": "ml20m_als_rank50_train_s",
        "value": value,
        "unit": "s",
        "device": "TFRT_CPU_0",
        "scale": 0.01,
        "solve_mode": "chunked",
        "gather_dtype": "f32",
        "sort_gather": False,
        "fused_gather": False,
        "holdout_rmse": 0.53,
        "vs_baseline": 0.0,
    }
    base.update(over)
    return perfledger.bench_to_record(base, source=source)


class TestPerfLedger:
    def test_append_load_round_trip_skips_torn_line(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        first = _bench_like(12.0, source="r1")
        second = _bench_like(12.1, source="r2")
        perfledger.append_record(path, first)
        with open(path, "a") as fh:
            fh.write('{"torn": ')  # a crash mid-append
            fh.write("\n")
        perfledger.append_record(path, second)
        records = perfledger.load_ledger(path)
        assert [r["source"] for r in records] == ["r1", "r2"]
        assert records[0]["schema"] == perfledger.SCHEMA_VERSION
        assert records[0]["levers"]["solve_mode"] == "chunked"

    def test_load_missing_ledger_is_empty(self, tmp_path):
        assert perfledger.load_ledger(str(tmp_path / "none.jsonl")) == []

    def test_checked_in_history_loads_and_is_flat(self):
        history = perfledger.load_bench_history(HISTORY)
        # r01 failed bring-up (parsed null) and contributes nothing
        assert len(history) >= 4
        assert all(r["schema"] == 1 for r in history)
        assert perfledger.detect_regressions(history) == []

    def test_injected_regression_is_flagged(self):
        history = perfledger.load_bench_history(HISTORY)
        prior = [r["value"] for r in history]
        baseline = sorted(prior)[len(prior) // 2]
        worse = _bench_like(round(baseline * 1.25, 3), source="injected")
        flagged = perfledger.detect_regressions(history + [worse])
        assert len(flagged) == 1
        assert flagged[0]["latest_source"] == "injected"
        assert flagged[0]["ratio"] > 1.15

    def test_device_class_separates_groups(self):
        # a TPU record never gates (or is gated by) the CPU history
        records = [
            _bench_like(12.0, source="c1"),
            _bench_like(12.1, source="c2"),
            _bench_like(12.0, source="c3"),
            _bench_like(
                40.0, source="tpu1", device="TPU v5 lite0", scale=1.0
            ),
        ]
        assert perfledger.detect_regressions(records) == []
        assert perfledger.comparable_key(
            records[0]
        ) != perfledger.comparable_key(records[3])

    def test_lever_flags_separate_groups(self):
        records = [
            _bench_like(10.0, source="a"),
            _bench_like(10.0, source="b"),
            # 2x slower but under a different lever: not comparable
            _bench_like(20.0, source="c", gather_dtype="bf16"),
        ]
        assert perfledger.detect_regressions(records) == []

    def test_failed_runs_gate_nothing(self):
        records = [
            _bench_like(10.0, source="a"),
            _bench_like(10.0, source="b"),
            _bench_like(-1.0, source="failed"),
        ]
        assert perfledger.detect_regressions(records) == []

    def test_quality_gate_failures_gate_nothing(self):
        """A holdout-RMSE gate failure carries a real positive wall time
        but measured an invalid run: it must neither be flagged as the
        latest nor sit in the baseline median."""
        records = [
            _bench_like(10.0, source="a"),
            _bench_like(10.0, source="b"),
            _bench_like(10.1, source="c"),
            _bench_like(20.0, source="bad", error="rmse gate failed"),
        ]
        assert perfledger.detect_regressions(records) == []
        # ...and a later healthy regression is still judged against the
        # healthy baseline only
        flagged = perfledger.detect_regressions(
            records + [_bench_like(14.0, source="later")]
        )
        assert len(flagged) == 1
        assert flagged[0]["latest_source"] == "later"
        assert flagged[0]["baseline_median"] == pytest.approx(10.0)

    def test_trend_survives_non_numeric_fields(self):
        good = _bench_like(10.0, source="ok")
        bad = dict(_bench_like(10.0, source="garbled"))
        bad["value"] = "12.3"
        bad2 = dict(_bench_like(11.0, source="half-garbled"))
        bad2["rmse"] = "n/a"
        bad2["vs_baseline"] = None
        text = perfledger.render_trend([good, bad, bad2])
        assert "ok" in text
        assert "half-garbled" in text  # renders, minus the bad fields
        assert "12.3" not in text  # the string-valued record is skipped

    def test_min_history_required(self):
        records = [
            _bench_like(10.0, source="a"),
            _bench_like(20.0, source="b"),  # worse, but one prior point
        ]
        assert perfledger.detect_regressions(records) == []

    def test_bf16_gate_margin_rides_extra(self):
        """Satellite hygiene (round 12): the bench's bf16 RMSE-gate
        block travels into the ledger record's extra, so r06+ rounds
        are self-describing."""
        gate = {"rmse_f32": 0.53, "rmse_bf16": 0.5301, "margin": 0.0001,
                "gate": 0.01, "ok": True}
        record = _bench_like(10.0, source="gated", bf16_gate=gate)
        assert record["extra"]["bf16_gate"] == gate


class TestNoPriorReporting:
    """Flipping a lever default starts a FRESH comparable group (flags
    are part of the key) — the diff must say "no comparable prior"
    explicitly, never let an ungated group read as "stable"."""

    def test_flipped_levers_reported_as_no_prior(self):
        history = perfledger.load_bench_history(HISTORY)
        flipped = _bench_like(5.0, source="flip", sort_gather=True)
        verdicts = perfledger.find_no_prior(history + [flipped])
        assert len(verdicts) == 1
        v = verdicts[0]
        assert v["latest_source"] == "flip"
        assert v["history"] == 0
        assert v["needed"] == perfledger.MIN_HISTORY
        assert v["key"]["sort_gather"] is True
        # ...and the flipped record is NOT a regression either
        assert perfledger.detect_regressions(history + [flipped]) == []

    def test_established_history_has_no_no_prior(self):
        history = perfledger.load_bench_history(HISTORY)
        assert perfledger.find_no_prior(history) == []

    def test_failed_runs_do_not_count_as_measurements(self):
        failed = _bench_like(-1.0, source="failed",
                             sort_gather=True)
        assert perfledger.find_no_prior([failed]) == []

    def test_stale_experiment_ages_out_of_report(self):
        """A one-off lever experiment must not print 'no comparable
        prior' forever: once enough newer gate-able evidence lands, the
        stale group drops out of the report."""
        stale = _bench_like(9.0, source="oneoff", gather_dtype="bf16")
        newer = [
            _bench_like(10.0 + i * 0.01, source=f"r{i}")
            for i in range(perfledger.NO_PRIOR_RECENT_WINDOW + 1)
        ]
        verdicts = perfledger.find_no_prior([stale] + newer)
        assert [v["latest_source"] for v in verdicts] == []
        # ...but while it is still recent, it IS reported
        recent = perfledger.find_no_prior([stale] + newer[:3])
        assert [v["latest_source"] for v in recent] == ["oneoff"]

    def test_trend_renders_lever_flags(self):
        """The trend output must name the levers so two short disjoint
        histories across a default flip read as what they are."""
        text = perfledger.render_trend(
            [
                _bench_like(12.0, source="old"),
                _bench_like(5.0, source="new", sort_gather=True,
                            gather_dtype="bf16"),
            ]
        )
        assert "solve=chunked gather=f32" in text
        assert "solve=chunked gather=bf16 sort" in text


# ---------------------------------------------------------------------------
# 5. CLIs (in-process through the console, tier-1-budget style)
# ---------------------------------------------------------------------------


class TestPerfCLI:
    def _main(self, argv):
        from predictionio_tpu.tools.console import main

        if argv[0] == "perf" and "--history-dir" not in argv:
            argv = argv + ["--history-dir", HISTORY]
        return main(argv)

    def test_perf_diff_clean_on_checked_in_history(self, capsys):
        assert self._main(["perf", "diff"]) == 0
        assert "no regressions" in capsys.readouterr().out

    def test_perf_diff_flags_injected_regression(self, tmp_path, capsys):
        history = perfledger.load_bench_history(HISTORY)
        baseline = sorted(r["value"] for r in history)[len(history) // 2]
        ledger = str(tmp_path / "ledger.jsonl")
        perfledger.append_record(
            ledger, _bench_like(round(baseline * 1.25, 3), source="pr")
        )
        rc = self._main(["perf", "diff", "--ledger", ledger])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out

    def test_perf_diff_json_shape(self, capsys):
        assert self._main(["perf", "diff", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["regressions"] == []
        assert doc["records"] >= 4

    def test_perf_diff_no_records_is_engine_error(self, tmp_path, capsys):
        rc = self._main(
            ["perf", "diff", "--history-dir", str(tmp_path)]
        )
        assert rc == 2

    def test_perf_trend_renders_history(self, capsys):
        assert self._main(["perf", "trend"]) == 0
        out = capsys.readouterr().out
        assert "ml20m_als_rank50_train_s" in out
        assert "bench_r05" in out

    def test_perf_diff_reports_no_prior_distinct_from_stable(
        self, tmp_path, capsys
    ):
        """A flipped-lever record exits 0 but is called out as
        unestablished — wording distinct from the clean-history line —
        while an empty ledger run says plain "no regressions"."""
        ledger = str(tmp_path / "ledger.jsonl")
        perfledger.append_record(
            ledger, _bench_like(5.0, source="flip", sort_gather=True)
        )
        rc = self._main(["perf", "diff", "--ledger", ledger])
        out = capsys.readouterr().out
        assert rc == 0
        assert "NO COMPARABLE PRIOR" in out
        assert "sort" in out  # the levers that opened the new group
        assert "await comparable history" in out
        # the stable leg: same history, no unestablished groups
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        rc = self._main(["perf", "diff", "--ledger", str(empty)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "NO COMPARABLE PRIOR" not in out
        assert "no regressions" in out
        assert "await comparable history" not in out

    def test_perf_diff_json_carries_no_prior(self, tmp_path, capsys):
        ledger = str(tmp_path / "ledger.jsonl")
        perfledger.append_record(
            ledger, _bench_like(5.0, source="flip", sort_gather=True)
        )
        rc = self._main(["perf", "diff", "--json", "--ledger", ledger])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["regressions"] == []
        assert [v["latest_source"] for v in doc["noPrior"]] == ["flip"]

    def test_profile_smoke_train_reports_everything(self, capsys):
        """The ISSUE 8 acceptance drive: a smoke-scale in-process train
        reports per-phase wall/device time, compile counts, retrace
        counts, and a roofline estimate."""
        rc = self._main(["profile", "--train-smoke", "--iterations", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        for token in (
            "phase", "wall_s", "device_s",  # per-phase wall/device time
            "bucketize", "train",
            "compiles", "retraces", "als_half",  # compile/retrace counts
            "mfu", "hbm_util",  # the roofline estimate
        ):
            assert token in out, out
        # the telemetry saw the two half-solves: one warmup compile,
        # the second half (different shapes) is a retrace
        import re as _re

        match = _re.search(r"als_half\s+(\d+)\s+(\d+)", out)
        assert match is not None, out
        assert int(match.group(1)) >= 2
        assert int(match.group(2)) >= 1

    def test_profile_smoke_train_json(self, capsys):
        rc = self._main(
            ["profile", "--train-smoke", "--iterations", "1", "--json"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        doc = json.loads(out)
        # the jit delta may be empty here: an earlier smoke run in this
        # process already compiled these shapes (the cache is process-
        # global), and a warm run compiling nothing is exactly what the
        # delta should say
        assert isinstance(doc["jit"], dict)
        assert "train" in doc["phases"]
        assert doc["phases"]["train"]["wall_s"] > 0
        assert "device" in doc


class TestInstanceProfile:
    """The persisted-profile path: run_train writes PIO_TRAIN_PHASES +
    PIO_TRAIN_PROFILE into the instance env; pio profile reads them back
    long after the training process died."""

    def test_env_round_trip(self):
        from predictionio_tpu.utils.profiling import (
            TRAIN_PROFILE_ENV_KEY,
            profile_from_env,
            profile_to_env,
        )

        snapshot = {
            "fns": {"als_half": {"compiles": 2, "retraces": 1,
                                 "compile_s": 3.2}},
            "cache": {"hits": 1, "misses": 2, "backend_compiles": 3,
                      "backend_compile_s": 4.0},
            "train_wall_s": 9.5,
        }
        env = {TRAIN_PROFILE_ENV_KEY: profile_to_env(snapshot)}
        assert profile_from_env(env) == snapshot
        assert profile_from_env({}) == {}
        assert profile_from_env({TRAIN_PROFILE_ENV_KEY: "not json"}) == {}

    def test_report_from_instance(self):
        import types

        from predictionio_tpu.tools.perf import _report_from_instance
        from predictionio_tpu.utils.profiling import (
            TRAIN_PHASES_ENV_KEY,
            TRAIN_PROFILE_ENV_KEY,
            profile_to_env,
        )

        instance = types.SimpleNamespace(
            id="AB12",
            env={
                TRAIN_PHASES_ENV_KEY: '{"train[0]": 5.5, "read": 0.5}',
                TRAIN_PROFILE_ENV_KEY: profile_to_env(
                    {
                        "fns": {"als_iteration": {"compiles": 1,
                                                  "retraces": 0,
                                                  "compile_s": 2.0}},
                        "cache": {"hits": 0, "misses": 1,
                                  "backend_compiles": 1,
                                  "backend_compile_s": 2.0},
                    }
                ),
            },
        )
        data = _report_from_instance(instance)
        assert data["phases"]["train[0]"]["wall_s"] == 5.5
        assert data["jit"]["als_iteration"]["compiles"] == 1
        text = render_profile_report("instance AB12", **{
            k: data[k] for k in ("phases", "jit", "cache")
        })
        assert "als_iteration" in text and "train[0]" in text


class TestFleetExposition:
    """The PR-7 leftover: continuous freshness (and the new jit
    counters) must be readable fleet-wide through LIVE exposition —
    a real HTTP scrape, not registry poking."""

    @pytest.fixture()
    def live_node(self):
        from predictionio_tpu.api.http import BackgroundHTTPServer
        from predictionio_tpu.api.http import JsonHTTPHandler

        class _Handler(JsonHTTPHandler):
            def do_GET(self):  # noqa: N802
                if not self.serve_obs(self.path):
                    self.respond(404, {"message": "not found"})

        server = BackgroundHTTPServer(("127.0.0.1", 0), _Handler)
        reg = server.metrics
        reg.gauge(
            "pio_continuous_feed_lag_ops", "feed lag"
        ).set(7)
        reg.gauge(
            "pio_continuous_candidate_age_seconds", "candidate age"
        ).set(42)
        tel = JitTelemetry(clock=lambda: 0.0)
        fn = FakeJit()
        tel.call("als_half", fn, "a")
        tel.call("als_half", fn, "b")
        tel.bind(reg)
        server.start_background()
        try:
            yield f"127.0.0.1:{server.bound_port}"
        finally:
            server.shutdown()
            server.server_close()

    def test_top_row_reads_freshness_and_jit_columns(self, live_node):
        from predictionio_tpu.obs.top import node_row, render_table

        row = node_row(live_node)
        assert row["up"] is True
        assert row["feed_lag"] == 7.0
        assert row["cand_age"] == 42.0
        assert row["jit_compiles"] == 2.0
        assert row["jit_retraces"] == 1.0
        table = render_table([row])
        header, data = table.splitlines()[:2]
        for column in ("FEEDLAG", "CANDAGE", "JITC", "RETRACE"):
            assert column in header
        assert "42" in data and "7" in data

    def test_dashboard_fleet_panel(self, live_node, tmp_path):
        from predictionio_tpu.storage import StorageRegistry
        from predictionio_tpu.tools.dashboard import (
            DashboardConfig,
            DashboardServer,
        )
        import requests

        srv = DashboardServer(
            DashboardConfig(ip="127.0.0.1", port=0, nodes=live_node),
            StorageRegistry(env={"PIO_FS_BASEDIR": str(tmp_path)}),
        )
        srv.start_background()
        try:
            base = f"http://127.0.0.1:{srv.bound_port}"
            rows = requests.get(base + "/fleet.json", timeout=10).json()
            assert rows[0]["feed_lag"] == 7.0
            assert rows[0]["jit_retraces"] == 1.0
            html_page = requests.get(base + "/fleet", timeout=10).text
            assert "FEEDLAG" in html_page and "RETRACE" in html_page
            assert "42" in html_page
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# perf-unfenced-timing lint fixtures (family D, the fixture-twin
# discipline of tests/test_lint.py)
# ---------------------------------------------------------------------------


class TestPerfLintFixtures:
    def _unsuppressed(self, path):
        from predictionio_tpu.lint import lint_file

        return [f for f in lint_file(path) if not f.suppressed]

    def test_bad_fixture_fires_exactly_intended_rule(self):
        path = os.path.join(FIXTURES, "unfenced_timing_bad.py")
        findings = self._unsuppressed(path)
        assert [f.rule_id for f in findings] == ["perf-unfenced-timing"], [
            (f.rule_id, f.line) for f in findings
        ]
        with open(path) as fh:
            marked = next(
                i for i, line in enumerate(fh, 1) if "BAD" in line
            )
        assert findings[0].line == marked

    def test_clean_twin_has_no_findings(self):
        findings = self._unsuppressed(
            os.path.join(FIXTURES, "unfenced_timing_clean.py")
        )
        assert findings == [], [(f.rule_id, f.line) for f in findings]

    def test_factory_and_alias_and_wrapper_shapes_flagged(self):
        """The resolution hops the rule must see: jit factories, one-hop
        aliases, and telemetry-wrapper call sites."""
        from predictionio_tpu.lint import lint_file

        src = (
            "import functools, time\n"
            "import jax\n"
            "def make():\n"
            "    return jax.jit(lambda x: x)\n"
            "g = make()\n"
            "h = g\n"
            "direct = functools.partial(jax.jit, static_argnames=())(abs)\n"
            "def a(x):\n"
            "    t0 = time.monotonic()\n"
            "    y = h(x)\n"
            "    return time.monotonic() - t0\n"
            "def b(tel, x):\n"
            "    t0 = time.perf_counter()\n"
            "    y = tel.call('n', direct, x)\n"
            "    return time.perf_counter() - t0\n"
        )
        findings = [
            f
            for f in lint_file("x.py", source=src)
            if f.rule_id == "perf-unfenced-timing"
        ]
        assert len(findings) == 2, findings

    def test_local_shadowing_not_flagged(self):
        """Jitted names resolve per scope: a function's own binding (or
        parameter) named like a module-level jitted fn is NOT a jitted
        call — honest host timing must not need a suppression."""
        from predictionio_tpu.lint import lint_file

        src = (
            "import time\n"
            "import jax\n"
            "f = jax.jit(lambda x: x)\n"
            "def host_timing(path):\n"
            "    f = open(path)\n"
            "    t0 = time.monotonic()\n"
            "    data = f.read()\n"
            "    return data, time.monotonic() - t0\n"
            "def param_shadow(f, x):\n"
            "    t0 = time.monotonic()\n"
            "    y = f(x)\n"
            "    return y, time.monotonic() - t0\n"
            "def still_flagged(x):\n"
            "    t0 = time.monotonic()\n"
            "    y = f(x)\n"
            "    return y, time.monotonic() - t0\n"
        )
        findings = [
            finding
            for finding in lint_file("x.py", source=src)
            if finding.rule_id == "perf-unfenced-timing"
        ]
        assert len(findings) == 1, findings
        assert findings[0].line == 16  # only the true module-jit bracket

    def test_fence_between_clears(self):
        from predictionio_tpu.lint import lint_file

        src = (
            "import time\n"
            "import jax\n"
            "f = jax.jit(lambda x: x)\n"
            "def a(x):\n"
            "    t0 = time.monotonic()\n"
            "    y = f(x)\n"
            "    jax.block_until_ready(y)\n"
            "    return time.monotonic() - t0\n"
        )
        findings = [
            f
            for f in lint_file("x.py", source=src)
            if f.rule_id == "perf-unfenced-timing"
        ]
        assert findings == []
