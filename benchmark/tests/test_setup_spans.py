"""``readers/setup_spans.py``: set-up by phase from the program's own
``SpanStore``, on a hand-made store and through whole traced rehearsals."""

import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest
from benchmark.readers import setup_spans

METRICS = ("setup_trace_lower_s", "setup_backend_s", "setup_cache_misses")


def read(name, obs=None):
    return setup_spans.read({} if obs is None else obs, manifest.metric(name)["params"])


@pytest.fixture
def store(monkeypatch):
    """A default tracer of the test's own, with room for 12 spans."""
    from predictionio_tpu.obs import trace

    tracer = trace.Tracer("process", store=trace.SpanStore(capacity=12))
    monkeypatch.setattr(trace, "_default", tracer)
    return tracer


def job(tracer, start_s, phases=(), extra=()):
    """One ``train`` root at ``start_s`` with ``(name, start, seconds, fn,
    tags, parent)`` phase spans in its trace; returns the spans' contexts
    by ``fn`` so that a phase can name another as its parent."""
    root = tracer.child_context(None)
    made = {}
    for name, at, seconds, fn, tags, parent in phases:
        ctx = tracer.child_context(root)
        made[fn] = ctx
        tracer.record(
            name, ctx, made[parent].span_id if parent else root.span_id,
            start_wall=start_s + at, duration_s=seconds, tags={"fn": fn, **tags})
    for name, at, seconds in extra:
        tracer.record(name, tracer.child_context(root), root.span_id,
                      start_wall=start_s + at, duration_s=seconds)
    tracer.record("train", root, None, start_wall=start_s, duration_s=30.0)
    return made


WARM_UP = (
    # tracing the step 0-8 s with a function traced inside it, its
    # lowering 8-10 s, and an eager program traced meanwhile on another
    # thread, 9-11 s: the union is 11 s, the plain sum 16.5
    ("jit.trace", 0.0, 8.0, "step", {}, None),
    ("jit.trace", 2.0, 2.5, "gated_delta_rule", {}, "step"),
    ("jit.lower", 8.0, 2.0, "jit(step)", {}, None),
    ("jit.trace", 9.0, 2.0, "draw", {}, None),
    ("jit.backend", 11.0, 4.0, "jit(step)", {"cache": "hit"}, None),
    ("jit.backend", 15.0, 60.0, "jit(draw)", {"cache": "miss"}, None),
)


def test_union_over_the_warm_up_jobs_spans(store, capsys):
    job(store, 1000.0, WARM_UP, extra=[("seqrec.step", 0.0, 15.0)])
    # the newest root is the timed job: what it brought to the device (a
    # retrace would show here) is no part of set-up
    job(store, 2000.0, [("jit.trace", 0.0, 5.0, "late", {}, None)])
    obs = {}
    assert read("setup_trace_lower_s", obs) == pytest.approx(11.0)
    assert read("setup_backend_s", obs) == pytest.approx(64.0)
    assert read("setup_cache_misses", obs) == 1
    said = [l for l in capsys.readouterr().out.splitlines() if l.startswith("[bench] set-up by phase")]
    assert len(said) == 1  # once a run, whichever metric is read first
    assert "trace and lower 11.00 s" in said[0] and "backend 64.00 s in 2 program(s)" in said[0]
    assert "1 of them compile cache misses" in said[0]
    # heaviest first, a program's phases under one name, nested ones in their parent's
    assert said[0].index("draw 62.00 s") < said[0].index("step 14.00 s")
    assert "gated_delta_rule" not in said[0]


def test_every_earlier_root_is_warm_up(store):
    job(store, 1000.0, WARM_UP[:1])
    job(store, 1100.0, WARM_UP[2:3])
    job(store, 2000.0)
    assert read("setup_trace_lower_s") == pytest.approx(10.0)
    assert read("setup_backend_s") is None  # no such span: nothing, not 0


def test_a_warm_machine_reads_no_miss(store):
    job(store, 1000.0, WARM_UP[:5])
    job(store, 2000.0)
    assert read("setup_cache_misses") == 0
    assert read("setup_backend_s") == pytest.approx(4.0)


@pytest.mark.parametrize("name", METRICS)
def test_one_root_reads_nothing(store, name):
    job(store, 1000.0, WARM_UP)
    assert read(name) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_full_store_reads_nothing(store, name):
    """A ring at its capacity has begun to drop its oldest spans, which
    are the warm-up's: a part of them is no reading."""
    job(store, 1000.0, WARM_UP, extra=[("seqrec.step", float(i), 1.0) for i in range(4)])
    job(store, 2000.0)
    assert len(store.store) == store.store.capacity == 12
    assert read(name) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_spans_reads_nothing(store, name, capsys):
    """The parent of PR 36: two roots, no ``jit.`` phase span."""
    job(store, 1000.0, extra=[("seqrec.step", 0.0, 9.0)])
    job(store, 2000.0)
    assert read(name) is None
    assert "set-up by phase" not in capsys.readouterr().out


@pytest.mark.parametrize("workload", ["rehearse-train-seqrec", "rehearse-train"])
def test_traced_rehearsal_lists_the_three_metrics(capsys, monkeypatch, workload):
    import jax

    from predictionio_tpu.models import sequencerec
    from predictionio_tpu.obs import trace

    # as a fresh process finds them: no program made, none compiled, no
    # job of an earlier run in the store
    sequencerec._programs.cache_clear()
    jax.clear_caches()
    monkeypatch.setattr(trace, "_default", trace.Tracer("process"))
    argv = ["--workload", workload, "--seed", "11", "--seconds", "1", "--trace", "1"]
    assert bench_run.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert set(result["rehearsed"]) >= set(METRICS)
    (said,) = [l for l in lines if l.startswith("[bench] set-up by phase")]
    # the reading agrees with the run's own set-up line: no more seconds
    # under the phases than the warm-up job took, the same misses
    (setup,) = [l for l in lines if l.startswith("[bench] set-up ") and "warm-up job" in l]
    warm_up_s = float(setup.split(" s for the warm-up job")[0].split()[-1])
    words = said.split()
    by_phase = float(words[words.index("lower") + 1]) + float(words[words.index("backend") + 1])
    assert 0 < by_phase <= warm_up_s + 0.1
