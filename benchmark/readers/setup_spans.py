"""Set-up by phase, from the program's own ``SpanStore``: the seconds the
warm-up job or jobs spent under the spans named in ``spans`` (union of
their intervals, so a span inside another counts once), or with ``count``
how many of those spans carry ``tag`` = ``value``.

The warm-up is every ``train`` root that began before the newest one (the
newest is the timed or traced job, as ``lib/scopes.job_spans`` has it): a
process traces, lowers and compiles or loads each program once, in its
first job. Nothing is read, and ``None`` returned, where the store holds
fewer than two ``train`` roots, where it is full (a ring: the oldest
spans, the warm-up's, are the ones it has dropped), and on a program that
leaves no such span. Once a run the three phases and the heaviest programs
are printed in one line.
"""

from typing import Dict, List, Optional

from ..lib import trace as tr

PHASES = ("jit.trace", "jit.lower", "jit.backend")


def warmup_spans() -> Optional[List[Dict]]:
    """The ``jit.`` phase spans of the ``train`` roots before the newest."""
    try:
        from predictionio_tpu.obs.trace import default_tracer
    except ImportError:
        return None
    store = default_tracer().store
    spans = store.dump()
    # (a program from before the phase spans has no ``capacity`` either)
    capacity = getattr(store, "capacity", None)
    if capacity is not None and len(spans) >= capacity:
        return None
    roots = [s for s in spans if s["name"] == "train" and s["parentId"] is None]
    if len(roots) < 2:
        return None
    warm = {r["traceId"] for r in roots[:-1]}
    return [s for s in spans if s["traceId"] in warm and s["name"] in PHASES]


def seconds(spans: List[Dict]) -> float:
    return sum(e - s for s, e in tr.union(
        [(x["startMs"], x["startMs"] + x["durationMs"]) for x in spans])) / 1e3


def _say(found: List[Dict]) -> None:
    ids = {s["spanId"] for s in found}
    by_fn: Dict[str, float] = {}
    for s in found:
        if s["parentId"] not in ids:  # not inside another phase
            fn = str(s.get("tags", {}).get("fn", "?"))
            fn = fn[4:-1] if fn.startswith("jit(") and fn.endswith(")") else fn
            by_fn[fn] = by_fn.get(fn, 0.0) + s["durationMs"] / 1e3
    backend = [s for s in found if s["name"] == "jit.backend"]
    misses = sum(s.get("tags", {}).get("cache") == "miss" for s in backend)
    heaviest = sorted(by_fn.items(), key=lambda kv: -kv[1])[:5]
    print(
        f"[bench] set-up by phase: trace and lower "
        f"{seconds([s for s in found if s['name'] != 'jit.backend']):.2f} s, backend "
        f"{seconds(backend):.2f} s in {len(backend)} program(s), {misses} of them "
        f"compile cache misses; {len(found)} jit spans; heaviest: "
        + ", ".join(f"{fn} {s:.2f} s" for fn, s in heaviest), flush=True)


def read(obs, params):
    if "setup_spans" not in obs:  # three metrics read the same spans
        obs["setup_spans"] = warmup_spans()
        if obs["setup_spans"]:
            _say(obs["setup_spans"])
    found = [s for s in obs["setup_spans"] or [] if s["name"] in params["spans"]]
    if not found:
        return None
    if params.get("count"):
        return sum(s.get("tags", {}).get(params["tag"]) == params["value"] for s in found)
    return seconds(found)
