"""The readers of the program's own spans and scopes (PR 24): on a
hand-made trace whose answers are known, on a fixture cut from a chip
trace of ``train-amazonbooks`` (``tools/scope_table.py --json``: the
first operations of every bucket of every program execution, with their
name stacks), and through a whole traced rehearsal off the chip."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import gather_roofline, manifest, scopes
from benchmark.lib import trace as tr
from benchmark.readers import idle_under, scope_time, span_sum
from benchmark.readers import gather_roofline as gather_roofline_reader

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = [
    "bucketize_s", "index_sort_s", "stage_put_s", "fetch_s", "idle_host_prep_s",
    "idle_attributed_pct.train", "w8_device_s", "gather_device_s", "gramian_device_s",
    "solve_device_s", "scatter_device_s", "gather_roofline_pct",
]
PREP = ["pio.als.bucketize", "pio.als.index_sort", "pio.als.stage", "pio.als.init_factors"]


def read(name, obs):
    spec = manifest.metric(name)
    return manifest.reader(spec["reader"])(obs, spec.get("params", {}))


def handmade():
    """Busy [2,3], [3.5,5], [8,9.5] in a window [0,10]: idle [0,2],
    [3,3.5], [5,8], [9.5,10] and a 10 us turn-around inside [8,9.5]."""
    side, w8 = "jit(f)/als.user_side/als.w8", "jit(f)/als.item_side/als.w8"
    ops = [
        ("%while.1", 2.0, 1.0), ("%fusion.7", 2.0, 0.5), ("%fusion.8", 2.5, 0.5),
        ("%fusion.7", 3.5, 1.0), ("%spd_solve_t.2", 4.0, 1.0),
        ("%fusion.9", 8.0, 0.75), ("%fusion.4", 8.75001, 0.74999),
        ("%late", 11.0, 1.0),
    ]
    stacks = [
        "", f"{side}/while/body/closed_call/als.gather/gather:",
        f"{side}/while/body/closed_call/als.gramian/dot_general:",
        f"{w8}/while/body/closed_call/als.gather/gather:",
        f"{w8}/while/body/closed_call/als.solve/spd_solve_t/pallas_call:",
        "jit(f)/als.item_side/als.w32/while/body/closed_call/als.gramian/mul:",
        "jit(f)/als.item_side/als.w32/als.scatter/scatter:", f"{side}/als.scatter/scatter:",
    ]
    modules = [("jit__als_half_body(1)", 2.0, 1.0), ("jit__als_half_body(2)", 3.5, 1.5),
               ("jit__als_iteration_body(3)", 8.0, 1.5)]
    host = [
        ("bench.window", 0.0, 10.0), ("bench.als_train", 0.2, 9.0),
        ("pio.train rank=50 iterations=2 shards=1", 0.1, 9.8),
        ("pio.als.bucketize side=user", 0.5, 1.0),  # [0.5, 1.5]
        ("pio.als.index_sort side=user", 1.5, 0.75),  # [1.5, 2.25]: idle to 2.0
        ("pio.als.enqueue program=half_user i=0", 1.9, 0.05),  # inside the sort, shorter
        ("pio.als.stage side=item", 3.1, 0.3),  # [3.1, 3.4] inside idle [3, 3.5]
        ("pio.als.index_sort side=item", 5.0, 2.0),  # [5, 7] of idle [5, 8]
        ("pio.als.init_factors", 6.0, 0.5),  # nested in the sort: innermost wins
        ("pio.train.fetch", 9.5, 0.4),
    ]
    return {"devices": {"/device:TPU:0": {tr.OP_LINE: ops, tr.MODULE_LINE: modules}},
            "stacks": {"/device:TPU:0": stacks}, "host": host}


def test_idle_goes_to_the_innermost_program_span_and_bench_spans_are_left_out():
    idle = scopes.idle_by_span(handmade())
    assert "bench.als_train" not in idle
    assert idle["pio.als.bucketize"] == pytest.approx(1.0)
    # [1.5, 2.0] less the enqueue's [1.9, 1.95], which is shorter and wins
    assert idle["pio.als.index_sort"] == pytest.approx(0.45 + 1.5)
    assert idle["pio.als.enqueue"] == pytest.approx(0.05)
    assert idle["pio.als.stage"] == pytest.approx(0.3)
    assert idle["pio.als.init_factors"] == pytest.approx(0.5)
    assert idle["pio.train.fetch"] == pytest.approx(0.4)
    # under the root alone: [0.1,0.5], [3,3.1], [3.4,3.5], [7,8], [9.9,9.9]
    assert idle["pio.train"] == pytest.approx(0.4 + 0.1 + 0.1 + 1.0)
    assert idle["(no span)"] == pytest.approx(0.1 + 0.1)
    assert idle["(between ops)"] == pytest.approx(1e-5)
    assert sum(idle.values()) == pytest.approx(10.0 - 3.99999)


def test_idle_readers_seconds_and_share():
    obs = {"pio_trace": handmade()}
    assert idle_under.read(obs, {"spans": PREP}) == pytest.approx(1.0 + 1.95 + 0.3 + 0.5)
    # every pio. span but the root, over the gaps longer than 20 us
    under = 1.0 + 1.95 + 0.05 + 0.3 + 0.5 + 0.4
    assert idle_under.read(obs, {}) == pytest.approx(100 * under / 6.0)
    assert read("idle_host_prep_s", obs) == pytest.approx(3.75)


def test_scope_seconds_are_a_union_inside_the_window():
    obs = {"pio_trace": handmade()}
    # gather: [2,2.5] and [3.5,4.5]; w8 both sides: [2,3] and [3.5,5]
    assert scope_time.read(obs, {"scope": "als.gather"}) == pytest.approx(1.5)
    assert scope_time.read(obs, {"scope": "als.w8"}) == pytest.approx(2.5)
    assert scope_time.read(obs, {"scope": "als.solve"}) == pytest.approx(1.0)
    # the scatter after the window's end does not count
    assert scope_time.read(obs, {"scope": "als.scatter"}) == pytest.approx(0.74999)
    assert scope_time.read(obs, {"scope": "als.w"}) is None  # a whole component
    assert scope_time.read(obs, {"scope": "als.yty"}) is None


def test_gather_bytes_count_the_buckets_that_gather_in_xla():
    shapes = {"by_user": [[16384, 8], [8192, 32], [4096, 128]], "by_item": [[16384, 8]]}
    fused = {"solve_mode": "pallas", "fused_gather": True, "gather_dtype": "f32"}
    slots = 2 * 16384 * 8 + 8192 * 32
    assert gather_roofline.gather_bytes_per_iteration(shapes, 50, fused) == slots * 56 * 4 * 2
    # width 8 reaches the rank: the fused kernel takes every bucket
    assert gather_roofline.gather_bytes_per_iteration(shapes, 8, fused) == 0
    plain = dict(fused, fused_gather=False, gather_dtype="bf16")
    every = slots + 4096 * 128
    assert gather_roofline.gather_bytes_per_iteration(shapes, 50, plain) == every * 56 * 2 * 2
    xla = dict(plain, solve_mode="chunked", gather_dtype="f32")
    assert gather_roofline.gather_bytes_per_iteration(shapes, 50, xla) == every * 50 * 4 * 2


def test_gather_roofline_counts_the_iterations_the_trace_shows():
    shapes = {"by_user": [[16384, 8]], "by_item": []}
    levers = {"solve_mode": "pallas", "fused_gather": True, "gather_dtype": "f32"}
    obs = {"pio_trace": handmade(), "peaks": {"hbm_bytes_per_s": 1e9},
           "bucket_shapes": shapes, "als_shape": {"rank": 50}, "levers": levers}
    # two half programs and one iteration program: two iterations
    moved = 2 * 16384 * 8 * 56 * 4 * 2
    assert read("gather_roofline_pct", obs) == pytest.approx(100 * (moved / 1e9) / 1.5)
    assert gather_roofline_reader.read({**obs, "peaks": None}, {}) is None


def test_a_program_without_spans_or_scopes_reads_nothing():
    """The parent of PR 24: no ``pio.`` annotation, no name stack."""
    trace = handmade()
    trace["host"] = [ev for ev in trace["host"] if ev[0].startswith("bench.")]
    trace["stacks"] = {"/device:TPU:0": [""] * len(trace["stacks"]["/device:TPU:0"])}
    obs = {"pio_trace": trace, "peaks": {"hbm_bytes_per_s": 1e9},
           "bucket_shapes": {}, "als_shape": {"rank": 50}, "levers": {}}
    for name in NEW_METRICS[4:]:
        assert read(name, obs) is None, name
    assert all(read(name, {"pio_trace": None}) is None for name in NEW_METRICS[4:])


def test_span_sum_reads_the_newest_job_of_the_programs_store():
    from predictionio_tpu.obs.trace import span

    for _ in range(2):  # two jobs: the newer one is read
        with span("train"):
            with span("als.stage", {"side": "user"}):
                pass
            with span("als.stage", {"side": "item"}):
                pass
    job = scopes.job_spans()
    assert [s["name"] for s in job] == ["als.stage", "als.stage", "train"]
    assert len({s["traceId"] for s in job}) == 1
    total = span_sum.read({}, {"span": "als.stage"})
    assert total == pytest.approx(sum(s["durationMs"] for s in job[:2]) / 1e3)
    assert span_sum.read({}, {"span": "als.absent"}) is None


@pytest.fixture(scope="module")
def chip_obs():
    with open(os.path.join(DATA, "train_scopes.json")) as f:
        trace = json.load(f)
    with open(os.path.join(manifest.ROOT, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    # the cell's own bucket shapes, as the run that was cut printed them
    with open(os.path.join(DATA, "train_scopes_obs.json")) as f:
        return {"pio_trace": trace, "peaks": peaks, **json.load(f)}


def test_every_device_reader_finds_its_events_in_the_recorded_chip_trace(chip_obs):
    values = {name: read(name, chip_obs) for name in NEW_METRICS[4:]}
    assert all(v is not None and v > 0 for v in values.values()), values
    busy, window = tr.busy_seconds(chip_obs["pio_trace"])
    phases = ["gather_device_s", "gramian_device_s", "solve_device_s", "scatter_device_s"]
    assert all(values[name] < busy for name in phases + ["w8_device_s"])
    # the phases do not overlap: together they are the scoped busy time,
    # less the few operations under a bucket scope alone (index casts,
    # the loop's slices)
    ops = scopes.scoped_ops(chip_obs["pio_trace"])
    scoped = sum(e - s for s, e in tr.union([(s, e) for s, e, sc in ops if sc]))
    assert 0.9 * scoped < sum(values[name] for name in phases) <= scoped * (1 + 1e-9)
    # the share is the count over the scope's seconds (over 100 here: the
    # fixture keeps a few operations of each scope and all of the bytes)
    moved = 5 * gather_roofline.gather_bytes_per_iteration(
        chip_obs["bucket_shapes"], 50, chip_obs["levers"])
    assert moved == 5 * 91_881_472 * 224 * 2
    assert values["gather_roofline_pct"] == pytest.approx(
        100 * moved / 819e9 / values["gather_device_s"])
    assert 0 < values["idle_attributed_pct.train"] <= 100
    assert values["idle_host_prep_s"] < window - busy


def test_recorded_stacks_read_side_bucket_phase(chip_obs):
    trace = chip_obs["pio_trace"]
    (plane,) = trace["stacks"]
    stacks = {"/".join(sc) for _, _, sc in scopes.scoped_ops(trace) if sc}
    for side in ("als.user_side", "als.item_side"):
        for phase in ("als.gather", "als.gramian", "als.solve"):
            assert f"{side}/als.w8/{phase}" in stacks
        assert f"{side}/als.w8/als.scatter" in stacks
        # a bucket at least as wide as the rank gathers inside the kernel
        assert f"{side}/als.w128/als.gramian" in stacks
        assert f"{side}/als.w128/als.gather" not in stacks
    # found by scope, whatever number the compiler gave the operation
    names = {n.split(" ")[0] for (n, _, _), st in zip(
        trace["devices"][plane][tr.OP_LINE], trace["stacks"][plane]) if "als.gather" in st}
    assert len(names) >= 2 and all(n.startswith("%") for n in names)


def test_traced_train_rehearsal_names_the_host_span_metrics(capsys):
    assert bench_run.main(["--workload", "rehearse-train", "--seed", "3000000019",
                           "--seconds", "1", "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    assert {"bucketize_s", "index_sort_s", "stage_put_s", "fetch_s"} <= set(result["rehearsed"])
    # off the chip there is no device plane: no device metric is named
    assert not {"w8_device_s", "idle_host_prep_s"} & set(result["rehearsed"])
