"""The reduction from a trace to numbers: on a hand-made trace whose
answers are known, and on a trace recorded on the chip (cut to the first
events of each line by ``tools/trace_dump.py --json``)."""

import json
import os

import pytest

from benchmark.lib import rooflines
from benchmark.lib import trace as tr
from benchmark.readers import device_idle, program_time, roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def handmade():
    ops = [("fusion.1", 1.0, 0.5), ("copy.2", 1.25, 0.5), ("fusion.1", 3.0, 1.0),
           ("late", 9.5, 1.0)]  # the last one ends outside the window
    modules = [("jit_step(11)", 1.0, 0.75), ("jit_step(11)", 3.0, 1.0),
               ("jit_other(12)", 5.0, 0.0)]
    host = [("bench.window", 0.0, 10.0), ("bench.job", 0.5, 6.0),
            ("bench.bucketize", 1.75, 1.25), ("bench.batch_predict b=4", 2.9, 1.2)]
    return {"devices": {"/device:TPU:0": {tr.OP_LINE: ops, tr.MODULE_LINE: modules}},
            "host": host}


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == [(1, 2.5), (3, 5)]


def test_busy_union_idle_share_and_window():
    trace = handmade()
    assert tr.window_of(trace) == (0.0, 10.0)
    busy, window = tr.busy_seconds(trace)
    # [1, 1.75] and [3, 4] and the half of [9.5, 10.5] inside the window
    assert busy == pytest.approx(0.75 + 1.0 + 0.5) and window == 10.0
    assert device_idle.read({"trace": trace}, {}) == pytest.approx(77.5)


def test_program_time_by_name():
    trace = handmade()
    assert tr.program_name("jit_step(11)") == "jit_step"
    events = tr.program_events(trace, "^jit_step$")
    assert [e[2] for e in events] == [0.75, 1.0]
    assert program_time.read({"trace": trace}, {"program": "^jit_step$"}) == pytest.approx(875.0)
    assert program_time.read({"trace": trace}, {"program": "^absent"}) is None


def test_idle_gaps_are_cut_at_span_edges_and_go_to_the_inner_span():
    gaps = dict(tr.idle_gaps(handmade()))
    # idle: [0,1], [1.75,3], [4,9.5]. job spans [0.5,6.5], bucketize
    # [1.75,3] (inside job), batch_predict [2.9,4.1] (inside both from 2.9)
    assert gaps["bench.bucketize"] == pytest.approx(1.15)
    assert gaps["bench.batch_predict b=4"] == pytest.approx(0.1 + 0.1)
    assert gaps["bench.job"] == pytest.approx(0.5 + 2.4)
    assert gaps["(no span)"] == pytest.approx(0.5 + 3.0)
    assert sum(gaps.values()) == pytest.approx(10.0 - 2.25)


def test_topk_roofline_reads_the_batch_from_the_span():
    trace = handmade()
    trace["devices"]["/device:TPU:0"][tr.MODULE_LINE].append(
        ("jit_top_k_for_users_fused(7)", 3.0, 1e-4))
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"trace": trace, "peaks": peaks,
           "topk": {"n_items": 26744, "rank": 50, "k": 16, "paths": {4: "dense"}}}
    share = roofline.read(obs, {"program": "^jit_top_k_for_users_fused", "model": "topk"})
    flops, hbm = rooflines.topk(4, 26744, 50, 16, "dense")
    assert hbm == 26744 * 200 + 4 * 200 + 4 * 16 * 8 + 2 * 4 * 26744 * 4
    least, bound = rooflines.least_time(flops, hbm, peaks)
    assert bound == "bytes" and share == pytest.approx(100 * least / 1e-4)


def test_counts_never_exceed_a_plain_reading_of_the_algorithm():
    # one row of 3 ratings at rank 2 against a 5-row table, uint16 ids
    flops, hbm = rooflines.als_half_iteration([3], 5, 2, 2)
    assert hbm == 3 * (2 + 4) + 3 * 2 * 4 + 1 * 2 * 4
    assert flops == 3 * (2 * 3 + 4) + (8 / 3 + 8)
    assert rooflines.topk(8, 100, 50, 16, "streaming")[1] < rooflines.topk(8, 100, 50, 16, "dense")[1]


@pytest.mark.parametrize("name,program", [
    ("serve_trace.json", "^jit_top_k_for_users_fused"),
    ("train_trace.json", "^jit__als_"),
])
def test_recorded_chip_trace(name, program):
    with open(os.path.join(DATA, name)) as f:
        trace = json.load(f)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    lines = trace["devices"]["/device:TPU:0"]
    assert lines[tr.MODULE_LINE] and lines[tr.OP_LINE]
    busy, window = tr.busy_seconds(trace)
    assert 0 < busy < window
    # ops run inside programs: the op union cannot exceed the program union
    modules = tr.union([(s, s + d) for _, s, d in lines[tr.MODULE_LINE]])
    assert sum(e - s for s, e in tr.busy_intervals(lines)) <= sum(e - s for s, e in modules) * 1.001
    assert tr.program_events(trace, program)
    assert any(n.startswith("bench.") for n, _, _ in trace["host"])
    assert tr.device_ops(trace) and tr.idle_gaps(trace)
