"""A whole run off the chip, through ``benchmark/run.py``'s own ``main``
with a rehearsal workload (which skips the look for a chip and nothing
else): sound, it says ``correct``; with the timed path broken underneath,
it says not."""

import json

import numpy as np
import pytest

from benchmark import run as bench_run


def result_of(capsys, *argv):
    assert bench_run.main(list(argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_train_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(
        capsys, "--workload", "rehearse-train", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = [l for l in lines if l.startswith("[bench] compared ")]
    assert {l.split()[2].rstrip(":") for l in compared} >= {
        "holdout_rmse", "half_step_err", "half_step_gap", "window_compiles"}


def test_train_with_the_item_half_step_left_out_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.ops import als

    real = als._als_iteration

    def stale_items(ub, ib, y, lam, alpha, **kw):
        x, _ = real(ub, ib, y, lam, alpha, **kw)
        return x, y  # the item table comes back unchanged

    monkeypatch.setattr(als, "_als_iteration", stale_items)
    result, _ = result_of(
        capsys, "--workload", "rehearse-train", "--seed", "5", "--seconds", "1",
        "--trace", "0")
    assert result["correct"] is False and result["failed"] == result["attempted"]


def test_train_control_is_not_correct(capsys):
    result, _ = result_of(
        capsys, "--workload", "rehearse-train", "--seed", "5", "--seconds", "1",
        "--trace", "0", "--control", "bf16_gather")
    assert result["correct"] is False


@pytest.mark.parametrize("cell,metrics", [
    ("rehearse-serve", {"serve_qps", "setup_s", "batch_size_avg.saturate"}),
    ("rehearse-saturate", {"serve_qps", "setup_s", "batch_size_avg.saturate"}),
])
def test_serve_rehearsal_is_correct(capsys, cell, metrics):
    result, lines = result_of(
        capsys, "--workload", cell, "--seed", "2147483659", "--seconds", "2",
        "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["rehearsed"]) == metrics
    assert any("generator:" in l for l in lines[:-1])


def test_serve_with_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import recommendation as rec

    real = rec.top_k_for_users_fused

    def swapped(*args, **kw):
        scores, items = real(*args, **kw)
        items = np.array(items)
        items[:, [0, 1]] = items[:, [1, 0]]  # best two change places
        return scores, items

    monkeypatch.setattr(rec, "top_k_for_users_fused", swapped)
    result, _ = result_of(
        capsys, "--workload", "rehearse-serve", "--seed", "8", "--seconds", "2",
        "--trace", "0")
    assert result["correct"] is False and result["failed"] > 0


def test_traced_rehearsal_reads_the_layer_metrics(capsys):
    result, _ = result_of(
        capsys, "--workload", "rehearse-serve", "--seed", "9", "--seconds", "3",
        "--trace", "1")
    assert {"dispatch_ms.saturate", "batch_size_avg.saturate"} <= set(result["rehearsed"])
