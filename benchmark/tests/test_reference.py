"""The references against the program at a tiny size on the CPU, and
against their controls: the same mathematics one precision lower has to
fall outside the limits."""

import numpy as np
import pytest

from benchmark.lib import reference, synth

SIZES = {"n_users": 400, "n_items": 120, "n_ratings": 12000, "rank": 8}
LAW = {"user_exponent": 0.8, "item_exponent": 0.9, "truth_rank": 4,
       "noise_sd": 0.5, "rating_mean": 3.5}
LAM = 0.05


def train(seed, gather_dtype):
    from predictionio_tpu.ops.als import ALSConfig, als_train_coo

    users, items, vals, _ = synth.ratings(SIZES, LAW, seed)
    cfg = ALSConfig(rank=8, iterations=3, lambda_=LAM, seed=3, gather_dtype=gather_dtype)
    f = als_train_coo(users, items, vals, n_users=400, n_items=120, cfg=cfg)
    rows = np.flatnonzero(np.bincount(items, minlength=120) >= 16)[:40]
    return reference.half_step_errors(
        np.asarray(f.user_factors), np.asarray(f.item_factors),
        users, items, vals, rows, LAM)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_half_step_reference_agrees_with_the_program_in_f32_and_not_in_bf16(seed):
    sound = train(seed, "f32")
    control = train(seed, "bf16")
    assert sound["half_step_err"] < 2e-4
    assert control["half_step_err"] > 10 * sound["half_step_err"]
    assert control["half_step_err"] > 1e-3


def test_bf16_round_is_round_to_nearest_even():
    a = np.array([1.0, 1.00390625, 1.005859375, -3.14159274, 0.0], np.float32)
    got = reference.bf16_round(a)
    np.testing.assert_array_equal(
        got, np.array([1.0, 1.0, 1.0078125, -3.140625, 0.0], np.float32))


def tables(seed):
    sizes = {"n_users": 64, "n_items": 3000, "rank": 50}
    return synth.factor_tables(sizes, seed)


def served(u, i, rows, k=10, precision="f32"):
    uu, ii = u[rows], i
    if precision == "bf16":
        uu, ii = reference.bf16_round(uu), reference.bf16_round(ii)
    scores = (uu @ ii.T).astype(np.float32)
    top = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return top, np.take_along_axis(scores, top, axis=1)


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_topk_reference_passes_f32_and_fails_one_precision_lower(seed):
    u, i = tables(seed)
    rows = np.arange(64)
    items, scores = served(u, i, rows)
    sound = reference.topk_gaps(u, i, rows, items, scores)
    assert sound["rank_gap"] <= 1e-6 and sound["score_err"] < 5e-6
    items, scores = served(u, i, rows, precision="bf16")
    control = reference.topk_gaps(u, i, rows, items, scores)
    assert control["score_err"] > 1e-3
    assert control["wrong_lists"] > 0


def test_topk_reference_sees_a_swapped_and_a_repeated_item():
    u, i = tables(7)
    rows = np.arange(8)
    items, scores = served(u, i, rows)
    items[3, [0, 1]] = items[3, [1, 0]]
    got = reference.topk_gaps(u, i, rows, items, scores)
    assert got["wrong_lists"] == 1 and got["rank_gap_each"][3] > 0
    assert (np.delete(got["rank_gap_each"], 3) == 0).all()
    items[5, 1] = items[5, 0]
    assert reference.topk_gaps(u, i, rows, items, scores)["rank_gap"] == np.inf


def test_verdict_fails_a_missing_or_infinite_reading():
    v = reference.verdict({"a": 1.0, "b": float("inf")}, {"a": 2.0, "b": 2.0, "c": 1.0})
    assert [x["ok"] for x in v] == [True, False, False]
