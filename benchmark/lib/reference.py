"""The plain references that decide ``correct``. Numpy and float64 only:
nothing here imports the program or takes anything the program made,
except the answers and factors that are being judged.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def bf16_round(a: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    returned as float32: what an MXU pass at default precision sees."""
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


# -- serving ---------------------------------------------------------------
def topk_gaps(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    rows: Sequence[int],
    served_items: np.ndarray,  # [n, k] item rows as served
    served_scores: np.ndarray,  # [n, k]
    block: int = 64,
) -> Dict:
    """Served top-k against float64 ``argsort(-(U[u] @ I.T))[:k]``.

    ``rank_gap``: the widest gap, over all answers and positions, by which
    the float64 score of the item served at a position lies below the
    float64 score of the item that belongs there (0 when the lists are
    equal; ``chip_smoke.check_answer``'s rule with the tie width read, not
    fixed). ``score_err``: the largest |served score - float64 score of
    the served item|. ``wrong_lists``: answers whose item ids differ from
    the reference's at some position. A repeated item in an answer is an
    infinite ``rank_gap``. ``*_each`` hold the two numbers per answer."""
    rows = np.asarray(rows, dtype=np.int64)
    k = served_items.shape[1]
    items64 = item_factors.astype(np.float64)
    rank_gap = np.zeros(len(rows))
    score_err = np.zeros(len(rows))
    wrong = 0
    for lo in range(0, len(rows), block):
        sl = slice(lo, lo + block)
        scores = user_factors[rows[sl]].astype(np.float64) @ items64.T
        # the best k of each row, best first, lower id first among equals
        part = np.argpartition(-scores, k, axis=1)[:, : k + 1]
        part_scores = np.take_along_axis(scores, part, axis=1)
        order = np.lexsort((part, -part_scores), axis=1)[:, :k]
        expected = np.take_along_axis(part, order, axis=1)
        got = served_items[sl]
        got_scores = np.take_along_axis(scores, got, axis=1)
        exp_scores = np.take_along_axis(scores, expected, axis=1)
        rank_gap[sl] = np.max(exp_scores - got_scores, axis=1)
        score_err[sl] = np.max(np.abs(served_scores[sl] - got_scores), axis=1)
        wrong += int(np.any(got != expected, axis=1).sum())
        repeated = [len(set(r)) != k for r in got.tolist()]
        rank_gap[sl][repeated] = np.inf
    return {
        "rank_gap": float(rank_gap.max(initial=0.0)),
        "score_err": float(score_err.max(initial=0.0)),
        "wrong_lists": float(wrong),
        "answers": float(len(rows)),
        "rank_gap_each": rank_gap,
        "score_err_each": score_err,
    }


# -- training --------------------------------------------------------------
def half_step_errors(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    sample_items: Sequence[int],
    lam: float,
) -> Dict[str, float]:
    """The last half-step of explicit ALS, by its definition: item row i
    solves ``(sum_u x_u x_u^T + lam * n_i * I) y_i = sum_u r_ui x_u`` over
    the users u that rated it, from the model's OWN user factors x.

    Two float64 references of it, each compared with the model's item
    rows by the worst row's ``max|y - y_ref| / max|y_ref|``:

    ``half_step_err``: x as it is (the mathematics).
    ``half_step_err_mxu``: the Gramian from x rounded to bfloat16, the
    right-hand side from x as it is: the arithmetic the configuration
    states for the chip's default build (MXU passes at default precision,
    f32 accumulation, f32 right-hand side on the VPU). It is the reading
    that tells the stated build from one that also reads the TABLE in
    bfloat16, which the first cannot (both round the Gramian alike).

    ``half_step_gap``: the worst row's distance to the NEARER of the two,
    the number that decides: a build is sound if every row follows the
    mathematics or the stated arithmetic, so a later build that computes
    the Gramian in true f32 passes as today's does."""
    sample = np.asarray(sample_items, dtype=np.int64)
    pick = np.isin(items, sample)
    u_s, i_s, r_s = users[pick], items[pick], ratings[pick].astype(np.float64)
    order = np.argsort(i_s, kind="stable")
    u_s, i_s, r_s = u_s[order], i_s[order], r_s[order]
    starts = np.searchsorted(i_s, sample)
    ends = np.searchsorted(i_s, sample, side="right")
    rank = user_factors.shape[1]
    eye = np.eye(rank)
    worst = {"half_step_err": 0.0, "half_step_err_mxu": 0.0, "half_step_gap": 0.0}
    for item, lo, hi in zip(sample.tolist(), starts.tolist(), ends.tolist()):
        x32 = user_factors[u_s[lo:hi]]
        x = x32.astype(np.float64)
        xb = bf16_round(x32).astype(np.float64)
        ridge = lam * (hi - lo) * eye
        rhs = x.T @ r_s[lo:hi]
        got = item_factors[item].astype(np.float64)
        errs = []
        for name, gram in (("half_step_err", x.T @ x), ("half_step_err_mxu", xb.T @ xb)):
            ref = np.linalg.solve(gram + ridge, rhs)
            errs.append(float(np.max(np.abs(got - ref)) / np.max(np.abs(ref))))
            worst[name] = max(worst[name], errs[-1])
        worst["half_step_gap"] = max(worst["half_step_gap"], min(errs))
    return worst


def rmse(user_factors, item_factors, users, items, ratings, block: int = 1 << 18) -> float:
    """Root mean squared error of ``x_u . y_i`` over the given triplets."""
    total = 0.0
    for lo in range(0, len(users), block):
        sl = slice(lo, lo + block)
        pred = np.einsum(
            "nk,nk->n",
            user_factors[users[sl]].astype(np.float64),
            item_factors[items[sl]].astype(np.float64),
        )
        total += float(np.sum((pred - ratings[sl]) ** 2))
    return float(np.sqrt(total / len(users)))


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> List[Dict]:
    """Each number compared beside its limit; a reading that is missing
    or not finite fails its limit."""
    out = []
    for name, limit in limits.items():
        value = readings.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        out.append({"name": name, "value": value, "limit": limit, "ok": bool(ok)})
    return out
