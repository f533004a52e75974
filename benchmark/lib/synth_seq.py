"""Everything the sequence-training cell draws from ``--seed``: history
lengths, item ids, and which slots of the last batch are compared."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .synth import rng_for


def history_lengths(traffic: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Pareto: ``length_min * U ** (-1 / exponent)``, floored, cut at
    ``length_cap``."""
    u = 1.0 - rng.random(n)  # (0, 1]
    lengths = np.floor(traffic["length_min"] * u ** (-1.0 / traffic["length_exponent"]))
    return np.minimum(lengths, traffic["length_cap"]).astype(np.int64)


def histories(traffic: Dict, n_items: int, tokens: int, seed: int) -> List[np.ndarray]:
    """Histories of item ids, ``tokens`` ids or a little more in all. The
    first id of a history is Zipf over the catalogue (item of popularity
    rank r in proportion to ``r ** -item_exponent``, ranks dealt to ids by
    a seeded permutation); each next id is, with ``follow_probability``,
    the seeded successor of the one before it, and else a fresh Zipf
    draw: something a sequence model can learn."""
    rng = rng_for(seed, "history")
    mean = float(history_lengths(traffic, 200_000, rng_for(seed, "mean")).mean())
    lengths = history_lengths(traffic, int(tokens / mean) + 8, rng)
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths), tokens)) + 1]
    total = int(lengths.sum())
    weights = np.arange(1, n_items + 1, dtype=np.float64) ** -traffic["item_exponent"]
    cdf = np.cumsum(weights / weights.sum())
    of_rank = rng.permutation(n_items).astype(np.int32)
    successor = rng.permutation(n_items).astype(np.int32)
    ids = of_rank[np.minimum(np.searchsorted(cdf, rng.random(total)), n_items - 1)]
    follow = rng.random(total) < traffic["follow_probability"]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    follow[starts] = False
    # how many slots in a row have followed their predecessor, up to here
    idx = np.arange(total)
    fresh = np.maximum.accumulate(np.where(~follow, idx, 0))
    run = idx - fresh
    for r in range(1, int(run.max()) + 1):
        at = np.flatnonzero(run == r)
        ids[at] = successor[ids[at - 1]]
    return np.split(ids, starts[1:])


def sampled_slots(seed: int, valid: np.ndarray, count: int) -> List[np.ndarray]:
    """Per row of the batch, the slots whose logits are compared: ``count``
    of the slots with a real target, spread over the rows."""
    rng = rng_for(seed, "slots")
    per_row = -(-count // valid.shape[0])
    out = []
    for row in valid:
        real = np.flatnonzero(row)
        out.append(np.sort(rng.choice(real, size=min(per_row, len(real)), replace=False)))
    return out
