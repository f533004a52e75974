"""``lib/synth_seq.histories`` with ONE catalogue for every run: the same law
of lengths drawn the same way from the seed, the same Zipf draws and follows,
but which id has which rank of popularity and which id follows which are the
deployment's (``lib/synth_seq_strata.CATALOGUE``, drawn once), not the run's.

Why a cell of short packed histories needs it where an expert layer holds a
share of the experts: with Zipf(1.0) ten ids make up 28 % of all slots, and a
freshly drawn router sends each of them to six fixed experts, so WHICH ids
are popular decides how many tokens this share's eight held experts take at
the start of a job. It is one of two causes of a load that follows the seed:
the other is the drift of the held routers' inputs at the whole learning
rate, which the configuration's warm-up holds still (``algorithm.warmup_steps``).
With both, the job's mean load read 792 to 799 tokens a held expert over
fourteen seeds on the chip; with the catalogue reshuffled by the seed and no
warm-up 387 to 728 over six, with one catalogue and no warm-up 510 to 914 over
thirteen, and ``train_s`` followed it (PERF.md section 6, PR 49);
``lib/synth_seq_strata.py`` says the same of long histories (PR 45).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .synth import rng_for
from .synth_seq import history_lengths
from .synth_seq_strata import CATALOGUE


def histories(traffic: Dict, n_items: int, tokens: int, seed: int) -> List[np.ndarray]:
    """Histories of item ids, ``tokens`` ids or a little more in all:
    ``lib/synth_seq.histories``'s lengths and process (the first id of a
    history Zipf over the catalogue, each next id with ``follow_probability``
    the successor of the one before it, else a fresh Zipf draw), the two
    permutations the catalogue's, the draws the seed's."""
    rng = rng_for(seed, "history")
    mean = float(history_lengths(traffic, 200_000, rng_for(seed, "mean")).mean())
    lengths = history_lengths(traffic, int(tokens / mean) + 8, rng)
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths), tokens)) + 1]
    total = int(lengths.sum())
    weights = np.arange(1, n_items + 1, dtype=np.float64) ** -traffic["item_exponent"]
    cdf = np.cumsum(weights / weights.sum())
    catalogue = rng_for(CATALOGUE, "catalogue")
    of_rank = catalogue.permutation(n_items).astype(np.int32)
    successor = catalogue.permutation(n_items).astype(np.int32)
    ids = of_rank[np.minimum(np.searchsorted(cdf, rng.random(total)), n_items - 1)]
    follow = rng.random(total) < traffic["follow_probability"]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    follow[starts] = False
    # how many slots in a row have followed their predecessor, up to here
    idx = np.arange(total)
    run = idx - np.maximum.accumulate(np.where(~follow, idx, 0))
    for r in range(1, int(run.max()) + 1):
        at = np.flatnonzero(run == r)
        ids[at] = successor[ids[at - 1]]
    return np.split(ids, starts[1:])
