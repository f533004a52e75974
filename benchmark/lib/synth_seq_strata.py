"""Histories whose lengths are the law's own quantiles, one a stratum: what a
sequence-training cell draws from ``--seed`` where a job holds too few
histories for independent draws to carry the law (sixty of a Pareto law whose
tenth fills a row: their causal pairs, which attention's time follows, swing by
a tenth from seed to seed). The law, the items and the sampled slots are
``lib/synth_seq.py``'s; only how a job's n lengths are taken from the law
differs: length i is the law's quantile at ``(i + U_i) / n``, ``U_i`` one seeded
uniform a stratum, and the n lengths are shuffled by the seed. Every job then
holds the law's whole range and its share of histories at the cap; the seed
still moves every length inside its stratum, the order, the packing and the
items.

A job of a dozen rows holds about thirty histories, and there two things more
are needed for its causal pairs to hold still (over sixty seeds they swing by
2 % with neither, by under 1 % with both: ``benchmark/tests``). n is a function
of the job's rows and the law alone, not of the seed: the histories that fill
``FILL`` of the rows' slots at the law's mean length (one history more or less
is 3 % of a job's pairs). And neighbouring strata mirror each other: stratum 2k
+ 1 takes ``1 - U`` where stratum 2k took ``U`` (each still uniform in its own
stratum), so what one draws long the next draws short. The shuffle is the
first of the seed's orders that first fit packs into exactly ``rows`` rows of
``slots`` slots, so the job trains every row once. (Off the chip, over sixty
seeds at the cell's sizes, the tiles of 512 that attention's time follows
spread by 3.7 % between the quartiles without the mirror and by 1.4 % with it;
half of ``train_s``'s bound is 2.5 %.)

The catalogue is the deployment's, not the sample's: which id has which rank
of popularity and which id follows which are drawn ONCE (``CATALOGUE``), not
from the run's seed, which draws the users' histories from it. With Zipf(1.0)
a few ids make up a third of all slots, long histories average them into
every slot's hidden state, and so the routed experts that a freshly drawn
model sends nearly every token to are a function of WHICH ids are popular:
with the catalogue reshuffled by the seed, this share's held experts took
735 to 1,458 tokens each by the seed (steady through a job) and ``train_s``
followed them (r 0.84 over ten runs on the chip; with one catalogue 1,110 to
1,286 over six seeds, and ``train_s`` 1.16 % between its quartiles for 2.02:
PERF.md section 6, PR 45).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .synth import rng_for


def quantile_lengths(traffic: Dict, at: np.ndarray) -> np.ndarray:
    """The law's lengths at the probabilities ``at`` in [0, 1): Pareto,
    ``length_min * (1 - p) ** (-1 / exponent)``, floored, cut at ``length_cap``
    (``lib/synth_seq.history_lengths`` at ``U = 1 - p``)."""
    lengths = np.floor(traffic["length_min"] * (1.0 - at) ** (-1.0 / traffic["length_exponent"]))
    return np.minimum(lengths, traffic["length_cap"]).astype(np.int64)


#: the share of a job's slots its histories fill at the law's mean length
FILL = 0.93
#: orders tried for one that packs into exactly the job's rows
ORDERS = 256
#: the seed of the catalogue's two permutations (popularity's ranks to ids,
#: every id's successor): one catalogue for every run
CATALOGUE = 0


def stratified_lengths(traffic: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths, one from each of the law's n equal strata, shortest stratum
    first: one uniform a pair of neighbouring strata, mirrored in the second."""
    u = rng.random((n + 1) // 2)
    u = np.stack([u, 1.0 - u], axis=1).reshape(-1)[:n]
    return quantile_lengths(traffic, (np.arange(n) + u) / n)


def rows_first_fit(lengths: np.ndarray, slots: int) -> int:
    """Rows of ``slots`` slots that first fit lays these pieces into, in order."""
    free: List[int] = []
    for n in lengths:
        for r, room in enumerate(free):
            if room >= n:
                free[r] -= n
                break
        else:
            free.append(slots - n)
    return len(free)


def job_lengths(traffic: Dict, rows: int, slots: int, seed: int) -> np.ndarray:
    """The lengths of a job that trains on ``rows`` packed rows, in the order
    they are packed: ``FILL`` of the slots at the law's mean, one length a
    stratum, in the first of the seed's orders that packs into exactly
    ``rows`` rows (the last tried where none does)."""
    mean = float(quantile_lengths(traffic, (np.arange(4096) + 0.5) / 4096).mean())
    n = max(1, int(FILL * rows * slots / mean))
    rng = rng_for(seed, "strata")
    lengths = stratified_lengths(traffic, n, rng)
    for _ in range(ORDERS):
        order = lengths[rng.permutation(n)]
        if rows_first_fit(order, slots) == rows:
            break
    return order


def histories(traffic: Dict, n_items: int, rows: int, slots: int, seed: int) -> List[np.ndarray]:
    """Histories of item ids for a job of ``rows`` packed rows of ``slots``
    slots. Lengths: :func:`job_lengths`. Items by ``lib/synth_seq.histories``'s
    process: the first id of a history Zipf over the catalogue (ranks dealt to
    ids by a permutation); each next id, with ``follow_probability``, the
    successor of the one before it, else a fresh Zipf draw. The two
    permutations are the catalogue's (``CATALOGUE``), the draws the seed's."""
    rng = rng_for(seed, "history")
    lengths = job_lengths(traffic, rows, slots, seed)
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
