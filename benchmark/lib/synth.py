"""Everything a run draws from ``--seed``: ratings, factor tables, users
to ask for, arrival times. Vectorised numpy, no JAX (the load generator's
child imports this module and must stay off the chip).

The same seed gives the same inputs; different seeds give the same SIZES
in another order, so that every seed runs the same compiled programs and
the same amount of work (a degree sequence drawn per seed would move the
bucket shapes of ``ops/als.py`` and compile anew for every seed).
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

_CHUNK = 1 << 19


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """One independent generator per named stream of one seed (any whole
    number: ``SeedSequence`` takes more than 32 bits)."""
    return np.random.default_rng(
        [int(seed), int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")]
    )


def degree_sequence(n: int, total: int, exponent: float) -> np.ndarray:
    """``total`` ratings over ``n`` rows, row of rank r in proportion to
    ``r ** -exponent`` (``bench.synth_ml20m``'s inverse-rank weights), at
    least one each (``chip_smoke.synth_ratings``), summing to ``total``
    exactly. Fixed by the sizes alone, not by the seed."""
    if total < n:
        raise ValueError(f"{total} ratings cannot cover {n} rows")
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    share = w / w.sum() * (total - n)
    deg = np.floor(share).astype(np.int64)
    short = int(total - n - deg.sum())
    deg[np.argsort(-(share - deg), kind="stable")[:short]] += 1
    return deg + 1


def _draw_ranks(rng: np.random.Generator, n: int, exponent: float, count: int) -> np.ndarray:
    """``count`` ranks in [0, n), rank r in proportion to ``(r + 1) ** -exponent``."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(count)).astype(np.int64)


def _coprime_stride(n: int, rng: np.random.Generator) -> Tuple[int, int]:
    """(a, b) of the permutation j -> (a*j + b) % n."""
    while True:
        a = int(rng.integers(n // 3, 2 * n // 3))
        if math.gcd(a, n) == 1:
            return a, int(rng.integers(0, n))


def ratings(sizes: Dict, law: Dict, seed: int, threads: int = 8):
    """Synthetic explicit ratings: power-law degrees on both sides, a
    rank-``truth_rank`` ground truth plus Gaussian noise, every user and
    item rated at least once. Returns int32 users, int32 items, float32
    ratings and the truth tables (x, y) for the holdout.

    A user's stubs are paired with item stubs by two affine permutations
    of the stub index drawn from the seed: each user meets items in
    proportion to their popularity, and the triplets come out in no
    order. (Two full random permutations of 20 M stubs cost three times
    the whole of this function.)"""
    n_users, n_items, nnz = sizes["n_users"], sizes["n_items"], sizes["n_ratings"]
    rng = rng_for(seed, "ratings")
    deg_u = degree_sequence(n_users, nnz, law["user_exponent"])
    deg_i = degree_sequence(n_items, nnz, law["item_exponent"])
    user_stubs = np.repeat(rng.permutation(n_users).astype(np.int32), deg_u)
    item_stubs = np.repeat(rng.permutation(n_items).astype(np.int32), deg_i)
    a, b = _coprime_stride(nnz, rng)
    c, e = _coprime_stride(nnz, rng)
    k = law["truth_rank"]
    x = rng.standard_normal((n_users, k), dtype=np.float32) / np.float32(math.sqrt(k))
    y = rng.standard_normal((n_items, k), dtype=np.float32) / np.float32(math.sqrt(k))
    noise = rng.standard_normal(nnz, dtype=np.float32)
    noise *= np.float32(law["noise_sd"])
    users = np.empty(nnz, np.int32)
    items = np.empty(nnz, np.int32)
    vals = np.empty(nnz, np.float32)
    mean = np.float32(law["rating_mean"])

    def fill(lo: int) -> None:
        hi = min(nnz, lo + _CHUNK)
        p = (c * np.arange(lo, hi, dtype=np.int64) + e) % nnz
        u = user_stubs[p]
        i = item_stubs[(a * p + b) % nnz]
        users[lo:hi] = u
        items[lo:hi] = i
        vals[lo:hi] = np.einsum("nk,nk->n", x[u], y[i]) + mean + noise[lo:hi]

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, nnz, _CHUNK)))
    return users, items, vals, (x, y)


def holdout(sizes: Dict, law: Dict, seed: int, truth, train_users, train_items, n: int):
    """``n`` seeded (user, item, rating) triplets from the same truth as
    :func:`ratings`, none of whose pairs is in the training set. The user
    of a pair is the user of a random training triplet and its item the
    item of another: the training set's own marginals on its own ids, so
    a pair falls to a user (an item) in proportion to the ratings the
    model has seen of them."""
    rng = rng_for(seed, "holdout")
    n_items = sizes["n_items"]
    train_pairs = train_users.astype(np.int64) * n_items + train_items
    u, i = np.empty(0, np.int64), np.empty(0, np.int64)
    for _ in range(8):  # a dense toy set sees most pairs it draws
        du = train_users[rng.integers(0, len(train_users), 2 * n)].astype(np.int64)
        di = train_items[rng.integers(0, len(train_items), 2 * n)].astype(np.int64)
        fresh = ~np.isin(du * n_items + di, train_pairs)
        u, i = np.concatenate([u, du[fresh]])[:n], np.concatenate([i, di[fresh]])[:n]
        if len(u) == n:
            break
    x, y = truth
    r = (
        np.einsum("nk,nk->n", x[u], y[i])
        + np.float32(law["rating_mean"])
        + np.float32(law["noise_sd"]) * rng.standard_normal(len(u), dtype=np.float32)
    )
    return u.astype(np.int32), i.astype(np.int32), r.astype(np.float32)


def factor_tables(sizes: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded f32 user and item tables for a serving cell: entries
    N(0, 1) / rank**0.25, so a score is about N(0, 1) and the best ten of
    a catalog lie some hundredths apart, as a trained model's do."""
    rng = rng_for(seed, "factors")
    scale = np.float32(sizes["rank"] ** -0.25)
    u = rng.standard_normal((sizes["n_users"], sizes["rank"]), dtype=np.float32)
    i = rng.standard_normal((sizes["n_items"], sizes["rank"]), dtype=np.float32)
    return u * scale, i * scale


def zipf_users(n_users: int, exponent: float, count: int, seed: int) -> np.ndarray:
    """``count`` user rows, rank r asked for in proportion to
    ``r ** -exponent`` over ALL users, ranks relabelled by the seed."""
    rng = rng_for(seed, "users")
    ranks = _draw_ranks(rng, n_users, exponent, count)
    return rng.permutation(n_users)[ranks].astype(np.int64)


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times in [0, seconds) of a Poisson process, FIXED in number:
    ``round(rate * seconds)`` arrivals are the order statistics of that
    many uniforms, which is the process conditioned on its count, so every
    seed offers the same load in another order."""
    n = int(round(rate * seconds))
    return np.sort(rng_for(seed, "arrivals").random(n)) * seconds
