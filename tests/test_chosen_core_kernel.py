"""The sparse-attention core's Pallas kernel pair (``ops/chosen_core.py``),
interpreted on the CPU, against the XLA loop it stands in for on the chip
(``ops/attention._flash_chosen`` through ``chosen_attention``): ``o``, the rows'
log-sum-exp and the three cotangents. Rows of 256 slots in kernel tiles of 128
(2 x 2 tiles), 4 query heads of 128 on one key/value head, values of 128, a
mask of the 40 best of random scores a query, unless a case says otherwise."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import attention, chosen_core

L, TILE, D = 256, 128, 128
NAMES = ("o", "lse", "dq", "dk", "dv")


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(chosen_core, "TILE", TILE)


def rel(got, want):
    got, want = (np.ravel(np.asarray(a, np.float64)) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _runs(*lengths):
    """Segment ids of histories of these lengths, one after another, then
    padding (id 0) to the row's end."""
    ids = np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])
    return np.concatenate([ids, np.zeros(L - len(ids), int)]).astype(np.int32)


def _valid(seg):
    pos = np.arange(L)
    return (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])


def _chosen(rng, seg, topk):
    """Of every query's causal keys inside its history the ``topk`` with the
    largest of random scores (all of them where there are no more)."""
    scores = np.where(_valid(seg), rng.normal(size=(L, L)), -np.inf)
    threshold = -np.sort(-scores, axis=-1)[:, topk - 1:topk]
    return _valid(seg) & (scores >= threshold)


def _no_key(chosen):
    chosen = chosen.copy()
    chosen[5:20] = chosen[130:141] = False  # real queries that read nothing
    return chosen


def _empty_tile(chosen):
    chosen = chosen.copy()
    chosen[TILE:, :TILE] = False  # the second query tile keeps no key of the first key tile
    return chosen


#: name -> (segment ids, topk, what is done to the mask, query heads, key/value heads)
CASES = {
    "one history that fills the row": (_runs(L), 40, None, 4, 1),
    "three histories and padding": (_runs(100, 60, 70), 40, None, 4, 1),
    "a boundary on a tile's first slot, two key heads": (_runs(128, 128), 40, None, 4, 2),
    "a history shorter than topk": (_runs(30, 200), 64, None, 4, 1),
    "queries with no chosen key": (_runs(L), 40, _no_key, 4, 1),
    "a tile with no chosen pair": (_runs(L), 40, _empty_tile, 4, 1),
    "one query head a key head": (_runs(180, 76), 40, None, 2, 2),
    "eight query heads a key head": (_runs(90, 166), 40, None, 8, 1),
}


def _inputs(name, dtype, seed=0):
    seg, topk, edit, heads, kv_heads = CASES[name]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, heads, L, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(1, kv_heads, L, D)), dtype) for _ in range(2))
    chosen = _chosen(rng, seg, topk)
    chosen = chosen if edit is None else edit(chosen)
    weight = jnp.asarray(rng.normal(size=(1, heads, L, D)), jnp.float32)
    return (q, k, v), jnp.asarray(chosen[None]), jnp.asarray(seg[None]), weight


def _all_five(core, qkv, weight):
    """``o``, the log-sum-exp and every gradient of a weighted sum of ``o``."""
    def total(q, k, v):
        o, lse = core(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * weight), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(total, argnums=(0, 1, 2), has_aux=True)(*qkv)
    return (o, lse) + grads


def _both(name, dtype):
    qkv, chosen, seg, weight = _inputs(name, dtype)
    loop = _all_five(lambda q, k, v: attention.chosen_attention(
        q, k, v, chosen, seg, block=TILE), qkv, weight)
    pair = _all_five(lambda q, k, v: chosen_core.chosen_core(
        q, k, v, chosen, seg, interpret=True), qkv, weight)
    return chosen, loop, pair


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_pair_gives_what_the_loop_gives(small_tiles, name, dtype):
    """float32 inputs: to the order of float32 sums. bfloat16 inputs: to the
    loop's own rounding (the same products from the same rounded weights, so
    what differs is a cotangent's last bit where two float32 sums differ)."""
    chosen, loop, pair = _both(name, jnp.dtype(dtype))
    limit = 1e-5 if dtype == "float32" else 2e-3
    for what, got, want in zip(NAMES, pair, loop):
        assert got.shape == want.shape and got.dtype == want.dtype, what
        assert rel(got, want) <= limit, (what, rel(got, want))
    kept = np.asarray(chosen[0]).any(-1)
    if not kept.all():  # a query without a chosen key: zeros, and the loop's log-sum-exp
        assert np.all(np.asarray(pair[0], np.float32)[0, :, ~kept] == 0)
        np.testing.assert_array_equal(np.asarray(pair[1])[0, :, ~kept],
                                      np.asarray(loop[1])[0, :, ~kept])
        assert np.all(np.asarray(pair[1])[0, :, ~kept] < -1e29)


def test_a_tile_without_a_kept_pair_is_skipped_and_fetches_nothing():
    """The tables a call hands its kernels: the tile above the diagonal and
    the emptied one are not live, and their grid steps name the key tile the
    walk already holds."""
    _, chosen, seg, _ = _inputs("a tile with no chosen pair", jnp.float32)
    live, fetch = chosen_core._tables(chosen_core._keep(chosen, seg), TILE)
    assert live.tolist() == [1, 0, 0, 1]  # (0, 0), (0, 1), (1, 0), (1, 1)
    assert fetch.tolist() == [0, 0, 1, 1]
    _, chosen, seg, _ = _inputs("three histories and padding", jnp.float32)
    live, fetch = chosen_core._tables(chosen_core._keep(chosen, seg), TILE)
    assert live.tolist() == [1, 0, 1, 1] and fetch.tolist() == [0, 0, 0, 1]


def test_the_mask_a_kernel_reads_is_the_loops():
    """``chosen`` may hold anything: the kernels keep of it what the loop's
    tiles keep, the causal pairs inside a history."""
    rng = np.random.default_rng(3)
    seg = _runs(100, 60, 70)
    anything = jnp.asarray(rng.random((1, L, L)) < 0.3)
    keep = np.asarray(chosen_core._keep(anything, jnp.asarray(seg[None])))
    assert keep.dtype == np.int8
    np.testing.assert_array_equal(keep[0] != 0, np.asarray(anything[0]) & _valid(seg))


def test_chosen_attention_runs_the_pair_where_core_kind_says_so(small_tiles, monkeypatch):
    """The one place the two forms meet: ``chosen_attention`` asks
    ``core_kind`` and hands the call over unchanged."""
    qkv, chosen, seg, weight = _inputs("three histories and padding", jnp.float32)
    want = attention.chosen_attention(*qkv, chosen, seg, block=TILE)
    asked = []

    def kind(*shape):
        asked.append(shape)
        return "pallas"

    monkeypatch.setattr(chosen_core, "core_kind", kind)
    monkeypatch.setattr(chosen_core, "chosen_core", functools.partial(
        chosen_core.chosen_core, interpret=True))
    attention.chosen_attention.clear_cache()
    try:
        got = attention.chosen_attention(*qkv, chosen, seg, block=TILE)
    finally:
        attention.chosen_attention.clear_cache()
    assert asked == [(4, 1, D, D, L, "float32")]
    for g, w in zip(got, want):
        assert rel(g, w) <= 1e-5


#: the cell's shape: 32 heads of 128 on 4 key heads, values of 128, 16,384 slots
CELL = dict(heads=32, kv_heads=4, head_dim=128, value_dim=128, length=16384)


@pytest.mark.parametrize("name, change, kind", [
    ("the cell's shape under the interpreter", {}, "pallas"),
    ("a row of one tile", {"length": 256}, "pallas"),
    ("heads of 64", {"head_dim": 64}, "xla"),
    ("values of 64", {"value_dim": 64}, "xla"),
    ("a ragged row", {"length": 16385}, "xla"),
    ("a row of no whole lane tile", {"length": 200}, "xla"),
    ("bfloat16 statistics", {"stats_dtype": "bfloat16"}, "xla"),
    ("query heads that do not divide", {"heads": 30}, "xla"),
    ("a row whose dk and dv do not fit VMEM", {"length": 65536}, "xla"),
    ("the CPU", {"interpret": False}, "xla"),
])
def test_core_kind(name, change, kind):
    assert chosen_core.core_kind(**{**CELL, "interpret": True, **change}) == kind


def test_core_kind_on_a_tpu_needs_no_interpreter(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chosen_core.core_kind(**CELL) == "pallas"
    assert chosen_core.core_kind(**{**CELL, "head_dim": 64}) == "xla"
    assert chosen_core.forms(32, 4, 128, 16384) == {"chosen_core": "pallas"}
