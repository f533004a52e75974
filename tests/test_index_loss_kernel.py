"""The indexers' loss as a Pallas kernel pair (``ops/indexer_kl.py``),
interpreted on the CPU, against the XLA loop it stands in for on the chip
(``ops/dsa._kl``): the per-query KL, the log-sum-exp of the index scores over
the chosen keys and the three cotangents. Rows of 256 slots in kernel tiles of
128 (2 x 2 tiles), 4 main heads of 128 on 2 key heads, 4 index heads of 64, the
mask ``dsa.select`` makes of the indexer's own scores (the 40 best a query),
the core's log-sum-exp from ``chosen_attention``, unless a case says otherwise."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.ops import attention, chosen_core, dsa, indexer_kl

L, TILE, D, J, DI = 256, 128, 128, 4, 64
NAMES = ("kl", "lse_i", "d_iq", "d_ik", "d_iw")


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


def _no_key(chosen):
    return chosen.at[:, 5:20].set(False).at[:, 130:141].set(False)  # real queries that read nothing


def _empty_tile(chosen):
    return chosen.at[:, TILE:, :TILE].set(False)  # the second query tile keeps no key of the first


#: name -> (segment ids a row, topk, what is done to the mask, main heads, key heads)
CASES = {
    "one history that fills the row": ([_runs(L)], 40, None, 4, 2),
    "three histories and padding": ([_runs(100, 60, 70)], 40, None, 4, 2),
    "two rows, a boundary on a tile's first slot": (
        [_runs(128, 128), _runs(90, 166)], 40, None, 4, 2),
    "a history shorter than topk": ([_runs(30, 200)], 64, None, 4, 1),
    "queries with no chosen key": ([_runs(L)], 40, _no_key, 4, 2),
    "a tile with no chosen pair": ([_runs(L)], 40, _empty_tile, 4, 2),
    "eight main heads a key head": ([_runs(180, 76)], 40, None, 8, 1),
}


def _inputs(name, dtype, seed=0):
    """The indexer's inputs, the mask ``dsa.select`` makes of them, the main
    heads' q and k and the log-sum-exp the core gives over that mask, and a
    cotangent a query."""
    segs, topk, edit, heads, kv_heads = CASES[name]
    rng = np.random.default_rng(seed)
    seg = jnp.asarray(np.stack(segs))
    b = len(segs)
    iq = jnp.asarray(rng.normal(size=(b, L, J, DI)), dtype)
    ik = jnp.asarray(rng.normal(size=(b, L, DI)), dtype)
    iw = jnp.asarray(rng.normal(size=(b, L, J)) * (J * DI) ** -0.5, jnp.float32)
    chosen = dsa.select(iq, ik, iw, seg, topk=topk, block=TILE)[0]
    chosen = chosen if edit is None else edit(chosen)
    q = jnp.asarray(rng.normal(size=(b, heads, L, D)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kv_heads, L, D)), dtype)
    lse = attention.chosen_attention(q, k, k, chosen, seg, block=TILE)[1]
    g = jnp.asarray(rng.normal(size=(b, L)), jnp.float32)
    return (iq, ik, iw), (q, k, lse, seg, chosen), g


def _loop(indexer, main, g):
    """What ``dsa.index_loss`` runs off the chip: ``_kl_forward``'s two and
    ``_kl_bwd``'s three."""
    q, k, lse, seg, chosen = main
    qg, k, _, seg_p, _ = attention._grouped_and_padded(q, k, k, seg, TILE, TILE)
    rest = (qg, k, lse.reshape(qg.shape[:4]), seg_p, chosen, TILE, jnp.dtype("float32"))
    acc, lse_i = dsa._kl_forward(*indexer, *rest)
    _, pull = jax.vjp(lambda *a: dsa._kl(*a, *rest), *indexer)
    return (acc + lse_i, lse_i) + pull(g)


def _pair(indexer, main, g):
    q, k, lse, seg, chosen = main
    kl, pull = jax.vjp(lambda *a: indexer_kl.kl(*a, *main, interpret=True), *indexer)
    b, h, length, d = q.shape
    hkv = k.shape[1]
    lse_i = indexer_kl._forward(
        (TILE, True), *indexer, q.reshape(b, hkv, h // hkv, length, d), k,
        lse.reshape(b, hkv, h // hkv, length), seg, chosen)[1]
    return (kl, lse_i) + pull(g)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_pair_gives_what_the_loop_gives(small_tiles, name, dtype):
    """float32 inputs: to the order of float32 sums. bfloat16 inputs: the
    forward's numbers to the same (the same products of the same inputs); the
    cotangents to the rounding of ``d_s`` to bfloat16 before its two products,
    which the chip's products make of a float32 operand anyway and the CPU's
    do not."""
    indexer, main, g = _inputs(name, jnp.dtype(dtype))
    loop, pair = _loop(indexer, main, g), _pair(indexer, main, g)
    kept = np.asarray(chosen_core._keep(main[4], main[3])).any(-1)
    for what, got, want in zip(NAMES, pair, loop):
        assert got.shape == want.shape and got.dtype == want.dtype, what
        if what in ("kl", "lse_i"):  # a query without a chosen key reads -1e30: compared apart
            got, want = (np.where(kept, np.asarray(a), 0.0) for a in (got, want))
        limit = 2e-5 if dtype == "float32" or what in ("kl", "lse_i") else 4e-3
        assert rel(got, want) <= limit, (what, rel(got, want))
    if not kept.all():  # the loop's numbers, and no gradient from such a query
        for got, want in zip(pair[:2], loop[:2]):
            np.testing.assert_array_equal(np.asarray(got)[~kept], np.asarray(want)[~kept])
            assert np.all(np.asarray(got)[~kept] < -1e29)
        assert np.all(np.asarray(pair[2], np.float32)[~kept] == 0)
        assert np.all(np.asarray(pair[4])[~kept] == 0)


def test_a_tile_without_a_kept_pair_is_skipped():
    """The tables the kernels are handed are the core's: the emptied tile and
    the one above the diagonal are not live, so their grid steps add nothing
    (the case above reads the loop's numbers with them skipped)."""
    _, (_, _, _, seg, chosen), _ = _inputs("a tile with no chosen pair", jnp.float32)
    live, _ = chosen_core._tables(chosen_core._keep(chosen, seg), TILE)
    assert live.tolist() == [1, 0, 0, 1]


def test_index_loss_runs_the_pair_where_loss_kind_says_so(small_tiles, monkeypatch):
    """The one place the two forms meet: ``dsa.index_loss`` asks ``loss_kind``
    and hands the call over; the sum over the real queries and their number
    are the loop's, and so is the gradient."""
    indexer, (q, k, lse, seg, chosen), _ = _inputs("three histories and padding", jnp.float32)

    def mean(form):
        def fn(*a):
            total, n = form(*a, q, k, lse, seg, chosen, block=TILE)
            return total / n
        return jax.value_and_grad(fn, argnums=(0, 1, 2))(*indexer)

    want = mean(dsa.index_loss)
    asked = []

    def kind(*shape):
        asked.append(shape)
        return "pallas"

    monkeypatch.setattr(indexer_kl, "loss_kind", kind)
    monkeypatch.setattr(indexer_kl, "kl", functools.partial(indexer_kl.kl, interpret=True))
    dsa.index_loss.clear_cache()
    try:
        got = mean(dsa.index_loss)
    finally:
        dsa.index_loss.clear_cache()
    assert asked == [(4, 2, D, J, DI, L, jnp.float32)]
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert rel(g, w) <= 2e-5


#: the cell's shape: 32 heads of 128 on 4 key heads, 16 index heads of 64, 16,384 slots
CELL = dict(heads=32, kv_heads=4, head_dim=128, index_heads=16, index_dim=64, length=16384)


@pytest.mark.parametrize("name, change, kind", [
    ("the cell's shape under the interpreter", {}, "pallas"),
    ("a row of one tile", {"length": 256}, "pallas"),
    ("index heads of 128", {"index_dim": 128}, "pallas"),
    ("a bfloat16 head-weighted sum: the benchmark's control", {"sum_dtype": "bfloat16"}, "xla"),
    ("main heads of 64", {"head_dim": 64}, "xla"),
    ("a ragged row", {"length": 16385}, "xla"),
    ("a row of no whole lane tile", {"length": 200}, "xla"),
    ("query heads that do not divide", {"heads": 30}, "xla"),
    ("a row whose blocks do not fit VMEM", {"length": 65536}, "xla"),
    ("the CPU", {"interpret": False}, "xla"),
])
def test_loss_kind(name, change, kind):
    assert indexer_kl.loss_kind(**{**CELL, "interpret": True, **change}) == kind


def test_loss_kind_on_a_tpu_needs_no_interpreter(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert indexer_kl.loss_kind(**CELL) == "pallas"
    assert indexer_kl.loss_kind(**{**CELL, "sum_dtype": jnp.bfloat16}) == "xla"
    assert indexer_kl.forms(**CELL) == {"index_kl": "pallas"}
    cfg = bb.BackboneConfig.load("keye-vl2-30b-a3b-ep8")
    assert bb.mechanisms(cfg, 16384) == {"chosen_core": "pallas", "index_kl": "pallas"}
    assert bb.mechanisms(bb.BackboneConfig.load("keye-vl2-tiny"), 64)["index_kl"] == "xla"


def _pallas_calls(fn, *args):
    """The names of the kernels ``fn``'s jaxpr calls, nested jaxprs too."""
    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    return sorted(eqn.params["name"] for eqn in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                  if eqn.primitive.name == "pallas_call")


@pytest.mark.parametrize("kept", [True, False])
def test_the_recomputed_layer_runs_the_forward_kernel_once(small_tiles, monkeypatch, kept):
    """A ``dsa`` layer's gradient as the step takes it (``_layer_fn``: the layer
    is recomputed in the backward pass but for what its record keeps by name,
    the forward kernel's two outputs): ``index_kl_forward`` once and
    ``index_kl_backward`` once, the core's forward twice, as before. With
    nothing kept the forward kernel would run in the recomputation too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(bb.BackboneConfig.load("keye-vl2-tiny"), head_dim=D, index_topk=40,
                              attn_block=TILE)
    assert bb.mechanisms(cfg, L) == {"chosen_core": "pallas", "index_kl": "pallas"}
    assert bb._MIXERS["dsa"].kept == indexer_kl.KEPT
    if not kept:
        monkeypatch.setattr(bb, "_POLICIES", {})
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, 50, L, 0))["periods"]
    block = jax.tree_util.tree_map(lambda s: jnp.full(s.shape[2:], 0.01, s.dtype), shapes)
    layer = bb._layer_fn(cfg, "dsa", None, "auto")
    seg = jnp.asarray(_runs(100, 60, 70)[None])

    def loss(x, block):
        y, counters, _ = layer(x, seg, bb.positions_of(seg), block["norm_in"], block["dsa"],
                               block["norm_post"], block["ffn"], {})
        return y.sum() + counters["index_loss"]

    jits = (attention.chosen_attention, dsa.index_loss)  # they ask for the backend while traced
    for fn in jits:
        fn.clear_cache()
    try:
        calls = _pallas_calls(jax.grad(loss, argnums=(0, 1)),
                              jnp.ones((1, L, cfg.hidden_size), jnp.float32), block)
    finally:
        for fn in jits:
            fn.clear_cache()
    assert calls == sorted(["chosen_core_forward"] * 2 + ["chosen_core_backward"]
                           + ["index_kl_forward"] * (1 if kept else 2) + ["index_kl_backward"])
