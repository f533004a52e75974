"""The sequence backbone as a decoder-hybrid-decoder (Mamba-1 selective scans
beside sliding-window and full differential attention, then a gated memory
unit on the last scan's output and cross-attention onto the full layer's keys
and values; LayerNorm with a bias, a dense SwiGLU in every layer, no
positions) against its plain reference, at a small size on the CPU: hidden
64, 128 channels on a state of 4, 8 query heads on 4 key heads of 8 in pairs,
a window of 16, one period of six layers, chunks of 8 slots, rows of 64 slots.

The program computes in float32 here (``compute_dtype`` of the tiny
configuration), so the distances are those of the mathematics: summation
order and nothing else.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.models import sequencerec
from predictionio_tpu.ops.attention import flash_attention, tiles_skipped_by_window
from predictionio_tpu.ops.selscan import mamba1, scan_kind, selective_scan
from predictionio_tpu.testing import phi4flash_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "phi4-mini-flash-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L = 50, 64
#: a history longer than the window of 16 beside two shorter ones, and padding
HISTORIES = {1: (0, 12), 2: (12, 53), 3: (53, 62)}


def rel(got, want):
    got = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.ravel(np.asarray(a, np.float64)) for a in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def seeded(cfg, seed=0):
    """Seeded weights with every norm scale and bias, convolution bias, skip
    and lambda vector moved off its starting value, so that none of them
    drops out of a comparison."""
    drawn = bb.init_params(cfg, VOCAB, L, seed)
    leaves, treedef = jax.tree_util.tree_flatten(drawn)
    rng = np.random.default_rng(1)
    leaves = [leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32)
              if leaf.ndim <= 3 else leaf for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def packed_batch():
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    for sid, (lo, hi) in HISTORIES.items():
        segs[0, lo:hi] = sid
    segs[1, :] = 1
    return rows, segs


def program_and_reference(raw, cfg, params, batch):
    rows, segs = batch
    program = jax.jit(jax.value_and_grad(
        lambda mp, r, s: bb.loss_fn(cfg, mp, r, s), has_aux=True))
    (loss, (hidden, counters, ran)), grads = program(params, rows, segs)
    slots = [np.arange(0, 60, 7), np.arange(3, 64, 5)]
    want = ref.loss_and_grads(bb.layers_of(params, cfg), rows, segs, raw, sample=slots)
    logits = [bb.logits_of(cfg, params, hidden[b][at]) for b, at in enumerate(slots)]
    return {"loss": float(loss), "grads": bb.layers_of(grads, cfg), "logits": logits,
            "counters": counters, "ran": ran, "want": dict(zip(("loss", "grads", "logits"), want))}


@pytest.fixture(scope="module")
def cfg():
    return bb.BackboneConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of L + 1 slots: three histories (one longer than the
    window) and padding in the first, one history that fills the second."""
    return packed_batch()


@pytest.fixture(scope="module")
def both(cfg, params, batch):
    """Program and reference on the same weights and batch."""
    return program_and_reference(TINY, cfg, params, batch)


def test_loss_and_logits_match_reference(both):
    want = both["want"]
    # float32 sums in another order over 128 targets
    assert abs(both["loss"] - want["loss"]) / want["loss"] < 1e-6
    for got, expected in zip(both["logits"], want["logits"]):
        assert rel(got, expected) < 1e-4


GROUPS = {
    **{name: (lambda layer, name=name: layer.get(name))
       for name in ("mamba1", "swa", "full", "cross", "gmu")},
    "mlp": lambda layer: layer["mlp"],
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


def assert_leaves_agree(got, want, tolerance, note):
    pairs = list(zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    assert pairs
    for a, b in pairs:
        assert np.any(b) and rel(a, b) < tolerance, note


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    """Leaf by leaf, none of them zero (the norms' biases, the four lambda
    vectors and the norm over a pair's value too): 5e-4 is a hundred times
    what float32 in another order reads here and a thousandth of what a
    state or a window let across a boundary does (tests below)."""
    got, want = both["grads"], both["want"]["grads"]
    if group in GROUPS:
        pick = GROUPS[group]
        got = [pick(layer) for layer in got["layers"]]
        want = [pick(layer) for layer in want["layers"]]
        assert any(w is not None for w in want)
    else:
        got, want = got[group], want[group]
    assert_leaves_agree(got, want, 5e-4, group)


def test_one_optimizer_step_is_plain_adamw_on_the_references_gradient(cfg, params, batch, both):
    """The job's own donated step from fresh moments against numpy AdamW on
    the REFERENCE's gradient: Adam's first step is lr * sign(g) nearly
    everywhere, so the two agree to float32 wherever the gradients do."""
    opt_init, step, _ = sequencerec._programs(cfg, 1e-2, None, "auto")
    copy = jax.tree_util.tree_map(jnp.array, params)
    new, _, loss, counters = step(copy, opt_init(copy), *batch)
    assert counters == {} and abs(float(loss) - both["loss"]) < 1e-6
    change = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), new, params)
    want = ref.adamw_first_step(
        bb.layers_of(params, cfg), both["want"]["grads"], 1e-2, 0.9, 0.999, 1e-8, 1e-4)
    assert rel(bb.layers_of(change, cfg), want) < 1e-3


def test_the_layout_comes_from_layer_types(cfg, both, params):
    layers = both["grads"]["layers"]
    assert [next(k for k in ("mamba1", "swa", "full", "gmu", "cross") if k in layer)
            for layer in layers] == ["mamba1", "swa", "mamba1", "full", "gmu", "cross"]
    assert all("mlp" in layer and set(layer["input_norm"]) == {"g", "b"} for layer in layers)
    assert (cfg.period_kinds, cfg.n_periods, cfg.first_k_dense_replace) == (
        ("mamba1", "swa", "mamba1", "full", "gmu", "cross"), 1, 0)
    assert cfg.mixers() == {"cross": 1, "gmu": 1, "gqa": 1, "mamba1": 2, "swa": 1}
    assert (cfg.positions, cfg.norm, cfg.sliding_window, cfg.layer_norm_eps) == (
        "none", "layer", 16, 1e-5)
    assert "pos" not in params and "head" not in params and "dense" not in params
    per = params["periods"]
    assert per["mamba1"]["w_in"].shape == (1, 2, 64, 256)
    assert per["mamba1"]["A_log"].shape == (1, 2, 128, 4) and per["mamba1"]["w_x"].shape == (1, 2, 128, 12)
    assert per["full"]["w_k"].shape == (1, 64, 32) and per["swa"]["w_k"].shape == (1, 1, 64, 32)
    assert "w_k" not in per["cross"] and "w_v" not in per["cross"]
    assert per["cross"]["subln"].shape == (1, 1, 16) and per["gmu"]["w_1"].shape == (1, 1, 64, 128)
    assert both["counters"] == {}  # no router anywhere: nothing is counted
    # the Mamba-1 start: A = 1 ... N along the state of every channel
    drawn = bb.init_params(cfg, VOCAB, L, 0)["periods"]["mamba1"]
    assert np.allclose(np.exp(drawn["A_log"][0, 1, 5]), [1, 2, 3, 4]) and np.all(drawn["D"] == 1)


def test_the_scan_is_the_recurrence_on_what_it_was_handed(both, params, batch):
    """The aux carries c, Delta, B, C and y of the FIRST Mamba-1 layer: y is
    the reference's slot-by-slot recurrence on the other four, and m the
    skip beside it."""
    ran, segs = both["ran"], batch[1]
    assert ran["c"].shape == ran["dt"].shape == ran["y"].shape == (1, 2, L, 128)
    assert ran["B"].shape == ran["C"].shape == (1, 2, L, 4)
    p = jax.tree_util.tree_map(lambda a: a[0, 0], params["periods"]["mamba1"])
    for b in range(2):
        want = ref.selective_scan_of(
            *(ran[name][0, b] for name in ("c", "dt", "B", "C")), p["A_log"], segs[b, :-1])
        assert rel(ran["y"][0, b], want) < 1e-5
        assert rel(ran["m"][0, b], want + p["D"] * ran["c"][0, b]) < 1e-5


def _reference_layout(ran, b):
    """The program's q, k, v of row ``b`` (members first along the heads, the
    values twice) in the reference's: pairs first."""
    q, k, v = (np.asarray(ran[name][0, b]) for name in ("q", "k", "v"))
    pairs = lambda t: t.reshape((2, t.shape[0] // 2) + t.shape[1:]).transpose(1, 0, 2, 3)  # noqa: E731
    return pairs(q), pairs(k), v[: v.shape[0] // 2]


def swa_core_err(ran, segs, window, merge=False):
    """The benchmark's number: the sliding layer's ``(A1 - lambda A2) v`` as
    the program's call computed it against the reference's two softmaxes on
    that call's own q, k, v under ``window`` (``merge``: with the row taken
    for one history)."""
    got, want = [], []
    for b in range(2):
        seg = np.minimum(segs[b, :-1], 1) if merge else segs[b, :-1]
        want.append(ref.differential_core_of(
            *_reference_layout(ran, b), ran["lam"][0], seg, window))
        got.append(ran["o"][0, b])
    return rel(got, want)


def test_the_sliding_core_is_two_softmaxes_inside_window_and_history(both, batch):
    """``swa_core_err``: zero to float32 against the reference's window of
    16, and far off against a window ignored, one slot shorter, one slot
    longer, or one that does not stop at a history's first slot (history 2
    is 41 slots long beside two shorter than the window, so the window and
    the segment mask are told apart)."""
    ran, segs = both["ran"], batch[1]
    assert ran["q"].shape == (1, 2, 8, L, 8) and ran["k"].shape == (1, 2, 4, L, 8)
    assert ran["v"].shape == (1, 2, 4, L, 16) and ran["o"].shape == (1, 2, 4, L, 16)
    assert swa_core_err(ran, segs, 16) < 1e-5
    for wrong in (0, 15, 17):
        assert swa_core_err(ran, segs, wrong) > 1e-2, wrong
    assert swa_core_err(ran, segs, 16, merge=True) > 1e-2


# -- what one layer hands to several above it ----------------------------------
def test_a_producers_gradient_is_the_sum_over_its_consumers():
    """Eight layers: the second Mamba-1 layer's ``m`` feeds two gated memory
    units (and its own gate), the full layer's ``k, v`` two cross-attention
    layers (and its own core). The gradients of those two layers, leaf by
    leaf, are the reference's, which sums the consumers' cotangents by hand
    before it pulls a producer back; a first Mamba-1 layer whose ``m`` no one
    reads gets nothing through it."""
    raw = {**TINY, "num_hidden_layers": 8, "layer_types": TINY["layer_types"] + ["gmu", "cross_attention"]}
    cfg = bb.BackboneConfig.from_dict(raw)
    assert cfg.mixers() == {"cross": 2, "gmu": 2, "gqa": 1, "mamba1": 2, "swa": 1}
    out = program_and_reference(raw, cfg, seeded(cfg), packed_batch())
    assert abs(out["loss"] - out["want"]["loss"]) / out["want"]["loss"] < 1e-6
    for i, name in ((0, "mamba1"), (2, "mamba1"), (3, "full")):
        assert_leaves_agree(out["grads"]["layers"][i][name], out["want"]["grads"]["layers"][i][name],
                            5e-4, (i, name))


def test_a_consumer_reads_the_newest_producer_below_it(cfg, params, batch):
    """The gated memory unit reads the SECOND Mamba-1 layer's ``m``: with the
    first layer's in its place the hidden states are other numbers."""
    rows, segs = batch
    tokens, seg = rows[:, :-1], segs[:, :-1]
    want = bb.hidden_states(cfg, params, tokens, seg)[0]
    swapped = {**params, "periods": {**params["periods"], "mamba1": jax.tree_util.tree_map(
        lambda a: a[:, ::-1], params["periods"]["mamba1"])}}
    assert rel(bb.hidden_states(cfg, swapped, tokens, seg)[0], want) > 1e-2


# -- the selective scan against the recurrence, boundary by boundary ----------
def _scan_inputs(rng, seg, channels=24, state=4):
    length = len(seg)
    x = rng.normal(size=(1, length, channels)).astype(np.float32)
    b, c = (rng.normal(size=(1, length, state)).astype(np.float32) for _ in range(2))
    # decays from nearly none to a state forgotten within a few slots
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=(1, length, channels))).astype(np.float32)
    a_log = np.log(rng.uniform(1.0, 16.0, size=(channels, state))).astype(np.float32)
    return x, dt, a_log, b, c, np.asarray(seg, np.int32)[None]


def _runs(*lengths):
    """Segment ids of histories of these lengths, one after another."""
    return np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])


BOUNDARIES = {
    "one history": _runs(64),
    "on a chunk's first slot": _runs(16, 32, 16),
    "on a chunk's last slot": _runs(15, 33, 16),
    "mid-chunk": _runs(7, 30, 27),
    "three in one chunk": _runs(18, 3, 4, 5, 34),
    "two in every chunk": _runs(3, 5, 3, 5, 3, 5, 3, 5, 3, 5, 3, 5, 3, 5, 3, 5),
    "histories shorter than the four taps": _runs(1, 2, 3, 1, 1, 2, 22, 3, 29),
    "every slot its own history": _runs(*[1] * 64),
    "a row that is no whole number of blocks": _runs(9, 20, 11),
    "padding (id 0) behind the histories": np.concatenate([_runs(20, 30), np.zeros(14, int)]),
}


def _weighted(scan):
    """``scan`` -> its value and every gradient of a weighted sum of it, jitted
    once for all cases of one length."""
    def total(x, dt, a_log, b, c, segs, weight):
        y = scan(x, dt, a_log, b, c, segs)
        return jnp.sum(y * weight), y

    return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4), has_aux=True))


_PROGRAM = _weighted(lambda x, dt, a_log, b, c, segs: selective_scan(
    x, dt, -jnp.exp(a_log), b, c, segs, chunk=8, block=32))
_RECURRENCE = _weighted(lambda x, dt, a_log, b, c, segs: ref.selective_recurrence(
    x[0], dt[0], b[0], c[0], a_log, segs[0], block=8)[None])


@pytest.mark.parametrize("case", sorted(BOUNDARIES))
def test_the_selective_scan_is_the_recurrence(case):
    """Value and the scan's own VJP against ``jax.grad`` of the recurrence;
    chunks of 8 in blocks of 32. float32 in another order: 1e-5 is ten times
    the largest reading of the values; a gradient is a sum over every slot of
    terms of both signs, so 1e-4."""
    seg = BOUNDARIES[case]
    inputs = _scan_inputs(np.random.default_rng(len(seg) + len(case)), seg)
    weight = np.random.default_rng(9).normal(size=inputs[0].shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = _RECURRENCE(*inputs, weight)
    (_, got), grads = _PROGRAM(*inputs, weight)
    assert rel(got, want) < 1e-5
    for name, a, w in zip(("x", "dt", "A_log", "B", "C"), grads, want_grads):
        assert np.isfinite(np.asarray(a)).all() and rel(a, w) < 1e-4, name


@pytest.mark.parametrize("chunk,block", [(4, 8), (5, 10), (16, 16), (16, 64), (64, 512)])
def test_chunk_and_block_are_no_part_of_the_result(chunk, block):
    x, dt, a_log, b, c, segs = _scan_inputs(np.random.default_rng(11), BOUNDARIES["mid-chunk"])
    want = ref.selective_scan_of(x[0], dt[0], b[0], c[0], a_log, segs[0])
    got = selective_scan(x, dt, -jnp.exp(a_log), b, c, segs, chunk=chunk, block=block)
    assert rel(got[0], want) < 1e-5 and scan_kind(24, 4, 64) == "xla"


def test_a_fast_decay_overflows_nothing():
    """Every decay is the exponential of a non-positive number: a channel
    that forgets within a slot (dt A = -400 a slot) gives finite values and
    gradients."""
    x, dt, a_log, b, c, segs = _scan_inputs(np.random.default_rng(12), _runs(40, 24))
    dt = np.full_like(dt, 25.0)

    def total(x, dt):
        return jnp.sum(selective_scan(x, dt, -jnp.exp(a_log), b, c, segs, chunk=8, block=32) ** 2)

    value, grads = jax.value_and_grad(total, argnums=(0, 1))(x, dt)
    assert np.isfinite(float(value)) and all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert rel(selective_scan(x, dt, -jnp.exp(a_log), b, c, segs, chunk=8, block=32)[0],
               ref.selective_scan_of(x[0], dt[0], b[0], c[0], a_log, segs[0])) < 1e-5


@pytest.mark.parametrize("build,passes", [("float32", True), ("bfloat16", False)])
def test_the_control_build_fails_selscan_err_and_the_sound_build_passes(build, passes):
    """The benchmark's control: state, Delta and the decay in bfloat16 on the
    same bfloat16 c is another result (a state that is rounded to 8 bits of
    mantissa at every slot of a history hundreds of slots long)."""
    rng = np.random.default_rng(3)
    x, dt, a_log, b, c, segs = _scan_inputs(rng, _runs(50, 14, 64), channels=64, state=16)
    dt = (0.05 * dt).astype(np.float32)  # a state that remembers a few hundred slots
    low = jnp.asarray(x, jnp.bfloat16)
    got = selective_scan(low, dt, -jnp.exp(a_log), b, c, segs, chunk=16, block=64,
                         state_dtype=jnp.dtype(build), gate_dtype=jnp.dtype(build))
    err = rel(got[0], ref.selective_scan_of(low[0], dt[0], b[0], c[0], a_log, segs[0]))
    assert (err < 1e-4) == passes and (passes or err > 3e-3), err


def test_the_mixer_is_the_references_on_histories_shorter_than_its_taps():
    rng = np.random.default_rng(5)
    d, inner, state, rank = 12, 16, 5, 3
    w = lambda *shape: (0.4 * rng.normal(size=shape)).astype(np.float32)  # noqa: E731
    p = {"w_in": w(d, 2 * inner), "conv_w": w(4, inner), "conv_b": w(inner),
         "w_x": w(inner, rank + 2 * state), "w_dt": w(rank, inner), "dt_bias": w(inner),
         "A_log": np.log(rng.uniform(1, 16, (inner, state))).astype(np.float32),
         "D": 1 + w(inner), "w_out": w(inner, d)}
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    seg = np.stack([_runs(1, 2, 3, 2, 1, 15), _runs(3, 21)]).astype(np.int32)
    got, ran = mamba1(p, x, seg, state=state, dt_rank=rank, chunk=8)
    cfg = {"mamba_d_state": state, "mamba_dt_rank": rank}
    with jax.default_matmul_precision("highest"):
        want = [ref.mamba1_mixer(p, jnp.asarray(x[b]), jnp.asarray(seg[b]), cfg) for b in range(2)]
    assert rel(got, np.stack([y for y, _ in want])) < 1e-5
    assert rel(ran["m"], np.stack([m for _, m in want])) < 1e-5
    assert set(ran) == {"c", "dt", "B", "C", "y", "m"}


# -- the window in the blockwise loop ------------------------------------------
def _dense(q, k, v, seg, window):
    """Masked softmax attention with full score matrices, grouped heads."""
    h, hkv = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    idx = jnp.arange(q.shape[2])
    keep = (idx[:, None] >= idx[None, :]) & (seg[:, :, None] == seg[:, None, :])
    if window:
        keep = keep & (idx[:, None] - idx[None, :] < window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


@pytest.mark.parametrize("length,block,window", [
    (96, 16, 16), (96, 16, 24), (96, 32, 8), (96, 16, 2), (96, 16, 200), (80, 32, 33), (64, 64, 16)])
def test_the_windowed_loop_is_the_masked_softmax(length, block, window):
    """Forward and every cotangent of the blockwise loop under a window
    against full score matrices: windows of one block, of no whole number of
    blocks, narrower than a block, of two slots, wider than the row; rows of no
    whole number of blocks; packed histories longer and shorter than the
    window; values wider than the keys."""
    rng = np.random.default_rng(length + block + window)
    q = jnp.asarray(rng.normal(size=(2, 4, length, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, length, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, length, 16)), jnp.float32)
    seg = np.ones((2, length), np.int32)
    seg[0, 5:50], seg[0, 50:58], seg[0, 58:] = 2, 3, 0
    weight = jnp.asarray(rng.normal(size=(2, 4, length, 16)), jnp.float32)

    def total(fn):
        return lambda q, k, v: jnp.sum(weight * fn(q, k, v))

    loop = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_k=block, segment_ids=jnp.asarray(seg), window=window)
    dense = lambda q, k, v: _dense(q, k, v, jnp.asarray(seg), window)  # noqa: E731
    assert rel(loop(q, k, v), dense(q, k, v)) < 1e-5
    for got, want in zip(jax.grad(total(loop), (0, 1, 2))(q, k, v),
                         jax.grad(total(dense), (0, 1, 2))(q, k, v)):
        assert rel(got, want) < 1e-5
    assert rel(loop(q, k, v), _dense(q, k, v, jnp.asarray(seg), 0)) > 1e-3 or window >= length


def test_tiles_wholly_outside_the_window_leave_the_loop():
    """From the static pair list: at 8,192 slots in tiles of 512 under a
    window of 512 a query block keeps its own tile and the one before it, 31
    of the 136 causal tiles; no window, nothing skipped; and a window may not
    come without the causal mask."""
    assert tiles_skipped_by_window(8192, 512, 512) == 136 - 31
    assert tiles_skipped_by_window(8192, 512, 513) == 136 - 31
    assert tiles_skipped_by_window(8192, 512, 514) == 136 - 45
    assert tiles_skipped_by_window(8192, 512, 0) == 0
    assert tiles_skipped_by_window(64, 16, 16) == 10 - 7
    q = jnp.ones((1, 2, 32, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)


# -- packing ----------------------------------------------------------------
@pytest.fixture(scope="module")
def hidden_of(cfg, params):
    """tokens, seg [1, L] -> the residual stream after the last layer."""
    program = jax.jit(lambda tokens, seg: bb.hidden_states(cfg, params, tokens, seg)[0])
    return lambda tokens, seg: np.asarray(program(np.asarray(tokens), np.asarray(seg)))


def test_a_packed_row_is_its_histories_one_by_one(hidden_of, batch):
    """No tap, state, window or key crosses a boundary, and nothing but the
    mixers knows where a history lies in its row: the hidden states of a
    history inside a packed row are those of the history alone in a row."""
    rows, segs = batch
    packed = hidden_of(rows[:1, :-1], segs[:1, :-1])
    for sid, (lo, hi) in HISTORIES.items():
        n = hi - lo
        alone, alone_seg = np.zeros((1, L), np.int32), np.zeros((1, L), np.int32)
        alone[0, :n], alone_seg[0, :n] = rows[0, lo:hi], 1
        single = hidden_of(alone, alone_seg)
        assert rel(packed[0, lo:hi], single[0, :n]) < 1e-5, sid


def test_a_neighbour_let_in_changes_the_row(hidden_of, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, merged = hidden_of(tokens, seg), hidden_of(tokens, np.minimum(seg, 1))
    assert rel(merged[0, 12:53], packed[0, 12:53]) > 1e-2


# -- refusals, counters, the two copies ----------------------------------------
@pytest.mark.parametrize("bad,says", [
    ({"layer_types": ["sliding_attention", "gmu", "mamba1", "full_attention", "gmu", "cross_attention"]},
     "layer 1 is a gmu layer and no mamba1 layer below it"),
    ({"layer_types": ["mamba1", "cross_attention", "mamba1", "full_attention", "gmu", "cross_attention"]},
     "layer 1 is a cross_attention layer and no full_attention layer below it"),
    ({"layer_types": ["mamba1", "sliding_attention", "mamba1", "sliding_attention", "gmu", "cross_attention"]},
     "no full_attention layer below it"),
    ({"backbone": {**TINY["backbone"], "differential": False}}, "differential"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"mamba_dt_rank": 0}, "mamba_dt_rank"),
    ({"mamba_proj_bias": True}, "none on its projections"),
    ({"attention_bias": True}, "no bias"),
    ({"num_key_value_heads": 1, "num_attention_heads": 8}, "pairs up"),
    ({"layer_types": TINY["layer_types"] * 2, "num_hidden_layers": 12}, "ONE period"),
    ({"layer_types": ["mamba1", "sliding_attention", "mamba1", "full_attention", "gmu", "window"]},
     "unknown here"),
])
def test_configurations_the_backbone_cannot_run_are_refused_with_a_message(bad, says):
    with pytest.raises(ValueError, match=says):
        bb.BackboneConfig.from_dict({**TINY, **bad})


def test_the_job_counts_its_mixers_its_scan_and_the_tiles_the_window_skipped(cfg):
    assert bb.mechanisms(cfg, L) == {
        "selective_scan": "xla", "conv": "xla", "attn_tiles_skipped_by_window": 10 - 7}
    # a backbone without a sliding layer counts no such tiles, one without Mamba-1 no such scan
    for name in ("granite4h-tiny", "lfm2-tiny", "qwen3next-tiny", "joyai-flash-tiny"):
        found = bb.mechanisms(bb.BackboneConfig.load(name), L)
        assert "attn_tiles_skipped_by_window" not in found and "selective_scan" not in found
    shipped = bb.BackboneConfig.load("phi4-mini-flash-vp8")
    assert bb._MIXERS["swa"].forms(shipped, 8192) == {"attn_tiles_skipped_by_window": 105}


def test_pio_train_and_predict_with_the_backbone_configuration():
    """The normal path: ``SeqRecAlgorithm.train`` on packed rows, the job's
    counters and tags, a prediction from the trained model."""
    rng = np.random.default_rng(4)
    pieces = [rng.integers(0, VOCAB, size=n).astype(np.int32) for n in (40, 9, 30, 64, 12, 50, 21)]
    prep = sequencerec.SeqPreparator(sequencerec.SeqPreparatorParams(seq_len=L))
    rows, segs = prep.pack(pieces)
    from predictionio_tpu.storage import BiMap

    items = BiMap.string_int([f"i{n}" for n in range(VOCAB)])
    data = sequencerec.PreparedData(item_map=items, windows=rows, segments=segs,
                                    user_recent={"u": [1, 2, 3]}, seq_len=L)
    algo = sequencerec.SeqRecAlgorithm(sequencerec.SeqRecAlgorithmParams(
        backbone="phi4-mini-flash-tiny", steps=12, batch_size=2, learning_rate=3e-3))
    model = algo.train(None, data)
    assert model.losses[-1] < model.losses[0] and np.isfinite(model.losses).all()
    assert model.stats["mixers"] == {"cross": 1, "gmu": 1, "gqa": 1, "mamba1": 2, "swa": 1}
    assert model.stats["selective_scan"] == "xla" and model.stats["attn_tiles_skipped_by_window"] == 3
    assert not [name for name in model.stats if "expert" in name or "router" in name]
    answer = algo.predict(model, sequencerec.Query(user="u", num=5))
    assert len(answer.item_scores) == 5


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "phi4flash_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_phi4flash.py")) as f:
        assert f.read() == ours


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import" in text and "predictionio_tpu" not in text.split('"""', 2)[2]
    assert "ops." not in text.split('"""', 2)[2] and "models." not in text.split('"""', 2)[2]


def test_the_shipped_configuration_has_the_published_widths():
    """Every width of ``conf/backbones/phi4-mini-flash-vp8.json`` is the
    catalog's; depth, pattern and vocabulary are the cut; the parameters add
    up to the issue's 697 M."""
    cfg = bb.BackboneConfig.load("phi4-mini-flash-vp8")
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.sliding_window) == (2560, 40, 20, 64, 10240, 512)
    assert (cfg.mamba_expand, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv) == (2, 16, 160, 4)
    assert cfg.kinds == ("mamba1", "swa", "mamba1", "full", "gmu", "cross")
    assert (cfg.norm, cfg.layer_norm_eps, cfg.positions, cfg.ffn, cfg.differential,
            cfg.layer_index_offset, cfg.tie_word_embeddings) == (
        "layer", 1e-5, "none", "swiglu", True, 14, True)
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, 25008, 8192, 0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))  # noqa: E731
    per = shapes["periods"]
    assert count(per["mamba1"]) == 2 * 41_241_600 and count(per["gmu"]) == 26_214_400
    assert count(per["full"]) == count(per["swa"]) == 19_660_800 + 384
    assert count(per["cross"]) == 13_107_200 + 384 and count(per["ffn"]) == 6 * 78_643_200
    assert count(shapes) == 697_073_792
    assert dataclasses.replace(cfg, sliding_window=0) != cfg
    assert bb.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
