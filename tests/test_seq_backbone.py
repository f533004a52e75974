"""The sequence backbone against its plain reference, at a small size on
the CPU: hidden 64, 8 routed experts of which 3 are held, two periods of
3 gated-DeltaNet layers + 1 gated-attention layer, rows of 64 slots.

The program computes in float32 here (``compute_dtype`` of the tiny
configuration), so the distances are those of the mathematics: summation
order and nothing else.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.ops import deltanet, moe
from predictionio_tpu.ops.attention import flash_attention, ring_attention
from predictionio_tpu.testing import qwen3_next_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "conf", "backbones", "qwen3next-tiny.json")) as f:
    TINY = json.load(f)
VOCAB, L = 50, 64


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


@pytest.fixture(scope="module")
def cfg():
    return bb.BackboneConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with every norm scale and gate parameter moved off
    its starting value, so that none of them drops out of a comparison."""
    drawn = bb.init_params(cfg, VOCAB, L, 0)
    leaves, treedef = jax.tree_util.tree_flatten(drawn)
    rng = np.random.default_rng(1)
    leaves = [leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32)
              if leaf.shape[-1] in (64, 16) else leaf for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of L + 1 slots: three histories and padding in the
    first, one history that fills the second."""
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    segs[0, :20], segs[0, 20:57], segs[0, 57:62] = 1, 2, 3
    segs[1, :] = 1
    return rows, segs


@pytest.fixture(scope="module")
def both(cfg, params, batch):
    """Program and reference on the same weights and batch."""
    rows, segs = batch
    program = jax.jit(jax.value_and_grad(
        lambda mp, r, s: bb.loss_fn(cfg, mp, r, s), has_aux=True))
    (loss, (hidden, counters, _)), grads = program(params, rows, segs)
    slots = [np.arange(0, 60, 7), np.arange(3, 64, 5)]
    want_loss, want_grads, want_logits = ref.loss_and_grads(
        bb.layers_of(params, cfg), rows, segs, TINY, sample=slots)
    logits = [bb.logits_of(cfg, params, hidden[b][at]) for b, at in enumerate(slots)]
    return {
        "loss": float(loss), "grads": bb.layers_of(grads, cfg), "logits": logits,
        "counters": counters, "want_loss": want_loss, "want_grads": want_grads,
        "want_logits": want_logits,
    }


def test_loss_matches_reference(both):
    assert abs(both["loss"] - both["want_loss"]) / both["want_loss"] < 1e-6


def test_logits_match_reference(both):
    # eight layers of float32 sums in another order
    for got, want in zip(both["logits"], both["want_logits"]):
        assert rel(got, want) < 1e-4


GROUPS = {
    "deltanet": lambda layer: layer.get("linear"),
    "attention": lambda layer: layer.get("full"),
    "router": lambda layer: layer["moe"]["router"],
    "experts": lambda layer: layer["moe"]["experts"],
    "shared": lambda layer: (layer["moe"]["shared"], layer["moe"]["shared_gate"]),
    "norms": lambda layer: (layer["input_norm"], layer["post_norm"]),
}


@pytest.mark.parametrize("group", sorted(GROUPS) + ["embed", "head", "final_norm"])
def test_gradient_group_matches_reference(both, group):
    got, want = both["grads"], both["want_grads"]
    if group in GROUPS:
        pick = GROUPS[group]
        got = [pick(layer) for layer in got["layers"]]
        want = [pick(layer) for layer in want["layers"]]
    else:
        got, want = got[group], want[group]
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert rel(a, b) < 5e-4, group


def test_no_assignment_dropped_on_the_normal_path(both):
    assert int(np.sum(both["counters"]["dropped"])) == 0
    assert np.asarray(both["counters"]["expert_tokens"]).shape == (2, 4, 3)


# -- the delta rule ---------------------------------------------------------
def _rule_inputs(length, heads=3, dk=8, dv=8, seed=0, starts=None):
    """One row; ``starts``: the first slot of every history but the first
    (by default a third of the way and five slots from the end)."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(1, length, heads, dk)).astype(np.float32)
    k = rng.normal(size=(1, length, heads, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(1, length, heads, dv)).astype(np.float32)
    g = -rng.uniform(0.01, 2.0, size=(1, length, heads)).astype(np.float32)
    beta = rng.uniform(0.1, 1.0, size=(1, length, heads)).astype(np.float32)
    seg = np.ones((1, length), np.int32)
    for at in (length // 3, length - 5) if starts is None else starts:
        seg[0, at:] += 1
    return q, k, v, g, beta, seg


def _recurrence(q, k, v, g, beta, seg):
    start = np.concatenate([[True], seg[0, 1:] != seg[0, :-1]])
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(q[0], k[0], v[0], jnp.exp(g[0]), beta[0], jnp.asarray(start))[None]


def _primitives_of(fn, *args):
    """(name, name stack, parameters) of every primitive of ``fn``'s
    jaxpr, nested ones too; a stack runs from the outermost jaxpr down."""
    def eqns(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            yield eqn.primitive.name, stack, eqn.params
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub, stack)

    return list(eqns(jax.make_jaxpr(fn)(*args).jaxpr, ""))


# rows of heads of 8 are the toy preset's tiles (the scan walk whatever is
# asked); rows of heads of 128 in chunks of 64 are the tiles the kernel takes:
# there the same cases run through the scan and through the kernel's interpreter
_SMALL = [dict(length=n, chunk=c) for n, c in [(37, 16), (64, 16), (50, 64), (96, 32), (19, 8)]]
_SMALL_BACKWARD = [dict(length=n, chunk=c, seed=3) for n, c in [(37, 16), (50, 64), (48, 16)]]
_TILES = dict(heads=2, dk=128, dv=128, chunk=64)
_WIDE = {
    "first_slot": dict(length=192, starts=(64, 128), **_TILES),  # a history starts a chunk
    "last_slot": dict(length=192, starts=(63, 127), **_TILES),  # and ends one with its first slot
    "three_chunks": dict(length=256, starts=(70, 250), **_TILES),  # slots 70-249: chunks 1, 2, 3
    "ragged": dict(length=150, starts=(50, 145), **_TILES),  # not a multiple of the chunk
}
_RULE_CASES = (
    [pytest.param("forward", "scan", c, id=f"forward-scan-{c['length']}-{c['chunk']}") for c in _SMALL]
    + [pytest.param("backward", "scan", c, id=f"backward-scan-{c['length']}-{c['chunk']}")
       for c in _SMALL_BACKWARD]
    + [pytest.param(way, walk, c, id=f"{way}-{walk}-{name}")
       for way in ("forward", "backward") for walk in ("scan", "kernel") for name, c in _WIDE.items()])


@pytest.mark.parametrize("way,walk,case", _RULE_CASES)
def test_chunked_rule_is_the_recurrence(way, walk, case):
    case = dict(case)
    chunk = case.pop("chunk")
    q, k, v, g, beta, seg = _rule_inputs(**case)
    rule = lambda *a: deltanet.gated_delta_rule(  # noqa: E731
        *a, seg, chunk=chunk, interpret=walk == "kernel")
    kernels = [name for name, _, _ in _primitives_of(rule, q, k, v, g, beta) if name == "pallas_call"]
    assert len(kernels) == (1 if walk == "kernel" else 0)
    if way == "forward":
        assert rel(rule(q, k, v, g, beta), _recurrence(q, k, v, g, beta, seg)) < 2e-5
        return
    weight = np.random.default_rng(4).normal(size=v.shape).astype(np.float32)
    chunked = jax.grad(lambda *a: (rule(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    plain = jax.grad(lambda *a: (_recurrence(*a, seg) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for a, b in zip(chunked, plain):
        assert rel(a, b) < 1e-4


@pytest.mark.parametrize("walk", ["scan", "kernel"])
def test_a_bfloat16_state_stays_apart_from_a_float32_one(walk):
    """The benchmark's control build is this rule with state and gates in
    bfloat16, and has to read at least five times further from the
    recurrence than the sound build (PERF.md section 2: 0.0126 against
    0.0024 on the chip, limit 0.006); either walk honours the two dtypes."""
    q, k, v, g, beta, seg = _rule_inputs(512, starts=(200, 230), seed=6, **{
        key: _TILES[key] for key in ("heads", "dk", "dv")})
    want = _recurrence(q, k, v, g, beta, seg)

    def distance(dtype):
        got = deltanet.gated_delta_rule(
            q, k, v, g, beta, seg, chunk=64, compute_dtype=jnp.bfloat16, state_dtype=dtype,
            gate_dtype=dtype, interpret=walk == "kernel")
        return rel(got, want)

    sound, control = distance(jnp.float32), distance(jnp.bfloat16)
    assert sound < 0.003 and control > 5 * sound, (sound, control)


def test_the_walk_is_made_three_times_forward_and_once_backward(cfg, params):
    """Through one gated-DeltaNet layer as the step differentiates it
    (``_layer_fn``: the layer is recomputed, and inside it each row): the
    layer's forward pass, its recomputation, the row's recomputation, and
    one walk in reverse. Nothing inside the rule is made again."""
    layer = bb._layer_fn(cfg, "linear", None, "auto")
    per = jax.tree_util.tree_map(lambda a: a[0], params["periods"])
    pick = lambda tree: jax.tree_util.tree_map(lambda a: a[0], tree)  # noqa: E731
    seg = jnp.ones((2, L), jnp.int32)
    x = jnp.ones((2, L, cfg.hidden_size), jnp.float32)

    def loss(x, mixer):
        return layer(x, seg, bb.positions_of(seg), pick(per["norm_in"]), mixer,
                     pick(per["norm_post"]), pick(per["ffn"]))[0].sum()

    walks = [params_["reverse"] for name, stack, params_ in _primitives_of(
        jax.grad(loss, argnums=(0, 1)), x, pick(per["linear"]))
        if name == "scan" and "seq.deltanet.scan.walk" in stack]
    assert walks.count(False) == 3 and walks.count(True) == 1

    q, k, v, g, beta, seg = _rule_inputs(37)
    inside = _primitives_of(jax.grad(lambda *a: deltanet.gated_delta_rule(*a, seg, chunk=16).sum(),
                                argnums=(0, 1, 2, 3, 4)), q, k, v, g, beta)
    assert not [name for name, _, _ in inside if name in ("checkpoint", "remat", "remat2")]
    scans = [params_["reverse"] for name, _, params_ in inside if name == "scan"]
    assert scans == [False, True]


def _tri_inv_in_place(a):
    """``ops/deltanet._tri_inv_impl`` as it stood before PR 35, frozen: the
    rows of a diagonal block and the block rows below the diagonal written
    into the whole array one at a time. What the shipped form must equal."""
    hi = jax.lax.Precision.HIGHEST
    c = a.shape[-1]
    b = 16 if c % 16 == 0 else c
    nb = c // b
    lead = a.shape[:-2]
    blocks = a.reshape(lead + (nb, b, nb, b))
    diag = jnp.stack([blocks[..., n, :, n, :] for n in range(nb)], axis=-3)
    t = jnp.broadcast_to(jnp.eye(b, dtype=a.dtype), diag.shape)
    for i in range(1, b):
        row = -jnp.einsum("...j,...jk->...k", diag[..., i, :], t, precision=hi)
        t = t.at[..., i, :].add(row)
    if nb == 1:
        return t.reshape(a.shape)
    eye_nb = jnp.eye(nb, dtype=a.dtype)
    full = jnp.einsum("...nij,nm->...nimj", t, eye_nb).reshape(a.shape)
    off = (blocks * (1.0 - eye_nb)[:, None, :, None]).reshape(a.shape)
    for n in range(1, nb):
        rows = slice(n * b, (n + 1) * b)
        below = jnp.einsum("...ij,...jk->...ik", off[..., rows, :], full, precision=hi)
        full = full.at[..., rows, :].add(
            -jnp.einsum("...ij,...jk->...ik", t[..., n, :, :], below, precision=hi))
    return full


# (leading shape, c): today's; the walk's [N, B, H] lead, four blocks; one
# block; no multiple of 16 (one block of 24); eight blocks
_TRI_CASES = [((3,), 64), ((4, 1, 8), 64), ((2,), 16), ((2,), 24), ((2,), 128)]


@pytest.mark.parametrize("lead,c", _TRI_CASES, ids=[f"{'x'.join(map(str, s))}-{c}" for s, c in _TRI_CASES])
def test_tri_inv_inverts_and_differentiates(lead, c):
    rng = np.random.default_rng(5)
    a = np.tril(rng.normal(size=lead + (c, c)), -1).astype(np.float32) * 0.3
    t = deltanet.tri_inv(jnp.asarray(a))
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    assert rel(t, want) < 1e-5
    # the same arithmetic in the same order as the in-place form it replaced
    assert np.array_equal(np.asarray(t), np.asarray(_tri_inv_in_place(jnp.asarray(a))))
    weight = rng.normal(size=a.shape).astype(np.float32)
    got = jax.grad(lambda x: (deltanet.tri_inv(x) * weight).sum())(jnp.asarray(a))
    plain = jax.grad(lambda x: (jnp.linalg.inv(jnp.eye(c) + x) * weight).sum())(jnp.asarray(a))
    assert rel(got, np.tril(np.asarray(plain), -1)) < 1e-4


@pytest.mark.parametrize("chunk,case", [(16, dict(length=50)), (64, dict(length=150, heads=2, dk=128, dv=128))],
                         ids=["one_block", "four_blocks"])
def test_the_rule_is_what_it_was_with_the_in_place_inverse(chunk, case, monkeypatch):
    """Output and gradients of the whole rule against the same rule with the
    frozen in-place inverse."""
    q, k, v, g, beta, seg = _rule_inputs(**case)
    weight = np.random.default_rng(6).normal(size=v.shape).astype(np.float32)

    def run():
        deltanet.gated_delta_rule.clear_cache()
        rule = lambda *a: deltanet.gated_delta_rule(*a, seg, chunk=chunk)  # noqa: E731
        return rule(q, k, v, g, beta), jax.grad(
            lambda *a: (rule(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    o, grads = run()
    monkeypatch.setattr(deltanet, "_tri_inv_impl", _tri_inv_in_place)
    try:
        o_was, grads_was = run()
    finally:
        deltanet.gated_delta_rule.clear_cache()
    assert rel(o, o_was) < 1e-6
    for got, was in zip(grads, grads_was):
        assert rel(got, was) < 1e-5


def test_the_preparation_updates_no_array_in_place():
    """At the cell's widths (heads of 128, chunks of 64) the preparation,
    forward and backward, holds no scatter and no ``dynamic_update_slice``:
    on the chip the blocks' minor dimension of 16 pads to a lane tile of
    128, and a row written in place copied the whole padded array, fifteen
    times a call (PERF.md section 6, PR 35)."""
    q, k, v, g, beta, seg = _rule_inputs(128, heads=2, dk=128, dv=128)

    def prepare(q, k, v, g, beta):
        return deltanet._prepare(q, k, v, g, beta, seg, 64, jnp.float32, jnp.float32)

    def backward(*a):
        return jax.grad(lambda *b: sum(x.astype(jnp.float32).sum() for x in prepare(*b)),
                        argnums=(0, 1, 2, 3, 4))(*a)

    for fn in (prepare, backward):
        names = {name for name, _, _ in _primitives_of(fn, q, k, v, g, beta)}
        assert not names & {"scatter", "scatter-add", "dynamic_update_slice"}, names
    stacks = [stack for _, stack, _ in _primitives_of(
        lambda *a: deltanet.gated_delta_rule(*a, seg, chunk=64), q, k, v, g, beta)]
    for scope in ("seq.deltanet.scan.prep.tri_inv", "seq.deltanet.scan.prep.layout"):
        assert any(scope in stack for stack in stacks), scope


# -- packing ----------------------------------------------------------------
def test_a_packed_row_is_its_histories_one_by_one(cfg, params, batch):
    """Convolution, state, positions and attention do not cross a
    boundary: the hidden states of a history inside a packed row are those
    of the history alone in a row."""
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, *_ = bb.hidden_states(cfg, params, tokens, seg)
    for sid in (1, 2, 3):
        at = np.flatnonzero(seg[0] == sid)
        alone = np.zeros((1, L), np.int32)
        alone_seg = np.zeros((1, L), np.int32)
        alone[0, :len(at)], alone_seg[0, :len(at)] = tokens[0, at], 1
        single, *_ = bb.hidden_states(cfg, params, alone, alone_seg)
        assert rel(packed[0, at], single[0, :len(at)]) < 1e-5, sid


def test_a_neighbour_let_in_changes_the_row(cfg, params, batch):
    rows, segs = batch
    tokens, seg = rows[:1, :-1], segs[:1, :-1]
    packed, *_ = bb.hidden_states(cfg, params, tokens, seg)
    merged, *_ = bb.hidden_states(cfg, params, tokens, np.minimum(seg, 1))
    assert rel(merged[0, 20:57], packed[0, 20:57]) > 1e-3


def test_first_fit_packing():
    from predictionio_tpu.models.sequencerec import pack_first_fit

    pieces = [np.arange(n, dtype=np.int32) + 1 for n in (5, 9, 4, 6, 2, 10)]
    rows, segs = pack_first_fit(pieces, 10)
    # 5 and 4 share row 0 (9 does not fit beside 5), 9 opens row 1, 6 and 2
    # open and fill row 2... every piece whole, in one row, under one id
    assert rows.shape == segs.shape and rows.shape[1] == 10
    found = []
    for r, s in zip(rows, segs):
        for sid in range(1, s.max() + 1):
            found.append(r[s == sid].tolist())
    assert sorted(map(tuple, found)) == sorted(tuple(p.tolist()) for p in pieces)
    assert segs[0].tolist() == [1] * 5 + [2] * 4 + [0]
    assert (rows[segs == 0] == 0).all()


def test_batch_order_names_the_batches_the_trainer_takes():
    """Seeded epochs without replacement, a function of its arguments: the
    input thread takes exactly those rows, so whoever holds the rows can
    name a job's last batch (the benchmark's check does)."""
    from predictionio_tpu.models import sequencerec

    takes = list(sequencerec.batch_order(5, 2, 6, seed=3))
    assert [t.tolist() for t in takes] == [
        t.tolist() for t in sequencerec.batch_order(5, 2, 6, seed=3)]
    assert all(len(t) == 2 for t in takes)
    assert sorted(np.concatenate(takes)[:5].tolist()) == [0, 1, 2, 3, 4]  # the first epoch
    pd = sequencerec.PreparedData(
        item_map=None, windows=np.arange(20, dtype=np.int32).reshape(5, 4),
        segments=np.ones((5, 4), np.int32), user_recent={}, seq_len=3)
    batches = sequencerec._Batches(pd, 2, 6, 3)
    try:
        for take in takes:
            rows, segs = batches.next()
            assert (np.asarray(rows) == pd.windows[take]).all() and segs.shape == (2, 4)
    finally:
        batches.close()


# -- experts ----------------------------------------------------------------
def _moe_params(rng, d=16, e=8, f=8, first=0, count=8):
    full = {
        "router": rng.normal(size=(d, e)).astype(np.float32),
        "shared_gate": rng.normal(size=(d,)).astype(np.float32),
        "shared": {"wg": rng.normal(size=(d, f)).astype(np.float32) * 0.3,
                   "wu": rng.normal(size=(d, f)).astype(np.float32) * 0.3,
                   "wd": rng.normal(size=(f, d)).astype(np.float32) * 0.3},
        "experts": {"wg": rng.normal(size=(e, d, f)).astype(np.float32) * 0.3,
                    "wu": rng.normal(size=(e, d, f)).astype(np.float32) * 0.3,
                    "wd": rng.normal(size=(e, f, d)).astype(np.float32) * 0.3},
    }
    return full


@pytest.fixture
def untraced_passes():
    """A pass and its pull-back are jitted functions, traced once for a
    shape: a test that plants a fault inside one forgets the sound traces
    first, and its own afterwards."""
    def forget():
        moe._one_pass.clear_cache()
        moe._pull_pass.clear_cache()

    forget()
    yield forget
    forget()


def _share(full, first, count):
    held = jax.tree_util.tree_map(lambda a: a[first:first + count], full["experts"])
    return {**full, "experts": held}


def _ref_cfg(first, count, top_k=3):
    return {"experts_held": [first, count], "num_experts_per_tok": top_k, "norm_topk_prob": True}


def test_the_shares_add_up_to_the_uncut_layer():
    """What all shares give, the shared expert counted once, is what the
    uncut reference gives for the whole layer (model-configs guide, §4)."""
    rng = np.random.default_rng(6)
    full = _moe_params(rng)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_block(full, jnp.asarray(x), _ref_cfg(0, 8))
        shared_once = jax.nn.sigmoid(x @ full["shared_gate"])[:, None] * ref._swiglu(full["shared"], x)
    total = np.zeros_like(x)
    for first in range(0, 8, 2):
        y, counters = moe.expert_layer(_share(full, first, 2), x, first=first, top_k=3)
        total += np.asarray(y) - np.asarray(shared_once)
        assert int(counters["dropped"]) == 0
    assert rel(total + np.asarray(shared_once), whole) < 1e-5


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3), (6, 2)])
def test_expert_layer_matches_reference_share(first, count):
    rng = np.random.default_rng(7)
    full = _moe_params(rng)
    x = rng.normal(size=(33, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_block(_share(full, first, count), jnp.asarray(x), _ref_cfg(first, count))
    got, _ = moe.expert_layer(_share(full, first, count), x, first=first, top_k=3)
    assert rel(got, want) < 1e-5


def test_no_token_dropped_when_the_router_is_forced_onto_held_experts():
    """Every token sends all its choices here: eight times the mean load,
    more passes than one, and still every assignment is computed."""
    rng = np.random.default_rng(8)
    full = _moe_params(rng)
    full["router"][:] = 0.0
    x = np.abs(rng.normal(size=(64, 16))).astype(np.float32)
    full["router"][:, 2:5] = 5.0  # positive inputs: experts 2, 3, 4 always win
    share = _share(full, 2, 3)
    got, counters = moe.expert_layer(share, x, first=2, top_k=3, pass_rows=48)
    assert int(counters["expert_tokens"].sum()) == 64 * 3
    assert int(counters["passes"]) == 4 and int(counters["dropped"]) == 0
    assert float(counters["absent_weight"]) < 1e-6
    with jax.default_matmul_precision("highest"):
        want = ref.moe_block(share, jnp.asarray(x), _ref_cfg(2, 3))
    assert rel(got, want) < 1e-5
    # the gradient passes through every pass too
    g = jax.grad(lambda p: moe.expert_layer(p, x, first=2, top_k=3, pass_rows=48)[0].sum())(share)
    with jax.default_matmul_precision("highest"):
        w = jax.grad(lambda p: ref.moe_block(p, jnp.asarray(x), _ref_cfg(2, 3)).sum())(share)
    assert rel(g["experts"]["wd"], w["experts"]["wd"]) < 1e-4


def _forced(rng):
    """The input and share of the test above: every choice is a held one."""
    full = _moe_params(rng)
    full["router"][:] = 0.0
    x = np.abs(rng.normal(size=(64, 16))).astype(np.float32)
    full["router"][:, 2:5] = 5.0
    return _share(full, 2, 3), x


@pytest.mark.parametrize("leaf", ["x", "wg", "wu", "wd", "router", "shared"])
@pytest.mark.parametrize("pass_rows,passes", [(0, 1), (48, 4), (100, 2)],
                         ids=["one_pass", "four_passes", "a_short_last_pass"])
def test_gradient_through_the_passes_matches_the_reference(pass_rows, passes, leaf):
    """The passes' sum has a VJP of its own: the first pass in line, the
    overflow passes behind one ``cond``. In the usual state (one pass
    needed), with four passes needed and with a last pass that is not full,
    every gradient is the reference's, which knows no passes."""
    rng = np.random.default_rng(8)
    if passes == 1:  # routed as it falls: about 72 held assignments, 144 rows a pass
        share, x = _share(_moe_params(rng), 2, 3), rng.normal(size=(64, 16)).astype(np.float32)
    else:
        share, x = _forced(rng)
        share["router"][:, 2:5] += 0.3 * rng.normal(size=(16, 3)).astype(np.float32)
    seen = rng.normal(size=x.shape).astype(np.float32)

    def loss(layer):
        return lambda p, x: (layer(p, x) * seen).sum()

    def program(p, x):
        return moe.expert_layer(p, x, first=2, top_k=3, pass_rows=pass_rows)

    counters = program(share, x)[1]
    assert int(counters["passes"]) == passes and int(counters["dropped"]) == 0
    got = jax.grad(loss(lambda p, x: program(p, x)[0]), argnums=(0, 1))(share, jnp.asarray(x))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(lambda p, x: ref.moe_block(p, x, _ref_cfg(2, 3))), argnums=(0, 1))(
            share, jnp.asarray(x))
    pick = {"x": lambda g: g[1], "router": lambda g: g[0]["router"],
            "shared": lambda g: jax.tree_util.tree_leaves((g[0]["shared"], g[0]["shared_gate"]))}
    of = pick.get(leaf, lambda g: g[0]["experts"][leaf])
    for a, b in zip(jax.tree_util.tree_leaves(of(got)), jax.tree_util.tree_leaves(of(want))):
        assert rel(a, b) < 1e-4


def test_the_usual_step_carries_no_cotangent_through_a_loop():
    """The mechanism, read from the gradient's jaxpr: the first pass lies
    in line (grouped products at the top level), and every ``scan`` lies
    inside a ``cond`` on the held assignments' count; where one pass covers
    all of a step's assignments there is no loop and no ``cond`` at all."""
    rng = np.random.default_rng(8)
    share, x = _share(_moe_params(rng), 2, 3), rng.normal(size=(64, 16)).astype(np.float32)

    def outline(jaxpr, inside=()):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("scan", "while", "ragged_dot_general"):
                yield name, inside
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from outline(sub, inside + ((name,) if name in ("cond", "scan", "while") else ()))

    def grad_of(pass_rows):
        return jax.make_jaxpr(jax.grad(lambda p, x: moe.expert_layer(
            p, x, first=2, top_k=3, pass_rows=pass_rows)[0].sum(), argnums=(0, 1)))(share, x).jaxpr

    found = list(outline(grad_of(48)))
    loops = [inside for name, inside in found if name != "ragged_dot_general"]
    assert loops and all(inside[:1] == ("cond",) for inside in loops)
    # forward 3 + recomputed 3 + backward 6 in line, as many again inside the overflow branch
    products = [inside for name, inside in found if name == "ragged_dot_general"]
    assert sum(inside == () for inside in products) == 12 and len(products) == 24
    assert all(inside == () for _, inside in outline(grad_of(192)))  # one pass covers all


@pytest.mark.parametrize("fault", ["a_pass_too_few", "groups_cut_short"])
def test_dropped_counts_what_the_passes_did_not_combine(monkeypatch, untraced_passes, fault):
    """``dropped`` is counted from the rows the passes' own masks let
    through, so it reads more than 0 when the loop stops a pass early or a
    pass is told too little of its groups; the sound layer reads 0 on the
    same input (the test above)."""
    rng = np.random.default_rng(8)
    full = _moe_params(rng)
    full["router"][:] = 0.0
    x = np.abs(rng.normal(size=(64, 16))).astype(np.float32)
    full["router"][:, 2:5] = 5.0
    share = _share(full, 2, 3)
    if fault == "a_pass_too_few":
        monkeypatch.setattr(moe, "_passes_for", lambda all_rows, rows: -(-all_rows // rows) - 1)
        lost = 48
    else:
        sound = moe._held_pass
        monkeypatch.setattr(
            moe, "_held_pass",
            lambda x, experts, take, sizes, *rest: sound(
                x, experts, take, sizes.at[-1].add(-1), *rest))
        lost = 4  # one row in each of the four passes
    _, counters = moe.expert_layer(share, x, first=2, top_k=3, pass_rows=48)
    assert int(counters["dropped"]) == lost


# -- attention --------------------------------------------------------------
def _naive_attention(q, k, v, seg):
    h, hkv, length, d = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    kk, vv = jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(d)
    idx = jnp.arange(length)
    keep = (idx[:, None] >= idx[None, :])[None, None] & (
        seg[:, None, :, None] == seg[:, None, None, :])
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(keep, s, -1e30), -1), vv)


@pytest.fixture(scope="module")
def qkv_seg():
    rng = np.random.default_rng(9)
    q = rng.normal(size=(2, 4, 70, 16)).astype(np.float32)
    k = rng.normal(size=(2, 2, 70, 16)).astype(np.float32)
    v = rng.normal(size=(2, 2, 70, 16)).astype(np.float32)
    seg = np.sort(rng.integers(1, 5, size=(2, 70)), axis=1).astype(np.int32)
    return q, k, v, seg


@pytest.mark.parametrize("block", [16, 32, 128])
def test_flash_with_segments_and_grouped_heads(qkv_seg, block):
    q, k, v, seg = qkv_seg
    got = flash_attention(q, k, v, causal=True, block_k=block, segment_ids=seg)
    assert rel(got, _naive_attention(q, k, v, seg)) < 1e-5


def test_flash_backward_with_segments_and_grouped_heads(qkv_seg):
    q, k, v, seg = qkv_seg
    got = jax.grad(lambda *a: (flash_attention(*a, block_k=16, segment_ids=seg) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_naive_attention(*a, seg) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_flash_with_segments_and_unequal_blocks(qkv_seg):
    """Query blocks twice the key blocks, one key/value head a query
    head, forward and through the recomputing VJP."""
    q, k, v, seg = qkv_seg
    k, v = np.repeat(k, 2, axis=1), np.repeat(v, 2, axis=1)
    got = flash_attention(q, k, v, block_q=32, block_k=16, segment_ids=seg)
    assert rel(got, _naive_attention(q, k, v, seg)) < 1e-5
    grads = jax.grad(lambda *a: (flash_attention(
        *a, block_q=32, block_k=16, segment_ids=seg) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_naive_attention(*a, seg) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        assert rel(a, b) < 1e-5


def test_attention_dispatch_keeps_grouped_heads_and_segments(qkv_seg):
    from predictionio_tpu.ops.attention import attention

    q, k, v, seg = qkv_seg
    assert rel(attention(q, k, v, segment_ids=seg, block=32),
               _naive_attention(q, k, v, seg)) < 1e-5


def test_ring_attention_with_segments(qkv_seg):
    from predictionio_tpu.parallel import MeshConfig, create_mesh

    q, k, v, seg = qkv_seg
    q, k, v, seg = q[:, :, :64], np.repeat(k[:, :, :64], 2, 1), np.repeat(v[:, :, :64], 2, 1), seg[:, :64]
    mesh = create_mesh(MeshConfig((("seq", 8),)))
    got = ring_attention(q, k, v, mesh, causal=True, segment_ids=seg)
    assert rel(got, _naive_attention(q, k, v, seg)) < 1e-5


# -- the shipped preset -----------------------------------------------------
def _old_forward(params, tokens, n_heads):
    """The template's forward as it was before the backbone became a
    function of a configuration (PR 25's ``sequencerec.forward``)."""
    def layer_norm(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-6) * g + b

    b, l = tokens.shape
    d = params["embed"].shape[1]
    h = params["embed"][tokens] + params["pos"][:l][None]
    dh = d // n_heads
    for layer in params["layers"]:
        x = layer_norm(h, layer["ln1_g"], layer["ln1_b"])
        q, k, v = jnp.split(x @ layer["qkv"], 3, axis=-1)
        heads = lambda t: t.reshape(b, l, n_heads, dh).transpose(0, 2, 1, 3)  # noqa: E731
        s = jnp.einsum("bhqd,bhkd->bhqk", heads(q), heads(k)) / np.sqrt(dh)
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads(v))
        h = h + o.transpose(0, 2, 1, 3).reshape(b, l, d) @ layer["proj"]
        x = layer_norm(h, layer["ln2_g"], layer["ln2_b"])
        h = h + jax.nn.gelu(x @ layer["mlp_in"]) @ layer["mlp_out"]
    h = layer_norm(h, params["lnf_g"], params["lnf_b"])
    return h @ params["embed"].T


def test_the_toy_preset_gives_the_logits_it_gave_before():
    d, n_heads, n_layers, vocab, length = 32, 2, 2, 13, 12
    cfg = bb.BackboneConfig.toy(d, n_heads, n_layers)
    new = jax.tree_util.tree_map(np.asarray, bb.init_params(cfg, vocab, length, 3))
    rng = np.random.default_rng(10)
    per = new["periods"]
    for name in ("norm_in", "norm_post"):  # off their starting values
        per[name] = {k: a + 0.1 * rng.normal(size=a.shape).astype(np.float32)
                     for k, a in per[name].items()}
    old = {
        "embed": new["embed"], "pos": new["pos"],
        "lnf_g": new["final_norm"]["g"], "lnf_b": new["final_norm"]["b"],
        "layers": [{
            "ln1_g": per["norm_in"]["g"][n, 0], "ln1_b": per["norm_in"]["b"][n, 0],
            "ln2_g": per["norm_post"]["g"][n, 0], "ln2_b": per["norm_post"]["b"][n, 0],
            "qkv": np.concatenate([per["full"][w][n] for w in ("w_q", "w_k", "w_v")], axis=1),
            "proj": per["full"]["w_o"][n],
            "mlp_in": per["ffn"]["mlp_in"][n, 0], "mlp_out": per["ffn"]["mlp_out"][n, 0],
        } for n in range(n_layers)],
    }
    tokens = rng.integers(0, vocab, size=(3, length)).astype(np.int32)
    hidden, *_ = bb.hidden_states(cfg, new, tokens, np.ones_like(tokens))
    got = bb.logits_of(cfg, new, hidden)
    np.testing.assert_allclose(got, _old_forward(old, tokens, n_heads), rtol=2e-5, atol=2e-6)


# -- the normal path --------------------------------------------------------
def test_pio_train_and_predict_with_the_backbone_configuration(tmp_path, monkeypatch):
    """The sequencerec engine with ``backbone`` naming a configuration
    trains and answers through the same workflow as every template."""
    import datetime as dt

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.sequencerec import (
        Query, SeqDataSourceParams, SeqPreparatorParams, SeqRecAlgorithm,
        SeqRecAlgorithmParams, engine_factory)
    from predictionio_tpu.storage import Event, get_registry
    from predictionio_tpu.workflow.context import WorkflowContext

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    get_registry(refresh=True)
    store = get_registry().get_events()
    store.init(11)
    t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(8):
        for t in range(5 + 3 * u):
            store.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}", target_entity_type="item",
                target_entity_id=f"i{(u + t) % 7}", event_time=t0 + dt.timedelta(minutes=t)), 11)
    algo_params = SeqRecAlgorithmParams(
        backbone="qwen3next-tiny", steps=30, batch_size=2, learning_rate=1e-2)
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_id=11)),
        preparator_params=("", SeqPreparatorParams(seq_len=32)),
        algorithm_params_list=[("", algo_params)],
    )
    model = engine_factory().train(WorkflowContext(), ep)[0]
    model.sanity_check()
    assert model.config.full_attention_interval == 4 and model.config.experts_held == (2, 3)
    assert model.losses[-1] < model.losses[0]
    assert 0.0 < model.stats["fill"] <= 1.0
    # heads of 16 on the CPU: not the kernel's tiles
    assert model.stats["delta_rule_walk"] == "scan" and model.stats["conv"] == "xla"
    answer = SeqRecAlgorithm(algo_params).predict(
        model, Query(recent_items=("i0", "i1", "i2"), num=3))
    assert len(answer.item_scores) == 3
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    get_registry(refresh=True)


@pytest.mark.parametrize("forced", [False, True], ids=["usual", "overflow"])
def test_a_job_counts_the_layer_steps_that_overflowed(forced, monkeypatch):
    """``passes_by_step``, ``layer_steps`` and ``overflow_layer_steps`` land
    in the model's stats and as tags of the job's ``train`` span; with
    passes of 8 rows every layer's step overflows."""
    import functools

    from predictionio_tpu.models import sequencerec
    from predictionio_tpu.obs.trace import default_tracer
    from predictionio_tpu.storage import BiMap

    if forced:
        monkeypatch.setattr(bb, "expert_layer", functools.partial(moe.expert_layer, pass_rows=8))
    sequencerec._programs.cache_clear()  # the job's programs are kept by configuration
    rng = np.random.default_rng(5)
    pd = sequencerec.PreparedData(
        item_map=BiMap.string_int([f"i{n}" for n in range(VOCAB)]),
        windows=rng.integers(1, VOCAB, size=(4, 33)).astype(np.int32),
        segments=np.ones((4, 33), np.int32), user_recent={}, seq_len=32)
    try:
        model = sequencerec.SeqRecAlgorithm(sequencerec.SeqRecAlgorithmParams(
            backbone="qwen3next-tiny", steps=3, batch_size=2)).train(None, pd)
    finally:
        sequencerec._programs.cache_clear()
    stats = model.stats
    assert stats["passes_by_step"].shape == (3, 2, 4) and stats["layer_steps"] == 24
    assert int(np.max(stats["dropped"])) == 0
    if forced:
        assert stats["overflow_layer_steps"] == 24 and stats["passes_by_step"].min() > 1
    else:
        assert stats["overflow_layer_steps"] == 0 and (stats["passes_by_step"] == 1).all()
    roots = [s for s in default_tracer().store.dump() if s["name"] == "train" and s["parentId"] is None]
    tags = roots[-1]["tags"]
    assert tags["layer_steps"] == 24 and tags["overflow_layer_steps"] == stats["overflow_layer_steps"]
    assert tags["passes_by_step"].split() == [str(n) for n in stats["passes_by_step"].max(axis=(1, 2))]
    assert tags["delta_rule_walk"] == "scan" and tags["conv"] == "xla"


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "qwen3_next_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(REPO, "benchmark", "lib", "reference_qwen3next.py")) as f:
        theirs = f.read()
    assert ours == theirs


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "predictionio_tpu", "testing", "qwen3_next_reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "ops" in line or "models" in line or "predictionio" in line]
    assert 'default_matmul_precision("highest")' in text


def test_the_shipped_configuration_has_the_published_widths():
    with open(os.path.join(REPO, "conf", "backbones", "qwen3next-80b-a3b-ep16.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs", "seqrec-qwen3next-80b-a3b-ep16.json")) as f:
        bench = json.load(f)
    for key, value in conf.items():
        if key not in ("name", "what"):
            assert bench[key] == value, key
    cfg = bb.BackboneConfig.from_dict(conf)
    assert (cfg.hidden_size, cfg.head_dim, cfg.router_width, cfg.num_experts_per_tok) == (2048, 256, 512, 10)
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, conf["vocab_size"], 8192, 0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) == 625_667_136


def test_rows_past_the_last_group_do_not_reach_a_token(monkeypatch, untraced_passes):
    """On the chip the grouped product leaves the rows past its last group
    unwritten, in the forward pass and in the cotangent of its left operand:
    they hold whatever the buffer held (PR 26's first chip runs scattered
    that onto the tokens' gradients, 1e6 times their size). Here the product
    is made to leave large numbers there, and nothing may change."""
    rng = np.random.default_rng(12)
    share = _share(_moe_params(rng), 2, 3)
    x = rng.normal(size=(64, 16)).astype(np.float32)

    def loss(p, x):
        return (moe.expert_layer(p, x, first=2, top_k=3)[0] ** 2).sum()

    clean = jax.grad(loss, argnums=(0, 1))(share, jnp.asarray(x))
    real = jax.lax.ragged_dot

    def past(out, group_sizes):
        rows = jnp.arange(out.shape[0])[:, None] >= group_sizes.sum()
        return jnp.where(rows, 1e30, out)

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return past(real(lhs, rhs, group_sizes, preferred_element_type=jnp.float32), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return dirty(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes, preferred_element_type=jnp.float32),
                         lhs, rhs)
        rows = jnp.arange(lhs.shape[0])[:, None] < group_sizes.sum()
        d_lhs, d_rhs = vjp(jnp.where(rows, g, 0.0))  # the chip reads the groups' rows only
        return past(d_lhs, group_sizes).astype(lhs.dtype), d_rhs, None

    dirty.defvjp(fwd, bwd)
    planted = []
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda a, b, gs, **kw: (planted.append(1), dirty(a, b, gs))[1])
    untraced_passes()  # the clean product's traces
    got = jax.grad(loss, argnums=(0, 1))(share, jnp.asarray(x))
    assert planted
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(clean)):
        assert np.isfinite(np.asarray(a)).all() and rel(a, b) < 1e-5


# -- the table of mixers ------------------------------------------------------
#: a configuration in which every kind of mixer can stand as the third layer
#: of one period: a Mamba-1 layer and a full differential layer below it hand
#: on what the reading kinds read
EVERY = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 16, "intermediate_size": 96,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "conv_L_cache": 3,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_n_groups": 1, "mamba_d_state": 4,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "backbone": {"differential": True, "norm": "layer", "positions": "none", "ffn": "swiglu",
                 "chunk": 8, "attn_block": 16, "loss_block": 32},
}

#: what a kind needs that ``EVERY`` cannot carry for all: sparse attention is
#: grouped-query attention and refuses a differential file
OWN = {"dsa": {"sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": 8},
               "backbone": {**EVERY["backbone"], "differential": False}},
       # an expert layer is a layer of its own where every layer is one part
       "moe": {"router_width": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
               "experts_held": [1, 2], "backbone": {**EVERY["backbone"], "ffn": "none"}}}


@pytest.mark.parametrize("kind", sorted(bb._MIXERS))
def test_every_record_of_the_table_is_complete_and_its_words_are_accepted(kind):
    record = bb._MIXERS[kind]
    assert record.words and record.scope and all(s.startswith("seq.") for s in record.scope)
    assert record.run.__module__ == bb.__name__  # a function of the module: it finds its op there
    if record.reads:  # some kind hands on what it reads
        assert any(set(record.reads) <= set(other.hands) for other in bb._MIXERS.values())
    for word in record.words:
        cfg = bb.BackboneConfig.from_dict(
            {**EVERY, **OWN.get(kind, {}), "layer_types": ["mamba1", "full_attention", word]})
        assert cfg.kinds[-1] == kind and cfg.stacked(kind) in (0, 1, 2)
        assert cfg.mixers()[record.name or cfg.attention] >= 1
        specs = jax.tree_util.tree_leaves(record.shapes(cfg), is_leaf=bb._is_spec)
        assert specs and all(bb._is_spec(spec) for spec in specs)
        assert isinstance(record.forms(cfg, L), dict) and isinstance(record.widths(cfg), dict)
        # and the layer runs with the parameters its record names (traced, not compiled)
        params = jax.eval_shape(lambda: bb.init_params(cfg, VOCAB, L, 0))
        rows = jax.ShapeDtypeStruct((2, L), jnp.int32)
        hidden, _, ran = jax.eval_shape(
            lambda p, t, s: bb.hidden_states(cfg, p, t, s), params, rows, rows)
        assert hidden.shape == (2, L, 64) and set(record.hands) <= set(ran)


def test_a_word_the_table_does_not_know_is_refused():
    with pytest.raises(ValueError, match=r"layer_types names mixers unknown here: \['window'\]"):
        bb.BackboneConfig.from_dict({**EVERY, "layer_types": ["mamba1", "full_attention", "window"]})


def _pallas_calls(jaxpr, above=""):
    """The name stacks of the ``pallas_call`` equations of a jaxpr and of the
    jaxprs its equations hold (a scan's body, a checkpoint's, a custom VJP's
    primal function), each under the stack of the equation that holds it."""
    found = []
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append(stack)
        for value in eqn.params.values():
            for held in (value if isinstance(value, (tuple, list)) else (value,)):
                held = getattr(held, "jaxpr", held)  # a closed jaxpr's
                if hasattr(held, "eqns"):
                    found += _pallas_calls(held, stack)
    return found


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks for the backend is told "tpu"; the delta rule, the
    sparse-attention core and the indexers' loss, whose jits keep a trace made
    under one answer, are traced anew on both sides."""
    from predictionio_tpu.ops.attention import chosen_attention
    from predictionio_tpu.ops.dsa import index_loss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for fn in (deltanet.gated_delta_rule, chosen_attention, index_loss):
        fn.clear_cache()
    yield
    for fn in (deltanet.gated_delta_rule, chosen_attention, index_loss):
        fn.clear_cache()


@pytest.mark.parametrize("name,rows", [
    ("qwen3next-80b-a3b-ep16", 2), ("joyai-flash-48b-a3b-ep16", 2), ("lfm2-24b-a2b-ep8", 2),
    ("granite4h-micro-vp8", 1), ("phi4-mini-flash-vp8", 1), ("keye-vl2-30b-a3b-ep8", 1),
    ("nemotron3-nano-30b-a3b-ep16", 2)])
def test_the_counters_say_pallas_exactly_where_the_step_holds_a_kernel(as_tpu, name, rows):
    """On a TPU, over the cells' rows of 8,192 slots: the forward pass traced
    on abstract arguments holds a ``pallas_call`` under a mixer's ``.conv``,
    ``.scan``, ``.core`` or ``.index_loss`` scope exactly where that mixer's ``forms`` (what ``mechanisms``
    merges into the counters the benchmark prints) say ``pallas``, and none
    where they say ``xla`` or ``scan``; as shipped, and in the cells' control
    build (bfloat16 state and gates), which asks most kernels for the other form."""
    import dataclasses

    shipped = bb.BackboneConfig.load(name)
    seen = set()
    for cfg in (shipped, dataclasses.replace(shipped, state_dtype="bfloat16", gate_dtype="bfloat16")):
        params = jax.eval_shape(lambda: bb.init_params(cfg, 1024, 8192, 0))
        tokens = jax.ShapeDtypeStruct((rows, 8192), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda p, t, s: bb.hidden_states(cfg, p, t, s)[0])(
            params, tokens, tokens)
        kernels = _pallas_calls(jaxpr.jaxpr)
        merged = bb.mechanisms(cfg, 8192)
        for kind in set(cfg.kinds):
            record = bb._MIXERS[kind]
            for counter, form in record.forms(cfg, 8192).items():
                if not isinstance(form, str):
                    continue  # a count of tiles, no form
                part = {"conv": "conv", "chosen_core": "core",
                        "index_kl": "index_loss"}.get(counter, "scan")
                scope = f"{record.scope[0]}.{part}"
                assert any(scope in stack for stack in kernels) == (form == "pallas"), (counter, form)
                assert form in merged[counter].split("+")
                seen.add(form)
    # every backbone with a counter of a form met a kernel (JoyAI has none)
    assert ("pallas" in seen) == any(isinstance(v, str) for v in bb.mechanisms(shipped, 8192).values())
