"""Attention schedules: flash vs naive, ring/Ulysses vs flash on the mesh.

The sequence-parallel schedules must be numerically equivalent to plain
attention — the mesh changes the communication pattern, never the math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.attention import (
    attention,
    flash_attention,
    ring_attention,
    ulysses_attention,
)
from predictionio_tpu.parallel import MeshConfig, create_mesh


def naive(q, k, v, causal):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((q.shape[2], k.shape[2]), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 4, 64, 16)  # B, H, L, D
    return tuple(rng.normal(size=shape).astype(np.float32) for _ in range(3))


@pytest.fixture(scope="module")
def seq_mesh():
    return create_mesh(MeshConfig((("seq", 8),)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [16, 64, 48])
def test_flash_matches_naive(qkv, causal, block_k):
    q, k, v = qkv
    ref = naive(q, k, v, causal)
    got = np.asarray(flash_attention(q, k, v, causal=causal, block_k=block_k))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_naive(qkv, seq_mesh, causal):
    q, k, v = qkv
    ref = naive(q, k, v, causal)
    got = np.asarray(ring_attention(q, k, v, seq_mesh, causal=causal))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_naive(qkv, causal):
    # H=4 heads need a 4-device seq axis (heads must divide)
    mesh4 = create_mesh(
        MeshConfig((("seq", 4),)), devices=jax.devices()[:4]
    )
    q, k, v = qkv
    ref = naive(q, k, v, causal)
    got = np.asarray(ulysses_attention(q, k, v, mesh4, causal=causal))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_dispatch(qkv, seq_mesh):
    q, k, v = qkv
    # no mesh → flash; mesh → ring; both equal naive
    ref = naive(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v)), ref, rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v, mesh=seq_mesh)), ref, rtol=2e-4, atol=2e-5
    )
    with pytest.raises(ValueError):
        attention(q, k, v, mesh=seq_mesh, schedule="bogus")


def test_ring_rejects_indivisible_length(seq_mesh):
    rng = np.random.default_rng(1)
    q, k, v = (rng.normal(size=(1, 2, 60, 8)).astype(np.float32)
               for _ in range(3))
    with pytest.raises(AssertionError):
        ring_attention(q, k, v, seq_mesh)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "b,h,lq,lk,d,bq,bk",
    [
        (2, 4, 64, 64, 16, 32, 32),
        (1, 2, 60, 60, 8, 32, 16),   # ragged L vs blocks
        (1, 1, 7, 13, 8, 8, 8),      # tiny + cross-attention
        (2, 2, 128, 96, 32, 64, 32),
    ],
)
def test_flash_shapes_match_naive(causal, b, h, lq, lk, d, bq, bk):
    """Lengths that do not divide the blocks, Lq != Lk and blocks that
    differ between queries and keys, all against the full score matrix."""
    rng = np.random.default_rng(7)
    q = rng.normal(size=(b, h, lq, d)).astype(np.float32)
    k = rng.normal(size=(b, h, lk, d)).astype(np.float32)
    v = rng.normal(size=(b, h, lk, d)).astype(np.float32)
    got = np.asarray(flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk))
    np.testing.assert_allclose(got, naive(q, k, v, causal), rtol=2e-4, atol=2e-5)


def test_flash_gradients_match_naive():
    """The custom VJP (each tile recomputed from q, k and the rows'
    log-sum-exp) against differentiating the full score matrix."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    q, k, v = (rng.normal(size=(1, 2, 40, 8)).astype(np.float32)
               for _ in range(3))

    def full(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(np.tril(np.ones((q.shape[2], k.shape[2]), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    blockwise = lambda q, k, v: flash_attention(q, k, v, causal=True, block_k=16)  # noqa: E731
    got = jax.grad(loss(blockwise), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(full), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)


def test_dispatch_on_one_device(qkv):
    """A mesh without a ``seq`` axis, or with one device on it, is the
    single-device path: grouped key/value heads go in as they are and
    ``block`` reaches the blockwise kernel."""
    q, k, v = qkv
    ref = naive(q, np.repeat(k[:, :2], 2, axis=1), np.repeat(v[:, :2], 2, axis=1), True)
    one = create_mesh(MeshConfig((("seq", 1),)), devices=jax.devices()[:1])
    other = create_mesh(MeshConfig((("data", 2),)), devices=jax.devices()[:2])
    for mesh in (one, other):
        got = attention(q, k[:, :2], v[:, :2], mesh=mesh, block=16)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-5)


# -- a mask the step computes (learned sparse attention) ----------------------
def _chosen_case(length, seed=0, heads=4, kv_heads=2, hd=8, keep_share=0.5):
    """Seeded q, k, v, packed histories and a random chosen mask that always
    holds the diagonal (every query keeps itself, as a choice of its best keys
    among its causal ones always leaves it one)."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(2, heads, length, hd)).astype(np.float32)
    k = rng.normal(size=(2, kv_heads, length, hd)).astype(np.float32)
    v = rng.normal(size=(2, kv_heads, length, hd)).astype(np.float32)
    seg = np.ones((2, length), np.int32)
    seg[0, length // 3:] = 2
    seg[0, -2:] = 0
    chosen = (rng.random((2, length, length)) < keep_share) | np.eye(length, dtype=bool)[None]
    idx = np.arange(length)
    kept = chosen & (idx[:, None] >= idx[None, :])[None] & (seg[:, :, None] == seg[:, None, :])
    return q, k, v, seg, chosen, kept


def _dense_chosen(q, k, v, kept):
    """Masked softmax over the full score matrix: o [B, H, L, hd], the weights
    [B, H, L, L]."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where(kept[:, None], s, -1e30), axis=-1)
    w = jnp.where(kept[:, None], w, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v), w


@pytest.mark.parametrize("length,block", [(48, 16), (40, 16), (24, 64)])
def test_the_loop_under_a_chosen_mask_is_the_dense_masked_softmax(length, block):
    """``chosen_attention``: forward, the rows' log-sum-exp and the VJP against
    a dense masked softmax, rows of whole tiles and not, one tile a row."""
    from predictionio_tpu.ops.attention import chosen_attention

    q, k, v, seg, chosen, kept = _chosen_case(length)
    probe = np.random.default_rng(9).normal(size=q.shape).astype(np.float32)

    def ours(q, k, v):
        o, lse = chosen_attention(q, k, v, jnp.asarray(chosen), jnp.asarray(seg), block=block)
        return jnp.sum(o * probe), (o, lse)

    def dense(q, k, v):
        o, _ = _dense_chosen(q, k, v, jnp.asarray(kept))
        return jnp.sum(o * probe), o

    (_, (o, lse)), grads = jax.value_and_grad(ours, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want_o), want_grads = jax.value_and_grad(dense, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, atol=5e-5)
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1)) / np.sqrt(q.shape[-1])
    want_lse = jax.nn.logsumexp(jnp.where(kept[:, None], s, -np.inf), axis=-1)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)


def test_no_gradient_flows_through_the_log_sum_exp_that_is_handed_back():
    from predictionio_tpu.ops.attention import chosen_attention

    q, k, v, seg, chosen, _ = _chosen_case(32)
    grads = jax.grad(lambda q, k, v: chosen_attention(
        q, k, v, jnp.asarray(chosen), jnp.asarray(seg), block=16)[1].sum(), argnums=(0, 1, 2))(q, k, v)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


@pytest.mark.parametrize("length,block", [(48, 16), (40, 16)])
def test_the_indexers_loss_is_the_kl_over_the_chosen_keys(length, block):
    """``ops.dsa.index_loss`` (the second pass over the tiles) against the KL
    written out on full matrices: the head-summed weights of the main heads
    over the chosen keys, held constant, from the softmax of the index scores
    over the same keys; value and the gradient onto the indexer's inputs, and
    none onto q and k."""
    from predictionio_tpu.ops import dsa
    from predictionio_tpu.ops.attention import chosen_attention

    q, k, v, seg, chosen, kept = _chosen_case(length, seed=3)
    rng = np.random.default_rng(4)
    iq = rng.normal(size=(2, length, 3, 4)).astype(np.float32)
    ik = rng.normal(size=(2, length, 4)).astype(np.float32)
    iw = rng.normal(size=(2, length, 3)).astype(np.float32)
    _, lse = chosen_attention(q, k, v, jnp.asarray(chosen), jnp.asarray(seg), block=block)

    def ours(iq, ik, iw, q, k):
        total, slots = dsa.index_loss(iq, ik, iw, q, k, lse, jnp.asarray(seg), jnp.asarray(chosen),
                                      block=block)
        return total / slots

    def dense(iq, ik, iw):
        scores = dsa.index_scores(iq, ik, iw)
        p = _dense_chosen(q, k, v, jnp.asarray(kept))[1].mean(1)
        log_soft = jax.nn.log_softmax(jnp.where(kept, scores, -1e30), axis=-1)
        kl = jnp.where(kept & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_soft), 0.0).sum(-1)
        real = jnp.asarray(seg > 0)
        return jnp.where(real, kl, 0.0).sum() / real.sum()

    got, grads = jax.value_and_grad(ours, argnums=(0, 1, 2, 3, 4))(iq, ik, iw, q, k)
    want, want_grads = jax.value_and_grad(dense, argnums=(0, 1, 2))(iq, ik, iw)
    assert float(got) == pytest.approx(float(want), rel=1e-5) and float(want) > 0.1
    for g, w in zip(grads[:3], want_grads):
        np.testing.assert_allclose(g, w, atol=2e-6)
    assert float(jnp.abs(grads[3]).max()) == 0.0 and float(jnp.abs(grads[4]).max()) == 0.0
