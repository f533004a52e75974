"""Attention schedules: flash vs naive, ring/Ulysses vs flash on the mesh.

The sequence-parallel schedules must be numerically equivalent to plain
attention — the mesh changes the communication pattern, never the math.
"""

import jax
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
