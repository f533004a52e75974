"""Streaming top-k serving kernel vs. the XLA reference path.

Runs the Pallas kernel in interpret mode on CPU (auto-selected) and checks
exact agreement with ``jax.lax.top_k`` over the materialized score matrix.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops.pallas_kernels import (
    top_k_for_users_streaming,
    top_k_streaming,
)
from predictionio_tpu.ops.scoring import top_k_for_vectors


def _ref_topk(q, items, k, exclude_idx=None):
    scores = q @ items.T
    if exclude_idx is not None:
        for b in range(scores.shape[0]):
            for e in exclude_idx[b]:
                if e >= 0:
                    scores[b, e] = -np.inf
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, idx, axis=1), idx


@pytest.mark.parametrize("b,n,r,k", [(4, 100, 16, 5), (8, 1030, 50, 10), (3, 7, 4, 3)])
def test_matches_reference(b, n, r, k):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(b, r)).astype(np.float32)
    items = rng.normal(size=(n, r)).astype(np.float32)
    got_s, got_i = top_k_streaming(q, items, k, block_items=256)
    ref_s, ref_i = _ref_topk(q, items, k)
    np.testing.assert_allclose(np.asarray(got_s), ref_s, rtol=1e-5, atol=1e-5)
    # indices can differ only on exact ties; scores already checked exactly
    same = np.asarray(got_i) == ref_i
    tied = np.isclose(np.asarray(got_s), ref_s)
    assert (same | tied).all()


def test_exclusion_lists():
    rng = np.random.default_rng(1)
    b, n, r, k = 4, 64, 8, 6
    q = rng.normal(size=(b, r)).astype(np.float32)
    items = rng.normal(size=(n, r)).astype(np.float32)
    # exclude the unfiltered top-2 of each row, padded with -1
    s0, i0 = top_k_streaming(q, items, 2)
    excl = np.concatenate(
        [np.asarray(i0), np.full((b, 3), -1, np.int32)], axis=1
    ).astype(np.int32)
    got_s, got_i = top_k_streaming(q, items, k, exclude_idx=jnp.asarray(excl))
    for row in range(b):
        assert not set(np.asarray(got_i)[row]).intersection(set(np.asarray(i0)[row]))
    ref_s, ref_i = _ref_topk(q, items, k, excl)
    np.testing.assert_allclose(np.asarray(got_s), ref_s, rtol=1e-5, atol=1e-5)


def test_k_larger_than_catalog():
    rng = np.random.default_rng(2)
    q = rng.normal(size=(2, 4)).astype(np.float32)
    items = rng.normal(size=(3, 4)).astype(np.float32)
    s, i = top_k_streaming(q, items, 8)
    assert s.shape == (2, 8) and i.shape == (2, 8)
    assert np.isneginf(np.asarray(s)[:, 3:]).all()
    assert (np.asarray(i)[:, 3:] == -1).all()


def test_user_gather_wrapper_agrees_with_xla_path():
    rng = np.random.default_rng(3)
    uf = rng.normal(size=(20, 12)).astype(np.float32)
    itf = rng.normal(size=(200, 12)).astype(np.float32)
    uidx = np.array([3, 17, 5], dtype=np.int32)
    s1, i1 = top_k_for_users_streaming(uf, itf, uidx, 7, block_items=128)
    s2, i2 = top_k_for_vectors(uf[uidx], itf, 7)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5)
    assert (np.asarray(i1) == np.asarray(i2)).all() or np.allclose(
        np.asarray(s1), np.asarray(s2)
    )


def test_dense_path_contract():
    """The dense XLA path (what ``resolve_topk_path`` chooses below the
    streaming bar) must honor exclusions and k > catalog."""
    from predictionio_tpu.ops.scoring import xla_topk_with_sentinels

    rng = np.random.default_rng(4)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    items = rng.normal(size=(20, 8)).astype(np.float32)
    s0, i0 = xla_topk_with_sentinels(q, items, 2)
    excl = np.concatenate(
        [np.asarray(i0), np.full((3, 2), -1, np.int32)], axis=1
    ).astype(np.int32)
    s, i = xla_topk_with_sentinels(q, items, 5, exclude_idx=jnp.asarray(excl))
    for row in range(3):
        assert not set(np.asarray(i)[row]).intersection(set(np.asarray(i0)[row]))
    s2, i2 = xla_topk_with_sentinels(q, items, 25)
    assert s2.shape == (3, 25)
    assert np.isneginf(np.asarray(s2)[:, 20:]).all()


def test_dense_sentinel_matches_kernel_when_exclusions_exhaust_catalog():
    """Both paths must return -1 (never a real excluded id) in -inf slots —
    the divergence flagged in round-1 ADVICE: a caller gathering by index
    would map a real-but-excluded id to a live item."""
    import predictionio_tpu.ops.pallas_kernels as pk

    rng = np.random.default_rng(6)
    q = rng.normal(size=(2, 4)).astype(np.float32)
    items = rng.normal(size=(5, 4)).astype(np.float32)
    # exclude ALL 5 items: fewer than k=3 valid candidates remain
    excl = np.tile(np.arange(5, dtype=np.int32), (2, 1))

    from predictionio_tpu.ops.scoring import xla_topk_with_sentinels

    s_k, i_k = pk.top_k_streaming(q, items, 3, exclude_idx=jnp.asarray(excl))
    s_f, i_f = xla_topk_with_sentinels(
        q, items, 3, exclude_idx=jnp.asarray(excl))

    for s, i in ((s_k, i_k), (s_f, i_f)):
        assert np.isneginf(np.asarray(s)).all()
        assert (np.asarray(i) == -1).all()


def test_wide_exclusion_list():
    """Exclusion lists wider than the kernel chunk (fori_loop path)."""
    rng = np.random.default_rng(5)
    b, n, r = 2, 300, 8
    q = rng.normal(size=(b, r)).astype(np.float32)
    items = rng.normal(size=(n, r)).astype(np.float32)
    # exclude the top 40 of each row (several 16-wide chunks + padding)
    _, i0 = top_k_streaming(q, items, 40, block_items=128)
    s, i = top_k_streaming(
        q, items, 10, exclude_idx=np.asarray(i0, np.int32), block_items=128
    )
    for row in range(b):
        assert not set(np.asarray(i)[row]).intersection(set(np.asarray(i0)[row]))


# ---------------------------------------------------------------------------
# spd_solve_t — fused batched Cholesky solve
# ---------------------------------------------------------------------------
class TestSpdSolve:
    def _systems(self, bsz, r, k, seed=0, lam=0.05):
        rng = np.random.default_rng(seed)
        g = rng.standard_normal((bsz, k, r)).astype(np.float32)
        a = np.einsum("bkr,bks->brs", g, g) + lam * k * np.eye(
            r, dtype=np.float32
        )
        b = rng.standard_normal((bsz, r)).astype(np.float32)
        return a, b

    def _to_t(self, a, b, n):
        bsz, r = b.shape
        a_t = np.zeros((n, n, bsz), np.float32)
        a_t[:r, :r] = np.transpose(a, (1, 2, 0))
        b_t = np.zeros((n, bsz), np.float32)
        b_t[:r] = b.T
        return jnp.asarray(a_t), jnp.asarray(b_t)

    @pytest.mark.parametrize("r,n", [(4, 8), (50, 56), (13, 16)])
    def test_matches_cho_solve(self, r, n):
        from predictionio_tpu.ops.pallas_kernels import spd_solve_t

        bsz = 128
        a, b = self._systems(bsz, r, k=32)
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        a_t, b_t = self._to_t(a, b, n)
        x = np.asarray(spd_solve_t(a_t, b_t))[:r].T
        rel = np.linalg.norm(x - ref, axis=-1) / (
            np.linalg.norm(ref, axis=-1) + 1e-9
        )
        assert np.max(rel) < 1e-4

    def test_zero_padded_systems_solve_to_zero(self):
        """Bucket-padding rows are all-zero systems; the inv_d guard must
        produce exact zeros (NaNs would poison the factor scatter)."""
        from predictionio_tpu.ops.pallas_kernels import spd_solve_t

        bsz, r, n = 128, 8, 8
        a, b = self._systems(64, r, k=16)
        a_t, b_t = self._to_t(a, b, n)
        a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, bsz - 64)))
        b_t = jnp.pad(b_t, ((0, 0), (0, bsz - 64)), constant_values=1.0)
        x = np.asarray(spd_solve_t(a_t, b_t))
        assert np.all(np.isfinite(x))
        np.testing.assert_array_equal(x[:, 64:], 0.0)
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        np.testing.assert_allclose(x[:r, :64].T, ref, rtol=1e-3, atol=1e-4)

    def test_shape_validation(self):
        from predictionio_tpu.ops.pallas_kernels import spd_solve_t

        with pytest.raises(ValueError, match="spd_solve_t"):
            spd_solve_t(jnp.zeros((7, 7, 128)), jnp.zeros((7, 128)))
        with pytest.raises(ValueError, match="spd_solve_t"):
            spd_solve_t(jnp.zeros((8, 8, 100)), jnp.zeros((8, 100)))


# ---------------------------------------------------------------------------
# gramian_fused — fused gather + normal-equation build
# ---------------------------------------------------------------------------
class TestGramianFused:
    """Interpret-mode equality vs the einsum reference at multiple shapes
    and ranks, including non-multiple-of-block edges (the wrapper pads B
    and K; R must be pre-padded to 8s by the caller, as the ALS solver
    path does)."""

    def _ref(self, y, idx, w2, rhs, ridge, yty=None):
        y = np.asarray(y, np.float32)
        g = y[np.asarray(idx)]
        a = np.einsum("bkr,bk,bks->brs", g, w2, g)
        r = y.shape[1]
        a += ridge[:, None, None] * np.eye(r, dtype=np.float32)
        if yty is not None:
            a += np.asarray(yty)[None]
        b = np.einsum("bkr,bk->br", g, rhs)
        return a, b

    def _data(self, b, k, n, r, seed=0, frac_valid=0.7):
        rng = np.random.default_rng(seed)
        y = rng.standard_normal((n, r), dtype=np.float32)
        idx = rng.integers(0, n, (b, k)).astype(np.int32)
        w2 = (rng.random((b, k)) < frac_valid).astype(np.float32)
        rhs = rng.standard_normal((b, k)).astype(np.float32) * w2
        ridge = rng.random(b).astype(np.float32)
        return y, idx, w2, rhs, ridge

    @pytest.mark.parametrize(
        "b,k,n,r",
        [
            (32, 16, 500, 56),   # typical narrow bucket
            (16, 512, 300, 56),  # one full K tile
            (8, 1024, 200, 24),  # K tiling (2 tiles), small rank
            (25, 13, 77, 16),    # non-multiple B and K (wrapper pads)
            (3, 600, 50, 8),     # B < tile, K pads to 1024
        ],
    )
    def test_matches_einsum(self, b, k, n, r):
        from predictionio_tpu.ops.pallas_kernels import gramian_fused

        y, idx, w2, rhs, ridge = self._data(b, k, n, r)
        a, bv = gramian_fused(jnp.asarray(y), jnp.asarray(idx),
                              jnp.asarray(w2), jnp.asarray(rhs),
                              jnp.asarray(ridge))
        a_ref, b_ref = self._ref(y, idx, w2, rhs, ridge)
        np.testing.assert_allclose(np.asarray(a), a_ref, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(bv), b_ref, rtol=1e-4,
                                   atol=1e-4)

    def test_yty_base(self):
        """Implicit mode seeds every system with YtY."""
        from predictionio_tpu.ops.pallas_kernels import gramian_fused

        y, idx, w2, rhs, ridge = self._data(8, 32, 100, 16, seed=3)
        yty = (y.T @ y).astype(np.float32)
        a, bv = gramian_fused(jnp.asarray(y), jnp.asarray(idx),
                              jnp.asarray(w2), jnp.asarray(rhs),
                              jnp.asarray(ridge), jnp.asarray(yty))
        a_ref, b_ref = self._ref(y, idx, w2, rhs, ridge, yty)
        np.testing.assert_allclose(np.asarray(a), a_ref, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(bv), b_ref, rtol=1e-4,
                                   atol=1e-4)

    def test_bf16_gathers(self):
        """bf16 factor table: the kernel upcasts it to f32 at entry —
        the per-row DMA floor is 128 lanes × 32 bits, so bf16 cannot
        reduce the fused path's gathered bytes (deviceless-AOT finding;
        see gramian_fused). Result must match the f32 reference computed
        from the bf16-quantized table exactly up to accumulation order."""
        from predictionio_tpu.ops.pallas_kernels import gramian_fused

        y, idx, w2, rhs, ridge = self._data(16, 64, 200, 24, seed=4)
        y_bf = jnp.asarray(y, jnp.bfloat16)
        a, bv = gramian_fused(y_bf, jnp.asarray(idx), jnp.asarray(w2),
                              jnp.asarray(rhs), jnp.asarray(ridge))
        # reference: bf16 quantization applies to the table ONLY; w2/rhs
        # stay f32 (the kernel upcasts, so g.dtype is f32)
        y_r = np.asarray(y_bf, np.float32)
        a_ref = np.einsum("bkr,bk,bks->brs", y_r[idx], w2, y_r[idx])
        a_ref += ridge[:, None, None] * np.eye(y.shape[1], dtype=np.float32)
        b_ref = np.einsum("bkr,bk->br", y_r[idx], rhs)
        assert np.asarray(a).dtype == np.float32
        np.testing.assert_allclose(np.asarray(a), a_ref, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(bv), b_ref, rtol=1e-4,
                                   atol=1e-4)

    def test_zero_weight_rows_give_ridge_only(self):
        """Bucket-padding rows (all weights 0, ridge 0) must produce an
        exactly-zero system — the SPD kernel's zero→zero contract depends
        on it; index padding must never leak gathered values."""
        from predictionio_tpu.ops.pallas_kernels import gramian_fused

        y, idx, w2, rhs, ridge = self._data(8, 16, 50, 8, seed=5)
        w2[4:] = 0.0
        rhs[4:] = 0.0
        ridge[4:] = 0.0
        a, bv = gramian_fused(jnp.asarray(y), jnp.asarray(idx),
                              jnp.asarray(w2), jnp.asarray(rhs),
                              jnp.asarray(ridge))
        np.testing.assert_array_equal(np.asarray(a)[4:], 0.0)
        np.testing.assert_array_equal(np.asarray(bv)[4:], 0.0)

    def test_rank_validation(self):
        from predictionio_tpu.ops.pallas_kernels import gramian_fused

        with pytest.raises(ValueError, match="rank"):
            gramian_fused(jnp.zeros((10, 7)), jnp.zeros((4, 4), jnp.int32),
                          jnp.zeros((4, 4)), jnp.zeros((4, 4)),
                          jnp.zeros((4,)))

    def test_wide_k_split_matches_einsum(self, monkeypatch):
        """K wider than the per-call SMEM bound splits into slices summed
        in XLA (base terms counted once) — forced small here so the test
        exercises 3 slices without a 32k-wide problem."""
        import predictionio_tpu.ops.pallas_kernels as pk

        monkeypatch.setattr(pk, "_FUSED_K_SPLIT", 32)
        y, idx, w2, rhs, ridge = self._data(6, 80, 60, 16, seed=6)
        yty = (y.T @ y).astype(np.float32)
        a, bv = pk.gramian_fused(jnp.asarray(y), jnp.asarray(idx),
                                 jnp.asarray(w2), jnp.asarray(rhs),
                                 jnp.asarray(ridge), jnp.asarray(yty))
        a_ref, b_ref = self._ref(y, idx, w2, rhs, ridge, yty)
        np.testing.assert_allclose(np.asarray(a), a_ref, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(bv), b_ref, rtol=1e-4,
                                   atol=1e-4)
