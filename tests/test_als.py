"""ALS kernel tests: exactness of the normal-equation solves against a numpy
reference, RMSE convergence on synthetic low-rank data, bucketing correctness,
and the serving top-k kernels."""

import dataclasses
import functools

import numpy as np
import pytest

from predictionio_tpu.ops import (
    ALSConfig,
    als_train_coo,
    bucketize,
    predict_pairs,
    rmse,
    standardize,
    top_k_for_users,
    top_k_for_vectors,
    top_k_similar_items,
)


def synthetic_ratings(n_users=60, n_items=40, rank=3, density=0.4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    y = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = x @ y.T + 3.0  # center around 3 like star ratings
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    return users, items, full[users, items].astype(np.float32)


def numpy_als_step(y, users, items, ratings, n_users, lam, rank):
    """Reference solve: one user-side update with weighted-lambda."""
    x = np.zeros((n_users, rank))
    for u in range(n_users):
        sel = users == u
        if not sel.any():
            continue
        yu = y[items[sel]]
        ru = ratings[sel]
        n_u = sel.sum()
        a = yu.T @ yu + lam * n_u * np.eye(rank)
        x[u] = np.linalg.solve(a, yu.T @ ru)
    return x


#: the default ladder before it grew rungs under the rank (PR 25): what a
#: caller gets who passes it, and what the new default is held against
OLD_LADDER = (8, 32, 128, 512, 2048, 8192, 32768)


class TestBucketize:
    def test_roundtrip_contents(self):
        users, items, ratings = synthetic_ratings()
        bm = bucketize(users, items, ratings, 60, 40)
        assert bm.nnz == len(users)
        # reconstruct COO from buckets
        got = set()
        for b in bm.buckets:
            for bi in range(b.rows.shape[0]):
                for kk in range(b.width):
                    if b.mask[bi, kk]:
                        got.add((int(b.rows[bi]), int(b.idx[bi, kk]),
                                 float(b.val[bi, kk])))
        expect = {(int(u), int(i), float(r))
                  for u, i, r in zip(users, items, ratings)}
        assert got == expect

    def test_bucket_widths_fit_degrees(self):
        users = np.array([0] * 5 + [1] * 40 + [2])
        items = np.arange(46) % 50
        vals = np.ones(46, dtype=np.float32)
        bm = bucketize(users, items, vals, 3, 50)
        widths = sorted(b.width for b in bm.buckets)
        # degree 1 -> 1, degree 5 -> 8, degree 40 -> 128 (nothing
        # between 32 and 128: a finer rung there would be padded back)
        assert widths == [1, 8, 128]
        coarse = bucketize(users, items, vals, 3, 50, bucket_widths=OLD_LADDER)
        assert sorted(b.width for b in coarse.buckets) == [8, 128]

    def test_empty_rows_absent(self):
        bm = bucketize(np.array([5]), np.array([0]), np.array([1.0]), 10, 1)
        assert sum(b.rows.shape[0] for b in bm.buckets) == 1


class TestALSExplicit:
    def test_single_step_matches_numpy(self):
        """One user-side solve must match the dense numpy normal equations."""
        from predictionio_tpu.ops.als import (
            ALSConfig,
            _update_side,
            bucketize,
            init_factors,
        )
        import jax.numpy as jnp

        users, items, ratings = synthetic_ratings()
        n_users, n_items, rank, lam = 60, 40, 4, 0.05
        y = init_factors(n_items, rank, seed=1)
        by_user = bucketize(users, items, ratings, n_users, n_items)
        cfg = ALSConfig(rank=rank, lambda_=lam)
        x_jax = _update_side(y, by_user, cfg, (n_users, rank), None)
        x_np = numpy_als_step(
            np.asarray(y), users, items, ratings, n_users, lam, rank
        )
        np.testing.assert_allclose(np.asarray(x_jax), x_np, rtol=2e-3, atol=2e-4)

    def test_rmse_converges_on_low_rank_data(self):
        users, items, ratings = synthetic_ratings(rank=3)
        cfg = ALSConfig(rank=6, iterations=10, lambda_=0.01)
        factors = als_train_coo(users, items, ratings, 60, 40, cfg)
        train_rmse = rmse(factors, users, items, ratings)
        assert train_rmse < 0.15, f"train RMSE too high: {train_rmse}"

    def test_more_iterations_improve(self):
        users, items, ratings = synthetic_ratings(rank=3, seed=7)
        r1 = rmse(
            als_train_coo(users, items, ratings, 60, 40,
                          ALSConfig(rank=6, iterations=1, lambda_=0.01)),
            users, items, ratings,
        )
        r8 = rmse(
            als_train_coo(users, items, ratings, 60, 40,
                          ALSConfig(rank=6, iterations=8, lambda_=0.01)),
            users, items, ratings,
        )
        assert r8 < r1

    def test_generalization_on_holdout(self):
        users, items, ratings = synthetic_ratings(
            n_users=80, n_items=50, rank=3, density=0.5, seed=3
        )
        n = len(users)
        rng = np.random.default_rng(0)
        perm = rng.permutation(n)
        tr, te = perm[: int(n * 0.8)], perm[int(n * 0.8):]
        cfg = ALSConfig(rank=5, iterations=10, lambda_=0.05)
        factors = als_train_coo(
            users[tr], items[tr], ratings[tr], 80, 50, cfg
        )
        test_rmse = rmse(factors, users[te], items[te], ratings[te])
        assert test_rmse < 0.35, f"holdout RMSE too high: {test_rmse}"


class TestALSImplicit:
    def test_implicit_ranks_observed_higher(self):
        rng = np.random.default_rng(5)
        n_users, n_items = 30, 20
        # two user cohorts with disjoint item tastes
        users, items, vals = [], [], []
        for u in range(n_users):
            liked = range(10) if u < 15 else range(10, 20)
            for i in liked:
                if rng.random() < 0.7:
                    users.append(u)
                    items.append(i)
                    vals.append(1.0)
        cfg = ALSConfig(rank=4, iterations=8, lambda_=0.1,
                        implicit_prefs=True, alpha=10.0)
        factors = als_train_coo(
            np.array(users), np.array(items),
            np.array(vals, dtype=np.float32), n_users, n_items, cfg,
        )
        import jax.numpy as jnp

        scores = np.asarray(
            factors.user_factors @ factors.item_factors.T
        )
        # cohort-A users should prefer cohort-A items on average
        a_pref = scores[:15, :10].mean() - scores[:15, 10:].mean()
        b_pref = scores[15:, 10:].mean() - scores[15:, :10].mean()
        assert a_pref > 0.2 and b_pref > 0.2


class TestScoring:
    def test_top_k_matches_numpy(self):
        rng = np.random.default_rng(0)
        uf = rng.normal(size=(10, 4)).astype(np.float32)
        itf = rng.normal(size=(25, 4)).astype(np.float32)
        scores, idx = top_k_for_users(uf, itf, np.array([2, 5]), k=3)
        full = uf[[2, 5]] @ itf.T
        np.testing.assert_array_equal(
            np.asarray(idx), np.argsort(-full, axis=1)[:, :3]
        )
        np.testing.assert_allclose(
            np.asarray(scores), np.sort(full, axis=1)[:, ::-1][:, :3], rtol=1e-5
        )

    def test_exclude_mask(self):
        uf = np.eye(3, dtype=np.float32)
        itf = np.eye(3, dtype=np.float32)
        mask = np.zeros((1, 3), dtype=bool)
        mask[0, 0] = True  # exclude the best item for user 0
        scores, idx = top_k_for_users(uf, itf, np.array([0]), k=1,
                                      exclude_mask=mask)
        assert int(idx[0, 0]) != 0

    def test_similar_items_excludes_self(self):
        rng = np.random.default_rng(1)
        itf = rng.normal(size=(12, 4)).astype(np.float32)
        scores, idx = top_k_similar_items(itf, np.array([3]), k=5)
        assert 3 not in np.asarray(idx[0])
        assert np.all(np.asarray(scores[0]) <= 1.0 + 1e-5)

    def test_vector_query(self):
        itf = np.eye(4, dtype=np.float32)
        q = np.array([[0.0, 1.0, 0.0, 0.0]], dtype=np.float32)
        scores, idx = top_k_for_vectors(q, itf, k=1)
        assert int(idx[0, 0]) == 1

    def test_standardize(self):
        s = standardize(np.array([1.0, 2.0, 3.0], dtype=np.float32))
        np.testing.assert_allclose(np.asarray(s).mean(), 0.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s).std(), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# Shared solver sweep (tier-1 budget, ROUND9): the solve-mode, fused-gather
# and mesh equivalence tests all compare trainings of the SAME zipf dataset
# under different lever settings — and this file alone used to burn 260-350s
# re-training overlapping configs per parametrization. One module-level
# cache trains each (mode, implicit, meshed) config exactly once per
# session; every equivalence test reads from it.
# ---------------------------------------------------------------------------

_SWEEP_CACHE: dict = {}


def _sweep_data():
    rng = np.random.default_rng(7)
    nnz, n_u, n_i = 30_000, 900, 250
    w = 1.0 / np.arange(1, n_u + 1) ** 0.8
    u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    v = rng.integers(1, 6, nnz).astype(np.float32)
    return u, i, v, n_u, n_i


def sweep_factors(mode, implicit=False, meshed=False, gather="f32"):
    """Factors for one lever setting over the shared dataset, trained at
    most once per session (rank 12, 3 iterations, seed 2 — identical
    across every consumer so the cached runs stay comparable). A
    ``pallas`` run builds its buckets of 12 slots or more with the fused
    kernel."""
    key = (mode, implicit, meshed, gather)
    if key not in _SWEEP_CACHE:
        from predictionio_tpu.ops.als import ALSConfig, als_train_coo
        from predictionio_tpu.parallel.mesh import create_mesh

        u, i, v, n_u, n_i = _sweep_data()
        cfg = ALSConfig(
            rank=12, iterations=3, lambda_=0.05,
            implicit_prefs=implicit, alpha=1.0, seed=2,
            solve_mode=mode,
            gather_dtype=gather,
        )
        f = als_train_coo(
            u, i, v, n_users=n_u, n_items=n_i, cfg=cfg,
            mesh=create_mesh() if meshed else None,
        )
        _SWEEP_CACHE[key] = (
            np.asarray(f.user_factors), np.asarray(f.item_factors)
        )
    return _SWEEP_CACHE[key]


class TestSolveModes:
    """"pallas" (the chip's solve, interpreted here) must reproduce the
    chunked solve to float tolerance, explicit and implicit."""

    @pytest.mark.parametrize("implicit", [False, True])
    def test_alternate_modes_match_chunked(self, implicit):
        chunked = sweep_factors("chunked", implicit=implicit)
        out = sweep_factors("pallas", implicit=implicit)
        np.testing.assert_allclose(chunked[0], out[0], rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(chunked[1], out[1], rtol=2e-3, atol=2e-4)

    def test_unknown_mode_fails_loudly(self):
        from predictionio_tpu.ops.als import ALSConfig, als_train_coo

        cfg = ALSConfig(rank=4, iterations=1, solve_mode="bogus")
        # unknown mode silently behaving like "chunked" would hide typos
        with pytest.raises(ValueError, match="solve_mode"):
            als_train_coo(
                np.array([0, 1], dtype=np.int32),
                np.array([0, 1], dtype=np.int32),
                np.ones(2, dtype=np.float32),
                n_users=2, n_items=2, cfg=cfg,
            )


class TestPallasModeGuards:
    """Explicit solve_mode="pallas" outside the kernel's VMEM envelope must
    fail loudly — "auto" silently falls back instead. (Meshes are accepted
    since round 3: the kernel runs per-device inside shard_map; equality
    tests live in tests/test_parallel.py.)"""

    def test_pallas_accepts_mesh(self):
        from predictionio_tpu.ops.als import ALSConfig, als_train_coo
        from predictionio_tpu.parallel.mesh import create_mesh

        u = np.array([0, 1, 2], dtype=np.int32)
        i = np.array([0, 1, 0], dtype=np.int32)
        v = np.ones(3, dtype=np.float32)
        cfg = ALSConfig(rank=4, iterations=1, solve_mode="pallas")
        factors = als_train_coo(
            u, i, v, n_users=3, n_items=2, cfg=cfg, mesh=create_mesh()
        )
        assert np.isfinite(np.asarray(factors.user_factors)).all()

    def test_pallas_rejects_high_rank(self):
        from predictionio_tpu.ops.als import ALSConfig, als_train_coo

        u = np.array([0, 1, 2], dtype=np.int32)
        i = np.array([0, 1, 0], dtype=np.int32)
        v = np.ones(3, dtype=np.float32)
        cfg = ALSConfig(rank=88, iterations=1, solve_mode="pallas")
        with pytest.raises(ValueError, match="rank"):
            als_train_coo(u, i, v, n_users=3, n_items=2, cfg=cfg)


class TestRowOrderInvariance:
    """The order of a row's ratings inside its bucket is not part of the
    result: the Gramian sum over K is permutation-invariant *in exact
    arithmetic*, and in float32 another order only reassociates the
    einsum's accumulation — ~1e-5 per solve, amplified through the
    alternating iterations (ROUND7_NOTES.md pins the analysis). Whatever
    order ``bucketize`` leaves is the one that trains (a host-side sort
    of each row bought the chip's gather nothing: PERF.md §6, PR 29), and
    the sharded trainer's permuted id space leans on the same contract."""

    @staticmethod
    def _shuffled(side, rng):
        """Each row's valid (idx, val) pairs in a random order; padding
        stays at the row's tail, where the counts-based mask expects it."""
        out = []
        for b in side.buckets:
            idx, val = b.idx.copy(), b.val.copy()
            for r, c in enumerate(b.counts):
                order = rng.permutation(int(c))
                idx[r, :c], val[r, :c] = idx[r, order], val[r, order]
            out.append(dataclasses.replace(b, idx=idx, val=val))
        return dataclasses.replace(side, buckets=out)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_training_result_unchanged_by_row_order(self, implicit):
        from predictionio_tpu.ops.als import (
            ALSConfig, ALSFactors, als_train, bucketize, rmse,
        )

        u, i, v, n_u, n_i = _sweep_data()
        cfg = ALSConfig(
            rank=12, iterations=3, lambda_=0.05, implicit_prefs=implicit,
            alpha=1.0, seed=2, solve_mode="chunked",
        )
        by_user = bucketize(u, i, v, n_u, n_i, pad_to_blocks=True)
        by_item = bucketize(i, u, v, n_i, n_u, pad_to_blocks=True)
        rng = np.random.default_rng(3)
        base = sweep_factors("chunked", implicit=implicit)
        f = als_train(
            self._shuffled(by_user, rng), self._shuffled(by_item, rng), cfg
        )
        shuffled = (np.asarray(f.user_factors), np.asarray(f.item_factors))
        np.testing.assert_allclose(base[0], shuffled[0], rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(base[1], shuffled[1], rtol=1e-3, atol=1e-4)
        r_base = rmse(ALSFactors(*base, rank=12), u, i, v)
        r_shuffled = rmse(ALSFactors(*shuffled, rank=12), u, i, v)
        assert abs(r_base - r_shuffled) < 1e-3


class TestTrainEntries:
    """The ways into a job: ``als_train_coo`` is bucketize twice and
    ``als_train``, which also takes matrices a caller staged itself; what
    the host does before the first program changes nothing of what the
    device computes."""

    @pytest.mark.parametrize("implicit", [False, True])
    def test_coo_entry_equals_prebucketized_matrices_bitwise(self, implicit):
        from predictionio_tpu.ops.als import (
            ALSConfig, als_train, als_train_coo, bucketize,
        )

        u, i, v, n_u, n_i = _sweep_data()
        cfg = ALSConfig(
            rank=12, iterations=3, lambda_=0.05, implicit_prefs=implicit,
            alpha=1.0, seed=2, solve_mode="chunked",
        )
        coo = als_train_coo(u, i, v, n_users=n_u, n_items=n_i, cfg=cfg)
        staged = als_train(
            bucketize(u, i, v, n_u, n_i, pad_to_blocks=True),
            bucketize(i, u, v, n_i, n_u, pad_to_blocks=True),
            cfg,
        )
        np.testing.assert_array_equal(
            np.asarray(coo.user_factors), np.asarray(staged.user_factors))
        np.testing.assert_array_equal(
            np.asarray(coo.item_factors), np.asarray(staged.item_factors))

    def test_staged_inputs_train_and_report_their_levers(self):
        """Pre-staged callers (``bench.py``, ``tools/prewarm_cache.py``)
        hand ``als_train`` device tensors: the run's resolved levers are
        in the profile."""
        from predictionio_tpu.ops.als import (
            ALSConfig, als_train, bucketize, stage,
        )

        rng = np.random.default_rng(7)
        u = rng.integers(0, 50, 500).astype(np.int32)
        i = rng.integers(0, 30, 500).astype(np.int32)
        v = np.ones(500, dtype=np.float32)
        bu = stage(bucketize(u, i, v, 50, 30, pad_to_blocks=True))
        bi = stage(bucketize(i, u, v, 30, 50, pad_to_blocks=True))
        profile: dict = {}
        factors = als_train(
            bu, bi, ALSConfig(rank=4, iterations=1), profile=profile,
        )
        assert np.isfinite(np.asarray(factors.user_factors)).all()
        assert "sort_gather" not in profile
        assert profile["fused_gather"] is False  # chunked on CPU
        assert profile["gather_dtype"] == "f32"

    def test_item_side_failure_leaves_as_itself(self, monkeypatch):
        """An exception in the item side's bucketize surfaces from
        ``als_train_coo`` unchanged."""
        from predictionio_tpu.ops import als

        class ItemSideBroke(Exception):
            pass

        boom = ItemSideBroke("item side")
        inner, calls = als.bucketize, []

        def bucketize(rows, cols, *args, **kwargs):
            calls.append(rows)
            if len(calls) == 2:
                raise boom
            return inner(rows, cols, *args, **kwargs)

        monkeypatch.setattr(als, "bucketize", bucketize)
        u, i, v, n_u, n_i = _sweep_data()
        with pytest.raises(ItemSideBroke) as raised:
            als.als_train_coo(
                u, i, v, n_users=n_u, n_items=n_i,
                cfg=als.ALSConfig(rank=4, iterations=2, solve_mode="chunked"),
            )
        assert raised.value is boom


class TestGatherDtype:
    """bf16 gathers must track the f32 result closely (input rounding at
    2^-8 relative; the λ·n_u ridge keeps solves stable) and fail loudly on
    unknown dtypes. Rides the shared sweep cache (tier-1 budget): the
    f32 leg IS TestSolveModes' chunked baseline, so only the bf16 legs
    train."""

    @pytest.mark.parametrize("implicit", [False, True])
    def test_bf16_tracks_f32(self, implicit):
        f32 = sweep_factors("chunked", implicit=implicit)
        bf16 = sweep_factors("chunked", implicit=implicit, gather="bf16")
        rel = np.linalg.norm(f32[0] - bf16[0]) / np.linalg.norm(f32[0])
        assert np.isfinite(bf16[0]).all()
        assert rel < 0.05, rel  # tracks, within reduced-precision drift

    def test_bf16_rmse_within_bench_gate(self):
        """The bench's bf16 RMSE gate (docs/performance.md#levers) holds
        at test scale too: reduced-precision gathers move training RMSE
        by far less than the documented 0.01 bound."""
        from predictionio_tpu.ops.als import ALSFactors, rmse

        u, i, v, _, _ = _sweep_data()
        f32 = sweep_factors("chunked")
        bf16 = sweep_factors("chunked", gather="bf16")
        r_f32 = rmse(ALSFactors(*f32, rank=12), u, i, v)
        r_bf16 = rmse(ALSFactors(*bf16, rank=12), u, i, v)
        assert abs(r_f32 - r_bf16) <= 0.01, (r_f32, r_bf16)

    def test_unknown_dtype_fails_loudly(self):
        from predictionio_tpu.ops.als import ALSConfig, als_train_coo

        cfg = ALSConfig(rank=4, iterations=1, gather_dtype="f16")
        with pytest.raises(ValueError, match="gather_dtype"):
            als_train_coo(
                np.array([0], dtype=np.int32), np.array([0], dtype=np.int32),
                np.ones(1, dtype=np.float32), n_users=1, n_items=1, cfg=cfg,
            )


class TestFusedGather:
    """Under the ``pallas`` solve a bucket as wide as the rank builds its
    normal equations with the fused gather+Gramian kernel: the same
    factors as the einsum build of the ``chunked`` solve, and the kernel
    is traced where the rule says and nowhere else."""

    @staticmethod
    def _fused_calls(width, rank, implicit):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        bucket = (
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8, width), jnp.int32),
            jnp.ones((1, 8, width), jnp.float32), jnp.full((1, 8), width, jnp.int32),
        )
        text = str(jax.make_jaxpr(functools.partial(
            als._als_half_body, rank=rank, implicit=implicit, n_rows=8,
            solve_mode="pallas",
        ))(jnp.ones((40, rank)), (bucket,), jnp.float32(0.1), jnp.float32(1.0)))
        return text.count("gramian_fused")

    @pytest.mark.parametrize("implicit", [False, True])
    def test_fused_matches_einsum_build(self, implicit):
        einsum = sweep_factors("chunked", implicit=implicit)
        fused = sweep_factors("pallas", implicit=implicit)
        np.testing.assert_allclose(
            einsum[0], fused[0], rtol=2e-3, atol=2e-4
        )
        np.testing.assert_allclose(
            einsum[1], fused[1], rtol=2e-3, atol=2e-4
        )
        # the sweep's rank is 12: a bucket of 8 slots goes dual (explicit)
        # or builds by einsum (implicit), one of 16 is the kernel's
        assert self._fused_calls(8, 12, implicit) == 0
        assert self._fused_calls(16, 12, implicit) >= 1

    def test_fused_on_mesh_matches_single_device(self):
        """Under a data mesh the whole fused build+solve runs per-device
        inside shard_map; factors must match the unmeshed fused run."""
        single = sweep_factors("pallas")
        meshed = sweep_factors("pallas", meshed=True)
        np.testing.assert_allclose(
            single[0], meshed[0], rtol=2e-3, atol=2e-4
        )


class TestLeverDefaults:
    """The resolved levers, pinned WITHOUT training anything:
    ``resolve_levers`` is the one home for the resolution the trainer,
    the bench and the ledger all read."""

    def test_defaults_resolve_fast_paths_on(self):
        from predictionio_tpu.ops.als import ALSConfig

        levers = ALSConfig().resolve_levers()
        # CPU test host: auto solve resolves chunked, so fused follows
        # it off; these three are all a run resolves
        assert set(levers) == {"solve_mode", "gather_dtype", "fused_gather"}
        assert levers["solve_mode"] == "chunked"
        assert levers["fused_gather"] is False
        assert levers["gather_dtype"] == "f32"

    def test_pallas_solver_resolves_fused_on(self):
        from predictionio_tpu.ops.als import ALSConfig

        levers = ALSConfig(solve_mode="pallas").resolve_levers()
        assert levers["fused_gather"] is True
        # a value the solve mode gives, not a field
        assert ALSConfig(solve_mode="chunked").resolve_levers()["fused_gather"] is False
        with pytest.raises(TypeError):
            ALSConfig(fused_gather=False)

    def test_explicit_opt_outs(self):
        from predictionio_tpu.ops.als import ALSConfig

        levers = ALSConfig(solve_mode="chunked", gather_dtype="bf16").resolve_levers()
        assert levers["solve_mode"] == "chunked"
        assert levers["gather_dtype"] == "bf16"
        # the solve has two options
        assert {f.name for f in dataclasses.fields(ALSConfig)} == {
            "rank", "iterations", "lambda_", "implicit_prefs", "alpha", "seed",
            "solve_mode", "gather_dtype",
        }


class TestAllocBlock:
    """Right-sized bucket allocation (round 12): blocks cap at the
    device bound but shrink to the bucket's pow2 row envelope — sentinel
    padding rows cost real solve FLOPs (74–99% of them at the bench's
    CPU-fallback scale before the fix)."""

    def test_alloc_block_arithmetic(self):
        from predictionio_tpu.ops.als import _alloc_block

        assert _alloc_block(32768, 1) == 8  # sublane floor
        assert _alloc_block(32768, 16) == 16
        assert _alloc_block(8192, 7) == 8
        assert _alloc_block(128, 1051) == 2048  # pow2 envelope
        assert _alloc_block(32, 10_000) == 8192  # device bound caps
        assert _alloc_block(512, 1024) == 1024

    def test_bucketize_allocates_right_sized_blocks(self):
        from predictionio_tpu.ops.als import _alloc_block, bucketize

        rng = np.random.default_rng(3)
        nnz, n_u, n_i = 8000, 400, 150
        u = rng.integers(0, n_u, nnz).astype(np.int32)
        i = rng.integers(0, n_i, nnz).astype(np.int32)
        v = np.ones(nnz, dtype=np.float32)
        side = bucketize(u, i, v, n_u, n_i, pad_to_blocks=True)
        for b in side.buckets:
            real = int((b.counts > 0).sum())
            block = _alloc_block(b.width, real)
            assert b.rows.shape[0] == -(-real // block) * block
            # the pow2 envelope bounds waste: less than one block spare
            assert b.rows.shape[0] - real < block

    def test_stage_keeps_right_sized_chunks(self):
        """stage() must not re-pad a right-sized bucket back up to a
        full device block (that would undo the allocation win)."""
        from predictionio_tpu.ops.als import bucketize, stage

        rng = np.random.default_rng(4)
        u = rng.integers(0, 100, 3000).astype(np.int32)
        i = rng.integers(0, 60, 3000).astype(np.int32)
        v = np.ones(3000, dtype=np.float32)
        side = bucketize(u, i, v, 100, 60, pad_to_blocks=True)
        staged = stage(side)
        for b, s in zip(side.buckets, staged.buckets):
            assert int(np.prod(s.rows.shape)) == b.rows.shape[0]


def _ladder_data(seed=11):
    """Degrees in every rung of the ladder on both sides: most users
    hold one to four ratings (as most rows of a recommendation job do),
    a few hold up to 40; items follow a power law, so the tail's items
    hold one or two."""
    rng = np.random.default_rng(seed)
    n_u, n_i = 500, 200
    degrees = rng.choice(
        [1, 1, 1, 2, 2, 2, 3, 4, 5, 8, 9, 16, 17, 32, 33, 40], size=n_u
    )
    w = 1.0 / np.arange(1, n_i + 1) ** 1.2
    u = np.repeat(np.arange(n_u), degrees).astype(np.int32)
    i = np.concatenate([
        rng.choice(n_i, size=d, replace=False, p=w / w.sum()) for d in degrees
    ]).astype(np.int32)
    v = rng.integers(1, 6, len(u)).astype(np.float32)
    return u, i, v, n_u, n_i


class TestBucketLadder:
    """The default ladder is fine under the rank (1, 2, 4, 8, 16, 32):
    a padded slot adds an exact zero to the Gramian and to the right-hand
    side, so the narrow rungs change what is gathered, not what is
    solved; and a job with no narrow rows builds what it always built."""

    @pytest.mark.parametrize("mode", ["chunked", "pallas"])
    @pytest.mark.parametrize("implicit", [False, True])
    def test_factors_equal_the_old_ladders(self, implicit, mode):
        from predictionio_tpu.ops.als import (
            ALSConfig, ALSFactors, als_train, bucketize, rmse,
        )

        u, i, v, n_u, n_i = _ladder_data()
        cfg = ALSConfig(
            rank=12, iterations=3, lambda_=0.05, implicit_prefs=implicit,
            alpha=1.0, seed=2, solve_mode=mode,
        )

        def train(**ladder):
            by_user = bucketize(u, i, v, n_u, n_i, pad_to_blocks=True, **ladder)
            by_item = bucketize(i, u, v, n_i, n_u, pad_to_blocks=True, **ladder)
            widths = {b.width for b in by_user.buckets + by_item.buckets}
            f = als_train(by_user, by_item, cfg)
            return widths, np.asarray(f.user_factors), np.asarray(f.item_factors)

        widths, x_new, y_new = train()
        assert {1, 2, 4, 8, 16, 32, 128} <= widths
        widths_old, x_old, y_old = train(bucket_widths=OLD_LADDER)
        assert widths_old == widths - {1, 2, 4, 16}
        # the contract of TestRowOrderInvariance: equal up to float
        # reassociation
        np.testing.assert_allclose(x_new, x_old, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(y_new, y_old, rtol=1e-3, atol=1e-4)
        r_new = rmse(ALSFactors(x_new, y_new, rank=12), u, i, v)
        r_old = rmse(ALSFactors(x_old, y_old, rank=12), u, i, v)
        assert abs(r_new - r_old) < 1e-3

    @pytest.mark.parametrize("pad_to_blocks", [False, True])
    def test_rows_above_32_ratings_build_the_old_buckets(self, pad_to_blocks):
        """Which buckets a job builds follows from its degrees: with
        every row above 32 ratings (MovieLens-20M's users hold 20 or
        more, the median 68) the finer ladder changes no array."""
        from predictionio_tpu.ops.als import DEFAULT_BUCKET_WIDTHS

        rng = np.random.default_rng(13)
        n_u, n_i = 120, 900
        degrees = rng.integers(33, 700, size=n_u)
        u = np.repeat(np.arange(n_u), degrees).astype(np.int32)
        i = np.concatenate(
            [rng.choice(n_i, size=d, replace=False) for d in degrees]
        ).astype(np.int32)
        v = rng.normal(size=len(u)).astype(np.float32)
        new = bucketize(u, i, v, n_u, n_i, pad_to_blocks=pad_to_blocks)
        old = bucketize(
            u, i, v, n_u, n_i, bucket_widths=OLD_LADDER,
            pad_to_blocks=pad_to_blocks,
        )
        assert set(DEFAULT_BUCKET_WIDTHS) > set(OLD_LADDER)
        assert [b.width for b in new.buckets] == [128, 512, 2048]
        assert len(new.buckets) == len(old.buckets)
        for a, b in zip(new.buckets, old.buckets):
            for field in ("rows", "idx", "val", "counts"):
                got, want = getattr(a, field), getattr(b, field)
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("width", [1, 2, 3, 4, 8, 16, 24, 32, 64, 127])
    def test_block_rows_bounded_under_the_rank(self, width):
        """A block's normal equations are [R, R, B] whatever the width:
        the rows of a block are bounded for a default width and for a
        caller's own alike (the fall-through gave 524,288 at width 2)."""
        from predictionio_tpu.ops.als import (
            DEFAULT_BUCKET_WIDTHS, _alloc_block, _block_rows_for,
        )

        assert _block_rows_for(width) <= 16384
        assert _alloc_block(width, 10_000_000) <= 16384
        if width in DEFAULT_BUCKET_WIDTHS and width <= 16:
            assert _block_rows_for(width) == 16384


class TestHbmBytesModel:
    """The roofline bytes accounting (``pio profile --train-smoke`` /
    bench est_hbm_*), pinned on hand-computed arithmetic so the model
    cannot silently drift from the kernels it describes."""

    @staticmethod
    def _staged(rows, width, idx_dtype=np.int32):
        from predictionio_tpu.ops.als import StagedMatrix, _StagedBucket

        bucket = _StagedBucket(
            rows=np.zeros((1, rows), np.int32),
            idx=np.zeros((1, rows, width), idx_dtype),
            val=np.zeros((1, rows, width), np.float32),
            counts=np.zeros((1, rows), np.int32),
        )
        return StagedMatrix(n_rows=rows, n_cols=64, nnz=rows * width,
                            buckets=[bucket])

    def test_einsum_path_counts_gather_at_dtype_width(self):
        from predictionio_tpu.ops.als import estimate_iteration_hbm_bytes

        side = self._staged(rows=4, width=16)
        empty = self._staged(rows=0, width=8)
        rank = 8
        # per row: gather 16·8·elt, idx+val 16·(4+4), counts 4, out 8·4
        f32 = estimate_iteration_hbm_bytes(side, empty, rank, "f32")
        assert f32 == 4 * (16 * 8 * 4 + 16 * 8 + 4 + 32)
        bf16 = estimate_iteration_hbm_bytes(side, empty, rank, "bf16")
        assert bf16 == 4 * (16 * 8 * 2 + 16 * 8 + 4 + 32)

    @pytest.mark.parametrize("dtype", ["f32", "bf16"])
    def test_fused_path_counts_lane_padded_f32_rows(self, dtype):
        """The fused kernel DMAs whole 128-lane f32 rows (bf16 upcasts
        at entry), so its gather bytes are dtype-INDEPENDENT and the
        [B, R, R] transpose round trip is charged."""
        from predictionio_tpu.ops.als import estimate_iteration_hbm_bytes

        side = self._staged(rows=4, width=16)
        empty = self._staged(rows=0, width=8)
        rank = 8
        expect = 4 * (
            16 * 128 * 4  # per-rating lane-padded row DMA
            + 16 * 8  # idx + val
            + 4  # counts
            + 3 * 8 * 8 * 4  # A write + transposed round trip
            + 2 * 8 * 4  # rhs + solution
        )
        got = estimate_iteration_hbm_bytes(
            side, empty, rank, dtype, fused_gather=True
        )
        assert got == expect

    def test_fused_gate_spares_narrow_buckets(self):
        """Buckets narrower than the rank keep the einsum build (the
        _solve_side_traced auto-gate) and must be charged accordingly."""
        from predictionio_tpu.ops.als import estimate_iteration_hbm_bytes

        narrow = self._staged(rows=4, width=4)  # width < rank
        empty = self._staged(rows=0, width=8)
        rank = 8
        fused = estimate_iteration_hbm_bytes(
            narrow, empty, rank, "f32", fused_gather=True
        )
        plain = estimate_iteration_hbm_bytes(narrow, empty, rank, "f32")
        assert fused == plain

    def test_topk_bytes_model(self):
        """Serve-side companion: streaming removes BOTH score-matrix
        trips; everything else is identical."""
        from predictionio_tpu.ops.scoring import estimate_topk_hbm_bytes

        b, n, r, k = 8, 1000, 8, 10
        factors = b * r * 4 + n * r * 4
        results = b * k * 8
        dense = estimate_topk_hbm_bytes(b, n, r, k, streaming=False)
        stream = estimate_topk_hbm_bytes(b, n, r, k, streaming=True)
        assert dense == factors + 2 * b * n * 4 + results
        assert stream == factors + results
        assert dense - stream == 2 * b * n * 4


class TestFusedTopK:
    """The serve-side fused score+select entries must reproduce the
    dense kernels exactly — same items, same order, scores to f32
    reassociation tolerance — on BOTH dispatch legs: the XLA fallback
    ("never"/off-TPU) and the Pallas streaming kernel ("always",
    interpret mode on CPU). The score contract is the fleet merge's
    ``merged_matches_reference`` (one home, fleet/merge.py)."""

    @staticmethod
    def _item_scores(scores, idx):
        return [
            {"item": str(int(i)), "score": float(s)}
            for s, i in zip(np.asarray(scores), np.asarray(idx))
            if i >= 0
        ]

    def _assert_matches(self, got, want):
        from predictionio_tpu.fleet.merge import merged_matches_reference

        got_s, got_i = got
        want_s, want_i = want
        for row in range(np.asarray(want_i).shape[0]):
            assert merged_matches_reference(
                {"itemScores": self._item_scores(got_s[row], got_i[row])},
                {"itemScores": self._item_scores(want_s[row], want_i[row])},
            ), (row, got_i[row], want_i[row])

    @pytest.mark.parametrize("mode", ["never", "always"])
    def test_users_fused_matches_dense(self, mode):
        from predictionio_tpu.ops.scoring import (
            top_k_for_users, top_k_for_users_fused,
        )

        rng = np.random.default_rng(2)
        uf = rng.normal(size=(12, 8)).astype(np.float32)
        itf = rng.normal(size=(64, 8)).astype(np.float32)
        users = np.array([1, 4, 9, 11], dtype=np.int32)
        want = top_k_for_users(uf, itf, users, k=8)
        got = top_k_for_users_fused(uf, itf, users, k=8, mode=mode)
        # ranking exact — same items, same order
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        self._assert_matches(got, want)

    @pytest.mark.parametrize("mode", ["never", "always"])
    def test_similar_items_fused_matches_dense(self, mode):
        from predictionio_tpu.ops.scoring import (
            top_k_similar_items, top_k_similar_items_fused,
        )

        rng = np.random.default_rng(5)
        itf = rng.normal(size=(40, 8)).astype(np.float32)
        queries = np.array([3, 17, 25], dtype=np.int32)
        want = top_k_similar_items(itf, queries, k=6)
        got = top_k_similar_items_fused(itf, queries, k=6, mode=mode)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        self._assert_matches(got, want)
        # self-exclusion holds on both legs
        for row, q in enumerate(queries):
            assert int(q) not in np.asarray(got[1])[row].tolist()

    @pytest.mark.parametrize("mode", ["never", "always"])
    def test_sentinel_contract_past_catalog(self, mode):
        """k beyond the catalog: sub-k slots are (-inf, -1) on BOTH
        legs — callers must never index with the sentinel."""
        from predictionio_tpu.ops.scoring import top_k_fused_vectors

        q = np.eye(2, 4, dtype=np.float32)
        itf = np.eye(3, 4, dtype=np.float32)
        scores, idx = top_k_fused_vectors(q, itf, k=5, mode=mode)
        assert np.asarray(idx).shape == (2, 5)
        assert (np.asarray(idx)[:, 3:] == -1).all()
        assert np.isneginf(np.asarray(scores)[:, 3:]).all()


def _dot_generals(fn, *args, **kwargs):
    """(precision, output shape) of every ``dot_general`` a function
    traces, loops and called functions included (``kwargs`` are the
    function's static arguments)."""
    import functools

    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(
                    (eqn.params["precision"], tuple(eqn.outvars[0].aval.shape))
                )
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(functools.partial(fn, **kwargs))(*args).jaxpr)
    return found


def _traces_dual(dots):
    """The dual body's two products are the program's only ones at
    ``Precision.HIGHEST``."""
    import jax

    highest = jax.lax.Precision.HIGHEST
    return any(
        p is not None and highest in (p if isinstance(p, tuple) else (p,))
        for p, _ in dots
    )


class TestDualForm:
    """Explicit rows narrower than the rank are solved in the space of
    their ratings (``solve_chunk_dual``): the same solution as the
    primal normal equations, from a ``k × k`` system."""

    BLOCK, CHUNKS, N_COLS = 16, 2, 300

    @classmethod
    def _block(cls, width, rank, seed=5):
        """Two chunks of one bucket as ``stage`` ships them: counts from
        1 to the width, a row of a single rating, whole padding rows."""
        rng = np.random.default_rng(seed + 100 * width + rank)
        n = cls.BLOCK * cls.CHUNKS
        counts = rng.integers(1, width + 1, n).astype(np.int32)
        counts[0], counts[1] = width, 1
        counts[-3:] = 0  # padding rows: the sentinel row id, no ratings
        rows = np.where(counts > 0, np.arange(n), n).astype(np.int32)
        idx = np.stack([
            rng.choice(cls.N_COLS, size=width, replace=False) for _ in range(n)
        ]).astype(np.int32)
        val = rng.integers(1, 6, (n, width)).astype(np.float32)
        slot = np.arange(width)[None, :] < counts[:, None]
        idx, val = np.where(slot, idx, 0), np.where(slot, val, 0.0)
        y = (rng.normal(size=(cls.N_COLS, rank)) / np.sqrt(rank)).astype(np.float32)
        shape = (cls.CHUNKS, cls.BLOCK)
        bucket = (
            rows.reshape(shape), idx.reshape(shape + (width,)).astype(np.int32),
            val.reshape(shape + (width,)).astype(np.float32), counts.reshape(shape),
        )
        return y, bucket, n

    @staticmethod
    def _primal_float64(y, bucket, n, lam):
        rows, idx, val, counts = (a.reshape((-1,) + a.shape[2:]) for a in bucket)
        rank = y.shape[1]
        ref = np.zeros((n, rank))
        for row, ids, r, c in zip(rows, idx, val, counts):
            if c == 0:
                continue
            g = y[ids[:c]].astype(np.float64)
            system = g.T @ g + lam * c * np.eye(rank)
            rhs = g.T @ r[:c].astype(np.float64)
            if lam > 0:
                ref[row] = np.linalg.solve(system, rhs)
            else:  # singular under the rank: the least-norm solution
                ref[row] = np.linalg.lstsq(g, r[:c].astype(np.float64), rcond=None)[0]
        return ref

    @pytest.mark.parametrize("mode", ["chunked", "pallas"])
    @pytest.mark.parametrize("rank", [10, 50])
    @pytest.mark.parametrize("width", [1, 2, 4, 8, 16, 32])
    @pytest.mark.parametrize("lam", [0.05, 1e-4, 0.0])
    def test_half_step_equals_the_primal_solve(self, lam, width, rank, mode):
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        y, bucket, n = self._block(width, rank)
        dual = width < rank
        if lam == 0.0 and not dual:
            pytest.skip("a primal system of fewer ratings than the rank")
        statics = dict(
            rank=rank, implicit=False, n_rows=n, solve_mode=mode,
            gather_dtype="f32", mesh=None, side="user",
        )
        args = (jnp.asarray(y), (tuple(jnp.asarray(a) for a in bucket),))
        dots = _dot_generals(
            als._als_half_body, *args, jnp.float32(0.05), jnp.float32(1.0),
            **statics,
        )
        assert _traces_dual(dots) == dual
        # the products a block traces, batch first: a k x k system and
        # the row's expansion, or (rank 10, widths 16 and 32: as wide as
        # the rank) the primal R x R system and its right-hand side,
        # which under ``pallas`` are the fused kernel's and no product
        # of the block's own
        r_pad = (rank + 7) // 8 * 8 if mode == "pallas" else rank
        shapes = [shape for _, shape in dots]
        if dual:
            assert shapes == [(self.BLOCK, width, width), (self.BLOCK, r_pad)]
        elif mode == "pallas":
            assert shapes and not [s for s in shapes if s[0] == self.BLOCK]
        else:
            assert shapes == [(self.BLOCK, rank, rank), (self.BLOCK, rank)]

        padding = np.asarray(bucket[3]).reshape(-1) == 0
        x = np.asarray(als._als_half(
            *args, jnp.float32(lam), jnp.float32(1.0), **statics))
        assert x.shape == (n, rank) and np.isfinite(x).all()
        assert not x[padding].any()
        ref = self._primal_float64(y, bucket, n, lam)
        err = np.abs(x - ref).max(axis=1) / np.maximum(
            np.abs(ref).max(axis=1), 1e-30)
        # today's primal system of a row with fewer ratings than the
        # rank is nearly singular at a small λ, in float32: its rows
        # are held to the tolerance they always had
        limit = (1e-4 if lam > 0 else 1e-3) if dual else 2e-3
        assert err[~padding].max() < limit, err.max()

    #: |x| and |y| summed and two rows' first entries, from the commit
    #: before the dual form (679fbee) on this data: rank 12, 3
    #: iterations, λ 0.05, seed 2
    PARENT = {
        ("implicit", "chunked"): (
            1051.566687341694, 483.6368902791728,
            [0.41221755743026733, 0.21378718316555023, -0.24483078718185425],
            [0.42877060174942017, 0.4944628179073334, 0.25755566358566284]),
        ("implicit", "pallas"): (
            1051.5666050482369, 483.63693218085973,
            [0.4122177064418793, 0.21378709375858307, -0.24483032524585724],
            [0.4287700355052948, 0.4944624602794647, 0.2575548589229584]),
        ("wide", "chunked"): (
            649.7081153525505, 232.41504542873008,
            [0.9020460247993469, 2.4688191413879395, 0.739781379699707],
            [1.163022756576538, 0.8243908286094666, 0.0441533625125885]),
        ("wide", "pallas"): (
            649.7080554750282, 232.41512989299372,
            [0.9020456671714783, 2.4688150882720947, 0.7397797703742981],
            [1.163022756576538, 0.824394166469574, 0.044154051691293716]),
    }

    @pytest.mark.parametrize("mode", ["chunked", "pallas"])
    @pytest.mark.parametrize("job", ["implicit", "wide"])
    def test_jobs_the_rule_leaves_out_run_the_parents_program(self, job, mode):
        """An implicit job (its base matrix is not a multiple of the
        identity), and an explicit job whose rows all hold the rank's
        ratings or more, trace nothing of the dual body and give the
        factors the parent commit gave."""
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        if job == "implicit":
            u, i, v, n_u, n_i = _ladder_data()
        else:
            rng = np.random.default_rng(17)
            n_u, n_i = 60, 40
            u, i = (a.astype(np.int32) for a in np.nonzero(
                rng.random((n_u, n_i)) < 0.6))
            v = rng.integers(1, 6, len(u)).astype(np.float32)
            assert min(np.bincount(u).min(), np.bincount(i).min()) >= 12
        cfg = als.ALSConfig(
            rank=12, iterations=3, lambda_=0.05, seed=2, alpha=1.0,
            implicit_prefs=job == "implicit", solve_mode=mode,
        )
        by_user = als.bucketize(u, i, v, n_u, n_i, pad_to_blocks=True)
        by_item = als.bucketize(i, u, v, n_i, n_u, pad_to_blocks=True)
        narrow = {b.width for b in by_user.buckets + by_item.buckets if b.width < 12}
        assert narrow == ({1, 2, 4, 8} if job == "implicit" else set())
        profile = {}
        f = als.als_train(by_user, by_item, cfg, profile=profile)
        rows = [np.count_nonzero(np.bincount(a)) for a in (u, i)]
        assert profile["solve_forms"] == {"user": [0, rows[0]], "item": [0, rows[1]]}
        staged = [als._bucket_tensors(als.stage(s)) for s in (by_user, by_item)]
        dots = _dot_generals(
            als._als_iteration_body, *staged, jnp.zeros((n_i, 12)),
            jnp.float32(0.05), jnp.float32(1.0),
            rank=12, implicit=cfg.implicit_prefs, n_users=n_u, n_items=n_i,
            solve_mode=mode,
        )
        assert dots and not _traces_dual(dots)
        # and no k x k system a block
        assert not [s for _, s in dots if len(s) == 3 and s[1] == s[2] < 12]
        x, y = (np.asarray(a, np.float64) for a in (f.user_factors, f.item_factors))
        abs_x, abs_y, x5, y7 = self.PARENT[job, mode]
        np.testing.assert_allclose(
            [np.abs(x).sum(), np.abs(y).sum()], [abs_x, abs_y], rtol=1e-6)
        np.testing.assert_allclose(x[5, :3], x5, rtol=1e-5)
        np.testing.assert_allclose(y[7, :3], y7, rtol=1e-5)
