"""Sharded ALS trainer (ops/als_sharded.py): shard-count invariance,
density balancing, tri-state resolution, and the loud-conflict surface.

CI budget (the ISSUE-15 guard): conftest.py already forces 8 virtual CPU
devices BEFORE the first jax import (the pre-jax-import fixture — no
per-test subprocess is spawned, every shard count runs in-process on the
same device pool), and every equivalence case reads ONE module-level
train-once sweep over the smallest ALS recipe, so the whole file costs
five small trainings + one implicit pair.
"""

import os

import numpy as np
import pytest

from predictionio_tpu.ops.als import ALSConfig, ALSFactors, als_train_coo, rmse
from predictionio_tpu.ops.als_sharded import (
    DEFAULT_BUCKET_WIDTHS,
    SHARDS_ENV,
    _padded_widths,
    als_train_sharded,
    assign_rows_balanced,
    plan_side,
    resolve_shards,
    row_solve_flops,
)

#: the PR-12 equivalence tolerances (ROUND7_NOTES contract): sharding
#: reorders float accumulation (per-shard gathers in permuted id space,
#: psum'd Gramians), never the per-row math
RTOL, ATOL, RMSE_TOL = 1e-3, 1e-4, 1e-3


def _recipe():
    rng = np.random.default_rng(7)
    nnz, n_u, n_i = 6_000, 240, 100
    w = 1.0 / np.arange(1, n_u + 1) ** 0.8  # zipf users: skewed degrees
    u = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    v = rng.integers(1, 6, nnz).astype(np.float32)
    return u, i, v, n_u, n_i


_CFG = ALSConfig(rank=8, iterations=3, lambda_=0.05, seed=2)
_SWEEP: dict = {}


def sweep(shards, implicit=False):
    """Factors for one (shard count, mode) over the shared recipe,
    trained at most once per session. ``shards=0`` is the single-device
    reference (``als_train_coo``)."""
    key = (shards, implicit)
    if key not in _SWEEP:
        u, i, v, n_u, n_i = _recipe()
        if implicit:
            cfg = ALSConfig(
                rank=6, iterations=2, lambda_=0.1,
                implicit_prefs=True, alpha=4.0, seed=2,
            )
            v = (v > 3).astype(np.float32)
        else:
            cfg = _CFG
        if shards == 0:
            f = als_train_coo(u, i, v, n_u, n_i, cfg)
        else:
            f = als_train_sharded(
                u, i, v, n_u, n_i, cfg, shards=shards
            )
        _SWEEP[key] = (
            np.asarray(f.user_factors), np.asarray(f.item_factors)
        )
    return _SWEEP[key]


class TestShardCountInvariance:
    """The CI-runnable ALX proof: 1/2/4/8 virtual-device shards produce
    the single-device trainer's factors within the reassociation
    tolerances and its holdout RMSE within 1e-3 — sharding is a layout,
    not a model change."""

    @pytest.mark.parametrize("shards", [1, 2, 4, 8])
    def test_factors_match_single_device(self, shards):
        ref_u, ref_i = sweep(0)
        got_u, got_i = sweep(shards)
        np.testing.assert_allclose(got_u, ref_u, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got_i, ref_i, rtol=RTOL, atol=ATOL)

    @pytest.mark.parametrize("shards", [2, 8])
    def test_rmse_matches_single_device(self, shards):
        u, i, v, _, _ = _recipe()
        ref = rmse(ALSFactors(*sweep(0), rank=_CFG.rank), u, i, v)
        got = rmse(ALSFactors(*sweep(shards), rank=_CFG.rank), u, i, v)
        assert abs(ref - got) < RMSE_TOL, (ref, got)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_narrow_rungs_match_single_device(self, implicit):
        """Rows of one to four ratings, which the shared recipe has none
        of: each shard's slabs at widths 1, 2 and 4 (and their ragged
        gather) solve what the single-device trainer solves."""
        rng = np.random.default_rng(5)
        n_u, n_i = 300, 60
        degrees = rng.choice([1, 1, 2, 2, 3, 4, 9, 20], size=n_u)
        u = np.repeat(np.arange(n_u), degrees).astype(np.int32)
        i = np.concatenate(
            [rng.choice(n_i, size=d, replace=False) for d in degrees]
        ).astype(np.int32)
        v = rng.integers(1, 6, len(u)).astype(np.float32)
        cfg = ALSConfig(
            rank=6, iterations=2, lambda_=0.1, implicit_prefs=implicit,
            alpha=4.0, seed=2,
        )
        ref = als_train_coo(u, i, v, n_u, n_i, cfg)
        got = als_train_sharded(u, i, v, n_u, n_i, cfg, shards=4)
        for a, b in ((got.user_factors, ref.user_factors),
                     (got.item_factors, ref.item_factors)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
            )

    def test_implicit_psum_gramian_matches_single_device(self):
        """Implicit mode builds YᵀY as a psum of per-shard Gramians —
        the collective path the explicit sweep never touches."""
        ref_u, ref_i = sweep(0, implicit=True)
        got_u, got_i = sweep(4, implicit=True)
        np.testing.assert_allclose(got_u, ref_u, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(got_i, ref_i, rtol=2e-3, atol=2e-4)


class TestDensityBalancing:
    """Rows are dealt to shards by padded solve-FLOP weight, widest
    class first — a deliberately skewed degree histogram still splits
    within a pinned imbalance bound, and the plan surfaces the evidence
    (``profile["shard_plan"]``)."""

    @pytest.mark.parametrize("narrow", [False, True])
    def test_skewed_histogram_splits_within_bound(self, narrow):
        # 8 heavy rows (pad to 2048), 60 medium (128), 600 light (16):
        # a power-law histogram a naive row-count split would skew badly;
        # ``narrow`` adds what most rows of a real job are, one to three
        # ratings each, on the ladder's rungs 1, 2 and 4
        degrees = np.concatenate([
            np.full(8, 1_500), np.full(60, 90), np.full(600, 10),
        ])
        if narrow:
            degrees = np.concatenate([
                degrees, np.full(2_001, 1), np.full(1_503, 2), np.full(402, 3),
            ])
            assert set(_padded_widths(degrees, DEFAULT_BUCKET_WIDTHS)) == {
                1, 2, 4, 16, 128, 2048,
            }
        plan = plan_side(degrees, shards=4, rank=16)
        assert plan.flop_imbalance <= 1.15, plan.per_shard_flops
        # every shard got its fair share of the heavy class
        heavy = np.nonzero(degrees == 1_500)[0]
        per_shard = np.bincount(plan.assign[heavy], minlength=4)
        assert per_shard.tolist() == [2, 2, 2, 2]

    def test_assignment_is_deterministic(self):
        degrees = np.random.default_rng(3).integers(0, 200, 500)
        a = assign_rows_balanced(degrees, 4, rank=8)
        b = assign_rows_balanced(degrees, 4, rank=8)
        np.testing.assert_array_equal(a, b)

    def test_zero_degree_rows_even_out_table_caps(self):
        # zero-degree rows carry no FLOPs but size the per-shard table
        # cap: they must spread, not pile onto shard 0
        degrees = np.concatenate([np.full(10, 50), np.zeros(90)])
        plan = plan_side(degrees, shards=4, rank=8)
        counts = np.bincount(plan.assign, minlength=4)
        assert counts.max() - counts.min() <= 1, counts.tolist()
        assert plan.cap == int(counts.max())

    def test_row_flops_matches_iteration_accounting(self):
        # the balancing weight is the estimate_iteration_flops per-row
        # arithmetic — hand-pinned so the two can never drift apart
        rank, k = 16, 128
        assert row_solve_flops(k, rank) == (
            k * (2 * rank * rank + 2 * rank) + rank**3 / 3 + 2 * rank * rank
        )


class TestShardsResolution:
    """The tri-state (PR-12 lever discipline): explicit wins, env
    (``pio train --shards``) next, default 1 — and the 1-shard path IS
    the single-device trainer, byte-identical config resolution."""

    def test_default_resolves_one(self, monkeypatch):
        monkeypatch.delenv(SHARDS_ENV, raising=False)
        assert resolve_shards(None) == 1

    def test_env_resolves(self, monkeypatch):
        monkeypatch.setenv(SHARDS_ENV, "4")
        assert resolve_shards(None) == 4

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv(SHARDS_ENV, "4")
        assert resolve_shards(2) == 2

    def test_invalid_values_fail_loudly(self, monkeypatch):
        with pytest.raises(ValueError):
            resolve_shards(0)
        monkeypatch.setenv(SHARDS_ENV, "zero")
        with pytest.raises(ValueError):
            resolve_shards(None)
        monkeypatch.setenv(SHARDS_ENV, "-1")
        with pytest.raises(ValueError):
            resolve_shards(None)

    def test_degenerate_one_shard_is_byte_identical(self, monkeypatch):
        """Explicit ``shards=1`` == tri-state None (no env): the same
        delegation to ``als_train``, so factors are BIT-identical and
        the resolved profile dicts agree on every non-timing field."""
        monkeypatch.delenv(SHARDS_ENV, raising=False)
        rng = np.random.default_rng(1)
        u = rng.integers(0, 30, 300).astype(np.int32)
        i = rng.integers(0, 20, 300).astype(np.int32)
        v = np.ones(300, dtype=np.float32)
        cfg = ALSConfig(rank=4, iterations=1, seed=0)
        p_explicit: dict = {}
        f_explicit = als_train_sharded(
            u, i, v, 30, 20, cfg, shards=1, profile=p_explicit
        )
        p_tristate: dict = {}
        f_tristate = als_train_sharded(
            u, i, v, 30, 20, cfg, shards=None, profile=p_tristate
        )
        np.testing.assert_array_equal(
            np.asarray(f_explicit.user_factors),
            np.asarray(f_tristate.user_factors),
        )
        timing = {"stage_s", "iteration_s"}
        cfg_fields = {
            k: v for k, v in p_explicit.items() if k not in timing
        }
        assert cfg_fields == {
            k: v for k, v in p_tristate.items() if k not in timing
        }
        assert p_explicit["shards"] == 1
        # the degenerate path resolves the SAME levers today's trainer
        # records — shards=1 is not a separate trainer
        assert p_explicit["solve_mode"] == "chunked"
        assert "sort_gather" not in p_explicit
        assert p_explicit["fused_gather"] is False


class TestLoudConflicts:
    """A silently ignored flag would corrupt the hardware A/B — every
    unsupported combination raises before any device work."""

    def _tiny(self):
        return (
            np.array([0, 1, 2], dtype=np.int32),
            np.array([0, 1, 0], dtype=np.int32),
            np.ones(3, dtype=np.float32),
        )

    def test_more_shards_than_devices(self):
        u, i, v = self._tiny()
        with pytest.raises(ValueError, match="devices"):
            als_train_sharded(
                u, i, v, 3, 2,
                ALSConfig(rank=4, iterations=1), shards=16,
            )

    def test_explicit_pallas_solve_mode(self):
        u, i, v = self._tiny()
        with pytest.raises(ValueError, match="solve_mode"):
            als_train_sharded(
                u, i, v, 3, 2,
                ALSConfig(rank=4, iterations=1, solve_mode="pallas"),
                shards=2,
            )

    def test_unknown_gather_dtype(self):
        u, i, v = self._tiny()
        with pytest.raises(ValueError, match="gather_dtype"):
            als_train_sharded(
                u, i, v, 3, 2,
                ALSConfig(rank=4, iterations=1, gather_dtype="f16"),
                shards=2,
            )

    def test_algorithm_params_conflicts(self):
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            PreparedData,
        )
        from predictionio_tpu.storage import BiMap

        u, i, v = self._tiny()
        pd = PreparedData(
            user_map=BiMap({"a": 0, "b": 1, "c": 2}),
            item_map=BiMap({"x": 0, "y": 1}),
            users=u, items=i, ratings=v,
        )
        with pytest.raises(ValueError, match="distributed"):
            ALSAlgorithm(
                ALSAlgorithmParams(
                    rank=2, num_iterations=1, shards=2, distributed=True
                )
            ).train(None, pd)
        # checkpoint_every + shards is SUPPORTED since ISSUE 20 (the
        # sharded trainer snapshots canonical factors); without a
        # workflow checkpoint store it simply trains uncheckpointed
        model = ALSAlgorithm(
            ALSAlgorithmParams(
                rank=2, num_iterations=1, shards=2, checkpoint_every=1
            )
        ).train(None, pd)
        assert model.user_factors.shape[0] == 3

    def test_negative_checkpoint_every(self):
        u, i, v = self._tiny()
        with pytest.raises(ValueError, match="checkpoint_every"):
            als_train_sharded(
                u, i, v, 3, 2,
                ALSConfig(rank=4, iterations=1),
                shards=2, checkpoint_every=-1,
            )

    def test_checkpoint_cadence_without_store(self):
        u, i, v = self._tiny()
        with pytest.raises(ValueError, match="checkpoint"):
            als_train_sharded(
                u, i, v, 3, 2,
                ALSConfig(rank=4, iterations=1),
                shards=2, checkpoint=None, checkpoint_every=1,
            )


class TestProfileEvidence:
    """The resolved-lever + balance evidence the bench/ledger reads
    (docs/performance.md#levers)."""

    def test_profile_records_resolved_levers_and_plan(self):
        u, i, v, n_u, n_i = _recipe()
        profile: dict = {}
        # rides the sweep's 2-shard cache only for factors; this train
        # is the one extra profiled run the evidence test needs
        f = als_train_sharded(
            u[:1500], i[:1500], v[:1500], n_u, n_i,
            ALSConfig(rank=4, iterations=1, seed=2),
            shards=2, profile=profile,
        )
        assert np.isfinite(np.asarray(f.user_factors)).all()
        assert profile["shards"] == 2
        assert profile["solve_mode"] == "chunked"
        assert profile["fused_gather"] is False
        assert "sort_gather" not in profile
        plan = profile["shard_plan"]
        assert plan["shards"] == 2
        assert len(plan["perShardFlops"]["user"]) == 2
        assert plan["flopImbalance"]["user"] >= 1.0
        assert len(profile["iteration_s"]) == 1
        assert profile["flops_per_iteration"] > 0


class TestCLISurface:
    """``pio train --shards`` rides the env tri-state end to end (the
    flag sets PIO_TRAIN_SHARDS; the algorithm's None resolves from
    it)."""

    def test_run_workflow_parser_accepts_shards(self):
        from predictionio_tpu.tools.run_workflow import build_parser

        args = build_parser().parse_args(["--shards", "4"])
        assert args.shards == 4

    def test_console_forwards_shards(self):
        import argparse

        from predictionio_tpu.tools.console import _workflow_argv

        ns = argparse.Namespace(
            engine_dir=".", engine_variant="engine.json", batch="",
            engine_params_key=None, verbose=False,
            skip_sanity_check=False, stop_after_read=False,
            stop_after_prepare=False, eval_parallelism=0, shards=4,
        )
        argv = _workflow_argv(ns)
        assert argv[-2:] == ["--shards", "4"]
        # an explicit 0 forwards too (it must FAIL LOUDLY in
        # resolve_shards, never silently train single-device)
        ns.shards = 0
        assert _workflow_argv(ns)[-2:] == ["--shards", "0"]

    def test_env_zero_fails_loudly(self, monkeypatch):
        monkeypatch.setenv(SHARDS_ENV, "0")
        with pytest.raises(ValueError):
            resolve_shards(None)

    def test_sharded_ledger_records_key_by_shard_count(self):
        from predictionio_tpu.obs import perfledger

        bench = {
            "shardedTrain": {
                "ok": True,
                "counts": {
                    "1": {"trainS": 10.0, "rmse": 0.9, "device": "cpu"},
                    "4": {"trainS": 4.0, "rmse": 0.9, "device": "cpu"},
                },
            }
        }
        records = perfledger.sharded_records(bench)
        assert [r["metric"] for r in records] == ["train_sharded_s"] * 2
        assert [r["scale"] for r in records] == [1, 4]
        assert all(r["unit"] == "s" for r in records)
        assert all(r["noise_band"] == 0.5 for r in records)
        # shard counts never share a comparable group: `pio perf diff`
        # can never gate a 4-shard run against the 1-shard trajectory
        keys = {perfledger.comparable_key(r) for r in records}
        assert len(keys) == 2
        # a failed drive records nothing
        assert perfledger.sharded_records(
            {"shardedTrain": {"ok": False, "counts": {}}}
        ) == []
