"""Deviceless AOT compile of the MULTI-CHIP programs for real v5e
topologies.

``__graft_entry__.dryrun_multichip`` proves the sharded programs execute
on a virtual CPU mesh; these tests close the other half of the claim:
the same programs COMPILE for actual TPU hardware topologies — XLA
collectives over ICI, Mosaic kernels embedded per-device via shard_map —
using compile-only v5e topologies (2×2 for the distributed-ALS mesh,
2×4 for the 8-way sequence-parallel ring). No device needed;
see tests/test_mosaic_aot.py for the single-chip kernel equivalents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# multi-chip/multi-slice AOT compiles: minutes of XLA/Mosaic work
pytestmark = pytest.mark.slow

from predictionio_tpu.ops import als
from predictionio_tpu.ops.attention import ring_attention, ulysses_attention
from predictionio_tpu.tools.prewarm_cache import _stage_avals


def _mesh(topo_name, shape, names, **topo_kwargs):
    # skip-wrapper duplicated from test_mosaic_aot rather than imported:
    # cross-importing a test module double-executes it under two module
    # identities (tests/ is a namespace package)
    from jax.experimental import topologies

    from predictionio_tpu.utils.topology import get_deviceless_topology

    try:
        topo = get_deviceless_topology(topo_name, **topo_kwargs)
    except Exception as exc:
        pytest.skip(f"deviceless TPU topology unavailable: {exc}")
    return topologies.make_mesh(topo, shape, names)


class TestDistributedALSCompile:
    """One full sharded ALS iteration on a data×model v5e 2×2 mesh —
    solve rows over ``data``, factor tables over ``model`` (the
    production distributed path of ``ops/als.py:als_train``)."""

    @pytest.fixture(scope="class")
    def problem(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh("v5e:2x2", (2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        rows_u, rows_i, nnz = 64, 32, 2048
        u = rng.integers(0, rows_u, nnz)
        i = rng.integers(0, rows_i, nnz)
        v = rng.normal(3.5, 1.0, nnz).astype(np.float32)
        bu = als.bucketize(u, i, v, rows_u, rows_i, pad_to_blocks=True)
        bi = als.bucketize(i, u, v, rows_i, rows_u, pad_to_blocks=True)
        row_sh = NamedSharding(mesh, P(None, "data"))
        tbl = NamedSharding(mesh, P("model"))
        return dict(
            mesh=mesh,
            tbl=tbl,
            ub=_stage_avals(bu, row_sh, row_multiple=2),
            ib=_stage_avals(bi, row_sh, row_multiple=2),
            y=jax.ShapeDtypeStruct((rows_i, 8), jnp.float32, sharding=tbl),
            s=jax.ShapeDtypeStruct((), jnp.float32,
                                   sharding=NamedSharding(mesh, P())),
            rows=(rows_u, rows_i),
        )

    @pytest.mark.parametrize(
        "solve_mode", ["chunked", "pallas"],
        ids=["xla-collectives", "pallas-shard_map"],
    )
    def test_sharded_iteration_compiles(self, problem, solve_mode):
        rows_u, rows_i = problem["rows"]
        it = als._als_iteration_sharded(problem["tbl"])
        compiled = it.lower(
            problem["ub"], problem["ib"], problem["y"],
            problem["s"], problem["s"],
            n_users=rows_u, n_items=rows_i, rank=8, implicit=False,
            solve_mode=solve_mode, gather_dtype="f32",
            mesh=problem["mesh"] if solve_mode == "pallas" else None,
        ).compile()
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0


class TestMultiSliceCompile:
    """The multi-HOST analogue: programs spanning TWO v5e slices (4
    chips each), where cross-slice collectives ride DCN and intra-slice
    ones ride ICI — the reference's NCCL/MPI-backend scaling story
    (SURVEY §2.8 collective-communication row), compiled for real
    topology. ``num_slices`` builds the deviceless 2-slice system."""

    @pytest.fixture(scope="class")
    def mesh8(self):
        mesh = _mesh("v5e:2x2", (8,), ("data",), num_slices=2)
        slices = {getattr(d, "slice_index", 0) for d in
                  mesh.devices.flat}
        assert slices == {0, 1}, slices
        return mesh

    def test_als_data_parallel_across_slices(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(1)
        rows_u, rows_i, nnz = 128, 64, 4096
        u = rng.integers(0, rows_u, nnz)
        i = rng.integers(0, rows_i, nnz)
        v = rng.normal(3.5, 1.0, nnz).astype(np.float32)
        bu = als.bucketize(u, i, v, rows_u, rows_i, pad_to_blocks=True)
        bi = als.bucketize(i, u, v, rows_i, rows_u, pad_to_blocks=True)
        row_sh = NamedSharding(mesh8, P(None, "data"))
        rep = NamedSharding(mesh8, P())
        it = als._als_iteration_sharded(rep)
        compiled = it.lower(
            _stage_avals(bu, row_sh, row_multiple=8),
            _stage_avals(bi, row_sh, row_multiple=8),
            jax.ShapeDtypeStruct((rows_i, 8), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            n_users=rows_u, n_items=rows_i, rank=8, implicit=False,
            solve_mode="chunked", gather_dtype="f32", mesh=None,
        ).compile()
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0

    def test_ring_attention_across_slices(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh8, P(None, None, "data", None))
        av = jax.ShapeDtypeStruct((1, 4, 8 * 256, 32), jnp.float32,
                                  sharding=sh)
        jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh=mesh8, axis="data", causal=True
            )
        ).lower(av, av, av).compile()


class TestSequenceParallelCompile:
    """Ring and Ulysses attention — forward and gradient — over an
    8-chip ``seq`` axis (v5e 2×4): ppermute / all-to-all ride ICI."""

    @pytest.fixture(scope="class")
    def setup(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh("v5e:2x4", (8,), ("seq",))
        sh = NamedSharding(mesh, P(None, None, "seq", None))
        av = jax.ShapeDtypeStruct((2, 8, 8 * 512, 64), jnp.float32,
                                  sharding=sh)
        return mesh, av

    @pytest.mark.parametrize("impl", [ring_attention, ulysses_attention],
                             ids=["ring", "ulysses"])
    def test_forward_compiles(self, setup, impl):
        mesh, av = setup
        f = functools.partial(impl, mesh=mesh, causal=True)
        compiled = jax.jit(
            lambda q, k, v: f(q, k, v)
        ).lower(av, av, av).compile()
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0

    @pytest.mark.parametrize("impl", [ring_attention, ulysses_attention],
                             ids=["ring", "ulysses"])
    def test_grad_compiles(self, setup, impl):
        mesh, av = setup

        def loss(q, k, v):
            return impl(
                q, k, v, mesh=mesh, causal=True
            ).astype(jnp.float32).sum()

        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            av, av, av
        ).compile()
