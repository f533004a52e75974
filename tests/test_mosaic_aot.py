"""Deviceless Mosaic validation of every Pallas kernel (VERDICT r4 item 3).

``jax.experimental.topologies.get_topology_desc`` builds a compile-only
TPU topology from libtpu with NO device attached, and
``jit(fn).lower(avals).compile()`` against
its devices runs the full XLA:TPU + Mosaic pipeline. These tests convert
the single worst on-chip risk — a Mosaic lowering error discovered
only there — into an offline check that runs in the CPU suite.

The argument-format key (the round-4 probe failed here):
``chips_per_host_bounds`` must be a TUPLE OF INTS, e.g. ``(1, 1, 1)``;
string forms are rejected by libtpu with a mangled type error.

On landing day this file's compiles found two real bugs in
``gramian_fused`` that interpret-mode equality testing could not see:
a 1×56 row-slice DMA violating the 128-lane tiling, and a 1-D→2-D
shape cast unsupported for bf16 vectors (see ops/pallas_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# deviceless AOT compile of every Pallas kernel: minutes of XLA/Mosaic work
pytestmark = pytest.mark.slow

from predictionio_tpu.ops.pallas_kernels import (
    gramian_fused,
    spd_solve_t,
    top_k_streaming,
)


def _topology(name: str, **kwargs):
    """Deviceless topology or skip — the lockfile retry lives in the
    shared helper (a concurrent prewarm run or test session holds
    libtpu's machine-wide lockfile transiently)."""
    from predictionio_tpu.utils.topology import get_deviceless_topology

    try:
        return get_deviceless_topology(name, **kwargs)
    except Exception as exc:  # no libtpu, or sustained contention
        pytest.skip(f"deviceless TPU topology unavailable: {exc}")


@pytest.fixture(scope="module")
def topo1():
    return _topology("v5e:1x1", chips_per_host_bounds=(1, 1, 1))


def _sds(topo, shape, dtype):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(topo.devices[0])
    )


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


class TestMosaicAOT:
    def test_spd_solve_single_device(self, topo1):
        _compile(
            functools.partial(spd_solve_t, interpret=False),
            _sds(topo1, (56, 56, 512), jnp.float32),
            _sds(topo1, (56, 512), jnp.float32),
        )

    def test_spd_solve_under_shard_map(self):
        # the exact embedding ops/als.py uses under a mesh: per-device
        # pallas blocks inside shard_map, compiled for a 4-chip slice
        from jax.experimental import topologies

        from predictionio_tpu.parallel.collectives import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        topo4 = _topology("v5e:2x2")
        mesh = topologies.make_mesh(topo4, (4,), ("data",))
        ns = NamedSharding(mesh, P("data"))
        fn = shard_map(
            functools.partial(spd_solve_t, interpret=False), mesh=mesh,
            in_specs=(P("data"), P("data")), out_specs=P("data"),
            check_vma=False,
        )
        compiled = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((4 * 56, 56, 512), jnp.float32, sharding=ns),
            jax.ShapeDtypeStruct((4 * 56, 512), jnp.float32, sharding=ns),
        ).compile()
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0

    @pytest.mark.parametrize(
        "n,b,k",
        [
            (27_000, 4, 8192),   # bench-realistic wide bucket, SMEM cap
            (300, 32, 512),      # small table (VMEM-resident y)
            (200, 2, 32_768),    # K-slice split path
        ],
    )
    def test_gramian_fused_f32(self, topo1, n, b, k):
        _compile(
            functools.partial(gramian_fused, interpret=False),
            _sds(topo1, (n, 56), jnp.float32),
            _sds(topo1, (b, k), jnp.int32),
            _sds(topo1, (b, k), jnp.float32),
            _sds(topo1, (b, k), jnp.float32),
            _sds(topo1, (b,), jnp.float32),
        )

    def test_gramian_fused_bf16_table(self, topo1):
        # bf16 tables upcast inside the kernel entry (per-row DMA floor
        # is 128 lanes × 32 bits); the flag combination must still lower
        _compile(
            functools.partial(gramian_fused, interpret=False),
            _sds(topo1, (27_000, 56), jnp.bfloat16),
            _sds(topo1, (4, 8192), jnp.int32),
            _sds(topo1, (4, 8192), jnp.float32),
            _sds(topo1, (4, 8192), jnp.float32),
            _sds(topo1, (4,), jnp.float32),
        )

    def test_top_k_streaming(self, topo1):
        _compile(
            functools.partial(top_k_streaming, k=10, interpret=False),
            _sds(topo1, (512, 50), jnp.float32),
            _sds(topo1, (60_000, 50), jnp.float32),
        )

    def test_top_k_streaming_with_exclusions(self, topo1):
        # the similarproduct/ecommerce serving path: seen/blacklisted
        # items masked inside the kernel — a distinct program from the
        # plain top-k (extra SMEM block + compare loop)
        def with_excl(q, items, excl):
            return top_k_streaming(q, items, 10, exclude_idx=excl,
                                   interpret=False)

        _compile(
            with_excl,
            _sds(topo1, (512, 50), jnp.float32),
            _sds(topo1, (60_000, 50), jnp.float32),
            _sds(topo1, (512, 64), jnp.int32),
        )

    def test_gramian_fused_implicit_yty(self, topo1):
        # implicit mode (similarproduct's training): the yty base term
        # rides into the kernel — a distinct program from the explicit
        # yty=None path the other fused tests cover
        def with_yty(y, idx, w2, rhs, ridge, yty):
            return gramian_fused(y, idx, w2, rhs, ridge, yty=yty,
                                 interpret=False)

        _compile(
            with_yty,
            _sds(topo1, (27_000, 56), jnp.float32),
            _sds(topo1, (4, 8192), jnp.int32),
            _sds(topo1, (4, 8192), jnp.float32),
            _sds(topo1, (4, 8192), jnp.float32),
            _sds(topo1, (4,), jnp.float32),
            _sds(topo1, (56, 56), jnp.float32),
        )

    def test_implicit_als_iteration(self, topo1):
        # the full implicit-mode training program (Hu-Koren confidence
        # weighting: YᵀY einsums + c−1 gramian weights) at moderate
        # shapes with the pallas solver — what the implicit_gate queue
        # step will run on hardware
        from jax.sharding import SingleDeviceSharding

        from predictionio_tpu.ops import als
        from predictionio_tpu.tools.prewarm_cache import _stage_avals

        rng = np.random.default_rng(2)
        n_u, n_i, nnz = 2_000, 500, 40_000
        u = rng.integers(0, n_u, nnz)
        i = rng.integers(0, n_i, nnz)
        v = rng.integers(1, 5, nnz).astype(np.float32)
        bu = als.bucketize(u, i, v, n_u, n_i, pad_to_blocks=True)
        bi = als.bucketize(i, u, v, n_i, n_u, pad_to_blocks=True)
        sh = SingleDeviceSharding(topo1.devices[0])
        compiled = als._als_iteration.lower(
            _stage_avals(bu, sh), _stage_avals(bi, sh),
            jax.ShapeDtypeStruct((n_i, 32), jnp.float32, sharding=sh),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=sh),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=sh),
            n_users=n_u, n_items=n_i, rank=32, implicit=True,
            solve_mode="pallas", gather_dtype="f32", mesh=None,
        ).compile()
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0
