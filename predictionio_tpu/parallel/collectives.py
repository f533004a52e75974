"""Collective-communication surface.

The reference has **zero in-tree collective code** — all inter-node traffic
rides Spark shuffle / akka RPC behind the ``RDD`` boundary (SURVEY §2.8,
§5 "Distributed communication backend"). The TPU-native equivalent is XLA
collectives over ICI/DCN, expressed here as explicit, user-callable wrappers
over ``jax.lax`` primitives inside ``shard_map``. Framework code (sharded
aggregation, ring attention, sweep reduction) builds on these; inside plain
``pjit`` programs XLA inserts the same collectives automatically from
sharding annotations — these helpers are for the cases where the schedule
must be explicit (rings, manual reductions).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def all_reduce_sum(x, mesh: Mesh, axis: str):
    """Sum ``x``'s per-device shards (leading dim sharded over ``axis``) —
    the ``psum`` analogue of the reference's ``aggregateByKey`` merges
    (``PEventAggregator.scala:198-203``). Returns the replicated sum of the
    per-shard slices."""
    f = shard_map(
        lambda s: jax.lax.psum(s, axis),
        mesh=mesh,
        in_specs=P(axis, *([None] * (x.ndim - 1))),
        out_specs=P(*([None] * x.ndim)),
    )
    return jax.jit(f)(x)


def all_gather_rows(x, mesh: Mesh, axis: str):
    """Gather row-shards of ``x`` onto every device (replicated result)."""
    f = shard_map(
        lambda s: jax.lax.all_gather(s, axis, tiled=True),
        mesh=mesh,
        in_specs=P(axis, *([None] * (x.ndim - 1))),
        out_specs=P(*([None] * x.ndim)),
        # the gathered result IS replicated; the static VMA check just can't
        # prove it through all_gather
        check_vma=False,
    )
    return jax.jit(f)(x)


def reduce_scatter_rows(x, mesh: Mesh, axis: str):
    """Sum a replicated array across devices, leaving each device 1/Nth of
    the rows (``reduce_scatter`` over ICI)."""
    f = shard_map(
        lambda s: jax.lax.psum_scatter(s, axis, tiled=True),
        mesh=mesh,
        in_specs=P(*([None] * x.ndim)),
        out_specs=P(axis, *([None] * (x.ndim - 1))),
    )
    return jax.jit(f)(x)


def ring_shift(x, mesh: Mesh, axis: str, shift: int = 1):
    """Rotate row-shards around the ``axis`` ring by ``shift`` positions
    (``ppermute`` — the building block of ring attention / pipelined
    exchanges). Shard i's rows end up on shard (i + shift) mod N."""
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]
    f = shard_map(
        lambda s: jax.lax.ppermute(s, axis, perm),
        mesh=mesh,
        in_specs=P(axis, *([None] * (x.ndim - 1))),
        out_specs=P(axis, *([None] * (x.ndim - 1))),
    )
    return jax.jit(f)(x)


def sharded_matmul_allreduce(a, b, mesh: Mesh, axis: str):
    """Contraction-dim-sharded matmul with ICI all-reduce: ``a [M, K/N]`` ×
    ``b [K/N, P]`` per device, psum of partial products — the canonical
    "model-parallel matmul" schedule from the scaling-book recipe."""
    f = shard_map(
        lambda sa, sb: jax.lax.psum(
            jnp.einsum("mk,kp->mp", sa, sb,
                       preferred_element_type=jnp.float32),
            axis,
        ),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, None),
    )
    return jax.jit(f)(a, b)
