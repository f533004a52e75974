"""Clean twin: collectives reduce over bound mesh axes — including a
replicated axis the specs never mention (legal, and the false positive
the rule must not produce)."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def reduce_rows(x, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        lambda s: jax.lax.psum(s, "data"),
        mesh=mesh,
        in_specs=P("data", None),
        out_specs=P(None, None),
    )
    return f(x)


def replicated_axis_reduce(x, devices):
    mesh = Mesh(devices, ("data", "model"))
    f = shard_map(
        # "model" never appears in the specs, but the mesh binds it:
        # a replicated-axis reduction, perfectly legal
        lambda s: jax.lax.psum(s, "model"),
        mesh=mesh,
        in_specs=P("data", None),
        out_specs=P("data", None),
    )
    return f(x)
