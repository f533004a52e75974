"""Family F fixture: collective names an axis the mesh does not bind."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def reduce_rows(x, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        lambda s: jax.lax.psum(s, "batch"),  # BAD: the mesh binds "data"
        mesh=mesh,
        in_specs=P("data", None),
        out_specs=P(None, None),
    )
    return f(x)
