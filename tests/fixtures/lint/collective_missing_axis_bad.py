"""Family F fixture: collective inside a mapped body with no axis
argument — a trace-time TypeError that only fires when the sharded path
actually runs (the mesh-gated trainer's hardware-day failure mode)."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _gramian_body(y_local):
    local = jax.numpy.einsum("nr,ns->rs", y_local, y_local)
    return jax.lax.psum(local)  # BAD: no axis argument


def sharded_gramian(y, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        _gramian_body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
    )
    return f(y)
