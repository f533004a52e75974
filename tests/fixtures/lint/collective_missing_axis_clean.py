"""Clean twin: collectives inside mapped bodies carry their axis —
positionally or as axis_name= — and an axis-less call OUTSIDE any mapped
body is not this rule's business (the first unit test catches it)."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _gramian_body(y_local):
    local = jax.numpy.einsum("nr,ns->rs", y_local, y_local)
    return jax.lax.psum(local, "data")  # positional axis


def _gather_body(y_local):
    # axis via keyword: equally statically provable
    return jax.lax.all_gather(y_local, axis_name="data", tiled=True)


def sharded_gramian(y, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        _gramian_body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
    )
    g = shard_map(
        _gather_body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
    )
    return f(y), g(y)


def unmapped_helper(x):
    # not inside any shard_map/pmap body: out of this rule's scope (and
    # the first direct call would raise immediately anyway)
    return jax.lax.psum(x)
