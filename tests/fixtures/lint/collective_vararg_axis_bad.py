"""Family G extension of the collective-axis twins: the helper forwards
its own ``*args`` into the collective's axis slot, and the mapped call
site feeds nothing extra — the missing axis is a static fact one hop
deep (the per-file rule's documented ``*args/**kwargs calls pass``
skip, now judged through the call graph)."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _reduce(x, *args):
    return jax.lax.psum(x, *args)


def _body(x):
    return _reduce(x)  # BAD: nothing fed into the helper's axis slot


def train(y, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
    )
    return f(y)
