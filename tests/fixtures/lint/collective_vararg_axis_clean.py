"""Clean twin: the helpers forward ``*args``/``**kwargs`` into their
collectives' axis slots AND every mapped call site provably feeds one —
an extra positional, or ``axis_name=`` riding the ``**kwargs``."""

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _reduce(x, *args):
    return jax.lax.psum(x, *args)


def _gather(x, **kwargs):
    return jax.lax.all_gather(x, **kwargs)


def _body(x):
    r = _reduce(x, "data")  # extra positional feeds the axis slot
    return _gather(r, axis_name="data", tiled=True)


def train(y, devices):
    mesh = Mesh(devices, ("data",))
    f = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("data", None),),
        out_specs=P(None, None),
    )
    return f(y)
