"""Clean twin: one spec per mapped operand."""

from jax import shard_map
from jax.sharding import PartitionSpec as P


def sharded_matmul(a, b, mesh):
    f = shard_map(
        lambda sa, sb: sa @ sb,
        mesh=mesh,
        in_specs=(P("x", None), P(None, None)),
        out_specs=P("x", None),
    )
    return f(a, b)
