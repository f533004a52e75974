"""Family F fixture: in_specs drifted from the mapped function arity."""

from jax import shard_map
from jax.sharding import PartitionSpec as P


def sharded_matmul(a, b, mesh):
    f = shard_map(  # BAD: 3 specs for a 2-argument body
        lambda sa, sb: sa @ sb,
        mesh=mesh,
        in_specs=(P("x", None), P(None, None), P(None, None)),
        out_specs=P("x", None),
    )
    return f(a, b)
