"""Family F fixture: in/out spec literals disagree on rank for a
rank-preserving collective body."""

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def reduce_rows(x, mesh):
    f = shard_map(  # BAD: psum preserves rank; the out spec lost a dim
        lambda s: jax.lax.psum(s, "data"),
        mesh=mesh,
        in_specs=P("data", None),
        out_specs=P(None),
    )
    return f(x)
