"""Device seconds of the traced job under one ``jax.named_scope`` of the
ALS programs: the union of the intervals of the operations whose name
stack (the ``tf_op`` stat of the operation's metadata in the TPU
runtime's plane) holds ``scope``, both sides, every program. A fusion
counts under the scope of its root instruction."""

from ..lib import scopes


def read(obs, params):
    trace = scopes.job_trace(obs)
    if not trace:
        return None
    return scopes.scope_seconds(trace, params["scope"])
