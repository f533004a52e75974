"""Deviceless (compile-only) TPU topology access, with lockfile retry.

``jax.experimental.topologies.get_topology_desc`` loads libtpu, which
holds a machine-wide lockfile during plugin init — a concurrent
prewarm run or test session makes the first attempt fail
transiently. Every in-repo user (``tools/prewarm_cache``, the Mosaic
AOT test modules) goes through this helper so they all share the retry
(full-jittered via the shared :class:`RetryPolicy`: the contenders are
exactly the processes that would otherwise wake in lockstep and collide
on the lockfile again).

Argument-format note (cost a whole round to discover):
``chips_per_host_bounds`` must be a TUPLE OF INTS, e.g. ``(1, 1, 1)``;
string forms are rejected by libtpu with a mangled type error.
"""

from __future__ import annotations

from .resilience import RetryPolicy


def get_deviceless_topology(name: str, retries: int = 1,
                            retry_delay_s: float = 10.0, **kwargs):
    """A compile-only TPU topology (e.g. ``"v5e:1x1"`` with
    ``chips_per_host_bounds=(1, 1, 1)``, or ``"v5e:2x2"``). Retries
    libtpu-lockfile contention ``retries`` times; any other failure
    (no libtpu at all) raises immediately."""
    from jax.experimental import topologies

    policy = RetryPolicy(
        attempts=retries + 1,
        base_delay_s=retry_delay_s,
        max_delay_s=retry_delay_s * 2,
    )
    return policy.call(
        lambda: topologies.get_topology_desc(name, "tpu", **kwargs),
        should_retry=lambda exc: "lockfile" in str(exc),
    )
