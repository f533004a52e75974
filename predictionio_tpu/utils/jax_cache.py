"""Persistent JAX compilation cache shared across processes.

Every train, deploy and bench run is its own process (``pio train`` and
``pio deploy`` spawn children), and each would otherwise
re-pay the full XLA/Mosaic compilation of largely identical programs:
about a minute for the ML-20M rank-50 ALS programs, plus one serving
compile per dispatch shape. JAX's persistent compilation cache stores
compiled executables on disk keyed by (program HLO, backend, compiler
options) and re-loads them in any later process, so the second and
subsequent processes start warm.

The reference has no analogue to point at — its equivalent cost is JVM +
Spark warmup, re-paid on every ``spark-submit`` child
(``tools/src/main/scala/io/prediction/tools/RunWorkflow.scala:103-169``);
caching the compiled program across processes is a place the TPU-native
stack can simply do better.

Where the cache lives (docs/performance.md):

- ``JAX_COMPILATION_CACHE_DIR`` — JAX's own knob; when set, the cache is
  there and nowhere else.
- otherwise ``<checkout>/.jax_cache``, derived from this package's own
  location: a fixed path (the path is part of the cache key, so a
  directory that moves never hits) that every process of one checkout
  computes identically, with no environment hand-off.
"""

from __future__ import annotations

import os
from typing import Optional

#: ``<checkout>/.jax_cache`` — the directory that holds the
#: ``predictionio_tpu`` package, not the working directory.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def compilation_cache_dir() -> str:
    """Where this process keeps its compile cache (see module docstring)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for this process.
    Children need no hand-off: they resolve the same directory.

    Must run before the first JAX compilation to help that compilation;
    safe (idempotent, best-effort) at any point. Returns the cache dir,
    or ``None`` when it cannot be created or configured.
    """
    cache_dir = compilation_cache_dir()
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError:
        return None
    # Cache every program: serving-dispatch programs compile in well
    # under the 1 s default threshold, and every deploy re-pays them.
    wanted = (
        ("jax_compilation_cache_dir", cache_dir),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
        ("jax_persistent_cache_min_entry_size_bytes", 0),
    )
    applied: list = []  # (name, previous value) of updates that landed
    try:
        import jax

        for name, value in wanted:
            previous = getattr(jax.config, name, None)
            jax.config.update(name, value)
            applied.append((name, previous))
    except Exception:
        # Partial failure must not half-enable caching: roll the config
        # back to its pre-call state so this process never runs with
        # (say) the cache dir set but the thresholds still defaulted.
        for name, previous in reversed(applied):
            try:
                jax.config.update(name, previous)
            except Exception:
                pass
        return None
    # Cache observability (docs/observability.md#profiling): every
    # process that enables the cache also starts counting its hits and
    # misses (jax.monitoring events) into the process jit telemetry, so
    # /metrics, `pio profile` and chip_smoke.py can say whether the
    # cache was warm.
    try:
        from ..obs.profile import default_telemetry

        default_telemetry().attach_monitoring()
    except Exception:
        pass  # telemetry is an observer; it must never fail cache setup
    return cache_dir
