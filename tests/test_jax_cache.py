"""Persistent-compilation-cache wiring (utils/jax_cache.py).

Every train, deploy and bench run is its own process; these tests prove
where the cache lives (``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``) and that it actually carries compiled
executables across the process boundary, using the CPU backend (same
cache machinery, no device needed).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A compile heavy enough that a persistent-cache hit is unmistakably
# cheaper than the miss, run in a child pinned to the CPU backend.
_CHILD = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
from predictionio_tpu.utils.platform import force_cpu_in_process
force_cpu_in_process()
from predictionio_tpu.utils.jax_cache import enable_compilation_cache
cache_dir = enable_compilation_cache()
import jax
import jax.numpy as jnp

def f(x):
    for i in range(12):
        x = jnp.tanh(x @ x) * (1.0 + 1.0 / (i + 2)) + x
    return x.sum()

t0 = time.monotonic()
jax.jit(f).lower(
    jax.ShapeDtypeStruct((256, 256), jnp.float32)
).compile()
from predictionio_tpu.obs.profile import default_telemetry
print(json.dumps({{"compile_s": time.monotonic() - t0,
                   "cache_dir": cache_dir,
                   "jax_dir": jax.config.jax_compilation_cache_dir,
                   "cache": default_telemetry().snapshot()["cache"]}}))
"""


def _run_child(cache_dir, cwd=REPO) -> dict:
    from predictionio_tpu.utils.platform import force_cpu_env

    env = force_cpu_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=REPO)],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_jax_env_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    import jax

    from predictionio_tpu.utils.jax_cache import enable_compilation_cache

    theirs = str(tmp_path / "theirs")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", theirs)
    before = dict(os.environ)
    previous = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compilation_cache() == theirs
        assert jax.config.jax_compilation_cache_dir == theirs
    finally:
        jax.config.update("jax_compilation_cache_dir", previous)
    # no second knob, and no environment hand-off to children
    assert dict(os.environ) == before
    assert not os.path.exists(os.path.join(REPO, "theirs"))


def test_default_is_checkout_jax_cache_in_every_process(tmp_path):
    """Unset, the cache is ``<checkout>/.jax_cache`` — derived from the
    package's own location, so two processes started from different
    working directories resolve the identical path (the path is part of
    the cache key: a directory that moves never hits)."""
    from predictionio_tpu.utils.jax_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    here = _run_child(None)
    elsewhere = _run_child(None, cwd=str(tmp_path))
    assert here["cache_dir"] == elsewhere["cache_dir"] == DEFAULT_CACHE_DIR
    assert here["jax_dir"] == DEFAULT_CACHE_DIR


def test_second_subprocess_hits_cache(tmp_path):
    """Process 1 populates the cache, process 2 (identical program) must
    add NO new entries and count a hit. File-set stability is the
    assertion that pins key stability across processes: a second process
    that *missed* would write new entries under a different cache key.
    (Wall-clock ratios flake under full-suite CPU contention; the cache
    key contract is the test.)"""
    cache_dir = str(tmp_path / "cache")
    first = _run_child(cache_dir)
    assert first["cache_dir"] == cache_dir
    assert first["cache"]["misses"] >= 1
    entries = {
        os.path.join(dp, f)
        for dp, _, fs in os.walk(cache_dir) for f in fs
    }
    assert entries, "first run wrote no cache entries"

    second = _run_child(cache_dir)
    assert second["cache_dir"] == cache_dir
    assert second["cache"]["hits"] >= 1 and second["cache"]["misses"] == 0
    entries_after = {
        os.path.join(dp, f)
        for dp, _, fs in os.walk(cache_dir) for f in fs
    }
    assert entries_after == entries, (
        "second process missed the cache (new entries written)"
    )
