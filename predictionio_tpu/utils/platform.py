"""JAX platform selection for child processes.

The reference's process model launches every train/eval/deploy run as a
child JVM via spark-submit, propagating the parent's configuration
explicitly (``tools/src/main/scala/io/prediction/tools/RunWorkflow.scala:
103-169`` passes ``--env`` and SPARK_YARN_USER_ENV through). Here the
children are Python processes and the configuration that matters is the
JAX backend: tests pin the CPU backend, production lets JAX take the
accelerator.

- :func:`force_cpu_env` — a child-process environment pinned to the CPU
  backend (``JAX_PLATFORMS=cpu`` + ``PIO_JAX_PLATFORM=cpu``), optionally
  with an N-device virtual CPU mesh via
  ``XLA_FLAGS=--xla_force_host_platform_device_count``.
- :func:`jax_child_env` — environment for spawned workflow/server
  children: CPU-pinned when this process is, otherwise passed through
  untouched so production children reach the accelerator.
- :func:`force_cpu_in_process` — pin THIS process to the CPU backend,
  before any JAX backend initialization (mirrors ``tests/conftest.py``).
- :func:`device_info` — the device JAX gave this process (reading, not
  choosing).
"""

from __future__ import annotations

import os
import re
from typing import Dict, Mapping, Optional

_FORCE_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def force_cpu_env(
    base: Optional[Mapping[str, str]] = None,
    n_devices: Optional[int] = None,
) -> Dict[str, str]:
    """Child-process environment pinned to the JAX CPU backend.

    ``n_devices`` > 1 additionally forces a virtual CPU device mesh
    (the test analogue of the reference's ``local[4]`` Spark master).
    """
    env = dict(base if base is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PIO_JAX_PLATFORM"] = "cpu"
    if n_devices is not None:
        flags = env.get("XLA_FLAGS", "")
        flags = _FORCE_COUNT_RE.sub("", flags).strip()
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def current_platform() -> str:
    """The platform this process intends: explicit ``PIO_JAX_PLATFORM``
    wins, then ``JAX_PLATFORMS``; empty string means 'let JAX choose'."""
    plat = os.environ.get("PIO_JAX_PLATFORM") or os.environ.get(
        "JAX_PLATFORMS", ""
    )
    return plat.split(",")[0].strip().lower()


def jax_child_env(
    base: Optional[Mapping[str, str]] = None,
    n_devices: Optional[int] = None,
) -> Dict[str, str]:
    """Environment for a spawned workflow/server child process.

    CPU-pinned parents (tests, dry-runs) produce CPU-pinned children.
    Anything else passes through unchanged so production children reach
    the real device.
    """
    if current_platform() == "cpu":
        return force_cpu_env(base, n_devices=n_devices)
    return dict(base if base is not None else os.environ)


def force_cpu_in_process() -> None:
    """Pin THIS process to the CPU backend (only reliable before the first
    JAX backend initialization). Mirrors ``tests/conftest.py``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["PIO_JAX_PLATFORM"] = "cpu"
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:  # jax missing/already initialized: env pin stands
        pass


def apply_env_platform() -> None:
    """Entry-point hook for driver processes (run_workflow / run_server):
    a CPU pin in the environment (``PIO_JAX_PLATFORM`` or
    ``JAX_PLATFORMS``) becomes this process's ``jax_platforms`` config
    before any backend initialization."""
    if current_platform() == "cpu":
        force_cpu_in_process()


def device_info() -> Dict[str, object]:
    """The device this process runs on, as JAX reports it — what
    ``run_workflow``'s result line and ``/status.json`` carry so a run
    that came up on the wrong backend cannot look healthy. Initializes
    the backend if nothing has yet."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
