"""Platform selection at the spawn/dry-run boundary: tests pin the CPU
backend, and a CPU-pinned parent's children come up on it too (the
spark-submit env-propagation analogue, ``RunWorkflow.scala:37-40``)."""

import os
import subprocess
import sys

from predictionio_tpu.utils.platform import (
    current_platform,
    force_cpu_env,
    jax_child_env,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_force_cpu_env_pins_cpu_and_device_count():
    base = {
        "JAX_PLATFORMS": "tpu",
        "TPU_LOG_DIR": "disabled",
        "PYTHONPATH": "/somewhere/else",
        "HOME": "/root",
    }
    env = force_cpu_env(base, n_devices=8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PIO_JAX_PLATFORM"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # nothing is stripped: every other variable passes through
    assert {k: env[k] for k in ("TPU_LOG_DIR", "PYTHONPATH", "HOME")} == {
        "TPU_LOG_DIR": "disabled",
        "PYTHONPATH": "/somewhere/else",
        "HOME": "/root",
    }
    assert base["JAX_PLATFORMS"] == "tpu"  # the caller's mapping is untouched


def test_force_cpu_env_replaces_existing_device_count():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 --other"}
    env = force_cpu_env(base, n_devices=8)
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert "device_count=8" in env["XLA_FLAGS"]
    assert "--other" in env["XLA_FLAGS"]


def test_jax_child_env_pins_only_under_a_cpu_parent(monkeypatch):
    base = {"JAX_PLATFORMS": "tpu", "SOME_VAR": "1"}
    # this process is cpu-pinned under conftest: its children are too
    assert current_platform() == "cpu"
    env = jax_child_env(base)
    assert env["JAX_PLATFORMS"] == "cpu" and env["SOME_VAR"] == "1"
    # a parent that is not pinned passes the environment through untouched,
    # so production children reach the accelerator
    monkeypatch.delenv("PIO_JAX_PLATFORM", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert current_platform() == ""
    assert jax_child_env(base) == base


def test_dryrun_multichip_child_runs_on_virtual_cpu_devices():
    """The dry-run entry: a parent that has not imported jax re-execs
    itself as a CPU-pinned child with 8 virtual devices."""
    env = dict(os.environ)
    env.pop("_PIO_DRYRUN_CHILD", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "__graft_entry__.py"),
         "--dryrun", "8"],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "dryrun_multichip(8) ok" in proc.stdout
