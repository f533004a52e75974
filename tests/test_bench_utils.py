"""bench.py host-side helpers: holdout split and synth-cache reaper.

The reaper rules were reworked twice by review (live-writer protection,
then pid-recycling age bound) — this pins the final contract: a YOUNG
tmp with a live writer pid survives, a young tmp with a dead writer is
reaped, and an OLD tmp is reaped even if its (possibly recycled) pid is
alive.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_holdout_mask_deterministic_five_percent():
    m1 = bench.holdout_mask(200_000)
    m2 = bench.holdout_mask(200_000)
    np.testing.assert_array_equal(m1, m2)
    assert 0.045 < m1.mean() < 0.055


def test_synth_cache_orphan_reaper(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_SYNTH_CACHE", str(tmp_path))
    scale = 0.0001
    cache = tmp_path / f"synth_ml20m_v{bench._SYNTH_VERSION}_s{scale}_seed0.npz"

    # pid 1 is always alive (and not OUR pid — synth_ml20m's own savez
    # tmp uses os.getpid() and would collide)
    young_alive = tmp_path / f"{cache.name}.1.tmp.npz"
    young_dead = tmp_path / f"{cache.name}.999999.tmp.npz"
    old_alive = tmp_path / f"{cache.name}.x.1.tmp.npz"
    for p in (young_alive, young_dead, old_alive):
        p.write_bytes(b"x")
    old = time.time() - 7 * 3600
    os.utime(old_alive, (old, old))

    bench.synth_ml20m(scale)

    assert cache.exists(), "cache file not written"
    assert young_alive.exists(), "live writer's young tmp was reaped"
    assert not young_dead.exists(), "dead writer's tmp not reaped"
    assert not old_alive.exists(), "old tmp kept alive by recycled pid"


def test_main_fails_with_the_error_and_starts_no_child(monkeypatch, capsys):
    """Whatever ``run_bench`` raises ends the run non-zero with the error
    on the JSON line — no fallback, no retry, no child process."""
    import json
    import subprocess

    def boom(scale, iterations):
        raise RuntimeError("no usable device")

    def no_children(*args, **kwargs):
        raise AssertionError(f"bench.main started a child: {args}")

    monkeypatch.setattr(bench, "run_bench", boom)
    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.delenv("BENCH_LEDGER", raising=False)
    assert bench.main() == 1
    captured = capsys.readouterr()
    record = json.loads(captured.out.strip().splitlines()[-1])
    assert record["error"] == "RuntimeError: no usable device"
    assert record["value"] == -1.0 and record["vs_baseline"] == 0.0
    assert "bench: running on cpu" in captured.err  # says which device


# the removed names, in halves: no file of the tree spells them out
@pytest.mark.parametrize("module, name", [
    ("bench", "_fallback" "_to_cpu"),
    ("bench", "probe" "_device"),
    ("bench", "_attach" "_last_good"),
    ("bench", "_save" "_last_good"),
    ("predictionio_tpu.ops.pallas_kernels", "_HAVE" "_PALLAS"),
    ("predictionio_tpu.ops.attention", "_HAVE" "_PALLAS"),
    ("predictionio_tpu.obs.profile", "REFERENCE" "_DEVICE"),
])
def test_fallbacks_are_gone(module, name):
    """Pallas is imported plainly (an import error there is an import
    error), the bench has no CPU fallback or last-good record, and no
    roofline is taken against an assumed device."""
    import importlib

    assert not hasattr(importlib.import_module(module), name)
