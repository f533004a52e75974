"""What the run is on: the accelerator check, the table of peaks, the
compile cache, peak device memory."""

from __future__ import annotations

import json
import os
import sys
from typing import Dict

from . import manifest


def require(chips: int, rehearsal: bool) -> Dict:
    """The device as JAX reports it. Anything but ``chips`` TPU chips or
    more ends the run with a message naming what was found; a rehearsal
    workload (``"rehearsal": true`` in its file, never listed in
    ``BENCHMARK.json``) runs anywhere and reports no metric."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        return found
    if found["platform"] != "tpu" or found["count"] < chips:
        sys.exit(
            f"benchmark: this cell needs {chips} TPU chip(s); JAX found "
            f"platform {found['platform']!r} ({found['kind']}), "
            f"{found['count']} device(s). There is no CPU fallback."
        )
    found["count"] = chips
    return found


def peaks(kind: str) -> Dict:
    with open(os.path.join(manifest.ROOT, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        sys.exit(
            f"benchmark: no peaks for device kind {kind!r} in benchmark/peaks.json "
            f"(known: {sorted(table)}); add a row with its source"
        )
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell uses (0
    where the backend reports none, as the CPU does)."""
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (``predictionio_tpu.utils.jax_cache``; a fixed path inside the
    checkout, which ``.gitignore`` lists)."""
    from predictionio_tpu.utils.jax_cache import enable_compilation_cache

    path = enable_compilation_cache()
    if path is None:
        sys.exit("benchmark: the compile cache directory cannot be set up")
    return path
