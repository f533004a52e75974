"""Where the benchmark's data files are and how one finds the other:
``BENCHMARK.json`` names cells, configurations and metrics; each has a
file of its own under ``benchmark/``, found by that name."""

from __future__ import annotations

import importlib
import json
import os
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
#: Everything a run writes (store, traces, the load generator's files).
#: A fixed path inside the checkout, listed in ``.gitignore``.
WORK = os.path.join(REPO, ".bench_work")


def _load(*parts: str) -> Dict:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _load(REPO, "BENCHMARK.json")


def workload(name: str) -> Dict:
    return _load(ROOT, "workloads", f"{name}.json")


def config(name: str) -> Dict:
    return _load(ROOT, "configs", f"{name}.json")


def metric(name: str) -> Dict:
    return _load(ROOT, "metrics", f"{name}.json")


def reader(name: str) -> Callable:
    """``benchmark/readers/<name>.py``'s ``read(obs, params)``."""
    return importlib.import_module(f"benchmark.readers.{name}").read


def kind(name: str):
    """``benchmark/kinds/<name>.py``: how a cell of that kind is run."""
    return importlib.import_module(f"benchmark.kinds.{name}")


def metrics_of(cell: str, trace: bool) -> List[str]:
    """The metrics ``BENCHMARK.json`` has this cell report: its
    end-to-end metrics untraced, its per-layer metrics traced. An
    end-to-end metric without a ``workloads`` key is every cell's; a
    per-layer metric without one belongs to every cell that reports the
    end-to-end metric it ``moves``."""
    bench = benchmark()
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}

    def reports(name: str) -> bool:
        return e2e[name] is None or cell in e2e[name]

    if not trace:
        return [name for name in e2e if reports(name)]
    return [
        m["name"] for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else reports(m["moves"]))
    ]
