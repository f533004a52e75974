"""``BENCHMARK.json`` and the files it names agree with each other and
with the contract's limits on names, units and counts."""

import json
import os
import re

import pytest

from benchmark.lib import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
BENCH = manifest.benchmark()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYER = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_names_units_and_entry_keys():
    names = []
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(set(names)) == len(names)
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16


def test_every_cell_has_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for c in configs.values():
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        on_disk = manifest.config(c["name"])
        assert on_disk["source"] == c["source"] and on_disk["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        on_disk = manifest.workload(w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert on_disk[key] == w[key], (w["name"], key)
        assert not on_disk.get("rehearsal")
        manifest.kind(on_disk["kind"]).run  # the kind exists


def test_every_metric_has_a_file_and_a_reader():
    for name, m in {**E2E, **LAYER}.items():
        spec = manifest.metric(name)
        assert spec["unit"] == m["unit"], name
        assert callable(manifest.reader(spec["reader"]))
        for cell in cells_of(m):
            assert cell in CELLS, (name, cell)
    for name, m in LAYER.items():
        spec = manifest.metric(name)
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]


def test_moves_and_coverage():
    for name, m in LAYER.items():
        assert m["moves"] in E2E, name
        for cell in cells_of(m):
            assert cell in cells_of(E2E[m["moves"]]), (name, cell)
    for cell in CELLS:
        e2e = [n for n, m in E2E.items() if cell in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in cells_of(m) for m in LAYER.values()), cell
        assert manifest.metrics_of(cell, trace=False) == [
            n for n in E2E if cell in cells_of(E2E[n])]


def test_layers_are_perf_md_layers():
    with open(os.path.join(manifest.REPO, "PERF.md")) as f:
        perf = f.read()
    for m in LAYER.values():
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_file_names_use_allowed_characters():
    for base, _, files in os.walk(manifest.ROOT):
        if "__pycache__" in base:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), manifest.REPO)
            assert FILE.match(rel), rel


@pytest.mark.parametrize("name", sorted(os.listdir(os.path.join(manifest.ROOT, "workloads"))))
def test_rehearsals_stay_out_of_the_manifest(name):
    spec = manifest.workload(name[: -len(".json")])
    assert bool(spec.get("rehearsal")) == (spec["name"] not in CELLS)
    if spec.get("rehearsal"):
        # the listed cell it stands for, or the metrics it rehearses
        if "stands_for" in spec:
            assert spec["stands_for"] in CELLS
        else:
            for metric in spec["metrics"]:
                assert callable(manifest.reader(manifest.metric(metric)["reader"]))
