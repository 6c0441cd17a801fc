"""``BENCHMARK.json`` and the files it names fit together: every cell names
an existing configuration and traffic file, every per-layer metric has its
reader, and every cell reports what the contract asks."""

import importlib.util
import json
import os
import re

import pytest

from conftest import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_keys_and_names(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert benchmark["paths"] == ["benchmarks/chip"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in benchmark[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


def test_cells_name_existing_files(benchmark):
    configs = {c["name"]: c for c in benchmark["configs"]}
    for cell in benchmark["workloads"]:
        cfg = configs[cell["config"]]
        assert cfg["file"] == f"benchmarks/chip/configs/{cell['config']}.json"
        assert os.path.exists(os.path.join(ROOT, cfg["file"]))
        with open(os.path.join(HERE, "workloads", f"{cell['traffic']}.json")) as f:
            traffic = json.load(f)
        assert traffic["kind"] == "train"
        assert set(traffic["limits"]) <= {"loss_gap", "grad_gap", "grad_median_gap",
                                          "update_gap"}
        assert "grad_gap" in traffic["limits"]
        assert all(v is not None for v in traffic["limits"].values())
    used = {c["config"] for c in benchmark["workloads"]}
    assert used == set(configs)


def test_every_cell_reports_enough(benchmark):
    cells = [c["name"] for c in benchmark["workloads"]]
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in cells:
        own = [m for m in e2e.values() if cell in m.get("workloads", cells)]
        assert len(own) >= 2
        moved = {m["name"] for m in own}
        layer = [m for m in benchmark["per_layer"] if cell in m.get("workloads", cells)]
        assert layer and all(m["moves"] in moved for m in layer)


def test_every_metric_has_a_reader(benchmark):
    cells = {c["name"] for c in benchmark["workloads"]}
    for m in benchmark["per_layer"]:
        assert set(m["workloads"]) <= cells
        read = _reader(m["name"])
        assert read({}) is None  # nothing to read: nothing returned
