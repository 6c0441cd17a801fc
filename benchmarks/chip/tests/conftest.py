"""Tests of the chip benchmark, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They live outside the repository's ``testpaths``. Runs of a cell here are at
small sizes (``small_traffic``) and skip the harness's look for a chip.
"""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_traffic(cell_name: str) -> dict:
    import bench

    cell = bench.find_cell(bench.load_json(ROOT, "BENCHMARK.json"), cell_name)
    return bench.load_json(HERE, "workloads", f"{cell['traffic']}.json")


def small_traffic(cell_name: str) -> dict:
    """The cell's traffic at a size a test run holds: a streamed graph of
    8,192 nodes in 4 chunks (widths and layer list unchanged); cora stays
    whole."""
    t = copy.deepcopy(load_traffic(cell_name))
    g = t["graph"]
    if g["kind"] == "streamed":
        g.update(num_nodes=8192, block_size=1024, chunks=4, max_degree=8)
    return t


@pytest.fixture(autouse=True)
def _private_caches(tmp_path_factory, monkeypatch):
    """Keep the graph cache and JAX's compile cache of test runs out of the
    checkout."""
    import graphs

    monkeypatch.setattr(graphs, "CACHE_DIR", str(tmp_path_factory.getbasetemp() / "graphs"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


def args(workload: str, seed: int = 2**31 + 11, seconds: float = 1.0, trace: int = 0):
    import argparse

    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
