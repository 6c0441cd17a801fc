"""The benchmark's copies against the program, at small sizes on the CPU:
its graphs equal the program's generators', and the plain reference follows
the program's compiled step (GCN on a small power-law graph, the paper GAT
on cora) to float32 round-off."""

import numpy as np
import pytest

import graphs
import models
import train_cell
from conftest import small_traffic

FIELDS = ("features", "neighbors", "mask", "norm", "labels", "train_mask", "val_mask",
          "test_mask")


def test_planted_graph_equals_the_programs():
    from repro.graphs import load_dataset

    ours = graphs.planted_graph("cora", 0)
    theirs = load_dataset("cora", seed=0)
    for f in FIELDS:
        np.testing.assert_array_equal(ours[f], np.asarray(getattr(theirs, f)), err_msg=f)


def test_streamed_chunks_equal_the_programs():
    from repro.graphs import open_streamed, streamed_plan

    spec = small_traffic("gcn-powerlaw1m")["graph"]
    ours = graphs.streamed_chunks(spec)
    ds = open_streamed(spec["name"], seed=spec["dataset_seed"], num_nodes=spec["num_nodes"],
                       block_size=spec["block_size"], p_intra=spec["p_intra"])
    theirs = streamed_plan(ds, spec["chunks"], max_degree=spec["max_degree"]).stacked().graph
    for f in FIELDS + ("node_ids",):
        np.testing.assert_array_equal(ours[f], np.asarray(getattr(theirs, f)), err_msg=f)


@pytest.mark.parametrize("cell", ["gcn-powerlaw1m", "gat-cora-paper"])
def test_reference_follows_the_program(cell):
    import bench

    c = bench.find_cell(bench.load_json(bench.ROOT, "BENCHMARK.json"), cell)
    tc = train_cell.TrainCell(models.load_config(c["config"]), small_traffic(cell))
    seed = 2**31 + 101
    _, prog, rngs = tc.first_steps(seed, 3)
    nums = tc.numbers(prog, tc.reference(seed, rngs))
    # on the CPU both compute in float32; only the order of sums differs
    assert nums["loss_gap"] < 1e-5
    assert nums["grad_gap"] < 1e-5
    assert nums["update_gap"] < 1e-3
