"""``correct`` comes out false where the timed path is broken underneath,
once for each fault a cell can have, and for the control (the reference
with three bfloat16 passes per product in the program's place). The runs
skip the harness's look for a chip and use small sizes; the limits are the
cells' own."""

import pytest

import bench
import check
import models
import train_cell
from conftest import args, load_traffic, small_traffic


def _run(cell, fault=None, trace=0):
    return bench.run(args(cell, trace=trace), require_tpu=False, fault=fault,
                     traffic=small_traffic(cell))


def test_sound_run_is_correct():
    r = _run("gcn-powerlaw1m")
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "step_s"}
    # the compiled step's bytes stand in where the runtime keeps no counters
    assert r["device"]["memory_peak_bytes"] > 0


def test_state_unchanged_is_caught():
    r = _run("gcn-powerlaw1m", "state_unchanged")
    assert not r["correct"]
    assert r["checks"]["update_gap"]["value"] == 1.0


def test_half_batch_is_caught():
    r = _run("gcn-powerlaw1m", "half_batch")
    assert not r["correct"], r["checks"]


def _limits(cell):
    return load_traffic(cell)["limits"]


def _control_traffic(cell):
    """The cell's traffic at the size its control is kept at: cora whole; the
    power-law graph at 32,768 nodes in 4 chunks, its widths and degree cap
    unchanged (at 8,192 nodes the control's gaps fall under the cell's
    limits on some seeds)."""
    t = load_traffic(cell)
    if t["graph"]["kind"] == "streamed":
        t["graph"].update(num_nodes=32768, chunks=4)
    return t


@pytest.mark.parametrize("cell", ["gat-cora-paper", "gcn-powerlaw1m"])
def test_training_control_is_not_correct(cell):
    """The control in the program's place, judged by the cell's own limits."""
    b = bench.load_json(bench.ROOT, "BENCHMARK.json")
    cfg = models.load_config(bench.find_cell(b, cell)["config"])
    tc = train_cell.TrainCell(cfg, _control_traffic(cell))
    for seed in (2**31 + 7, 2**31 + 8, 2**31 + 9):
        _, _, rngs = tc.first_steps(seed, 3)
        ref = tc.reference(seed, rngs)
        nums = tc.numbers(tc.reference(seed, rngs, control=True), ref)
        ok, table = check.judge(nums, _limits(cell))
        assert not ok, table
