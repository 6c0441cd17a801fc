"""Readings that set a training cell's limits: the program against the
reference over many seeds, the control (three bfloat16 passes per product)
in the program's place, and the program with a fault planted. One process
builds and compiles the cell once and reads every seed through it.

    python3 benchmarks/chip/readings.py --workload gcn-powerlaw1m \\
        --seeds 12 --control 4 --half-batch 3 --state-unchanged 3 \\
        --out readings.json

A state left unchanged reads 1 on ``grad_gap`` and ``update_gap`` by
construction; its run is for ``loss_gap``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench  # noqa: E402  (puts the program's src on the path)

SEED0 = 3_000_000_017  # readings seeds: SEED0 + 7919 * i, apart from bench trials


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=0, help="program seeds")
    ap.add_argument("--control", type=int, default=0, help="control seeds")
    ap.add_argument("--half-batch", type=int, default=0, help="half-batch fault seeds")
    ap.add_argument("--state-unchanged", type=int, default=0,
                    help="state-unchanged fault seeds (for the loss, which it moves least)")
    ap.add_argument("--first-seed", type=int, default=SEED0)
    ap.add_argument("--traffic", default=None, help="a traffic file in place of the cell's")
    ap.add_argument("--cpu", action="store_true", help="rehearse on the CPU")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import models

    benchmark = bench.load_json(bench.ROOT, "BENCHMARK.json")
    cell = bench.find_cell(benchmark, args.workload)
    cfg = models.load_config(cell["config"])
    traffic = bench.load_json(args.traffic or os.path.join(
        bench.HERE, "workloads", f"{cell['traffic']}.json"))
    device = bench.device_facts(cell["chips"], require_tpu=not args.cpu)
    bench.configure_jax(cfg)
    rows = train_readings(cfg, traffic, args)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device, "rows": rows}, f, indent=1)
    return 0


def _seeds(args, n):
    return [args.first_seed + 7919 * i for i in range(n)]


def _record(rows, kind, seed, nums, t0):
    row = {"kind": kind, "seed": seed, **nums, "seconds": time.perf_counter() - t0}
    rows.append(row)
    print(json.dumps(row), flush=True)


def train_readings(cfg, traffic, args) -> list:
    import train_cell

    seeds = _seeds(args, max(args.seeds, args.control, args.half_batch, args.state_unchanged))
    tc = train_cell.TrainCell(cfg, traffic)
    n = traffic["check_steps"]
    rows = []

    def record(kind, seed, nums, t0):
        _record(rows, kind, seed, nums, t0)

    refs = {}
    for seed in seeds[:args.seeds]:
        t0 = time.perf_counter()
        _, prog, rngs = tc.first_steps(seed, n)
        refs[seed] = (tc.reference(seed, rngs), rngs)
        record("program", seed, tc.numbers(prog, refs[seed][0]), t0)
    for seed in seeds[:args.control]:
        t0 = time.perf_counter()
        if seed not in refs:
            _, _, rngs = tc.first_steps(seed, n)
            refs[seed] = (tc.reference(seed, rngs), rngs)
        ref, rngs = refs[seed]
        record("control", seed, tc.numbers(tc.reference(seed, rngs, control=True), ref), t0)
    for fault, count in (("half_batch", args.half_batch),
                         ("state_unchanged", args.state_unchanged)):
        tc.fault = fault
        for seed in seeds[:count]:
            t0 = time.perf_counter()
            _, prog, rngs = tc.first_steps(seed, n)
            ref = refs[seed][0] if seed in refs else tc.reference(seed, rngs)
            record(fault, seed, tc.numbers(prog, ref), t0)
    return rows


if __name__ == "__main__":
    sys.exit(main())
