# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure + roofline table.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--dataset cora]

``--fast`` trims epochs for CI-speed runs; the full-protocol numbers
(300 epochs, pubmed) are produced with ``--full`` as in the paper.
"""

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--full", action="store_true", help="paper protocol: 300 epochs + pubmed")
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--only", default=None, help="comma list: table1,table2,fig3,fig4,kernels,roofline")
    ap.add_argument("--json-out", default=None,
                    help="directory for machine-readable outputs (BENCH_fig3.json, "
                         "consumed by benchmarks.check_perf)")
    ap.add_argument("--partition", default="uniform", choices=["uniform", "profiled"],
                    help="fig3: stage balance for the engine×schedule matrix "
                         "(the imbalanced-stack partitioner comparison runs either way)")
    ap.add_argument("--table1-backends", default="padded,dense,pallas",
                    help="comma list of aggregation backends for the table1 "
                         "columns (pallas runs the fused kernel in interpret "
                         "mode on CPU)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    epochs = 300 if args.full else (15 if args.fast else 60)
    dataset = "pubmed" if args.full else args.dataset
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    if want("table1"):
        from benchmarks import table1

        datasets = ("cora", "citeseer", "pubmed") if args.full else ("cora",)
        table1.run(
            datasets=datasets,
            backends=tuple(args.table1_backends.split(",")),
            epochs=epochs,
        )
    if want("table2"):
        from benchmarks import table2

        table2.run(dataset=dataset, epochs=epochs)
    if want("fig3"):
        from benchmarks import fig3

        json_path = None
        if args.json_out:
            os.makedirs(args.json_out, exist_ok=True)
            json_path = os.path.join(args.json_out, "BENCH_fig3.json")
        fig3.run(dataset=dataset, epochs=max(epochs // 2, 10), json_path=json_path,
                 partition=args.partition)
    if want("fig4"):
        from benchmarks import fig4

        fig4.run(dataset=dataset, epochs=epochs)
    if want("kernels"):
        from benchmarks import kernels_bench

        json_path = None
        if args.json_out:
            os.makedirs(args.json_out, exist_ok=True)
            json_path = os.path.join(args.json_out, "BENCH_kernels.json")
        kernels_bench.run(json_path=json_path)
    if want("roofline"):
        from benchmarks import roofline_table

        roofline_table.run()
    sys.stdout.flush()


if __name__ == "__main__":
    main()
