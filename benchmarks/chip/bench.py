"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 benchmarks/chip/bench.py --workload gcn-powerlaw1m --seed 7 \\
        --seconds 30 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the checkout
root: a configuration (``configs/<config>.json``) under a traffic mix
(``workloads/<traffic>.json``). ``--seed`` draws the weights and the
dropout; the graph is fixed by the traffic file. Set-up (imports,
graph, compile and warm-up of every shape the window uses, and the first
steps the correctness check reads) is ``setup_s``; then the window runs for
``--seconds``. With ``--trace 1`` the run also traces a short window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` end standard error. A run that finds no
TPU, or another number of chips than the cell asks for, exits non-zero
without a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


class NoChip(SystemExit):
    """Raised when the machine does not hold the chips the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def device_facts(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX found {devs[0].platform!r} devices; "
                     f"this benchmark measures only on the chip")
    if require_tpu and len(devs) != chips:
        raise NoChip(f"bench: the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def configure_jax(cfg: dict) -> None:
    """The program's compile cache at its fixed path, every program the
    window uses kept in it however quickly it compiles (so that a later
    run's set-up compiles nothing), and the configuration's matmul
    precision for every program."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


def memory_facts() -> dict:
    """The runtime's peak and limit of the fullest device. The TPU runtime's
    ``peak_bytes_in_use`` leaves out a program's temporaries, so the runner
    takes the peak as the larger of it and the compiled step's bytes."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
    i = max(range(len(peaks)), key=peaks.__getitem__)
    return {"counter_bytes": int(peaks[i]), "bytes_limit": int(stats[i].get("bytes_limit", 0))}


def per_layer_metrics(bench: dict, cell: str, ctx: dict) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that names this cell (or
    names no cells), read by ``metrics/<name>.py``; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = importlib.util.spec_from_file_location(
            f"metric_{m['name']}", os.path.join(HERE, "metrics", f"{m['name']}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class CompileCounter:
    """Counts the programs JAX compiles, or loads from its persistent
    cache, while it is open: a window should count none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count, self.open = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.open and name in self.EVENTS:
            self.count += 1


def traced(fn, trace_seconds: float):
    """Run ``fn(seconds)`` under the profiler inside a ``bench.window`` span;
    return its result and the reduced trace."""
    import jax

    import devtrace as trace_lib

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                result = fn(trace_seconds)
        finally:
            jax.profiler.stop_trace()
        tr = trace_lib.load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return result, {"shares": trace_lib.device_shares(tr), "top_ops": trace_lib.top_ops(tr),
                    "idle_gaps": trace_lib.idle_gaps(tr)}


def run(args, *, require_tpu: bool = True, fault: str | None = None,
        traffic: dict | None = None) -> dict:
    """One run of the cell; returns the result object. ``require_tpu``,
    ``fault`` and ``traffic`` (a replacement of the cell's traffic file)
    serve the tests, which drive a run on the CPU at a small size."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    import models

    cfg = models.load_config(cell["config"])
    if traffic is None:
        traffic = load_json(HERE, "workloads", f"{cell['traffic']}.json")
    device = device_facts(cell["chips"], require_tpu)

    configure_jax(cfg)
    if traffic["kind"] != "train":
        raise SystemExit(f"bench: no runner for traffic kind {traffic['kind']!r}")
    import train_cell as runner

    counter = CompileCounter()
    out = runner.run(cfg, traffic, args, device=device, t_start=T_START, fault=fault,
                     traced=traced, memory_facts=memory_facts, counter=counter)
    ctx = out.pop("ctx")
    sys.stderr.write(f"bench: programs compiled or loaded inside the window: {counter.count}\n")
    if args.trace:
        metrics = per_layer_metrics(bench, cell["name"], ctx)
        shares = ctx["trace"]["shares"]
        devs = list(shares["devices"].values())
        device["busy_s"] = sum(d["busy_s"] for d in devs) / max(len(devs), 1)
        device["window_s"] = shares["window_s"]
    else:
        metrics = out.pop("metrics")
    device["memory_peak_bytes"] = ctx["memory"]["peak_bytes"]
    import check

    correct, table = check.judge(out.pop("numbers"), traffic["limits"])
    result = {"correct": bool(correct and out["failed"] == 0), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": ctx["trace"]["top_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["checks"] = table
    check.report(table)
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main() -> int:
    args = build_parser().parse_args()
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a non-negative whole number")
    try:
        result = run(args)
    except NoChip as exc:
        sys.stderr.write(f"{exc}\n")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
