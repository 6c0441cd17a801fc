"""Fig 3 analogue — training time growth with micro-batch count.

The paper's slowdown comes from per-chunk sub-graph rebuilds; we report
epoch time AND the isolated rebuild cost so the overhead source is explicit.

Beyond-paper: every chunk count runs the full engine × schedule matrix —
host (fill-drain / 1F1B / interleaved / zb-h1 where legal) and compiled,
where fill-drain runs the fused scan and 1F1B/interleaved/zb-h1 run the
scheduled executor (``spmd_pipeline_scheduled``) inside the same jitted
program (zb-h1 splits every backward into B/W halves and fills the drain
bubble with deferred weight-grad work — its win needs concurrent ticks, so
the CI perf gate measures this table under 4 forced host devices). Each
row carries the schedule's bubble fraction and peak live activations
(measured on the host engine, static stash accounting on the scheduled
compiled path) next to the epoch time; ``compiled_vs_host`` reports the
speedup against the host fill-drain baseline of the same chunk count.

``json_path`` writes the whole table as machine-readable ``BENCH_fig3.json``
— the artifact the CI perf-regression gate (``benchmarks/check_perf.py``)
diffs against the committed baseline.

The table also carries the ``partition/*`` rows: the cost-model-driven stage
partitioner vs the layer-count-uniform split on a deliberately imbalanced
GCN stack (see ``_partition_bench``), with the measured per-layer cost table
written alongside the json as ``partition_costs.json``. ``partition=
"profiled"`` additionally reruns the main engine×schedule matrix with the
profiler choosing the paper model's balance (exercising the ``--partition``
CLI path end to end).

``scale/*`` rows extend the figure along the graph axis: streamed power-law
graphs up to 1e5 nodes, built chunk-by-chunk with nothing global ever
materialized, stepped on the (data, stage) mesh when the host has enough
devices (see ``_scale_bench``). The perf gate checks the run-internal
growth ratio step(n)/step(n_min) and the in-run host-oracle
``updates_match`` bit.

``auto/*`` rows measure the ``--auto`` planner: its pick (resolved
schedule x chunks x balance x placement) stepped interleaved against a set
of hand-picked configs on the imbalanced GCN fixture, with the planner's
predicted step time in the row (see ``_auto_bench``). The perf gate
requires the pick to be within threshold of the best measured hand-picked
config and bounds the predicted/measured ratio against the baseline's.

``overlap/*`` rows measure the double-buffered wire dataflow
(``--overlap double-buffer``) against the serialized ppermute-after-work
baseline on the deepest ring of the matrix, interleaved-stepped with an
in-run host fill-drain oracle check, plus a ``jax.profiler`` overlap
report for both modes (``overlap_report.json`` + uploaded traces — see
``_overlap_bench`` and ``repro.core.overlap_report``). The perf gate's
overlap rule is platform-conditional; the row carries the tick accounting
(``num_ticks``, ``wire_latency``) it needs.
"""

from __future__ import annotations

import json
import os
import statistics
import time

import jax

from benchmarks.common import PipelineCLIConfig, emit
from repro.core.microbatch import make_plan
from repro.graphs import load_dataset
from repro.launch.train import run_gnn

SCHEDULES = ("fill_drain", "1f1b", "interleaved", "zb-h1")
ENGINES = ("host", "compiled")


def run(*, dataset="cora", epochs=30, max_chunks=4, schedules=SCHEDULES,
        json_path=None, partition="uniform"):
    g = load_dataset(dataset)
    rows = []
    stages, pipe_devices = 4, 2
    bench = {
        "dataset": dataset,
        "stages": stages,
        "pipe_devices": pipe_devices,
        "epochs": epochs,
        "rows": {},
    }
    for chunks in range(1, max_chunks + 1):
        plan = make_plan(g, chunks, strategy="sequential")
        layer_costs = None
        if partition == "profiled":
            # profile ONCE per chunk count (costs depend only on the model
            # and the padded chunk shape) — every matrix cell below reuses
            # the measurement through the ``args.layer_costs`` pass-through,
            # and the fingerprint-keyed sidecar means a rerun (or the
            # ``--auto`` planner sweeping the same shapes) reuses it across
            # processes too
            from repro.core.costmodel import cached_profile_layer_costs
            from repro.models.gnn.net import build_paper_gat

            model = build_paper_gat(g.num_features, g.num_classes)
            chunk0 = jax.tree_util.tree_map(lambda a: a[0], plan.stacked().graph)
            layer_costs = cached_profile_layer_costs(
                model, model.init_params(jax.random.PRNGKey(0)), chunk0,
                cache_path=(
                    os.path.join(os.path.dirname(json_path), "layer_costs_cache.json")
                    if json_path else None
                ),
            )
        host_epoch_s = None
        for engine in ENGINES:
            for schedule in schedules:
                args = PipelineCLIConfig(
                    engine=engine, schedule=schedule, chunks=chunks, stages=stages,
                    partition=partition, pipe_devices=pipe_devices,
                ).namespace(
                    mode="gnn", dataset=dataset, strategy="sequential",
                    epochs=epochs, seed=0, log_every=0, layer_costs=layer_costs,
                )
                try:
                    r = run_gnn(args)
                except ValueError:
                    continue  # schedule rejects this (stages, chunks) combo
                finally:
                    # each cell leaves its jitted programs in the global
                    # compilation cache; without clearing, LATE cells measure
                    # under 30+ resident programs' worth of allocator/cache
                    # pressure the early cells never saw — a positional bias
                    # that lands exactly on the zb-h1 rows the perf gate
                    # compares against 1f1b
                    jax.clear_caches()
                # the CSV, the speedup ratio and the gated JSON all use the
                # same MEDIAN estimator — mixing estimators made the human
                # artifact disagree with what the gate enforces whenever a
                # scheduler hiccup inflated one cell's mean
                step_s = r["median_epoch_s"]
                if engine == "host" and schedule == "fill_drain":
                    host_epoch_s = step_s
                name = (
                    f"{schedule}_chunks{chunks}" if engine == "host"
                    else f"compiled_{schedule}_chunks{chunks}"
                )
                derived = (
                    f"rebuild_s={plan.rebuild_seconds:.3f};edge_cut={plan.edge_cut:.3f};"
                    f"bubble={r['bubble_fraction']:.3f};"
                    f"peak_live={r['peak_live_activations']}"
                )
                if engine == "compiled" and host_epoch_s:
                    derived += f";compiled_vs_host={host_epoch_s / step_s:.2f}x"
                emit(f"fig3/{dataset}/{name}", step_s * 1e6, derived)
                bench["rows"][f"{engine}/{schedule}/chunks{chunks}"] = {
                    # median, not mean: the gate's strict/thresholded row
                    # comparisons must not hinge on whether a scheduler
                    # hiccup landed in this cell's epochs (means came out
                    # 2-3x the median on contended CI-class hosts)
                    "step_s": r["median_epoch_s"],
                    "bubble": r["bubble_fraction"],
                    "peak_live": r["peak_live_activations"],
                    "peak_live_accounted": r["peak_live_accounted"],
                    "rebuild_s": plan.rebuild_seconds,
                }
                rows.append((f"{engine}/{schedule}", chunks, step_s, plan.rebuild_seconds))
    rows.extend(
        _partition_bench(
            bench,
            epochs=max(epochs, 12),
            json_dir=os.path.dirname(json_path) if json_path else None,
        )
    )
    rows.extend(
        _sparse_bench(
            bench,
            epochs=max(epochs, 12),
            json_dir=os.path.dirname(json_path) if json_path else None,
        )
    )
    rows.extend(_scale_bench(bench, epochs=max(epochs // 2, 8)))
    rows.extend(
        _overlap_bench(
            bench,
            epochs=max(epochs, 12),
            json_dir=os.path.dirname(json_path) if json_path else None,
        )
    )
    rows.extend(
        _auto_bench(
            bench,
            epochs=max(epochs, 12),
            json_dir=os.path.dirname(json_path) if json_path else None,
        )
    )
    if json_path:
        with open(json_path, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
    return rows


def _partition_bench(bench, *, epochs, chunks=4, dataset="cora", json_dir=None):
    """Cost-model partitioner vs the layer-count-uniform split on the
    deliberately imbalanced GCN stack (``build_imbalanced_gcn``: the leading
    convs are ~10x the tail, so the uniform split stacks the two heavy
    layers into stage 0 and every pipeline tick waits on it). Both configs
    run the compiled 1F1B executor; rows land in the BENCH json as
    ``partition/{uniform|profiled}/chunksC`` — the perf gate requires
    profiled to beat uniform when ticks run concurrently (the CI gate's
    4 forced host devices). The measured per-layer cost table is written to
    ``json_dir/partition_costs.json`` (the CI artifact)."""
    from repro.core.costmodel import (
        choose_balance,
        predicted_balance_time,
        profile_layer_costs,
        uniform_balance,
    )
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.core.schedule import get_schedule
    from repro.models.gnn.net import build_imbalanced_gcn
    from repro.train import optimizer as opt_lib

    g = load_dataset(dataset)
    model = build_imbalanced_gcn(g.num_features, g.num_classes)
    plan = make_plan(g, chunks, strategy="sequential")
    chunk0 = jax.tree_util.tree_map(lambda a: a[0], plan.stacked().graph)
    params0 = model.init_params(jax.random.PRNGKey(0))
    costs = profile_layer_costs(model, params0, chunk0)
    schedule = get_schedule("1f1b")
    stages = 4
    profiled, _ = choose_balance(costs, stages, schedule, chunks)
    balances = {
        "uniform": uniform_balance(len(model.layers), stages),
        "profiled": profiled,
    }
    if json_dir:
        os.makedirs(json_dir, exist_ok=True)
        with open(os.path.join(json_dir, "partition_costs.json"), "w") as f:
            json.dump(
                {
                    "dataset": dataset,
                    "model": "imbalanced_gcn",
                    "layers": costs.table(),
                    "balances": {
                        name: {
                            "balance": list(bal),
                            "predicted_step_s": predicted_balance_time(
                                costs, bal, schedule, chunks
                            ),
                        }
                        for name, bal in balances.items()
                    },
                },
                f, indent=2,
            )
            f.write("\n")

    # the perf gate compares the two rows STRICTLY, so the measurement must
    # be drift-proof: the configs' steps run interleaved (machine drift —
    # thermal, neighbors, allocator state — hits both equally instead of
    # whichever ran second) and the estimator is the median with the
    # compile step dropped
    opt = opt_lib.adam(1e-2)
    pipes, states, times = {}, {}, {}
    for name, balance in balances.items():
        pipes[name] = make_engine(model, GPipeConfig(engine="compiled",
            balance=balance, chunks=chunks, schedule="1f1b",
        ))
        params = pipes[name].init_params(jax.random.PRNGKey(0))
        states[name] = [params, opt.init(params), jax.random.PRNGKey(0)]
        times[name] = []
    for _ in range(epochs):
        for name, pipe in pipes.items():
            params, state, key = states[name]
            key, rng = jax.random.split(key)
            t0 = time.perf_counter()
            params, state, loss = pipe.train_step(params, state, plan, rng, opt)
            jax.block_until_ready(loss)
            times[name].append(time.perf_counter() - t0)
            states[name] = [params, state, key]

    rows = []
    for name, balance in balances.items():
        step_s = statistics.median(times[name][1:])
        predicted = predicted_balance_time(costs, balance, schedule, chunks)
        emit(
            f"fig3/{dataset}/partition_{name}_chunks{chunks}",
            step_s * 1e6,
            f"balance={'-'.join(map(str, balance))};predicted_s={predicted:.4f}",
        )
        bench["rows"][f"partition/{name}/chunks{chunks}"] = {
            "step_s": step_s,
            "balance": list(balance),
            "predicted_step_s": predicted,
        }
        rows.append((f"partition/{name}", chunks, step_s, plan.rebuild_seconds))
    return rows


def _scale_bench(bench, *, epochs, sizes=(25_000, 50_000, 100_000),
                 nodes_per_chunk=12_500, dataset="powerlaw-64k"):
    """Step time vs graph size on the streamed power-law generator — the
    paper's figure extended along the graph axis instead of the chunk axis.

    Each size ``n`` materializes nothing globally: ``open_streamed`` builds
    ``n / nodes_per_chunk`` chunks block-by-block on the host (so per-chunk
    work stays roughly constant and chunk count carries the growth), and the
    compiled engine shards them over the (data, stage) mesh when the host
    has >= data_parallel * ring devices (the CI gate's 4 forced devices),
    else the single-replica fallback — recorded per row as
    ``data_parallel_active``. Rows land in the BENCH json as
    ``scale/n{N}/chunks{C}`` with the one-step host fill-drain oracle check
    (``updates_match``) computed in the SAME run the gate times; the gate
    compares the run-internal growth ratio step(n)/step(n_min) against the
    baseline's ratio, which cancels machine speed entirely.

    The sizes stay ~1e5 so the oracle+timing loop fits a CI lane; the 1e6
    registry entries (``powerlaw-1m``) run through the identical code path
    (see ``examples/scaling_larger_graphs.py``)."""
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.graphs import open_streamed, streamed_plan
    from repro.models.gnn.net import build_gnn
    from repro.train import optimizer as opt_lib

    balance = (2, 2)
    dp = 2 if jax.device_count() >= 2 * len(balance) else 1
    opt = opt_lib.adam(1e-2)

    pipes, plans, states, times, meta = {}, {}, {}, {}, {}
    for n in sizes:
        chunks = max(n // nodes_per_chunk, dp)
        ds = open_streamed(dataset, num_nodes=n)
        plan = streamed_plan(ds, chunks, max_degree=32)
        g0 = plan.batches[0].graph
        model = build_gnn("gcn", g0.num_features, g0.num_classes,
                          hidden=32, depth=2)
        pipe = make_engine(model, GPipeConfig(
            engine="compiled", balance=balance, chunks=chunks,
            schedule="1f1b", data_parallel=dp,
        ))
        params0 = pipe.init_params(jax.random.PRNGKey(0))

        # oracle check in the measured run: one step from identical params
        # through the host fill-drain reference and the compiled mesh config
        host = make_engine(model, GPipeConfig(
            engine="host", balance=balance, chunks=chunks))
        rng0 = jax.random.PRNGKey(1)
        p_ref, _, _ = host.train_step(params0, opt.init(params0), plan, rng0, opt)
        p_cmp, _, _ = pipe.train_step(params0, opt.init(params0), plan, rng0, opt)
        diff = max(
            float(abs(a - b).max()) for a, b in zip(
                jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_cmp)
            )
        )
        pipes[n], plans[n] = pipe, plan
        states[n] = [params0, opt.init(params0), jax.random.PRNGKey(0)]
        times[n] = []
        meta[n] = {"chunks": chunks, "diff": diff, "edge_cut": plan.edge_cut,
                   "dp_active": pipe._data_parallel_active}

    # interleaved measurement across sizes: drift (thermal, neighbors,
    # allocator) hits every size equally, so the gate's step(n)/step(n_min)
    # ratio is drift-free; median with the warm-up step dropped
    for _ in range(epochs):
        for n, pipe in pipes.items():
            params, state, key = states[n]
            key, rng = jax.random.split(key)
            t0 = time.perf_counter()
            params, state, loss = pipe.train_step(params, state, plans[n], rng, opt)
            jax.block_until_ready(loss)
            times[n].append(time.perf_counter() - t0)
            states[n] = [params, state, key]

    tol = 5e-4  # engine-oracle tolerance (compiled program fuses differently)
    rows = []
    for n in sizes:
        step_s = statistics.median(times[n][1:])
        chunks = meta[n]["chunks"]
        emit(
            f"fig3/{dataset}/scale_n{n}_chunks{chunks}",
            step_s * 1e6,
            f"max_update_diff={meta[n]['diff']:.2e};"
            f"edge_cut={meta[n]['edge_cut']:.3f};"
            f"data_parallel={dp if meta[n]['dp_active'] else 1}",
        )
        bench["rows"][f"scale/n{n}/chunks{chunks}"] = {
            "step_s": step_s,
            "nodes": n,
            "chunks": chunks,
            "max_update_diff": meta[n]["diff"],
            "updates_match": meta[n]["diff"] <= tol,
            "edge_cut": meta[n]["edge_cut"],
            "data_parallel_active": meta[n]["dp_active"],
        }
        rows.append((f"scale/n{n}", chunks, step_s, 0.0))
    return rows


def _sparse_bench(bench, *, epochs, chunks=2, dataset="skewed-powerlaw", json_dir=None):
    """The pallas and padded backends' compiled GCN steps on the power-law
    fixture (median degree ~14, max capped at 128 — the padded layout
    spends ~90% of its slots on padding, so both engines hand either
    backend the degree-bucketed layout). Rows land in the BENCH json as
    ``sparse/{padded|bucketed}/chunksC``; the perf gate requires each
    update to agree at oracle tolerance with a host fill-drain step of the
    dense backend, which never buckets. The layout's speed is gated at the
    op level (``kernels_bench``'s padded-vs-bucketed rows), where the
    padded op still runs the plain padded layout. The per-stage roofline
    table (measured vs roof bytes/FLOPs for both layouts — the fig's
    sparse row) is written to ``json_dir/roofline_stages.json``."""
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.graphs import bucketize_stacked
    from repro.graphs.partition import layout_slots
    from repro.models.gnn.net import build_gnn
    from repro.roofline import sparse_stage_report
    from repro.train import optimizer as opt_lib

    # max_degree=128 keeps the padded einsum's (n, max_deg, hidden) gather
    # bounded while preserving the skew (median 14 vs cap 128)
    g = load_dataset(dataset, max_degree=128)
    balance = (2, 2)
    models = {
        "padded": build_gnn("gcn", g.num_features, g.num_classes,
                            hidden=32, depth=2, backend="padded"),
        "bucketed": build_gnn("gcn", g.num_features, g.num_classes,
                              hidden=32, depth=2, backend="pallas"),
    }
    plan = make_plan(g, chunks, strategy="sequential")
    opt = opt_lib.adam(1e-2)

    # oracle-tolerance update identity, asserted in the SAME run the gate
    # times: one step from identical params through the host fill-drain
    # dense reference (no bucket layout) and each measured compiled config
    dense = build_gnn("gcn", g.num_features, g.num_classes,
                      hidden=32, depth=2, backend="dense")
    ref = make_engine(dense, GPipeConfig(
        balance=balance, chunks=chunks, engine="host", backend="dense"))
    params0 = ref.init_params(jax.random.PRNGKey(0))
    rng0 = jax.random.PRNGKey(1)
    p_ref, _, _ = ref.train_step(params0, opt.init(params0), plan, rng0, opt)

    pipes, states, times, diffs = {}, {}, {}, {}
    for name, model in models.items():
        pipes[name] = make_engine(model, GPipeConfig(
            balance=balance, chunks=chunks, engine="compiled",
            backend="pallas" if name == "bucketed" else "padded",
        ))
        p1, _, _ = pipes[name].train_step(params0, opt.init(params0), plan, rng0, opt)
        diffs[name] = max(
            float(abs(a - b).max()) for a, b in zip(
                jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p1)
            )
        )
        states[name] = [params0, opt.init(params0), jax.random.PRNGKey(0)]
        times[name] = []

    # interleaved measurement (drift hits both layouts equally), median
    # with the warm-up step dropped — same discipline as _partition_bench
    for _ in range(epochs):
        for name, pipe in pipes.items():
            params, state, key = states[name]
            key, rng = jax.random.split(key)
            t0 = time.perf_counter()
            params, state, loss = pipe.train_step(params, state, plan, rng, opt)
            jax.block_until_ready(loss)
            times[name].append(time.perf_counter() - t0)
            states[name] = [params, state, key]

    stacked = plan.stacked().graph
    report = sparse_stage_report(
        models["bucketed"], params0, stacked, bucketize_stacked(stacked), balance
    )
    if json_dir:
        os.makedirs(json_dir, exist_ok=True)
        with open(os.path.join(json_dir, "roofline_stages.json"), "w") as f:
            json.dump({"dataset": dataset, "balance": list(balance), **report}, f, indent=2)
            f.write("\n")

    tol = 2e-4  # oracle tolerance: the buckets and the dense matmul order f32 sums differently
    rows = []
    for name in models:
        step_s = statistics.median(times[name][1:])
        slots = layout_slots(pipes[name].layout(stacked))  # the layout it ran
        emit(
            f"fig3/{dataset}/sparse_{name}_chunks{chunks}",
            step_s * 1e6,
            f"max_update_diff={diffs[name]:.2e};"
            f"slots={slots:.0f};"
            f"live_slots={report['slots']['live']:.0f}",
        )
        bench["rows"][f"sparse/{name}/chunks{chunks}"] = {
            "step_s": step_s,
            "max_update_diff": diffs[name],
            "updates_match": diffs[name] <= tol,
            "layout_slots": slots,
            "live_slots": report["slots"]["live"],
        }
        rows.append((f"sparse/{name}", chunks, step_s, plan.rebuild_seconds))
    return rows


def _overlap_bench(bench, *, epochs, chunks=8, dataset="cora", json_dir=None):
    """Double-buffered wire ticks vs the serialized baseline on the deepest
    ring of the matrix (paper GAT, balance (2,1,1,2), 1f1b).

    Both engines run the SAME lowered schedule family; ``double-buffer``
    retimes it to wire latency 2 so each tick's ppermute pair is posted one
    tick before its arrivals are consumed (no data dependency pins it to
    the critical path). The update stays bit-identical dataflow, checked
    here at oracle tolerance against one host fill-drain step from
    identical params — in the SAME run the gate times.

    Each row carries the tick accounting the perf gate's
    platform-conditional rule needs: on runtimes whose traced
    ``overlap_fraction`` shows real hiding, the gate requires the
    double-buffered STEP to win outright; on lockstep single-threaded
    executors (CI's forced-host CPU — fraction ~0, no scheduling can win
    wall-clock there) it bounds the retimed program's per-TICK cost
    instead. ``capture_overlap_report`` traces one warm step per mode and
    the pair of reports lands in ``json_dir/overlap_report.json`` with the
    raw profiler traces beside it."""
    from repro.core.overlap_report import capture_overlap_report
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.models.gnn.net import build_paper_gat
    from repro.train import optimizer as opt_lib

    g = load_dataset(dataset)
    model = build_paper_gat(g.num_features, g.num_classes)
    plan = make_plan(g, chunks, strategy="sequential")
    balance = (2, 1, 1, 2)
    opt = opt_lib.adam(1e-2)
    modes = {"serialized": "off", "double-buffer": "double-buffer"}

    # oracle update check, same discipline as _sparse_bench: one step from
    # identical params through a host fill-drain reference and through each
    # measured compiled config, in the run the gate times
    ref = make_engine(model, GPipeConfig(balance=balance, chunks=chunks, engine="host"))
    params0 = ref.init_params(jax.random.PRNGKey(0))
    rng0 = jax.random.PRNGKey(1)
    p_ref, _, _ = ref.train_step(params0, opt.init(params0), plan, rng0, opt)

    pipes, states, times, diffs, stats = {}, {}, {}, {}, {}
    for name, overlap in modes.items():
        pipes[name] = make_engine(model, GPipeConfig(
            balance=balance, chunks=chunks, schedule="1f1b",
            engine="compiled", overlap=overlap,
        ))
        st: dict = {}
        p1, _, _ = pipes[name].train_step(
            params0, opt.init(params0), plan, rng0, opt, stats=st
        )
        stats[name] = st
        diffs[name] = max(
            float(abs(a - b).max()) for a, b in zip(
                jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p1)
            )
        )
        states[name] = [params0, opt.init(params0), jax.random.PRNGKey(0)]
        times[name] = []

    # interleaved measurement, median with the warm-up step dropped
    for _ in range(epochs):
        for name, pipe in pipes.items():
            params, state, key = states[name]
            key, rng = jax.random.split(key)
            t0 = time.perf_counter()
            params, state, loss = pipe.train_step(params, state, plan, rng, opt)
            jax.block_until_ready(loss)
            times[name].append(time.perf_counter() - t0)
            states[name] = [params, state, key]

    # trace one warm step per mode; the report pair (and the raw traces) is
    # the figure's overlap evidence — a fraction of ~0 on forced-host CPU is
    # itself the documented finding (see repro.core.overlap_report)
    reports = {}
    trace_root = os.path.join(json_dir, "overlap_traces") if json_dir else None
    for name, pipe in pipes.items():
        params, state, key = states[name]
        _, rng = jax.random.split(key)

        def one_step(pipe=pipe, params=params, state=state, rng=rng):
            _, _, loss = pipe.train_step(params, state, plan, rng, opt)
            jax.block_until_ready(loss)

        tdir = os.path.join(trace_root, name) if trace_root else None
        if tdir:
            os.makedirs(tdir, exist_ok=True)
        reports[name] = capture_overlap_report(one_step, trace_dir=tdir)

    if json_dir:
        os.makedirs(json_dir, exist_ok=True)
        with open(os.path.join(json_dir, "overlap_report.json"), "w") as f:
            json.dump(
                {"dataset": dataset, "chunks": chunks, "schedule": "1f1b",
                 "balance": list(balance), "modes": reports},
                f, indent=2, sort_keys=True,
            )
            f.write("\n")

    tol = 5e-4  # engine-cross tolerance (fused host vs scheduled float order)
    rows = []
    for name in modes:
        step_s = statistics.median(times[name][1:])
        ticks = int(stats[name].get("num_ticks", 0))
        emit(
            f"fig3/{dataset}/overlap_{name}_chunks{chunks}",
            step_s * 1e6,
            f"max_update_diff={diffs[name]:.2e};num_ticks={ticks};"
            f"overlap_fraction={reports[name]['overlap_fraction']:.3f}",
        )
        bench["rows"][f"overlap/{name}/chunks{chunks}"] = {
            "step_s": step_s,
            "num_ticks": ticks,
            "wire_latency": int(stats[name].get("wire_latency", 0)),
            "max_update_diff": diffs[name],
            "updates_match": diffs[name] <= tol,
            "overlap_fraction": reports[name]["overlap_fraction"],
        }
        rows.append((f"overlap/{name}", chunks, step_s, plan.rebuild_seconds))
    return rows


def _auto_bench(bench, *, epochs, chunks=4, dataset="cora", json_dir=None):
    """The ``--auto`` planner's pick vs hand-picked configs on the
    deliberately imbalanced GCN stack (the partitioner's fixture — the
    stack where config choice actually matters).

    A small set of representative hand-picked configs (uniform and profiled
    balances under fill-drain / 1F1B / zb-h1, all at the paper's 4-chunk
    operating point) is measured INTERLEAVED with the planner's pick —
    machine drift hits every config equally, medians with the warm-up step
    dropped, same discipline as ``_partition_bench``. Rows land in the
    BENCH json as ``auto/hand/{name}/chunksC`` plus the stable-keyed
    ``auto/pick``; the perf gate (``check_perf``) requires the pick's
    measured step to be within threshold of the BEST measured hand-picked
    config (the planner must not pick badly) and bounds the pick's
    predicted/measured ratio against the baseline's same ratio (the
    prediction layer must not drift — the ratio is machine-relative, since
    on forced-host CPU the unmodeled per-tick dispatch dominates the
    absolute step time). The planner's profile lands in the shared
    ``layer_costs_cache.json`` sidecar, so the sweep costs one profile per
    (model, chunk shape)."""
    from repro.core.autotune import PlanConstraints, plan_pipeline
    from repro.core.costmodel import (
        cached_profile_layer_costs,
        choose_balance,
        predicted_balance_time,
        uniform_balance,
    )
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.core.schedule import get_schedule
    from repro.models.gnn.net import build_imbalanced_gcn
    from repro.train import optimizer as opt_lib

    g = load_dataset(dataset)
    model = build_imbalanced_gcn(g.num_features, g.num_classes)
    stages = 4
    cache_path = os.path.join(json_dir, "layer_costs_cache.json") if json_dir else None
    plan = make_plan(g, chunks, strategy="sequential")
    chunk0 = jax.tree_util.tree_map(lambda a: a[0], plan.stacked().graph)
    params0 = model.init_params(jax.random.PRNGKey(0))
    costs = cached_profile_layer_costs(model, params0, chunk0, cache_path=cache_path)

    uniform = uniform_balance(len(model.layers), stages)
    hand = {
        "fill_drain_uniform": ("fill_drain", uniform),
        "1f1b_uniform": ("1f1b", uniform),
        "1f1b_profiled": (
            "1f1b", choose_balance(costs, stages, get_schedule("1f1b"), chunks)[0],
        ),
        "zb-h1_profiled": (
            "zb-h1", choose_balance(costs, stages, get_schedule("zb-h1"), chunks)[0],
        ),
    }

    # the planner resolves schedule x chunks x balance over the full search
    # space (rotations off: predicted time is placement-invariant, so the
    # axis only pads the table here); each candidate chunk count's profile
    # comes from the same sidecar cache
    auto_plan = plan_pipeline(
        model, g,
        PlanConstraints(num_stages=stages, chunk_counts=(2, chunks),
                        rotations=False),
        params=params0, cache_path=cache_path,
    )
    plans = {name: plan for name in hand}
    plans["pick"] = (
        plan if auto_plan.chunks == chunks
        else make_plan(g, auto_plan.chunks, strategy="sequential")
    )

    opt = opt_lib.adam(1e-2)
    pipes, states, times = {}, {}, {}
    for name, (schedule, balance) in hand.items():
        pipes[name] = make_engine(model, GPipeConfig(engine="compiled",
            balance=balance, chunks=chunks, schedule=schedule,
        ))
    pipes["pick"] = make_engine(model, auto_plan)
    for name, pipe in pipes.items():
        params = pipe.init_params(jax.random.PRNGKey(0))
        states[name] = [params, opt.init(params), jax.random.PRNGKey(0)]
        times[name] = []
    for _ in range(epochs):
        for name, pipe in pipes.items():
            params, state, key = states[name]
            key, rng = jax.random.split(key)
            t0 = time.perf_counter()
            params, state, loss = pipe.train_step(params, state, plans[name], rng, opt)
            jax.block_until_ready(loss)
            times[name].append(time.perf_counter() - t0)
            states[name] = [params, state, key]

    rows = []
    for name, (schedule, balance) in hand.items():
        step_s = statistics.median(times[name][1:])
        predicted = predicted_balance_time(
            costs, balance, get_schedule(schedule), chunks
        )
        emit(
            f"fig3/{dataset}/auto_hand_{name}_chunks{chunks}",
            step_s * 1e6,
            f"schedule={schedule};balance={'-'.join(map(str, balance))};"
            f"predicted_s={predicted:.4f}",
        )
        bench["rows"][f"auto/hand/{name}/chunks{chunks}"] = {
            "step_s": step_s,
            "schedule": schedule,
            "balance": list(balance),
            "predicted_step_s": predicted,
        }
        rows.append((f"auto/hand/{name}", chunks, step_s, plan.rebuild_seconds))
    pick_s = statistics.median(times["pick"][1:])
    emit(
        f"fig3/{dataset}/auto_pick",
        pick_s * 1e6,
        f"schedule={auto_plan.schedule};chunks={auto_plan.chunks};"
        f"balance={'-'.join(map(str, auto_plan.balance))};"
        f"predicted_s={auto_plan.predicted_step_s:.4f};"
        f"evaluated={auto_plan.evaluated}",
    )
    # stable key on purpose (no chunk suffix): the pick's chunk count is the
    # planner's to choose, and a changed pick must not read as a coverage
    # regression — the payload carries the resolved config
    bench["rows"]["auto/pick"] = {
        "step_s": pick_s,
        "schedule": auto_plan.schedule,
        "chunks": auto_plan.chunks,
        "balance": list(auto_plan.balance),
        "predicted_step_s": auto_plan.predicted_step_s,
        "evaluated": auto_plan.evaluated,
    }
    rows.append(("auto/pick", auto_plan.chunks, pick_s, plan.rebuild_seconds))
    return rows


def main_auto() -> None:
    """Standalone auto-cell entry for CI's bench-smoke: run only the
    ``auto/*`` rows (planner pick vs hand-picked configs) and write them as
    ``BENCH_fig3_auto.json`` plus the profile sidecar — uploaded artifacts,
    not the gate baseline (the perf-gate job regenerates the full table)."""
    import argparse

    ap = argparse.ArgumentParser(description="fig3 planner (auto) cells only")
    ap.add_argument("--auto-cell", action="store_true",
                    help="marker flag selecting this entry from __main__")
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--json-out", default=None)
    a = ap.parse_args()
    bench = {"dataset": a.dataset, "epochs": a.epochs, "rows": {}}
    _auto_bench(bench, epochs=a.epochs, chunks=a.chunks,
                dataset=a.dataset, json_dir=a.json_out)
    if a.json_out:
        os.makedirs(a.json_out, exist_ok=True)
        path = os.path.join(a.json_out, "BENCH_fig3_auto.json")
        with open(path, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")


def main_overlap() -> None:
    """Standalone overlap-cell entry for CI's bench-smoke: run only the
    ``overlap/*`` pair and write ``BENCH_fig3_overlap.json`` plus
    ``overlap_report.json`` and the raw profiler traces — uploaded
    artifacts, not the gate baseline (the perf-gate job regenerates the
    full table)."""
    import argparse

    ap = argparse.ArgumentParser(description="fig3 overlap cells only")
    ap.add_argument("--overlap-cell", action="store_true",
                    help="marker flag selecting this entry from __main__")
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--json-out", default=None)
    a = ap.parse_args()
    bench = {"dataset": a.dataset, "epochs": a.epochs, "rows": {}}
    _overlap_bench(bench, epochs=a.epochs, chunks=a.chunks,
                   dataset=a.dataset, json_dir=a.json_out)
    if a.json_out:
        os.makedirs(a.json_out, exist_ok=True)
        path = os.path.join(a.json_out, "BENCH_fig3_overlap.json")
        with open(path, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")


def main_scale() -> None:
    """Standalone streamed-cell entry for CI's bench-smoke: run only the
    ``scale/*`` rows (one or a few mid-size streamed-generator cells) and
    write them as ``BENCH_fig3_scale.json`` — an uploaded artifact, not the
    gate baseline (the perf-gate job regenerates the full table)."""
    import argparse

    ap = argparse.ArgumentParser(description="fig3 streamed graph-scaling cells only")
    ap.add_argument("--scale-sizes", default="100000",
                    help="comma list of streamed node counts (default: one 1e5 cell)")
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--dataset", default="powerlaw-64k")
    ap.add_argument("--json-out", default=None)
    a = ap.parse_args()
    sizes = tuple(int(s) for s in a.scale_sizes.split(","))
    bench = {"dataset": a.dataset, "epochs": a.epochs, "rows": {}}
    _scale_bench(bench, epochs=a.epochs, sizes=sizes, dataset=a.dataset)
    if a.json_out:
        os.makedirs(a.json_out, exist_ok=True)
        path = os.path.join(a.json_out, "BENCH_fig3_scale.json")
        with open(path, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")


if __name__ == "__main__":
    import sys

    if "--overlap-cell" in sys.argv:
        main_overlap()
    elif "--auto-cell" in sys.argv:
        main_auto()
    else:
        main_scale()
