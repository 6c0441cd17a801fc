"""Batched GNN serving: shape-bucketed ego-subgraph inference at production
rates, pumped through the pipelined compiled eval program.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
        python -m repro.launch.serve_gnn --dataset cora --qps 50 --duration 5 \\
        --engine compiled --stages 4 --chunks 4

A synthetic open-loop arrival process (Poisson at ``--qps``) emits
node-classification and link-prediction queries against a loaded graph. Each
query is served from its seed nodes' k-hop **ego-subgraph**
(``graphs/partition.ego_subgraph``): with ``--hops`` >= the model's
receptive depth (2 for the paper GAT) the halo is lossless, so the served
prediction is *bit-identical* to a full-graph forward pass — ``--verify``
checks exactly that against a host full-batch apply.

Shape discipline: arbitrary traffic produces arbitrary ego sizes, and every
new array shape is a new XLA compilation. The server therefore pads each
ego-subgraph into a small static ladder of node-count **buckets**
(``ShapeBuckets``; neighbor width is always the full graph's ``max_degree``),
so the jitted program count is bounded by the ladder length regardless of
traffic — the same reason the training path stacks uniform-shape chunks.
Same-bucket requests batch together, ``--chunks`` per dispatch, and run as
ONE stacked batch through the engine's ``compile_eval`` program — the
pipelined scheduled executor on ``--engine compiled``, the fused host scan
on ``--engine host`` (the interface is symmetric).

The driver reports achieved queries/s, p50/p99 latency and per-bucket batch
occupancy, writes a machine-readable row for the CI serving gate
(``benchmarks/check_perf.py --serving-current``) plus a latency histogram
artifact, and exits non-zero if ``--verify`` finds any served prediction
diverging from the full-batch oracle.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from collections import deque

import numpy as np
import jax

from repro.graphs.data import GraphBatch, pad_graph
from repro.graphs.partition import ego_subgraph
from repro.launch.compile_cache import enable_compile_cache


@dataclasses.dataclass(frozen=True)
class Query:
    """One serving request: classify node ``u`` ("node") or score the pair
    ``(u, v)`` ("link"). ``arrival_s`` is the open-loop schedule offset."""

    qid: int
    kind: str  # "node" | "link"
    u: int
    v: int = -1
    arrival_s: float = 0.0

    @property
    def seeds(self) -> tuple[int, ...]:
        return (self.u,) if self.kind == "node" else (self.u, self.v)


@dataclasses.dataclass
class PreparedQuery:
    """A query with its bucket-padded ego-subgraph attached."""

    query: Query
    graph: GraphBatch  # padded to (bucket size, full-graph max_degree)
    rows: tuple[int, ...]  # seed rows in the padded subgraph
    bucket: int
    ego_nodes: int  # pre-pad ego size (diagnostics)


@dataclasses.dataclass
class ServedResult:
    query: Query
    latency_s: float
    pred: int  # node: argmax class; link: 1 iff score >= 0
    score: float  # node: max logp; link: logp_u . logp_v
    logp: np.ndarray  # (num_seeds, out_dim) — the verification surface


class ShapeBuckets:
    """A static, sorted node-count ladder. ``bucket_of(n)`` is a pure
    function of the ego size, so bucket assignment is deterministic and
    independent of arrival order; the jitted-program count is bounded by
    ``len(sizes)`` no matter what traffic arrives."""

    def __init__(self, sizes):
        self.sizes = tuple(sorted(set(int(s) for s in sizes)))
        if not self.sizes:
            raise ValueError("ShapeBuckets needs at least one size")

    @classmethod
    def geometric(cls, g: GraphBatch, *, base: int = 64, factor: int = 2) -> "ShapeBuckets":
        """base, base*factor, ... capped at the full graph's node count (the
        largest possible ego-subgraph, so the ladder always has a fit)."""
        sizes, s = [], base
        while s < g.num_nodes:
            sizes.append(s)
            s *= factor
        sizes.append(g.num_nodes)
        return cls(sizes)

    def __len__(self) -> int:
        return len(self.sizes)

    def bucket_of(self, n: int) -> int:
        for i, s in enumerate(self.sizes):
            if n <= s:
                return i
        raise ValueError(f"ego of {n} nodes exceeds the largest bucket {self.sizes[-1]}")

    def size_of(self, bucket: int) -> int:
        return self.sizes[bucket]


def _stack(graphs):
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *graphs)


class GNNServer:
    """Bucketed batching frontend over a pipeline engine's compiled eval
    programs: ``prepare`` extracts/pads one query's ego-subgraph, ``execute``
    runs up to ``chunks`` same-bucket prepared queries as one stacked batch.
    Params are bound to each bucket's ``EvalProgram`` once at warmup —
    serving never re-replicates the param tree per call."""

    def __init__(self, engine, params, g: GraphBatch, *, hops: int = 2, buckets=None):
        self.engine = engine
        self.params = params
        self.g = g
        self.hops = hops
        self.chunks = engine.config.chunks
        self.buckets = buckets if buckets is not None else ShapeBuckets.geometric(g)
        # one neighbor width everywhere: ego max_deg never exceeds the full
        # graph's, and a fixed width keeps the bucket key one-dimensional
        self.max_deg = g.max_degree
        self.stats = {}  # bucket -> {"batches": int, "queries": int}

    def prepare(self, query: Query) -> PreparedQuery:
        sub, rows = ego_subgraph(self.g, list(query.seeds), self.hops)
        bucket = self.buckets.bucket_of(sub.num_nodes)
        padded = pad_graph(sub, self.buckets.size_of(bucket), self.max_deg)
        return PreparedQuery(query, padded, tuple(int(r) for r in rows), bucket, sub.num_nodes)

    def warm(self, bucket: int, probe: PreparedQuery) -> float:
        """Compile (and time one warm call of) the bucket's program.
        Returns the warm per-batch call time in seconds."""
        batch = _stack([probe.graph] * self.chunks)
        prog = self.engine.compile_eval(self.params, batch)
        np.asarray(prog(batch))  # compile + first run
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(prog(batch))
            reps.append(time.perf_counter() - t0)
        return float(np.median(reps))

    def execute(self, prepared: list[PreparedQuery]) -> list[ServedResult]:
        """Run one same-bucket batch (1..chunks real requests; partial
        batches are padded by repeating the first request's subgraph)."""
        assert 0 < len(prepared) <= self.chunks
        bucket = prepared[0].bucket
        assert all(p.bucket == bucket for p in prepared)
        graphs = [p.graph for p in prepared]
        graphs += [prepared[0].graph] * (self.chunks - len(prepared))
        batch = _stack(graphs)
        prog = self.engine.compile_eval(self.params, batch)
        logp = np.asarray(prog(batch))  # (chunks, n_pad, out_dim); blocks
        st = self.stats.setdefault(bucket, {"batches": 0, "queries": 0})
        st["batches"] += 1
        st["queries"] += len(prepared)
        out = []
        for i, p in enumerate(prepared):
            rows = logp[i][list(p.rows)]
            if p.query.kind == "node":
                pred, score = int(rows[0].argmax()), float(rows[0].max())
            else:
                score = float(np.dot(rows[0], rows[1]))
                pred = int(score >= 0.0)
            out.append(ServedResult(p.query, 0.0, pred, score, rows))
        return out

    def occupancy(self) -> dict:
        """Per-bucket fill: real requests / (batches * chunks)."""
        return {
            self.buckets.size_of(b): {
                "batches": st["batches"],
                "queries": st["queries"],
                "occupancy": st["queries"] / (st["batches"] * self.chunks),
            }
            for b, st in sorted(self.stats.items())
        }


def synth_queries(g: GraphBatch, n: int, *, qps: float, link_frac: float, seed: int):
    """n queries over random seed nodes with exponential inter-arrivals
    (open-loop Poisson at ``qps``). Half the link queries score a real edge,
    half a random pair."""
    rng = np.random.default_rng(seed)
    nbr, msk = np.asarray(g.neighbors), np.asarray(g.mask)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n))
    queries = []
    for qid in range(n):
        u = int(rng.integers(g.num_nodes))
        if rng.random() < link_frac:
            row = nbr[u][msk[u]]
            if rng.random() < 0.5 and len(row) > 1:
                v = int(rng.choice(row[1:]))  # slot 0 is the self-loop
            else:
                v = int(rng.integers(g.num_nodes))
            if v == u:
                v = (u + 1) % g.num_nodes
            queries.append(Query(qid, "link", u, v, float(arrivals[qid])))
        else:
            queries.append(Query(qid, "node", u, -1, float(arrivals[qid])))
    return queries


def serve(server: GNNServer, queries: list[Query], *, max_wait_s: float) -> list[ServedResult]:
    """The open-loop driver: queries become eligible at their scheduled
    arrival time; same-bucket requests batch up to ``chunks``, a partial
    batch dispatches once its oldest request has waited ``max_wait_s``.
    Latency is completion minus *scheduled* arrival (queueing included), the
    honest open-loop definition — a server that can't keep up pays for it."""
    pending: dict[int, deque] = {}
    results: list[ServedResult] = []
    n_pending = 0
    i = 0
    t0 = time.perf_counter()

    def dispatch(bucket):
        nonlocal n_pending
        q = pending[bucket]
        batch = [q.popleft() for _ in range(min(len(q), server.chunks))]
        n_pending -= len(batch)
        done = server.execute(batch)
        t_done = time.perf_counter() - t0
        for r in done:
            r.latency_s = t_done - r.query.arrival_s
        results.extend(done)

    while i < len(queries) or n_pending:
        now = time.perf_counter() - t0
        while i < len(queries) and queries[i].arrival_s <= now:
            p = server.prepare(queries[i])  # prep cost is inside the clock
            pending.setdefault(p.bucket, deque()).append(p)
            n_pending += 1
            i += 1
        # full batches first; then age out partial batches; then, once the
        # arrival stream is exhausted, drain whatever is left
        ready = [b for b, q in pending.items() if len(q) >= server.chunks]
        if not ready:
            now = time.perf_counter() - t0
            ready = [
                b for b, q in pending.items()
                if q and now - q[0].query.arrival_s >= max_wait_s
            ]
        if not ready and i >= len(queries):
            ready = [b for b, q in pending.items() if q]
        if ready:
            dispatch(ready[0])
            continue
        if i < len(queries):
            now = time.perf_counter() - t0
            wake = queries[i].arrival_s
            for q in pending.values():
                if q:
                    wake = min(wake, q[0].query.arrival_s + max_wait_s)
            if wake > now:
                time.sleep(min(wake - now, 0.05))
    return results


def verify_results(
    model, params, g: GraphBatch, results: list[ServedResult], *, atol: float = 0.0
) -> tuple[int, int, float]:
    """Served-vs-full-batch check. Returns ``(mismatches, exact, max_diff)``
    where ``exact`` counts bit-identical results and ``mismatches`` counts
    results with any |diff| > ``atol``.

    On a single CPU device every served logp row is bit-identical to the
    full-batch forward — lossless halo + preserved neighbor column order
    + identical per-row reductions. A TPU tiles each bucket-shaped matmul
    by its shape: on one v5e, 2 of 40 served cora rows differed by up to
    5.96e-7 (~2 ULP of the log-probabilities). Under ``--xla_force_host_platform_
    device_count`` XLA CPU divides its thread pool and may re-tile the
    bucket-shaped gemms, re-ordering a dot product's accumulation: rare rows
    then differ by ~1 ULP (observed 1/250 at 1.19e-7). That is XLA numerics
    vs shape+threading, not the serving path — a plain ``model.apply`` on
    the same padded ego reproduces it — so the forced-device CI smoke
    verifies with a 1e-6 tolerance while the single-device tests pin strict
    bit-identity."""
    full = np.asarray(model.apply(params, g, train=False))
    bad = exact = 0
    max_diff = 0.0
    for r in results:
        want = full[list(r.query.seeds)]
        if np.array_equal(r.logp, want):
            exact += 1
        else:
            diff = float(np.abs(r.logp - want).max())
            max_diff = max(max_diff, diff)
            if diff > atol:
                bad += 1
    return bad, exact, max_diff


def run(args) -> dict:
    from repro.core.cli import PipelineCLIConfig
    from repro.core.pipeline import make_engine
    from repro.graphs import load_dataset
    from repro.models.gnn.net import build_paper_gat

    g = load_dataset(args.dataset, seed=args.seed)
    # serving is forward-only (train=False), so the pallas backend's
    # attn-dropout restriction never triggers and the paper rate can stay
    model = build_paper_gat(g.num_features, g.num_classes, backend=args.backend)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    cli = PipelineCLIConfig.from_args(args)
    if cli.auto:
        # serving shares the planner: the pick's schedule/chunks/balance/
        # placement configure the engine whose eval programs serve traffic
        from repro.core.autotune import plan_for_cli

        auto_plan = plan_for_cli(model, g, cli, params=params, seed=args.seed)
        print(auto_plan.format_table(limit=10))
        if cli.dry_run:
            return {"mode": "auto-dry-run", "schedule": auto_plan.schedule,
                    "chunks": auto_plan.chunks, "balance": list(auto_plan.balance)}
        cli = dataclasses.replace(
            cli, schedule=auto_plan.schedule, chunks=auto_plan.chunks,
            stages=auto_plan.num_stages,
        )
        engine = make_engine(model, auto_plan)
    else:
        engine = make_engine(model, cli.gpipe_config())
    buckets = ShapeBuckets.geometric(g, base=args.bucket_base)
    server = GNNServer(engine, params, g, hops=args.hops, buckets=buckets)

    n = max(1, int(round(args.qps * args.duration)))
    queries = synth_queries(g, n, qps=args.qps, link_frac=args.link_frac, seed=args.seed)

    # warmup: compile every bucket this query set will touch (compile time
    # must not land inside the measured window) and time one warm call each
    probes, order = {}, []
    for q in queries:
        p = server.prepare(q)
        if p.bucket not in probes:
            probes[p.bucket] = p
            order.append(p.bucket)
    t_warm = time.perf_counter()
    eval_call_s = {b: server.warm(b, probes[b]) for b in order}
    warmup_s = time.perf_counter() - t_warm
    server.stats.clear()
    print(f"[serve] dataset={args.dataset} engine={cli.engine} schedule={cli.schedule} "
          f"stages={cli.stages} chunks={cli.chunks} hops={args.hops} "
          f"buckets={[buckets.size_of(b) for b in sorted(probes)]} "
          f"warm_call_ms={ {buckets.size_of(b): round(t * 1e3, 2) for b, t in sorted(eval_call_s.items())} }")

    results = serve(server, queries, max_wait_s=args.max_wait_ms / 1e3)
    assert len(results) == n

    lat = np.array([r.latency_s for r in results])
    span = max(max(r.query.arrival_s + r.latency_s for r in results), 1e-9)
    occupancy = server.occupancy()
    total_batches = sum(v["batches"] for v in occupancy.values())
    summary = {
        "dataset": args.dataset,
        "engine": cli.engine,
        "schedule": cli.schedule,
        "chunks": cli.chunks,
        "stages": cli.stages,
        "hops": args.hops,
        "qps": args.qps,
        "queries": n,
        "achieved_qps": n / span,
        "p50_s": float(np.percentile(lat, 50)),
        "p99_s": float(np.percentile(lat, 99)),
        "mean_s": float(lat.mean()),
        # the gate's machine-cancelling normalizer: one warm batch call of
        # the heaviest bucket in use, measured in the same run
        "eval_call_s": float(max(eval_call_s.values())),
        # compiling and warming every bucket the query set touches
        "warmup_s": warmup_s,
        "occupancy": sum(v["queries"] for v in occupancy.values())
        / max(total_batches * server.chunks, 1),
        "buckets": occupancy,
    }
    print(f"[serve] {n} queries in {span:.2f}s: {summary['achieved_qps']:.1f} q/s "
          f"(offered {args.qps}), p50 {summary['p50_s'] * 1e3:.1f}ms "
          f"p99 {summary['p99_s'] * 1e3:.1f}ms, occupancy {summary['occupancy']:.2f}")
    for size, v in occupancy.items():
        print(f"[serve]   bucket n<={size}: {v['queries']} queries / "
              f"{v['batches']} batches (occupancy {v['occupancy']:.2f})")

    mismatches = None
    if args.verify:
        mismatches, exact, max_diff = verify_results(
            model, params, g, results, atol=args.verify_atol
        )
        summary["verify_mismatches"] = mismatches
        summary["verify_exact"] = exact
        summary["verify_max_diff"] = max_diff
        print(f"[serve] verify: {exact}/{n} served predictions bit-identical "
              f"to host full-batch eval, {mismatches} beyond "
              f"atol={args.verify_atol:g} (max diff {max_diff:.3g})")

    if args.json_out:
        os.makedirs(args.json_out, exist_ok=True)
        key = f"serving/{args.dataset}/{cli.engine}/qps{args.qps:g}"
        with open(os.path.join(args.json_out, "BENCH_serve.json"), "w") as f:
            json.dump({"rows": {key: summary}}, f, indent=2, sort_keys=True)
            f.write("\n")
        counts, edges = np.histogram(lat * 1e3, bins=30)
        with open(os.path.join(args.json_out, "latency_hist.json"), "w") as f:
            json.dump({
                "unit": "ms",
                "bin_edges": [float(e) for e in edges],
                "counts": [int(c) for c in counts],
                "p50": summary["p50_s"] * 1e3,
                "p99": summary["p99_s"] * 1e3,
            }, f, indent=2)
            f.write("\n")
    if mismatches:
        raise SystemExit(f"--verify: {mismatches} served predictions diverged")
    return summary


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags."""
    from repro.core.cli import add_pipeline_args

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--qps", type=float, default=50.0, help="offered load (open-loop Poisson)")
    ap.add_argument("--duration", type=float, default=5.0, help="arrival window, seconds")
    ap.add_argument("--hops", type=int, default=2,
                    help="ego-subgraph halo depth; >= model receptive depth (2 for "
                         "the paper GAT) makes served predictions exact")
    ap.add_argument("--link-frac", type=float, default=0.25,
                    help="fraction of link-prediction queries in the stream")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="partial batches dispatch after the oldest request waits this long")
    ap.add_argument("--bucket-base", type=int, default=64,
                    help="smallest shape bucket; ladder doubles up to the full graph")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None,
                    help="directory for BENCH_serve.json + latency_hist.json")
    ap.add_argument("--verify", action="store_true",
                    help="check every served prediction bit-identical to host full-batch eval")
    ap.add_argument("--verify-atol", type=float, default=0.0,
                    help="--verify failure tolerance; 0 = strict bit-identity (the "
                         "single-CPU-device guarantee; a TPU moves the last bits, "
                         "see verify_results). Forced-device CI uses 1e-6: "
                         "XLA CPU re-tiles bucket-shaped gemms under a divided thread "
                         "pool and rare rows shift ~1 ULP (see verify_results)")
    add_pipeline_args(ap, engine="compiled", chunks=4, stages=4)
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
