"""Profiling-driven stage partitioning for the GNN pipeline.

The paper's Fig 3 runtime model (and ``Schedule.predicted_step_time``'s
default) assumes every stage costs ``total / num_stages`` — but real GNN
stacks are heterogeneous (a 1433-wide input conv next to an 8-wide hidden
conv, attention next to dropout), so the slowest stage sets the pipeline
tick and the balanced model silently diverges from measurement. GNNPipe
(Chen et al. 2023) and GraphPipe (Jeon et al. 2024) both show cost-aware
partitioning — not just a better tick order — is where pipelined GNN
training wins. This module supplies that layer:

  * ``profile_layer_costs`` — measure each ``SeqLayer``'s forward,
    input-grad (B) and weight-grad (W) cost over the REAL jitted slices on
    a representative padded chunk, exactly the work the engines dispatch;
  * ``choose_balance`` — enumerate contiguous layer->stage groupings and
    pick the one minimizing the target schedule's *weighted* makespan
    (``predicted_step_time(stage_fwd_costs=..., stage_bwd_costs=...)``, the
    ``_weighted`` hooks that previously only ever saw uniform costs);
  * ``uniform_balance`` — the layer-count split the profiled partition is
    benchmarked against.

The output is an ordinary ``balance`` tuple, so the partitioner composes
with every engine, schedule and ``Placement`` unchanged — partitioning
moves layer boundaries, never the math (property-tested: any balance
produces updates bit-identical to the host fill-drain baseline).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.graphs.data import GraphBatch
from repro.models.gnn.net import GNNModel


# eq=False: float tuples would compare fine, but cost tables are measurement
# artifacts — identity semantics keep accidental == out of test assertions.
@dataclasses.dataclass(frozen=True, eq=False)
class LayerCosts:
    """Measured per-layer per-chunk costs (seconds) on one padded chunk.

    ``bwd`` is the fused backward — ONE vjp producing both grads, exactly
    what the fused-``bwd`` schedules execute. It is measured directly, not
    summed from the halves: each split half replays the layer's forward
    primal, so ``bwd_b + bwd_w`` carries two primals where the fused vjp
    carries one (the halves match the real zb-h1 execution, which does
    re-materialize per half; the fused number matches everything else).
    """

    names: tuple[str, ...]
    fwd: tuple[float, ...]
    bwd: tuple[float, ...]  # fused backward: one vjp, both grads
    bwd_b: tuple[float, ...]  # input-grad half (the pipeline's critical path)
    bwd_w: tuple[float, ...]  # weight-grad half (deferred by zb-h1)

    def _check_balance(self, balance: tuple[int, ...]):
        if sum(balance) != len(self.names):
            raise ValueError(
                f"balance {balance} must sum to {len(self.names)} layers"
            )

    def stage_costs(self, balance: tuple[int, ...]):
        """(stage_fwd_costs, stage_bwd_costs) for a contiguous ``balance``
        grouping — each stage's cost is the sum of its member layers'
        (``bwd`` = the measured fused backward)."""
        self._check_balance(balance)
        f, b, lo = [], [], 0
        for n in balance:
            f.append(sum(self.fwd[lo : lo + n]))
            b.append(sum(self.bwd[lo : lo + n]))
            lo += n
        return f, b

    def stage_costs_split(self, balance: tuple[int, ...]):
        """(fwd, bwd_b, bwd_w) per-stage sums — the measured B/W halves the
        zero-bubble makespan weights separately (B is critical-path, W is
        bubble filler; a 50/50 assumption misprices e.g. a wide input conv
        whose weight grad dominates its input grad)."""
        self._check_balance(balance)
        f, b, w, lo = [], [], [], 0
        for n in balance:
            f.append(sum(self.fwd[lo : lo + n]))
            b.append(sum(self.bwd_b[lo : lo + n]))
            w.append(sum(self.bwd_w[lo : lo + n]))
            lo += n
        return f, b, w

    def table(self) -> list[dict]:
        """The per-layer cost table (benchmark artifact / CLI printout)."""
        return [
            {
                "layer": i,
                "name": self.names[i],
                "fwd_s": self.fwd[i],
                "bwd_s": self.bwd[i],
                "bwd_b_s": self.bwd_b[i],
                "bwd_w_s": self.bwd_w[i],
            }
            for i in range(len(self.names))
        ]


def _time_best_of(fn, args, *, repeats: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def profile_layer_costs(
    model: GNNModel,
    params: list,
    graph: GraphBatch,
    *,
    rng: jax.Array | None = None,
    train: bool = True,
    repeats: int = 3,
    warmup: int = 1,
) -> LayerCosts:
    """Measure fwd / input-grad / weight-grad cost of every ``SeqLayer`` on
    ``graph`` (one representative padded chunk — the same shape the engines
    dispatch per tick, so stage sums predict per-tick stage costs).

    Each layer is timed through its own jitted callable: forward is the
    layer's ``apply``; the halves are explicit ``jax.vjp``s wrt the input
    and wrt the params — precisely the slices the scheduled executor's
    ``bwd_b`` / ``bwd_w`` branches differentiate. Best-of-``repeats`` with
    ``warmup`` discarded compile runs.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    fwd_s, bwd_s, b_s, w_s = [], [], [], []
    h = graph.features
    for layer, p in zip(model.layers, params):
        def fwd(p_, h_, L=layer):
            return L.apply(p_, graph, h_, rng, train)

        def bwd(p_, h_, ct, L=layer):
            # the fused backward: ONE vjp, one primal, both grads
            _, vjp = jax.vjp(lambda pp, hh: L.apply(pp, graph, hh, rng, train), p_, h_)
            return vjp(ct)

        def bwd_b(p_, h_, ct, L=layer):
            _, vjp = jax.vjp(lambda hh: L.apply(p_, graph, hh, rng, train), h_)
            return vjp(ct)[0]

        def bwd_w(p_, h_, ct, L=layer):
            _, vjp = jax.vjp(lambda pp: L.apply(pp, graph, h_, rng, train), p_)
            return vjp(ct)[0]

        fwd_j = jax.jit(fwd)
        y = jax.block_until_ready(fwd_j(p, h))
        ct = jnp.ones_like(y)
        fwd_s.append(_time_best_of(fwd_j, (p, h), repeats=repeats, warmup=warmup))
        bwd_s.append(
            _time_best_of(jax.jit(bwd), (p, h, ct), repeats=repeats, warmup=warmup)
        )
        b_s.append(
            _time_best_of(jax.jit(bwd_b), (p, h, ct), repeats=repeats, warmup=warmup)
        )
        w_s.append(
            _time_best_of(jax.jit(bwd_w), (p, h, ct), repeats=repeats, warmup=warmup)
        )
        h = y
    return LayerCosts(
        names=tuple(layer.name for layer in model.layers),
        fwd=tuple(fwd_s),
        bwd=tuple(bwd_s),
        bwd_b=tuple(b_s),
        bwd_w=tuple(w_s),
    )


def profile_fingerprint(model, params, graph, backend: str = "padded") -> str:
    """The cache key a profile is stored under: a digest of the model's
    layer stack (names + every param leaf's shape/dtype), the chunk shape
    the engines dispatch per tick, the aggregation backend and the device
    that measures (platform and kind). Two runs measuring the same (model,
    chunk shape, backend) on the same kind of device re-measure the same
    jitted programs, so their costs are interchangeable — anything else
    (different widths, padding, backend lowering, a CPU profile read on a
    TPU) is a different key."""
    device = jax.devices()[0]
    spec = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "layers": [layer.name for layer in model.layers],
        "params": [
            [(list(a.shape), str(a.dtype)) for a in jax.tree_util.tree_leaves(p)]
            for p in params
        ],
        "chunk": [
            list(graph.features.shape),
            list(graph.neighbors.shape),
        ],
        "backend": backend,
    }
    return hashlib.sha1(json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]


# in-process profile cache: fingerprint -> LayerCosts. One sweep (fig3's
# matrix, the --auto planner's chunk ladder) profiles each shape once.
_PROFILE_CACHE: dict[str, LayerCosts] = {}


def cached_profile_layer_costs(
    model,
    params,
    graph,
    *,
    backend: str = "padded",
    cache_path: str | None = None,
    refresh: bool = False,
    **profile_kwargs,
) -> LayerCosts:
    """``profile_layer_costs`` behind a two-level cache keyed by
    ``profile_fingerprint`` (model layer stack + chunk shape + backend):

      * an in-process dict, so ``--auto`` and ``--partition profiled``
        never re-profile the same shape within a run;
      * an optional JSON sidecar at ``cache_path``, so a benchmark sweep
        (fig3's ``args.layer_costs`` pass-through) reuses measurements
        across processes — and ships them as an artifact.

    ``refresh=True`` bypasses both reads (the write still lands, replacing
    the stale entry). Corrupt or unreadable sidecars are ignored, never
    fatal: the profiler is the fallback."""
    key = profile_fingerprint(model, params, graph, backend)
    if not refresh:
        hit = _PROFILE_CACHE.get(key)
        if hit is not None:
            return hit
        if cache_path and os.path.exists(cache_path):
            try:
                with open(cache_path) as f:
                    entry = json.load(f).get(key)
            except (OSError, json.JSONDecodeError):
                entry = None
            if entry is not None:
                costs = LayerCosts(
                    names=tuple(entry["names"]),
                    fwd=tuple(entry["fwd"]),
                    bwd=tuple(entry["bwd"]),
                    bwd_b=tuple(entry["bwd_b"]),
                    bwd_w=tuple(entry["bwd_w"]),
                )
                _PROFILE_CACHE[key] = costs
                return costs
    costs = profile_layer_costs(model, params, graph, **profile_kwargs)
    _PROFILE_CACHE[key] = costs
    if cache_path:
        store: dict = {}
        if os.path.exists(cache_path):
            try:
                with open(cache_path) as f:
                    store = json.load(f)
            except (OSError, json.JSONDecodeError):
                store = {}
        store[key] = {
            "names": list(costs.names),
            "fwd": list(costs.fwd),
            "bwd": list(costs.bwd),
            "bwd_b": list(costs.bwd_b),
            "bwd_w": list(costs.bwd_w),
        }
        parent = os.path.dirname(cache_path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = f"{cache_path}.tmp"
        with open(tmp, "w") as f:
            json.dump(store, f, indent=1, sort_keys=True)
        os.replace(tmp, cache_path)
    return costs


def uniform_balance(n_layers: int, num_stages: int) -> tuple[int, ...]:
    """The layer-COUNT-balanced contiguous split (earlier stages take the
    remainder) — the baseline the profiled partition is measured against."""
    if not 1 <= num_stages <= n_layers:
        raise ValueError(f"need 1 <= num_stages <= {n_layers}, got {num_stages}")
    base, rem = divmod(n_layers, num_stages)
    return tuple(base + (1 if s < rem else 0) for s in range(num_stages))


def enumerate_balances(n_layers: int, num_stages: int):
    """All contiguous groupings of ``n_layers`` into ``num_stages`` non-empty
    stages, as balance tuples (C(n-1, S-1) of them)."""
    for cuts in itertools.combinations(range(1, n_layers), num_stages - 1):
        bounds = (0, *cuts, n_layers)
        yield tuple(bounds[i + 1] - bounds[i] for i in range(num_stages))


def predicted_balance_time(
    costs: LayerCosts,
    balance: tuple[int, ...],
    schedule,
    num_chunks: int,
    *,
    transfer_cost: float = 0.0,
) -> float:
    """``schedule``'s weighted makespan under ``costs`` grouped by
    ``balance`` (seconds per step, rebuild excluded — it is
    partition-independent). Zero-bubble schedules get the MEASURED B/W
    halves instead of the 50/50 fallback split."""
    from repro.core.schedule import ZeroBubbleH1Schedule

    if isinstance(schedule, ZeroBubbleH1Schedule):
        f, b, w = costs.stage_costs_split(balance)
        return schedule.predicted_step_time(
            len(balance),
            num_chunks,
            stage_fwd_costs=f,
            stage_bwd_b_costs=b,
            stage_bwd_w_costs=w,
            transfer_cost=transfer_cost,
        )
    f, b = costs.stage_costs(balance)
    return schedule.predicted_step_time(
        len(balance),
        num_chunks,
        stage_fwd_costs=f,
        stage_bwd_costs=b,
        transfer_cost=transfer_cost,
    )


def choose_balance(
    costs: LayerCosts,
    num_stages: int,
    schedule,
    num_chunks: int,
    *,
    transfer_cost: float = 0.0,
    max_candidates: int = 100_000,
) -> tuple[tuple[int, ...], float]:
    """The contiguous balance minimizing ``schedule``'s weighted makespan
    under the measured costs. Exhaustive over the C(n-1, S-1) candidates
    (ties break toward the uniform split, then lexicographically) — GNN
    stacks are tens of layers, not thousands; ``max_candidates`` guards the
    combinatorial cliff with a clear error instead of a silent hang.
    Returns (balance, predicted_step_seconds)."""
    n = len(costs.names)
    n_cand = math.comb(n - 1, num_stages - 1)
    if n_cand > max_candidates:
        raise ValueError(
            f"{n_cand} candidate partitions of {n} layers into {num_stages} "
            f"stages exceeds max_candidates={max_candidates}"
        )
    uniform = uniform_balance(n, num_stages)
    best: tuple | None = None
    for bal in enumerate_balances(n, num_stages):
        t = predicted_balance_time(
            costs, bal, schedule, num_chunks, transfer_cost=transfer_cost
        )
        cand = (t, bal != uniform, bal)
        if best is None or cand < best:
            best = cand
    assert best is not None
    return best[2], best[0]
