"""Pipeline engines for GNNs — one interface, two executors.

``PipelineEngine`` is the contract (init_params / train_step / describe);
two implementations ship:

  * ``GPipe`` — the paper's §6 implementation, JAX-native and host-driven:
    the pluggable ``Schedule`` timeline executes at Python level with
    per-stage jitted kernels, mirroring torchgpipe's queues. Paper-faithful;
    schedules (fill-drain / 1F1B / interleaved) untouched.
  * ``CompiledGNNPipeline`` — the whole train step (forward pipeline over
    ``lax.scan`` + ``lax.ppermute``, loss over core masks, backward through
    the same collectives, canonical gradient reduction, optimizer update) is
    ONE jitted SPMD program over a ``("stage",)`` mesh axis. The micro-batch
    plan rides as a stacked uniform-shape pytree (``MicroBatchPlan.stacked``)
    so the subgraphs travel with the activations. With fewer devices than
    stages the same program body runs under ``jax.vmap(axis_name="stage")``
    — identical collective semantics, still one fused XLA program.

``make_engine(model, config)`` picks one via ``config.engine``; ``config``
may also be a planner ``PipelinePlan`` (``repro.core.autotune``), so an
``--auto`` pick replays directly. Both engines expose
``compile_eval(params, graph) -> EvalProgram`` — a per-shape forward-only
program handle with the params bound once — which ``evaluate`` and the
serving frontend (``repro.launch.serve_gnn``) share.

GPipe's faithful semantics:

  * the sequential model is partitioned into stages by a ``balance`` array
    (same contract as ``torchgpipe.GPipe(model, balance, chunks)``);
  * the input is micro-batched into ``chunks`` (strategy pluggable — the
    paper's index-sequential split is the default and reproduces its
    accuracy collapse);
  * work executes in the order a pluggable ``Schedule`` timeline dictates —
    fill-drain (GPipe, the paper), 1F1B, or interleaved 1F1B over virtual
    stages (``repro.core.schedule``); backward re-computes each stage's
    internals from its saved input (GPipe's activation re-materialization)
    and accumulates gradients across micro-batches;
  * a single synchronous optimizer update closes the step, so neither the
    number of chunks nor the schedule ever changes the *intended* gradient —
    per-chunk gradients are reduced in a canonical order, making every
    schedule's update bit-identical to the fill-drain baseline. Only lossy
    micro-batching of the graph moves the numbers (measured by
    ``plan.edge_cut``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.vma import match_vma
from repro.core.microbatch import MicroBatchPlan
from repro.graphs.data import BucketedGraphBatch
from repro.graphs.partition import bucketize_stacked
from repro.core.schedule import (
    PHASE_BWD,
    PHASE_BWD_B,
    PHASE_BWD_W,
    PHASE_FWD,
    Placement,
    forward_timeline,
    get_schedule,
    lower_timeline,
    retime_timeline,
)
from repro.core.spmd_pipe import (
    spmd_pipeline,
    spmd_pipeline_scheduled,
    spmd_pipeline_scheduled_eval,
    spmd_pipeline_scheduled_eval_lanes,
    spmd_pipeline_scheduled_lanes,
)
from repro.models.gnn.net import (
    GNNModel,
    activation_widths,
    make_gnn_stage,
    make_gnn_stage_slices,
    make_gnn_stage_slices_bw,
    travel_width,
)
from repro.train import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class GPipeConfig:
    """Everything that selects a pipeline: stage balance, chunking, the
    schedule and its device placement, the engine that executes it, the
    aggregation backend and the data-parallel width."""

    balance: tuple[int, ...]  # layers per stage; sums to len(model.layers)
    chunks: int
    devices: tuple | None = None  # optional per-stage device placement
    schedule: str = "fill_drain"  # any repro.core.schedule.SCHEDULES name
    num_devices: int | None = None  # interleaved/zb-v: physical devices (V = stages/devices)
    remat: bool = True  # compiled engine: GPipe-style activation re-materialization
    # stage -> device assignment overriding the schedule's default (ring
    # rotations + a physical device order); validated against the lowering's
    # ring check at engine construction
    placement: Placement | None = None
    engine: str = "host"  # "host" | "compiled"; consumed by make_engine
    # aggregation backend: "padded" | "dense" | "pallas". Must match the
    # backend the model's layers were built with; under "pallas" both
    # engines additionally feed the stage programs the degree-bucketed
    # layout (graphs.partition.bucketize_stacked) instead of the raw
    # padded batch, so aggregation work tracks the degree distribution.
    backend: str = "padded"
    # graph data parallelism (compiled engine): replicas on the "data" axis
    # of a (data, stage) mesh, each running the pipeline over its contiguous
    # shard of the chunks. Gradients are gathered over the axis and reduced
    # in the canonical global chunk order, so the update stays bit-identical
    # to a single replica. Requires chunks % data_parallel == 0.
    data_parallel: int = 1
    # communication/compute overlap (compiled engine): "off" keeps the
    # serialized ppermute-after-work tick; "double-buffer" retimes the
    # timeline to wire_latency 2 so each tick posts the NEXT tick's
    # transfers before its work (parity-alternating wire buffers — see
    # spmd_pipe's wire-parity rule); "async" is double-buffer plus
    # best-effort XLA latency-hiding-scheduler flags (core.overlap_report).
    # Pure retiming: updates stay bit-identical to "off" for every
    # schedule × placement × data-parallel combo.
    overlap: str = "off"

    @property
    def num_stages(self) -> int:
        """Pipeline stages (= entries in ``balance``)."""
        return len(self.balance)


def _varying(branches, axes: tuple[str, ...]):
    """``lax.switch`` branches whose outputs all vary over ``axes``: an idle
    branch's zeros are unvarying while a compute branch's outputs vary over
    the ring, and the switch requires one output type."""
    return [lambda operand, b=b: match_vma(b(operand), extra=axes) for b in branches]


@jax.jit
def _eval_metric_head(logp, labels, masks):
    """Shared metric head for both engines' eval programs: masked means over
    the (chunks, n_pad) grid — padding rows and halo ghosts carry zero mask,
    so on a lossless plan these equal the full-batch numbers bit for bit."""
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    hit = (jnp.argmax(logp, axis=-1) == labels).astype(jnp.float32)

    def masked_mean(x, mask):
        m = mask.astype(jnp.float32)
        return jnp.sum(x * m) / jnp.maximum(jnp.sum(m), 1.0)

    return {
        "train_loss": masked_mean(nll, masks["train"]),
        "train_acc": masked_mean(hit, masks["train"]),
        "val_acc": masked_mean(hit, masks["val"]),
        "test_acc": masked_mean(hit, masks["test"]),
    }


class EvalProgram:
    """Handle for ONE compiled forward-only inference program at a fixed
    stacked-batch shape ``(chunks, n_pad, max_deg)`` — the unit of the
    serving engine's shape bucketing.

    ``engine.compile_eval(params, graph)`` compiles (or fetches the cached)
    program for the graph's shape and ``bind``s the params: replication onto
    the program's eval mesh happens ONCE here, not per call — the old
    ``evaluate`` re-issued a ``device_put`` of the full param tree on every
    call, allocation churn that dominates small-batch serving.
    ``__call__(graph)`` runs one stacked batch and returns per-chunk
    log-probabilities ``(chunks, n_pad, out_dim)``; ``metrics`` is the fused
    metric head ``evaluate`` layers on top."""

    def __init__(self, forward, mesh, out_dim: int, key: tuple):
        self._forward = forward
        self.mesh = mesh  # None on the host / lane substrates
        self.out_dim = out_dim
        self.key = key  # (chunks, n_pad, max_deg)
        self._bound = None  # (params as handed in, params placed on the mesh)

    @property
    def chunks(self) -> int:
        """Chunk count this program was compiled for."""
        return self.key[0]

    @property
    def n_pad(self) -> int:
        """Padded per-chunk node count this program was compiled for."""
        return self.key[1]

    def bind(self, params) -> "EvalProgram":
        """Place ``params`` for this program — replicated over the eval mesh
        when there is one — unless the same tree object is already bound.
        Serving binds once at warmup; every batch reuses the resident copy.
        (Training naturally re-binds each epoch: new step, new param tree.)"""
        if self._bound is None or self._bound[0] is not params:
            placed = params
            if self.mesh is not None:
                # the eval ring places one stage per device; params coming out
                # of a train step whose mesh spans a different device set
                # (e.g. interleaved's 2-device ring on a 4-device host) must
                # be re-replicated onto the eval mesh or jit rejects the mix
                placed = jax.device_put(
                    params, jax.sharding.NamedSharding(self.mesh, P())
                )
            self._bound = (params, placed)
        return self

    def __call__(self, graph):
        """Run one stacked batch -> logp ``(chunks, n_pad, out_dim)``."""
        if self._bound is None:
            raise ValueError("EvalProgram: call bind(params) before __call__")
        return self._forward(self._bound[1], graph)

    def metrics(self, graph, core_mask) -> dict:
        """The classic ``evaluate`` metric dict over the batch's core nodes."""
        masks = {
            "train": graph.train_mask & core_mask,
            "val": graph.val_mask & core_mask,
            "test": graph.test_mask & core_mask,
        }
        return _eval_metric_head(self(graph), graph.labels, masks)


class PipelineEngine:
    """Contract both engines implement: partition a sequential ``GNNModel``
    by a ``balance`` array, then run synchronous pipeline train steps over a
    ``MicroBatchPlan``. Subclasses provide ``train_step``."""

    name = "base"

    def __init__(self, model: GNNModel, config: GPipeConfig):
        if sum(config.balance) != len(model.layers):
            raise ValueError(
                f"balance {config.balance} must sum to {len(model.layers)} layers"
            )
        if config.data_parallel < 1:
            raise ValueError(f"data_parallel must be >= 1, got {config.data_parallel}")
        if config.overlap not in ("off", "double-buffer", "async"):
            raise ValueError(
                f"overlap must be 'off', 'double-buffer' or 'async', got "
                f"{config.overlap!r}"
            )
        self.model = model
        self.config = config
        # flipped by the compiled engine's step builder when the 2-D
        # (data, stage) mesh actually runs (enough devices for dp * ring)
        self._data_parallel_active = False
        self.schedule = get_schedule(config.schedule, num_devices=config.num_devices)
        self.placement = config.placement
        if self.placement is not None:
            self.placement.validate(config.num_stages)
            want = self.schedule.num_devices(config.num_stages)
            if self.placement.num_devices != want:
                raise ValueError(
                    f"placement spans {self.placement.num_devices} devices "
                    f"but schedule {config.schedule!r} places "
                    f"{config.num_stages} stages on {want}"
                )
        self._bounds: list[tuple[int, int]] = []
        lo = 0
        for b in config.balance:
            self._bounds.append((lo, lo + b))
            lo += b
        # graph -> backend layout, keyed by id(); entries retain the graph
        # so a recycled id() can never serve a stale layout
        self._layout_cache: dict = {}

    # ------------------------------------------------------------ stages --

    def stage_params(self, params: list, s: int) -> list:
        """The slice of per-layer params owned by stage ``s``."""
        lo, hi = self._bounds[s]
        return params[lo:hi]

    def _stage_of_layer(self, layer_idx: int) -> int:
        for s, (lo, hi) in enumerate(self._bounds):
            if lo <= layer_idx < hi:
                return s
        raise IndexError(layer_idx)

    # ---------------------------------------------------------- contract --

    def init_params(self, key: jax.Array) -> list:
        """Fresh per-layer params from the wrapped model."""
        return self.model.init_params(key)

    def train_step(
        self,
        params: list,
        opt_state,
        plan: MicroBatchPlan,
        rng: jax.Array,
        optimizer: opt_lib.Optimizer,
        *,
        record: list | None = None,
        stats: dict | None = None,
    ):
        """One optimizer step over the plan's chunks; returns
        ``(params, opt_state, mean_loss)``."""
        raise NotImplementedError

    def compile_eval(self, params: list, graph) -> EvalProgram:
        """Compile (or fetch the cached) forward-only eval program for the
        shape of ``graph`` — a stacked pytree with leaves ``(chunks, n_pad,
        ...)`` such as ``StackedPlan.graph`` or a serving bucket batch — and
        bind ``params`` to it (replicated once, reused across calls). Both
        engines implement this, so ``--engine host|compiled`` stays symmetric
        all the way into the serving frontend."""
        raise NotImplementedError

    def layout(self, graph):
        """The aggregation layout this engine's programs consume for a
        chunk-stacked ``graph``: the padded batch itself for the padded and
        dense backends, its degree-bucketed wrapper
        (``graphs.partition.bucketize_stacked``) under ``backend="pallas"``.
        The wrapper delegates every padded-batch attribute, so downstream
        plumbing (loss masks, metric heads, shape keys) is layout-blind."""
        if self.config.backend != "pallas" or isinstance(graph, BucketedGraphBatch):
            return graph
        cached = self._layout_cache.get(id(graph))
        if cached is not None and cached[0] is graph:
            return cached[1]
        wrapped = bucketize_stacked(graph)
        self._layout_cache[id(graph)] = (graph, wrapped)
        return wrapped

    def evaluate(self, params: list, plan: MicroBatchPlan) -> dict:
        """Forward-only inference over the plan's chunks: the same metric
        dict as ``repro.train.loop.make_eval``, produced by this engine's
        compiled eval program. Metrics cover each chunk's core nodes; with a
        lossless plan (halo, hops >= model depth) they equal the full-batch
        numbers, with the paper's sequential split they reflect its dropped
        edges."""
        stacked = plan.stacked()
        graph = self.layout(stacked.graph)
        prog = self.compile_eval(params, graph)
        return prog.metrics(graph, stacked.core_mask)

    def describe(self) -> dict:
        """Engine + schedule metadata for logs and benchmark tables."""
        d = self.schedule.describe(self.config.num_stages, self.config.chunks)
        d.update(
            {
                "engine": self.name,
                "balance": list(self.config.balance),
                "chunks": self.config.chunks,
                "layers": [l.name for l in self.model.layers],
            }
        )
        if self.placement is not None:
            d["placement"] = list(self.placement.stage_to_device)
        if self.config.data_parallel > 1:
            d["data_parallel"] = self.config.data_parallel
        return d


class GPipe(PipelineEngine):
    """Host-driven pipeline-parallel wrapper around a sequential ``GNNModel``
    (the paper's §6 torchgpipe analogue; schedules are pluggable)."""

    name = "host"

    def __init__(self, model: GNNModel, config: GPipeConfig):
        super().__init__(model, config)
        if config.data_parallel > 1:
            raise ValueError(
                "data_parallel > 1 needs the compiled engine's (data, stage) "
                "mesh; the host queue loop has no data axis"
            )
        if config.overlap != "off":
            raise ValueError(
                "overlap needs the compiled engine's wire buffers; the host "
                "queue loop has no wires to double-buffer"
            )
        self._fwd_fns = [self._make_fwd(s) for s in range(config.num_stages)]
        self._bwd_fns = [self._make_bwd(s) for s in range(config.num_stages)]
        # split-backward halves (zb-h1); jit is lazy, so unused schedules
        # never pay for them
        self._bwd_b_fns = [self._make_bwd_b(s) for s in range(config.num_stages)]
        self._bwd_w_fns = [self._make_bwd_w(s) for s in range(config.num_stages)]
        self._loss_grad = jax.jit(jax.value_and_grad(_chunk_loss_sum, argnums=0, has_aux=True))
        self._evals: dict = {}  # (chunks, n_pad, max_deg) -> EvalProgram

    def compile_eval(self, params: list, graph) -> EvalProgram:
        """Host twin of the compiled engine's eval program: one jitted
        ``lax.scan`` over the stacked chunks applying the full layer stack
        (eval needs no pipelining — there is no queue to keep busy)."""
        key = (
            graph.features.shape[0],
            graph.features.shape[1],
            graph.neighbors.shape[2],
        )
        prog = self._evals.get(key)
        if prog is None:
            model = self.model

            def forward(params, g):
                def body(_, chunk):
                    return None, model.apply(params, chunk, train=False)

                _, logp = lax.scan(body, None, g)
                return logp

            prog = EvalProgram(jax.jit(forward), None, model.out_dim, key)
            self._evals[key] = prog
        return prog.bind(params)

    def _stage_apply(self, s: int, stage_params: list, mb_graph, h, rngs, train: bool):
        lo, hi = self._bounds[s]
        for i, layer in enumerate(self.model.layers[lo:hi]):
            h = layer.apply(stage_params[i], mb_graph, h, rngs[i], train)
        return h

    def _make_fwd(self, s: int):
        def fwd(stage_params, mb_graph, h, rngs):
            return self._stage_apply(s, stage_params, mb_graph, h, rngs, True)

        return jax.jit(fwd)

    def _make_bwd(self, s: int):
        """Recompute-backward: GPipe re-materializes the stage forward from
        its saved input, then pulls the cotangent back."""

        def bwd(stage_params, mb_graph, h_in, rngs, ct):
            def f(p, h):
                return self._stage_apply(s, p, mb_graph, h, rngs, True)

            _, vjp = jax.vjp(f, stage_params, h_in)
            d_params, d_h = vjp(ct)
            return d_params, d_h

        return jax.jit(bwd)

    def _make_bwd_b(self, s: int):
        """Zero-bubble B half: input-grad only (vjp wrt the stage input, so
        the weight-grad work is dead code) — the critical-path product."""

        def bwd_b(stage_params, mb_graph, h_in, rngs, ct):
            def f(h):
                return self._stage_apply(s, stage_params, mb_graph, h, rngs, True)

            _, vjp = jax.vjp(f, h_in)
            (d_h,) = vjp(ct)
            return d_h

        return jax.jit(bwd_b)

    def _make_bwd_w(self, s: int):
        """Zero-bubble W half: weight-grad only, re-materialized from the
        residual its B half banked (the saved stage input + applied
        cotangent) — runs whenever the schedule finds an idle tick."""

        def bwd_w(stage_params, mb_graph, h_in, rngs, ct):
            def f(p):
                return self._stage_apply(s, p, mb_graph, h_in, rngs, True)

            _, vjp = jax.vjp(f, stage_params)
            (d_params,) = vjp(ct)
            return d_params

        return jax.jit(bwd_w)

    def _place(self, tree, s: int):
        devs = self.config.devices
        if not devs:
            return tree
        if self.placement is not None:
            pos = self.placement.stage_to_device[s]
            order = self.placement.device_order
            phys = order[pos] if order is not None else pos
        else:
            phys = self.schedule.device_of(s, self.config.num_stages)
        return jax.device_put(tree, devs[phys % len(devs)])

    # -------------------------------------------------------------- step --

    def init_params(self, key: jax.Array) -> list:
        """Fresh per-layer params, placed on the configured stage devices
        when the config carries an explicit device list."""
        params = self.model.init_params(key)
        if self.config.devices:
            params = [
                self._place(p, self._stage_of_layer(i)) for i, p in enumerate(params)
            ]
        return params

    def _layer_rngs(self, rng: jax.Array, chunk: int):
        n_layers = len(self.model.layers)
        chunk_key = jax.random.fold_in(rng, chunk)
        return jax.random.split(chunk_key, n_layers)

    def _chunk_graphs(self, plan: MicroBatchPlan) -> list:
        """Per-chunk graphs the stage fns consume: the plan's padded batches
        as-is, or (pallas) their degree-bucketed layouts. All chunks share
        one set of bucket capacities (``bucketize_stacked`` on the stacked
        plan, sliced back per chunk), so each per-stage jitted fn compiles
        once and serves every chunk."""
        if self.config.backend != "pallas":
            return [mb.graph for mb in plan.batches]
        cached = self._layout_cache.get(id(plan))
        if cached is not None and cached[0] is plan:
            return cached[1]
        stacked = self.layout(plan.stacked().graph)
        graphs = [
            jax.tree_util.tree_map(lambda a, c=c: a[c], stacked)
            for c in range(plan.chunks)
        ]
        self._layout_cache[id(plan)] = (plan, graphs)
        return graphs

    def _run_fwd_item(self, params, plan, graphs, rng, it, saved, outs, record):
        """Execute one forward work item: consume the saved stage input,
        produce (and route) the stage output."""
        s, c = it.stage, it.chunk
        g = graphs[c]
        h = g.features if s == 0 else saved[(s, c)]
        t0 = time.perf_counter()
        rngs = self._layer_rngs(rng, c)
        lo, _ = self._bounds[s]
        h_out = self._fwd_fns[s](
            self.stage_params(params, s),
            g,
            self._place(h, s),
            rngs[lo : lo + self.config.balance[s]],
        )
        if record is not None:
            jax.block_until_ready(h_out)
            record.append(("fwd", it.tick, s, c, time.perf_counter() - t0))
        if s == 0:
            saved[(0, c)] = g.features
        if s + 1 < self.config.num_stages:
            saved[(s + 1, c)] = h_out
        else:
            outs[c] = h_out

    def train_step(
        self,
        params: list,
        opt_state,
        plan: MicroBatchPlan,
        rng: jax.Array,
        optimizer: opt_lib.Optimizer,
        *,
        record: list | None = None,
        stats: dict | None = None,
    ):
        """One synchronous pipeline step under ``config.schedule``: the
        timeline's work items execute in order (fwd saves its stage input,
        bwd recomputes + frees it, accumulating per-chunk gradients), then
        one optimizer update closes the step. Gradients are reduced in a
        canonical chunk order so every schedule produces a bit-identical
        update. ``stats`` (if given) receives measured peak live activations
        and the schedule's bubble accounting."""
        S, C = self.config.num_stages, plan.chunks
        timeline = self.schedule.timeline(S, C)
        if self.placement is not None:
            # re-device the items (ticks/order untouched): recorded items and
            # _place() then reflect the configured stage->device assignment
            timeline = self.placement.apply(timeline)
        graphs = self._chunk_graphs(plan)

        saved: dict[tuple[int, int], Any] = {}
        outs: dict[int, Any] = {}
        cts: dict[int, Any] = {}
        residuals: dict[tuple[int, int], Any] = {}  # zb-h1: (h_in, ct) per B
        chunk_losses: list[Any] = [None] * C
        chunk_grads: list[list[Any]] = [[None] * C for _ in range(S)]
        peak_live = 0
        peak_residuals = 0

        for it in timeline:
            if it.phase == "fwd":
                self._run_fwd_item(params, plan, graphs, rng, it, saved, outs, record)
                peak_live = max(peak_live, len(saved))
                continue
            s, c = it.stage, it.chunk
            mb = plan.batches[c]
            g = graphs[c]
            if s == S - 1 and it.phase in ("bwd", "bwd_b"):
                # the chunk's loss cotangent, computed once its fwd completes
                (loss_sum, count), d_h = self._loss_grad(
                    outs.pop(c), mb.graph.labels, mb.graph.train_mask & mb.core_mask
                )
                chunk_losses[c] = (loss_sum, count)
                cts[c] = d_h
            rngs = self._layer_rngs(rng, c)
            lo, hi = self._bounds[s]
            t0 = time.perf_counter()
            # route the saved stage input and the arriving cotangent onto
            # this stage's device, exactly like the forward path does for its
            # input — with per-stage placement they arrive committed to the
            # NEIGHBOR stage's device and the jitted backward rejects the mix
            if it.phase == "bwd":
                d_params, d_h = self._bwd_fns[s](
                    self.stage_params(params, s),
                    g,
                    self._place(saved.pop((s, c)), s),
                    rngs[lo:hi],
                    self._place(cts[c], s),
                )
                cts[c] = d_h
                chunk_grads[s][c] = d_params
                produced = d_h
            elif it.phase == "bwd_b":
                # B: emit the upstream cotangent now, defer the weight grad
                # — the stage input moves from `saved` into the W residual
                h_in = self._place(saved.pop((s, c)), s)
                ct = self._place(cts[c], s)
                d_h = self._bwd_b_fns[s](
                    self.stage_params(params, s), g, h_in, rngs[lo:hi], ct
                )
                residuals[(s, c)] = (h_in, ct)
                peak_residuals = max(peak_residuals, len(residuals))
                cts[c] = d_h
                produced = d_h
            else:  # "bwd_w": consume the residual, produce the weight grad
                h_in, ct = residuals.pop((s, c))
                chunk_grads[s][c] = self._bwd_w_fns[s](
                    self.stage_params(params, s), g, h_in, rngs[lo:hi], ct
                )
                produced = chunk_grads[s][c]  # W emits no cotangent
            if record is not None:
                jax.block_until_ready(produced)
                record.append((it.phase, it.tick, s, c, time.perf_counter() - t0))

        # canonical reduction — per stage, chunks in descending order (the
        # fill-drain drain order), so the accumulated floats are identical
        # no matter which schedule produced the per-chunk gradients
        grads = [jax.tree_util.tree_map(jnp.zeros_like, p) for p in params]
        total_loss = jnp.zeros((), jnp.float32)
        total_count = jnp.zeros((), jnp.float32)
        for s in range(S):
            lo, _ = self._bounds[s]
            for c in reversed(range(C)):
                for i, g in enumerate(chunk_grads[s][c]):
                    grads[lo + i] = jax.tree_util.tree_map(jnp.add, grads[lo + i], g)
        for c in range(C):
            loss_sum, count = chunk_losses[c]
            total_loss = total_loss + loss_sum
            total_count = total_count + count

        if stats is not None:
            stats.update(self.schedule.describe(S, C))
            stats["measured_peak_live_activations"] = peak_live
            stats["measured_peak_w_residuals"] = peak_residuals

        scale = 1.0 / jnp.maximum(total_count, 1.0)
        # scale is committed to the LAST stage's device (it came from the
        # loss); each layer's gradients live on their own stage's device, so
        # ship the scalar to each stage before multiplying (no-op placement
        # when no device list is configured)
        grads = [
            jax.tree_util.tree_map(
                lambda g, sc=self._place(scale, self._stage_of_layer(i)): g * sc,
                layer_grads,
            )
            for i, layer_grads in enumerate(grads)
        ]
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        loss = total_loss / jnp.maximum(total_count, 1.0)
        return params, opt_state, loss

def _chunk_loss_sum(log_probs, labels, mask):
    """(Σ nll·mask, Σ mask) — summed form so cross-chunk accumulation equals
    the full-batch masked mean exactly."""
    nll = -jnp.take_along_axis(log_probs, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m), jnp.sum(m)


class CompiledGNNPipeline(PipelineEngine):
    """Compiled SPMD engine: the whole train step is one jitted program.

    The stacked micro-batch plan (``MicroBatchPlan.stacked()``) feeds
    ``repro.core.spmd_pipe.spmd_pipeline`` with a pytree of per-chunk leaves
    — padded subgraph + activation + chunk id — so the graph travels
    stage→stage through ``lax.ppermute`` exactly like the activations, and
    ``lax.scan`` ticks replace the host-driven queue. The loss is computed
    from the last stage's outputs (zeros elsewhere, ``reduce="none"``) and
    psum-assembled; differentiation happens *outside* the stage-axis map —
    the same structure as the transformer train step — so backward runs
    through the transposed ``ppermute``/scan and each stage's device
    contributes exactly its layers' gradients: the canonical cross-stage
    reduction. One synchronous optimizer update closes the step, fused into
    the same jitted program.

    Executor substrates (chosen at build time, same update either way):

      * ``jax.device_count() >= num_stages`` — ``shard_map`` over a
        ``("stage",)`` mesh: true SPMD, one stage per device, activations
        hopping the ring through ``ppermute``.
      * fewer devices — the chunk-sequential *specialization*: one fused
        ``lax.scan`` over chunks applying the whole layer stack. Pipelining
        only reorders execution, never the math (the engine's
        schedule-invariance), so on a single device the fastest valid order
        is no interleaving at all — emulating the ring there (e.g. via
        ``vmap(axis_name="stage")``) computes every stage's ``switch``
        branch in every lane, an S× FLOP blow-up for zero parallelism. This
        is what makes ``--engine compiled`` meaningful on a laptop: one jit
        dispatch per step instead of 2·S·C.

    The engine is schedule-aware (``config.schedule``): fill-drain routes to
    the executors above (AD through scan/ppermute, unchanged numerics);
    1F1B and interleaved-1F1B lower their ``WorkItem`` timeline to static
    per-tick index arrays (``repro.core.schedule.lower_timeline``) and run
    through ``spmd_pipeline_scheduled`` — mixed fwd/bwd ticks with explicit
    ``jax.vjp`` backward stages (no AD through the scan, so no per-tick
    residual buffers) and an activation stash sized to the schedule's live
    window (1F1B's min(S-s, C)) instead of the fill-drain S·C. Per-chunk
    gradients are reduced in the canonical descending-chunk order after the
    scan, so every schedule×engine combination stays bit-identical to the
    host fill-drain baseline. With fewer devices than the schedule's
    placement needs, the same work dispatcher runs through
    ``spmd_pipeline_scheduled_lanes`` — the ring as a lane axis inside one
    program, a static lane loop keeping every ``lax.switch`` a real
    single-branch conditional (a ``vmap(axis_name=...)`` emulation would
    batch the predicate and compute all 2S+1 branches per lane).
    """

    name = "compiled"

    def __init__(self, model: GNNModel, config: GPipeConfig):
        super().__init__(model, config)
        self._widths: list[int] | None = None
        self._steps: dict = {}
        self._evals: dict = {}  # (chunks, n_pad, max_deg) -> EvalProgram
        self._travel_cache: dict = {}
        self._lowered: dict = {}  # chunks -> LoweredTimeline (scheduled path)

    @property
    def _identity_ring(self) -> bool:
        p = self.placement
        return p is None or p.stage_to_device == tuple(range(self.config.num_stages))

    @property
    def _fill_drain(self) -> bool:
        # a rotated placement re-devices the timeline, which only the
        # scheduled executor understands — fill-drain under a non-identity
        # ring routes through it instead of the fused axis_index scan; the
        # same goes for data parallelism, whose chunk sharding and gathered
        # gradient reduction live in the scheduled executor only
        # overlap also routes through the scheduled executor: the fused scan
        # has no retimed index arrays to double-buffer against
        return (
            self.config.schedule in ("fill_drain", "gpipe")
            and self._identity_ring
            and self.config.data_parallel == 1
            and self.config.overlap == "off"
        )

    def _mesh_devices(self, num_devices: int):
        """The mesh's device array: position d of the ring is
        ``device_order[d]`` of the host's devices when the placement picks an
        order FOR THIS RING SIZE, devices 0..D-1 otherwise. The size check
        matters: the eval path rings S devices even when an interleaved
        placement trains on D < S, and applying the train ring's (shorter)
        device_order there would hand the S-hop ppermute a D-device mesh."""
        devs = jax.devices()
        p = self.placement
        if p is not None and p.device_order is not None and len(p.device_order) == num_devices:
            if max(p.device_order) >= len(devs):
                raise ValueError(
                    f"placement device_order {p.device_order} references "
                    f"device indices beyond the host's {len(devs)} devices"
                )
            return np.array([devs[i] for i in p.device_order])
        return np.array(devs[:num_devices])

    # ------------------------------------------------------------ program --

    def _make_local_loss(self, widths: list[int]):
        """Per-device masked-NLL mean over every chunk's core nodes. Runs
        inside the stage-axis map; the psum assembles the last stage's local
        sum on every device, so the scalar is replicated."""
        S = self.config.num_stages
        model, bounds, remat = self.model, self._bounds, self.config.remat

        def local_loss(params, travel, graph, labels, m, count, rng):
            # vary the params over the ring HERE: the transpose of this cast
            # is the one psum of the stage gradients, outside the scan. Left
            # implicit, it lands inside the stage switch, where only the
            # devices taking a branch would issue it
            params = match_vma(params, extra=("stage",))
            stage_fn = make_gnn_stage(
                model, params, bounds, widths, graph, rng, stage_axis="stage", train=True
            )
            out, _ = spmd_pipeline(
                stage_fn, travel, stage_axis="stage", num_stages=S,
                remat=remat, reduce="none",
            )
            logp = out["h"][..., : model.out_dim]
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            return lax.psum(jnp.sum(nll * m), "stage") / jnp.maximum(count, 1.0)

        return local_loss

    def _make_scan_loss(self):
        """Single-device specialization: one ``lax.scan`` over chunks, each
        applying the full layer stack (no activation-width padding needed —
        nothing rides a wire). Same per-(chunk, layer) rng derivation and
        same masked-NLL accumulation as the pipelined program, so the update
        matches the ring substrate (and the host engine) exactly."""
        model = self.model
        n_layers = len(model.layers)
        remat = self.config.remat

        def scan_loss(params, travel, graph, labels, m, count, rng):
            def chunk_nll(c):
                g = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False), graph
                )
                rngs = jax.random.split(jax.random.fold_in(rng, c), n_layers)
                h = g.features
                for i, layer in enumerate(model.layers):
                    h = layer.apply(params[i], g, h, rngs[i], True)
                nll = -jnp.take_along_axis(h, labels[c][:, None], axis=-1)[:, 0]
                return jnp.sum(nll * m[c])

            body = jax.checkpoint(chunk_nll) if remat else chunk_nll

            def tick(acc, c):
                return acc + body(c), None

            lsum, _ = lax.scan(tick, jnp.zeros(()), travel["chunk"])
            return lsum / jnp.maximum(count, 1.0)

        return scan_loss

    def _build_step(self, widths: list[int], optimizer: opt_lib.Optimizer):
        S = self.config.num_stages
        if jax.device_count() >= S:
            mesh = jax.sharding.Mesh(self._mesh_devices(S), ("stage",))
            loss_fn = jax.shard_map(
                self._make_local_loss(widths), mesh=mesh,
                in_specs=(P(),) * 7, out_specs=P(),
            )
        else:
            loss_fn = self._make_scan_loss()

        def step(params, opt_state, travel, graph, labels, loss_mask, rng):
            m = loss_mask.astype(jnp.float32)
            count = jnp.sum(m)
            # differentiate OUTSIDE the stage-axis map (transformer-style):
            # backward runs through the transposed ppermute/scan and each
            # device contributes exactly its stage's layer gradients
            loss, grads = jax.value_and_grad(loss_fn)(
                params, travel, graph, labels, m, count, rng
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(step)

    def _make_work_fn(
        self, widths: list[int], params, graph, labels, m, rng, *, phases,
        chunk_offset=0, vary: tuple[str, ...] = (),
    ):
        """The per-tick work dispatcher for ``spmd_pipeline_scheduled``: one
        ``lax.switch`` over 1 + 4·S branches (idle, then fwd / fused bwd /
        split B / split W per stage; phases the timeline never emits —
        ``phases`` is the set it does — compile to the trivial idle branch).
        Backward branches are explicit ``jax.vjp``s of the params-explicit
        stage slices — differentiating wrt the FULL params list yields a
        full-shaped gradient pytree with zeros outside the stage's layers,
        which is exactly what the canonical cross-stage psum reduction
        needs. The last stage derives its cotangent from the same summed
        masked-NLL the host engine differentiates (``_chunk_loss_sum``) —
        in the fused bwd branch or, under zb-h1, in the B half — so the
        loss trajectory matches chunk for chunk. Split B/W branches come
        from ``make_gnn_stage_slices_bw``: B emits the upstream cotangent
        plus the (input, cotangent) residual; W re-materializes from the
        residual and emits the deferred weight grad.

        ``vary`` names the mesh axes of the enclosing ``shard_map``. The
        params are cast to vary over them before any branch closes over
        them, so each ``jax.vjp`` yields this device's own gradients (no
        collective inside a branch), and every branch's outputs are cast
        to the same axes so the switch sees one output type."""
        S = self.config.num_stages
        model = self.model
        params = match_vma(params, extra=vary)
        slices = make_gnn_stage_slices(
            model, self._bounds, widths, graph, rng, train=True,
            chunk_offset=chunk_offset,
        )
        d_travel = travel_width(self._bounds, widths)
        n_pad = graph.features.shape[1]
        zero_wire = jnp.zeros((n_pad, d_travel), graph.features.dtype)
        zero_wres = (zero_wire, zero_wire)
        zero = jnp.zeros((), jnp.float32)

        def loss_ct(y, chunk):
            logp = y[:, : model.out_dim]
            (loss_sum, count), d_logp = jax.value_and_grad(
                _chunk_loss_sum, argnums=0, has_aux=True
            )(logp, labels[chunk], m[chunk])
            ct = jnp.pad(d_logp, ((0, 0), (0, d_travel - d_logp.shape[-1])))
            return ct, loss_sum, count

        b_fns, w_fns = make_gnn_stage_slices_bw(
            model, self._bounds, widths, graph, rng, train=True, loss_ct=loss_ct,
            chunk_offset=chunk_offset,
        )

        def zeros_grads():
            return jax.tree_util.tree_map(jnp.zeros_like, params)

        def idle(operand):
            return zero_wire, zero_wire, zero_wres, zeros_grads(), zero, zero

        def fwd(s):
            def branch(operand):
                chunk, h_in, _ct, _w = operand
                y = slices[s](params, chunk, h_in)
                return y, zero_wire, zero_wres, zeros_grads(), zero, zero

            return branch

        def bwd(s):
            last = s == S - 1

            def branch(operand):
                chunk, h_in, ct, _w = operand

                def f(p, h):
                    return slices[s](p, chunk, h)

                y, vjp = jax.vjp(f, params, h_in)
                if last:
                    ct, loss_sum, count = loss_ct(y, chunk)
                else:
                    loss_sum = count = zero
                d_params, d_h = vjp(ct)
                return zero_wire, d_h, zero_wres, d_params, loss_sum, count

            return branch

        def bwd_b(s):
            def branch(operand):
                chunk, h_in, ct, _w = operand
                d_h, w_out, loss_sum, count = b_fns[s](params, chunk, h_in, ct)
                return zero_wire, d_h, w_out, zeros_grads(), loss_sum, count

            return branch

        def bwd_w(s):
            def branch(operand):
                chunk, _h, _ct, w_res = operand
                d_params = w_fns[s](params, chunk, w_res)
                return zero_wire, zero_wire, zero_wres, d_params, zero, zero

            return branch

        def used(phase, make):
            return [make(s) if phase in phases else idle for s in range(S)]

        branches = (
            [idle]
            + used(PHASE_FWD, fwd)
            + used(PHASE_BWD, bwd)
            + used(PHASE_BWD_B, bwd_b)
            + used(PHASE_BWD_W, bwd_w)
        )

        def work_fn(phase, stage, chunk, h_in, ct, w_res):
            # idle -> 0, fwd(s) -> 1 + s, bwd(s) -> 1 + S + s,
            # bwd_b(s) -> 1 + 2S + s, bwd_w(s) -> 1 + 3S + s
            index = jnp.where(phase == 0, 0, (phase - 1) * S + stage + 1)
            return lax.switch(index, _varying(branches, vary), (chunk, h_in, ct, w_res))

        return work_fn

    def _lower_for(self, chunks: int, skip_chunks: tuple = ()):
        """Lower the configured schedule's timeline for ``chunks`` chunks
        (placement re-deviced; the lowering's ring check rejects anything
        the executors could not route). Under ``config.overlap != "off"``
        the timeline is first retimed to wire latency 2 so the lowering can
        emit the double-buffered (send, compute) index arrays;
        ``skip_chunks`` drops loss-free chunks and their dead ticks."""
        S = self.config.num_stages
        timeline = self.schedule.timeline(S, chunks)  # raises on bad (S, C)
        if self.placement is not None:
            timeline = self.placement.apply(timeline)
        latency = 1 if self.config.overlap == "off" else 2
        if latency != 1:
            timeline = retime_timeline(timeline, S, chunks, wire_latency=latency)
        return lower_timeline(
            timeline, S, chunks, wire_latency=latency, skip_chunks=skip_chunks
        )

    def _build_step_scheduled(
        self, widths: list[int], chunks: int, optimizer: opt_lib.Optimizer,
        skip_chunks: tuple = (),
    ):
        """One jitted train step executing the configured 1F1B/interleaved
        timeline: shard_map over the schedule's device count when the host
        has enough devices, else the lane-stacked substrate of the same
        dataflow (``spmd_pipeline_scheduled_lanes``).

        ``config.data_parallel`` (dp) > 1 widens the mesh to 2-D ``(data,
        stage)`` — the fsdp×stage composition the transformer ``Topology``
        runs, with graph-partition shards in place of batch shards: the
        stacked plan's leading chunk axis is sharded dp ways, replica ``r``
        pipelines its contiguous local chunks ``[r·C/dp, (r+1)·C/dp)``
        through the per-replica timeline, and the executor gathers the
        per-chunk gradient slots over the data axis to reduce them in the
        canonical GLOBAL chunk order — bit-identical to one replica (each
        (layer, chunk) gradient lives on exactly one replica and stage; see
        ``spmd_pipeline_scheduled``). Dropout keys stay global through the
        ``chunk_offset`` fold in the stage slices. A host with fewer than
        dp·ring devices is an error: the mesh would not exist."""
        S = self.config.num_stages
        dp = self.config.data_parallel
        if dp > 1 and chunks % dp:
            raise ValueError(
                f"chunks {chunks} must split evenly across data_parallel={dp} "
                f"replicas"
            )
        lowered = self._lower_for(
            chunks // dp if dp > 1 else chunks,
            skip_chunks if dp == 1 else (),
        )
        D = lowered.num_devices
        dp_active = dp > 1
        if dp_active and jax.device_count() < dp * D:
            raise ValueError(
                f"data_parallel={dp} x a {D}-device ring needs {dp * D} "
                f"devices; this host has {jax.device_count()}"
            )
        self._lowered[chunks] = lowered
        self._data_parallel_active = dp_active
        d_travel = travel_width(self._bounds, widths)

        spmd = jax.device_count() >= D
        vary = ("data", "stage") if dp_active else ("stage",) if spmd else ()
        phases = set(np.unique(lowered.phase).tolist())

        def local(params, graph, labels, m, rng):
            offset = 0
            if dp_active:
                # graph/labels/m arrive as this replica's chunk shard and are
                # indexed by LOCAL chunk id; only the dropout-key fold needs
                # the global id (host-engine bitwise compatibility)
                offset = lax.axis_index("data") * (chunks // dp)
            work_fn = self._make_work_fn(
                widths, params, graph, labels, m, rng, phases=phases,
                chunk_offset=offset, vary=vary,
            )
            wire_like = jnp.zeros(
                (graph.features.shape[1], d_travel), graph.features.dtype
            )
            if spmd:
                return spmd_pipeline_scheduled(
                    work_fn, lowered, stage_axis="stage",
                    wire_like=wire_like, grads_like=params,
                    data_axis="data" if dp_active else None,
                )
            return spmd_pipeline_scheduled_lanes(
                work_fn, lowered, wire_like=wire_like, grads_like=params
            )

        if dp_active:
            grid = np.array(jax.devices()[: dp * D]).reshape(dp, D)
            p = self.placement
            if p is not None and p.device_order is not None and len(p.device_order) == D:
                # the ring's device order picks which column of each replica
                # row occupies which ring position
                grid = grid[:, list(p.device_order)]
            mesh = jax.sharding.Mesh(grid, ("data", "stage"))
            mapped = jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), P("data"), P("data"), P("data"), P()),
                out_specs=P(),
            )
        elif spmd:
            mesh = jax.sharding.Mesh(self._mesh_devices(D), ("stage",))
            mapped = jax.shard_map(
                local, mesh=mesh, in_specs=(P(),) * 5, out_specs=P()
            )
        else:
            mapped = local

        def step(params, opt_state, graph, labels, loss_mask, rng):
            m = loss_mask.astype(jnp.float32)
            grads, loss_sum, count = mapped(params, graph, labels, m, rng)
            scale = 1.0 / jnp.maximum(count, 1.0)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, loss_sum / jnp.maximum(count, 1.0)

        return jax.jit(step)

    def _build_eval_forward(self, widths: list[int], chunks: int):
        """One jitted forward-only program (no vjp, no optimizer): the
        fill-drain forward wave lowered through the same machinery as the
        train schedules (``forward_timeline`` + ``lower_timeline(...,
        forward_only=True)``) and executed by the scheduled executor's eval
        twin — the shard_map ring with enough devices, the lane-stacked
        substrate below it. Returns ``(jitted (params, graph) -> logp,
        mesh)``; the metric head lives on ``EvalProgram`` so the raw
        log-probabilities are directly servable."""
        S = self.config.num_stages
        items = forward_timeline(S, chunks)
        if self.placement is not None and self.placement.num_devices == S:
            # one-stage-per-device rings re-device the eval wave too; an
            # interleaved round-robin placement (D < S) would double-book
            # devices on the fill-drain forward wave, so eval keeps its own
            # S-device identity ring there (as it always has)
            items = self.placement.apply(items)
        lowered = lower_timeline(items, S, chunks, forward_only=True)
        D = lowered.num_devices
        d_travel = travel_width(self._bounds, widths)
        model, bounds = self.model, self._bounds
        spmd = jax.device_count() >= D
        vary = ("stage",) if spmd else ()

        def local(params, graph):
            # train=False: dropout is the identity, the rng is never consumed
            slices = make_gnn_stage_slices(
                model, bounds, widths, graph, jax.random.PRNGKey(0), train=False
            )
            zero_wire = jnp.zeros(
                (graph.features.shape[1], d_travel), graph.features.dtype
            )

            def idle(operand):
                return zero_wire

            def fwd(s):
                def branch(operand):
                    chunk, h_in = operand
                    return slices[s](params, chunk, h_in)

                return branch

            branches = [idle] + [fwd(s) for s in range(S)]

            def work_fn(phase, stage, chunk, h_in):
                index = jnp.where(phase == 0, 0, stage + 1)
                return lax.switch(index, _varying(branches, vary), (chunk, h_in))

            if spmd:
                return spmd_pipeline_scheduled_eval(
                    work_fn, lowered, stage_axis="stage", wire_like=zero_wire
                )
            return spmd_pipeline_scheduled_eval_lanes(
                work_fn, lowered, wire_like=zero_wire
            )

        mesh = None
        if spmd:
            devs = self._mesh_devices(D) if D == S else np.array(jax.devices()[:D])
            mesh = jax.sharding.Mesh(devs, ("stage",))
            mapped = jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P()), out_specs=P()
            )
        else:
            mapped = local

        def forward(params, graph):
            return mapped(params, graph)[..., : model.out_dim]

        return jax.jit(forward), mesh

    def compile_eval(self, params: list, graph) -> EvalProgram:
        """Compiled-eval handle for the shape of ``graph`` (a stacked pytree,
        leaves ``(chunks, n_pad, ...)``): one scheduled pipeline program per
        ``(chunks, n_pad, max_deg)`` bucket, cached for the engine's
        lifetime, with params bound (replicated once) on the handle."""
        if self._widths is None:
            chunk0 = jax.tree_util.tree_map(lambda a: a[0], graph)
            self._widths = activation_widths(self.model, params, chunk0)
        key = (
            graph.features.shape[0],
            graph.features.shape[1],
            graph.neighbors.shape[2],
        )
        prog = self._evals.get(key)
        if prog is None:
            fwd, mesh = self._build_eval_forward(self._widths, key[0])
            prog = EvalProgram(fwd, mesh, self.model.out_dim, key)
            self._evals[key] = prog
        return prog.bind(params)

    def _travel_inputs(self, stacked):
        """(travel pytree, loss_mask) for one stacked plan, cached. Only the
        activation buffer and the chunk id travel the wire; the stacked
        subgraphs enter the program as a replicated constant that branches
        dynamic-slice by chunk id (see ``make_gnn_stage``). The cache entry
        retains the StackedPlan itself — an id() key alone could be reused
        by a new same-shape plan after the old one is garbage-collected and
        silently serve the stale loss mask."""
        cached = self._travel_cache.get(id(stacked))
        if cached is not None and cached[0] is stacked:
            return cached[1], cached[2]
        C, n_pad = stacked.chunks, stacked.n_pad
        travel = {
            "h": jnp.zeros(
                (C, n_pad, travel_width(self._bounds, self._widths)),
                stacked.graph.features.dtype,
            ),
            "chunk": jnp.arange(C, dtype=jnp.int32),
        }
        loss_mask = stacked.graph.train_mask & stacked.core_mask
        self._travel_cache[id(stacked)] = (stacked, travel, loss_mask)
        return travel, loss_mask

    # -------------------------------------------------------------- step --

    def train_step(
        self,
        params: list,
        opt_state,
        plan: MicroBatchPlan,
        rng: jax.Array,
        optimizer: opt_lib.Optimizer,
        *,
        record: list | None = None,  # per-item timings don't exist in a fused program
        stats: dict | None = None,
    ):
        """One fused SPMD step over the stacked plan (compiled per
        ``(chunks, n_pad, max_deg, optimizer)`` shape key and cached)."""
        step, args = self.step_program(
            params, opt_state, plan, rng, optimizer, stats=stats
        )
        return step(*args)

    def step_program(
        self,
        params: list,
        opt_state,
        plan: MicroBatchPlan,
        rng: jax.Array,
        optimizer: opt_lib.Optimizer,
        *,
        stats: dict | None = None,
    ):
        """``(jitted step, its arguments)`` for one ``train_step``: call the
        one on the other to step, or ``.lower(*args)`` it to read the
        compiled program."""
        stacked = plan.stacked()
        graph = self.layout(stacked.graph)
        if self._widths is None:
            chunk0 = jax.tree_util.tree_map(lambda a: a[0], stacked.graph)
            self._widths = activation_widths(self.model, params, chunk0)
        skip: tuple = ()
        if not self._fill_drain:
            loss_mask = stacked.graph.train_mask & stacked.core_mask
            if self.config.data_parallel == 1:
                # chunks with no loss rows (ragged plans pad with empty
                # microbatches) contribute exactly-zero gradients and loss —
                # drop them so the lowering can eliminate their dead ticks.
                # dp > 1 keeps the full grid: one SPMD program cannot carry
                # per-replica tick counts.
                live = np.asarray(loss_mask.any(axis=tuple(range(1, loss_mask.ndim))))
                skip = tuple(int(c) for c in np.flatnonzero(~live))
        # the cache entry retains the optimizer: an id() key alone could be
        # reused by a new optimizer after the old one is garbage-collected,
        # silently serving a step jitted around stale hyperparameters
        key = (stacked.chunks, stacked.n_pad, stacked.max_deg, id(optimizer), skip)
        entry = self._steps.get(key)
        if entry is not None and entry[0] is optimizer:
            step = entry[1]
        elif self._fill_drain:
            step = self._build_step(self._widths, optimizer)
            self._steps[key] = (optimizer, step)
        else:
            step = self._build_step_scheduled(
                self._widths, stacked.chunks, optimizer, skip
            )
            self._steps[key] = (optimizer, step)
        if self._fill_drain:
            travel, loss_mask = self._travel_inputs(stacked)
        if stats is not None:
            stats.update(self.describe())
            if self._fill_drain:
                # fused fill-drain scan: every stage banks all C outputs
                stats["measured_peak_live_activations"] = None  # not observable
            else:
                lowered = self._lowered[stacked.chunks]
                # static accounting of the scheduled executor's stash: max
                # simultaneously banked stage inputs (stage-0 inputs are read
                # from the replicated feature table, never stashed)
                stats["measured_peak_live_activations"] = lowered.peak_live_stash
                stats["stash_slots_per_device"] = lowered.n_fslots
                stats["w_slots_per_device"] = lowered.n_wslots
                stats["num_ticks"] = lowered.num_ticks
                stats["wire_latency"] = lowered.wire_latency
        if self._fill_drain:
            return step, (
                params, opt_state, travel, graph, stacked.graph.labels,
                loss_mask, rng,
            )
        return step, (
            params, opt_state, graph, stacked.graph.labels, loss_mask, rng
        )


ENGINES = {"host": GPipe, "compiled": CompiledGNNPipeline}


def make_engine(model, config) -> PipelineEngine:
    """Engine factory: ``host`` (paper-faithful GPipe queue loop) or
    ``compiled`` (one jitted SPMD program), selected by ``config.engine``:

        make_engine(model, GPipeConfig(engine="compiled", balance=..., ...))

    ``config`` is either an assembled ``GPipeConfig`` or a planner
    ``PipelinePlan`` (``repro.core.autotune``) — a plan converts through its
    own ``to_config()``, so an ``--auto`` pick is directly replayable on
    either engine. Anything else is a ``TypeError``. (The pre-PR-6
    name-first ``make_engine(name, model, config)`` shim is gone; spell the
    engine via ``config.engine``.)"""
    from repro.core.autotune import PipelinePlan  # local: autotune imports us

    if isinstance(config, PipelinePlan):
        config = config.to_config()
    if not isinstance(config, GPipeConfig):
        raise TypeError(
            f"make_engine(model, config) expects a GPipeConfig or a "
            f"PipelinePlan, got {type(config).__name__}"
        )
    try:
        cls = ENGINES[config.engine]
    except KeyError:
        raise KeyError(
            f"unknown engine {config.engine!r}; have {tuple(ENGINES)}"
        ) from None
    return cls(model, config)
