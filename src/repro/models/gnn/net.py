"""The paper's sequential GAT network (§6), as a stage-able layer sequence.

The model is expressed as an explicit ``list[SeqLayer]`` — the same shape as
the paper's ``nn.Sequential`` — so the GPipe engine in ``repro.core`` can
partition it with a ``balance`` array exactly like torchgpipe does.

Forward structure (paper §6, fixed across all experiments):

    dropout(0.6) -> GAT(8 heads, concat, attn-dropout 0.6) -> ELU
    -> dropout(0.6) -> GAT(8 heads, average, attn-dropout 0.6) -> log_softmax
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.graphs.data import GraphBatch
from repro.models.gnn import layers as L


@dataclasses.dataclass(frozen=True)
class SeqLayer:
    """One element of a sequential model: init + pure apply.

    ``apply(params, graph, h, rng, train) -> h`` — the graph rides along the
    carry, mirroring the paper's (node-indices, features) tuple workaround,
    minus the workaround: pytrees make it first-class.
    """

    name: str
    init: Callable[[jax.Array], Any]
    apply: Callable[[Any, GraphBatch, jax.Array, jax.Array | None, bool], jax.Array]


def _dropout_layer(rate: float, name: str) -> SeqLayer:
    return SeqLayer(
        name=name,
        init=lambda key: {},
        apply=lambda p, g, h, rng, train: L.dropout(h, rate, rng, train),
    )


def _elu_layer() -> SeqLayer:
    return SeqLayer("elu", lambda key: {}, lambda p, g, h, rng, train: jax.nn.elu(h))


def _log_softmax_layer() -> SeqLayer:
    return SeqLayer(
        "log_softmax", lambda key: {}, lambda p, g, h, rng, train: jax.nn.log_softmax(h, axis=-1)
    )


def _gat_seq_layer(
    name: str,
    in_dim: int,
    out_dim: int,
    *,
    heads: int,
    concat: bool,
    attn_dropout: float,
    backend: str,
) -> SeqLayer:
    def apply(p, g, h, rng, train):
        # attn_dropout passes through unchanged: the pallas backend validates
        # up-front in gat_layer and raises a clear error instead of this
        # wrapper silently zeroing the rate (eval / rate-0 paths are fine).
        return L.gat_layer(
            p,
            g,
            h,
            concat=concat,
            attn_dropout=attn_dropout,
            rng=rng,
            train=train,
            backend=backend,
        )

    return SeqLayer(name, lambda key: L.init_gat(key, in_dim, out_dim, heads=heads), apply)


def _gcn_seq_layer(name: str, in_dim: int, out_dim: int, *, backend: str) -> SeqLayer:
    return SeqLayer(
        name,
        lambda key: L.init_gcn(key, in_dim, out_dim),
        lambda p, g, h, rng, train: L.gcn_layer(p, g, h, backend=backend),
    )


@dataclasses.dataclass(frozen=True)
class GNNModel:
    layers: tuple[SeqLayer, ...]
    in_dim: int
    out_dim: int

    def init_params(self, key: jax.Array) -> list:
        keys = jax.random.split(key, len(self.layers))
        return [layer.init(k) for layer, k in zip(self.layers, keys)]

    def apply(
        self,
        params: list,
        g: GraphBatch,
        h: jax.Array | None = None,
        *,
        rng: jax.Array | None = None,
        train: bool = False,
    ) -> jax.Array:
        h = g.features if h is None else h
        rngs = (
            jax.random.split(rng, len(self.layers))
            if rng is not None
            else [None] * len(self.layers)
        )
        for layer, p, r in zip(self.layers, params, rngs):
            h = layer.apply(p, g, h, r, train)
        return h

    def num_params(self, params: list) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(params))


def activation_widths(model: GNNModel, params: list, graph: GraphBatch) -> list[int]:
    """Feature width at every layer boundary: ``widths[i]`` is the input dim
    of layer ``i``, ``widths[len(layers)]`` the model output dim. Computed by
    shape-tracing each layer (no FLOPs), so it works for any SeqLayer mix."""
    g_struct = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), graph
    )
    n = graph.num_nodes
    h = jax.ShapeDtypeStruct((n, model.in_dim), jnp.float32)
    widths = [model.in_dim]
    for layer, p in zip(model.layers, params):
        h = jax.eval_shape(lambda p_, g_, h_, L=layer: L.apply(p_, g_, h_, None, False), p, g_struct, h)
        widths.append(h.shape[-1])
    return widths


def travel_width(bounds: list[tuple[int, int]], widths: list[int]) -> int:
    """Wire width of the traveling activation: the widest *stage-boundary*
    dim (every stage's output width). The model input width is excluded —
    stage 0 reads features by chunk id, they never ride the wire."""
    return max(widths[hi] for _, hi in bounds)


def make_gnn_stage(
    model: GNNModel,
    params: list,
    bounds: list[tuple[int, int]],
    widths: list[int],
    graph: GraphBatch,
    rng: jax.Array,
    *,
    stage_axis: str,
    train: bool = True,
):
    """Adapter from a sequential GNN to an SPMD pipeline stage for
    ``repro.core.spmd_pipe.spmd_pipeline``.

    The device's stage index (``lax.axis_index``) selects — via ``lax.switch``
    — the branch that closes a contiguous ``SeqLayer`` slice ``[lo, hi)`` over
    its stage params. Because inter-stage activation widths differ (features →
    hidden → classes), the traveling activation is padded to the widest stage
    boundary (``travel_width``); each branch slices its true input width and
    re-pads its output, so every branch has the uniform shape ``ppermute``
    requires.

    The travel pytree is ``{"h", "chunk"}`` — deliberately minimal. The
    stacked per-chunk subgraphs (``graph``, leaves (chunks, n_pad, ...)) are
    closed over as a replicated constant and every branch dynamic-slices its
    chunk's subgraph by the *traveling chunk id*: the graph rides the
    pipeline keyed by an int32 scalar instead of re-``ppermute``-ing the
    neighbor/mask/norm arrays (and the feature matrix) every tick. Stage 0
    reads its input activation from the sliced chunk's features the same way.

    Per-(chunk, layer) dropout keys are derived from the traveling chunk id
    exactly as the host engine derives them
    (``split(fold_in(rng, chunk), n_layers)``), keeping the two engines'
    stochastic training bitwise-comparable. The key derivation is hoisted
    out of the ``switch`` into the stage body: branches that consume
    fold_in/split asymmetrically break ``cond``'s partial-eval when the
    pipeline is linearized (jax <= 0.4.x), whereas key *use* inside a
    branch is fine.
    """
    n_layers = len(model.layers)
    d_travel = travel_width(bounds, widths)

    def branch(s: int):
        lo, hi = bounds[s]

        @jax.named_scope(f"pipe.fwd.s{s}")
        def apply_slice(operand):
            travel, rngs = operand
            c = travel["chunk"]
            g = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False), graph
            )
            h = g.features if lo == 0 else travel["h"][:, : widths[lo]]
            for i in range(lo, hi):
                h = model.layers[i].apply(params[i], g, h, rngs[i], train)
            return jnp.pad(h, ((0, 0), (0, d_travel - h.shape[-1])))

        return apply_slice

    branches = [branch(s) for s in range(len(bounds))]

    def stage_fn(travel, state_mb):
        s = jax.lax.axis_index(stage_axis)
        rngs = jax.random.split(jax.random.fold_in(rng, travel["chunk"]), n_layers)
        h_out = jax.lax.switch(s, branches, (travel, rngs))
        return dict(travel, h=h_out), state_mb

    return stage_fn


def make_gnn_stage_slices(
    model: GNNModel,
    bounds: list[tuple[int, int]],
    widths: list[int],
    graph: GraphBatch,
    rng: jax.Array,
    *,
    train: bool = True,
    chunk_offset=0,
):
    """Params-EXPLICIT per-stage slice functions for the scheduled executor
    (``spmd_pipeline_scheduled``), which differentiates stages explicitly
    via ``jax.vjp`` instead of AD-ing through the whole pipeline program.

    Returns ``slices[s](params, chunk, h_in) -> h_out``: apply the
    contiguous ``SeqLayer`` slice ``[lo, hi)`` of stage ``s`` to chunk
    ``chunk`` (a traced int32 — the stacked subgraphs are closed over and
    dynamic-sliced by it, exactly like ``make_gnn_stage``). ``params`` is
    the FULL layer-params list so ``jax.vjp(f, params, h_in)`` yields a
    full-params gradient pytree with zeros outside the stage's layers — the
    uniform structure ``lax.switch`` and the cross-stage psum reduction
    need. ``h_in``/``h_out`` are padded to the uniform wire width
    (``travel_width``); stage 0 ignores ``h_in`` and reads the chunk's
    features, so its input cotangent comes out zero automatically.

    Per-(chunk, layer) dropout keys are derived exactly as the host engine
    derives them (``split(fold_in(rng, chunk), n_layers)``), keeping every
    schedule×engine combination bitwise-comparable. Under data parallelism
    the chunk id traveling the pipeline is LOCAL to the replica while the
    host engine folds the GLOBAL chunk id; ``chunk_offset`` (a traced scalar
    — each replica passes ``axis_index("data") * chunks_per_replica``) is
    added before the fold so the keys stay bitwise identical. It offsets
    ONLY the rng derivation: graph slicing keeps the local id, because each
    replica's stacked graph shard is indexed locally.
    """
    n_layers = len(model.layers)
    d_travel = travel_width(bounds, widths)

    def make(s: int):
        lo, hi = bounds[s]

        def apply_slice(params, chunk, h_in):
            g = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, chunk, 0, keepdims=False),
                graph,
            )
            rngs = jax.random.split(jax.random.fold_in(rng, chunk + chunk_offset), n_layers)
            h = g.features if lo == 0 else h_in[:, : widths[lo]]
            for i in range(lo, hi):
                h = model.layers[i].apply(params[i], g, h, rngs[i], train)
            return jnp.pad(h, ((0, 0), (0, d_travel - h.shape[-1])))

        return apply_slice

    return [make(s) for s in range(len(bounds))]


def make_gnn_stage_slices_bw(
    model: GNNModel,
    bounds: list[tuple[int, int]],
    widths: list[int],
    graph: GraphBatch,
    rng: jax.Array,
    *,
    train: bool = True,
    loss_ct=None,
    chunk_offset=0,
):
    """Split-backward (zero-bubble) halves of ``make_gnn_stage_slices``: the
    stage backward is cut along the vjp's two cotangent outputs so the
    scheduled executor can run them in separate ticks.

    Returns ``(b_fns, w_fns)``:

      * ``b_fns[s](params, chunk, h_in, ct) -> (d_h, residual, loss_sum,
        count)`` — the **B** (input-grad) half: differentiate the stage wrt
        its *input only* (``jax.vjp`` of ``h -> slice(params, chunk, h)``,
        so XLA dead-code-eliminates the weight-grad work) and return the
        upstream cotangent immediately — the only product on the pipeline's
        critical path — plus the residual the deferred W half needs: the
        ``(h_in, ct_applied)`` pair, two uniform wire-shaped buffers (kept
        as a tuple, not stacked — the executor stashes the halves
        separately so no concat/slice materializes per tick).
        At the LAST stage ``loss_ct(y, chunk) -> (ct, loss_sum, count)``
        derives the applied cotangent from the stage's own output (the
        pipeline's loss head); other stages consume the wire ``ct`` and
        report zeros.
      * ``w_fns[s](params, chunk, residual) -> d_params`` — the **W**
        (weight-grad) half: re-materialize the stage forward from the
        residual's banked input (GPipe's recompute discipline) and
        differentiate wrt the FULL params list, yielding the same
        zero-outside-the-stage gradient pytree the fused backward produces
        — float-identical, since both halves replay the identical primal
        and cotangent chains.

    Stage 0 ignores ``h_in`` (features are read by chunk id), so its B half
    is almost entirely dead code — mirroring zb-h1's accounting, where the
    first stage's critical-path backward is free.
    """
    slices = make_gnn_stage_slices(
        model, bounds, widths, graph, rng, train=train, chunk_offset=chunk_offset
    )
    zero = jnp.zeros((), jnp.float32)

    def make(s: int):
        fwd = slices[s]
        last = s == len(bounds) - 1 and loss_ct is not None

        def b_fn(params, chunk, h_in, ct):
            y, vjp = jax.vjp(lambda h: fwd(params, chunk, h), h_in)
            if last:
                ct, loss_sum, count = loss_ct(y, chunk)
            else:
                loss_sum = count = zero
            (d_h,) = vjp(ct)
            return d_h, (h_in, ct), loss_sum, count

        def w_fn(params, chunk, residual):
            h_in, ct = residual
            _, vjp = jax.vjp(lambda p: fwd(p, chunk, h_in), params)
            (d_params,) = vjp(ct)
            return d_params

        return b_fn, w_fn

    pairs = [make(s) for s in range(len(bounds))]
    return [b for b, _ in pairs], [w for _, w in pairs]


def build_paper_gat(
    num_features: int,
    num_classes: int,
    *,
    hidden_per_head: int = 8,
    heads: int = 8,
    feat_dropout: float = 0.6,
    attn_dropout: float = 0.6,
    backend: str = "padded",
) -> GNNModel:
    """The exact model of paper §6 (GAT defaults of Veličković et al.)."""
    layers = (
        _dropout_layer(feat_dropout, "dropout_0"),
        _gat_seq_layer(
            "gat_0",
            num_features,
            hidden_per_head,
            heads=heads,
            concat=True,
            attn_dropout=attn_dropout,
            backend=backend,
        ),
        _elu_layer(),
        _dropout_layer(feat_dropout, "dropout_1"),
        _gat_seq_layer(
            "gat_1",
            hidden_per_head * heads,
            num_classes,
            heads=heads,
            concat=False,
            attn_dropout=attn_dropout,
            backend=backend,
        ),
        _log_softmax_layer(),
    )
    return GNNModel(layers=layers, in_dim=num_features, out_dim=num_classes)


def build_imbalanced_gcn(
    num_features: int,
    num_classes: int,
    *,
    hidden: tuple[int, ...] = (256, 256, 32, 32, 32, 32),
    backend: str = "padded",
) -> GNNModel:
    """A deliberately cost-IMBALANCED GCN stack — the partitioner's benchmark
    and test fixture. The leading layers are an order of magnitude wider than
    the tail, so a layer-count-uniform ``balance`` packs the heavy layers
    into one stage (which then sets every pipeline tick) while the profiled
    partitioner isolates them: with the default widths and 4 stages,
    ``uniform_balance`` groups the two 256-wide convs together and the
    cost-aware split pulls them apart."""
    dims = [num_features, *hidden, num_classes]
    layers = tuple(
        _gcn_seq_layer(f"gcn_{i}", dims[i], dims[i + 1], backend=backend)
        for i in range(len(dims) - 1)
    ) + (_log_softmax_layer(),)
    return GNNModel(layers=layers, in_dim=num_features, out_dim=num_classes)


def build_gnn(
    kind: str,
    num_features: int,
    num_classes: int,
    *,
    hidden: int = 64,
    depth: int = 2,
    backend: str = "padded",
) -> GNNModel:
    """Generic builders for the future-work §8 model zoo (GCN / GraphConv /
    GatedGraphConv), assembled in the same sequential form."""
    if kind == "gat":
        return build_paper_gat(num_features, num_classes, backend=backend)

    layers: list[SeqLayer] = []
    dims = [num_features] + [hidden] * (depth - 1) + [num_classes]
    for i in range(depth):
        din, dout = dims[i], dims[i + 1]
        if kind == "gcn":
            layers.append(_gcn_seq_layer(f"gcn_{i}", din, dout, backend=backend))
        elif kind == "graphconv":
            layers.append(
                SeqLayer(
                    f"graphconv_{i}",
                    (lambda din=din, dout=dout: (lambda key: L.init_graph_conv(key, din, dout)))(),
                    lambda p, g, h, rng, train: L.graph_conv_layer(p, g, h, backend=backend),
                )
            )
        elif kind == "gatedgraphconv":
            if din != dout:
                layers.append(_gcn_seq_layer(f"proj_{i}", din, dout, backend=backend))
            layers.append(
                SeqLayer(
                    f"ggc_{i}",
                    (lambda dout=dout: (lambda key: L.init_gated_graph_conv(key, dout)))(),
                    lambda p, g, h, rng, train: L.gated_graph_conv_layer(p, g, h, backend=backend),
                )
            )
        else:
            raise KeyError(f"unknown GNN kind {kind!r}")
        if i < depth - 1:
            layers.append(_elu_layer())
    layers.append(_log_softmax_layer())
    return GNNModel(layers=tuple(layers), in_dim=num_features, out_dim=num_classes)
