"""Per-stage roofline accounting for the GNN pipeline's aggregation layouts.

For each pipeline stage this lowers the REAL stage-slice program
(``make_gnn_stage_slices`` — the exact function the scheduled executor
dispatches per tick) at the stacked plan's shape, walks the optimized HLO
(``roofline.hlo_walk.analyze_hlo``), and sets the measured FLOPs/bytes next
to an analytic *roof*: the floor cost of the stage's layers if aggregation
touched only the graph's LIVE edge slots. The padded layout's distance to
that roof is pure padding traffic — ``n_pad · max_deg`` slots for a
power-law degree distribution whose live count is a fraction of that — and
the degree-bucketed layout's distance shows how much of it bucketing wins
back (its slot count is ``Σ rows_b · width_b``).

Everything is per (stage, chunk): the stage program processes one chunk per
dispatch, so live-slot counts are averaged over chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.partition import layout_slots
from repro.models.gnn.net import (
    GNNModel,
    activation_widths,
    make_gnn_stage_slices,
    travel_width,
)
from repro.roofline.hlo_walk import analyze_hlo

_F32 = 4  # bytes; the framework's layers run f32


def live_slots(graph) -> float:
    """Mean live (mask-True) neighbor slots per chunk — the roof's edge
    count: no layout can aggregate fewer slots and stay exact."""
    msk = np.asarray(graph.mask)
    chunks = msk.shape[0] if msk.ndim == 3 else 1
    return float(msk.sum()) / chunks


def _layer_roof(params: dict, n: int, live: float) -> tuple[float, float]:
    """(flops, bytes) floor for one layer at ``live`` aggregated slots.

    Recognizes the framework's layer param shapes: a 2-D ``w`` is a
    GCN/GraphConv-style transform + weighted-sum aggregate; a 3-D ``w`` is
    the multi-head GAT (transform, per-edge score, masked softmax,
    aggregate). Param-less layers (dropout/elu/log_softmax) are elementwise
    and contribute no flops floor.
    """
    w = params.get("w") if isinstance(params, dict) else None
    if w is None:
        return 0.0, 0.0
    if w.ndim == 2:
        d_in, d_out = w.shape
        flops = 2.0 * n * d_in * d_out + 2.0 * live * d_out
        byts = _F32 * (n * d_in + d_in * d_out + live * d_out + n * d_out)
        return flops, byts
    heads, d_in, d_out = w.shape
    flops = (
        2.0 * n * d_in * heads * d_out  # feature transform
        + 4.0 * n * heads * d_out  # a_src/a_dst score projections
        + 6.0 * live * heads  # leaky-relu + masked softmax per edge
        + 2.0 * live * heads * d_out  # attention-weighted aggregate
    )
    byts = _F32 * (
        n * d_in + heads * d_in * d_out + live * heads * (d_out + 1) + n * heads * d_out
    )
    return flops, byts


def stage_report(
    model: GNNModel,
    params: list,
    graph,
    balance: tuple[int, ...],
    *,
    train: bool = False,
) -> list[dict]:
    """Measured-vs-roof rows, one per pipeline stage.

    ``graph`` is a chunk-stacked batch (padded ``GraphBatch`` or its
    ``BucketedGraphBatch`` wrapper, leaves ``(chunks, n_pad, ...)``). Each
    stage's slice program is jit-lowered at that shape and its optimized
    HLO walked for per-dispatch FLOPs/bytes; the roof comes from
    ``_layer_roof`` at the graph's live slot count.
    """
    bounds = []
    lo = 0
    for b in balance:
        bounds.append((lo, lo + b))
        lo += b
    chunk0 = jax.tree_util.tree_map(lambda a: a[0], graph)
    widths = activation_widths(model, params, chunk0)
    slices = make_gnn_stage_slices(
        model, bounds, widths, graph, jax.random.PRNGKey(0), train=train
    )
    n_pad = graph.features.shape[1]
    d_travel = travel_width(bounds, widths)
    h_like = jax.ShapeDtypeStruct((n_pad, d_travel), jnp.float32)
    chunk_like = jax.ShapeDtypeStruct((), jnp.int32)
    live = live_slots(graph)

    rows = []
    for s, fn in enumerate(slices):
        text = jax.jit(fn).lower(params, chunk_like, h_like).compile().as_text()
        measured = analyze_hlo(text)
        roof_flops = roof_bytes = 0.0
        for i in range(*bounds[s]):
            f, b = _layer_roof(params[i], n_pad, live)
            roof_flops += f
            roof_bytes += b
        rows.append(
            {
                "stage": s,
                "layers": [model.layers[i].name for i in range(*bounds[s])],
                "measured_flops": float(measured["flops"]),
                "measured_bytes": float(measured["bytes"]),
                "roof_flops": roof_flops,
                "roof_bytes": roof_bytes,
            }
        )
    return rows


def sparse_stage_report(
    model: GNNModel,
    params: list,
    padded_graph,
    bucketed_graph,
    balance: tuple[int, ...],
) -> dict:
    """The fig-row payload: per-stage measured-vs-roof for the padded layout
    next to the degree-bucketed one, plus the slot accounting that explains
    the gap (live edge slots vs each layout's materialized slots)."""
    padded = stage_report(model, params, padded_graph, balance)
    bucketed = stage_report(model, params, bucketed_graph, balance)
    slots = {
        "live": live_slots(padded_graph),
        "padded": layout_slots(padded_graph),
        "bucketed": layout_slots(bucketed_graph),
    }
    stages = [
        {
            "stage": p["stage"],
            "layers": p["layers"],
            "roof_flops": p["roof_flops"],
            "roof_bytes": p["roof_bytes"],
            "padded": {k: p[k] for k in ("measured_flops", "measured_bytes")},
            "bucketed": {k: b[k] for k in ("measured_flops", "measured_bytes")},
        }
        for p, b in zip(padded, bucketed)
    ]
    return {"slots": slots, "stages": stages}
