"""Public ops for the fused GAT attention kernels (+ custom VJP).

``gat_aggregate`` takes the UNgathered layer tensors (matching the layer
call-site in ``repro.models.gnn.layers``) and runs the fused kernel forward
over the padded layout — the square bucket, one row per node.

``bucketed_gat_aggregate`` is the degree-bucketed variant: per-bucket
rectangular tiles (see ``graphs.partition.degree_bucketed_layout``), one
kernel launch per non-empty bucket. Either way the feature gather happens
INSIDE the kernel, so the gathered ``(R, W, H, F)`` tensor is never
materialized by XLA. Score gathers (no F factor) stay in XLA.

Forward routing follows ``kernels.use_kernel_forward()`` (Pallas kernel on
TPU / forced, jnp oracle elsewhere); backward re-derives the vjp from the
oracle either way (kernel-forward / oracle-backward is the standard
recompute pairing; the two agree to float tolerance by the kernel tests).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import use_kernel_forward
from repro.kernels.gat_edge.kernel import bucket_gat_kernel
from repro.kernels.gat_edge.ref import bucket_gat_ref, gat_aggregate_ref


def _prepare(hw, s_src, s_dst, neighbors):
    # hw: (N, H, F) -> head-major gathered (H, N, D, F)
    nbr_hw = jnp.moveaxis(hw[neighbors], 2, 0)  # (H, N, D, F)
    s_self = s_src.T  # (H, N)
    s_nbr = jnp.moveaxis(s_dst[neighbors], 2, 0)  # (H, N, D)
    return nbr_hw, s_self, s_nbr


def _bucket_inputs(s_src, s_dst, nbr, row):
    # per-bucket score gathers (XLA-side: no F factor, (H, R, W) is small)
    s_self = s_src[row].T  # (H, R)
    s_nbr = jnp.moveaxis(s_dst[nbr], 2, 0)  # (H, R, W)
    return s_self, s_nbr


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def gat_aggregate(hw, s_src, s_dst, neighbors, mask, negative_slope=0.2):
    """(N, H, F) aggregated outputs over the padded layout."""
    if use_kernel_forward():
        s_self, s_nbr = _bucket_inputs(s_src, s_dst, neighbors, slice(None))
        out = bucket_gat_kernel(
            jnp.moveaxis(hw, 1, 0), neighbors, s_self, s_nbr, mask,
            negative_slope=negative_slope,
        )
        return jnp.moveaxis(out, 0, 1)  # (N, H, F)
    return _ref_call(hw, s_src, s_dst, neighbors, mask, negative_slope)


def _ref_call(hw, s_src, s_dst, neighbors, mask, negative_slope):
    nbr_hw, s_self, s_nbr = _prepare(hw, s_src, s_dst, neighbors)
    return jnp.moveaxis(
        gat_aggregate_ref(nbr_hw, s_self, s_nbr, mask, negative_slope=negative_slope),
        0,
        1,
    )


def _fwd(hw, s_src, s_dst, neighbors, mask, negative_slope):
    out = gat_aggregate(hw, s_src, s_dst, neighbors, mask, negative_slope)
    return out, (hw, s_src, s_dst, neighbors, mask)


def _bwd(negative_slope, res, ct):
    hw, s_src, s_dst, neighbors, mask = res
    _, vjp = jax.vjp(
        lambda a, b, c: _ref_call(a, b, c, neighbors, mask, negative_slope),
        hw, s_src, s_dst,
    )
    d_hw, d_src, d_dst = vjp(ct)
    return d_hw, d_src, d_dst, None, None


gat_aggregate.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def bucketed_gat_aggregate(
    hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows, negative_slope=0.2
):
    """(N, H, F) aggregated outputs over the degree-bucketed layout.

    ``neighbors``/``masks``/``row_nodes`` are equal-length tuples of one
    bucket's ``(R_b, W_b)`` tiles (+ ``(R_b,)`` original-row map);
    ``gather_rows`` maps node i into the bucket concatenation.
    """
    hw_heads = jnp.moveaxis(hw, 1, 0)  # (H, N, F)
    kernel = use_kernel_forward()
    outs = []
    for nbr, mask, row in zip(neighbors, masks, row_nodes):
        if nbr.shape[0] == 0:
            outs.append(jnp.zeros((0,) + hw.shape[1:], hw.dtype))
            continue
        s_self, s_nbr = _bucket_inputs(s_src, s_dst, nbr, row)
        fn = bucket_gat_kernel if kernel else bucket_gat_ref
        out = fn(hw_heads, nbr, s_self, s_nbr, mask, negative_slope=negative_slope)
        outs.append(jnp.moveaxis(out, 0, 1))  # (R, H, F)
    return jnp.concatenate(outs, axis=0)[gather_rows]


def _bucketed_ref_call(
    hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows, negative_slope
):
    hw_heads = jnp.moveaxis(hw, 1, 0)
    outs = []
    for nbr, mask, row in zip(neighbors, masks, row_nodes):
        s_self, s_nbr = _bucket_inputs(s_src, s_dst, nbr, row)
        out = bucket_gat_ref(
            hw_heads, nbr, s_self, s_nbr, mask, negative_slope=negative_slope
        )
        outs.append(jnp.moveaxis(out, 0, 1))
    return jnp.concatenate(outs, axis=0)[gather_rows]


def _bucketed_fwd(hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows, negative_slope):
    out = bucketed_gat_aggregate(
        hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows, negative_slope
    )
    return out, (hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows)


def _bucketed_bwd(negative_slope, res, ct):
    hw, s_src, s_dst, neighbors, masks, row_nodes, gather_rows = res
    _, vjp = jax.vjp(
        lambda a, b, c: _bucketed_ref_call(
            a, b, c, neighbors, masks, row_nodes, gather_rows, negative_slope
        ),
        hw, s_src, s_dst,
    )
    d_hw, d_src, d_dst = vjp(ct)
    none_like = tuple(None for _ in neighbors)
    return d_hw, d_src, d_dst, none_like, none_like, none_like, None


bucketed_gat_aggregate.defvjp(_bucketed_fwd, _bucketed_bwd)
