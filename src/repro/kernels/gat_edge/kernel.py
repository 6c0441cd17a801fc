"""Fused GAT neighbor attention — Pallas TPU kernel.

One VMEM-resident fusion of score → masked softmax → weighted aggregate
(DESIGN.md §3): the per-edge attention tensor is never materialized in HBM
(the paper's DGL/PyG backends materialize it and make two extra passes).

``bucket_gat_kernel`` runs over one degree bucket's rectangular tile
(``graphs.partition.degree_bucketed_layout``); the padded layout is the
square bucket with one row per node, so ``ops.gat_aggregate`` calls the same
kernel. The feature gather happens INSIDE the kernel: each tile's neighbor
indices ride into SMEM and drive dynamic row loads out of the current
head's VMEM-resident (N, F) feature block, so the gathered ``(R, W, H, F)``
tensor never exists in HBM. Scores are gathered in XLA (no F factor —
(H, R, W) is small). Grid (H, R/T), one launch per bucket; the weighted
sum is the slot-major gather-accumulate shared with the SpMM kernel
(``spmm.kernel.aggregate_tile``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime_interpret
from repro.kernels.spmm.kernel import (
    aggregate_tile,
    check_resident_vmem,
    compiler_params,
    tile_rows,
)

_NEG = -1e9


def _bucket_kernel(
    nbr_ref, s_self_ref, s_nbr_ref, mask_ref, hw_ref, out_ref, gather_ref,
    *, negative_slope,
):
    # blocks: nbr (T, W) in SMEM; s_self (T, 1); s_nbr (T, W); mask (T, W)
    # as 0/1 floats; hw (N, F) — the current head's resident feature matrix
    s = s_self_ref[...] + s_nbr_ref[...]
    s = jnp.where(s >= 0, s, negative_slope * s).astype(jnp.float32)
    mask = mask_ref[...]
    s = jnp.where(mask > 0, s, _NEG)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m) * mask
    l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
    alpha = p / l  # (T, W) f32
    out_ref[...] = aggregate_tile(nbr_ref, hw_ref, gather_ref, alpha).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("negative_slope", "block_r", "interpret"))
def _bucket_gat_call(hw_heads, neighbors, s_self, s_nbr, mask, *, negative_slope, block_r, interpret):
    h, n, f = hw_heads.shape
    r, w = neighbors.shape
    check_resident_vmem(n, f, hw_heads.dtype, what="GAT kernel")
    block_r = tile_rows(block_r, w, what="GAT kernel")
    pad = (-r) % block_r
    neighbors = jnp.pad(neighbors, ((0, pad), (0, 0)))
    s_self = jnp.pad(s_self, ((0, 0), (0, pad)))[..., None]  # (H, R_pad, 1)
    s_nbr = jnp.pad(s_nbr, ((0, 0), (0, pad), (0, 0)))
    mask = jnp.pad(mask, ((0, pad), (0, 0))).astype(jnp.float32)
    r_pad = r + pad

    out = pl.pallas_call(
        functools.partial(_bucket_kernel, negative_slope=negative_slope),
        grid=(h, r_pad // block_r),
        in_specs=[
            pl.BlockSpec((block_r, w), lambda hh, i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((None, block_r, 1), lambda hh, i: (hh, i, 0)),
            pl.BlockSpec((None, block_r, w), lambda hh, i: (hh, i, 0)),
            pl.BlockSpec((block_r, w), lambda hh, i: (i, 0)),
            pl.BlockSpec((None, n, f), lambda hh, i: (hh, 0, 0)),  # head hh's (N, F)
        ],
        out_specs=pl.BlockSpec((None, block_r, f), lambda hh, i: (hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, r_pad, f), hw_heads.dtype),
        scratch_shapes=[pltpu.VMEM((block_r, f), hw_heads.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="gat_aggregate",
    )(neighbors, s_self, s_nbr, mask, hw_heads)
    return out[:, :r]


def bucket_gat_kernel(
    hw_heads: jax.Array,  # (H, N, F) — full feature matrix, original numbering
    neighbors: jax.Array,  # (R, W) int32 — one degree bucket's rows
    s_self: jax.Array,  # (H, R)
    s_nbr: jax.Array,  # (H, R, W)
    mask: jax.Array,  # (R, W) bool
    *,
    negative_slope: float = 0.2,
    block_r: int = 128,
    interpret: bool | None = None,
) -> jax.Array:  # (H, R, F)
    if interpret is None:
        interpret = runtime_interpret()
    return _bucket_gat_call(
        hw_heads, neighbors, s_self, s_nbr, mask,
        negative_slope=negative_slope, block_r=block_r, interpret=interpret,
    )
