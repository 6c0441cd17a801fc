"""Padded-neighbor SpMM — Pallas TPU kernel with an in-kernel row gather.

The TPU adaptation of the paper's DGL/PyG CSR SpMM (DESIGN.md §3): the
feature matrix stays resident in VMEM, and each grid step aggregates one
tile of T rows. The tile's ``(T, W)`` neighbor indices ride into SMEM as a
blocked input, so every index is a scalar read that drives a dynamic VMEM
row load. Work runs slot-major: for neighbor slot j the tile gathers its T
rows ``hw[nbr[:, j]]`` into a ``(T, F)`` scratch and adds them in one vector
op weighted by column j of the tile's weights. Mosaic reads no scalar out of
a VMEM vector, so the column is taken by a masked lane reduction instead
(``slot_weight``).

Two entry points share the inner kernel:

* ``padded_spmm_kernel`` — square layout, one row of ``neighbors`` per row
  of ``hw`` (the original padded path).
* ``bucket_spmm_kernel`` — a degree bucket's rectangular tile: ``neighbors``
  has R rows of width W indexing into an (N, F) feature matrix with R ≠ N.
  One launch per bucket; the per-bucket width is what makes aggregation
  cost follow the degree distribution instead of the global max degree.

The resident feature block caps N·F, and SMEM caps the tile's T·W:
``check_resident_vmem`` raises past the first cap, ``tile_rows`` shrinks T
for wide buckets and raises past the second, instead of handing Mosaic
blocks it would refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime_interpret

# Scoped VMEM the kernels ask Mosaic for. v5e has 128 MiB of VMEM; the
# default scoped limit is 16 MiB.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# What the resident (N, F) feature block may take of that limit: Mosaic
# keeps one buffer of a block that spans the whole array, and the tiles,
# the gather scratch and its own internal scratch need the rest. Compiled
# for a described v5e, N = 188416 at F = 64 (f32) fits and 196608 is
# refused; this cap stops at 180224.
RESIDENT_VMEM_BYTES = VMEM_LIMIT_BYTES - 8 * 1024 * 1024
# v5e scalar memory (SMEM) is 1 MiB. The (T, W) int32 index tile is double
# buffered, and the two buffers may take half of it.
SMEM_INDEX_BYTES = 512 * 1024


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) block: sublanes pad to 8, lanes to 128."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * jnp.dtype(dtype).itemsize


def check_resident_vmem(n: int, f: int, dtype, *, what: str) -> None:
    """Raise if the resident ``(n, f)`` feature block cannot fit the
    kernels' scoped VMEM. The op then fails by name instead of compiling a
    kernel Mosaic refuses, or silently running the oracle instead."""
    need = _vmem_bytes(n, f, dtype)
    if need > RESIDENT_VMEM_BYTES:
        raise ValueError(
            f"{what}: the resident ({n}, {f}) {jnp.dtype(dtype).name} feature "
            f"block needs {need} bytes of VMEM (lanes padded to 128), over "
            f"RESIDENT_VMEM_BYTES={RESIDENT_VMEM_BYTES}; use --backend padded "
            f"for graphs this large"
        )


def tile_rows(block_r: int, width: int, *, what: str) -> int:
    """Rows per grid step: ``block_r``, cut to a multiple of 8 where a wide
    bucket's double-buffered ``(T, width)`` index tile would overflow
    ``SMEM_INDEX_BYTES``. Raises where even 8 rows do not fit."""
    fit = SMEM_INDEX_BYTES // (2 * 4 * max(width, 1)) // 8 * 8
    if fit < 8:
        raise ValueError(
            f"{what}: a bucket {width} neighbors wide needs more than "
            f"SMEM_INDEX_BYTES={SMEM_INDEX_BYTES} for an 8-row index tile"
        )
    return min(block_r, fit)


def compiler_params() -> pltpu.CompilerParams:
    """Mosaic options shared by the sparse kernels."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def slot_weight(weights: jax.Array, j) -> jax.Array:
    """Column ``j`` (a traced scalar) of a ``(T, W)`` tile as a ``(T, 1)``
    vector, by a masked lane reduction."""
    lane = lax.broadcasted_iota(jnp.int32, weights.shape, 1)
    return jnp.sum(jnp.where(lane == j, weights, 0.0), axis=1, keepdims=True)


def aggregate_tile(nbr_ref, hw_ref, gather_ref, weights: jax.Array) -> jax.Array:
    """``out[t] = Σ_j weights[t, j] · hw[nbr[t, j]]`` for one ``(T, W)`` tile,
    as a float32 ``(T, F)`` value. ``nbr_ref`` is the tile's SMEM index
    block, ``hw_ref`` the resident feature block and ``gather_ref`` a
    ``(T, F)`` VMEM scratch."""
    t_rows, width = weights.shape

    def slot(j, acc):
        def gather(t, carry):
            idx = nbr_ref[t, j]
            gather_ref[pl.ds(t, 1), :] = hw_ref[pl.ds(idx, 1), :]
            return carry

        lax.fori_loop(0, t_rows, gather, 0)
        return acc + slot_weight(weights, j) * gather_ref[...].astype(jnp.float32)

    acc0 = jnp.zeros((t_rows, hw_ref.shape[-1]), jnp.float32)
    return lax.fori_loop(0, width, slot, acc0)


def _kernel(nbr_ref, norm_ref, hw_ref, out_ref, gather_ref):
    weights = norm_ref[...].astype(jnp.float32)
    out_ref[...] = aggregate_tile(nbr_ref, hw_ref, gather_ref, weights).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _spmm_call(
    hw: jax.Array,  # (N, F)
    neighbors: jax.Array,  # (R, W) int32, rows indexing into hw
    norm: jax.Array,  # (R, W)
    *,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    n, f = hw.shape
    r, w = neighbors.shape
    check_resident_vmem(n, f, hw.dtype, what="spmm kernel")
    block_n = tile_rows(block_n, w, what="spmm kernel")
    pad = (-r) % block_n
    nbr_p = jnp.pad(neighbors, ((0, pad), (0, 0)))
    norm_p = jnp.pad(norm, ((0, pad), (0, 0)))
    r_pad = r + pad

    out = pl.pallas_call(
        _kernel,
        grid=(r_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((n, f), lambda i: (0, 0)),  # resident
        ],
        out_specs=pl.BlockSpec((block_n, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, f), hw.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, f), hw.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="spmm",
    )(nbr_p, norm_p, hw)
    return out[:r]


def padded_spmm_kernel(
    hw: jax.Array,  # (N, F)
    neighbors: jax.Array,  # (N, D) int32
    norm: jax.Array,  # (N, D)
    *,
    block_n: int = 256,
    interpret: bool | None = None,  # None -> kernels.runtime_interpret()
) -> jax.Array:
    if interpret is None:
        interpret = runtime_interpret()
    return _spmm_call(hw, neighbors, norm, block_n=block_n, interpret=interpret)


def bucket_spmm_kernel(
    hw: jax.Array,  # (N, F) — full feature matrix, original node numbering
    neighbors: jax.Array,  # (R, W) int32 — one degree bucket's rows
    norm: jax.Array,  # (R, W)
    *,
    block_r: int = 128,
    interpret: bool | None = None,
) -> jax.Array:  # (R, F)
    if interpret is None:
        interpret = runtime_interpret()
    return _spmm_call(hw, neighbors, norm, block_n=block_r, interpret=interpret)
