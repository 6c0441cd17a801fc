"""GNN layers in JAX over the padded-neighbor layout.

Every layer comes as an ``init_*`` (params pytree) plus a pure ``*_layer``
apply function. Three aggregation backends exist:

  * ``padded`` — gather neighbors along the (n, max_deg) layout; the
    TPU-native default. GCN handed a ``BucketedGraphBatch`` gathers over
    its degree-bucketed tiles instead, in the same plain XLA ops.
  * ``dense``  — materialize a masked (n, n) adjacency and matmul; only for
    small graphs, used by benchmarks as the "second framework" analogue of
    the paper's DGL-vs-PyG comparison.
  * ``pallas`` — the fused Pallas kernels in repro.kernels (GAT + GCN).

The GAT layer follows the paper §2.1 / Veličković et al. exactly:
``alpha_ij ∝ exp(LeakyReLU(a^T [Wh_i || Wh_j]))`` with multi-head concat or
average, attention dropout, masked softmax over the neighborhood.

Every layer puts its feature matmuls under ``jax.named_scope("gnn.transform")``
and its neighbourhood work (gather, scores, softmax, weighted sum or kernel
call, bias) under ``"gnn.agg"``. The names reach the compiled program's op
metadata, so a device profile tells aggregation from transform; backward ops
carry ``transpose(jvp(...))`` around the same names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graphs.data import BucketedGraphBatch, GraphBatch
from repro.kernels.spmm.ref import bucketed_spmm_ref

_NEG_INF = -1e9


def _bucket_fields(g: BucketedGraphBatch):
    return (
        tuple(b.neighbors for b in g.buckets),
        tuple(b.norm for b in g.buckets),
        tuple(b.mask for b in g.buckets),
        tuple(b.row_node for b in g.buckets),
    )


def glorot(key: jax.Array, shape: tuple[int, ...], dtype=jnp.float32) -> jax.Array:
    fan_in, fan_out = shape[-2], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def dropout(x: jax.Array, rate: float, rng: jax.Array | None, train: bool) -> jax.Array:
    if not train or rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _dense_adj(g: GraphBatch) -> jax.Array:
    """Masked (n, n) adjacency (with self-loops) from the padded layout."""
    n = g.num_nodes
    adj = jnp.zeros((n, n), dtype=bool)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], g.neighbors.shape)
    return adj.at[rows, g.neighbors].max(g.mask)


def _dense_norm(g: GraphBatch) -> jax.Array:
    n = g.num_nodes
    out = jnp.zeros((n, n), dtype=g.norm.dtype)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], g.neighbors.shape)
    return out.at[rows, g.neighbors].max(g.norm)


# ---------------------------------------------------------------- GCN ----


def init_gcn(key: jax.Array, in_dim: int, out_dim: int) -> dict:
    return {"w": glorot(key, (in_dim, out_dim)), "b": jnp.zeros((out_dim,))}


def gcn_layer(params: dict, g: GraphBatch, h: jax.Array, *, backend: str = "padded") -> jax.Array:
    """H' = Â H W + b with symmetric normalization (Kipf & Welling)."""
    with jax.named_scope("gnn.transform"):
        hw = h @ params["w"]
    with jax.named_scope("gnn.agg"):
        if backend == "dense":
            agg = _dense_norm(g) @ hw
        elif backend == "pallas":
            if isinstance(g, BucketedGraphBatch):
                from repro.kernels.spmm.ops import bucketed_spmm

                nbrs, nrms, _, _ = _bucket_fields(g)
                agg = bucketed_spmm(hw, nbrs, nrms, g.gather_rows)
            else:
                from repro.kernels.spmm.ops import padded_spmm

                agg = padded_spmm(hw, g.neighbors, g.norm)
        elif isinstance(g, BucketedGraphBatch):
            # the same weighted gather over the degree buckets, which leave
            # most padding slots (exact zeros) out of the gather and out of
            # its transposed scatter-add
            nbrs, nrms, _, _ = _bucket_fields(g)
            agg = bucketed_spmm_ref(hw, nbrs, nrms, g.gather_rows)
        else:
            gathered = hw[g.neighbors]  # (n, max_deg, out)
            agg = jnp.einsum("nd,ndo->no", g.norm, gathered)
        return agg + params["b"]


# ---------------------------------------------------------------- GAT ----


def init_gat(key: jax.Array, in_dim: int, out_dim: int, *, heads: int = 8) -> dict:
    kw, ks, kd = jax.random.split(key, 3)
    return {
        "w": glorot(kw, (heads, in_dim, out_dim)),
        "a_src": glorot(ks, (heads, out_dim, 1))[..., 0],
        "a_dst": glorot(kd, (heads, out_dim, 1))[..., 0],
        "b": jnp.zeros((heads, out_dim)),
    }


def gat_layer(
    params: dict,
    g: GraphBatch,
    h: jax.Array,
    *,
    concat: bool = True,
    attn_dropout: float = 0.0,
    negative_slope: float = 0.2,
    rng: jax.Array | None = None,
    train: bool = False,
    backend: str = "padded",
) -> jax.Array:
    """Multi-head GAT layer (paper eq. 3–4). Returns (n, heads*out) if concat
    else (n, out) (head average, the paper's prediction layer)."""
    if backend == "pallas" and attn_dropout > 0.0 and train and rng is not None:
        # validated up-front, BEFORE any kernel work: the fused
        # softmax-aggregate kernel cannot apply per-edge dropout inside the
        # softmax. Eval (train=False) and rate-0 paths are unaffected.
        raise ValueError(
            "pallas GAT backend is deterministic and cannot apply attention "
            f"dropout (attn_dropout={attn_dropout}) during training; set "
            "attn_dropout=0.0 or use the 'padded'/'dense' backend"
        )
    heads, _, out_dim = params["w"].shape
    with jax.named_scope("gnn.transform"):
        hw = jnp.einsum("nf,hfo->nho", h, params["w"])  # (n, H, F')
        s_src = jnp.einsum("nho,ho->nh", hw, params["a_src"])  # importance of i as dst
        s_dst = jnp.einsum("nho,ho->nh", hw, params["a_dst"])  # importance of j as src
    with jax.named_scope("gnn.agg"):
        if backend == "pallas":
            if isinstance(g, BucketedGraphBatch):
                from repro.kernels.gat_edge.ops import bucketed_gat_aggregate

                nbrs, _, msks, rows = _bucket_fields(g)
                out = bucketed_gat_aggregate(
                    hw, s_src, s_dst, nbrs, msks, rows, g.gather_rows,
                    negative_slope,
                )
            else:
                from repro.kernels.gat_edge.ops import gat_aggregate

                out = gat_aggregate(
                    hw, s_src, s_dst, g.neighbors, g.mask, negative_slope=negative_slope
                )
        elif backend == "dense":
            adj = _dense_adj(g)  # (n, n)
            scores = s_src[:, None, :] + s_dst[None, :, :]  # (n, n, H)
            scores = jax.nn.leaky_relu(scores, negative_slope)
            scores = jnp.where(adj[..., None], scores, _NEG_INF)
            alpha = jax.nn.softmax(scores, axis=1)
            alpha = alpha * adj[..., None]
            alpha = dropout(alpha, attn_dropout, rng, train)
            out = jnp.einsum("njh,jho->nho", alpha, hw)
        else:
            nbr_scores = s_dst[g.neighbors]  # (n, max_deg, H)
            scores = jax.nn.leaky_relu(s_src[:, None, :] + nbr_scores, negative_slope)
            scores = jnp.where(g.mask[..., None], scores, _NEG_INF)
            alpha = jax.nn.softmax(scores, axis=1)
            alpha = alpha * g.mask[..., None]  # zero out fully-padded rows
            alpha = dropout(alpha, attn_dropout, rng, train)
            out = jnp.einsum("ndh,ndho->nho", alpha, hw[g.neighbors])

        out = out + params["b"]
        if concat:
            return out.reshape(out.shape[0], heads * out_dim)
        return out.mean(axis=1)


# ---------------------------------------------------------- GraphConv ----


def init_graph_conv(key: jax.Array, in_dim: int, out_dim: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_self": glorot(k1, (in_dim, out_dim)),
        "w_nbr": glorot(k2, (in_dim, out_dim)),
        "b": jnp.zeros((out_dim,)),
    }


def graph_conv_layer(params: dict, g: GraphBatch, h: jax.Array, *, backend: str = "padded") -> jax.Array:
    """GraphConv (Morris et al.): H' = H W1 + (A H) W2 + b (no self in A)."""
    with jax.named_scope("gnn.agg"):
        nbr_mask = g.mask.at[:, 0].set(False)  # slot 0 is the self-loop
        if backend == "dense":
            adj = _dense_adj(g) & ~jnp.eye(g.num_nodes, dtype=bool)
            agg = adj.astype(h.dtype) @ h
        else:
            agg = jnp.einsum("nd,ndf->nf", nbr_mask.astype(h.dtype), h[g.neighbors])
    with jax.named_scope("gnn.transform"):
        return h @ params["w_self"] + agg @ params["w_nbr"] + params["b"]


# ----------------------------------------------------- GatedGraphConv ----


def init_gated_graph_conv(key: jax.Array, dim: int) -> dict:
    # five independent keys: w_h and u_h previously shared ks[3], making the
    # GRU candidate's input and recurrent projections identical at init. The
    # propagation step count is the layer's ``steps`` kwarg (a static trace
    # constant), not a params entry.
    ks = jax.random.split(key, 5)
    return {
        "w_msg": glorot(ks[0], (dim, dim)),
        "w_zr": glorot(ks[1], (dim, 2 * dim)),
        "u_zr": glorot(ks[2], (dim, 2 * dim)),
        "w_h": glorot(ks[3], (dim, dim)),
        "u_h": glorot(ks[4], (dim, dim)),
    }


def gated_graph_conv_layer(
    params: dict, g: GraphBatch, h: jax.Array, *, steps: int = 3, backend: str = "padded"
) -> jax.Array:
    """GatedGraphConv (Li et al. 2015): GRU state updates over aggregated
    messages for a fixed number of propagation steps."""
    nbr_mask = g.mask.astype(h.dtype)

    def step(state, _):
        with jax.named_scope("gnn.transform"):
            msg = state @ params["w_msg"]
        with jax.named_scope("gnn.agg"):
            if backend == "dense":
                agg = _dense_adj(g).astype(h.dtype) @ msg
            else:
                agg = jnp.einsum("nd,ndf->nf", nbr_mask, msg[g.neighbors])
        with jax.named_scope("gnn.transform"):
            zr = jax.nn.sigmoid(agg @ params["w_zr"] + state @ params["u_zr"])
            z, r = jnp.split(zr, 2, axis=-1)
            cand = jnp.tanh(agg @ params["w_h"] + (r * state) @ params["u_h"])
            return (1.0 - z) * state + z * cand, None

    out, _ = jax.lax.scan(step, h, None, length=steps)
    return out
