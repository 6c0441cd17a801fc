"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional-hypothesis shim

from repro.kernels.gat_edge.kernel import bucket_gat_kernel
from repro.kernels.gat_edge.ref import gat_aggregate_ref
from repro.kernels.gat_edge.ops import gat_aggregate, _ref_call
from repro.kernels.spmm.kernel import padded_spmm_kernel
from repro.kernels.spmm.ref import padded_spmm_ref
from repro.kernels.spmm.ops import padded_spmm
from repro.kernels.ssd.ops import ssd
from repro.models.transformer.ssm import ssd_chunked, ssd_reference


def _tol(dtype):
    return 5e-2 if dtype == jnp.bfloat16 else 1e-4


# ------------------------------------------------------------- GAT edge --


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,h,f", [(64, 4, 2, 8), (300, 9, 8, 8), (130, 16, 4, 16)])
def test_gat_kernel_shapes(n, d, h, f, dtype):
    # the padded layout is the square bucket: one kernel row per node
    k = jax.random.PRNGKey(n + d)
    hw_heads = jax.random.normal(k, (h, n, f), dtype)
    nbr = jax.random.randint(jax.random.PRNGKey(4), (n, d), 0, n)
    s_self = jax.random.normal(jax.random.PRNGKey(1), (h, n), dtype)
    s_nbr = jax.random.normal(jax.random.PRNGKey(2), (h, n, d), dtype)
    mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.7, (n, d)).at[:, 0].set(True)
    out_k = bucket_gat_kernel(hw_heads, nbr, s_self, s_nbr, mask)
    out_r = gat_aggregate_ref(hw_heads[:, nbr], s_self, s_nbr, mask)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), atol=_tol(dtype)
    )


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 200),
    d=st.integers(1, 12),
    h=st.integers(1, 4),
    f=st.integers(1, 16),
    seed=st.integers(0, 99),
)
def test_gat_kernel_hypothesis(n, d, h, f, seed):
    k = jax.random.PRNGKey(seed)
    hw_heads = jax.random.normal(k, (h, n, f))
    nbr = jax.random.randint(jax.random.fold_in(k, 4), (n, d), 0, n)
    s_self = jax.random.normal(jax.random.fold_in(k, 1), (h, n))
    s_nbr = jax.random.normal(jax.random.fold_in(k, 2), (h, n, d))
    mask = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.6, (n, d)).at[:, 0].set(True)
    out_k = bucket_gat_kernel(hw_heads, nbr, s_self, s_nbr, mask, block_r=64)
    out_r = gat_aggregate_ref(hw_heads[:, nbr], s_self, s_nbr, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-4)


def test_gat_op_gradients():
    N, D, H, F = 70, 5, 3, 8
    hw = jax.random.normal(jax.random.PRNGKey(0), (N, H, F))
    s_src = jax.random.normal(jax.random.PRNGKey(1), (N, H))
    s_dst = jax.random.normal(jax.random.PRNGKey(2), (N, H))
    nbr = jax.random.randint(jax.random.PRNGKey(3), (N, D), 0, N)
    mask = jnp.ones((N, D), bool)
    args = (hw, s_src, s_dst)
    g_k = jax.grad(lambda *a: jnp.sum(gat_aggregate(*a, nbr, mask) ** 2), argnums=(0, 1, 2))(*args)
    g_r = jax.grad(lambda *a: jnp.sum(_ref_call(*a, nbr, mask, 0.2) ** 2), argnums=(0, 1, 2))(*args)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ----------------------------------------------------------------- SpMM --


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,f", [(100, 7, 32), (512, 16, 64), (33, 3, 8)])
def test_spmm_kernel_shapes(n, d, f, dtype):
    hw = jax.random.normal(jax.random.PRNGKey(0), (n, f), dtype)
    nbr = jax.random.randint(jax.random.PRNGKey(1), (n, d), 0, n)
    norm = (jax.random.uniform(jax.random.PRNGKey(2), (n, d)) * 0.5).astype(dtype)
    out_k = padded_spmm_kernel(hw, nbr, norm, block_n=128)
    out_r = padded_spmm_ref(hw, nbr, norm)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=_tol(dtype) * d,
    )


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 300), d=st.integers(1, 10), f=st.integers(1, 32), seed=st.integers(0, 99))
def test_spmm_hypothesis(n, d, f, seed):
    k = jax.random.PRNGKey(seed)
    hw = jax.random.normal(k, (n, f))
    nbr = jax.random.randint(jax.random.fold_in(k, 1), (n, d), 0, n)
    norm = jax.random.uniform(jax.random.fold_in(k, 2), (n, d))
    out_k = padded_spmm_kernel(hw, nbr, norm, block_n=64)
    out_r = padded_spmm_ref(hw, nbr, norm)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-4)


def test_spmm_grad():
    n, d, f = 64, 5, 16
    hw = jax.random.normal(jax.random.PRNGKey(0), (n, f))
    nbr = jax.random.randint(jax.random.PRNGKey(1), (n, d), 0, n)
    norm = jax.random.uniform(jax.random.PRNGKey(2), (n, d))
    g1 = jax.grad(lambda a: jnp.sum(padded_spmm(a, nbr, norm) ** 2))(hw)
    g2 = jax.grad(lambda a: jnp.sum(padded_spmm_ref(a, nbr, norm) ** 2))(hw)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


# ------------------------------------------------------------------ SSD --


@pytest.mark.parametrize("s,chunk", [(64, 16), (256, 64), (128, 128)])
def test_ssd_kernel_vs_sequential(s, chunk):
    b, h, p, n = 2, 3, 8, 16
    k = jax.random.PRNGKey(s)
    x = jax.random.normal(k, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (b, s, h))) * 0.1
    A = -jnp.exp(jnp.linspace(0.0, 2.0, h))
    B = jax.random.normal(jax.random.fold_in(k, 2), (b, s, n)) * 0.3
    C = jax.random.normal(jax.random.fold_in(k, 3), (b, s, n)) * 0.3
    y_k = ssd(x, dt, A, B, C, chunk)
    y_r, _ = ssd_reference(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)


def test_ssd_grad_matches_chunked():
    b, s, h, p, n = 1, 64, 2, 4, 8
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (b, s, h))) * 0.1
    A = -jnp.exp(jnp.linspace(0.0, 1.0, h))
    B = jax.random.normal(jax.random.fold_in(k, 2), (b, s, n)) * 0.3
    C = jax.random.normal(jax.random.fold_in(k, 3), (b, s, n)) * 0.3
    g1 = jax.grad(lambda a: jnp.sum(ssd(a, dt, A, B, C, 16) ** 2))(x)
    g2 = jax.grad(lambda a: jnp.sum(ssd_chunked(a, dt, A, B, C, chunk=16)[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(
    s_chunks=st.sampled_from([(32, 8), (64, 32), (96, 32)]),
    p=st.sampled_from([4, 8]),
    n=st.sampled_from([8, 16]),
    seed=st.integers(0, 50),
)
def test_ssd_hypothesis(s_chunks, p, n, seed):
    s, chunk = s_chunks
    b, h = 1, 2
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.uniform(jax.random.fold_in(k, 4), (h,)) * 2)
    B = jax.random.normal(jax.random.fold_in(k, 2), (b, s, n)) * 0.3
    C = jax.random.normal(jax.random.fold_in(k, 3), (b, s, n)) * 0.3
    y_k = ssd(x, dt, A, B, C, chunk)
    y_r, _ = ssd_reference(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-4)


# ----------------------------------------- bucketed (degree-tiled) layout --


def _bucketed_fixture(seed=0, n=80, m=220, f=16):
    """A real degree-bucketed layout (from graphs.partition) plus random
    features — the kernels' contract is the layout the layers feed them."""
    from repro.graphs.data import build_graph_batch
    from repro.graphs.partition import degree_bucketed_layout

    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(m, 2))
    feats = rng.standard_normal((n, 4)).astype(np.float32)
    labels = rng.integers(0, 3, size=n)
    g = build_graph_batch(feats, edges, labels, 3)
    b = degree_bucketed_layout(g, widths=(4, 8, g.neighbors.shape[1]))
    hw = jax.random.normal(jax.random.PRNGKey(seed), (n, f))
    return g, b, hw


def _bucket_tuples(b):
    return (
        tuple(bk.neighbors for bk in b.buckets),
        tuple(bk.norm for bk in b.buckets),
        tuple(bk.mask for bk in b.buckets),
        tuple(bk.row_node for bk in b.buckets),
    )


def test_bucket_spmm_kernel_matches_ref_tile():
    from repro.kernels.spmm.kernel import bucket_spmm_kernel

    k = jax.random.PRNGKey(5)
    N, R, W, F = 120, 24, 8, 16
    hw = jax.random.normal(k, (N, F))
    nbr = jax.random.randint(jax.random.fold_in(k, 1), (R, W), 0, N)
    nrm = jax.random.uniform(jax.random.fold_in(k, 2), (R, W))
    out_k = bucket_spmm_kernel(hw, nbr, nrm, block_r=16)
    out_r = jnp.einsum("rw,rwf->rf", nrm, hw[nbr])
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-4)


def test_bucketed_spmm_matches_padded_layout():
    from repro.kernels.spmm.ops import bucketed_spmm

    g, b, hw = _bucketed_fixture()
    nbrs, nrms, _, _ = _bucket_tuples(b)
    out_b = bucketed_spmm(hw, nbrs, nrms, b.gather_rows)
    out_p = padded_spmm_ref(hw, g.neighbors, g.norm)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_p), atol=1e-4)


def test_bucketed_spmm_grad_matches_padded():
    from repro.kernels.spmm.ops import bucketed_spmm

    g, b, hw = _bucketed_fixture(seed=1)
    nbrs, nrms, _, _ = _bucket_tuples(b)
    g_b = jax.grad(lambda a: jnp.sum(bucketed_spmm(a, nbrs, nrms, b.gather_rows) ** 2))(hw)
    g_p = jax.grad(lambda a: jnp.sum(padded_spmm_ref(a, g.neighbors, g.norm) ** 2))(hw)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_p), atol=1e-4)


def test_bucket_gat_kernel_matches_ref_tile():
    from repro.kernels.gat_edge.kernel import bucket_gat_kernel
    from repro.kernels.gat_edge.ref import bucket_gat_ref

    k = jax.random.PRNGKey(9)
    N, R, W, H, F = 90, 16, 8, 3, 8
    hw_heads = jax.random.normal(k, (H, N, F))
    nbr = jax.random.randint(jax.random.fold_in(k, 1), (R, W), 0, N)
    s_self = jax.random.normal(jax.random.fold_in(k, 2), (H, R))
    s_nbr = jax.random.normal(jax.random.fold_in(k, 3), (H, R, W))
    mask = jax.random.bernoulli(jax.random.fold_in(k, 4), 0.7, (R, W)).at[:, 0].set(True)
    out_k = bucket_gat_kernel(hw_heads, nbr, s_self, s_nbr, mask, block_r=8)
    out_r = bucket_gat_ref(hw_heads, nbr, s_self, s_nbr, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-4)


def test_bucketed_gat_matches_padded_layout():
    from repro.kernels.gat_edge.ops import bucketed_gat_aggregate

    g, b, _ = _bucketed_fixture(seed=2)
    H, F = 3, 8
    k = jax.random.PRNGKey(4)
    hw = jax.random.normal(k, (g.num_nodes, H, F))
    s_src = jax.random.normal(jax.random.fold_in(k, 1), (g.num_nodes, H))
    s_dst = jax.random.normal(jax.random.fold_in(k, 2), (g.num_nodes, H))
    nbrs, _, msks, rows = _bucket_tuples(b)
    out_b = bucketed_gat_aggregate(hw, s_src, s_dst, nbrs, msks, rows, b.gather_rows)
    out_p = _ref_call(hw, s_src, s_dst, g.neighbors, g.mask, 0.2)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_p), atol=1e-4)


def test_bucketed_gat_grad_matches_padded():
    from repro.kernels.gat_edge.ops import bucketed_gat_aggregate

    g, b, _ = _bucketed_fixture(seed=3, n=50, m=140)
    H, F = 2, 4
    k = jax.random.PRNGKey(6)
    hw = jax.random.normal(k, (g.num_nodes, H, F))
    s_src = jax.random.normal(jax.random.fold_in(k, 1), (g.num_nodes, H))
    s_dst = jax.random.normal(jax.random.fold_in(k, 2), (g.num_nodes, H))
    nbrs, _, msks, rows = _bucket_tuples(b)
    g_b = jax.grad(
        lambda *a: jnp.sum(
            bucketed_gat_aggregate(*a, nbrs, msks, rows, b.gather_rows) ** 2
        ),
        argnums=(0, 1, 2),
    )(hw, s_src, s_dst)
    g_p = jax.grad(
        lambda *a: jnp.sum(_ref_call(*a, g.neighbors, g.mask, 0.2) ** 2),
        argnums=(0, 1, 2),
    )(hw, s_src, s_dst)
    for a, b_ in zip(g_b, g_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_bucketed_ops_jit_with_forced_kernel(monkeypatch):
    """REPRO_PALLAS_FORCE_KERNEL=1 routes the bucketed forwards through the
    Pallas kernels (interpret mode here) inside jit — the CI smoke path —
    and still matches the oracle."""
    from repro.kernels.spmm.ops import bucketed_spmm
    from repro.kernels.spmm.ref import bucketed_spmm_ref

    g, b, hw = _bucketed_fixture(seed=4, n=40, m=90, f=8)
    nbrs, nrms, _, _ = _bucket_tuples(b)
    want = bucketed_spmm_ref(hw, nbrs, nrms, b.gather_rows)
    monkeypatch.setenv("REPRO_PALLAS_FORCE_KERNEL", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got = jax.jit(
        lambda a: bucketed_spmm(a, nbrs, nrms, b.gather_rows)
    )(hw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
