"""GNN layers: backend agreement, normalization, attention invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs import load_dataset
from repro.models.gnn import layers as L
from repro.models.gnn.net import build_paper_gat, build_gnn


@pytest.fixture(scope="module")
def karate():
    return load_dataset("karate")


def test_gat_dense_equals_padded(karate):
    g = karate
    p = L.init_gat(jax.random.PRNGKey(0), g.num_features, 8, heads=4)
    h = g.features
    out_p = L.gat_layer(p, g, h, backend="padded")
    out_d = L.gat_layer(p, g, h, backend="dense")
    assert jnp.allclose(out_p, out_d, atol=1e-4), float(jnp.max(jnp.abs(out_p - out_d)))


def test_gat_pallas_matches_padded(karate):
    g = karate
    p = L.init_gat(jax.random.PRNGKey(0), g.num_features, 8, heads=4)
    out_p = L.gat_layer(p, g, g.features, backend="padded")
    out_k = L.gat_layer(p, g, g.features, backend="pallas")
    assert jnp.allclose(out_p, out_k, atol=1e-4)


def test_gcn_backends_agree(karate):
    g = karate
    p = L.init_gcn(jax.random.PRNGKey(0), g.num_features, 16)
    out_p = L.gcn_layer(p, g, g.features, backend="padded")
    out_d = L.gcn_layer(p, g, g.features, backend="dense")
    out_k = L.gcn_layer(p, g, g.features, backend="pallas")
    assert jnp.allclose(out_p, out_d, atol=1e-4)
    assert jnp.allclose(out_p, out_k, atol=1e-4)


def test_gat_attention_rows_sum_to_one(karate):
    """Masked softmax invariant, via a uniform-value probe: if all neighbor
    features are 1, the attention-weighted sum must be exactly 1."""
    g = karate
    heads, out_dim = 3, 5
    p = L.init_gat(jax.random.PRNGKey(1), g.num_features, out_dim, heads=heads)
    ones = jnp.ones((g.num_nodes, g.num_features))
    # force W·h == 1 by zeroing W and adding bias-like trick: instead probe
    # alpha directly through a linear model with constant transformed feats
    p = dict(p, w=jnp.zeros_like(p["w"]), b=jnp.ones_like(p["b"]))
    out = L.gat_layer(p, g, ones, concat=False, backend="padded")
    # Wh == 0 -> out = Σ alpha·0 + b = 1 exactly; checks padding rows too
    assert jnp.allclose(out, jnp.ones_like(out), atol=1e-5)


def test_graphconv_and_gated(karate):
    g = karate
    p1 = L.init_graph_conv(jax.random.PRNGKey(0), g.num_features, 8)
    o1 = L.graph_conv_layer(p1, g, g.features)
    assert o1.shape == (g.num_nodes, 8)
    p2 = L.init_gated_graph_conv(jax.random.PRNGKey(1), 8)
    o2 = L.gated_graph_conv_layer(p2, g, o1)
    assert o2.shape == (g.num_nodes, 8)
    assert np.isfinite(np.asarray(o2)).all()


def test_gated_graph_conv_init_keys_independent():
    """w_h and u_h were both drawn from the same key (GRU candidate's input
    and recurrent projections started identical); params carry no dead
    entries — the step count is the apply-time kwarg."""
    p = L.init_gated_graph_conv(jax.random.PRNGKey(0), 16)
    assert set(p) == {"w_msg", "w_zr", "u_zr", "w_h", "u_h"}
    assert not np.allclose(np.asarray(p["w_h"]), np.asarray(p["u_h"]))
    # every weight pairwise distinct (5 independent subkeys)
    mats = [np.asarray(p[k]) for k in ("w_msg", "w_h", "u_h")]
    for i in range(len(mats)):
        for j in range(i + 1, len(mats)):
            assert not np.allclose(mats[i], mats[j])


def test_pallas_gat_attn_dropout_validates_up_front(karate):
    """Both entry points fail fast with a clear error when asked to train
    attention dropout through the deterministic fused kernel; eval and
    rate-0 paths stay usable."""
    g = karate
    rng = jax.random.PRNGKey(0)
    p = L.init_gat(jax.random.PRNGKey(1), g.num_features, 8, heads=2)
    # layer path: raises before running the kernel
    with pytest.raises(ValueError, match="deterministic"):
        L.gat_layer(p, g, g.features, attn_dropout=0.5, rng=rng, train=True,
                    backend="pallas")
    # net path: no silent zeroing — the same clear error surfaces
    m = build_paper_gat(g.num_features, g.num_classes, backend="pallas")
    params = m.init_params(jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="deterministic"):
        m.apply(params, g, rng=rng, train=True)
    # eval path (train=False) is deterministic anyway and must work
    logp = m.apply(params, g, train=False)
    assert np.isfinite(np.asarray(logp)).all()
    # rate-0 training works
    m0 = build_paper_gat(g.num_features, g.num_classes, backend="pallas", attn_dropout=0.0)
    p0 = m0.init_params(jax.random.PRNGKey(2))
    logp = m0.apply(p0, g, rng=rng, train=True)
    assert np.isfinite(np.asarray(logp)).all()


def test_paper_model_shapes(karate):
    g = karate
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    logp = m.apply(params, g)
    assert logp.shape == (g.num_nodes, g.num_classes)
    # log-softmax rows normalize
    assert jnp.allclose(jnp.exp(logp).sum(-1), 1.0, atol=1e-4)


@pytest.mark.parametrize("kind", ["gcn", "graphconv", "gatedgraphconv"])
def test_model_zoo_builds(karate, kind):
    g = karate
    m = build_gnn(kind, g.num_features, g.num_classes, hidden=16)
    params = m.init_params(jax.random.PRNGKey(0))
    logp = m.apply(params, g)
    assert logp.shape == (g.num_nodes, g.num_classes)
    assert np.isfinite(np.asarray(logp)).all()


# -------------------------------------------- degree-bucketed layer inputs --


@pytest.fixture(scope="module")
def skewed_mini():
    from repro.graphs import degree_bucketed_layout

    g = load_dataset("skewed-mini")
    return g, degree_bucketed_layout(g)


def test_gcn_pallas_bucketed_matches_padded(skewed_mini):
    """The pallas backend reads the degree-bucketed tiles when handed a
    BucketedGraphBatch and must agree with the padded gather on the same
    graph (same math, different layout)."""
    g, b = skewed_mini
    p = L.init_gcn(jax.random.PRNGKey(0), g.num_features, 16)
    out_p = L.gcn_layer(p, g, g.features, backend="padded")
    out_b = L.gcn_layer(p, b, g.features, backend="pallas")
    assert jnp.allclose(out_p, out_b, atol=1e-4), float(jnp.max(jnp.abs(out_p - out_b)))


def test_gat_pallas_bucketed_matches_padded(skewed_mini):
    g, b = skewed_mini
    p = L.init_gat(jax.random.PRNGKey(0), g.num_features, 8, heads=4)
    out_p = L.gat_layer(p, g, g.features, backend="padded")
    out_b = L.gat_layer(p, b, g.features, backend="pallas")
    assert jnp.allclose(out_p, out_b, atol=1e-4), float(jnp.max(jnp.abs(out_p - out_b)))


def test_padded_backend_ignores_bucket_wrapper(skewed_mini):
    """BucketedGraphBatch delegates to its base: the padded GAT and the
    dense GCN see the wrapper as the plain padded batch (layout-blind
    plumbing). The padded GCN reads the buckets (next test)."""
    g, b = skewed_mini
    p = L.init_gat(jax.random.PRNGKey(1), g.num_features, 8, heads=2)
    out_g = L.gat_layer(p, g, g.features, backend="padded")
    out_b = L.gat_layer(p, b, g.features, backend="padded")
    assert jnp.array_equal(out_g, out_b)
    p = L.init_gcn(jax.random.PRNGKey(1), g.num_features, 8)
    out_g = L.gcn_layer(p, g, g.features, backend="dense")
    out_b = L.gcn_layer(p, b, g.features, backend="dense")
    assert jnp.array_equal(out_g, out_b)


def capped_powerlaw():
    """A power-law chunk capped at 32 neighbours (33-wide rows, some full),
    cut by ``subgraph`` so rows have holes in the mask, and padded with
    isolated rows that hold no slot at all."""
    from repro.graphs import open_streamed
    from repro.graphs.data import pad_graph, subgraph

    ds = open_streamed("powerlaw-64k", num_nodes=4096, block_size=512)
    g = ds.chunk_batch(0, 4096, max_degree=32)
    g = subgraph(g, np.flatnonzero(np.arange(g.num_nodes) % 97 != 1))
    g = pad_graph(g, g.num_nodes + 6, g.max_degree)
    msk = np.asarray(g.mask)
    assert g.max_degree == 33 and msk.all(axis=1).any()
    assert (~msk.any(axis=1)).sum() == 6
    assert ((~msk[:, :-1]) & msk[:, 1:]).any()  # a hole before a live slot
    return g


@pytest.fixture(scope="module", params=["capped-powerlaw", "skewed-mini"])
def gcn_graph(request):
    from repro.graphs import degree_bucketed_layout

    g = capped_powerlaw() if request.param == "capped-powerlaw" else load_dataset("skewed-mini")
    return g, degree_bucketed_layout(g)


def test_gcn_padded_bucketed_matches_padded(gcn_graph):
    """The padded GCN over the degree-bucketed wrapper computes the plain
    padded gather's output and its gradients with respect to ``w``, ``b``
    and ``h`` to float32 rounding: only padding slots, which add zero, are
    left out."""
    g, b = gcn_graph
    p = L.init_gcn(jax.random.PRNGKey(3), g.num_features, 16)
    ct = jax.random.normal(jax.random.PRNGKey(4), (g.num_nodes, 16))

    def loss(p, h, graph):
        return jnp.sum(L.gcn_layer(p, graph, h, backend="padded") * ct)

    with jax.default_matmul_precision("highest"):
        out_g = L.gcn_layer(p, g, g.features, backend="padded")
        out_b = L.gcn_layer(p, b, g.features, backend="padded")
        grad_g = jax.grad(loss, argnums=(0, 1))(p, g.features, g)
        grad_b = jax.grad(loss, argnums=(0, 1))(p, g.features, b)
    scale = float(jnp.max(jnp.abs(out_g)))
    np.testing.assert_allclose(out_b, out_g, rtol=1e-5, atol=1e-6 * scale)
    for name, want, got in (("w", grad_g[0]["w"], grad_b[0]["w"]),
                            ("b", grad_g[0]["b"], grad_b[0]["b"]),
                            ("h", grad_g[1], grad_b[1])):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale, err_msg=name)


def test_bucketed_layer_forced_kernel_matches_oracle(monkeypatch, skewed_mini):
    """REPRO_PALLAS_FORCE_KERNEL=1 (the CI kernels-smoke env) drives the
    layer through the real Pallas kernels in interpret mode."""
    g, b = skewed_mini
    p = L.init_gcn(jax.random.PRNGKey(2), g.num_features, 8)
    want = L.gcn_layer(p, b, g.features, backend="pallas")
    monkeypatch.setenv("REPRO_PALLAS_FORCE_KERNEL", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got = L.gcn_layer(p, b, g.features, backend="pallas")
    assert jnp.allclose(want, got, atol=1e-4)
