"""Serving-path correctness: bucketed/padded ego-subgraph inference must be
bit-identical to a host full-batch forward on the same query nodes.

The chain under test is ``ego_subgraph`` (lossless k-hop halo) ->
``pad_graph`` (inert rows/columns) -> ``GNNServer.execute`` (stacked bucket
batch through ``compile_eval``). Single-device tests pin strict bit-identity
on both engines; the ``slow`` subprocess test reruns the check on the
4-forced-device shard_map ring, where XLA CPU's divided thread pool may
re-tile bucket-shaped gemms and shift rare rows ~1 ULP — there the bound is
1e-6 plus argmax equality (see ``serve_gnn.verify_results``).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.cli import PipelineCLIConfig
from repro.core.pipeline import make_engine
from repro.graphs import load_dataset
from repro.graphs.data import pad_graph
from repro.graphs.partition import ego_subgraph
from repro.launch.serve_gnn import (
    GNNServer,
    Query,
    ShapeBuckets,
    serve,
    synth_queries,
    verify_results,
)
from repro.models.gnn.net import build_paper_gat


@pytest.fixture(scope="module")
def setup():
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    full = np.asarray(m.apply(params, g, train=False))
    return g, m, params, full


def _server(g, m, params, *, engine="compiled", chunks=2, buckets=None):
    cfg = PipelineCLIConfig(engine=engine, stages=4, chunks=chunks).gpipe_config()
    return GNNServer(make_engine(m, cfg), params, g, hops=2, buckets=buckets)


# ------------------------------------------------------------ ego-subgraph --


def test_ego_subgraph_lossless_bitwise(setup):
    """hops == receptive depth (2 for the paper GAT): every seed's logp row
    on its ego-subgraph equals the full-graph row BIT FOR BIT — subgraph
    keeps neighbor column order, trailing pad slots contribute exact zeros,
    and per-row reductions are order-stable on a single device."""
    g, m, params, full = setup
    for u in range(g.num_nodes):
        sub, rows = ego_subgraph(g, [u], 2)
        got = np.asarray(m.apply(params, sub, train=False))[rows]
        assert np.array_equal(got, full[[u]]), u
        # ...and padding to a bucket shape must not move a single bit
        padded = pad_graph(sub, g.num_nodes, g.max_degree)
        got_p = np.asarray(m.apply(params, padded, train=False))[rows]
        assert np.array_equal(got_p, full[[u]]), u


def test_ego_subgraph_seed_rows(setup):
    g, _, _, _ = setup
    sub, rows = ego_subgraph(g, [0, 33], 2)
    ids = np.asarray(sub.node_ids)
    assert list(ids[rows]) == [0, 33]


# ------------------------------------------------------------- served path --


@pytest.mark.parametrize("engine", ["host", "compiled"])
def test_served_predictions_bit_identical(setup, engine):
    """The tentpole acceptance check at 1 device: every node-classification
    and link-prediction query served through bucketed, padded, stacked
    batches — on BOTH engines — returns logp rows bit-identical to the host
    full-batch forward."""
    g, m, params, full = setup
    server = _server(g, m, params, engine=engine, chunks=2)
    queries = [Query(i, "node", i) for i in range(g.num_nodes)]
    queries += [Query(100 + i, "link", i, (i + 7) % g.num_nodes) for i in range(6)]
    prepared = [server.prepare(q) for q in queries]
    results = []
    for i in range(0, len(prepared), 2):
        results.extend(server.execute(prepared[i : i + 2]))
    assert len(results) == len(queries)
    mismatches, exact, max_diff = verify_results(m, params, g, results)
    assert (mismatches, exact) == (0, len(queries)), max_diff
    # link scores are the dot of the two served rows — recompute from oracle
    for r in results:
        if r.query.kind == "link":
            want = float(np.dot(full[r.query.u], full[r.query.v]))
            assert r.score == want


def test_partial_batch_is_padded_not_dropped(setup):
    """A 1-request dispatch on a chunks=4 server still returns exactly that
    request's (bit-identical) prediction — the pad replicas are discarded."""
    g, m, params, full = setup
    server = _server(g, m, params, chunks=4)
    out = server.execute([server.prepare(Query(0, "node", 17))])
    assert len(out) == 1
    assert np.array_equal(out[0].logp, full[[17]])
    assert server.occupancy()[g.num_nodes]["occupancy"] == 0.25


def test_open_loop_serve_reports_latency_and_occupancy(setup):
    """The open-loop driver end to end (no wall-clock assumptions beyond
    monotonicity): every query completes, latency covers queueing, and the
    batching stats add up."""
    g, m, params, _ = setup
    server = _server(g, m, params, chunks=2)
    queries = synth_queries(g, 12, qps=500.0, link_frac=0.3, seed=1)
    results = serve(server, queries, max_wait_s=0.01)
    assert len(results) == 12
    assert sorted(r.query.qid for r in results) == list(range(12))
    assert all(r.latency_s > 0 for r in results)
    mismatches, exact, _ = verify_results(m, params, g, results)
    assert mismatches == 0 and exact == 12
    occ = server.occupancy()
    assert sum(v["queries"] for v in occ.values()) == 12
    for v in occ.values():
        assert 0 < v["occupancy"] <= 1


# ---------------------------------------------------------------- buckets --


def test_shape_buckets_ladder():
    g = load_dataset("cora")
    b = ShapeBuckets.geometric(g, base=64)
    assert b.sizes[-1] == g.num_nodes
    assert b.sizes == tuple(sorted(set(b.sizes)))
    assert b.bucket_of(1) == 0
    assert b.bucket_of(64) == 0
    assert b.bucket_of(65) == 1
    assert b.bucket_of(g.num_nodes) == len(b.sizes) - 1
    with pytest.raises(ValueError):
        b.bucket_of(g.num_nodes + 1)
    # small graphs collapse to a single full-graph bucket
    k = load_dataset("karate")
    assert ShapeBuckets.geometric(k, base=64).sizes == (k.num_nodes,)


@given(st.integers(min_value=0, max_value=2**32 - 1))
@settings(max_examples=10, deadline=None)
def test_bucket_assignment_order_invariant(seed):
    """Bucketing determinism: the same query set maps to the same buckets
    regardless of arrival order — bucket_of is a pure function of the ego
    size, and prepare() carries no cross-query state."""
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    buckets = ShapeBuckets([8, 16, 34])
    server = _server(g, m, params, chunks=2, buckets=buckets)
    queries = [Query(i, "node", i % g.num_nodes) for i in range(12)]
    baseline = {q.qid: server.prepare(q).bucket for q in queries}
    rng = np.random.default_rng(seed)
    shuffled = list(queries)
    rng.shuffle(shuffled)
    assert {q.qid: server.prepare(q).bucket for q in shuffled} == baseline


# ------------------------------------------------- multi-device substrate --


def _run(src: str, devices: int = 4, timeout: int = 1200):
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": "src",
        "JAX_PLATFORMS": "cpu",
    }
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, **env},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_served_path_multidevice():
    """The serving chain on the 4-forced-device shard_map ring: predictions
    stay within 1 ULP of the full-batch oracle (strict bit-identity is a
    single-device guarantee; forced-device XLA may re-tile gemms), argmax
    never moves, and the bound EvalProgram issues ZERO device_puts after
    warmup — the re-replication bugfix on the mesh path, where it matters."""
    out = _run("""
    import numpy as np, jax
    from repro.core.cli import PipelineCLIConfig
    from repro.core.pipeline import make_engine
    from repro.graphs import load_dataset
    from repro.launch.serve_gnn import GNNServer, Query, verify_results
    from repro.models.gnn.net import build_paper_gat

    assert jax.device_count() == 4, jax.device_count()
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    full = np.asarray(m.apply(params, g, train=False))
    cfg = PipelineCLIConfig(engine="compiled", stages=4, chunks=2).gpipe_config()
    server = GNNServer(make_engine(m, cfg), params, g, hops=2)
    queries = [Query(i, "node", i) for i in range(g.num_nodes)]
    prepared = [server.prepare(q) for q in queries]
    results = []
    for i in range(0, len(prepared), 2):
        results.extend(server.execute(prepared[i:i+2]))
    mism, exact, max_diff = verify_results(m, params, g, results, atol=1e-6)
    assert mism == 0, (mism, max_diff)
    for r in results:
        assert r.pred == int(full[r.query.u].argmax()), r.query
    print('MD_SERVE_OK', exact, len(results), max_diff)

    # params were bound at the first execute; further batches must not
    # re-place the tree (the per-call device_put regression, mesh path)
    calls = []
    orig = jax.device_put
    jax.device_put = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        server.execute(prepared[:2])
        server.execute(prepared[2:4])
    finally:
        jax.device_put = orig
    assert not calls, f"served batches issued {len(calls)} device_puts"
    print('MD_NO_REPLICATION_OK')
    """)
    assert "MD_SERVE_OK" in out
    assert "MD_NO_REPLICATION_OK" in out
