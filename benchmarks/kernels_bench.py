"""Kernel microbenches: Pallas (interpret on CPU) vs jnp oracle per shape.

On this container the numbers measure the *reference* math (interpret mode
executes kernel bodies in Python/XLA); they validate plumbing and give the
oracle's CPU cost. TPU wall-clock comes from deploying with interpret=False.

The padded-vs-degree-bucketed rows (``kernels/{spmm|gat}/{padded|bucketed}``)
time the PUBLIC aggregation ops — the exact entry points the GNN layers
call, routed by ``kernels.use_kernel_forward()`` — on the skewed power-law
fixtures' real layouts, and compare the two ops' outputs at float tolerance
in the same run. ``json_path`` writes them as ``BENCH_kernels.json``, the
artifact ``benchmarks.check_perf --kernels-current`` gates (coverage +
output agreement + strict bucketed win + ratio vs the committed baseline).
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.graphs import degree_bucketed_layout, load_dataset
from repro.kernels.gat_edge.kernel import bucket_gat_kernel
from repro.kernels.gat_edge.ops import bucketed_gat_aggregate, gat_aggregate
from repro.kernels.gat_edge.ref import bucket_gat_ref
from repro.kernels.spmm.kernel import padded_spmm_kernel
from repro.kernels.spmm.ops import bucketed_spmm, padded_spmm
from repro.kernels.spmm.ref import padded_spmm_ref
from repro.kernels.ssd.ops import ssd
from repro.models.transformer.ssm import ssd_chunked


def _sparse_rows(rows: dict) -> None:
    """padded vs degree-bucketed aggregation ops at the skewed shapes.

    SpMM runs at full ``skewed-powerlaw`` scale (8k nodes, degree cap 128 —
    the serving-relevant shape); GAT at the ``skewed-mini`` twin because the
    padded GAT op materializes the gathered ``(H, N, D, F)`` tensor, which
    at 8k x 128 is the exact blow-up the bucketed layout exists to avoid —
    timing it would mostly measure the allocator.
    """
    k = jax.random.PRNGKey(7)
    tol = 2e-4  # bucket concat reorders the f32 edge sums

    def bench(family, g, fns, args, derived):
        b = degree_bucketed_layout(g)
        slots = {
            "padded": int(g.neighbors.shape[0] * g.neighbors.shape[1]),
            "bucketed": int(sum(bk.rows * bk.width for bk in b.buckets)),
        }
        jitted = {name: jax.jit(fn(b) if name == "bucketed" else fn(g))
                  for name, fn in fns.items()}
        outs = {name: jax.block_until_ready(fn(*args)) for name, fn in jitted.items()}
        diff = float(jnp.max(jnp.abs(outs["padded"] - outs["bucketed"])))
        for name, fn in jitted.items():
            t = timed(fn, *args, iters=10)
            extra = "" if name == "padded" else f";max_abs_diff={diff:.2e}"
            emit(f"kernels/{family}/{name}", t, f"{derived};slots={slots[name]}{extra}")
            rows[f"kernels/{family}/{name}"] = {
                "t_us": t,
                "layout_slots": slots[name],
                "max_abs_diff": diff,
                "outputs_match": diff <= tol,
            }

    def bucket_fields(b):
        return (
            tuple(bk.neighbors for bk in b.buckets),
            tuple(bk.mask for bk in b.buckets),
            tuple(bk.norm for bk in b.buckets),
            tuple(bk.row_node for bk in b.buckets),
            b.gather_rows,
        )

    # SpMM — GCN-style weighted neighbor sum at hidden width 32
    g = load_dataset("skewed-powerlaw", max_degree=128)
    hw = jax.random.normal(k, (g.features.shape[0], 32))
    bench(
        "spmm", g,
        {
            "padded": lambda g: lambda h: padded_spmm(h, g.neighbors, g.norm),
            "bucketed": lambda b: (
                lambda h, fields=bucket_fields(b):
                bucketed_spmm(h, fields[0], fields[2], fields[4])
            ),
        },
        (hw,),
        f"dataset=skewed-powerlaw;n={g.features.shape[0]};"
        f"max_deg={g.neighbors.shape[1]};f=32",
    )

    # GAT — fused attention aggregate, 8 heads x 8 features. The mini twin,
    # not the 8k fixture: the padded op materializes the gathered
    # (H, N, D, F) tensor, which at 8k x 128 mostly measures the allocator.
    g = load_dataset("skewed-mini")
    heads, f = 8, 8
    hw = jax.random.normal(k, (g.features.shape[0], heads, f))
    s_src = jax.random.normal(jax.random.fold_in(k, 1), (g.features.shape[0], heads))
    s_dst = jax.random.normal(jax.random.fold_in(k, 2), (g.features.shape[0], heads))
    bench(
        "gat", g,
        {
            "padded": lambda g: (
                lambda h, a, c: gat_aggregate(h, a, c, g.neighbors, g.mask)
            ),
            "bucketed": lambda b: (
                lambda h, a, c, fields=bucket_fields(b):
                bucketed_gat_aggregate(h, a, c, fields[0], fields[1], fields[3], fields[4])
            ),
        },
        (hw, s_src, s_dst),
        f"dataset=skewed-mini;n={g.features.shape[0]};"
        f"max_deg={g.neighbors.shape[1]};h={heads};f={f}",
    )


def run(*, json_path=None):
    k = jax.random.PRNGKey(0)
    # GAT edge (cora-scale)
    h_, n, d, f = 8, 2708, 14, 8
    hw_heads = jax.random.normal(k, (h_, n, f))
    nbr_g = jax.random.randint(jax.random.fold_in(k, 3), (n, d), 0, n)
    s_self = jax.random.normal(jax.random.fold_in(k, 1), (h_, n))
    s_nbr = jax.random.normal(jax.random.fold_in(k, 2), (h_, n, d))
    mask = jnp.ones((n, d), bool)
    args = (hw_heads, nbr_g, s_self, s_nbr, mask)
    t_ref = timed(jax.jit(bucket_gat_ref), *args)
    t_ker = timed(lambda *a: bucket_gat_kernel(*a), *args)
    emit("kernels/gat_edge/ref", t_ref, f"n={n};d={d};h={h_}")
    emit("kernels/gat_edge/pallas_interpret", t_ker, "same shape")

    # SpMM (pubmed-scale features)
    n2, d2, f2 = 8192, 16, 64
    hw = jax.random.normal(k, (n2, f2))
    nbr = jax.random.randint(jax.random.fold_in(k, 3), (n2, d2), 0, n2)
    norm = jax.random.uniform(jax.random.fold_in(k, 4), (n2, d2))
    t_ref = timed(jax.jit(padded_spmm_ref), hw, nbr, norm)
    t_ker = timed(lambda *a: padded_spmm_kernel(*a), hw, nbr, norm)
    emit("kernels/spmm/ref", t_ref, f"n={n2};d={d2};f={f2}")
    emit("kernels/spmm/pallas_interpret", t_ker, "same shape")

    # SSD (mamba2-130m-ish slice)
    b, s, hh, p, nn = 1, 512, 8, 64, 64
    x = jax.random.normal(k, (b, s, hh, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 5), (b, s, hh))) * 0.1
    A = -jnp.exp(jnp.linspace(0.0, 2.0, hh))
    B = jax.random.normal(jax.random.fold_in(k, 6), (b, s, nn)) * 0.3
    C = jax.random.normal(jax.random.fold_in(k, 7), (b, s, nn)) * 0.3
    t_ref = timed(jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0]), x, dt, A, B, C)
    t_ker = timed(lambda *a: ssd(*a, 128), x, dt, A, B, C)
    emit("kernels/ssd/ref_chunked", t_ref, f"s={s};h={hh};p={p};n={nn}")
    emit("kernels/ssd/pallas_interpret", t_ker, "same shape")

    rows: dict = {}
    _sparse_rows(rows)
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"rows": rows}, f, indent=2, sort_keys=True)
            f.write("\n")
