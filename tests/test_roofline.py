"""Roofline machinery: loop-aware HLO walker + report math."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import model_flops, peaks_for, roofline_report
from repro.roofline.hlo_walk import analyze_hlo, parse_module
from repro.configs import get_arch, get_shape


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_walker_counts_plain_dot():
    m, k, n = 64, 32, 16
    txt = _compile_text(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32),
    )
    r = analyze_hlo(txt)
    assert r["flops"] == pytest.approx(2 * m * k * n, rel=0.01)


def test_walker_multiplies_scan_trip_count():
    m = 32

    def f(a, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, a, None, length=10)
        return out

    txt = _compile_text(
        f,
        jax.ShapeDtypeStruct((m, m), jnp.float32),
        jax.ShapeDtypeStruct((m, m), jnp.float32),
    )
    r = analyze_hlo(txt)
    # ten matmuls, not one
    assert r["flops"] == pytest.approx(10 * 2 * m * m * m, rel=0.05)


def test_walker_nested_scans():
    m = 16

    def f(a, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        out, _ = jax.lax.scan(outer, a, None, length=4)
        return out

    txt = _compile_text(
        f,
        jax.ShapeDtypeStruct((m, m), jnp.float32),
        jax.ShapeDtypeStruct((m, m), jnp.float32),
    )
    r = analyze_hlo(txt)
    assert r["flops"] == pytest.approx(12 * 2 * m ** 3, rel=0.05)


def test_parse_module_finds_computations():
    txt = _compile_text(lambda a: jnp.sum(a * a), jax.ShapeDtypeStruct((8, 8), jnp.float32))
    comps = parse_module(txt)
    assert len(comps) >= 1


def test_roofline_report_terms():
    rep = roofline_report(
        device_flops=197e12,  # exactly one second of compute
        device_bytes=819e9,  # exactly one second of HBM
        device_collective={"total": 0, "all-gather": 0},
        chips=256,
        model_flops_global=197e12 * 256 * 0.5,
        hw=peaks_for("TPU v5 lite"),
    )
    assert rep["compute_s"] == pytest.approx(1.0)
    assert rep["memory_s"] == pytest.approx(1.0)
    assert rep["collective_s"] == 0.0
    assert rep["useful_flops_ratio"] == pytest.approx(0.5)
    assert rep["dominant"] in ("compute_s", "memory_s")


def test_peaks_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite").peak_flops == 197e12
    with pytest.raises(KeyError, match="cpu"):
        peaks_for("cpu")


def test_model_flops_train_vs_decode():
    cfg = get_arch("codeqwen1.5-7b")
    tr = model_flops(cfg, get_shape("train_4k"), training=True)
    de = model_flops(cfg, get_shape("decode_32k"), training=False)
    # train: 6·N·(256·4096) ; decode: 2·N·128
    assert tr / de == pytest.approx(3 * 256 * 4096 / 128, rel=0.01)


def test_moe_active_params_used():
    cfg = get_arch("deepseek-v3-671b")
    assert cfg.active_param_count() < 0.1 * cfg.param_count()
    mf = model_flops(cfg, get_shape("train_4k"), training=True)
    assert mf == pytest.approx(6 * cfg.active_param_count() * 256 * 4096, rel=1e-6)


# ----------------------------------------------- per-stage layout roofline --


def test_sparse_stage_report_padded_vs_bucketed():
    """The fig-row payload: per-stage measured HLO flops/bytes for the padded
    vs degree-bucketed layouts, against the live-slot roof. The invariants
    the report exists to show: measured >= roof (it is a floor), the padded
    layout materializes more slots than the bucketed one, and neither layout
    can undercut the live count."""
    from repro.core.microbatch import make_plan
    from repro.graphs import bucketize_stacked, load_dataset
    from repro.models.gnn.net import build_gnn
    from repro.roofline import layout_slots, live_slots, sparse_stage_report

    g = load_dataset("skewed-mini")
    model = build_gnn("gcn", g.num_features, g.num_classes,
                      hidden=16, depth=2, backend="pallas")
    params = model.init_params(jax.random.PRNGKey(0))
    plan = make_plan(g, 2, strategy="sequential")
    stacked = plan.stacked().graph
    bucketed = bucketize_stacked(stacked)

    assert live_slots(stacked) == live_slots(bucketed)
    assert layout_slots(bucketed) < layout_slots(stacked)
    assert live_slots(bucketed) <= layout_slots(bucketed)

    report = sparse_stage_report(model, params, stacked, bucketed, (2, 2))
    assert report["slots"]["bucketed"] < report["slots"]["padded"]
    assert report["slots"]["live"] <= report["slots"]["bucketed"]
    assert len(report["stages"]) == 2
    for row in report["stages"]:
        assert row["layers"]
        for layout in ("padded", "bucketed"):
            assert row[layout]["measured_flops"] >= row["roof_flops"] * 0.99
            assert row[layout]["measured_bytes"] >= row["roof_bytes"] * 0.99
    # the stack in total reads fewer bytes through the bucketed tiles
    total = {
        layout: sum(r[layout]["measured_bytes"] for r in report["stages"])
        for layout in ("padded", "bucketed")
    }
    assert total["bucketed"] < total["padded"]
