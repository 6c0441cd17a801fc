"""Unit tests for the CI perf gate's decision logic (benchmarks/check_perf).

The gate ran for several PRs with no tests of its own; the host-normalization
path in particular could silently shrink the comparison set when a host
fill-drain normalizer row was missing or zero — in the limit turning the
speed gate into a no-op pass. These tests drive ``check`` on hand-built
tables covering the missing-row, zero-time, zero-bubble and partition paths
directly.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.check_perf import (  # noqa: E402
    check,
    check_kernels,
    check_serving,
    normalized_ratios,
)


def _row(step_s, *, bubble=0.4, peak=8, peak_acc=16):
    return {
        "step_s": step_s,
        "bubble": bubble,
        "peak_live": peak,
        "peak_live_accounted": peak_acc,
    }


def _table(**rows):
    return {"rows": rows}


def _base_rows(host=1.0, compiled=0.5):
    return {
        "host/fill_drain/chunks2": _row(host),
        "compiled/fill_drain/chunks2": _row(compiled),
    }


def test_gate_passes_on_identical_tables():
    t = _table(**_base_rows())
    assert check(t, t, threshold=1.2, absolute=False) == []


def test_speed_regression_fails_and_is_threshold_scaled():
    base = _table(**_base_rows(host=1.0, compiled=0.5))
    ok = _table(**_base_rows(host=1.0, compiled=0.55))  # 1.1x, inside 1.2
    bad = _table(**_base_rows(host=1.0, compiled=0.7))  # 1.4x
    assert check(base, ok, threshold=1.2, absolute=False) == []
    failures = check(base, bad, threshold=1.2, absolute=False)
    assert any(f.startswith("perf:") for f in failures), failures


def test_missing_compiled_row_is_coverage_failure():
    base = _table(**_base_rows())
    cur = _table(**{"host/fill_drain/chunks2": _row(1.0)})
    failures = check(base, cur, threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "compiled/fill_drain/chunks2" in f
        for f in failures
    ), failures


@pytest.mark.parametrize("side", ["baseline", "current"])
def test_missing_host_normalizer_fails_by_name(side):
    """Regression: a table whose host fill-drain normalizer row is MISSING
    used to drop the pair silently — on the baseline side without any
    failure at all. Both sides must now fail naming the missing row."""
    good = _table(**_base_rows())
    broken = _table(**{"compiled/fill_drain/chunks2": _row(0.5)})
    baseline, current = (broken, good) if side == "baseline" else (good, broken)
    failures = check(baseline, current, threshold=1.2, absolute=False)
    assert any(
        f.startswith(f"normalizer({side}):")
        and "host/fill_drain/chunks2 is missing" in f
        for f in failures
    ), failures


@pytest.mark.parametrize("side", ["baseline", "current"])
def test_zero_time_host_normalizer_fails_by_name(side):
    """A zero (or negative) host step time is a broken measurement, not a
    divisor to crash on or a row to skip: the gate fails naming the row."""
    good = _table(**_base_rows())
    broken = _table(**_base_rows(host=0.0))
    baseline, current = (broken, good) if side == "baseline" else (good, broken)
    failures = check(baseline, current, threshold=1.2, absolute=False)
    assert any(
        f.startswith(f"normalizer({side}):") and "non-positive" in f
        for f in failures
    ), failures


def test_normalized_ratios_reports_problems_not_exceptions():
    ratios, problems = normalized_ratios(
        {
            "compiled/fill_drain/chunks2": _row(0.5),
            "compiled/1f1b/chunks4": _row(0.5),
            "host/fill_drain/chunks4": _row(0.0),
        }
    )
    assert ratios == {}
    assert len(problems) == 2
    assert any("is missing" in p for p in problems)
    assert any("non-positive" in p for p in problems)


def test_empty_comparison_set_fails():
    failures = check(_table(), _table(), threshold=1.2, absolute=False)
    assert any("no comparable compiled rows" in f for f in failures)


# ------------------------------------------------------- zero-bubble path --


def _zb_rows(zb_step, ob_step, *, zb_bubble=0.2, ob_bubble=0.43, zb_peak=9, ob_peak=9):
    return {
        "host/fill_drain/chunks4": _row(1.0),
        "compiled/fill_drain/chunks4": _row(0.6, peak=None, peak_acc=16),
        "compiled/1f1b/chunks4": _row(ob_step, bubble=ob_bubble, peak=ob_peak),
        "compiled/zb-h1/chunks4": _row(zb_step, bubble=zb_bubble, peak=zb_peak),
    }


def test_zero_bubble_gate_passes_when_zb_dominates():
    t = _table(**_zb_rows(0.45, 0.5))
    assert check(t, t, threshold=1.2, absolute=False) == []


def test_zero_bubble_gate_fails_on_step_bubble_and_peak():
    base = _table(**_zb_rows(0.45, 0.5))
    slow = _table(**_zb_rows(0.7, 0.5))  # zb step > 1f1b * 1.2
    failures = check(base, slow, threshold=1.2, absolute=False)
    assert any("zero-bubble" in f and "does not beat" in f for f in failures)
    bubbly = _table(**_zb_rows(0.45, 0.5, zb_bubble=0.43))
    failures = check(base, bubbly, threshold=1.2, absolute=False)
    assert any("zero-bubble" in f and "bubble" in f for f in failures)
    fat = _table(**_zb_rows(0.45, 0.5, zb_peak=12))
    failures = check(base, fat, threshold=1.2, absolute=False)
    assert any("zero-bubble" in f and "peak_live" in f for f in failures)


def test_zero_bubble_gate_fails_without_1f1b_row():
    base = _table(**_zb_rows(0.45, 0.5))
    cur = dict(_zb_rows(0.45, 0.5))
    del cur["compiled/1f1b/chunks4"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any("zero-bubble" in f and "no compiled 1f1b row" in f for f in failures)


# --------------------------------------------------------- partition path --


def _part_rows(uniform, profiled):
    rows = _base_rows()
    rows["partition/uniform/chunks4"] = {"step_s": uniform, "balance": [2, 2, 2, 2]}
    rows["partition/profiled/chunks4"] = {"step_s": profiled, "balance": [1, 1, 1, 5]}
    return rows


def test_partition_gate_requires_profiled_to_beat_uniform():
    good = _table(**_part_rows(0.40, 0.30))
    assert check(good, good, threshold=1.2, absolute=False) == []
    tie = _table(**_part_rows(0.40, 0.40))
    failures = check(good, tie, threshold=1.2, absolute=False)
    assert any(f.startswith("partition:") and "does not beat" in f for f in failures)
    worse = _table(**_part_rows(0.40, 0.50))
    failures = check(good, worse, threshold=1.2, absolute=False)
    assert any(f.startswith("partition:") for f in failures)


def test_partition_gate_coverage():
    base = _table(**_part_rows(0.40, 0.30))
    cur = dict(_part_rows(0.40, 0.30))
    del cur["partition/uniform/chunks4"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "partition/uniform/chunks4" in f
        for f in failures
    ), failures
    assert any(
        f.startswith("partition:") and "no uniform row" in f for f in failures
    ), failures


# -------------------------------------------------------------- auto gate --


def _auto_rows(pick, best_hand, *, predicted=None, other_hand=None):
    rows = _base_rows()
    rows["auto/hand/1f1b_profiled/chunks4"] = {
        "step_s": best_hand, "schedule": "1f1b", "balance": [1, 1, 1, 5],
    }
    rows["auto/hand/fill_drain_uniform/chunks4"] = {
        "step_s": other_hand if other_hand is not None else best_hand * 1.5,
        "schedule": "fill_drain", "balance": [2, 2, 2, 2],
    }
    rows["auto/pick"] = {
        "step_s": pick, "schedule": "1f1b", "chunks": 4,
        "balance": [1, 1, 1, 5],
        "predicted_step_s": predicted if predicted is not None else pick,
    }
    return rows


def test_auto_gate_passes_when_pick_competitive():
    t = _table(**_auto_rows(0.28, 0.30))
    assert check(t, t, threshold=1.2, absolute=False) == []
    # pick slightly worse than best hand but inside threshold
    ok = _table(**_auto_rows(0.33, 0.30))
    assert check(t, ok, threshold=1.2, absolute=False) == []


def test_auto_gate_fails_by_name_when_pick_loses_to_hand():
    base = _table(**_auto_rows(0.28, 0.30))
    bad = _table(**_auto_rows(0.40, 0.30))  # 1.33x the best hand config
    failures = check(base, bad, threshold=1.2, absolute=False)
    assert any(
        f.startswith("auto-pick:") and "1f1b_profiled" in f for f in failures
    ), failures


def test_auto_gate_bounds_prediction_error_by_name():
    base = _table(**_auto_rows(0.28, 0.30, predicted=0.09))
    assert check(base, base, threshold=1.2, absolute=False) == []
    wild = _table(**_auto_rows(0.28, 0.30, predicted=0.28 * 30))
    failures = check(base, wild, threshold=1.2, absolute=False)
    assert any(f.startswith("auto-prediction:") and "off by" in f for f in failures)
    tiny = _table(**_auto_rows(0.28, 0.30, predicted=0.28 / 30))
    failures = check(base, tiny, threshold=1.2, absolute=False)
    assert any(f.startswith("auto-prediction:") for f in failures)
    # the cap is a flag: a tighter ratio turns the committed-style gap fatal
    failures = check(base, base, threshold=1.2, absolute=False, auto_pred_ratio=2.0)
    assert any(f.startswith("auto-prediction:") for f in failures)


def test_auto_gate_unusable_prediction_fails_by_name():
    rows = _auto_rows(0.28, 0.30)
    rows["auto/pick"]["predicted_step_s"] = 0.0
    t = _table(**rows)
    failures = check(t, t, threshold=1.2, absolute=False)
    assert any(
        f.startswith("auto-prediction:") and "unusable" in f for f in failures
    ), failures


def test_auto_gate_coverage_and_missing_hands():
    base = _table(**_auto_rows(0.28, 0.30))
    # current run lost the pick row entirely
    cur = dict(_auto_rows(0.28, 0.30))
    del cur["auto/pick"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "auto/pick" in f for f in failures
    ), failures
    assert any(
        f.startswith("auto-pick:") and "produced none" in f for f in failures
    ), failures
    # pick present but no hand rows to compare against
    cur = dict(_base_rows())
    cur["auto/pick"] = dict(_auto_rows(0.28, 0.30)["auto/pick"])
    failures = check(_table(**cur), _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("auto-pick:") and "no auto/hand" in f for f in failures
    ), failures


# ------------------------------------------------------------ sparse gate --


def _sparse_rows(padded, bucketed, *, padded_match=True, bucketed_match=True):
    rows = _base_rows()
    rows["sparse/padded/chunks2"] = {
        "step_s": padded, "max_update_diff": 5e-8, "updates_match": padded_match,
    }
    rows["sparse/bucketed/chunks2"] = {
        "step_s": bucketed, "max_update_diff": 5e-8, "updates_match": bucketed_match,
    }
    return rows


def test_sparse_gate_leaves_the_step_race_to_the_kernels_gate():
    """Both backends bucket the fixture, so the engine rows may tie either
    way; only their updates are gated."""
    good = _table(**_sparse_rows(0.35, 0.05))
    assert check(good, good, threshold=1.2, absolute=False) == []
    for padded, bucketed in ((0.35, 0.35), (0.35, 0.36)):
        cur = _table(**_sparse_rows(padded, bucketed))
        assert not any(
            f.startswith("sparse:")
            for f in check(good, cur, threshold=1.2, absolute=False)
        ), (padded, bucketed)


def test_sparse_gate_requires_updates_match_on_both_rows():
    good = _table(**_sparse_rows(0.35, 0.05))
    for kw in ({"padded_match": False}, {"bucketed_match": False}):
        bad = _table(**_sparse_rows(0.35, 0.05, **kw))
        failures = check(good, bad, threshold=1.2, absolute=False)
        assert any(
            f.startswith("sparse:") and "diverged" in f for f in failures
        ), (kw, failures)


def test_sparse_gate_coverage():
    base = _table(**_sparse_rows(0.35, 0.05))
    cur = dict(_sparse_rows(0.35, 0.05))
    del cur["sparse/padded/chunks2"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "sparse/padded/chunks2" in f for f in failures
    ), failures
    assert any(
        f.startswith("sparse:") and "no padded row" in f for f in failures
    ), failures


# ------------------------------------------------------------- scale gate --


def _scale_rows(s1=0.05, s2=0.10, s4=0.22, *, match=True):
    rows = _base_rows()
    for n, s in ((25_000, s1), (50_000, s2), (100_000, s4)):
        rows[f"scale/n{n}/chunks{max(n // 25_000, 2)}"] = {
            "step_s": s, "nodes": n, "max_update_diff": 0.0,
            "updates_match": match, "edge_cut": 0.4,
            "data_parallel_active": True,
        }
    return rows


def test_scale_gate_passes_on_identical_tables():
    t = _table(**_scale_rows())
    assert check(t, t, threshold=1.2, absolute=False) == []


def test_scale_gate_growth_ratio_is_machine_cancelling():
    base = _table(**_scale_rows(0.05, 0.10, 0.22))
    # uniformly 3x slower machine: every ratio to n_min is unchanged
    slower = _table(**_scale_rows(0.15, 0.30, 0.66))
    assert check(base, slower, threshold=1.2, absolute=False) == []
    # superlinear blow-up at the largest size: ratio 8.0x vs baseline 4.4x
    regressed = _table(**_scale_rows(0.05, 0.10, 0.40))
    failures = check(base, regressed, threshold=1.2, absolute=False)
    assert any(
        f.startswith("scale:") and "growth ratio" in f and "n100000" in f
        for f in failures
    ), failures


def test_scale_gate_requires_updates_match():
    base = _table(**_scale_rows())
    bad = _table(**_scale_rows(match=False))
    failures = check(base, bad, threshold=1.2, absolute=False)
    assert any(f.startswith("scale:") and "diverged" in f for f in failures), failures


def test_scale_gate_coverage_fails_by_name():
    base = _table(**_scale_rows())
    cur = dict(_scale_rows())
    del cur["scale/n100000/chunks4"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "scale/n100000/chunks4" in f
        for f in failures
    ), failures


def test_scale_gate_zero_anchor_fails():
    base = _table(**_scale_rows())
    cur = _table(**_scale_rows(s1=0.0))
    failures = check(base, cur, threshold=1.2, absolute=False)
    assert any(
        f.startswith("scale:") and "non-positive anchor" in f for f in failures
    ), failures


# ------------------------------------------------------------ overlap gate --


def _overlap_rows(ser=0.140, db=0.158, *, ser_ticks=22, db_ticks=38,
                  fraction=0.0, ser_match=True, db_match=True):
    rows = _base_rows()
    rows["overlap/serialized/chunks8"] = {
        "step_s": ser, "num_ticks": ser_ticks, "wire_latency": 1,
        "max_update_diff": 0.0, "updates_match": ser_match,
        "overlap_fraction": 0.0,
    }
    rows["overlap/double-buffer/chunks8"] = {
        "step_s": db, "num_ticks": db_ticks, "wire_latency": 2,
        "max_update_diff": 0.0, "updates_match": db_match,
        "overlap_fraction": fraction,
    }
    return rows


def test_overlap_gate_passes_on_identical_tables():
    t = _table(**_overlap_rows())
    assert check(t, t, threshold=1.2, absolute=False) == []


def test_overlap_gate_tick_bound_when_no_traced_overlap():
    """fraction ~0 (lockstep CPU): the rule is per-tick — 0.158/38 beats
    0.140/22 even though the raw step is slower; a double-buffered tick
    that got DEARER than the serialized tick fails by name."""
    ok = _table(**_overlap_rows(ser=0.140, db=0.158))
    assert check(ok, ok, threshold=1.2, absolute=False) == []
    # 0.30/38 per tick > 0.140/22 per tick
    bad = _table(**_overlap_rows(ser=0.140, db=0.30))
    failures = check(bad, bad, threshold=1.2, absolute=False)
    assert any(
        f.startswith("overlap:") and "per-tick" in f for f in failures
    ), failures


def test_overlap_gate_strict_step_bound_when_overlap_traced():
    """fraction > 0.05 (the runtime demonstrably hid collectives): the
    double-buffered STEP must beat/match serialized within threshold —
    tick inflation is no excuse once overlap is real."""
    ok = _table(**_overlap_rows(ser=0.140, db=0.130, fraction=0.4))
    assert check(ok, ok, threshold=1.1, absolute=False) == []
    bad = _table(**_overlap_rows(ser=0.140, db=0.158, fraction=0.4))
    failures = check(bad, bad, threshold=1.1, absolute=False)
    assert any(
        f.startswith("overlap:") and "despite traced overlap_fraction" in f
        for f in failures
    ), failures


def test_overlap_gate_requires_updates_match_on_both_rows():
    good = _table(**_overlap_rows())
    for kw in ({"ser_match": False}, {"db_match": False}):
        bad = _table(**_overlap_rows(**kw))
        failures = check(good, bad, threshold=1.2, absolute=False)
        assert any(
            f.startswith("overlap:") and "diverged" in f for f in failures
        ), (kw, failures)


def test_overlap_gate_coverage_and_partner_fail_by_name():
    base = _table(**_overlap_rows())
    cur = dict(_overlap_rows())
    del cur["overlap/serialized/chunks8"]
    failures = check(base, _table(**cur), threshold=1.2, absolute=False)
    assert any(
        f.startswith("coverage:") and "overlap/serialized/chunks8" in f
        for f in failures
    ), failures
    assert any(
        f.startswith("overlap:") and "no serialized row" in f for f in failures
    ), failures


def test_overlap_gate_missing_accounting_fails_by_name():
    """A row without overlap_fraction (no profiler report) or without tick
    counts cannot be gated — named failure, never a silent pass."""
    rows = _overlap_rows()
    del rows["overlap/double-buffer/chunks8"]["overlap_fraction"]
    failures = check(_table(**rows), _table(**rows), threshold=1.2, absolute=False)
    assert any(
        f.startswith("overlap:") and "overlap_fraction" in f for f in failures
    ), failures
    rows = _overlap_rows(db_ticks=0)
    failures = check(_table(**rows), _table(**rows), threshold=1.2, absolute=False)
    assert any(
        f.startswith("overlap:") and "tick accounting" in f for f in failures
    ), failures


# ----------------------------------------------------------- kernels gate --


def _kernel_row(t_us, *, match=True, diff=0.0):
    return {"t_us": t_us, "layout_slots": 1000,
            "max_abs_diff": diff, "outputs_match": match}


def _kernel_table(padded=100.0, bucketed=10.0, **kw):
    return {"rows": {
        "kernels/spmm/padded": _kernel_row(padded),
        "kernels/spmm/bucketed": _kernel_row(bucketed, **kw),
    }}


def test_kernels_gate_passes_on_identical_tables():
    t = _kernel_table()
    assert check_kernels(t, t, threshold=1.3) == []


def test_kernels_gate_requires_strict_bucketed_win():
    base = _kernel_table(padded=100.0, bucketed=10.0)
    cur = _kernel_table(padded=100.0, bucketed=100.0)
    failures = check_kernels(base, cur, threshold=1.3)
    assert any("must win strictly" in f for f in failures), failures


def test_kernels_gate_ratio_regression_is_machine_cancelling():
    base = _kernel_table(padded=100.0, bucketed=10.0)  # 0.10x
    slower_machine = _kernel_table(padded=300.0, bucketed=30.0)  # still 0.10x
    assert check_kernels(base, slower_machine, threshold=1.3) == []
    regressed = _kernel_table(padded=100.0, bucketed=20.0)  # 0.20x > 0.10 * 1.3
    failures = check_kernels(base, regressed, threshold=1.3)
    assert any("bucketed/padded ratio" in f for f in failures), failures


def test_kernels_gate_output_divergence_fails():
    base = _kernel_table()
    bad = _kernel_table(match=False, diff=0.5)
    failures = check_kernels(base, bad, threshold=1.3)
    assert any("output diverged" in f for f in failures), failures


def test_kernels_gate_coverage_fails_by_name():
    base = _kernel_table()
    cur = {"rows": {"kernels/spmm/padded": _kernel_row(100.0)}}
    failures = check_kernels(base, cur, threshold=1.3)
    assert any(
        f.startswith("kernels-coverage:") and "kernels/spmm/bucketed" in f
        for f in failures
    ), failures
    failures = check_kernels(base, {"rows": {}}, threshold=1.3)
    assert any("no kernels/ rows" in f for f in failures), failures


def test_kernels_gate_zero_padded_normalizer_fails():
    base = _kernel_table()
    cur = _kernel_table(padded=0.0)
    failures = check_kernels(base, cur, threshold=1.3)
    assert any("not positive" in f for f in failures), failures


# ----------------------------------------------------------- serving gate --


def _serve_row(p99=0.12, call=0.04, *, qps=45.0, queries=250):
    return {
        "p99_s": p99,
        "p50_s": p99 / 3,
        "eval_call_s": call,
        "achieved_qps": qps,
        "queries": queries,
    }


def _serve_table(**rows):
    return {"rows": {f"serving/{k}": v for k, v in rows.items()}}


def test_serving_gate_passes_on_identical_tables():
    t = _serve_table(cora=_serve_row())
    assert check_serving(t, t, threshold=2.0) == []


def test_serving_gate_p99_ratio_regression():
    """p99 is compared as a ratio over the run's own warm eval_call_s, so a
    uniformly slower machine cancels out — only a genuinely worse
    p99-to-compute ratio trips the gate."""
    base = _serve_table(cora=_serve_row(p99=0.12, call=0.04))  # 3.0x
    slower_machine = _serve_table(cora=_serve_row(p99=0.24, call=0.08))  # still 3.0x
    assert check_serving(base, slower_machine, threshold=2.0) == []
    regressed = _serve_table(cora=_serve_row(p99=0.30, call=0.04))  # 7.5x
    failures = check_serving(base, regressed, threshold=2.0)
    assert any(f.startswith("serving:") and "p99/eval_call" in f for f in failures)


def test_serving_gate_coverage_fails_by_name():
    base = _serve_table(cora=_serve_row(), karate=_serve_row())
    cur = _serve_table(cora=_serve_row())
    failures = check_serving(base, cur, threshold=2.0)
    assert any(
        f.startswith("serving-coverage:") and "serving/karate" in f for f in failures
    ), failures
    failures = check_serving(base, {"rows": {}}, threshold=2.0)
    assert any("no serving/ rows" in f for f in failures), failures


@pytest.mark.parametrize("side", ["baseline", "current"])
def test_serving_gate_missing_normalizer_fails_by_name(side):
    good = _serve_table(cora=_serve_row())
    broken = _serve_table(cora={**_serve_row(), "eval_call_s": 0.0})
    baseline, current = (broken, good) if side == "baseline" else (good, broken)
    failures = check_serving(baseline, current, threshold=2.0)
    assert any(
        f.startswith(f"serving-normalizer({side}):") and "non-positive" in f
        for f in failures
    ), failures


def test_serving_gate_broken_run_fails():
    t = _serve_table(cora=_serve_row())
    dead = _serve_table(cora=_serve_row(qps=0.0, queries=0))
    failures = check_serving(t, dead, threshold=2.0)
    assert any("served no queries" in f for f in failures)
    assert any("achieved_qps" in f for f in failures)


def test_serving_gate_new_row_needs_no_baseline():
    """A row the baseline has never seen is checked for sanity but not for
    regression — committing the baseline is a separate, deliberate step."""
    base = _serve_table(cora=_serve_row())
    cur = _serve_table(cora=_serve_row(), pubmed=_serve_row(p99=9.0, call=0.01))
    assert check_serving(base, cur, threshold=2.0) == []
