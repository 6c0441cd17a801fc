"""Trace-attribution logic of the overlap profiler (core.overlap_report):
HLO-name filtering, leaf-only compute detection, same-lane intersection,
and the gzipped chrome-trace loader — all on synthetic events, no profiler
run needed."""

import gzip
import json
import os

import pytest

from repro.core.overlap_report import (
    ASYNC_XLA_FLAGS,
    capture_overlap_report,
    load_trace_events,
    overlap_from_events,
)


def _ev(name, ts, dur, *, pid=1, tid=1):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid, "tid": tid}


def test_overlap_fraction_same_lane_only():
    """A collective only counts as hidden under compute on ITS OWN lane —
    cross-device concurrency is just the pipeline running."""
    events = [
        _ev("collective-permute.1", 0, 10, tid=1),
        _ev("dot.2", 0, 10, tid=2),  # other device: not overlap
    ]
    r = overlap_from_events(events)
    assert r["collective_time_us"] == 10
    assert r["compute_time_us"] == 10
    assert r["overlapped_time_us"] == 0
    assert r["overlap_fraction"] == 0.0
    # same lane, half-covered
    events = [
        _ev("collective-permute.1", 0, 10),
        _ev("dot.2", 5, 10),
    ]
    r = overlap_from_events(events)
    assert r["overlapped_time_us"] == 5
    assert r["overlap_fraction"] == 0.5


def test_container_events_do_not_count_as_compute():
    """A scan's ``while.N`` span contains every tick including the
    collectives inside it; counting it as compute would report those
    collectives as 100% hidden under themselves."""
    events = [
        _ev("while.1", 0, 100),  # container: spans both children
        _ev("dot.3", 10, 10),
        _ev("collective-permute.2", 50, 20),
    ]
    r = overlap_from_events(events)
    assert r["compute_time_us"] == 10  # the leaf dot only
    assert r["collective_time_us"] == 20
    assert r["overlap_fraction"] == 0.0
    assert r["num_compute_events"] == 1


def test_collectives_count_even_as_parents():
    """An async collective wrapping its own done-event is still collective
    time — only COMPUTE is restricted to leaves."""
    events = [
        _ev("all-gather.1", 0, 30),
        _ev("all-gather-done.2", 20, 5),
        _ev("tanh.4", 10, 10),
    ]
    r = overlap_from_events(events)
    assert r["collective_time_us"] == 30  # union of parent + nested done
    assert r["overlapped_time_us"] == 10
    assert 0.3 < r["overlap_fraction"] < 0.34


def test_non_hlo_events_are_ignored():
    """Python frames, runtime bookkeeping, and zero-duration markers never
    enter the attribution; an all-host trace reports fraction 0.0 without
    dividing by zero."""
    events = [
        _ev("$src/module.py:12 step", 0, 100),
        _ev("PjitFunction(step)", 0, 50),
        _ev("ThreadpoolListener::run", 0, 40),
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 1},
        _ev("dot.1", 0, 0),  # zero dur: dropped
    ]
    r = overlap_from_events(events)
    assert r["collective_time_us"] == 0
    assert r["compute_time_us"] == 0
    assert r["overlap_fraction"] == 0.0


def test_load_trace_events_reads_gzipped_chrome_traces(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [_ev("dot.1", 0, 5)]}, f)
    with gzip.open(run / "bad.trace.json.gz", "wt") as f:
        f.write("not json")  # truncated/foreign file: skipped, not fatal
    events = load_trace_events(str(tmp_path))
    assert [e["name"] for e in events] == ["dot.1"]
    assert load_trace_events(str(tmp_path / "missing")) == []


def test_capture_overlap_report_degrades_on_error(tmp_path):
    """A step_fn that raises propagates its error out of the capture —
    a zeroed report would read as a measured absence of overlap — and the
    profiler is stopped again, so the next capture can start."""
    def boom():
        raise RuntimeError("no step")

    with pytest.raises(RuntimeError, match="no step"):
        capture_overlap_report(boom, trace_dir=str(tmp_path / "t"))
    r = capture_overlap_report(lambda: None, trace_dir=str(tmp_path / "u"))
    assert r["trace_dir"] == str(tmp_path / "u")


def test_async_flags_are_verified_spellings():
    """The async fallback appends these to XLA_FLAGS; an unknown flag
    ABORTS backend init, so the list must stay exactly the spellings the
    bundled jaxlib accepts."""
    assert ASYNC_XLA_FLAGS == (
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_cpu_enable_concurrency_optimized_scheduler=true",
    )
    assert all(f.startswith("--xla_") and "=" in f for f in ASYNC_XLA_FLAGS)
