"""Tick-level communication/compute overlap profiler for the pipeline.

The double-buffered wire dataflow (``GPipeConfig.overlap`` — see the
wire-parity rule in ``repro.core.spmd_pipe``) only removes the DATA
dependency that pins each tick's ``ppermute`` pair to the critical path;
whether the runtime actually runs the collective under the neighbouring
compute is XLA's call. This module builds the proof the ISSUE's tentpole
asks for: capture a ``jax.profiler`` trace of one step, attribute per-op
time to collective vs compute, and report the fraction of collective time
that was hidden under same-device compute — the way
``roofline.sparse_stage_report`` turns kernel timings into evidence.

``capture_overlap_report(step_fn)`` is the entry point (fig3's overlap
rows write its dict to ``overlap_report.json``). A traced fraction of ~0
is itself a finding — single-threaded device executors (host-platform CPU
rings) cannot overlap by construction — so ``apply_async_overlap_flags``
offers the documented fallback: best-effort XLA latency-hiding-scheduler
flags, applied through ``XLA_FLAGS`` before the backend initializes
(``--overlap async`` in the CLI).

Everything here is stdlib + jax: the profiler writes gzipped chrome traces
under ``<dir>/plugins/profile/<run>/``, which ``load_trace_events`` parses
directly — no tensorboard/tensorflow dependency.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import tempfile
import warnings
from typing import Callable

# substrings identifying collective ops in XLA trace event names (HLO names
# like "collective-permute.1", "all-gather-start.2")
COLLECTIVE_MARKERS = (
    "collective-permute",
    "all-gather",
    "all-reduce",
    "all-to-all",
    "reduce-scatter",
)

# XLA_FLAGS requesting the latency-hiding / concurrency-optimized
# schedulers (both accepted by current jaxlib; unknown flags would abort
# backend init, so keep this list to verified spellings)
ASYNC_XLA_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_cpu_enable_concurrency_optimized_scheduler=true",
)


# HLO instruction names as they appear in device lanes: "dot.2", "tanh.1",
# "collective-permute.1". Python-frame events ("$module.py:123 fn"), runtime
# bookkeeping ("ThreadpoolListener::...", "DevicePut", "PjitFunction(step)")
# all fail this shape.
_HLO_NAME = re.compile(r"^[a-z][a-z0-9_.-]*\.\d+$")


def _is_xla_op(name: str) -> bool:
    """True for device-lane HLO-op trace events — filters the profiler's
    Python-frame events and host runtime bookkeeping out of the
    attribution."""
    return bool(_HLO_NAME.match(name))


def _is_collective(name: str) -> bool:
    """True when an XLA op name is one of the ring/mesh collectives."""
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKERS)


def load_trace_events(trace_dir: str) -> list:
    """All chrome-trace events the profiler wrote under ``trace_dir``
    (searched recursively for ``*.trace.json.gz``; empty list when the
    profiler produced nothing — callers degrade, not crash)."""
    events: list = []
    for path in sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True)
    ):
        try:
            with gzip.open(path, "rt") as f:
                events.extend(json.load(f).get("traceEvents", []))
        except (OSError, ValueError):  # truncated/foreign file: skip it
            continue
    return events


def _union(intervals: list) -> list:
    """Union of (start, end) intervals as a sorted disjoint list."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _intersect_len(intervals: list, union: list) -> float:
    """Total length of ``intervals`` covered by the disjoint ``union``."""
    total = 0.0
    for s, e in intervals:
        for us, ue in union:
            if ue <= s:
                continue
            if us >= e:
                break
            total += min(e, ue) - max(s, us)
    return total


def overlap_from_events(events: list) -> dict:
    """Attribute trace time to collective vs compute ops and measure how
    much collective time ran UNDER same-device compute.

    Events are grouped per (pid, tid) — each device executor is one trace
    thread — because hiding a collective means that device doing its own
    useful work meanwhile; cross-device concurrency is just the pipeline
    running. Only LEAF events count as compute: chrome-trace lanes nest
    control-flow containers (a scan's ``while.N`` spans every tick
    including the collectives inside it) around the real ops, and counting
    a container would report its collectives as 100% hidden under
    themselves. Returns total microseconds per class, the overlapped
    microseconds, and ``overlap_fraction`` (0.0 when no collective ran —
    the gate never divides by zero)."""
    lanes: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or not ev.get("dur"):
            continue
        name = ev.get("name", "")
        if not _is_xla_op(name):
            continue
        lane = lanes.setdefault((ev.get("pid"), ev.get("tid")), [])
        start = float(ev.get("ts", 0.0))
        lane.append((start, start + float(ev["dur"]), _is_collective(name)))

    coll_time = comp_time = hidden = 0.0
    n_coll = n_comp = 0
    for spans in lanes.values():
        # properly nested flame lanes: an event that starts before its
        # successor's start but ends after it contains it — drop such
        # containers, keep leaves
        spans.sort(key=lambda x: (x[0], -x[1]))
        coll, comp = [], []
        for i, (s, e, is_coll) in enumerate(spans):
            is_container = i + 1 < len(spans) and spans[i + 1][0] < e
            if is_coll:
                coll.append((s, e))  # a collective counts even as a parent
            elif not is_container:
                comp.append((s, e))
        n_coll += len(coll)
        n_comp += len(comp)
        coll_union = _union(coll)
        coll_time += sum(e - s for s, e in coll_union)
        comp_time += sum(e - s for s, e in _union(comp))
        hidden += _intersect_len(coll_union, _union(comp))
    return {
        "collective_time_us": coll_time,
        "compute_time_us": comp_time,
        "overlapped_time_us": hidden,
        "overlap_fraction": (hidden / coll_time) if coll_time > 0 else 0.0,
        "num_collective_events": n_coll,
        "num_compute_events": n_comp,
    }


def capture_overlap_report(step_fn: Callable[[], None], *, trace_dir: str | None = None) -> dict:
    """Trace ONE call of ``step_fn`` and return its overlap report.

    ``step_fn`` should run exactly one already-compiled step and block on
    the result (tracing a compile would attribute tracing-time Python to
    the step). The profiler's output stays on disk at ``trace_dir``
    (a fresh temp dir by default) — CI uploads it next to the JSON report.
    A profiler that fails raises: zeroed metrics would read as a measured
    absence of overlap."""
    import jax

    out_dir = trace_dir or tempfile.mkdtemp(prefix="overlap_trace_")
    jax.profiler.start_trace(out_dir)
    try:
        step_fn()
    finally:
        jax.profiler.stop_trace()
    report = overlap_from_events(load_trace_events(out_dir))
    report["trace_dir"] = out_dir
    return report


def apply_async_overlap_flags() -> bool:
    """Best-effort ``--overlap async`` fallback: append the latency-hiding
    scheduler flags to ``XLA_FLAGS`` so the compiler is ASKED to move
    collectives off the critical path even where the double-buffered
    dataflow alone is not enough. Returns True when the flags are in place
    before the backend initialized (they only take effect then); False —
    with a warning — when jax already built its backends, in which case the
    caller keeps the double-buffered dataflow and reports overlap as
    measured."""
    current = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in ASYNC_XLA_FLAGS if f not in current]
    if missing:
        os.environ["XLA_FLAGS"] = " ".join([current] + missing).strip()
    import jax._src.xla_bridge as xb

    if xb.backends_are_initialized():
        warnings.warn(
            "overlap=async: XLA backends already initialized; latency-hiding "
            "flags will not apply to this process — running with the "
            "double-buffered dataflow only",
            RuntimeWarning,
            stacklevel=2,
        )
        return False
    return True
