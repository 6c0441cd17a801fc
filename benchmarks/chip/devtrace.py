"""From a ``jax.profiler`` trace to the benchmark's device numbers.

``load(trace_dir)`` reads the ``.xplane.pb`` the profiler wrote and keeps two
things: for each device plane (``/device:TPU:<n>``), the intervals of the
operations on its ``XLA Ops`` line; and the host spans the benchmark itself
opened (``jax.profiler.TraceAnnotation`` names starting ``bench.``). The
reductions below work on that plain structure, so a test can hand them a
small synthetic trace.

The traced window is the host span ``bench.window``. Busy time of a device
is the union of its op intervals inside the window; idle share is one minus
busy over the window. A collective is exposed where it runs on a device
while no other op does.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

# substrings naming the XLA collectives in device op names
COLLECTIVE_MARKERS = ("collective-permute", "all-reduce", "all-gather",
                      "reduce-scatter", "all-to-all")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    """Device op intervals ``{device: [(start_ns, end_ns, name)]}`` and the
    benchmark's host spans ``[(start_ns, end_ns, name)]``."""

    devices: dict
    host: list

    def window(self) -> tuple[float, float]:
        spans = [(s, e) for s, e, n in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return min(s for s, _ in spans), max(e for _, e in spans)


def load(trace_dir: str) -> Trace:
    """The device ops and benchmark spans of the one ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events)
            elif not m:
                host.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices=devices, host=host)


def _is_collective(name: str) -> bool:
    low = name.lower()
    return any(k in low for k in COLLECTIVE_MARKERS)


def union(intervals, lo, hi) -> list:
    """Disjoint sorted union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _minus(a, b) -> float:
    """Length of the disjoint intervals ``a`` not covered by disjoint ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        covered = 0.0
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
        total += (e - s) - covered
    return total


def device_shares(tr: Trace) -> dict:
    """Per device: ``busy_s``, ``idle_share`` and ``collective_exposed_share``
    inside the window, and the window's length ``window_s``."""
    lo, hi = tr.window()
    span = hi - lo
    out = {}
    for dev, ops in sorted(tr.devices.items()):
        busy = union([(s, e) for s, e, _ in ops], lo, hi)
        compute = union([(s, e) for s, e, n in ops if not _is_collective(n)], lo, hi)
        coll = union([(s, e) for s, e, n in ops if _is_collective(n)], lo, hi)
        out[dev] = {
            "busy_s": _length(busy) * 1e-9,
            "idle_share": 1.0 - _length(busy) / span,
            "collective_exposed_share": _minus(coll, compute) / span,
        }
    return {"window_s": span * 1e-9, "devices": out}


def short_name(op: str) -> str:
    """``"%fusion.30 = f32[65536,256]{1,0:T(8,128)} fusion(...)"`` ->
    ``"fusion.30 f32[65536,256]"``; a plain name stays as it is."""
    if " = " not in op:
        return op
    name, rest = op.split(" = ", 1)
    return f"{name.lstrip('%')} {re.sub(r'{[^}]*}', '', rest.split(' ', 1)[0])}"


def self_times(ops, lo, hi) -> dict:
    """Seconds of each op inside ``[lo, hi]`` less the ops nested in it (a
    loop or a conditional holds its body's ops), by short name."""
    total: dict = {}
    stack: list = []  # [end, name] of the ops that enclose the current one
    for s, e, n in sorted(ops, key=lambda x: (x[0], -x[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            total[parent] = total.get(parent, 0.0) - (e - s) * 1e-9
        name = short_name(n)
        total[name] = total.get(name, 0.0) + (e - s) * 1e-9
        stack.append([e, name])
    return total


def top_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` device operations with the most self time inside the
    window, summed over devices: ``[[name, seconds], ...]``."""
    lo, hi = tr.window()
    total: dict = {}
    for ops in tr.devices.values():
        for n, t in self_times(ops, lo, hi).items():
            total[n] = total.get(n, 0.0) + t
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """Device idle time inside the window, summed over devices and labelled
    by the benchmark span the host was in at each gap's middle (``between
    spans`` where it was in none): ``[[label, seconds], ...]``, the ``k``
    largest. The spans other than the window do not overlap."""
    lo, hi = tr.window()
    spans = sorted((s, e, n) for s, e, n in tr.host if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    total: dict = {}
    for ops in tr.devices.values():
        busy = union([(s, e) for s, e, _ in ops], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            label = spans[i][2] if i >= 0 and spans[i][1] >= mid else "between spans"
            total[label] = total.get(label, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]
