"""Device seconds per step by the program's named scopes, from a profiler trace.

The program names its work with ``jax.named_scope``: each pipeline stage's
phase (``pipe.fwd.s<s>``, ``pipe.bwd.s<s>``, ``pipe.bwd_b.s<s>``,
``pipe.bwd_w.s<s>``), the loss head (``pipe.loss``), the optimizer
(``pipe.optimizer``), the executor around them (``pipe.exec``) and its
collectives (``pipe.wire``); inside each layer the feature matmuls
(``gnn.transform``) and the neighbourhood aggregation (``gnn.agg``). The
names reach each compiled op's ``op_name``, a path such as
``jit(step)/pipe.exec/while/body/pipe.bwd.s0/transpose(jvp(gnn.agg))/mul``;
backward ops carry ``transpose(...)`` around a segment.

An op's ``op_name`` comes from the trace alone. The device's op events
carry none (on a TPU v5e their stats are offsets and durations), but the
profiler keeps each program it ran in the ``/host:metadata`` plane (the
``Hlo Proto`` stat), and an op event is named after its instruction. An op
the compiler made without a name of its own takes that of the nearest-root
op it fuses, else that of its first named operand (a layout copy is charged
to what it copies), else that of the instruction that calls its
computation (a loop the compiler wrote inside a branch, to what runs the
branch).

Each op's device self time (``devtrace.self_times``: nested ops counted
once) inside the ``bench.window`` span goes to one class of ``CLASSES``, by
the innermost ``pipe.*`` segment of its path (``fwd`` under a transpose is
``bwd``: the fill-drain program differentiates its forward scan); ops with
no ``pipe.*`` and no ``gnn.*`` segment are ``unscoped``. Across the classes,
ops under ``gnn.agg`` and ``gnn.transform`` are counted again, forward and
backward apart. Seconds are per step (``bench.dispatch`` spans inside the
window) and per chip (averaged over the devices).

``python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> --seconds
<s>`` runs a training cell's program as ``bench.py`` does, then an untraced
window of ``--seconds`` and a traced one of the traffic's ``trace_seconds``,
and prints the reduction with both windows' median step as one JSON line.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys

import devtrace

CLASSES = ("fwd", "bwd", "bwd_b", "bwd_w", "loss", "optimizer", "exec", "wire", "unscoped")
STEP_SPAN = "bench.dispatch"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_SEGMENT = re.compile(r"^((?:[\w.]+\()*)(.*?)\)*$")
_MODULE_ID = re.compile(r"\(\d+\)$")


# ------------------------------------------------------- protobuf wire --


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes) -> dict:
    """``{field number: [values]}`` of one protobuf message: varints as
    ints, length-delimited fields as bytes."""
    out: dict = {}
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        out.setdefault(key >> 3, []).append(value)
    return out


def _ints(values) -> list:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _text(fields: dict, number: int) -> str:
    return fields.get(number, [b""])[0].decode()


def module_op_names(hlo_module: bytes) -> tuple[str, dict]:
    """``(module name, {instruction name: op_name})`` of a serialized
    ``HloModuleProto``, every instruction of every computation named by the
    rule of the module docstring."""
    # HloModuleProto: name 1, computations 3. HloComputationProto:
    # instructions 2 (operands before users), id 5. HloInstructionProto:
    # name 1, opcode 2, metadata 7 (OpMetadata: op_name 2), id 35,
    # operand_ids 36, called_computation_ids 38.
    module = _fields(hlo_module)
    comps, comp_of, callers = {}, {}, {}
    for raw in module.get(3, []):
        c = _fields(raw)
        cid = _ints(c.get(5, [0]))[0]
        comps[cid] = [_fields(x) for x in c.get(2, [])]
        for inst in comps[cid]:
            comp_of[_ints(inst.get(35, [0]))[0]] = cid
    own, local = {}, {}
    for cid, insts in comps.items():
        for inst in insts:
            iid = _ints(inst.get(35, [0]))[0]
            own[iid] = _text(_fields(inst.get(7, [b""])[0]), 2)
            for called in _ints(inst.get(38, [])):
                callers.setdefault(called, iid)
    for cid, insts in comps.items():
        for inst in insts:
            iid = _ints(inst.get(35, [0]))[0]
            name = own[iid]
            if not name and _text(inst, 2) == "fusion":
                fused = [own[_ints(x.get(35, [0]))[0]]
                         for c in _ints(inst.get(38, [])) for x in comps.get(c, [])]
                name = next((n for n in reversed(fused) if n), "")
            if not name:
                name = next((local[o] for o in _ints(inst.get(36, [])) if local.get(o)), "")
            local[iid] = name
    final: dict = {}

    def resolve(iid):
        if iid not in final:
            caller = callers.get(comp_of[iid])
            final[iid] = local[iid] or (resolve(caller) if caller is not None else "")
        return final[iid]

    names = {}
    for insts in comps.values():
        for inst in insts:
            names[_text(inst, 1)] = resolve(_ints(inst.get(35, [0]))[0])
    return _text(module, 1), names


def hlo_op_names(xplane: bytes) -> dict:
    """``{module name: {instruction: op_name}}`` of every program the
    serialized ``XSpace`` keeps in its ``/host:metadata`` plane."""
    # XSpace: planes 1. XPlane: name 2, event_metadata 4 (map entry: key 1,
    # value 2), stat_metadata 5. XEventMetadata: stats 5. XStat:
    # metadata_id 1, bytes_value 6. XStatMetadata: name 2. HloProto:
    # hlo_module 1.
    out = {}
    for raw in _fields(xplane).get(1, []):
        plane = _fields(raw)
        if _text(plane, 2) != "/host:metadata":
            continue
        stat_names = {}
        for entry in plane.get(5, []):
            e = _fields(entry)
            stat_names[_ints(e.get(1, [0]))[0]] = _text(_fields(e.get(2, [b""])[0]), 2)
        for entry in plane.get(4, []):
            meta = _fields(_fields(entry).get(2, [b""])[0])
            for stat in meta.get(5, []):
                s = _fields(stat)
                if stat_names.get(_ints(s.get(1, [0]))[0]) == "Hlo Proto" and 6 in s:
                    name, ops = module_op_names(_fields(s[6][0]).get(1, [b""])[0])
                    out[name] = ops
    return out


# ------------------------------------------------------------- classes --


def classify(op_name: str) -> tuple[str, str | None, bool]:
    """``(class, layer part, backward)`` of an op's ``op_name``: the class
    from ``CLASSES``; ``"agg"`` or ``"transform"`` where a segment is
    ``gnn.agg`` (first) or ``gnn.transform``, else None; backward where a
    segment carries ``transpose(``."""
    bases, backward = [], False
    for seg in op_name.split("/"):
        m = _SEGMENT.match(seg)
        backward = backward or "transpose(" in m.group(1)
        bases.append(m.group(2))
    part = "agg" if "gnn.agg" in bases else "transform" if "gnn.transform" in bases else None
    pipes = [b for b in bases if b.startswith("pipe.")]
    if pipes:
        words = pipes[-1].split(".")
        cls = words[1]
        if cls == "fwd" and backward:
            cls = "bwd"
    elif part is not None:
        cls = "bwd" if backward else "fwd"
    else:
        cls = "unscoped"
    if cls not in CLASSES:
        raise ValueError(f"unknown scope {pipes[-1]!r} in {op_name!r}")
    return cls, part, backward


# ---------------------------------------------------------------- trace --


@dataclasses.dataclass
class Scoped:
    """A ``devtrace.Trace`` with what the scopes need besides: each
    instruction's ``op_name`` (``{module: {instruction: op_name}}``), each
    device's ``XLA Modules`` intervals ``{device: [(start, end, module)]}``,
    and every host event ``[(start, end, name, thread)]``."""

    trace: devtrace.Trace
    op_names: dict
    modules: dict
    host: list


def load(trace_dir: str) -> Scoped:
    """The scopes' view of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    modules, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = devtrace._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, _MODULE_ID.sub("", e.name)) for e in line.events)
            elif plane.name == HOST_PLANE:
                host.extend((e.start_ns, e.end_ns, e.name, line.name) for e in line.events)
    return Scoped(trace=devtrace.load(trace_dir), op_names=hlo_op_names(raw),
                  modules=modules, host=host)


def steps_in_window(tr: devtrace.Trace) -> int:
    """The steps dispatched inside the window: its ``bench.dispatch`` spans."""
    lo, hi = tr.window()
    return sum(1 for s, e, n in tr.host if n == STEP_SPAN and lo <= s and e <= hi)


def _by_module(ops, intervals) -> dict:
    """``{module: ops}``: each op under the ``XLA Modules`` interval that
    holds its start (None where none does)."""
    intervals = sorted(intervals)
    starts = [s for s, _, _ in intervals]
    out: dict = {}
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        module = intervals[i][2] if i >= 0 and intervals[i][1] >= op[1] else None
        out.setdefault(module, []).append(op)
    return out


def reduce(sc: Scoped) -> dict | None:
    """Seconds per step and chip of each class and layer part (keys of
    ``CLASSES``, ``agg_fwd``, ``agg_bwd``, ``transform_fwd``,
    ``transform_bwd``), the op self time they share (``op_s``), and the
    steps they are over; None where the window holds no step or no device."""
    tr = sc.trace
    steps = steps_in_window(tr)
    if not steps or not tr.devices:
        return None
    lo, hi = tr.window()
    total = dict.fromkeys(CLASSES + ("agg_fwd", "agg_bwd", "transform_fwd",
                                     "transform_bwd"), 0.0)
    for dev, ops in tr.devices.items():
        for module, part_ops in _by_module(ops, sc.modules.get(dev, [])).items():
            names = sc.op_names.get(module)
            if names is None:
                names = {k: v for m in sc.op_names.values() for k, v in m.items()}
            for op, t in devtrace.self_times(part_ops, lo, hi).items():
                inst = op.split(" ")[0]
                cls, part, backward = classify(names.get(inst, ""))
                total[cls] += t
                if part:
                    total[f"{part}_{'bwd' if backward else 'fwd'}"] += t
    scale = 1.0 / (steps * len(tr.devices))
    out = {k: v * scale for k, v in total.items()}
    out["op_s"] = sum(out[c] for c in CLASSES)
    return {"steps": steps, "scopes": out}


def agg_s(scopes: dict) -> float:
    """Seconds per step and chip of the ops under ``gnn.agg``."""
    return scopes["agg_fwd"] + scopes["agg_bwd"]


def exec_s(scopes: dict) -> float:
    """Seconds per step and chip of the executor: ``pipe.exec`` and
    ``pipe.wire`` innermost."""
    return scopes["exec"] + scopes["wire"]


# --------------------------------------------------------- host idling --


def idle_by_host(sc: Scoped, k: int = 10) -> list:
    """Device idle time inside the window, summed over devices and labelled
    by what the host was doing at each gap's middle: the innermost host
    event covering it on each thread (a gap counts once for each label it
    gets, so labels overlap; ``no host event`` where no thread had one).
    ``[[label, seconds], ...]``, the ``k`` largest."""
    tr = sc.trace
    lo, hi = tr.window()
    threads: dict = {}
    for s, e, n, line in sc.host:
        if n != devtrace.WINDOW_SPAN:
            threads.setdefault(line, []).append((s, e, n))
    index = []
    for events in threads.values():
        events.sort()
        reach, top = [], float("-inf")
        for _, e, _ in events:
            top = max(top, e)
            reach.append(top)
        index.append(([s for s, _, _ in events], reach, events))
    total: dict = {}
    for ops in tr.devices.values():
        busy = devtrace.union([(s, e) for s, e, _ in ops], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            labels = set()
            for starts, reach, events in index:
                i = bisect.bisect_right(starts, mid) - 1
                while i >= 0 and reach[i] >= mid:
                    if events[i][1] >= mid:
                        labels.add(events[i][2])
                        break
                    i -= 1
            for label in labels or {"no host event"}:
                total[label] = total.get(label, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


# ---------------------------------------------------------------- runner --


def run(args, *, require_tpu: bool = True, traffic: dict | None = None) -> dict:
    """The cell's program driven as ``bench.py`` drives it, an untraced
    window of ``args.seconds``, then a traced one; returns the reduction,
    ``idle_by_host``, and both windows' steps and median step."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    import bench
    import models
    import train_cell

    cell = bench.find_cell(bench.load_json(bench.ROOT, "BENCHMARK.json"), args.workload)
    cfg = models.load_config(cell["config"])
    if traffic is None:
        traffic = bench.load_json(bench.HERE, "workloads", f"{cell['traffic']}.json")
    device = bench.device_facts(cell["chips"], require_tpu)
    bench.configure_jax(cfg)
    tc = train_cell.TrainCell(cfg, traffic)
    state, _, _ = tc.first_steps(args.seed, traffic["check_steps"])
    plain = tc.window(state, args.seconds)
    tmp = tempfile.mkdtemp(prefix="scopes-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                traced = tc.window(state, traffic["trace_seconds"], annotate=True)
        finally:
            jax.profiler.stop_trace()
        sc = load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "windows": {name: {"steps": w["steps"],
                              "step_median_s": float(np.median(w["step_seconds"]))}
                       for name, w in (("untraced", plain), ("traced", traced))}}
    reduced = reduce(sc)
    if reduced is not None:
        out.update(reduced, agg_s=agg_s(reduced["scopes"]), exec_s=exec_s(reduced["scopes"]),
                   idle_by_host=idle_by_host(sc))
    return out


def main() -> int:
    import json

    import bench

    args = bench.build_parser().parse_args()
    try:
        out = run(args)
    except bench.NoChip as exc:
        sys.stderr.write(f"{exc}\n")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
