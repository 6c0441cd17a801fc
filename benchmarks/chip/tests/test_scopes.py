"""The scope reduction, on small synthetic traces and one real CPU trace."""

import glob
import importlib.util
import os

import pytest

import devtrace
import scopes
from conftest import HERE

STEP = "jit(step)/pipe.exec/while/body/closed_call"


@pytest.mark.parametrize("op_name,want", [
    (f"{STEP}/cond/branch_1_fun/pipe.fwd.s0/gnn.agg/gather", ("fwd", "agg", False)),
    (f"{STEP}/cond/branch_5_fun/pipe.bwd.s0/jvp(gnn.agg)/gather", ("bwd", "agg", False)),
    (f"{STEP}/cond/branch_5_fun/pipe.bwd.s0/transpose(jvp(gnn.agg))/scatter-add",
     ("bwd", "agg", True)),
    (f"{STEP}/cond/branch_6_fun/pipe.bwd.s1/gnn.transform/dot_general",
     ("bwd", "transform", False)),
    (f"{STEP}/cond/branch_6_fun/pipe.bwd.s1/pipe.loss/transpose(jvp())/neg",
     ("loss", None, True)),
    (f"{STEP}/cond/branch_9_fun/pipe.bwd_b.s1/transpose(jvp(gnn.agg))/mul",
     ("bwd_b", "agg", True)),
    (f"{STEP}/cond/branch_10_fun/pipe.bwd_w.s0/transpose(jvp(gnn.transform))/dot_general",
     ("bwd_w", "transform", True)),
    (f"{STEP}/dynamic_update_slice", ("exec", None, False)),
    (f"{STEP}/cond/branch_1_fun/broadcast_in_dim", ("exec", None, False)),
    (f"{STEP}/pipe.wire/ppermute", ("wire", None, False)),
    ("jit(step)/pipe.optimizer/sqrt", ("optimizer", None, False)),
    # the fill-drain program differentiates its forward scan: its stages'
    # forward scopes sit under a transposed executor
    ("jit(step)/transpose(jvp(pipe.exec))/while/body/checkpoint/pipe.fwd.s1/gnn.agg/mul",
     ("bwd", "agg", True)),
    ("jit(step)/div", ("unscoped", None, False)),
    ("", ("unscoped", None, False)),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def _scoped():
    # device 0, window [0, 100]: one step program [0, 60] whose loop
    # (while.1) holds a conditional holding an agg forward fusion and an agg
    # backward scatter; an exec dynamic-update-slice; a wire hop; the
    # optimizer; then the key-split program [70, 80] whose fusion.1 shares
    # a name with the step's
    step = {"while.1": f"{STEP[:-len('/while/body/closed_call')]}/while",
            "conditional.2": f"{STEP}/cond",
            "fusion.1": f"{STEP}/cond/branch_1_fun/pipe.fwd.s0/gnn.agg/gather",
            "scatter.3": f"{STEP}/cond/branch_5_fun/pipe.bwd.s0/transpose(jvp(gnn.agg))/add",
            "dynamic-update-slice.4": f"{STEP}/dynamic_update_slice",
            "collective-permute-done.5": f"{STEP}/pipe.wire/ppermute",
            "fusion.6": "jit(step)/pipe.optimizer/mul"}
    ops = [(0, 50, "while.1"), (2, 30, "conditional.2"), (4, 14, "fusion.1"),
           (16, 28, "%scatter.3 = f32[8]{0} scatter(...)"), (32, 36, "dynamic-update-slice.4"),
           (40, 44, "collective-permute-done.5"), (52, 58, "fusion.6"), (70, 80, "fusion.1")]
    host = [(0, 100, "bench.window"), (1, 2, "bench.dispatch"), (60, 62, "bench.dispatch"),
            (101, 102, "bench.dispatch")]
    return scopes.Scoped(
        trace=devtrace.Trace(devices={0: ops}, host=host),
        op_names={"jit_step": step, "jit_split": {"fusion.1": "jit(split)/threefry"}},
        modules={0: [(0, 60, "jit_step"), (70, 80, "jit_split")]},
        host=[(s, e, n, "python") for s, e, n in host])


def test_steps_are_the_dispatch_spans_inside_the_window():
    assert scopes.steps_in_window(_scoped().trace) == 2


def test_reduce_counts_self_time_per_class_and_step():
    out = scopes.reduce(_scoped())
    assert out["steps"] == 2
    s = {k: v / 1e-9 for k, v in out["scopes"].items()}  # ns per step
    # while.1 and conditional.2 are executor control flow, less their bodies
    assert s["exec"] == pytest.approx(((50 - 28 - 4 - 4) + (28 - 10 - 12) + 4) / 2)
    assert s["fwd"] == pytest.approx(10 / 2)
    assert s["bwd"] == pytest.approx(12 / 2)
    assert s["wire"] == pytest.approx(4 / 2)
    assert s["optimizer"] == pytest.approx(6 / 2)
    assert s["unscoped"] == pytest.approx(10 / 2)  # the split program's fusion.1
    assert s["agg_fwd"] == pytest.approx(10 / 2) and s["agg_bwd"] == pytest.approx(12 / 2)
    assert s["op_s"] == pytest.approx(sum(s[c] for c in scopes.CLASSES))
    assert s["op_s"] == pytest.approx((50 + 6 + 10) / 2)  # every op's time, once
    assert scopes.agg_s(out["scopes"]) == pytest.approx(22 / 2 * 1e-9)
    assert scopes.exec_s(out["scopes"]) == pytest.approx((s["exec"] + 2) * 1e-9)


def test_reduce_without_steps_or_devices_is_none():
    sc = _scoped()
    sc.trace.host = [(0, 100, "bench.window")]
    assert scopes.reduce(sc) is None
    sc = _scoped()
    sc.trace.devices = {}
    assert scopes.reduce(sc) is None


def test_idle_by_host_labels_gaps_by_each_threads_innermost_event():
    sc = _scoped()
    # device idle: [50, 52], [58, 70] and [80, 100]. At 64, the middle of
    # the second gap, the python thread waits and a runtime thread reads a
    # flag inside an execute; no thread has an event at 51 or at 90
    sc.host = [(0, 100, "bench.window", "python"), (60, 70, "bench.wait", "python"),
               (62, 75, "Execute", "runtime"), (64, 68, "ReadSyncFlag", "runtime")]
    gaps = dict(scopes.idle_by_host(sc))
    assert gaps["bench.wait"] == pytest.approx(12e-9)
    assert gaps["ReadSyncFlag"] == pytest.approx(12e-9)
    assert gaps["no host event"] == pytest.approx(22e-9)
    assert "Execute" not in gaps and "bench.window" not in gaps


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _inst(iid, name, opcode, op_name="", operands=(), called=()):
    body = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name:
        body += _field(7, _field(2, op_name))
    body += b"".join(_field(36, o) for o in operands)
    body += b"".join(_field(38, c) for c in called)
    return body


def test_module_op_names_name_what_the_compiler_made():
    fused = _field(5, 1) + b"".join(_field(2, i) for i in (
        _inst(1, "param_0", "parameter"),
        _inst(2, "gather.1", "gather", f"{STEP}/pipe.fwd.s0/gnn.agg/gather", [1])))
    body = _field(5, 3) + b"".join(_field(2, i) for i in (
        _inst(7, "param_1", "parameter"),
        _inst(8, "dynamic-update-slice.5", "dynamic-update-slice", operands=[7])))
    entry = _field(5, 2) + b"".join(_field(2, i) for i in (
        _inst(3, "p", "parameter"),
        _inst(4, "fusion.9", "fusion", operands=[3], called=[1]),
        _inst(5, "copy.7", "copy", operands=[4]),
        _inst(6, "add.2", "add", "jit(step)/pipe.optimizer/add", [5]),
        _inst(9, "while.3", "while", "jit(step)/pipe.exec/while", [3], [3])))
    module = (_field(1, "jit_step") + _field(3, fused) + _field(3, body)
              + _field(3, entry))
    name, names = scopes.module_op_names(module)
    assert name == "jit_step"
    agg = f"{STEP}/pipe.fwd.s0/gnn.agg/gather"
    # a fusion without a name takes its fused root's; a copy, its operand's;
    # an op of a loop the compiler wrote, the loop's
    assert names["fusion.9"] == agg and names["copy.7"] == agg
    assert names["dynamic-update-slice.5"] == "jit(step)/pipe.exec/while"
    assert names["add.2"] == "jit(step)/pipe.optimizer/add" and names["p"] == ""


def test_a_cpu_trace_holds_the_programs_op_names(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("pipe.fwd.s0"), jax.named_scope("gnn.agg"):
            return jnp.sin(x) @ x

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        names = scopes.hlo_op_names(fh.read())
    assert any("gnn.agg" in op.split("/") and "pipe.fwd.s0" in op.split("/")
               for op in names["jit_f"].values())
    sc = scopes.load(str(tmp_path))
    assert any(n == devtrace.WINDOW_SPAN for _, _, n, _ in sc.host)
    assert scopes.reduce(sc) is None  # the CPU has no device plane


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", ["agg_s.train", "exec_s.train"])
def test_readers_return_none_without_scopes(name):
    read = _reader(name)
    assert read({"kind": "train"}) is None
    assert read({"kind": "train", "trace": {"shares": {}}}) is None
    reduced = scopes.reduce(_scoped())
    value = read({"kind": "train", "trace": {"scopes": reduced}})
    fn = scopes.agg_s if name.startswith("agg") else scopes.exec_s
    assert value == pytest.approx(fn(reduced["scopes"]))


def test_runner_drives_a_cell_and_needs_a_tpu():
    import subprocess
    import sys

    from conftest import ROOT, args

    out = scopes.run(args("gat-cora-paper", seconds=1.0), require_tpu=False)
    assert set(out["windows"]) == {"untraced", "traced"}
    assert all(w["steps"] >= 1 for w in out["windows"].values())
    assert "scopes" not in out  # the CPU has no device plane to reduce
    r = subprocess.run([sys.executable, "benchmarks/chip/scopes.py", "--workload",
                        "gat-cora-paper", "--seed", "3", "--seconds", "1"], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 2 and "no TPU" in r.stderr and not r.stdout
