"""The compiled train step names its phases and its aggregation.

The program puts its work under fixed ``jax.named_scope`` names: each
stage's phase (``pipe.fwd.s<s>``, ``pipe.bwd.s<s>``, ``pipe.bwd_b.s<s>``,
``pipe.bwd_w.s<s>``), the loss head (``pipe.loss``), the optimizer
(``pipe.optimizer``), the executor (``pipe.exec``) and its collectives
(``pipe.wire``), and inside each layer ``gnn.transform`` and ``gnn.agg``.
The names reach each compiled instruction's ``op_name`` metadata, which a
device profile carries, so device time can be split by phase and by
aggregation. These tests read the compiled step's HLO text on the CPU: the
1F1B and zb-h1 steps on the lane substrate and the fill-drain step on the
single-device scan, each with 2 stages and 2 chunks of karate.
"""

import os
import re

import jax
import pytest

from repro.core.pipeline import GPipeConfig, make_engine
from repro.core.microbatch import make_plan
from repro.graphs import load_dataset
from repro.models.gnn.net import build_paper_gat
from repro.train import optimizer as opt_lib

STAGES = 2
# the ops that carry a step's work; each one inside the step's loop must
# sit under a pipe.* scope
WORK_OPS = ("fusion", "gather", "scatter", "dot", "dynamic-update-slice")

_HEADER = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INST = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\((.*)$")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def _parse(text):
    """``({computation: [instruction]}, entry name)`` of compiled HLO text;
    an instruction is a dict of its name, opcode, operands, called
    computations and ``op_name``."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
            continue
        m = _INST.match(line)
        if m and cur is not None:
            name, op, rest = m.groups()
            called = _CALLED.findall(rest)
            for group in _BRANCHES.findall(rest):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            found = _OP_NAME.search(rest)
            cur.append({"name": name, "op": op, "called": called,
                        "operands": _OPERAND.findall(rest.split(")", 1)[0]),
                        "op_name": found.group(1) if found else ""})
    return comps, entry


def _names(comps):
    """Each instruction's ``op_name``; one the compiler made without a name
    takes that of the nearest-root instruction it fuses, else that of its
    first named operand (a layout copy is named by what it copies)."""
    by_name = {i["name"]: i for insts in comps.values() for i in insts}
    out: dict = {}

    def resolve(n):
        if n not in out:
            inst = by_name[n]
            name = inst["op_name"]
            if not name and inst["op"] == "fusion":
                name = next((x["op_name"] for c in inst["called"]
                             for x in reversed(comps[c]) if x["op_name"]), "")
            out[n] = name
            if not name:
                out[n] = next((resolve(o) for o in inst["operands"]
                               if o in by_name and resolve(o)), "")
        return out[n]

    for n in by_name:
        resolve(n)
    return out


def _loop_ops(comps, entry):
    """The top-level instructions of the step's executor loop: the bodies
    of the entry's ``while``s under ``pipe.exec`` and the branches and calls
    they reach, fused computations left out."""
    todo = [c for i in comps[entry] if i["op"] == "while" and "pipe.exec" in i["op_name"]
            for c in i["called"]]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for inst in comps[c]:
            out.append(inst)
            if inst["op"] != "fusion":
                todo.extend(inst["called"])
    return out


@pytest.fixture(scope="module", params=["1f1b", "zb-h1", "fill_drain"])
def step(request):
    """``(schedule, op_names, parsed HLO)`` of one compiled train step."""
    g = load_dataset("karate")
    model = build_paper_gat(g.num_features, g.num_classes)
    engine = make_engine(model, GPipeConfig(
        balance=(3, 3), chunks=2, schedule=request.param, engine="compiled"))
    plan = make_plan(g, 2, strategy="halo", halo_hops=1)
    optimizer = opt_lib.adam(5e-3)
    params = engine.init_params(jax.random.PRNGKey(0))
    fn, args = engine.step_program(
        params, optimizer.init(params), plan, jax.random.PRNGKey(1), optimizer)
    comps, entry = _parse(fn.lower(*args).compile().as_text())
    op_names = {i["op_name"] for insts in comps.values() for i in insts}
    return request.param, op_names, (comps, entry)


def _bases(op_name):
    """The scope names along an ``op_name`` path, with transforms such as
    ``transpose(jvp(...))`` taken off."""
    return [re.sub(r"^(?:[\w.]+\()*(.*?)\)*$", r"\1", s) for s in op_name.split("/")]


def test_each_stage_names_its_phases(step):
    schedule, op_names, _ = step
    names = {b for n in op_names for b in _bases(n)}
    want = {f"pipe.fwd.s{s}" for s in range(STAGES)} | {"pipe.loss", "pipe.exec"}
    if schedule == "1f1b":
        want |= {f"pipe.bwd.s{s}" for s in range(STAGES)}
    if schedule == "zb-h1":
        # stage 0's input gradient is dead code: its features enter by chunk
        # id, so its B half compiles to nothing
        want |= {f"pipe.bwd_b.s{s}" for s in range(1, STAGES)}
        want |= {f"pipe.bwd_w.s{s}" for s in range(STAGES)}
    assert want <= names, sorted(want - names)


def test_aggregation_backward_and_optimizer_are_named(step):
    schedule, op_names, _ = step
    bases = {n: _bases(n) for n in op_names}
    assert any("gnn.transform" in b for b in bases.values())
    assert any("gnn.agg" in b and "transpose(" not in n for n, b in bases.items())
    assert any("gnn.agg" in b and "transpose(" in n for n, b in bases.items())
    if schedule != "fill_drain":
        # an explicit vjp per stage: the transpose wraps the layer's own scope
        assert any("transpose(jvp(gnn.agg))" in n.split("/") for n in op_names)
    assert any("pipe.optimizer" in b for b in bases.values())


def test_work_in_the_loop_sits_under_a_pipe_scope(step):
    _, _, (comps, entry) = step
    names = _names(comps)
    by_name = {i["name"]: i for insts in comps.values() for i in insts}
    ops = [i for i in _loop_ops(comps, entry) if i["op"] in WORK_OPS]
    assert len(ops) > 50
    loose = [(i["name"], names[i["name"]]) for i in ops
             if not any(b.startswith("pipe.") for b in _bases(names[i["name"]]))]
    # a literal the compiler materialises (a fusion of constants only, such
    # as an idle branch's zero fill) carries no name at all
    loose = [(n, op) for n, op in loose
             if op or any(by_name[o]["op"] != "constant" for o in by_name[n]["operands"])]
    assert not loose, loose[:10]


def test_the_compile_cache_keeps_each_programs_own_names(tmp_path, monkeypatch):
    """The entry points' persistent compile cache keys on op metadata: a
    program that differs from a cached one only by a scope name compiles
    anew and carries its own names, where a key without metadata would
    serve the cached executable and its stale names to the profiler."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import enable_compile_cache

    flags = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {f: getattr(jax.config, f) for f in flags}

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2

        return f

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compilation_cache.reset_cache()
        x = jnp.ones((8,))
        texts = [jax.jit(scoped(n)).lower(x).compile().as_text() for n in ("pipe.a", "pipe.b")]
    finally:
        for f, v in saved.items():
            jax.config.update(f, v)
        compilation_cache.reset_cache()
    assert "pipe.a" in texts[0] and "pipe.b" in texts[1]
    assert len([n for n in os.listdir(tmp_path) if n.startswith("jit_f")]) == 2


_TYPED = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]")
_SCATTER = re.compile(r" scatter\(([^)]*)\).*index_vector_dim=(\d+)")


def _scatter_rows(text):
    """Update rows of every ``scatter`` in compiled HLO text: the indices'
    element count over the index vector's width."""
    shapes, rows = {}, []
    for line in text.splitlines():
        m = _TYPED.match(line)
        if m:
            shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    for line in text.splitlines():
        m = _SCATTER.search(line)
        if m:
            idx = shapes[m.group(1).split(",")[1].strip().lstrip("%")]
            ivd = int(m.group(2))
            n = 1
            for d in idx:
                n *= d
            rows.append(n // idx[ivd] if ivd < len(idx) else n)
    return rows


def test_bucketed_gcn_gradient_scatters_only_real_slots():
    """The padded GCN's gradient over the degree-bucketed layout scatters
    one row per bucket slot and one per node (the row permutation), fewer
    than the padded layout's n * max_deg; every gather and scatter of the
    aggregation sits under ``gnn.agg``."""
    import jax.numpy as jnp

    from repro.graphs import degree_bucketed_layout, open_streamed
    from repro.graphs.partition import layout_slots
    from repro.models.gnn import layers as L

    ds = open_streamed("powerlaw-64k", num_nodes=4096, block_size=512)
    g = ds.chunk_batch(0, 4096, max_degree=32)
    b = degree_bucketed_layout(g)
    p = L.init_gcn(jax.random.PRNGKey(0), g.num_features, 16)

    def loss(p, h, graph):
        return jnp.sum(L.gcn_layer(p, graph, h) ** 2)

    def compiled(graph):
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, g.features, graph).compile().as_text()

    n, width = g.neighbors.shape
    assert sum(_scatter_rows(compiled(g))) == n * width
    text = compiled(b)
    rows = _scatter_rows(text)
    assert len(rows) == len(b.buckets) + 1, rows
    assert sum(rows) <= layout_slots(b) + n < n * width
    assert layout_slots(b) < 0.6 * n * width
    comps, _ = _parse(text)
    names = _names(comps)
    agg = [i["name"] for insts in comps.values() for i in insts
           if i["op"] in ("gather", "scatter")]
    assert agg and all("gnn.agg" in _bases(names[a]) for a in agg), [
        (a, names[a]) for a in agg]
