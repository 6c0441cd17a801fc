"""The self-tuning planner (``--auto``): predicted step times validated
against an abstract-machine replay of each schedule's timeline, the
argmin's documented tie-break order, budget truncation, memory pruning,
the cached profiler's sidecar roundtrip, and the ``--auto --dry-run``
candidate table through ``run_gnn``."""

import json
import types

import jax
import pytest

from repro.core.autotune import (
    DEFAULT_CHUNK_COUNTS,
    PLAN_SCHEDULES,
    PipelinePlan,
    PlanConstraints,
    plan_pipeline,
)
from repro.core.cli import PipelineCLIConfig
from repro.core.costmodel import (
    LayerCosts,
    _PROFILE_CACHE,
    cached_profile_layer_costs,
    profile_fingerprint,
    uniform_balance,
)
from repro.core.pipeline import GPipeConfig, make_engine
from repro.core.schedule import get_schedule
from repro.graphs import load_dataset
from repro.launch.train import run_gnn
from repro.models.gnn.net import build_paper_gat


def _costs(fwd, scale_b=1.0, scale_w=1.0):
    return LayerCosts(
        names=tuple(f"l{i}" for i in range(len(fwd))),
        fwd=tuple(fwd),
        bwd=tuple(f * (scale_b + scale_w) for f in fwd),
        bwd_b=tuple(f * scale_b for f in fwd),
        bwd_w=tuple(f * scale_w for f in fwd),
    )


def _uniform_costs_by_chunks(n_layers=6, chunk_counts=DEFAULT_CHUNK_COUNTS):
    """Shape-invariant synthetic costs for every candidate chunk count —
    the injection path that lets the planner run without a graph."""
    c = _costs([1e-3] * n_layers)
    return {C: c for C in chunk_counts}


def _stub_model(n_layers=6):
    """plan_pipeline only touches ``model.layers`` when costs are injected
    and params are supplied."""
    return types.SimpleNamespace(
        layers=[types.SimpleNamespace(name=f"l{i}") for i in range(n_layers)]
    )


# ------------------------------------- predicted time vs abstract machine --


def _replay(sched, S, C, cost):
    """Abstract-machine replay of a schedule's timeline: execute the work
    items in tick order, each starting when its dependencies are done AND
    its device is free, taking ``cost[phase][stage]`` time. The makespan of
    this machine is what ``predicted_step_time`` models."""
    done, free = {}, {}
    for it in sched.timeline(S, C):
        deps = []
        if it.phase == "fwd" and it.stage > 0:
            deps.append((it.stage - 1, it.chunk, "fwd"))
        if it.phase in ("bwd", "bwd_b"):
            deps.append((it.stage, it.chunk, "fwd"))
            if it.stage < S - 1:
                deps.append((it.stage + 1, it.chunk, it.phase))
        if it.phase == "bwd_w":
            deps.append((it.stage, it.chunk, "bwd_b"))
        start = max([free.get(it.device, 0.0)] + [done[d] for d in deps if d in done])
        end = start + cost[it.phase][it.stage]
        done[(it.stage, it.chunk, it.phase)] = end
        free[it.device] = end
    return max(done.values())


SCHED_MATRIX = [  # (name, get_schedule kwargs, split B/W backward?)
    ("fill_drain", {}, False),
    ("1f1b", {}, False),
    ("interleaved", {"num_devices": 2}, False),
    ("zb-h1", {}, True),
    ("zb-v", {"num_devices": 2}, True),
]


@pytest.mark.parametrize("name,kw,split", SCHED_MATRIX)
@pytest.mark.parametrize("S,C", [(4, 4), (4, 8), (6, 4), (4, 2)])
def test_predicted_step_time_equals_tick_count_unit_costs(name, kw, split, S, C):
    """With unit per-stage costs every schedule's predicted makespan is
    EXACTLY its timeline's tick count — the prediction layer and the
    abstract machine agree on the schedule's own currency (ticks), for the
    fused schedules and both zero-bubble ones (B and W each one tick)."""
    sched = get_schedule(name, **kw)
    try:
        sched.timeline(S, C)
    except ValueError:
        pytest.skip(f"{name} rejects S={S},C={C}")
    if split:
        pred = sched.predicted_step_time(
            S, C, stage_fwd_costs=[1.0] * S,
            stage_bwd_b_costs=[1.0] * S, stage_bwd_w_costs=[1.0] * S,
        )
        cost = {"fwd": [1.0] * S, "bwd_b": [1.0] * S, "bwd_w": [1.0] * S}
    else:
        pred = sched.predicted_step_time(
            S, C, stage_fwd_costs=[1.0] * S, stage_bwd_costs=[1.0] * S
        )
        cost = {"fwd": [1.0] * S, "bwd": [1.0] * S}
    assert pred == sched.ticks(S, C), (name, S, C)
    assert pred == _replay(sched, S, C, cost), (name, S, C)


@pytest.mark.parametrize(
    "name,kw",
    [("fill_drain", {}), ("interleaved", {"num_devices": 2}), ("zb-h1", {})],
)
def test_predicted_step_time_equals_replay_skewed_vectors(name, kw):
    """Per-stage cost vectors: for the tick-exact schedules the weighted
    makespan equals the abstract machine's replay of the same timeline with
    per-(stage, phase) costs — not just a bound."""
    S, C = 4, 4
    sched = get_schedule(name, **kw)
    f = [0.7, 0.1, 0.1, 0.1]
    bb = [0.9, 0.15, 0.15, 0.2]
    bw = [0.5, 0.05, 0.05, 0.3]
    if name == "zb-h1":
        pred = sched.predicted_step_time(
            S, C, stage_fwd_costs=f, stage_bwd_b_costs=bb, stage_bwd_w_costs=bw
        )
        rep = _replay(sched, S, C, {"fwd": f, "bwd_b": bb, "bwd_w": bw})
    else:
        fused = [x + y for x, y in zip(bb, bw)]
        pred = sched.predicted_step_time(S, C, stage_fwd_costs=f, stage_bwd_costs=fused)
        rep = _replay(sched, S, C, {"fwd": f, "bwd": fused})
    assert abs(pred - rep) < 1e-9, (name, pred, rep)


@pytest.mark.parametrize("S,C,D", [(4, 4, 2), (4, 2, 2), (6, 6, 3), (4, 8, 2)])
def test_zb_v_predicted_bounded_by_replay_and_device_work(S, C, D):
    """zb-v's prediction re-runs the cost-aware greedy, which may ORDER ops
    differently than the unit-cost timeline — so skewed-cost equality with
    the frozen timeline is not owed. What is owed: the prediction is a
    valid execution (>= the per-device total-work lower bound) and never
    worse than naively replaying the unit-cost order with the true
    costs."""
    sched = get_schedule("zb-v", num_devices=D)
    f = [0.1 + 0.15 * (s % 3) for s in range(S)]
    bb = [0.2 + 0.1 * ((s + 1) % 3) for s in range(S)]
    bw = [0.05 + 0.1 * (s % 2) for s in range(S)]
    pred = sched.predicted_step_time(
        S, C, stage_fwd_costs=f, stage_bwd_b_costs=bb, stage_bwd_w_costs=bw
    )
    rep = _replay(sched, S, C, {"fwd": f, "bwd_b": bb, "bwd_w": bw})
    per_dev = [0.0] * D
    for s in range(S):
        per_dev[s % D] += C * (f[s] + bb[s] + bw[s])
    assert pred >= max(per_dev) - 1e-9, (pred, per_dev)
    assert pred <= rep + 1e-9, (pred, rep)


# ----------------------------------------------------------- the planner --


def test_plan_pipeline_argmin_stable_under_ties():
    """Shape-invariant uniform costs tie huge swaths of the space; the
    documented total order must break them identically on every run — same
    pick, same ranked table."""
    costs = _uniform_costs_by_chunks()
    m = _stub_model()
    kw = dict(params=(), costs_by_chunks=costs)
    p1 = plan_pipeline(m, None, **kw)
    p2 = plan_pipeline(m, None, **kw)
    assert (p1.schedule, p1.chunks, p1.balance, p1.num_devices) == (
        p2.schedule, p2.chunks, p2.balance, p2.num_devices)
    assert p1.table() == p2.table()
    # rotation axis: predicted time is placement-invariant, so the pick is
    # always the schedule's default placement (rotation 0 -> placement None)
    assert p1.placement is None
    assert p1.predicted_step_s == p1.candidates[0].predicted_step_s
    # the winner is feasible and ranked first; pruned candidates sink
    assert p1.candidates[0].pruned is None
    seen_pruned = False
    for c in p1.candidates:
        if c.pruned is not None:
            seen_pruned = True
        else:
            assert not seen_pruned, "feasible candidate ranked after a pruned one"


def test_plan_pipeline_prefers_cheaper_split_backward():
    """A W-light cost profile makes the zero-bubble schedules strictly
    cheaper than fill-drain in the model; the planner must pick one of
    them, and the pick's predicted time must be the table's minimum."""
    base = _costs([2e-3, 1e-3, 1e-3, 1e-3, 1e-3, 2e-3], scale_b=0.9, scale_w=0.1)
    costs = {C: base for C in DEFAULT_CHUNK_COUNTS}
    plan = plan_pipeline(_stub_model(), None, params=(), costs_by_chunks=costs)
    assert plan.schedule in ("zb-h1", "zb-v", "1f1b", "interleaved", "fill_drain")
    feasible = [c for c in plan.candidates if c.pruned is None]
    assert plan.predicted_step_s == min(c.predicted_step_s for c in feasible)


def test_plan_pipeline_budget_truncates_deterministically():
    costs = _uniform_costs_by_chunks()
    plan = plan_pipeline(
        _stub_model(), None,
        PlanConstraints(budget=40), params=(), costs_by_chunks=costs,
    )
    assert plan.evaluated == 40
    assert plan.truncated
    full = plan_pipeline(_stub_model(), None, params=(), costs_by_chunks=costs)
    assert not full.truncated
    assert full.evaluated > 40


def test_plan_pipeline_memory_pruning_and_infeasible():
    costs = _uniform_costs_by_chunks()
    m = _stub_model()
    plan = plan_pipeline(
        m, None, PlanConstraints(max_live_activations=8),
        params=(), costs_by_chunks=costs,
    )
    pruned = [c for c in plan.candidates if c.pruned]
    assert any("peak_live" in c.pruned for c in pruned)
    assert plan.candidates[0].peak_live <= 8
    # over-constrained: every candidate pruned -> ValueError naming reasons
    with pytest.raises(ValueError, match="peak_live"):
        plan_pipeline(
            m, None, PlanConstraints(max_live_activations=0),
            params=(), costs_by_chunks=costs,
        )


def test_plan_pipeline_missing_costs_and_bad_stages():
    m = _stub_model()
    with pytest.raises(ValueError, match="no costs_by_chunks entry"):
        plan_pipeline(m, None, params=(), costs_by_chunks={4: _costs([1.0] * 6)})
    with pytest.raises(ValueError, match="num_stages"):
        plan_pipeline(m, None, PlanConstraints(num_stages=7), params=(),
                      costs_by_chunks=_uniform_costs_by_chunks())


def test_make_engine_accepts_plan_and_to_config_overrides():
    """Both engines take a PipelinePlan directly; ``to_config`` replays the
    pick with overrides winning."""
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    plan = plan_pipeline(
        m, None, params=(), costs_by_chunks=_uniform_costs_by_chunks(),
        engine="host",
    )
    pipe = make_engine(m, plan)
    assert pipe.describe()["engine"] == "host"
    assert pipe.describe()["schedule"] == plan.schedule
    cfg = plan.to_config(engine="compiled")
    assert isinstance(cfg, GPipeConfig)
    assert cfg.engine == "compiled"
    assert cfg.balance == plan.balance and cfg.chunks == plan.chunks


# ------------------------------------------------ cached profiler sidecar --


def test_explicit_pipe_devices_beyond_host_is_an_error():
    """An explicit --pipe-devices the host cannot give the compiled engine
    raises; the default 2-device interleaved ring may still run on the
    one-device lane substrate."""
    too_many = jax.device_count() + 1
    cli = PipelineCLIConfig(engine="compiled", schedule="interleaved",
                            stages=4, chunks=4, pipe_devices=too_many)
    with pytest.raises(ValueError, match="--pipe-devices"):
        cli.gpipe_config()
    default = PipelineCLIConfig(engine="compiled", schedule="interleaved",
                                stages=4, chunks=4)
    assert default.gpipe_config().num_devices == 2
    host = PipelineCLIConfig(engine="host", schedule="interleaved",
                             stages=4, chunks=4, pipe_devices=too_many)
    assert host.gpipe_config().num_devices == too_many


def test_cached_profile_sidecar_roundtrip(tmp_path):
    """First call profiles and writes the JSON sidecar; a cold process
    (in-process cache cleared) reads the sidecar back instead of
    re-profiling — proven by poisoning the profiler."""
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    path = str(tmp_path / "costs.json")
    key = profile_fingerprint(m, params, g, "padded")
    _PROFILE_CACHE.pop(key, None)
    c1 = cached_profile_layer_costs(m, params, g, cache_path=path,
                                    repeats=1, warmup=0)
    with open(path) as f:
        assert key in json.load(f)
    _PROFILE_CACHE.clear()  # simulate a fresh process
    import repro.core.costmodel as cm

    real = cm.profile_layer_costs
    cm.profile_layer_costs = lambda *a, **k: pytest.fail("re-profiled despite sidecar")
    try:
        c2 = cached_profile_layer_costs(m, params, g, cache_path=path)
    finally:
        cm.profile_layer_costs = real
    assert c1.names == c2.names and c1.fwd == c2.fwd and c1.bwd_w == c2.bwd_w
    # corrupt sidecar: ignored, falls back to the profiler
    with open(path, "w") as f:
        f.write("{not json")
    _PROFILE_CACHE.clear()
    c3 = cached_profile_layer_costs(m, params, g, cache_path=path,
                                    repeats=1, warmup=0)
    assert c3.names == c1.names


def test_profile_fingerprint_keys_on_shape_and_backend():
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    k1 = profile_fingerprint(m, params, g, "padded")
    assert k1 == profile_fingerprint(m, params, g, "padded")
    assert k1 != profile_fingerprint(m, params, g, "bucketed")


def test_profile_fingerprint_keys_on_device(monkeypatch):
    """A profile measured on one kind of device never keys a lookup on
    another: the CPU sidecar cannot drive --auto on the chip."""
    import types

    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    k_here = profile_fingerprint(m, params, g, "padded")
    keys = {}
    for platform, kind in (("tpu", "TPU v5 lite"), ("tpu", "TPU v4"), ("cpu", "cpu")):
        chip = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, chip=chip: [chip])
        keys[platform, kind] = profile_fingerprint(m, params, g, "padded")
    assert len(set(keys.values())) == 3
    assert k_here == keys["cpu", "cpu"]


# ------------------------------------------------- --auto --dry-run table --


def test_auto_dry_run_prints_ranked_table(capsys):
    """``--auto --dry-run`` through run_gnn: prints the ranked candidate
    table and returns the pick without training."""
    costs = _uniform_costs_by_chunks()
    ns = PipelineCLIConfig(stages=4, auto=True, dry_run=True).namespace(
        mode="gnn", dataset="karate", strategy="sequential", epochs=2,
        seed=0, log_every=0, costs_by_chunks=costs,
    )
    out = run_gnn(ns)
    text = capsys.readouterr().out
    assert out["mode"] == "auto-dry-run"
    assert out["schedule"] in PLAN_SCHEDULES
    assert out["chunks"] in DEFAULT_CHUNK_COUNTS
    assert "[auto] evaluated" in text
    assert "pick: schedule=" in text
    header = [ln for ln in text.splitlines() if "rank" in ln and "pred_ms" in ln]
    assert header, text
    # the pick echoes rank-0's fields
    plan_line = [ln for ln in text.splitlines() if ln.strip().startswith("0 ")][0]
    assert out["schedule"] in plan_line


def test_format_table_marks_truncation_and_pruned_rows():
    costs = _uniform_costs_by_chunks()
    plan = plan_pipeline(
        _stub_model(), None,
        PlanConstraints(budget=40, max_live_activations=8),
        params=(), costs_by_chunks=costs,
    )
    text = plan.format_table(limit=5)
    assert "(budget-truncated)" in text
    assert "more candidates" in text
    full = plan.format_table(limit=None)
    assert "peak_live" in full  # pruned rows carry their reason in the note
