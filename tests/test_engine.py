"""Engine equivalence: host GPipe vs compiled SPMD program, plus the stacked
micro-batch plan and the pytree-generalized spmd_pipeline.

The 1-device tests exercise the compiled engine's chunk-scan substrate; the
`slow` subprocess test forces 4 host devices so the shard_map/ppermute ring
substrate runs (same pattern as tests/test_spmd_pipe.py)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core.microbatch import make_plan
from repro.core.pipeline import GPipeConfig, make_engine
from repro.core.spmd_pipe import spmd_pipeline
from repro.graphs import load_dataset
from repro.models.gnn.net import build_paper_gat
from repro.train import optimizer as opt_lib


@pytest.fixture(scope="module")
def setup():
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    return g, m, params


def _params_close(p1, p2, atol):
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        assert jnp.allclose(a, b, atol=atol), float(jnp.max(jnp.abs(a - b)))


# ------------------------------------------------------------ stacked plan --


@pytest.mark.parametrize("pad_to_max", [True, False])
def test_stacked_plan_uniform_shapes(setup, pad_to_max):
    g, _, _ = setup
    plan = make_plan(g, 3, strategy="halo", halo_hops=2, pad_to_max=pad_to_max)
    stacked = plan.stacked()
    assert stacked is plan.stacked()  # cached
    # one uniform-shape pytree: every leaf leads with the chunk axis
    for leaf in jax.tree_util.tree_leaves(stacked.graph):
        assert leaf.shape[0] == 3
        assert leaf.shape[1] == stacked.n_pad
    assert stacked.graph.neighbors.shape == (3, stacked.n_pad, stacked.max_deg)
    assert stacked.core_mask.shape == (3, stacked.n_pad)
    # padding must not invent loss rows: core counts survive stacking
    want = sum(int(mb.core_mask.sum()) for mb in plan.batches)
    assert int(stacked.core_mask.sum()) == want == g.num_nodes
    # padded rows are inert: no edge slots, no norm mass
    for c, mb in enumerate(plan.batches):
        n = mb.num_nodes
        assert not bool(stacked.graph.mask[c, n:].any())
        assert float(jnp.abs(stacked.graph.norm[c, n:]).sum()) == 0.0


# ----------------------------------------------------- engine equivalence --


@pytest.mark.parametrize("strategy", ["halo", "sequential"])
def test_compiled_engine_matches_host(setup, strategy):
    """Same plan, same seed: the compiled engine's loss trajectory and
    post-step params match the host GPipe fill-drain baseline — including
    the paper's dropout, whose per-(chunk, layer) keys both engines derive
    identically."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy=strategy, halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    comp = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 1, 1, 2), chunks=C))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(3):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert abs(float(lh) - float(lc)) < 1e-4, (float(lh), float(lc))
    # 5e-4 over 3 adam steps: 1/(sqrt(v)+eps) amplifies the engines'
    # different float-accumulation orders on near-zero gradients (the same
    # effect the host-only schedule tests absorb at 5e-5 per single step)
    _params_close(ph, pc, atol=5e-4)


def _grad_gap(a, b) -> float:
    """Largest gap of gradients ``a`` from ``b``, leaf by leaf, relative to
    the leaf's largest entry of ``b``."""
    return max(
        float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


@pytest.mark.parametrize("schedule", ["fill_drain", "1f1b"])
def test_compiled_engine_gradients_match_host(setup, schedule):
    """The compiled step's gradients, not only its Adam update, equal the
    host engine's: Adam's first update is about lr * sign(g), so a gradient
    scaled by a constant (a psum issued twice) leaves params nearly as they
    were. ``grad_probe`` keeps the exact gradients as the optimizer state."""
    g, m, params = setup
    probe = opt_lib.grad_probe()
    plan = make_plan(g, 4, strategy="halo", halo_hops=2)
    rng = jax.random.PRNGKey(42)
    grads, losses = [], []
    for engine, sched in (("host", "fill_drain"), ("compiled", schedule)):
        eng = make_engine(m, GPipeConfig(engine=engine, balance=(2, 1, 1, 2), chunks=4,
                                         schedule=sched))
        p, gr, loss = eng.train_step(params, probe.init(params), plan, rng, probe)
        _params_close(p, params, atol=0.0)  # the probe never moves params
        grads.append(gr)
        losses.append(float(loss))
    assert abs(losses[0] - losses[1]) < 1e-4, losses
    # f32 sums in another order: 4.7e-7 of a leaf's largest entry on CPU
    assert _grad_gap(grads[1], grads[0]) < 1e-5


def test_compiled_engine_trains(setup):
    """30 compiled-engine epochs on karate reach high train accuracy (the
    host-engine learning test, rerun through the fused program)."""
    g, m, _ = setup
    opt = opt_lib.adam(1e-2)
    pipe = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 1, 1, 2), chunks=2))
    plan = make_plan(g, 2, strategy="halo", halo_hops=2)
    key = jax.random.PRNGKey(42)
    params = pipe.init_params(key)
    state = opt.init(params)
    for _ in range(30):
        key, rng = jax.random.split(key)
        params, state, loss = pipe.train_step(params, state, plan, rng, opt)
    logp = m.apply(params, g)
    acc = float(((jnp.argmax(logp, -1) == g.labels) * g.train_mask).sum() / g.train_mask.sum())
    assert acc >= 0.8, acc


def test_compiled_engine_stats_and_describe(setup):
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    pipe = make_engine(m, GPipeConfig(engine="compiled", balance=(3, 3), chunks=2))
    plan = make_plan(g, 2, strategy="sequential")
    stats = {}
    pipe.train_step(params, opt.init(params), plan, jax.random.PRNGKey(0), opt, stats=stats)
    assert stats["engine"] == "compiled"
    assert stats["bubble_fraction"] == pipe.schedule.bubble_fraction(2, 2)
    assert pipe.describe()["engine"] == "compiled"


def test_engine_factory_and_config_validation(setup):
    _, m, _ = setup
    with pytest.raises(KeyError):
        make_engine(m, GPipeConfig(engine="nope", balance=(3, 3), chunks=2))
    # both engines accept every schedule; interleaved still needs num_devices
    with pytest.raises(ValueError):
        make_engine(m, GPipeConfig(engine="compiled", balance=(3, 3), chunks=2, schedule="interleaved"))
    comp = make_engine(m, GPipeConfig(engine="compiled", balance=(3, 3), chunks=2, schedule="1f1b"))
    assert comp.describe()["schedule"] == "1f1b"
    host = make_engine(m, GPipeConfig(engine="host", balance=(3, 3), chunks=2, schedule="1f1b"))
    assert host.describe()["engine"] == "host"


def test_make_engine_requires_config(setup):
    """The redesigned factory: model first, assembled GPipeConfig second —
    anything else (a bare dict, a missing config) is a TypeError, not a
    silent default."""
    _, m, _ = setup
    with pytest.raises(TypeError):
        make_engine(m)
    with pytest.raises(TypeError):
        make_engine(m, {"engine": "host", "balance": (3, 3)})


def test_make_engine_name_first_removed(setup):
    """The deprecated name-first spelling make_engine("host", model, config)
    is gone: the engine name lives on GPipeConfig.engine and the old form
    now raises TypeError instead of warning."""
    _, m, _ = setup
    with pytest.raises(TypeError):
        make_engine("host", m)
    with pytest.raises(TypeError):
        make_engine("nope", m)


# ------------------------------------------- scheduled compiled executor --


SCHEDULE_MATRIX = [  # (schedule, num_devices kwarg)
    ("fill_drain", None),
    ("1f1b", None),
    ("interleaved", 2),
    ("zb-h1", None),
    ("zb-v", 2),
]


@pytest.mark.parametrize("schedule,pipe_devices", SCHEDULE_MATRIX)
def test_compiled_schedules_match_host_fill_drain(setup, schedule, pipe_devices):
    """The full schedule×engine matrix: every compiled schedule (fill-drain
    scan path, 1F1B and interleaved through the scheduled executor) produces
    the same loss trajectory and post-step params as the host fill-drain
    baseline — the canonical gradient-reduction order makes the update
    schedule-invariant on both engines. On hosts with fewer devices than the
    schedule's placement the scheduled work dispatcher runs through the
    lane-stacked substrate (spmd_pipeline_scheduled_lanes); with enough
    devices it runs the shard_map ring — so CI forcing 1 and 4 host devices
    covers both substrates."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    comp = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=C, schedule=schedule, num_devices=pipe_devices,
    ))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(3):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert abs(float(lh) - float(lc)) < 1e-4, (schedule, float(lh), float(lc))
    _params_close(ph, pc, atol=5e-4)


def test_scheduled_engine_peak_live_below_fill_drain(setup):
    """The scheduled executor's stash accounting realizes 1F1B's memory
    lever: peak banked activations strictly below the fill-drain S*C at
    chunks >= 4 (the fig3 acceptance invariant), and the per-device slot
    count is the schedule's live window, not C."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    pipe = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=C, schedule="1f1b",
    ))
    stats = {}
    pipe.train_step(params, opt.init(params), plan, jax.random.PRNGKey(0), opt, stats=stats)
    S = 4
    assert stats["measured_peak_live_activations"] < S * C
    assert stats["stash_slots_per_device"] <= min(S, C) + 1
    # and the schedule's own accounting agrees with the dominance claim
    assert pipe.schedule.peak_live_activations(S, C) < S * C


def test_zb_h1_engine_peak_live_not_above_1f1b(setup):
    """The zero-bubble invariant in the engine: zb-h1's B half keeps 1F1B's
    activation window, so its peak banked stage inputs never exceed 1F1B's
    (the residual stash is accounted separately as ``w_slots_per_device``)."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    peaks = {}
    for schedule in ("1f1b", "zb-h1"):
        pipe = make_engine(m, GPipeConfig(engine="compiled",
            balance=(2, 1, 1, 2), chunks=C, schedule=schedule,
        ))
        stats = {}
        pipe.train_step(
            params, opt.init(params), plan, jax.random.PRNGKey(0), opt, stats=stats
        )
        peaks[schedule] = stats
    zb, ob = peaks["zb-h1"], peaks["1f1b"]
    assert zb["measured_peak_live_activations"] <= ob["measured_peak_live_activations"]
    assert zb["stash_slots_per_device"] == ob["stash_slots_per_device"]
    assert 0 < zb["w_slots_per_device"] <= C
    assert ob["w_slots_per_device"] == 0  # fused backward banks no residuals
    assert zb["bubble_fraction"] < ob["bubble_fraction"]


# ------------------------------------------- placement / partition matrix --


PLACED_MATRIX = [  # (schedule, num_devices kwarg, ring rotation)
    ("fill_drain", None, 1),  # non-identity ring: routes through the scheduled executor
    ("1f1b", None, 2),
    ("interleaved", 2, 1),
    ("zb-h1", None, 3),
    ("zb-v", 2, 1),
]


@pytest.mark.parametrize("schedule,pipe_devices,rotation", PLACED_MATRIX)
def test_placed_schedules_match_host_fill_drain(setup, schedule, pipe_devices, rotation):
    """The placement axis of the property matrix: ANY valid (= ring) device
    placement produces updates bit-identical to the host fill-drain baseline
    on every schedule — placement relabels which device hosts which stage,
    never what runs. On 1 device this exercises the lane substrate's rotated
    columns; under CI's 4 forced devices the shard_map ring."""
    from repro.core.schedule import Placement

    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    placement = Placement.ring(4, pipe_devices, rotation=rotation)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    comp = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=C, schedule=schedule,
        num_devices=pipe_devices, placement=placement,
    ))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(2):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert abs(float(lh) - float(lc)) < 1e-4, (schedule, float(lh), float(lc))
    _params_close(ph, pc, atol=5e-4)


@pytest.mark.parametrize("balance", [(1, 2, 2, 1), (1, 1, 1, 3)])
def test_any_partition_matches_host_fill_drain(setup, balance):
    """The partition axis: moving stage boundaries (any contiguous balance,
    e.g. the cost-model partitioner's output) leaves the update bit-identical
    to the canonical host fill-drain baseline — partitioning redistributes
    work across devices, never reorders the math."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    comp = make_engine(m, GPipeConfig(engine="compiled",
        balance=balance, chunks=C, schedule="1f1b",
    ))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(2):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert abs(float(lh) - float(lc)) < 1e-4, (balance, float(lh), float(lc))
    _params_close(ph, pc, atol=5e-4)


def test_host_engine_with_placement_matches_baseline(setup):
    """Host-engine leg of the placement matrix: an explicit ring placement
    (with a device list, so ``_place`` actually routes tensors) leaves the
    host zb-h1 update identical to the unplaced host fill-drain baseline."""
    from repro.core.schedule import Placement

    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    placed = make_engine(m, GPipeConfig(engine="host",
        balance=(2, 1, 1, 2), chunks=C, schedule="zb-h1",
        devices=tuple(jax.devices()) * 4,  # cycle the host's devices
        placement=Placement.ring(4, rotation=2, device_order=(2, 0, 3, 1)),
    ))
    ph = pp = params
    oh = op = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(2):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pp, op, lp = placed.train_step(pp, op, plan, rng, opt)
        assert abs(float(lh) - float(lp)) < 1e-6, (float(lh), float(lp))
    _params_close(ph, pp, atol=1e-6)


def test_engine_rejects_incompatible_placement(setup):
    from repro.core.schedule import Placement

    _, m, _ = setup
    with pytest.raises(ValueError):  # not ring-compatible
        make_engine(m, GPipeConfig(engine="compiled",
            balance=(2, 1, 1, 2), chunks=4, placement=Placement((0, 2, 1, 3)),
        ))
    with pytest.raises(ValueError):  # device count != schedule's placement
        make_engine(m, GPipeConfig(engine="host",
            balance=(2, 1, 1, 2), chunks=4, schedule="interleaved",
            num_devices=2, placement=Placement.ring(4),
        ))


def test_scheduled_engine_rejects_illegal_combo(setup):
    """Interleaved needs chunks divisible by devices: the lowering-time
    ValueError surfaces at train_step, not as silent mis-routing."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    plan = make_plan(g, 3, strategy="sequential")
    pipe = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=3, schedule="interleaved", num_devices=2,
    ))
    with pytest.raises(ValueError):
        pipe.train_step(params, opt.init(params), plan, jax.random.PRNGKey(0), opt)


# ----------------------------------------- data axis (data x stage mesh) --


def _dp_fixture(chunks):
    """A small streamed power-law graph + GCN for the data-parallel matrix
    (streamed because the data axis exists for the streamed-graph scale
    path; tiny node count keeps the oracle runs fast)."""
    from repro.graphs import open_streamed, streamed_plan
    from repro.models.gnn.net import build_gnn

    ds = open_streamed("powerlaw-64k", num_nodes=512, block_size=256)
    plan = streamed_plan(ds, chunks, max_degree=16)
    g0 = plan.batches[0].graph
    m = build_gnn("gcn", g0.num_features, g0.num_classes, hidden=16, depth=2)
    return plan, m


def test_data_parallel_validation(setup):
    _, m, _ = setup
    with pytest.raises(ValueError):  # dp < 1
        make_engine(m, GPipeConfig(engine="compiled", balance=(3, 3),
                                   chunks=4, data_parallel=0))
    with pytest.raises(ValueError):  # host queue loop has no data axis
        make_engine(m, GPipeConfig(engine="host", balance=(3, 3),
                                   chunks=4, data_parallel=2))
    plan, m2 = _dp_fixture(3)
    eng = make_engine(m2, GPipeConfig(engine="compiled", balance=(2, 2),
                                      chunks=3, schedule="1f1b",
                                      data_parallel=2))
    opt = opt_lib.adam(1e-2)
    params = eng.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):  # chunks % dp != 0
        eng.train_step(params, opt.init(params), plan, jax.random.PRNGKey(0), opt)


def test_data_parallel_needs_enough_devices():
    """data_parallel replicas of a 2-device ring need 2x as many devices:
    on a smaller host the step raises instead of quietly running one
    replica."""
    dp = jax.device_count() + 1
    plan, m = _dp_fixture(dp)
    opt = opt_lib.adam(1e-2)
    eng = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 2),
                                     chunks=dp, schedule="1f1b",
                                     data_parallel=dp))
    params = eng.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=f"needs {2 * dp} devices"):
        eng.train_step(params, opt.init(params), plan, jax.random.PRNGKey(0), opt)


DP_SCHEDULES = (("fill_drain", 1), ("1f1b", None), ("zb-h1", None))


@pytest.fixture(scope="module")
def dp_mesh_report():
    """One 4-device run of the real 2-D (data, stage) mesh (2 replicas x 2
    ring positions) per schedule, against data_parallel=1 and the host
    fill-drain oracle on the same streamed plan. Returns, per schedule:
    whether the mesh ran, whether its params are BIT-identical to one
    replica, the max param gap and the per-step loss gaps to the host."""
    out = _run("""
    import json
    import jax, numpy as np
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.core.schedule import Placement
    from repro.graphs import open_streamed, streamed_plan
    from repro.models.gnn.net import build_gnn
    from repro.train import optimizer as opt_lib

    assert jax.device_count() == 4, jax.device_count()
    ds = open_streamed("powerlaw-64k", num_nodes=512, block_size=256)
    plan = streamed_plan(ds, 4, max_degree=16)
    g0 = plan.batches[0].graph
    m = build_gnn("gcn", g0.num_features, g0.num_classes, hidden=16, depth=2)
    opt = opt_lib.adam(1e-2)
    params = m.init_params(jax.random.PRNGKey(0))
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 2), chunks=4))
    for schedule, rotation in %r:
        # the rotated ring keeps dp=1 fill-drain on the scheduled executor
        # (the fused scan fuses differently -> not bit-comparable)
        placement = None if rotation is None else Placement.ring(2, rotation=rotation)
        e1 = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 2),
            chunks=4, schedule=schedule, placement=placement, data_parallel=1))
        e2 = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 2),
            chunks=4, schedule=schedule, placement=placement, data_parallel=2))
        inactive_before = not e2._data_parallel_active  # set lazily at first step
        ph = p1 = p2 = params
        oh = o1 = o2 = opt.init(params)
        key = jax.random.PRNGKey(42)
        loss_gaps = []
        for _ in range(2):
            key, rng = jax.random.split(key)
            ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
            p1, o1, l1 = e1.train_step(p1, o1, plan, rng, opt)
            p2, o2, l2 = e2.train_step(p2, o2, plan, rng, opt)
            loss_gaps.append(abs(float(lh) - float(l2)))
        leaves = lambda p: [np.asarray(a) for a in jax.tree_util.tree_leaves(p)]
        print("DP_REPORT " + json.dumps({
            "schedule": schedule,
            "active": inactive_before and e2._data_parallel_active,
            "bitwise": all(np.array_equal(a, b) for a, b in zip(leaves(p1), leaves(p2))),
            "host_gap": max(float(np.max(np.abs(a - b))) for a, b in zip(leaves(ph), leaves(p2))),
            "loss_gaps": loss_gaps,
        }))
    """ % (DP_SCHEDULES,))
    rows = [json.loads(line.split(" ", 1)[1]) for line in out.splitlines()
            if line.startswith("DP_REPORT ")]
    return {r["schedule"]: r for r in rows}


@pytest.mark.parametrize("schedule,rotation", DP_SCHEDULES)
def test_data_parallel_bit_identical_to_one_replica(dp_mesh_report, schedule, rotation):
    """data_parallel=2 produces updates BIT-identical to data_parallel=1 on
    every scheduled executor: the data axis re-distributes which replica
    pipelines which chunks, and the executor's ordered gather reduction
    restores the canonical global chunk order exactly — zero numerical
    change. Checked on the real (data, stage) mesh of 4 devices."""
    assert dp_mesh_report[schedule]["bitwise"], dp_mesh_report[schedule]


def test_data_parallel_matches_host_fill_drain(dp_mesh_report):
    """The dp=2 update agrees with the host fill-drain oracle on the same
    streamed plan at the standard engine tolerance (the compiled program
    fuses differently; bit-identity is vs dp=1 above)."""
    r = dp_mesh_report["1f1b"]
    assert max(r["loss_gaps"]) < 1e-4, r
    assert r["host_gap"] <= 5e-4, r


@pytest.mark.slow
def test_data_parallel_mesh_multidevice(dp_mesh_report):
    """The real 2-D (data, stage) mesh on 4 simulated devices (2 replicas x
    2 ring positions): per-replica timelines over sharded streamed chunks
    still produce BIT-identical params to data_parallel=1 on every
    scheduled executor, and match the host fill-drain oracle."""
    assert sorted(dp_mesh_report) == sorted(s for s, _ in DP_SCHEDULES)
    for schedule, r in dp_mesh_report.items():
        assert r["active"], r  # the 2-D mesh really ran
        assert r["bitwise"], r
        assert max(r["loss_gaps"]) < 1e-4, r
        assert r["host_gap"] <= 5e-4, r


# ------------------------------------------------- compiled eval path --


def test_compiled_evaluate_matches_host_eval(setup):
    """The forward-only jitted eval program: on a lossless halo plan (hops
    >= model depth) the chunked core-node metrics equal the host full-batch
    ``make_eval`` numbers — so --engine compiled validation can run through
    the compiled path without changing any reported accuracy."""
    from repro.train.loop import make_eval

    g, m, params = setup
    plan = make_plan(g, 3, strategy="halo", halo_hops=2)
    pipe = make_engine(m, GPipeConfig(engine="compiled", balance=(2, 1, 1, 2), chunks=3))
    got = pipe.evaluate(params, plan)
    want = make_eval(m)(params, g)
    assert set(got) == {"train_loss", "train_acc", "val_acc", "test_acc"}
    for k in got:
        assert abs(float(got[k]) - float(want[k])) < 1e-5, (k, got[k], want[k])


def test_compiled_evaluate_after_training(setup):
    """Eval and train steps share the engine: training through the
    scheduled executor then evaluating through the forward-only program
    works on the same instance (separate program caches), and the eval
    program is cached per plan shape."""
    g, m, _ = setup
    opt = opt_lib.adam(1e-2)
    pipe = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=2, schedule="zb-h1",
    ))
    plan = make_plan(g, 2, strategy="halo", halo_hops=2)
    key = jax.random.PRNGKey(42)
    params = pipe.init_params(key)
    state = opt.init(params)
    accs = []
    for _ in range(15):
        key, rng = jax.random.split(key)
        params, state, loss = pipe.train_step(params, state, plan, rng, opt)
        accs.append(float(pipe.evaluate(params, plan)["train_acc"]))
    assert len(pipe._evals) == 1  # one program per stacked-plan shape
    assert accs[-1] >= 0.8, accs[-1]
    # and the metrics agree with a host full-batch apply of the same params
    logp = m.apply(params, g)
    want = float(((jnp.argmax(logp, -1) == g.labels) * g.train_mask).sum()
                 / g.train_mask.sum())
    assert abs(accs[-1] - want) < 1e-5


@pytest.mark.parametrize("engine", ["host", "compiled"])
def test_eval_program_binds_params_once(setup, engine, monkeypatch):
    """The re-replication bugfix: ``compile_eval`` returns a bound
    EvalProgram and repeated calls with the *same params object* must not
    device_put the param tree again — binding is identity-cached, so a
    serving loop pays replication once per param version, not per batch.
    (On 1 device the eval mesh is absent and the count is zero throughout;
    the 4-forced-device serving test checks the mesh path.)"""
    g, m, params = setup
    plan = make_plan(g, 2, strategy="halo", halo_hops=2)
    pipe = make_engine(m, GPipeConfig(engine=engine, balance=(3, 3), chunks=2))
    first = pipe.evaluate(params, plan)  # compile + first bind outside the count

    calls = []
    real_put = jax.device_put

    def counting_put(*args, **kwargs):
        calls.append(1)
        return real_put(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting_put)
    again = pipe.evaluate(params, plan)
    assert not calls, f"evaluate re-replicated params: {len(calls)} device_puts"
    for k in first:
        assert float(first[k]) == float(again[k]), k
    # same shape + same params -> the exact same cached program object
    stacked = plan.stacked()
    assert pipe.compile_eval(params, stacked.graph) is pipe.compile_eval(
        params, stacked.graph
    )


# ------------------------------------------------ ragged / empty chunks --


def _plan_with_empty_chunk(g, chunks=3):
    """A ragged halo plan plus one chunk that is EMPTY after core-halo
    padding: its nodes are all pad duplicates of node 0 with core_mask False
    (count == 0), the shape every chunk in the plan shares."""
    import dataclasses as dc

    import numpy as np

    from repro.core.microbatch import MicroBatch
    from repro.graphs.data import subgraph
    from repro.graphs.partition import pad_partition

    plan = make_plan(g, chunks, strategy="halo", halo_hops=2)
    n_pad = max(mb.num_nodes for mb in plan.batches)
    nodes, core = pad_partition(
        np.zeros(0, dtype=np.int64), np.zeros(0, dtype=bool), n_pad
    )
    empty = MicroBatch(graph=subgraph(g, nodes), core_mask=jnp.asarray(core))
    assert int(empty.core_mask.sum()) == 0
    # replace() gives the new plan a fresh (empty) _stacked cache — the old
    # cache never carries over (the microbatch satellite bugfix)
    return dc.replace(plan, chunks=chunks + 1, batches=plan.batches + [empty])


def test_stacked_plan_keeps_empty_chunk_mask_correct(setup):
    g, _, _ = setup
    plan = _plan_with_empty_chunk(g, chunks=3)
    stacked = plan.stacked()
    assert stacked.chunks == 4
    # the empty chunk contributes zero loss rows and zero norm mass
    assert int(stacked.core_mask[3].sum()) == 0
    assert int((stacked.graph.train_mask[3] & stacked.core_mask[3]).sum()) == 0
    assert float(jnp.abs(stacked.graph.norm[3]).sum()) > 0  # self-loops exist...
    # ...but every loss-counting row across the plan is a real core node
    assert int(stacked.core_mask.sum()) == g.num_nodes


@pytest.mark.parametrize("schedule", ["1f1b", "zb-h1"])
def test_empty_chunk_trains_identically_on_both_engines(setup, schedule):
    """A count=0 chunk must ride the scheduled executor as an inert
    microbatch: same loss and params as the host engine running the same
    ragged plan, and everything stays finite — including through zb-h1's
    split B/W ticks, whose deferred weight grads for the empty chunk are
    all zeros."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    plan = _plan_with_empty_chunk(g, chunks=3)  # C = 4 incl. empty
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=4))
    comp = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=4, schedule=schedule,
    ))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(7)
    for _ in range(2):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert jnp.isfinite(lh) and jnp.isfinite(lc)
        assert abs(float(lh) - float(lc)) < 1e-4, (float(lh), float(lc))
    _params_close(ph, pc, atol=5e-4)


# ---------------------------------------- communication/compute overlap --


@pytest.mark.parametrize("schedule,rotation,dp", [
    ("fill_drain", 1, 1),  # rotated ring: the serialized side must ALSO run
    ("1f1b", None, 1),     # the scheduled executor (the fused fill-drain
    ("zb-h1", None, 1),    # scan fuses differently at the float level)
    ("1f1b", None, 2),
])
def test_double_buffer_bit_identical_to_serialized(schedule, rotation, dp, request):
    """The tentpole's correctness property: retiming the wires to latency 2
    (ppermute pair posted one tick before its arrivals are consumed) is pure
    dataflow retiming — params after each step are BIT-identical to the
    serialized latency-1 executor, on every schedule and with the data axis
    active. On 1 device this exercises the lane substrate's pend-tuple
    rotation; the data axis needs the real (data, stage) mesh of 4
    devices, so that case reads the 4-device run."""
    import numpy as np

    from repro.core.schedule import Placement

    if dp > 1 and jax.device_count() < 2 * dp:
        r = request.getfixturevalue("overlap_mesh_report")[schedule, dp]
        assert r["latencies"] == [1, 2] and r["bitwise"], r
        return
    plan, m = _dp_fixture(4)
    opt = opt_lib.adam(1e-2)
    params = m.init_params(jax.random.PRNGKey(0))
    placement = None if rotation is None else Placement.ring(2, rotation=rotation)
    engines = [
        make_engine(m, GPipeConfig(engine="compiled", balance=(2, 2), chunks=4,
                                   schedule=schedule, placement=placement,
                                   data_parallel=dp, overlap=overlap))
        for overlap in ("off", "double-buffer")
    ]
    ps = [params, params]
    os_ = [opt.init(params), opt.init(params)]
    key = jax.random.PRNGKey(42)
    stats = [{}, {}]
    for _ in range(2):
        key, rng = jax.random.split(key)
        for i, eng in enumerate(engines):
            ps[i], os_[i], _ = eng.train_step(
                ps[i], os_[i], plan, rng, opt, stats=stats[i]
            )
    assert stats[0]["wire_latency"] == 1
    assert stats[1]["wire_latency"] == 2
    assert stats[1]["num_ticks"] > stats[0]["num_ticks"]  # retime adds ticks
    for a, b in zip(jax.tree_util.tree_leaves(ps[0]), jax.tree_util.tree_leaves(ps[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            schedule, dp, float(jnp.max(jnp.abs(a - b))))


def test_double_buffer_matches_host_oracle(setup):
    """Engine-cross check on the paper model: the double-buffered 1f1b
    update agrees with the host fill-drain oracle at the standard engine
    tolerance (bit-identity is vs the serialized executor above)."""
    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    plan = make_plan(g, 4, strategy="halo", halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=4))
    comp = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=4, schedule="1f1b", overlap="double-buffer",
    ))
    ph = pc = params
    oh = oc = opt.init(params)
    key = jax.random.PRNGKey(42)
    for _ in range(2):
        key, rng = jax.random.split(key)
        ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
        pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
        assert abs(float(lh) - float(lc)) < 1e-4, (float(lh), float(lc))
    _params_close(ph, pc, atol=5e-4)


def test_empty_chunk_skips_its_ticks(setup):
    """Dead-tick elimination at the engine level: the ragged karate plan
    with a trailing EMPTY chunk runs in exactly the tick count of the clean
    3-chunk plan (the empty chunk's ticks are skipped, not pipelined), and
    the double-buffered executor composes with the skip — bit-identical
    params to the serialized run on the same ragged plan."""
    import numpy as np

    g, m, params = setup
    opt = opt_lib.adam(1e-2)
    ragged = _plan_with_empty_chunk(g, chunks=3)  # C = 4 incl. empty
    clean = make_plan(g, 3, strategy="halo", halo_hops=2)
    engines = {
        name: make_engine(m, GPipeConfig(engine="compiled",
            balance=(2, 1, 1, 2), chunks=4, schedule="1f1b", overlap=name))
        for name in ("off", "double-buffer")
    }
    clean3 = make_engine(m, GPipeConfig(engine="compiled",
        balance=(2, 1, 1, 2), chunks=3, schedule="1f1b"))
    st = {name: {} for name in engines}
    st["clean"] = {}
    ps = {}
    for name, eng in engines.items():
        p, o, loss = eng.train_step(
            params, opt.init(params), ragged, jax.random.PRNGKey(7), opt,
            stats=st[name],
        )
        assert jnp.isfinite(loss)
        ps[name] = p
    clean3.train_step(params, opt.init(params), clean, jax.random.PRNGKey(7),
                      opt, stats=st["clean"])
    assert st["off"]["num_ticks"] == st["clean"]["num_ticks"]
    for a, b in zip(jax.tree_util.tree_leaves(ps["off"]),
                    jax.tree_util.tree_leaves(ps["double-buffer"])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            float(jnp.max(jnp.abs(a - b))))


def test_overlap_validation(setup):
    """The host queue loop has no wires to double-buffer — overlap modes are
    compiled-engine only — and an unknown mode is a config error."""
    g, m, params = setup
    with pytest.raises(ValueError, match="host"):
        make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2),
                                   chunks=4, overlap="double-buffer"))
    with pytest.raises(ValueError, match="overlap"):
        make_engine(m, GPipeConfig(engine="compiled", balance=(2, 1, 1, 2),
                                   chunks=4, overlap="eager"))


# ------------------------------------------- pytree-generalized pipeline --


def test_spmd_pipeline_accepts_pytree_microbatches():
    """x may be any pytree of (num_micro, ...) leaves — mixed float/int/bool
    dtypes ride the scan + ppermute with the activations (the GNN contract).
    Runs under vmap(axis_name=...), which shares the collective semantics."""
    S, NM, D = 3, 4, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.4

    def stage_fn(my_in, state):
        s = jax.lax.axis_index("stage")
        wp = jax.lax.dynamic_index_in_dim(w, s, 0, keepdims=False)
        h = jnp.tanh(my_in["h"] @ wp)
        # int/bool leaves pass through untouched
        return dict(my_in, h=h), state

    x = {
        "h": jax.random.normal(jax.random.PRNGKey(1), (NM, 2, D)),
        "tag": jnp.arange(NM, dtype=jnp.int32),
        "flag": jnp.ones((NM,), bool),
    }

    def body(xs):
        out, _ = spmd_pipeline(
            stage_fn, xs, stage_axis="stage", num_stages=S, reduce="psum"
        )
        return out

    out = jax.jit(
        jax.vmap(body, in_axes=None, out_axes=0, axis_name="stage", axis_size=S)
    )(x)
    out = jax.tree_util.tree_map(lambda a: a[0], out)  # identical post-psum lanes
    ref = x["h"]
    for s in range(S):
        ref = jnp.tanh(ref @ w[s])
    assert jnp.allclose(out["h"], ref, atol=1e-5)
    assert jnp.array_equal(out["tag"], x["tag"])
    assert jnp.array_equal(out["flag"], x["flag"])


def test_spmd_pipeline_reduce_validation():
    with pytest.raises(ValueError):
        spmd_pipeline(lambda a, b: (a, b), jnp.ones((2, 2)),
                      stage_axis="stage", num_stages=2, reduce="mean")


# ------------------------------------------------- multi-device substrate --


def _run(src: str, devices: int = 4, timeout: int = 1200):
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": "src",
        "JAX_PLATFORMS": "cpu",
    }
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, **env},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_compiled_engine_matches_host_multidevice():
    """The full schedule×engine matrix on 4 simulated devices: the
    fill-drain shard_map/ppermute ring AND the scheduled executor (1F1B and
    zb-h1 split backward on the 4-device ring, interleaved on a 2-device
    ring with 2 virtual stages each) all produce the same per-epoch losses
    and post-step params as the host GPipe fill-drain baseline, and the
    same one-step gradients (``grad_probe``) — and the forward-only
    compiled eval program agrees with the host full-batch eval on the ring
    substrate too."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.graphs import load_dataset
    from repro.models.gnn.net import build_paper_gat
    from repro.train import optimizer as opt_lib
    from repro.train.loop import make_eval

    assert jax.device_count() == 4, jax.device_count()
    g = load_dataset("karate")
    m = build_paper_gat(g.num_features, g.num_classes)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = opt_lib.adam(1e-2)
    probe = opt_lib.grad_probe()
    C = 4
    plan = make_plan(g, C, strategy="halo", halo_hops=2)
    host = make_engine(m, GPipeConfig(engine="host", balance=(2, 1, 1, 2), chunks=C))
    g_rng = jax.random.PRNGKey(7)
    _, hg, _ = host.train_step(params, probe.init(params), plan, g_rng, probe)
    for schedule, nd in (("fill_drain", None), ("1f1b", None),
                         ("interleaved", 2), ("zb-h1", None), ("zb-v", 2)):
        comp = make_engine(m, GPipeConfig(engine="compiled",
            balance=(2, 1, 1, 2), chunks=C, schedule=schedule, num_devices=nd))
        ph = pc = params
        oh = oc = opt.init(params)
        key = jax.random.PRNGKey(42)
        for ep in range(3):
            key, rng = jax.random.split(key)
            ph, oh, lh = host.train_step(ph, oh, plan, rng, opt)
            pc, oc, lc = comp.train_step(pc, oc, plan, rng, opt)
            assert abs(float(lh) - float(lc)) < 1e-4, (schedule, ep, float(lh), float(lc))
        for a, b in zip(jax.tree_util.tree_leaves(ph), jax.tree_util.tree_leaves(pc)):
            assert jnp.allclose(a, b, atol=5e-4), (schedule, float(jnp.max(jnp.abs(a - b))))
        # Adam's first update is about lr * sign(g): compare the gradients
        _, cg, _ = comp.train_step(params, probe.init(params), plan, g_rng, probe)
        for a, b in zip(jax.tree_util.tree_leaves(cg), jax.tree_util.tree_leaves(hg)):
            gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            assert gap < 1e-5, (schedule, gap)  # 4.7e-7 on the fused ring
        print('MD_ENGINE_OK', schedule)
    ev = comp.evaluate(pc, plan)
    want = make_eval(m)(pc, g)
    for k in ev:
        assert abs(float(ev[k]) - float(want[k])) < 1e-5, (k, ev[k], want[k])
    print('MD_EVAL_OK')
    """)
    for schedule in ("fill_drain", "1f1b", "interleaved", "zb-h1", "zb-v"):
        assert f"MD_ENGINE_OK {schedule}" in out
    assert "MD_EVAL_OK" in out


OVERLAP_MESH_CASES = (
    ("fill_drain", (1, 1, 1, 1), 1, 1),  # rotated ring
    ("1f1b", (1, 1, 1, 1), None, 1),
    ("zb-h1", (1, 1, 1, 1), None, 1),
    ("1f1b", (2, 2), None, 2),  # 2 replicas x 2-stage ring
)


@pytest.fixture(scope="module")
def overlap_mesh_report():
    """One run on the real 4-device shard_map ring of the double-buffered
    executor against the serialized one, per (schedule, dp) case: whether
    the wire latencies are 1 and 2 and whether params after two steps are
    BIT-identical."""
    out = _run("""
    import json
    import jax, numpy as np
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.core.schedule import Placement
    from repro.graphs import open_streamed, streamed_plan
    from repro.models.gnn.net import build_gnn
    from repro.train import optimizer as opt_lib

    assert jax.device_count() == 4, jax.device_count()
    ds = open_streamed("powerlaw-64k", num_nodes=512, block_size=256)
    plan = streamed_plan(ds, 4, max_degree=16)
    g0 = plan.batches[0].graph
    m = build_gnn("gcn", g0.num_features, g0.num_classes, hidden=16, depth=2)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = opt_lib.adam(1e-2)
    for schedule, balance, rotation, dp in %r:
        placement = None if rotation is None else Placement.ring(4, rotation=rotation)
        engines = [
            make_engine(m, GPipeConfig(engine="compiled", balance=balance,
                chunks=4, schedule=schedule, placement=placement,
                data_parallel=dp, overlap=overlap))
            for overlap in ("off", "double-buffer")
        ]
        ps = [params, params]
        os_ = [opt.init(params), opt.init(params)]
        key = jax.random.PRNGKey(42)
        stats = [{}, {}]
        for _ in range(2):
            key, rng = jax.random.split(key)
            for i, eng in enumerate(engines):
                ps[i], os_[i], _ = eng.train_step(
                    ps[i], os_[i], plan, rng, opt, stats=stats[i])
        print("OVERLAP_REPORT " + json.dumps({
            "schedule": schedule, "dp": dp,
            "latencies": [stats[0]["wire_latency"], stats[1]["wire_latency"]],
            "bitwise": all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves(ps[0]),
                                jax.tree_util.tree_leaves(ps[1]))),
        }))
    """ % (OVERLAP_MESH_CASES,))
    rows = [json.loads(line.split(" ", 1)[1]) for line in out.splitlines()
            if line.startswith("OVERLAP_REPORT ")]
    return {(r["schedule"], r["dp"]): r for r in rows}


@pytest.mark.slow
def test_double_buffer_bit_identical_multidevice(overlap_mesh_report):
    """The tentpole property on the real 4-device shard_map ring: the
    double-buffered executor (ppermute pair for tick t+1 issued before
    tick t's work) produces BIT-identical params to the serialized latency-1
    executor for every schedule family — rotated fill-drain (so both sides
    run the scheduled path), 1f1b, zb-h1, and 1f1b on the 2x2 (data, stage)
    mesh."""
    assert sorted(overlap_mesh_report) == sorted((c[0], c[3]) for c in OVERLAP_MESH_CASES)
    for case, r in overlap_mesh_report.items():
        assert r["latencies"] == [1, 2], r
        assert r["bitwise"], r


# ---------------------------------------------- aggregation backend matrix --


BACKEND_MATRIX = [  # (engine, schedule): fused scan + split-B/W executor
    ("host", "fill_drain"),
    ("compiled", "fill_drain"),
    ("compiled", "zb-h1"),
]


def _backend_fixture(dataset):
    """(graph, model-factory, balance) for the backend-equivalence matrix.

    karate drives the paper GAT (attention path; attn_dropout=0 because the
    fused kernel is deterministic), the skewed twin drives a GCN whose padded
    layout is mostly padding — the case the bucketed layout exists for."""
    from repro.models.gnn.net import build_gnn

    if dataset == "karate":
        g = load_dataset("karate")
        def mk(backend):
            return build_paper_gat(g.num_features, g.num_classes,
                                   backend=backend, attn_dropout=0.0)
        return g, mk, (3, 3)
    g = load_dataset("skewed-mini")
    def mk(backend):
        return build_gnn("gcn", g.num_features, g.num_classes,
                         hidden=16, depth=2, backend=backend)
    return g, mk, (2, 2)


@pytest.mark.parametrize("dataset", ["karate", "skewed-mini"])
@pytest.mark.parametrize("engine,schedule", BACKEND_MATRIX)
def test_pallas_backend_matches_padded_host(dataset, engine, schedule):
    """backend="pallas" (degree-bucketed aggregation inside the stage
    programs) must reproduce the host fill-drain padded baseline's losses,
    updates and eval metrics on both engines, through the fused scan AND the
    split-B/W zb-h1 executor — the layout changes where edge slots live,
    never the math (float summation order absorbed by the oracle
    tolerance)."""
    g, mk, balance = _backend_fixture(dataset)
    opt = opt_lib.adam(1e-2)
    C = 2
    plan = make_plan(g, C, strategy="sequential")
    ref = make_engine(mk("padded"), GPipeConfig(
        engine="host", balance=balance, chunks=C, backend="padded"))
    pal = make_engine(mk("pallas"), GPipeConfig(
        engine=engine, balance=balance, chunks=C, schedule=schedule,
        backend="pallas"))
    params = ref.init_params(jax.random.PRNGKey(0))
    pr = pp = params
    orf = opl = opt.init(params)
    key = jax.random.PRNGKey(11)
    for _ in range(2):
        key, rng = jax.random.split(key)
        pr, orf, lr = ref.train_step(pr, orf, plan, rng, opt)
        pp, opl, lp = pal.train_step(pp, opl, plan, rng, opt)
        assert abs(float(lr) - float(lp)) < 1e-4, (dataset, engine, schedule)
    _params_close(pr, pp, atol=5e-4)
    ev_r, ev_p = ref.evaluate(pr, plan), pal.evaluate(pp, plan)
    for k in ev_r:
        assert abs(float(ev_r[k]) - float(ev_p[k])) < 1e-4, (k, ev_r[k], ev_p[k])


def test_engine_layout_cache_and_passthrough(setup):
    """PipelineEngine.layout: identity for non-pallas backends and for
    already-bucketed graphs; under backend="pallas" the bucketed wrapper is
    built once per stacked graph and cached by identity (entries retain the
    graph, so a recycled id() can never serve a stale layout)."""
    from repro.graphs.data import BucketedGraphBatch

    g, m, _ = setup
    plan = make_plan(g, 2, strategy="sequential")
    stacked = plan.stacked().graph

    padded = make_engine(m, GPipeConfig(engine="host", balance=(3, 3), chunks=2))
    assert padded.layout(stacked) is stacked

    pal = make_engine(m, GPipeConfig(engine="host", balance=(3, 3), chunks=2,
                                     backend="pallas"))
    wrapped = pal.layout(stacked)
    assert isinstance(wrapped, BucketedGraphBatch)
    assert wrapped.base is stacked
    assert pal.layout(stacked) is wrapped  # cached by identity
    assert pal.layout(wrapped) is wrapped  # already bucketed: pass through


@pytest.mark.slow
def test_pallas_backend_matches_padded_multidevice():
    """The backend axis on 4 simulated devices: the bucketed pallas stage
    programs ride the shard_map ring (fused fill-drain AND the zb-h1
    scheduled executor) and still match the host padded fill-drain
    baseline's updates at oracle tolerance."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.graphs import load_dataset
    from repro.models.gnn.net import build_gnn
    from repro.train import optimizer as opt_lib

    assert jax.device_count() == 4, jax.device_count()
    g = load_dataset("skewed-mini")
    def mk(backend):
        return build_gnn("gcn", g.num_features, g.num_classes,
                         hidden=16, depth=4, backend=backend)
    opt = opt_lib.adam(1e-2)
    C = 4
    plan = make_plan(g, C, strategy="sequential")
    balance = (3, 3, 2)  # the depth-4 gcn stack's 8 layers over 3 stages
    ref = make_engine(mk("padded"), GPipeConfig(
        engine="host", balance=balance, chunks=C, backend="padded"))
    params = ref.init_params(jax.random.PRNGKey(0))
    for schedule in ("fill_drain", "zb-h1"):
        pal = make_engine(mk("pallas"), GPipeConfig(
            engine="compiled", balance=balance, chunks=C, schedule=schedule,
            backend="pallas"))
        pr = pp = params
        orf = opl = opt.init(params)
        key = jax.random.PRNGKey(11)
        for _ in range(2):
            key, rng = jax.random.split(key)
            pr, orf, lr = ref.train_step(pr, orf, plan, rng, opt)
            pp, opl, lp = pal.train_step(pp, opl, plan, rng, opt)
            assert abs(float(lr) - float(lp)) < 1e-4, (schedule, float(lr), float(lp))
        for a, b in zip(jax.tree_util.tree_leaves(pr), jax.tree_util.tree_leaves(pp)):
            assert jnp.allclose(a, b, atol=5e-4), (schedule, float(jnp.max(jnp.abs(a - b))))
        print('MD_BACKEND_OK', schedule)
    """)
    for schedule in ("fill_drain", "zb-h1"):
        assert f"MD_BACKEND_OK {schedule}" in out
