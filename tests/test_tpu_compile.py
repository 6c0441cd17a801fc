"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler installed with JAX compiles for a topology it is only
told about. These tests compile the Pallas kernels of the main path at the
cora stand-in's widths, and one whole lane-substrate train step of the
paper GAT with ``--backend pallas``, for one chip of a ``v5e:2x2``, and the
4-stage ring's train step across its four chips. They catch what interpret
mode cannot: blocks not aligned to the tiling, scalar reads Mosaic refuses,
more VMEM or SMEM than a kernel may use. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The module's tests share one ``xdist_group``, so under
``--dist loadgroup`` (as under ``loadfile``) one worker runs them all.
They skip only where the TPU library is not installed; any other failure
to describe the topology fails them.
"""

import importlib.util

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

pytestmark = pytest.mark.xdist_group("tpu_compile")

# the cora stand-in: 2,708 nodes, 1,433 features, 7 classes; its 4-chunk
# halo plan pads each chunk to 2,602 nodes
N, F_IN, CLASSES = 2708, 1433, 7
HEADS, HIDDEN = 8, 8


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2, with JAX's persistent compilation cache off: a
    compile for a described chip is written to the cache but can never be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        if importlib.util.find_spec("libtpu") is None:
            pytest.skip("the TPU library (libtpu) is not installed")
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compiled text of ``fn`` for one chip, from (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("f", [HIDDEN, HEADS * HIDDEN])
def test_padded_spmm_kernel_compiles(one_chip, f):
    from repro.kernels.spmm.kernel import padded_spmm_kernel

    text = _compile(
        lambda hw, nbr, norm: padded_spmm_kernel(hw, nbr, norm, interpret=False),
        one_chip, ((N, f), jnp.float32), ((N, 32), jnp.int32), ((N, 32), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,width", [(2480, 8), (136, 16), (5, 256)])
def test_bucket_spmm_kernel_compiles(one_chip, rows, width):
    from repro.kernels.spmm.kernel import bucket_spmm_kernel

    text = _compile(
        lambda hw, nbr, norm: bucket_spmm_kernel(hw, nbr, norm, interpret=False),
        one_chip, ((N, 64), jnp.float32), ((rows, width), jnp.int32),
        ((rows, width), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("f", [HIDDEN, CLASSES])  # the paper GAT's two layers
@pytest.mark.parametrize("rows,width", [(2480, 8), (136, 16), (5, 256)])
def test_bucket_gat_kernel_compiles(one_chip, f, rows, width):
    from repro.kernels.gat_edge.kernel import bucket_gat_kernel

    text = _compile(
        lambda hw, nbr, s_self, s_nbr, mask: bucket_gat_kernel(
            hw, nbr, s_self, s_nbr, mask, interpret=False
        ),
        one_chip, ((HEADS, N, f), jnp.float32), ((rows, width), jnp.int32),
        ((HEADS, rows), jnp.float32), ((HEADS, rows, width), jnp.float32),
        ((rows, width), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_kernels_name_the_cap_past_it():
    """Past the resident-block cap the op raises by name; it never hands
    Mosaic a block it refuses or falls back to the oracle."""
    from repro.kernels.spmm.kernel import RESIDENT_VMEM_BYTES, padded_spmm_kernel

    n = RESIDENT_VMEM_BYTES // (128 * 4) + 8
    with pytest.raises(ValueError, match="RESIDENT_VMEM_BYTES"):
        jax.eval_shape(
            lambda hw, nbr, norm: padded_spmm_kernel(hw, nbr, norm, interpret=False),
            jax.ShapeDtypeStruct((n, 64), jnp.float32),
            jax.ShapeDtypeStruct((n, 8), jnp.int32),
            jax.ShapeDtypeStruct((n, 8), jnp.float32),
        )


def test_pallas_lane_train_step_compiles(one_chip, monkeypatch):
    """One train step of the paper GAT on the 4-stage lane substrate (one
    chip), 4 halo chunks of cora, 1F1B, ``--backend pallas``: the whole
    program compiles for the chip and holds the Pallas kernels. The kernel
    routing asks the backend at trace time, and the backend here is the
    CPU, so the test asks for the compiled kernels itself."""
    from repro.core.cli import PipelineCLIConfig
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import make_engine
    from repro.graphs import load_dataset
    from repro.models.gnn.net import build_paper_gat
    from repro.train import optimizer as opt_lib

    monkeypatch.setenv("REPRO_PALLAS_FORCE_KERNEL", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    g = load_dataset("cora")
    assert (g.num_nodes, g.num_features, g.num_classes) == (N, F_IN, CLASSES)
    model = build_paper_gat(g.num_features, g.num_classes, backend="pallas", attn_dropout=0.0)
    cli = PipelineCLIConfig(engine="compiled", schedule="1f1b", stages=4, chunks=4,
                            backend="pallas")
    engine = make_engine(model, cli.gpipe_config())
    plan = make_plan(g, 4, strategy="halo", halo_hops=2)
    optimizer = opt_lib.adam(5e-3, weight_decay=5e-4)
    params = engine.init_params(jax.random.PRNGKey(0))
    step, args = engine.step_program(
        params, optimizer.init(params), plan, jax.random.PRNGKey(1), optimizer
    )
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    compiled = step.lower(*abstract).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 1024**3  # v5e HBM


def test_ring_step_hops_sit_under_the_wire_scope(v5e_2x2, monkeypatch):
    """The 4-stage ring's 1F1B train step, one stage per chip of the
    v5e:2x2 (a small GCN on karate): the compiler keeps every ring hop
    (``collective-permute``) under the executor's ``pipe.wire`` scope, so a
    device profile reads the ring's collectives by name. The engine builds
    its mesh from ``jax.devices()``, so the test hands it the described
    chips."""
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import GPipeConfig, make_engine
    from repro.graphs import load_dataset
    from repro.models.gnn.net import build_gnn
    from repro.train import optimizer as opt_lib

    chips = list(v5e_2x2.devices)
    g = load_dataset("karate")
    model = build_gnn("gcn", g.num_features, g.num_classes, hidden=16, depth=4)
    engine = make_engine(model, GPipeConfig(
        balance=(2, 2, 2, 2), chunks=4, schedule="1f1b", engine="compiled"))
    plan = make_plan(g, 4, strategy="halo", halo_hops=1)
    optimizer = opt_lib.adam(1e-2)
    params = engine.init_params(jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "devices", lambda *a: chips)
    monkeypatch.setattr(jax, "device_count", lambda *a: len(chips))
    step, args = engine.step_program(
        params, optimizer.init(params), plan, jax.random.PRNGKey(1), optimizer)
    replicated = NamedSharding(Mesh(chips, ("stage",)), PartitionSpec())
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated), args)
    text = step.lower(*abstract).compile().as_text()
    hops = [line for line in text.splitlines()
            if re.search(r" collective-permute(?:-start|-done)?\(", line)]
    assert len(hops) >= 2  # the activation hop and the cotangent hop
    for hop in hops:
        found = re.search(r'op_name="([^"]*)"', hop)
        assert found and "pipe.wire" in found.group(1).split("/"), hop[:200]
