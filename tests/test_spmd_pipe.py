"""SPMD pipeline correctness on a multi-device (forced host) mesh.

These run in subprocesses so the 8-device XLA flag never leaks into the
main test process (smoke tests must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest


def _run(src: str, devices: int = 8, timeout: int = 1200):
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": "src",
        "JAX_PLATFORMS": "cpu",
    }
    r = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(src)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, **env},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


# The hand-written programs below run on Auto mesh axes, not the Explicit
# axes jax.make_mesh gives by default.
PRELUDE = textwrap.dedent("""
    import jax
    from jax.sharding import AxisType

    def auto_mesh(shape, names):
        return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))
""")


@pytest.mark.slow
def test_pipeline_fwd_grad_equivalence():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map
    from repro.core.spmd_pipe import spmd_pipeline, make_scanned_stage, make_gather_fn

    mesh = auto_mesh((2, 4), ('data', 'model'))
    D, S, PER, NM, B = 16, 4, 2, 4, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (S, PER, D, D)) * 0.3
    extras = {'active': jnp.ones((S, PER))}
    x = jax.random.normal(jax.random.PRNGKey(1), (NM, B // NM, D))

    def block_fn(lp, ex, h):
        return jnp.where(ex['active'] > 0, jnp.tanh(h @ lp['w']), h)

    def pipe(wp, ex, xm):
        gfn = make_gather_fn({'w': True}, 'data')
        stage_fn = make_scanned_stage(
            block_fn,
            jax.tree_util.tree_map(lambda a: a[0], wp),
            jax.tree_util.tree_map(lambda a: a[0], ex),
            gather_fn=gfn)
        out, _ = spmd_pipeline(stage_fn, xm, stage_axis='model', num_stages=S,
                               remat=True, vma_refs=(wp,))
        return out

    f = jax.jit(shard_map(pipe, mesh=mesh,
        in_specs=({'w': P('model', None, 'data', None)}, {'active': P('model', None)},
                  P(None, 'data', None)),
        out_specs=P(None, 'data', None)))
    out = f({'w': w}, extras, x)
    ref = x
    for s in range(S):
        for i in range(PER):
            ref = jnp.tanh(ref @ w[s, i])
    assert jnp.allclose(out, ref, atol=1e-5), float(jnp.max(jnp.abs(out - ref)))

    g1 = jax.grad(lambda wd: jnp.sum(f(wd, extras, x) ** 2) / 2)({'w': w})
    def loss_ref(wd):
        h = x
        for s in range(S):
            for i in range(PER):
                h = jnp.tanh(h @ wd['w'][s, i])
        return jnp.sum(h ** 2) / 2
    g2 = jax.grad(loss_ref)({'w': w})
    assert jnp.allclose(g1['w'], g2['w'], atol=1e-4), float(jnp.max(jnp.abs(g1['w'] - g2['w'])))
    print('EQUIV_OK')
    """)
    assert "EQUIV_OK" in out


@pytest.mark.slow
def test_pipeline_scatter_dim_equivalence():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map
    from repro.core.spmd_pipe import spmd_pipeline, make_scanned_stage

    mesh = auto_mesh((2, 4), ('data', 'model'))
    D, S, PER, NM, B, SEQ = 8, 4, 1, 2, 4, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (S, PER, D, D)) * 0.3
    ex = {'active': jnp.ones((S, PER))}
    x = jax.random.normal(jax.random.PRNGKey(1), (NM, B // NM, SEQ, D))

    def block_fn(lp, exx, h):
        return jnp.tanh(h @ lp['w'])

    def pipe(wp, exx, xm):
        stage_fn = make_scanned_stage(block_fn,
            jax.tree_util.tree_map(lambda a: a[0], wp),
            jax.tree_util.tree_map(lambda a: a[0], exx))
        out, _ = spmd_pipeline(stage_fn, xm, stage_axis='model', num_stages=S,
                               scatter_dim=2, vma_refs=(wp,))
        return out

    f = jax.jit(shard_map(pipe, mesh=mesh,
        in_specs=({'w': P('model', None, None, None)}, {'active': P('model', None)},
                  P(None, 'data', None, None)),
        out_specs=P(None, 'data', 'model', None)))
    out = f({'w': w}, ex, x)   # (NM, mb, SEQ, D) with SEQ sharded over model
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s, 0])
    assert out.shape == ref.shape
    assert jnp.allclose(out, ref, atol=1e-5), float(jnp.max(jnp.abs(out - ref)))
    print('SCATTER_OK')
    """)
    assert "SCATTER_OK" in out


@pytest.mark.slow
def test_stateful_pipeline_cache_writes():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map
    from repro.core.spmd_pipe import spmd_pipeline, make_scanned_stage_stateful

    mesh = auto_mesh((4,), ('model',))
    D, S, PER, NM, B = 8, 4, 1, 4, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (S, PER, D, D)) * 0.3
    ex = {'active': jnp.ones((S, PER))}
    x = jax.random.normal(jax.random.PRNGKey(1), (NM, B // NM, D))
    state = jnp.zeros((S, NM, PER, B // NM, D))  # per-layer cache of inputs

    def block_fn(lp, exx, h, cache_i):
        return jnp.tanh(h @ lp['w']), h  # cache the INPUT seen by each layer

    def pipe(wp, exx, xm, st):
        stage_fn = make_scanned_stage_stateful(block_fn,
            jax.tree_util.tree_map(lambda a: a[0], wp),
            jax.tree_util.tree_map(lambda a: a[0], exx))
        out, st2 = spmd_pipeline(stage_fn, xm, stage_axis='model', num_stages=S,
                                 state=st[0], vma_refs=(wp,))
        return out, st2[None]

    f = jax.jit(shard_map(pipe, mesh=mesh,
        in_specs=({'w': P('model', None, None, None)}, {'active': P('model', None)},
                  P(None, None, None), P('model', None, None, None, None)),
        out_specs=(P(None, None, None), P('model', None, None, None, None))))
    out, st2 = f({'w': w}, ex, x, state)
    # stage 0's cached input for microbatch m must equal x[m]
    st0 = st2[0]   # (NM, PER, mb, D)
    assert jnp.allclose(st0[:, 0], x, atol=1e-6)
    # stage 1's cached input must equal tanh(x @ w0)
    st1 = st2[1]
    assert jnp.allclose(st1[:, 0], jnp.tanh(x @ w[0, 0]), atol=1e-5)
    print('STATE_OK')
    """)
    assert "STATE_OK" in out


@pytest.mark.slow
def test_interleaved_pipeline_fwd_grad_equivalence():
    """Circular/interleaved pipeline: D devices x V virtual stages each;
    forward matches the sequential reference and grads flow through the
    ppermute ring + rotating chunk buffer."""
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map
    from repro.core.spmd_pipe import spmd_pipeline_interleaved, make_interleaved_stage

    mesh = auto_mesh((2,), ('model',))
    D, V, PER, NM, B, Dm = 2, 2, 2, 4, 8, 16
    S = D * V
    w = jax.random.normal(jax.random.PRNGKey(0), (S, PER, Dm, Dm)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (NM, B // NM, Dm))

    def dev_stack(a):   # (S, ...) -> (D, V, ...); device d holds stages v*D + d
        return jnp.stack([jnp.stack([a[v*D + d] for v in range(V)]) for d in range(D)])

    wp = {'w': dev_stack(w)}
    ex = {'active': dev_stack(jnp.ones((S, PER)))}

    def block_fn(lp, exx, h):
        return jnp.where(exx['active'] > 0, jnp.tanh(h @ lp['w']), h)

    def pipe(wpp, exx, xm):
        stage_fn = make_interleaved_stage(
            block_fn,
            jax.tree_util.tree_map(lambda a: a[0], wpp),
            jax.tree_util.tree_map(lambda a: a[0], exx))
        return spmd_pipeline_interleaved(stage_fn, xm, stage_axis='model',
                                         num_devices=D, num_virtual=V,
                                         remat=True, vma_refs=(wpp,))

    f = jax.jit(shard_map(pipe, mesh=mesh,
        in_specs=({'w': P('model')}, {'active': P('model')}, P()),
        out_specs=P()))
    out = f(wp, ex, x)
    ref = x
    for k in range(S):
        for i in range(PER):
            ref = jnp.tanh(ref @ w[k, i])
    assert jnp.allclose(out, ref, atol=1e-5), float(jnp.max(jnp.abs(out - ref)))

    g1 = jax.grad(lambda wd: jnp.sum(f(wd, ex, x) ** 2) / 2)(wp)
    def loss_ref(wd):
        h = x
        for k in range(S):
            for i in range(PER):
                h = jnp.tanh(h @ wd[k, i])
        return jnp.sum(h ** 2) / 2
    g2 = dev_stack(jax.grad(loss_ref)(w))
    assert jnp.allclose(g1['w'], g2, atol=1e-4), float(jnp.max(jnp.abs(g1['w'] - g2)))

    # C == D edge (same-tick buffer write/read) and deeper virtual stacks
    mesh4 = auto_mesh((4,), ('model',))
    for D2, V2, NM2 in [(4, 2, 4), (4, 3, 8)]:
        S2 = D2 * V2
        w2 = jax.random.normal(jax.random.PRNGKey(2), (S2, 1, Dm, Dm)) * 0.3
        x2 = jax.random.normal(jax.random.PRNGKey(3), (NM2, 4, Dm))
        def ds(a, D=D2, V=V2):
            return jnp.stack([jnp.stack([a[v*D + d] for v in range(V)]) for d in range(D)])
        def pipe2(wpp, xm, D=D2, V=V2):
            sf = make_interleaved_stage(lambda lp, e, h: jnp.tanh(h @ lp),
                                        jax.tree_util.tree_map(lambda a: a[0], wpp),
                                        jax.tree_util.tree_map(lambda a: a[0], wpp) * 0)
            return spmd_pipeline_interleaved(sf, xm, stage_axis='model',
                                             num_devices=D, num_virtual=V, vma_refs=(wpp,))
        f2 = jax.jit(shard_map(pipe2, mesh=mesh4, in_specs=(P('model'), P()),
                               out_specs=P()))
        o2 = f2(ds(w2), x2)
        r2 = x2
        for k in range(S2):
            r2 = jnp.tanh(r2 @ w2[k, 0])
        assert jnp.allclose(o2, r2, atol=1e-5), float(jnp.max(jnp.abs(o2 - r2)))
    print('INTERLEAVED_OK')
    """)
    assert "INTERLEAVED_OK" in out


@pytest.mark.slow
def test_multidevice_train_smoke_all_paths():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch, ShapeConfig
    from repro.models.transformer.model import Topology, init_params, make_train_step
    from repro.data.tokens import token_batch

    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    shape = ShapeConfig('smoke', 64, 8, 'train')
    for name in ['qwen2.5-32b', 'arctic-480b', 'musicgen-large', 'glm4-9b']:
        cfg = get_arch(name, smoke=True)
        topo = Topology(num_stages=4, fsdp_size=2, num_micro=2, loss_chunks=2)
        art = make_train_step(cfg, topo, shape, mesh, dtype=jnp.float32)
        params = jax.device_put(
            init_params(cfg, jax.random.PRNGKey(0), num_stages=4, dtype=jnp.float32),
            art.in_shardings[0])
        opt_state = art.meta['optimizer'].init(params)
        s_front = int(shape.seq_len * cfg.frontend_frac) if cfg.frontend != 'none' else 0
        batch = {'tokens': jnp.asarray(token_batch(batch=8, seq=shape.seq_len - s_front,
                                                   vocab=cfg.vocab_size))}
        if s_front:
            from repro.data.tokens import frontend_embeds
            batch['frontend_embeds'] = jnp.asarray(frontend_embeds(
                batch=8, seq=s_front, d_model=cfg.d_model))
        _, _, m = jax.jit(art.fn, in_shardings=art.in_shardings,
                          out_shardings=art.out_shardings)(params, opt_state, batch)
        assert np.isfinite(float(m['loss'])), name
    print('MD_SMOKE_OK')
    """, timeout=2400)
    assert "MD_SMOKE_OK" in out
