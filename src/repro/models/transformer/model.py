"""Full model assembly: embed → SPMD-pipelined block stack → head.

``Topology`` captures the mesh contract of DESIGN.md §5:

  * ``stage_axis`` ("model") — pipeline stages (the paper's technique);
  * ``fsdp_axis`` ("data")   — data parallel + ZeRO-3 param sharding +
    expert parallelism for MoE;
  * ``pod_axis`` ("pod")     — cross-pod data parallelism (optional);
  * ``num_micro``            — GPipe chunks per step.

Parameter layout: block leaves are stacked (num_stages, layers_per_stage,
*dims); ``param_layout`` assigns each leaf fsdp / expert / replicated
placement, used both for pjit in_shardings and for the in-pipeline ZeRO-3
gather. Embedding/head live outside the pipeline (DESIGN.md §5); the head's
vocab dim is sharded over the stage axis and the loss runs in a scan over
batch chunks so full-vocab logits are never materialized at full batch.

Step builders return ``StepArtifacts`` — fn + shardings + abstract inputs —
consumed identically by the training driver, the multi-pod dry-run, and
tests.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig, pipeline_padding
from repro.core.spmd_pipe import (
    make_gather_fn,
    make_interleaved_stage,
    make_scanned_stage,
    make_scanned_stage_stateful,
    spmd_pipeline,
    spmd_pipeline_interleaved,
)
from repro.models.transformer import blocks as B
from repro.models.transformer.common import normal_init, rms_norm, softcap
from repro.train import optimizer as opt_lib
from repro.train.losses import softmax_xent


@dataclasses.dataclass(frozen=True)
class Topology:
    num_stages: int  # TOTAL pipeline stages (virtual stages when interleaved)
    stage_axis: str = "model"
    fsdp_axis: str = "data"
    pod_axis: str | None = None
    fsdp_size: int = 1
    num_micro: int = 1
    moe_mode: str = "gathered"  # "gathered" | "a2a"
    zero3: bool = True  # False: blocks replicated over fsdp (ZeRO-1 only)
    attn_backend: str = "blocked"  # "blocked" (jnp) | "flash" (Pallas kernel)
    remat: bool = True
    seq_shard_decode: bool = False  # long_500k: shard KV seq over fsdp axis
    kv_block: int = 512
    loss_chunks: int = 8
    schedule: str = "fill_drain"  # "fill_drain" | "interleaved"
    num_virtual: int = 1  # interleaved: V virtual stages per physical device

    @property
    def data_axes(self):
        return (self.pod_axis, self.fsdp_axis) if self.pod_axis else (self.fsdp_axis,)

    @property
    def ep_enabled(self) -> bool:
        return self.fsdp_size > 1

    @property
    def pipe_devices(self) -> int:
        """Physical devices on the stage axis: num_stages for fill-drain,
        num_stages / num_virtual for the interleaved (circular) schedule."""
        if self.schedule != "interleaved":
            return self.num_stages
        if self.num_virtual < 1 or self.num_stages % self.num_virtual:
            raise ValueError(
                f"num_virtual ({self.num_virtual}) must divide num_stages ({self.num_stages})"
            )
        return self.num_stages // self.num_virtual


# ------------------------------------------------------------- stacking --


def _hybrid_layout(cfg: ArchConfig, num_stages: int) -> tuple[int, int]:
    """(mamba_slots_per_stage, total_slots_per_stage): the attention slot is
    the last of each ``hybrid_attn_every`` group."""
    every = cfg.hybrid_attn_every
    per, _ = pipeline_padding(cfg.num_layers, num_stages)
    per = math.ceil(per / every) * every
    return per - per // every, per


def stacked_shape_plan(cfg: ArchConfig, num_stages: int) -> dict:
    if cfg.arch_type == "hybrid":
        m_per, per = _hybrid_layout(cfg, num_stages)
        return {
            "per_stage": per,
            "mamba_per_stage": m_per,
            "attn_per_stage": per // cfg.hybrid_attn_every,
        }
    per, pad = pipeline_padding(cfg.num_layers, num_stages)
    return {"per_stage": per, "pad": pad}


def init_params(cfg: ArchConfig, key: jax.Array, *, num_stages: int, dtype=jnp.bfloat16) -> dict:
    plan = stacked_shape_plan(cfg, num_stages)
    k_embed, k_head, k_blocks, k_shared, k_mtp = jax.random.split(key, 5)

    params: dict[str, Any] = {
        "embed": normal_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype=dtype),
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal_init(k_head, (cfg.d_model, cfg.vocab_size), dtype=dtype)
    if cfg.mtp:
        params["mtp_proj"] = normal_init(k_mtp, (cfg.d_model, cfg.d_model), dtype=dtype)

    if cfg.arch_type == "hybrid":
        lead = plan["mamba_per_stage"]
        init_one = lambda k: B.init_mamba_block(cfg, k, dtype=dtype)
        params["shared_attn"] = B.init_block(cfg, k_shared, dtype=dtype)
    elif cfg.arch_type == "ssm":
        lead = plan["per_stage"]
        init_one = lambda k: B.init_mamba_block(cfg, k, dtype=dtype)
    else:
        lead = plan["per_stage"]
        init_one = lambda k: B.init_block(cfg, k, dtype=dtype)
    stack = jax.vmap(init_one)(jax.random.split(k_blocks, num_stages * lead))
    params["blocks"] = jax.tree_util.tree_map(
        lambda a: a.reshape(num_stages, lead, *a.shape[1:]), stack
    )
    return params


def make_extras(cfg: ArchConfig, num_stages: int, *, long_context: bool = False) -> dict:
    """Per-layer-slot metadata, stacked (num_stages, slots)."""
    plan = stacked_shape_plan(cfg, num_stages)
    per = plan["per_stage"]
    wins_src = cfg.layer_windows(long_context=long_context)
    if cfg.arch_type == "hybrid":
        every = cfg.hybrid_attn_every
        m_per, a_per = plan["mamba_per_stage"], plan["attn_per_stage"]
        active_m = np.zeros((num_stages, m_per), np.float32)
        active_a = np.zeros((num_stages, a_per), np.float32)
        win_a = np.zeros((num_stages, a_per), np.int32)
        for s in range(num_stages):
            mi = ai = 0
            for i in range(per):
                g = s * per + i
                if (i % every) == (every - 1):
                    active_a[s, ai] = float(g < cfg.num_layers)
                    win_a[s, ai] = wins_src[min(g, cfg.num_layers - 1)]
                    ai += 1
                else:
                    active_m[s, mi] = float(g < cfg.num_layers)
                    mi += 1
        return {
            "mamba": {"active": jnp.asarray(active_m)},
            "attn": {"active": jnp.asarray(active_a), "window": jnp.asarray(win_a)},
        }
    total = num_stages * per
    active = (np.arange(total) < cfg.num_layers).astype(np.float32).reshape(num_stages, per)
    wins = np.asarray(wins_src + [0] * (total - len(wins_src)), np.int32).reshape(num_stages, per)
    return {"active": jnp.asarray(active), "window": jnp.asarray(wins)}


def extras_specs(cfg: ArchConfig, topo: Topology):
    def sp(a):
        return P(topo.stage_axis, None)

    return jax.tree_util.tree_map(sp, make_extras(cfg, topo.num_stages))


# ------------------------------------------------------- sharding layout --


def _path_names(path) -> list[str]:
    return [str(getattr(p_, "key", getattr(p_, "name", p_))) for p_ in path]


def param_layout(cfg: ArchConfig, params_shapes: Any, topo: Topology) -> tuple[Any, Any]:
    """-> (PartitionSpec pytree, ZeRO-3 gather-mask pytree of bool)."""
    fsdp, stage = topo.fsdp_axis, topo.stage_axis
    use_ep = topo.fsdp_size > 1
    use_fsdp = use_ep and topo.zero3

    def spec_for(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        top = names[0]
        if top == "embed":
            # replicated: a vocab-sharded table turns every lookup into a
            # (B,S,D)-sized all-reduce (measured 1 GiB/step on codeqwen);
            # ZeRO-1 shards its optimizer moments instead (moment_specs)
            return P(None, None)
        if top == "head":
            # replicated: the pipeline reduce-scatters its output over the
            # stage axis along seq, so the head matmul is already distributed
            return P(None, None)
        if top in ("final_ln", "mtp_proj"):
            return P(*([None] * len(shape)))
        if top == "shared_attn":
            if use_fsdp and len(shape) >= 2 and shape[0] % topo.fsdp_size == 0:
                return P(fsdp, *([None] * (len(shape) - 1)))
            return P(*([None] * len(shape)))
        # blocks: (S, per, *dims)
        dims = shape[2:]
        if any(n.startswith("we_") for n in names):
            # expert parallelism is orthogonal to ZeRO: stays sharded
            ax = fsdp if use_ep else None
            return P(stage, None, ax, *([None] * (len(dims) - 1)))
        if use_fsdp and len(dims) >= 2 and dims[0] % topo.fsdp_size == 0:
            return P(stage, None, fsdp, *([None] * (len(dims) - 1)))
        return P(stage, None, *([None] * len(dims)))

    def gather_for(path, leaf):
        if not use_fsdp:
            return False
        names = _path_names(path)
        top = names[0]
        if top == "shared_attn":
            return len(leaf.shape) >= 2 and leaf.shape[0] % topo.fsdp_size == 0
        if top != "blocks":
            return False
        if any(n.startswith("we_") for n in names):
            return False  # expert-parallel: stays local
        dims = leaf.shape[2:]
        return len(dims) >= 2 and dims[0] % topo.fsdp_size == 0

    specs = jax.tree_util.tree_map_with_path(spec_for, params_shapes)
    gather = jax.tree_util.tree_map_with_path(gather_for, params_shapes)
    return specs, gather


def moment_specs(cfg: ArchConfig, params_shapes: Any, topo: Topology) -> Any:
    """Optimizer-moment shardings: like param specs, but replicated embed /
    head moments are ZeRO-1 sharded over the fsdp axis (f32 moments are 4×
    the bf16 params — sharding them is the bulk of ZeRO-1's win)."""
    specs, _ = param_layout(cfg, params_shapes, topo)
    if topo.fsdp_size <= 1:
        return specs
    out = dict(specs)
    vocab, d = cfg.vocab_size, cfg.d_model
    if "embed" in out and vocab % topo.fsdp_size == 0:
        out["embed"] = P(topo.fsdp_axis, None)
    elif "embed" in out and d % topo.fsdp_size == 0:
        out["embed"] = P(None, topo.fsdp_axis)
    if "head" in out and vocab % topo.fsdp_size == 0:
        out["head"] = P(None, topo.fsdp_axis)
    return out


# ------------------------------------------------------------ embeddings --


def embed_inputs(cfg: ArchConfig, params: dict, batch: dict) -> jax.Array:
    x = params["embed"][batch["tokens"]]  # (B, S_text, d)
    if cfg.frontend != "none":
        x = jnp.concatenate([batch["frontend_embeds"].astype(x.dtype), x], axis=1)
    return x


def make_positions(cfg: ArchConfig, seq: int) -> jax.Array:
    """(S,) rope positions, or (3, S) for m-rope (image grid then text).
    Concrete (numpy-backed) so pipeline bodies may close over it."""
    if cfg.rope_kind != "mrope":
        return jnp.arange(seq, dtype=jnp.int32)
    s_front = int(seq * cfg.frontend_frac) if cfg.frontend != "none" else 0
    side = max(1, int(math.sqrt(max(s_front, 1))))
    idx = np.arange(seq)
    t = np.where(idx < s_front, 0, idx - s_front + 1)
    hh = np.where(idx < s_front, (idx // side) % side, idx - s_front + 1)
    ww = np.where(idx < s_front, idx % side, idx - s_front + 1)
    return jnp.asarray(np.stack([t, hh, ww]), jnp.int32)


def lm_head_logits(cfg: ArchConfig, params: dict, y: jax.Array) -> jax.Array:
    y = rms_norm(y, params["final_ln"], eps=cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (y @ head).astype(jnp.float32)
    return softcap(logits, cfg.logit_softcap)


# --------------------------------------------------------- stage builders --


def _train_block_fn(cfg, topo, positions):
    """Homogeneous per-layer train body ``block_fn(lp, ex, h) -> h`` shared by
    the fill-drain (``make_scanned_stage``) and interleaved
    (``make_interleaved_stage``) stage builders. Hybrid stacks are
    heterogeneous and keep their dedicated ``_hybrid_stage``."""
    if cfg.arch_type == "hybrid":
        raise NotImplementedError("hybrid stacks have no homogeneous block fn")
    if cfg.arch_type == "ssm":
        return lambda lp, ex, h: B.mamba_block_train(cfg, lp, ex, h)
    ep = bool(cfg.num_experts) and topo.ep_enabled
    return lambda lp, ex, h: B.block_train(
        cfg, lp, ex, h, positions=positions,
        ep_axis=topo.fsdp_axis if ep else None, ep_size=topo.fsdp_size if ep else 1,
        moe_mode=topo.moe_mode, kv_block=topo.kv_block,
        attn_backend=topo.attn_backend,
    )


def _stage_fn_train(cfg, topo, blocks_local, shared, extras_local, gather_mask, positions):
    gfn = make_gather_fn(gather_mask["blocks"], topo.fsdp_axis) if topo.fsdp_size > 1 else None
    if cfg.arch_type == "hybrid":
        return _hybrid_stage(
            cfg, topo, blocks_local, shared, extras_local, gather_mask, positions,
            mode="train",
        )
    block = _train_block_fn(cfg, topo, positions)
    return make_scanned_stage(block, blocks_local, extras_local, gather_fn=gfn)


def _stage_fn_train_interleaved(cfg, topo, blocks_local, extras_local, gather_mask, positions):
    """Interleaved twin of ``_stage_fn_train``: ``blocks_local`` leaves are
    (num_virtual, layers_per_stage, ...) — this device's circularly-placed
    virtual-stage slices."""
    if cfg.arch_type == "hybrid":
        raise NotImplementedError(
            "interleaved schedule requires a homogeneous block stack; "
            "zamba2-style hybrid stages run fill_drain"
        )
    gfn = make_gather_fn(gather_mask["blocks"], topo.fsdp_axis) if topo.fsdp_size > 1 else None
    block = _train_block_fn(cfg, topo, positions)
    return make_interleaved_stage(block, blocks_local, extras_local, gather_fn=gfn)


def _stage_fn_prefill(cfg, topo, blocks_local, shared, extras_local, gather_mask, positions):
    gfn = make_gather_fn(gather_mask["blocks"], topo.fsdp_axis) if topo.fsdp_size > 1 else None
    if cfg.arch_type == "ssm":
        return make_scanned_stage_stateful(
            lambda lp, ex, h, c: B.mamba_block_prefill(cfg, lp, ex, h, c),
            blocks_local, extras_local, gather_fn=gfn,
        )
    if cfg.arch_type == "hybrid":
        return _hybrid_stage(
            cfg, topo, blocks_local, shared, extras_local, gather_mask, positions,
            mode="prefill",
        )
    ep = bool(cfg.num_experts) and topo.ep_enabled
    block = lambda lp, ex, h, c: B.block_prefill(
        cfg, lp, ex, h, c, positions=positions,
        ep_axis=topo.fsdp_axis if ep else None, ep_size=topo.fsdp_size if ep else 1,
        moe_mode=topo.moe_mode, kv_block=topo.kv_block,
    )
    return make_scanned_stage_stateful(block, blocks_local, extras_local, gather_fn=gfn)


def _stage_fn_decode(cfg, topo, blocks_local, shared, extras_local, gather_mask, cur_pos):
    gfn = make_gather_fn(gather_mask["blocks"], topo.fsdp_axis) if topo.fsdp_size > 1 else None
    seq_axis = topo.fsdp_axis if topo.seq_shard_decode else None
    seq_shards = topo.fsdp_size if topo.seq_shard_decode else 1
    if cfg.arch_type == "ssm":
        return make_scanned_stage_stateful(
            lambda lp, ex, h, c: B.mamba_block_decode(cfg, lp, ex, h, c),
            blocks_local, extras_local, gather_fn=gfn,
        )
    if cfg.arch_type == "hybrid":
        return _hybrid_stage(
            cfg, topo, blocks_local, shared, extras_local, gather_mask, cur_pos,
            mode="decode",
        )
    # batch-replicated decode (long_500k) runs EP with replicated tokens
    ep = bool(cfg.num_experts) and topo.ep_enabled
    block = lambda lp, ex, h, c: B.block_decode(
        cfg, lp, ex, h, c, cur_pos=cur_pos,
        ep_axis=topo.fsdp_axis if ep else None, ep_size=topo.fsdp_size if ep else 1,
        moe_mode="replicated" if (ep and topo.seq_shard_decode) else topo.moe_mode,
        seq_axis=seq_axis, seq_shards=seq_shards,
    )
    return make_scanned_stage_stateful(block, blocks_local, extras_local, gather_fn=gfn)


def _hybrid_stage(cfg, topo, m_params, shared, extras_local, gather_mask, pos_or_cur, *, mode):
    """zamba2 stage: groups of mamba slots, each followed by one application
    of the weight-shared attention block. State (prefill/decode):
    {'mamba': leaves (m_per, ...), 'attn': leaves (a_per, ...)}."""
    m_ex = extras_local["mamba"]
    a_ex = extras_local["attn"]
    n_attn = a_ex["active"].shape[0]
    m_total = jax.tree_util.tree_leaves(m_params)[0].shape[0]
    m_grp = m_total // max(n_attn, 1)
    gfn = make_gather_fn(gather_mask["blocks"], topo.fsdp_axis) if topo.fsdp_size > 1 else None
    sgfn = (
        make_gather_fn(gather_mask["shared_attn"], topo.fsdp_axis)
        if topo.fsdp_size > 1
        else None
    )
    seq_axis = topo.fsdp_axis if topo.seq_shard_decode else None
    seq_shards = topo.fsdp_size if topo.seq_shard_decode else 1

    def slice_group(tree, g):
        return jax.tree_util.tree_map(lambda a: a[g * m_grp : (g + 1) * m_grp], tree)

    def stage_fn(h, state_mb):
        new_state = {"mamba": [], "attn": []} if mode != "train" else None

        def one_mamba(c, xs):
            if mode == "train":
                lp, ex = xs
                if gfn is not None:
                    lp = gfn(lp)
                return B.mamba_block_train(cfg, lp, ex, c), None
            lp, ex, cache_i = xs
            if gfn is not None:
                lp = gfn(lp)
            fn = B.mamba_block_prefill if mode == "prefill" else B.mamba_block_decode
            return fn(cfg, lp, ex, c, cache_i)

        for g in range(max(n_attn, 1)):
            grp, grp_ex = slice_group(m_params, g), slice_group(m_ex, g)
            if mode == "train":
                h, _ = lax.scan(one_mamba, h, (grp, grp_ex))
            else:
                grp_cache = slice_group(state_mb["mamba"], g)
                h, cache_out = lax.scan(one_mamba, h, (grp, grp_ex, grp_cache))
                new_state["mamba"].append(cache_out)
            if n_attn:
                sp = sgfn(shared) if sgfn is not None else shared
                ex_g = jax.tree_util.tree_map(lambda a: a[g], a_ex)
                if mode == "train":
                    h = B.block_train(cfg, sp, ex_g, h, positions=pos_or_cur, kv_block=topo.kv_block)
                elif mode == "prefill":
                    a_cache = jax.tree_util.tree_map(lambda a: a[g], state_mb["attn"])
                    h, a_out = B.block_prefill(
                        cfg, sp, ex_g, h, a_cache, positions=pos_or_cur, kv_block=topo.kv_block
                    )
                    new_state["attn"].append(a_out)
                else:
                    a_cache = jax.tree_util.tree_map(lambda a: a[g], state_mb["attn"])
                    h, a_out = B.block_decode(
                        cfg, sp, ex_g, h, a_cache, cur_pos=pos_or_cur,
                        seq_axis=seq_axis, seq_shards=seq_shards,
                    )
                    new_state["attn"].append(a_out)
        if mode == "train":
            return h, state_mb
        stacked = {
            "mamba": jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0), *new_state["mamba"]
            ),
            "attn": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *new_state["attn"]),
        }
        return h, stacked

    return stage_fn


# ------------------------------------------------------------ step fns --


@dataclasses.dataclass
class StepArtifacts:
    fn: Callable
    in_shardings: Any
    out_shardings: Any
    abstract_inputs: tuple  # ShapeDtypeStructs matching fn's signature
    meta: dict


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def _abstract_params(cfg: ArchConfig, topo: Topology, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda k: init_params(cfg, k, num_stages=topo.num_stages, dtype=dtype),
        jax.random.PRNGKey(0),
    )


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, topo: Topology) -> tuple[dict, dict]:
    """(abstract batch, PartitionSpec tree) for one step's input batch."""
    bsz, seq = shape.global_batch, shape.seq_len
    data = topo.data_axes if bsz > 1 else (None,)
    d_axes = data[0] if len(data) == 1 else data
    if shape.kind == "decode":
        batch = {"tokens": jax.ShapeDtypeStruct((bsz,), jnp.int32),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        specs = {"tokens": P(d_axes) if bsz > 1 else P(None), "pos": P()}
        return batch, specs
    s_front = int(seq * cfg.frontend_frac) if cfg.frontend != "none" else 0
    s_text = seq - s_front + (1 if shape.kind == "train" else 0)  # train carries labels
    batch = {"tokens": jax.ShapeDtypeStruct((bsz, s_text), jnp.int32)}
    specs = {"tokens": P(d_axes, None)}
    if cfg.frontend != "none":
        batch["frontend_embeds"] = jax.ShapeDtypeStruct((bsz, s_front, cfg.d_model), jnp.bfloat16)
        specs["frontend_embeds"] = P(d_axes, None, None)
    return batch, specs


def _labels_from_batch(cfg: ArchConfig, batch: dict, seq: int) -> tuple[jax.Array, jax.Array]:
    """(labels (B, S), mask (B, S)) aligned with the concatenated sequence."""
    toks = batch["tokens"]
    bsz = toks.shape[0]
    s_front = seq - (toks.shape[1] - 1)
    labels_text = toks[:, 1:]
    if s_front > 0:
        pad = jnp.full((bsz, s_front), -1, jnp.int32)
        labels = jnp.concatenate([pad, labels_text], axis=1)
    else:
        labels = labels_text
    return labels, (labels >= 0).astype(jnp.float32)


def _auto_axes(mesh):
    """``mesh`` with Auto axes. The step builders place values with
    ``with_sharding_constraint``, which Explicit axes (``jax.make_mesh``'s
    default) refuse."""
    return jax.sharding.Mesh(
        mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * len(mesh.axis_names)
    )


def make_train_step(
    cfg: ArchConfig,
    topo: Topology,
    shape: ShapeConfig,
    mesh,
    *,
    lr: float = 1e-4,
    dtype=jnp.bfloat16,
) -> StepArtifacts:
    mesh = _auto_axes(mesh)
    seq = shape.seq_len
    positions = make_positions(cfg, seq)
    extras = make_extras(cfg, topo.num_stages)
    aparams = _abstract_params(cfg, topo, dtype)
    specs, gather_mask = param_layout(cfg, aparams, topo)
    optimizer = opt_lib.adam(lr)
    aopt = jax.eval_shape(optimizer.init, aparams)
    m_specs = moment_specs(cfg, aparams, topo)
    opt_specs = opt_lib.AdamState(step=P(), mu=m_specs, nu=m_specs)
    abatch, bspecs = batch_specs(cfg, shape, topo)
    ex_specs = jax.tree_util.tree_map(lambda a: P(topo.stage_axis, None), extras)
    xspec = P(topo.data_axes, None, None)

    if topo.schedule not in ("fill_drain", "interleaved"):
        raise ValueError(
            f"Topology.schedule must be 'fill_drain' or 'interleaved', got {topo.schedule!r}"
        )
    interleaved = topo.schedule == "interleaved" and topo.num_stages > 1
    if interleaved:
        D, V = topo.pipe_devices, topo.num_virtual
        if topo.num_micro < D:
            raise ValueError(
                f"interleaved schedule needs num_micro ({topo.num_micro}) >= "
                f"physical stage devices ({D})"
            )
        # circular placement: device d hosts virtual stages {v·D + d}; the
        # stacked (S, per, ...) leaves are row-permuted so the contiguous
        # V-row shard each device receives under P(stage_axis, ...) is
        # exactly its virtual-stage slices
        circ = np.array([v * D + d for d in range(D) for v in range(V)])
        extras = jax.tree_util.tree_map(lambda a: a[circ], extras)

    def loss_fn(params, batch):
        inputs = dict(batch, tokens=batch["tokens"][:, :-1])
        x = embed_inputs(cfg, params, inputs).astype(dtype)
        x = lax.with_sharding_constraint(x, NamedSharding(mesh, xspec))

        def pipe_body(blocks, shared, ex, x_local):
            b_local = x_local.shape[0]
            x_mb = x_local.reshape(topo.num_micro, b_local // topo.num_micro, seq, -1)
            if interleaved:
                # blocks/ex arrive as this device's (V, per, ...) shard
                stage_fn = _stage_fn_train_interleaved(
                    cfg, topo, blocks, ex, gather_mask, positions
                )
                out = spmd_pipeline_interleaved(
                    stage_fn, x_mb, stage_axis=topo.stage_axis,
                    num_devices=D, num_virtual=V, remat=topo.remat,
                    vma_refs=(blocks, shared),
                )
                return out.reshape(b_local, seq, -1)
            blocks_local = jax.tree_util.tree_map(lambda a: a[0], blocks)
            ex_local = jax.tree_util.tree_map(lambda a: a[0], ex)
            stage_fn = _stage_fn_train(
                cfg, topo, blocks_local, shared, ex_local, gather_mask, positions
            )
            # reduce-scatter output along seq over the stage axis: the LM
            # head + loss then run stage-sharded instead of 16×-replicated
            out, _ = spmd_pipeline(
                stage_fn, x_mb, stage_axis=topo.stage_axis,
                num_stages=topo.num_stages, remat=topo.remat, scatter_dim=2,
                vma_refs=(blocks_local, shared),
            )
            return out.reshape(b_local, seq // topo.num_stages, -1)

        shared = params.get("shared_attn", ())
        shared_spec = specs.get("shared_attn", ())
        blocks_in = params["blocks"]
        if interleaved:
            blocks_in = jax.tree_util.tree_map(lambda a: a[circ], blocks_in)
            # outputs are psum-broadcast (not seq-scattered) on the ring
            yspec = P(topo.data_axes, None, None)
        else:
            yspec = P(topo.data_axes, topo.stage_axis, None)
        y = jax.shard_map(
            pipe_body,
            mesh=mesh,
            in_specs=(specs["blocks"], shared_spec, ex_specs, xspec),
            out_specs=yspec,
        )(blocks_in, shared, extras, x)

        labels, mask = _labels_from_batch(cfg, batch, seq)
        bsz = y.shape[0]
        chunks = min(topo.loss_chunks, bsz)
        # chunk along the MINOR batch dim so each device keeps its own rows
        # (a major-dim chunking would all-to-all the whole activation)
        chunk_spec = NamedSharding(mesh, P(None, topo.data_axes, topo.stage_axis, None))
        yc = lax.with_sharding_constraint(
            jnp.swapaxes(y.reshape(bsz // chunks, chunks, seq, -1), 0, 1), chunk_spec
        )
        lc = jnp.swapaxes(labels.reshape(bsz // chunks, chunks, seq), 0, 1)
        mc = jnp.swapaxes(mask.reshape(bsz // chunks, chunks, seq), 0, 1)
        logit_spec = NamedSharding(mesh, P(topo.data_axes, topo.stage_axis, None))

        @jax.checkpoint
        def chunk_loss(carry, xs):
            yi, li, mi = xs
            logits = lax.with_sharding_constraint(lm_head_logits(cfg, params, yi), logit_spec)
            # masked mean accumulated as (sum, count)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
            s, c = carry
            s = s + ((lse - ll) * mi).sum()
            c = c + mi.sum()
            if cfg.mtp:
                # multi-token prediction aux head (deepseek-v3): predict t+2
                y2 = (yi @ params["mtp_proj"]).astype(yi.dtype)
                logits2 = lax.with_sharding_constraint(
                    lm_head_logits(cfg, params, y2), logit_spec
                )[:, :-1]
                li2 = jnp.maximum(li[:, 1:], 0)
                mi2 = mi[:, 1:] * mi[:, :-1]
                lse2 = jax.nn.logsumexp(logits2, axis=-1)
                ll2 = jnp.take_along_axis(logits2, li2[..., None], axis=-1)[..., 0]
                s = s + 0.3 * ((lse2 - ll2) * mi2).sum()
            return (s, c), None

        (s, c), _ = lax.scan(chunk_loss, (jnp.zeros(()), jnp.zeros(())), (yc, lc, mc))
        return s / jnp.maximum(c, 1.0)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    in_sh = (_named(mesh, specs), _named(mesh, opt_specs), _named(mesh, bspecs))
    out_sh = (_named(mesh, specs), _named(mesh, opt_specs), {"loss": NamedSharding(mesh, P())})
    return StepArtifacts(
        fn=train_step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        abstract_inputs=(aparams, aopt, abatch),
        meta={"positions": positions, "extras": extras, "optimizer": optimizer,
              "specs": specs, "gather_mask": gather_mask},
    )


# --------------------------------------------------------------- caches --


def cache_plan(cfg: ArchConfig, topo: Topology, shape: ShapeConfig) -> dict:
    """Static cache geometry for decode/prefill shapes."""
    bsz = shape.global_batch
    nm = topo.num_micro
    b_mb = max(bsz // nm, 1)
    if shape.kind == "decode":
        if topo.seq_shard_decode:
            w_total = cfg.long_context_window if not cfg.is_subquadratic() else cfg.long_context_window
            # windows already reflected in layer_windows(long_context=True);
            # cache width = max window, sharded over fsdp
            w_total = max(w for w in cfg.layer_windows(long_context=True)) if cfg.arch_type not in ("ssm",) else 0
            w_local = w_total // topo.fsdp_size if w_total else 0
        else:
            w_total = shape.seq_len + 16
            w_local = w_total
    else:
        w_total = w_local = shape.seq_len
    return {"b_mb": b_mb, "w_total": w_total, "w_local": w_local, "nm": nm}


def abstract_cache(cfg: ArchConfig, topo: Topology, shape: ShapeConfig, *, dtype=jnp.bfloat16):
    """(abstract cache pytree, PartitionSpec tree). Global leaves are
    (num_stages, num_micro, slots, b_mb, ...)."""
    plan = cache_plan(cfg, topo, shape)
    sp = stacked_shape_plan(cfg, topo.num_stages)
    nm, b_mb, w_local = plan["nm"], plan["b_mb"], plan["w_local"]
    S = topo.num_stages
    stage, fsdp = topo.stage_axis, topo.fsdp_axis
    batch_axes = topo.data_axes if shape.global_batch > 1 else None
    seq_ax = fsdp if topo.seq_shard_decode else None

    def attn_leaf(inner_shape, *, has_batch=True, seq_dim=None, dt=dtype):
        shp = (S, nm, slots, b_mb, *inner_shape) if has_batch else (S, nm, slots, *inner_shape)
        ax = [stage, None, None]
        if has_batch:
            ax.append(batch_axes)
        for i in range(len(inner_shape)):
            ax.append(seq_ax if (seq_dim is not None and i == seq_dim) else None)
        return jax.ShapeDtypeStruct(shp, dt), P(*ax)

    def build_attn(slots_):
        nonlocal slots
        slots = slots_
        if cfg.attn_kind == "mla":
            c, cs = attn_leaf((w_local, cfg.kv_lora_rank + cfg.qk_rope_head_dim), seq_dim=0)
            return {"ckv": c}, {"ckv": cs}
        k, ks = attn_leaf((w_local, cfg.num_kv_heads, cfg.head_dim), seq_dim=0)
        v, vs = attn_leaf((w_local, cfg.num_kv_heads, cfg.head_dim), seq_dim=0)
        return {"k": k, "v": v}, {"k": ks, "v": vs}

    def build_mamba(slots_):
        nonlocal slots
        slots = slots_
        d_in = cfg.ssm_expand * cfg.d_model
        h = d_in // cfg.ssm_head_dim
        conv_dim = d_in + 2 * cfg.ssm_state
        s1, sp1 = attn_leaf((h, cfg.ssm_head_dim, cfg.ssm_state), dt=jnp.float32)
        c1, cp1 = attn_leaf((cfg.ssm_conv_width - 1, conv_dim))
        return {"ssm": s1, "conv": c1}, {"ssm": sp1, "conv": cp1}

    slots = 0
    if cfg.arch_type == "ssm":
        cache, cspec = build_mamba(sp["per_stage"])
    elif cfg.arch_type == "hybrid":
        m_cache, m_spec = build_mamba(sp["mamba_per_stage"])
        a_cache, a_spec = build_attn(sp["attn_per_stage"])
        cache = {"mamba": m_cache, "attn": a_cache}
        cspec = {"mamba": m_spec, "attn": a_spec}
    else:
        cache, cspec = build_attn(sp["per_stage"])
    return cache, cspec


def make_serve_step(
    cfg: ArchConfig,
    topo: Topology,
    shape: ShapeConfig,
    mesh,
    *,
    dtype=jnp.bfloat16,
) -> StepArtifacts:
    """One decode step: next-token logits + cache update, pipelined."""
    mesh = _auto_axes(mesh)
    extras = make_extras(cfg, topo.num_stages, long_context=topo.seq_shard_decode)
    aparams = _abstract_params(cfg, topo, dtype)
    specs, gather_mask = param_layout(cfg, aparams, topo)
    abatch, bspecs = batch_specs(cfg, shape, topo)
    acache, cache_specs = abstract_cache(cfg, topo, shape, dtype=dtype)
    ex_specs = jax.tree_util.tree_map(lambda a: P(topo.stage_axis, None), extras)
    bsz = shape.global_batch
    data = topo.data_axes if bsz > 1 else None
    xspec = P(data, None, None)

    def serve_step(params, cache, batch):
        x = params["embed"][batch["tokens"]][:, None, :].astype(dtype)  # (B,1,d)
        x = lax.with_sharding_constraint(x, NamedSharding(mesh, xspec))
        cur_pos = batch["pos"]

        def pipe_body(blocks, shared, ex, cache_in, x_local, pos_scalar):
            blocks_local = jax.tree_util.tree_map(lambda a: a[0], blocks)
            ex_local = jax.tree_util.tree_map(lambda a: a[0], ex)
            cache_local = jax.tree_util.tree_map(lambda a: a[0], cache_in)
            stage_fn = _stage_fn_decode(
                cfg, topo, blocks_local, shared, ex_local, gather_mask, pos_scalar
            )
            b_local = x_local.shape[0]
            mb = b_local // topo.num_micro
            x_mb = x_local.reshape(topo.num_micro, mb, 1, -1)
            out, new_cache = spmd_pipeline(
                stage_fn, x_mb, stage_axis=topo.stage_axis,
                num_stages=topo.num_stages, state=cache_local, remat=False,
                vma_refs=(blocks_local, shared),
            )
            new_cache = jax.tree_util.tree_map(lambda a: a[None], new_cache)
            return out.reshape(b_local, 1, -1), new_cache

        shared = params.get("shared_attn", ())
        shared_spec = specs.get("shared_attn", ())
        # batch-replicated decode (long_500k): the cache is genuinely
        # invariant over idle mesh axes but shard_map cannot infer it
        # through the gathered-param dataflow — skip the static check.
        y, cache = jax.shard_map(
            pipe_body,
            mesh=mesh,
            in_specs=(specs["blocks"], shared_spec, ex_specs, cache_specs, xspec, P()),
            out_specs=(xspec, cache_specs),
            check_vma=False,
        )(params["blocks"], shared, extras, cache, x, cur_pos)

        logits = lm_head_logits(cfg, params, y[:, 0])  # (B, V)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    in_sh = (_named(mesh, specs), _named(mesh, cache_specs), _named(mesh, bspecs))
    out_sh = (NamedSharding(mesh, P(data) if bsz > 1 else P(None)), _named(mesh, cache_specs))
    return StepArtifacts(
        fn=serve_step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        abstract_inputs=(aparams, acache, abatch),
        meta={"extras": extras, "specs": specs, "cache_specs": cache_specs},
    )


def make_prefill_step(
    cfg: ArchConfig,
    topo: Topology,
    shape: ShapeConfig,
    mesh,
    *,
    dtype=jnp.bfloat16,
) -> StepArtifacts:
    """Full-sequence prefill: last-token logits + populated KV cache."""
    mesh = _auto_axes(mesh)
    seq = shape.seq_len
    positions = make_positions(cfg, seq)
    extras = make_extras(cfg, topo.num_stages)
    aparams = _abstract_params(cfg, topo, dtype)
    specs, gather_mask = param_layout(cfg, aparams, topo)
    abatch, bspecs = batch_specs(cfg, shape, topo)
    acache, cache_specs = abstract_cache(cfg, topo, shape, dtype=dtype)
    ex_specs = jax.tree_util.tree_map(lambda a: P(topo.stage_axis, None), extras)
    xspec = P(topo.data_axes, None, None)

    def prefill_step(params, cache, batch):
        x = embed_inputs(cfg, params, batch).astype(dtype)
        x = lax.with_sharding_constraint(x, NamedSharding(mesh, xspec))

        def pipe_body(blocks, shared, ex, cache_in, x_local):
            blocks_local = jax.tree_util.tree_map(lambda a: a[0], blocks)
            ex_local = jax.tree_util.tree_map(lambda a: a[0], ex)
            cache_local = jax.tree_util.tree_map(lambda a: a[0], cache_in)
            stage_fn = _stage_fn_prefill(
                cfg, topo, blocks_local, shared, ex_local, gather_mask, positions
            )
            b_local = x_local.shape[0]
            mb = b_local // topo.num_micro
            x_mb = x_local.reshape(topo.num_micro, mb, seq, -1)
            out, new_cache = spmd_pipeline(
                stage_fn, x_mb, stage_axis=topo.stage_axis,
                num_stages=topo.num_stages, state=cache_local, remat=topo.remat,
                scatter_dim=2, vma_refs=(blocks_local, shared),
            )
            new_cache = jax.tree_util.tree_map(lambda a: a[None], new_cache)
            return out.reshape(b_local, seq // topo.num_stages, -1), new_cache

        shared = params.get("shared_attn", ())
        shared_spec = specs.get("shared_attn", ())
        yspec = P(topo.data_axes, topo.stage_axis, None)
        y, cache = jax.shard_map(
            pipe_body,
            mesh=mesh,
            in_specs=(specs["blocks"], shared_spec, ex_specs, cache_specs, xspec),
            out_specs=(yspec, cache_specs),
        )(params["blocks"], shared, extras, cache, x)

        logits = lm_head_logits(cfg, params, y[:, -1])  # (B, V)
        return logits, cache

    in_sh = (_named(mesh, specs), _named(mesh, cache_specs), _named(mesh, bspecs))
    out_sh = (
        NamedSharding(mesh, P(topo.data_axes, None)),
        _named(mesh, cache_specs),
    )
    return StepArtifacts(
        fn=prefill_step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        abstract_inputs=(aparams, acache, abatch),
        meta={"extras": extras, "specs": specs, "cache_specs": cache_specs},
    )
