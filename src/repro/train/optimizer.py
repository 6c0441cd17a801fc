"""Pure-JAX optimizers (no external deps): Adam/AdamW, SGD, schedules.

API mirrors optax minimally: ``init(params) -> state``,
``update(grads, state, params) -> (updates, state)``; apply with
``apply_updates``. States are plain pytrees so they shard with pjit (ZeRO-1
just means sharding these over the data axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple[Any, Any]]


def apply_updates(params, updates):
    return jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves))) if leaves else jnp.zeros(())


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda x: x * scale, tree), norm


@dataclasses.dataclass(frozen=True)
class AdamState:
    step: jax.Array
    mu: Any
    nu: Any


jax.tree_util.register_dataclass(AdamState, data_fields=["step", "mu", "nu"], meta_fields=[])


def adam(
    lr: float | Callable[[jax.Array], jax.Array],
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float | None = None,
) -> Optimizer:
    """Adam / AdamW. Moments are kept in f32 regardless of param dtype so
    bf16 training stays stable (master-quality moments)."""

    def init(params):
        f32 = lambda p: jnp.zeros(p.shape, dtype=jnp.float32)
        return AdamState(
            step=jnp.zeros((), dtype=jnp.int32),
            mu=jax.tree_util.tree_map(f32, params),
            nu=jax.tree_util.tree_map(f32, params),
        )

    def update(grads, state, params):
        if grad_clip is not None:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        step = state.step + 1
        lr_t = lr(step) if callable(lr) else jnp.asarray(lr)
        g32 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, g32)
        nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, g32)
        mu_hat = jax.tree_util.tree_map(lambda m: m / (1 - b1 ** step.astype(jnp.float32)), mu)
        nu_hat = jax.tree_util.tree_map(lambda v: v / (1 - b2 ** step.astype(jnp.float32)), nu)

        def upd(m, v, p):
            u = -lr_t * m / (jnp.sqrt(v) + eps)
            if weight_decay > 0.0:
                u = u - lr_t * weight_decay * p.astype(jnp.float32)
            # cast to param dtype here: with ZeRO-1 (sharded moments,
            # replicated param) the update is what gets all-gathered
            return u.astype(p.dtype)

        updates = jax.tree_util.tree_map(upd, mu_hat, nu_hat, params)
        return updates, AdamState(step=step, mu=mu, nu=nu)

    return Optimizer(init=init, update=update)


def sgd(lr: float | Callable, *, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return {"step": jnp.zeros((), jnp.int32)}
        return {
            "step": jnp.zeros((), jnp.int32),
            "vel": jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params),
        }

    def update(grads, state, params):
        del params
        step = state["step"] + 1
        lr_t = lr(step) if callable(lr) else jnp.asarray(lr)
        if momentum == 0.0:
            updates = jax.tree_util.tree_map(lambda g: -lr_t * g, grads)
            return updates, {"step": step}
        vel = jax.tree_util.tree_map(
            lambda v, g: momentum * v + g.astype(jnp.float32), state["vel"], grads
        )
        updates = jax.tree_util.tree_map(lambda v: -lr_t * v, vel)
        return updates, {"step": step, "vel": vel}

    return Optimizer(init=init, update=update)


def grad_probe() -> Optimizer:
    """Leaves params unchanged and keeps the gradients as its state: after
    one ``train_step`` the returned optimizer state IS the step's gradient
    pytree, so engines can be compared on gradients rather than on an
    update (Adam's first update is about ``lr * sign(g)`` whatever g's size)."""

    def init(params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(grads, state, params):
        del state
        return jax.tree_util.tree_map(jnp.zeros_like, params), grads

    return Optimizer(init=init, update=update)


def cosine_schedule(peak: float, *, warmup: int, total: int, floor: float = 0.0):
    def sched(step):
        step = step.astype(jnp.float32)
        warm = peak * step / jnp.maximum(1.0, warmup)
        prog = jnp.clip((step - warmup) / jnp.maximum(1.0, total - warmup), 0.0, 1.0)
        cos = floor + 0.5 * (peak - floor) * (1.0 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)

    return sched
