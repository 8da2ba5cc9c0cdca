"""AdamW + LR schedules + global-norm clipping + gradient accumulation.

Written from scratch (no optax in the environment).  Functional API:
    opt = adamw(lr_schedule, ...)
    state = opt.init(params)
    updates, state = opt.update(grads, state, params)
    params = apply_updates(params, updates)

Moments are fp32 regardless of param dtype (bf16-safe); the update is cast
back to the param dtype.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray          # int32
    m: Any                     # fp32 pytree
    v: Any                     # fp32 pytree
    master: Any = None         # fp32 master weights (bf16-param training)


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable           # (grads, state, params) -> (updates, state)


def cosine_schedule(peak_lr: float, warmup_steps: int, total_steps: int,
                    min_ratio: float = 0.1) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = step / jnp.maximum(warmup_steps, 1)
        prog = (step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1)
        prog = jnp.clip(prog, 0.0, 1.0)
        cos = min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return peak_lr * jnp.where(step < warmup_steps, warm, cos)
    return lr


def constant_schedule(lr_value: float) -> Callable:
    return lambda step: jnp.asarray(lr_value, jnp.float32)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        tree), norm


def adamw(lr: Callable | float, *, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1,
          clip_norm: Optional[float] = 1.0,
          master_weights: bool = False) -> Optimizer:
    """master_weights=True keeps an fp32 copy in the state — use when params
    are stored bf16 (halves weight traffic; update precision preserved)."""
    lr_fn = lr if callable(lr) else constant_schedule(lr)

    def init(params):
        # zeros_like keeps each param's sharding: the moments of a stage's
        # weights live on that stage's devices
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)
        master = (jax.tree.map(lambda p: p.astype(jnp.float32), params)
                  if master_weights else None)
        return AdamWState(jnp.zeros((), jnp.int32),
                          jax.tree.map(zeros, params),
                          jax.tree.map(zeros, params), master)

    def update(grads, state, params):
        step = state.step + 1
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.m, g32)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state.v, g32)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr_t = lr_fn(step)
        ref = state.master if master_weights else params

        def upd(m, v, p):
            mhat = m / bc1
            vhat = v / bc2
            return -lr_t * (mhat / (jnp.sqrt(vhat) + eps)
                            + weight_decay * p.astype(jnp.float32))

        upd32 = jax.tree.map(upd, m, v, ref)
        if master_weights:
            new_master = jax.tree.map(lambda p, u: p + u, state.master, upd32)
            # "updates" reconstruct bf16 params from the fp32 master
            updates = jax.tree.map(lambda nm, p: nm.astype(p.dtype) - p,
                                   new_master, params)
            return updates, AdamWState(step, m, v, new_master)
        updates = jax.tree.map(lambda u, p: u.astype(p.dtype), upd32, params)
        return updates, AdamWState(step, m, v, None)

    return Optimizer(init, update)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u, params, updates)


# ---------------------------------------------------------------------------
# Gradient accumulation (paper §3.4 "combine with memory optimization")
# ---------------------------------------------------------------------------
def accumulate_grads(loss_fn: Callable, params, batches) -> Tuple[jnp.ndarray, Any]:
    """Average loss/grads over a leading accumulation axis of ``batches``."""
    def one(carry, batch):
        loss_acc, grad_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return (loss_acc + loss,
                jax.tree.map(jnp.add, grad_acc, grads)), None

    n = jax.tree.leaves(batches)[0].shape[0]
    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grad_sum), _ = jax.lax.scan(one, (jnp.float32(0.0), zero_g), batches)
    inv = 1.0 / n
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, grad_sum)
