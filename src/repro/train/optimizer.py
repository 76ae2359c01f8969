"""AdamW (pure JAX, shardable) + schedules + global-norm clipping.

Implemented from scratch (no optax dependency): ``init`` builds f32
moment/master trees shaped like the params; ``update`` is fully
elementwise, so XLA SPMD lays the optimizer out under whatever shardings
the trainer assigns — with ZeRO-1 the moments are additionally sharded
over the data axes and XLA inserts the reduce-scatter / all-gather pair
automatically.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array  # ()
    mu: dict
    nu: dict


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: Callable | float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float | None = 1.0

    def init(self, params) -> AdamState:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        return AdamState(
            step=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def _lr(self, step):
        return self.lr(step) if callable(self.lr) else self.lr

    @jax.named_scope("optimizer")
    def update(self, grads, state: AdamState, params):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if self.clip_norm is not None:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        else:
            gnorm = global_norm(grads)
        step = state.step + 1
        b1, b2 = self.b1, self.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          state.nu, grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr = self._lr(step)

        def upd(p, m, v):
            mhat = m / bc1
            vhat = v / bc2
            delta = mhat / (jnp.sqrt(vhat) + self.eps)
            if self.weight_decay:
                delta = delta + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, AdamState(step=step, mu=mu, nu=nu), gnorm


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def cosine_schedule(peak_lr: float, warmup: int, total: int,
                    floor_frac: float = 0.1) -> Callable:
    def lr(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup, 1)
        t = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor_frac + (1 - floor_frac)
                         * 0.5 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def constant_schedule(lr_val: float) -> Callable:
    return lambda step: jnp.full((), lr_val, jnp.float32)
