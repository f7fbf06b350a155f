"""Adam / AdamW on arbitrary pytrees (fp32 moments regardless of param dtype)."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs


class AdamState(NamedTuple):
    step: jax.Array
    mu: object     # pytree like params, fp32
    nu: object     # pytree like params, fp32


def adam_init(params) -> AdamState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(step=jnp.zeros((), jnp.int32), mu=zeros,
                     nu=jax.tree.map(jnp.copy, zeros))


def adam_update(params, grads, state: AdamState, lr,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                weight_decay: float = 0.0):
    """One AdamW step. lr may be a scalar or a callable of the step index.

    Eager per-leaf ops; an `adam_update` span times them (repro.obs).
    """
    with obs.span("adam_update"):
        step = state.step + 1
        if callable(lr):
            lr = lr(step)
        lr = jnp.asarray(lr, jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * g32 * g32
            mhat = m / (1 - b1 ** step.astype(jnp.float32))
            vhat = v / (1 - b2 ** step.astype(jnp.float32))
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state.mu)
        flat_v = tdef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return new_p, AdamState(step=step, mu=new_m, nu=new_v)


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), gn
