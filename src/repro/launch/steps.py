"""jit'd step factories: train_step / prefill_step / decode_step per arch.

Each factory binds an ArchConfig to a mesh, installs the sharding rules
(params FSDP x TP, activations batch x SP, caches batch x seq-over-model)
and returns an AOT-lowerable function + the matching in/out shardings.
`launch.dryrun` lowers these against ShapeDtypeStructs; `launch.train` and
the examples execute them for real on small configs.

The GP workload (gp-exact-1m) gets its own factories at the bottom — the
paper's distributed MLL step and prediction-cache solve on the same mesh.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import init_params, train_loss
from repro.models.model import decode_step as model_decode_step
from repro.models.model import init_decode_state, prefill
from repro.models.sharding import (
    batch_shardings, decode_state_shardings, param_shardings,
)
from repro.models.shardctx import use_mesh
from repro.optim import clip_by_global_norm


class TrainState(NamedTuple):
    params: dict
    mu: dict          # fp32 Adam moments
    nu: dict
    step: jax.Array


def init_train_state(cfg, key, dtype=jnp.bfloat16) -> TrainState:
    params = init_params(cfg, key, dtype)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return TrainState(params=params, mu=zeros,
                      nu=jax.tree.map(jnp.copy, zeros),
                      step=jnp.zeros((), jnp.int32))


def train_state_shardings(mesh: Mesh, state_or_specs) -> TrainState:
    ps = param_shardings(mesh, state_or_specs.params)
    return TrainState(params=ps, mu=ps, nu=ps,
                      step=NamedSharding(mesh, P()))


def _adamw(params, grads, mu, nu, step, *, lr=3e-4, b1=0.9, b2=0.95,
           eps=1e-8, wd=0.1):
    step = step + 1
    t = step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * g32 * g32
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        p32 = p.astype(jnp.float32)
        p32 = p32 - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p32)
        return p32.astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    outs = [upd(p, g, m, v) for p, g, m, v in zip(
        flat_p, tdef.flatten_up_to(grads), tdef.flatten_up_to(mu),
        tdef.flatten_up_to(nu))]
    return (tdef.unflatten([o[0] for o in outs]),
            tdef.unflatten([o[1] for o in outs]),
            tdef.unflatten([o[2] for o in outs]), step)


def make_train_step(cfg, mesh: Mesh, *, lr=3e-4, microbatch: int = 1):
    """Returns (step_fn, state_shardings_fn, batch_shardings_fn)."""

    def step_fn(state: TrainState, batch: dict):
        def loss_fn(p):
            if microbatch == 1:
                return train_loss(cfg, p, batch)
            # gradient accumulation over micro-slices of the batch
            def one(i):
                sl = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // microbatch),
                        x.shape[0] // microbatch, 0), batch)
                return train_loss(cfg, p, sl)
            losses, metrics = jax.lax.map(one, jnp.arange(microbatch))
            return jnp.mean(losses), jax.tree.map(jnp.mean, metrics)

        with use_mesh(mesh):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            params, mu, nu, step = _adamw(state.params, grads, state.mu,
                                          state.nu, state.step, lr=lr)
        new_state = TrainState(params, mu, nu, step)
        return new_state, {"loss": loss, "grad_norm": gnorm, **metrics}

    return step_fn


def make_prefill_step(cfg, mesh: Mesh):
    def step_fn(params, state, batch):
        with use_mesh(mesh):
            return prefill(cfg, params, state, batch)
    return step_fn


def make_decode_step(cfg, mesh: Mesh):
    def step_fn(params, state, tokens):
        with use_mesh(mesh):
            return model_decode_step(cfg, params, state, tokens)
    return step_fn


def metrics_shardings(mesh: Mesh, metrics):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics)


# ---------------------------------------------------------------------------
# GP workload steps (the paper's own dry-run cells)
# ---------------------------------------------------------------------------


def make_gp_train_step(gp_cfg, mesh: Mesh, *, lr: float = 0.1,
                       pcg_method: str = "standard"):
    """(X, y, params, opt, key) -> (loss, params, opt): one BBMM MLL Adam step."""
    from repro.core.distributed import (
        DistMLLConfig, make_dist_mll, make_geometry)
    from jax import shard_map

    geom = make_geometry(mesh, gp_cfg.n, gp_cfg.d, mode=gp_cfg.mode,
                         row_block=gp_cfg.row_block,
                         overlap=getattr(gp_cfg, "overlap", False))
    cfg = DistMLLConfig(kernel=gp_cfg.kernel, precond_rank=gp_cfg.precond_rank,
                        num_probes=gp_cfg.num_probes,
                        max_cg_iters=gp_cfg.train_cg_iters,
                        pcg_method=pcg_method,
                        backend=gp_cfg.backend,
                        compute_dtype=gp_cfg.compute_dtype)
    mll = make_dist_mll(geom, cfg)
    vec = geom.vector_pspec()

    def local_fn(X, y_loc, params, mu, nu, step, key):
        def loss(p):
            value, aux = mll(X, y_loc, p, key)
            return -value / geom.n
        val, g = jax.value_and_grad(loss)(params)
        params, mu, nu, step = _adamw(params, g, mu, nu, step, lr=lr, wd=0.0)
        return val, params, mu, nu, step

    sharded = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), vec, P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)
    return sharded, geom


def make_gp_predict_setup(gp_cfg, mesh: Mesh):
    """Tight-tolerance mean-cache solve (the paper's precomputation)."""
    from repro.core.distributed import DistMLLConfig, make_geometry, \
        make_mean_cache_solve

    geom = make_geometry(mesh, gp_cfg.n, gp_cfg.d, mode=gp_cfg.mode,
                         row_block=gp_cfg.row_block,
                         overlap=getattr(gp_cfg, "overlap", False))
    cfg = DistMLLConfig(kernel=gp_cfg.kernel, precond_rank=gp_cfg.precond_rank,
                        backend=gp_cfg.backend,
                        compute_dtype=gp_cfg.compute_dtype)
    return make_mean_cache_solve(mesh, geom, cfg, tol=0.01,
                                 max_iters=gp_cfg.pred_cg_iters), geom
