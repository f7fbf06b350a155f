"""BBMM exact GP log marginal likelihood with a custom VJP.

Forward (paper Eq. 1): one mBCG call solves K_hat^{-1}[y_c, z_1..z_t] and
yields the SLQ log-determinant; the MLL value is
    -0.5 * ( y_c^T K_hat^{-1} y_c + logdet(K_hat) + n log 2pi ).

All kernel access goes through a `repro.core.operators.KernelOperator`
built by `MLLConfig.operator_config()` — the dense / partitioned /
Pallas-fused backends (and their bf16-compute fast path) are
interchangeable here, and `operator_mll_forward` is shared verbatim by the
sharded engine (`repro.core.distributed`), which passes its ShardedOperator
instead.

Backward (paper Eq. 2): instead of differentiating through the CG iterations
(which would store every intermediate), the VJP contracts the saved solves
against dK/dtheta through the operator's differentiable blockwise quadratic
form `KernelOperator.quad_form_grads`:

    d/dth [ y^T K^-1 y ]    = - u_y^T (dK/dth) u_y,          u_y = K^{-1} y_c
    d/dth [ logdet K ]      =   tr(K^{-1} dK/dth)
                           ~=   mean_i u_i^T (dK/dth) (P^{-1} z_i),
    with z_i ~ N(0, P):  E[z^T K^{-1} (dK) P^{-1} z] = tr(K^{-1} dK) exactly.

Everything stays O(row_block * n) memory. Gradients flow to the kernel
hyperparameters AND to X (enabling deep kernel learning, `repro.core.dkl`).
Probe draws and the preconditioner are treated as constants of the
estimator (standard BBMM practice; the estimator of the gradient remains
unbiased for fixed P). The backward always contracts in full precision
even when the forward ran bf16-compute solves — gradient noise comes from
the trace estimator, not from the matmul dtype.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.profiling import named_scope

from .kernels_math import constant_mean, dense_khat
from .operators import OperatorConfig, backward_backend_for, make_operator
from .pcg import pcg
from .slq import slq_logdet_correction


class MLLConfig(NamedTuple):
    """Static (hashable) solver configuration.

    kernel: legacy kind string (with GPParams) or a composable
    KernelSpec / expression (with KernelParams) — see
    `repro.core.kernels_math`. Threading is transparent: the custom VJP's
    parameter gradients take the SHAPE of whatever params pytree is passed.
    """

    kernel: str = "matern32"
    precond_rank: int = 100
    num_probes: int = 8
    max_cg_iters: int = 100
    min_cg_iters: int = 3
    cg_tol: float = 1.0
    row_block: int = 1024
    noise_floor: float = 1e-4
    pcg_method: str = "standard"
    backend: str = "partitioned"          # operator registry key
    compute_dtype: str | None = None      # "bfloat16" = MXU fast path
    plan: object | None = None            # SparsePlan (backend="blocksparse")
    autotune: bool = False                # Pallas (bm, bn) tile autotuner
    fused_cg: bool | None = None          # fused-CG megakernel step (None=auto)

    def operator_config(self) -> OperatorConfig:
        return OperatorConfig(
            kernel=self.kernel,
            backend=self.backend,
            row_block=self.row_block,
            add_noise=True,
            noise_floor=self.noise_floor,
            compute_dtype=self.compute_dtype,
            plan=self.plan,
            autotune=self.autotune,
            fused_cg=self.fused_cg,
        )


class MLLAux(NamedTuple):
    """Diagnostics (no gradients flow through these)."""

    logdet: jax.Array
    quad: jax.Array
    cg_iterations: jax.Array
    rel_residual: jax.Array
    # (max_cg_iters, t+1) per-iteration relative residuals when the forward
    # ran with track_residuals=True, else None (None is an empty pytree, so
    # the aux structure — and the compiled program — is unchanged when off).
    residuals: jax.Array | None = None
    # () int32 operator applications the solve ran (PCGResult.traversals)
    traversals: jax.Array | None = None


def operator_mll_forward(op, y, key, *, precond_rank: int, num_probes: int,
                         max_cg_iters: int, min_cg_iters: int, cg_tol: float,
                         pcg_method: str = "standard",
                         precond=None, probes: jax.Array | None = None,
                         x0: jax.Array | None = None,
                         logdet_carry: jax.Array | None = None,
                         track_residuals: bool = False):
    """Paper Eq. 1 against ANY KernelOperator (single-device or sharded).

    y is the operator-local slice of the targets (the full vector on one
    device, the row-shard chunk inside shard_map); scalar reductions go
    through op.allreduce, so the same code runs in both worlds. The y
    column and every SLQ/trace probe ride the SAME (n, t+1) mBCG matmat —
    one kernel traversal per CG iteration amortized over all right-hand
    sides — and on operators with `supports_fused_step` (Pallas) each
    iteration's reductions fuse into that traversal too (`pcg(fused=...)`).

    Warm-start surface (the stateful training engine,
    `repro.train.solver_state`): `precond` reuses a previous step's
    preconditioner instead of refactorizing; `probes` reuses the previous
    SLQ probe block (must be P-distributed draws of the SAME precond);
    `x0` seeds mBCG with the previous step's solutions. `logdet_carry`
    replaces the SLQ estimate in the returned value: warm-started probe
    iterates tridiagonalize the Krylov space of r0 = z - K x0, not of z, so
    their quadrature does NOT estimate logdet — a warm step carries the
    estimate from the last refresh instead. Gradients are unaffected: the
    Eq. 2 trace estimator contracts the CONVERGED solves u_i = K^{-1} z_i
    and P^{-1} z_i, both of which warm-starting leaves unbiased.

    Returns ((value, aux), (yc, u_y, U, pinv_z), state) — the saved solves
    the custom VJPs contract against dK/dtheta, plus the `pcg.SolveState`
    (solutions + probe block) to thread into the next step.
    """
    n = op.shape[0]
    yc = y - constant_mean(op.params)
    if op.local_mask is not None:
        # padded sharded layouts: zero the pad rows of the targets so every
        # CG vector stays in the true-row subspace (K_hat_pad is block-
        # diagonal there; n above is already the TRUE count)
        yc = yc * op.local_mask
    if precond is None:
        with named_scope("precond_build"):
            precond = op.preconditioner(precond_rank)
    if probes is None:
        probes = precond.sample(key, num_probes, dtype=yc.dtype)
    B = jnp.concatenate([yc[:, None], probes], axis=1)

    res = pcg(op, B, precond.solve,
              max_iters=max_cg_iters, min_iters=min_cg_iters,
              tol=cg_tol, method=pcg_method, x0=x0,
              track_residuals=track_residuals)
    u_y = res.solution[:, 0]
    U = res.solution[:, 1:]
    pinv_z = precond.solve(probes)

    if logdet_carry is None:
        # alphas/betas/rz0 are replicated scalars under sharding -> SLQ is free
        with named_scope("slq_logdet"):
            logdet = precond.logdet() + slq_logdet_correction(
                res.alphas[:, 1:], res.betas[:, 1:], res.active[:, 1:],
                res.rz0[1:])
    else:
        logdet = logdet_carry
    quad = op.allreduce(jnp.dot(yc, u_y))
    value = -0.5 * (quad + logdet + n * math.log(2.0 * math.pi))
    aux = MLLAux(logdet=logdet, quad=quad,
                 cg_iterations=res.iterations, rel_residual=res.rel_residual,
                 residuals=res.residuals, traversals=res.traversals)
    state = res.state._replace(probes=probes)
    return (value, aux), (yc, u_y, U, pinv_z), state


def operator_mll_quad_grads(make_op, X, u_y, U, pinv_z):
    """Paper Eq. 2 assembly, shared by the single-device and sharded VJPs.

    make_op: X -> KernelOperator (full precision — see module docstring).
    Returns (g_params, g_X) of the MLL w.r.t. (theta, X) BEFORE any
    cross-device reduction, g_value scaling, or the raw_mean term — the
    callers layer those on (the sharded VJP psums partials first).

    Both Eq. 2 contractions — the data-fit term -u_y^T dK u_y and the
    trace term (1/t) sum_i u_i^T dK P^{-1}z_i — are LINEAR in the (a, v)
    column pairs of the quadratic form, so they batch into ONE
    `quad_form_grads` call over t+1 columns. Every backend's gradient
    surface walks its slabs/tiles once for the whole column block (the
    kernel slab and its VJP residuals are shared across columns), halving
    the backward's HBM traversals vs the historical two-call assembly; it
    also obviates the barrier link that serialized the two chains.
    """
    t = max(U.shape[1], 1)
    op = make_op(X)
    A = jnp.concatenate([-u_y[:, None], U / t], axis=1)
    V = jnp.concatenate([u_y[:, None], pinv_z], axis=1)
    gp, gx = op.quad_form_grads(A, V)
    g_params = jax.tree.map(lambda a: -0.5 * a, gp)
    g_X = -0.5 * gx
    return g_params, g_X


def operator_mll_backward(cfg: MLLConfig, X, params, u_y, U, pinv_z, g_value):
    """(g_X, g_y, g_params) of g_value * mll from the saved forward solves.

    The single assembly point shared by the custom VJP below and the
    warm-start training engine (`repro.train.solver_state`), which computes
    gradients explicitly from its stateful forward rather than through
    jax.grad. Bitwise-identical to the historical `_mll_bwd` body.
    """
    # the backward surface is operator-owned too, but always full precision;
    # the backend is re-resolved through `backward_backend_for`: every dense
    # single-device backend shares the "partitioned" blockwise partials
    # (base-class quad_form_grads — NOT AD through the forward, see
    # partitioned.quad_form_partials for why), while blocksparse keeps its
    # own fill-proportional gradient surface
    bwd_cfg = cfg.operator_config()._replace(
        compute_dtype=None, backend=backward_backend_for(cfg.backend))

    # d(-0.5[-u_y^T Khat u_y + (1/t) sum_i u_i^T Khat P^{-1}z_i])/d(theta, X)
    with named_scope("eq2_backward"):
        g_params, g_X = operator_mll_quad_grads(
            lambda x: make_operator(bwd_cfg, x, params), X, u_y, U, pinv_z)
    # mean parameter: d mll / d mu = sum(u_y); noise & kernel already covered.
    g_params = g_params._replace(
        raw_mean=g_params.raw_mean + jnp.sum(u_y))
    g_params = jax.tree.map(lambda a: g_value * a, g_params)
    g_X = g_value * g_X
    g_y = g_value * (-u_y)
    return g_X, g_y, g_params


def _mll_forward_impl(cfg: MLLConfig, X, y, params, key):
    op = make_operator(cfg.operator_config(), X, params)
    (value, aux), (yc, u_y, U, pinv_z), _state = operator_mll_forward(
        op, y, key,
        precond_rank=cfg.precond_rank, num_probes=cfg.num_probes,
        max_cg_iters=cfg.max_cg_iters, min_cg_iters=cfg.min_cg_iters,
        cg_tol=cfg.cg_tol, pcg_method=cfg.pcg_method)
    saved = (X, params, yc, u_y, U, pinv_z)
    return (value, aux), saved


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def exact_mll(cfg: MLLConfig, X, y, params, key):
    """Log marginal likelihood (total, not per-datum) and diagnostics.

    key: uint32 PRNGKey array (probe randomness; gets a float0 cotangent).
    """
    out, _ = _mll_forward_impl(cfg, X, y, params, key)
    return out


def _mll_fwd(cfg, X, y, params, key):
    out, saved = _mll_forward_impl(cfg, X, y, params, key)
    return out, saved


def _mll_bwd(cfg, saved, cotangents):
    g_value = cotangents[0]  # aux cotangents are ignored (diagnostics)
    X, params, yc, u_y, U, pinv_z = saved
    g_X, g_y, g_params = operator_mll_backward(
        cfg, X, params, u_y, U, pinv_z, g_value)
    g_key = np.zeros((2,), jax.dtypes.float0)
    return (g_X, g_y, g_params, g_key)


exact_mll.defvjp(_mll_fwd, _mll_bwd)


# ---------------------------------------------------------------------------
# dense oracle (test/reference only): closed-form MLL via Cholesky
# ---------------------------------------------------------------------------


def dense_mll(kernel, X, y, params, noise_floor: float = 1e-4):
    """O(n^3)/O(n^2) reference MLL — what the paper says standard
    implementations do and cannot scale. Used as the unit-test oracle.
    Accepts any (kernel, params) pair `kernels_math.canonicalize_kernel`
    does."""
    n = X.shape[0]
    yc = y - constant_mean(params)
    Khat = dense_khat(kernel, X, params, noise_floor)
    L = jnp.linalg.cholesky(Khat)
    alpha = jax.scipy.linalg.cho_solve((L, True), yc)
    quad = jnp.dot(yc, alpha)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
    return -0.5 * (quad + logdet + n * math.log(2.0 * math.pi))
