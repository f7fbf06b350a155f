"""Stochastic Lanczos quadrature log-determinant from mBCG coefficients.

PCG on (K_hat, P) implicitly runs Lanczos on A~ = P^{-1/2} K_hat P^{-1/2}
with start vector b~ = P^{-1/2} b. The CG coefficients give the Lanczos
tridiagonal T:

    T[j, j]   = 1/alpha_j + beta_{j-1}/alpha_{j-1}
    T[j, j+1] = sqrt(beta_j) / alpha_j

For probes z ~ N(0, P) we have b~ ~ N(0, I), so

    E[ b~^T log(A~) b~ ] = tr(log A~) = logdet(K_hat) - logdet(P)

and b~^T log(A~) b~ ~= ||b~||^2 e1^T log(T) e1 with ||b~||^2 = z^T P^{-1} z —
which is exactly the first <r, z> of the PCG run (PCGResult.rz0). Hence

    logdet(K_hat) ~= logdet(P) + mean_i [ rz0_i * e1^T log(T_i) e1 ].

logdet(P) comes in closed form from the pivoted-Cholesky factor
(`Preconditioner.logdet`). Converged-and-frozen CG iterations are patched to
identity rows of T (log contribution 0), and so are the rows the CG loop
never ran after its early exit (their records hold alpha = beta = 0,
active = False): the (max_iters, t) records need no ragged handling.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SLQAux(NamedTuple):
    """Probe accounting for the standalone estimator — returned aux, the
    only way device-side counts reach the obs metrics registry (no host
    callbacks on the jit path; see `repro.obs`)."""

    iterations: jax.Array    # (t,) CG iterations applied per probe
    rel_residual: jax.Array  # (t,) final relative residual per probe
    num_probes: int


def lanczos_tridiag_from_coeffs(
    alphas: jax.Array, betas: jax.Array, active: jax.Array
) -> jax.Array:
    """Build the (m, m) symmetric tridiagonal T for ONE probe column.

    alphas, betas, active: (m,) CG coefficient traces for this probe.
    Frozen iterations become identity rows (diag 1, offdiag 0).
    """
    m = alphas.shape[0]
    safe_alpha = jnp.where(active, alphas, 1.0)
    safe_alpha = jnp.where(jnp.abs(safe_alpha) > 1e-30, safe_alpha, 1.0)

    prev_beta = jnp.concatenate([jnp.zeros((1,), alphas.dtype), betas[:-1]])
    prev_alpha = jnp.concatenate([jnp.ones((1,), alphas.dtype), safe_alpha[:-1]])
    diag = 1.0 / safe_alpha + prev_beta / prev_alpha
    diag = jnp.where(active, diag, 1.0)

    # off-diagonal j <-> j+1 requires both iterations active
    next_active = jnp.concatenate([active[1:], jnp.zeros((1,), bool)])
    off = jnp.sqrt(jnp.maximum(betas, 0.0)) / safe_alpha
    off = jnp.where(active & next_active, off, 0.0)
    off = off[:-1]

    T = jnp.diag(diag) + jnp.diag(off, 1) + jnp.diag(off, -1)
    return T


def _e1_log_e1(T: jax.Array) -> jax.Array:
    """e1^T log(T) e1 for symmetric positive-definite T via eigh."""
    evals, evecs = jnp.linalg.eigh(T)
    evals = jnp.maximum(evals, 1e-10)
    w = evecs[0, :] ** 2
    return jnp.sum(w * jnp.log(evals))


def slq_logdet_correction(
    alphas: jax.Array,    # (m, t) over probes
    betas: jax.Array,     # (m, t)
    active: jax.Array,    # (m, t)
    probe_rz0: jax.Array, # (t,) z^T P^{-1} z per probe
) -> jax.Array:
    """Estimate logdet(K_hat) - logdet(P) from mBCG probe traces."""
    def one(alpha_col, beta_col, active_col, rz0):
        T = lanczos_tridiag_from_coeffs(alpha_col, beta_col, active_col)
        return rz0 * _e1_log_e1(T)

    per_probe = jax.vmap(one, in_axes=(1, 1, 1, 0))(alphas, betas, active, probe_rz0)
    return jnp.mean(per_probe)


def slq_logdet(
    op,
    key: jax.Array,
    *,
    num_probes: int = 8,
    precond_rank: int = 100,
    max_iters: int = 100,
    tol: float = 1e-8,
    method: str = "standard",
    with_aux: bool = False,
):
    """Standalone SLQ estimate of logdet(K_hat) from a KernelOperator.

    Runs one mBCG solve on probes z ~ N(0, P) drawn from the operator's
    pivoted-Cholesky preconditioner and assembles logdet(P) + the Lanczos
    correction. All probes ride one (n, num_probes) matmat — a single
    kernel traversal per CG iteration, with the per-iteration reductions
    fused into it on operators that support the fused step (see
    `repro.core.pcg`). This is the logdet the MLL forward gets for free
    from its shared solve (`repro.core.mll`); use this entry point when
    only the log-determinant is needed (e.g. model comparison, ablations).

    With `with_aux=True` also returns an `SLQAux` carrying per-probe CG
    iteration counts and final residuals as device arrays — jit-safe
    accounting the caller feeds to the obs registry after fencing.
    """
    from .pcg import pcg  # local import: pcg has no slq dependency

    precond = op.preconditioner(precond_rank)
    probes = precond.sample(key, num_probes, dtype=op.dtype)
    res = pcg(op, probes, precond.solve, max_iters=max_iters,
              min_iters=3, tol=tol, method=method)
    logdet = precond.logdet() + slq_logdet_correction(
        res.alphas, res.betas, res.active, res.rz0)
    if with_aux:
        aux = SLQAux(iterations=res.iterations,
                     rel_residual=res.rel_residual,
                     num_probes=num_probes)
        return logdet, aux
    return logdet


def exact_logdet(A: jax.Array) -> jax.Array:
    """Dense reference: logdet via Cholesky. Test oracle only."""
    L = jnp.linalg.cholesky(A)
    return 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
