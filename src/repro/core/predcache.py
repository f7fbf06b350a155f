"""Prediction-time caches (paper Section 3, "Predictions").

After training, two training-data-dependent caches make test-time O(n):

  * mean cache  a = K_hat^{-1} y_c  — one tight-tolerance PCG solve
    (paper: eps <= 0.01 is critical at test time). The predictive mean is
    then mu + K_{x* X} a: a single rectangular MVM, no solves.
  * variance cache — a rank-r Lanczos decomposition Q T Q^T ~= K_hat
    restricted to the Krylov subspace (LOVE-style, Pleiss et al. [28]):
    Var(x*) ~= k** - k_{X x*}^T Q T^{-1} Q^T k_{X x*}, an O(n r) product per
    test point. The cache *underestimates* the subtracted correction, so the
    approximate variance upper-bounds the exact one; an exact PCG variance
    path is provided for small test batches and used as its test oracle.

Both caches are computed once (the paper's "precomputation" column in
Table 2) and reused for every prediction. When observations STREAM in after
that precomputation, `update_prediction_cache` extends both caches to the
grown system at O(n*m)-class cost per m-row batch instead of re-running the
cold precompute (the serving fleet's `observe()` path — see
`repro.serve.fleet`).

Every function here takes a `repro.core.operators.KernelOperator` — the
solves use `op.matvec`, the test-time products use `op.cross_matvec`
(which runs on the same backend, so e.g. the Pallas-fused path serves
predictions too), and the preconditioner comes from `op.preconditioner`.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .kernels_math import constant_mean
from .partitioned import map_row_chunks
from .pcg import pcg
from .pivchol import Preconditioner, extend_preconditioner


def solver_dtype(op, *operands) -> jnp.dtype:
    """Dtype for solver/cache state: at least fp32, regardless of backend.

    Reduced-precision operands (X stored in bf16, or a bf16 compute_dtype
    backend) must never set the dtype of CG residuals, Lanczos vectors or
    the caches themselves — the paper's eps <= 0.01 prediction tolerance is
    unreachable in bf16 state. fp64 operands (x64 mode) keep fp64.
    """
    dt = jnp.dtype(op.dtype)
    for a in operands:
        dt = jnp.promote_types(dt, jnp.result_type(a))
    return jnp.promote_types(dt, jnp.float32)


def lanczos(mvm, v0: jax.Array, rank: int):
    """Lanczos with full reorthogonalization.

    Returns Q (n, rank), T (rank, rank) symmetric tridiagonal with
    Q^T A Q = T. Fixed trip count; rank is expected << n. State stays in
    v0.dtype (the operator's reduced compute dtype never leaks in).
    """
    n = v0.shape[0]
    q = v0 / jnp.linalg.norm(v0)
    Q = jnp.zeros((rank, n), v0.dtype).at[0].set(q)
    alphas = jnp.zeros((rank,), v0.dtype)
    betas = jnp.zeros((rank,), v0.dtype)  # betas[j] links j and j+1

    def body(j, carry):
        Q, alphas, betas = carry
        qj = Q[j]
        w = mvm(qj[:, None])[:, 0]
        alpha = jnp.dot(qj, w)
        w = w - alpha * qj
        # full reorthogonalization (rows >= j+1 are zero, contraction exact)
        w = w - Q.T @ (Q @ w)
        w = w - Q.T @ (Q @ w)  # twice is enough (Kahan)
        beta = jnp.linalg.norm(w)
        qn = jnp.where(beta > 1e-10, w / jnp.maximum(beta, 1e-30), 0.0)
        Q = jax.lax.cond(j + 1 < rank, lambda Q: Q.at[j + 1].set(qn), lambda Q: Q, Q)
        alphas = alphas.at[j].set(alpha)
        betas = betas.at[j].set(jnp.where(j + 1 < rank, beta, 0.0))
        return Q, alphas, betas

    Q, alphas, betas = jax.lax.fori_loop(0, rank, body, (Q, alphas, betas))
    T = jnp.diag(alphas) + jnp.diag(betas[:-1], 1) + jnp.diag(betas[:-1], -1)
    return Q.T, T


class PredictionCache(NamedTuple):
    mean_cache: jax.Array   # (n,) K_hat^{-1} (y - mu)
    var_Q: jax.Array        # (n, r)
    var_T_chol: jax.Array   # (r, r) Cholesky of T (+jitter)
    solve_rel_residual: jax.Array  # diagnostic from the mean solve


def build_prediction_cache(
    op,
    y: jax.Array,
    key: jax.Array,
    *,
    precond_rank: int = 100,
    lanczos_rank: int = 128,
    pred_tol: float = 0.01,
    max_cg_iters: int = 400,
) -> PredictionCache:
    """The paper's one-time precomputation (tight-tolerance solves).

    Solver and cache state are forced to at least fp32 (`solver_dtype`) so a
    reduced-precision operator backend only affects the matvecs, never the
    CG/Lanczos state or the cache the engine serves from.
    """
    sdt = solver_dtype(op, y)
    yc = (y - constant_mean(op.params)).astype(sdt)
    precond = op.preconditioner(precond_rank)

    res = pcg(op, yc[:, None], precond.solve,
              max_iters=max_cg_iters, min_iters=10, tol=pred_tol)
    mean_cache = res.solution[:, 0]

    Q, T_chol = build_variance_cache(op, key, lanczos_rank=lanczos_rank)
    return PredictionCache(mean_cache, Q, T_chol, res.rel_residual)


def build_variance_cache(op, key: jax.Array, *, lanczos_rank: int = 128):
    """The Lanczos half of the precomputation: (Q, chol(T)) for the LOVE
    variance. Split out so callers that already hold a mean cache (e.g. from
    a distributed tight solve, see `repro.serve.artifact`) only pay the r
    extra MVMs. State is at least fp32 (`solver_dtype`)."""
    n = op.shape[0]
    r = min(lanczos_rank, n)
    v0 = jax.random.normal(key, (n,), solver_dtype(op))
    Q, T = lanczos(op.matvec, v0, r)
    T = T + 1e-6 * jnp.eye(r, dtype=T.dtype)
    T_chol = jnp.linalg.cholesky(T)
    return Q, T_chol


def predict_mean(op, Xstar: jax.Array, cache: PredictionCache) -> jax.Array:
    """mu + K_{x* X} a — no solves (paper: <1s for 1000 points at n>10^6)."""
    return constant_mean(op.params) + op.cross_matvec(Xstar, cache.mean_cache)


def predict_var_cached(
    op, Xstar: jax.Array, cache: PredictionCache,
    include_noise: bool = False,
) -> jax.Array:
    """LOVE-style O(n r) predictive variance from the Lanczos cache."""
    proj = op.cross_matvec(Xstar, cache.var_Q)         # (n*, r)
    sol = jax.scipy.linalg.cho_solve((cache.var_T_chol, True), proj.T)  # (r, n*)
    correction = jnp.sum(proj * sol.T, axis=1)
    var = jnp.maximum(op.prior_diag(Xstar) - correction, 1e-10)
    if include_noise:
        var = var + op.noise()
    return var


def predict_var_exact(
    op, Xstar: jax.Array,
    *,
    precond_rank: int = 100,
    pred_tol: float = 0.01,
    max_cg_iters: int = 400,
    include_noise: bool = False,
    xstar_chunk: int | None = 1024,
) -> jax.Array:
    """Exact predictive variance: PCG-solve K_hat^{-1} k_{X x*} per test point
    (batched over the test set as mBCG columns).

    Chunked over Xstar (`map_row_chunks`, `xstar_chunk` columns of RHS at a
    time) so only an (n, chunk) block is ever live — the oracle works at test
    sizes where the full (n, n*) RHS would not fit. mBCG columns are
    independent, so chunking is exact. None = one unchunked solve.
    """
    precond = op.preconditioner(precond_rank)

    def one_chunk(Xc: jax.Array) -> jax.Array:
        Kxs = op.kernel_rows(Xc).T                     # (n, chunk)
        res = pcg(op, Kxs.astype(solver_dtype(op)), precond.solve,
                  max_iters=max_cg_iters, min_iters=10, tol=pred_tol)
        return jnp.sum(Kxs * res.solution, axis=0)

    if xstar_chunk is None or Xstar.shape[0] <= xstar_chunk:
        correction = one_chunk(Xstar)
    else:
        correction = map_row_chunks(one_chunk, Xstar, xstar_chunk)
    var = jnp.maximum(op.prior_diag(Xstar) - correction, 1e-10)
    if include_noise:
        var = var + op.noise()
    return var


# ---------------------------------------------------------------------------
# incremental updates (streaming observations)
# ---------------------------------------------------------------------------


class CacheUpdateResult(NamedTuple):
    """`update_prediction_cache` output: the grown cache plus the state a
    caller needs to keep updating (`repro.serve.fleet` threads `precond`
    back in on the next batch) and the cost/shape diagnostics the
    incremental-vs-refit benchmark records."""

    cache: PredictionCache
    precond: Preconditioner      # extended (or freshly built) preconditioner
    mean_iters: jax.Array        # CG iterations of the warm mean solve
    variance_refreshed: bool     # True when compaction re-ran full Lanczos
    num_new: int                 # m, appended rows this batch


def update_prediction_cache(
    op,
    y: jax.Array,
    cache: PredictionCache,
    key: jax.Array,
    *,
    precond: Preconditioner | None = None,
    precond_rank: int = 100,
    lanczos_rank: int = 128,
    max_rank: int | None = None,
    pred_tol: float = 0.01,
    max_cg_iters: int = 400,
    min_cg_iters: int = 1,
    iter_block: int = 16,
    jitter: float = 1e-6,
) -> CacheUpdateResult:
    """Absorb m new observations into an existing prediction cache.

    `op` is an operator over the EXTENDED inputs X_ext = [X_old; X_new]
    (n + m rows) at the same hyperparameters the cache was built under
    (incremental updates hold hyperparameters fixed — drift is a refit,
    not an update), and `y` is the full (n + m,) target vector. `cache`
    covers the first n rows. Cost per batch is O(n*m)-class instead of the
    cold precompute's full tight solve + rank-r Lanczos pass:

    * MEAN — one PCG solve of K_hat_ext a = y_c warm-started from the
      zero-padded previous solution (the WarmStartEngine x0 pattern): the
      initial residual is [rho_old; y_new_c - K(X_new, X_old) a_old] — the
      old solve's residual plus the predictive residual at the new points —
      so a model that fits its stream starts nearly converged and CG runs a
      handful of iterations, not a cold solve's schedule. The solve is
      host-paced in `iter_block`-iteration jitted blocks with early exit
      between blocks (`_pcg_blocked`) so the warm start saves WALL-CLOCK,
      not just masked iterations. The
      preconditioner is REUSED via `pivchol.extend_preconditioner`
      (zero-padded factor, Woodbury inner block unchanged) rather than
      refactorized; pass the previous batch's `precond` back in.

    * VARIANCE — the rank-r Lanczos cache is extended with its own basis
      (LOVE-style): with the blocking K_hat_ext = [[A, B^T], [B, C]], the
      exact blockwise inverse needs A^{-1} only through A^{-1} B^T, which
      the cache already approximates as Q T^{-1} Q^T B^T. The update
      appends m columns F = Q T^{-1} (Q^T B^T) and the Schur complement
      S = C - B F:

          Q_ext = [[Q, F], [0, -I_m]],   T_ext = blockdiag(T, S)

      so Q_ext T_ext^{-1} Q_ext^T is exactly the Woodbury block inverse
      with the cached A-approximation spliced in — PSD by construction
      (S >= sigma^2 I because the cache UNDERestimates A^{-1}), and served
      by `predict_var_cached` unchanged since blockdiag Cholesky factors
      blockwise. Cost: one (m, n) kernel block + O(n m r) GEMMs, no solves.
      The rank grows by m per batch; once it would exceed `max_rank`
      (default 2 * lanczos_rank), the update COMPACTS — re-runs the full
      rank-`lanczos_rank` Lanczos pass on the extended operator
      (`variance_refreshed=True`), which is the periodic full refresh that
      bounds both serve-time O(n r) cost and approximation-error growth.

    Accuracy envelope: the mean matches a cold refit within the CG
    tolerance (same system, same tol, warm start only changes iteration
    count); the extended variance carries the previous cache's LOVE error
    through F, so update-vs-refit agreement degrades gracefully with
    (lanczos_rank / n) exactly like the cold cache itself — pinned by
    tests/test_predcache.py against both the cold refit and the exact
    PCG variance oracle.
    """
    n_ext = int(op.shape[0])
    n_prev = int(cache.mean_cache.shape[0])
    m = n_ext - n_prev
    if m <= 0:
        raise ValueError(
            f"operator covers {n_ext} rows but the cache already covers "
            f"{n_prev} — update_prediction_cache needs at least one new row")
    sdt = solver_dtype(op, y)
    yc = (y - constant_mean(op.params)).astype(sdt)

    if precond is not None:
        precond = extend_preconditioner(precond, n_ext - precond.L.shape[0])
    else:
        precond = op.preconditioner(precond_rank)

    x0 = jnp.concatenate(
        [cache.mean_cache.astype(sdt), jnp.zeros((m,), sdt)])
    res, mean_iters = _pcg_blocked(
        op, yc[:, None], precond, x0=x0[:, None], tol=pred_tol,
        max_iters=max_cg_iters, min_iters=min_cg_iters, block=iter_block)
    mean_cache = res.solution[:, 0]

    r_prev = int(cache.var_Q.shape[1])
    limit = 2 * lanczos_rank if max_rank is None else int(max_rank)
    if r_prev + m > limit:
        Q, T_chol = build_variance_cache(op, key, lanczos_rank=lanczos_rank)
        refreshed = True
    else:
        Q, T_chol = _extend_variance_cache(op, cache, n_prev, sdt, jitter)
        refreshed = False

    return CacheUpdateResult(
        cache=PredictionCache(mean_cache, Q, T_chol, res.rel_residual),
        precond=precond, mean_iters=mean_iters,
        variance_refreshed=refreshed, num_new=m)


@partial(jax.jit, static_argnums=(0,),
         static_argnames=("max_iters", "min_iters", "tol"))
def _pcg_block_jit(op, B, precond, x0, *, max_iters, min_iters, tol):
    """One jitted PCG block with a COMPILE-CACHE-STABLE signature.

    Calling eager `pcg` with a freshly built preconditioner retraces the
    whole scan every call (the Woodbury solve closure has a new identity),
    which on the serve path would recompile on EVERY `observe()` batch.
    Here the operator is a static arg (hashed by identity — stable while a
    fleet entry is resident) and the preconditioner's arrays travel as a
    `jax.tree_util.Partial` pytree, so repeated updates at a given shape
    reuse one executable.
    """
    solve = jax.tree_util.Partial(Preconditioner.solve, precond)
    return pcg(op, B, solve, x0=x0, max_iters=max_iters,
               min_iters=min_iters, tol=tol)


def _pcg_blocked(op, B, precond, *, tol, max_iters, min_iters, block, x0):
    """Host-paced PCG: `block`-iteration solves with a convergence check
    between blocks.

    Run one small fixed-shape block (`_pcg_block_jit`), sync the
    relative residual, stop when it clears `tol`. Each block warm-starts
    from the previous block's solution — mathematically the same iterate
    sequence. `pcg` itself leaves its loop once no column is active, so
    the blocks save no MVMs over one `pcg` call; they are left for a
    simplification to remove.

    Returns (last block's PCGResult, total iterations applied per column).
    """
    total_iters = None
    res = None
    done = 0
    while done < max_iters:
        k = min(block, max_iters - done)
        res = _pcg_block_jit(
            op, B, precond, x0, max_iters=k,
            min_iters=min(min_iters, k) if done == 0 else 1, tol=tol)
        applied = res.iterations
        total_iters = applied if total_iters is None else total_iters + applied
        done += k
        if float(jnp.max(res.rel_residual)) <= tol:  # host sync per block
            break
        x0 = res.solution
    return res, total_iters


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _extend_variance_cache(op, cache: PredictionCache, n_prev: int,
                           sdt, jitter: float):
    """The Woodbury rank extension of the LOVE cache (see
    `update_prediction_cache`): one (m, n_ext) kernel block, no solves.
    Jitted with the operator static (identity-hashed) and the cache arrays
    dynamic, for the same compile-cache stability as `_pcg_block_jit`."""
    X_new = op.X[n_prev:]
    m = X_new.shape[0]
    R = op.kernel_rows(X_new).astype(sdt)       # (m, n_ext), noise-free
    Bt = R[:, :n_prev].T                        # (n_prev, m) = B^T
    C = R[:, n_prev:] + (op.noise() + jitter) * jnp.eye(m, dtype=sdt)

    Q = cache.var_Q.astype(sdt)                 # (n_prev, r)
    T_chol = cache.var_T_chol.astype(sdt)
    W = jax.scipy.linalg.cho_solve((T_chol, True), Q.T @ Bt)   # (r, m)
    F = Q @ W                                   # (n_prev, m) ~= A^{-1} B^T
    S = C - Bt.T @ F
    S = 0.5 * (S + S.T) + jitter * jnp.eye(m, dtype=sdt)
    S_chol = jnp.linalg.cholesky(S)

    r = Q.shape[1]
    Q_ext = jnp.block([[Q, F],
                       [jnp.zeros((m, r), sdt), -jnp.eye(m, dtype=sdt)]])
    T_chol_ext = jnp.block(
        [[T_chol, jnp.zeros((r, m), sdt)],
         [jnp.zeros((m, r), sdt), S_chol]])
    return Q_ext, T_chol_ext
