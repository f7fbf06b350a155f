"""Batched preconditioned conjugate gradients (mBCG) with tridiagonal tracking.

This is the BBMM engine of Gardner et al. [11] that the paper builds on: one
call solves K_hat^{-1} [y, z_1..z_t] for all right-hand sides simultaneously
(sharing every kernel MVM across columns) and records the CG step/momentum
coefficients (alpha_j, beta_j), which define the Lanczos tridiagonalization
T of P^{-1/2} K_hat P^{-1/2} used by the SLQ log-determinant estimator
(`repro.core.slq`).

Two loop structures:
  * `method="standard"` — textbook PCG; two *dependent* inner-product
    reductions per iteration (paper-faithful: this is what GPyTorch runs).
  * `method="pipelined"` — Chronopoulos–Gear CG: algebraically identical
    iterates, but gamma = <r, u>, delta = <w, u> and the convergence norm
    <r, r> are all formed from vectors available before any reduction, so
    they are fused into ONE all-reduce per iteration. Under the distributed
    engine this halves the blocking collective count (beyond-paper
    optimization; see EXPERIMENTS.md §Perf).

Each loop is a `lax.while_loop` over at most `max_iters` iterations with
per-column convergence masking: a converged column's alpha and beta are 0,
so it stays frozen while the others iterate, and the loop leaves as soon
as no column is active (`_masked_loop`). That exit changes no output: an
iteration in which every column is inactive leaves the state as it was
and records alpha = beta = 0, active = False — exactly what the rows of
the preallocated `(max_iters, t)` records already hold — so the solution
and the coefficient traces are bitwise those of the whole trip count;
only `PCGResult.traversals` reads fewer. The exit predicate is formed from
the all-reduced norms (`allreduce` of ||r||^2, or rr), so on a mesh every
device computes the same predicate and leaves at the same iteration: no
ragged iteration counts, no stragglers, and the compiled HLO is
identical across steps.

The solver is warm-startable: `pcg(..., x0=...)` seeds the iteration with a
previous solution (r0 = B - K x0, one extra MVM), and `PCGResult.state` is a
`SolveState` carrying the converged solutions for the next call — the basis
of the amortized training engine (`repro.train.solver_state`), where
successive optimizer steps solve nearly identical systems. `x0=None`
reproduces the zero-start loop bitwise.

Kernel access is injected as a `repro.core.operators.KernelOperator`: one
object supplies both the MVM (dense / partitioned / Pallas-fused / sharded,
optionally with a bf16-compute fast path) and the matching `allreduce` — a
function summing per-shard partial reductions across the row axis (identity
on a single device, `lax.psum` under shard_map) — see
`repro.core.distributed`.

Operators that report `supports_fused_step` (the Pallas megakernel path)
additionally supply `fused_matvec_dots`: the MVM and the iteration's whole
reduction block out of ONE kernel launch. Both loop bodies exploit it —
the standard method fuses <p, Kp> and ||r||^2 into the MVM (its <r, z>
reduction depends on alpha and stays separate); the pipelined method's
reductions are ALL formable pre-reduction, so a warm iteration becomes a
single launch plus the O(nk) preconditioner apply. See the `fused` arg.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

# HLO name scopes (op_name metadata: they change no op); device-side
# accounting leaves via PCGResult.iterations / .traversals — returned aux,
# never host callbacks on the jit path (see repro.obs)
from repro.obs.profiling import named_scope


class SolveState(NamedTuple):
    """Portable warm-start state for a linear system that recurs across
    optimizer steps.

    `solutions` is the converged solution block of the last call — the
    natural `x0` for the next call against a nearby K_hat. `probes` is
    filled in by MLL-level callers (`repro.core.mll.operator_mll_forward`)
    that reuse the SAME SLQ probe block across steps, which is what makes
    warm-starting the probe columns meaningful at all: a fresh probe draw
    would invalidate the previous solutions as initial guesses.
    """

    solutions: jax.Array            # (n, t) converged solutions
    probes: jax.Array | None = None  # (n, t-1) reused SLQ probe block

    def pad_rows(self, m: int) -> "SolveState":
        """Zero-pad the state to m appended rows (streaming observations).

        The padded SOLUTIONS remain valid x0 guesses for the grown system —
        CG is exact from any start, and a zero guess on the new rows is the
        natural cold start for them. The padded PROBES are dropped: SLQ
        probes must be drawn from N(0, P) over the NEW row count, and a
        zero-padded draw is not a sample from the extended P — callers
        (`repro.train.solver_state.WarmStartEngine.extend_rows`) must treat
        the next step as a refresh.
        """
        if m < 0:
            raise ValueError(f"cannot pad SolveState by {m} rows")
        if m == 0:
            return self
        pad = jnp.zeros((m, self.solutions.shape[1]), self.solutions.dtype)
        return SolveState(
            solutions=jnp.concatenate([self.solutions, pad], axis=0),
            probes=None)


class PCGResult(NamedTuple):
    solution: jax.Array    # (n, t)
    alphas: jax.Array      # (m, t) CG step sizes (0 where column was frozen)
    betas: jax.Array       # (m, t) CG momentum coefficients
    active: jax.Array      # (m, t) bool, iteration actually applied
    rz0: jax.Array         # (t,) r0^T P^{-1} r0 (= z^T P^{-1} z when x0=0;
                           #      the SLQ probe norms)
    rel_residual: jax.Array  # (t,) final ||r|| / ||b||
    iterations: jax.Array  # (t,) iterations applied per column
    # () int32: operator applications (kernel traversals) the solve ran —
    # one per loop body executed, plus the warm start's residual B - K x0
    # and the pipelined loop's pre-loop MVM. Counted in the loop carry, so
    # it is the executed count: the loop leaves once no column is active.
    traversals: jax.Array
    # (m, t) per-iteration relative residuals, or None unless the solve
    # was called with track_residuals=True (opt-in: by default the loop
    # records only (alpha, beta, active), keeping the untracked jaxpr
    # identical). Rows past the loop's exit repeat the frozen value.
    # This is the health-monitor feed (repro.obs.health): stagnation /
    # divergence sentinels read the trajectory, not just the endpoint.
    residuals: jax.Array | None = None

    @property
    def state(self) -> SolveState:
        """Warm-start handle: feed `state.solutions` as the next `x0`."""
        return SolveState(solutions=self.solution)


def _identity(x: jax.Array) -> jax.Array:
    return x


def pcg(
    A,
    B: jax.Array,
    precond_solve: Callable[[jax.Array], jax.Array] | None = None,
    *,
    max_iters: int = 100,
    min_iters: int = 3,
    tol: float = 1.0,
    allreduce: Callable[[jax.Array], jax.Array] | None = None,
    method: str = "standard",
    x0: jax.Array | None = None,
    fused: bool | None = None,
    track_residuals: bool = False,
) -> PCGResult:
    """Solve K_hat U = B for all columns of B at once.

    Args:
      A: a `repro.core.operators.KernelOperator` (preferred — its `matvec`
        is the only access to the kernel matrix, and its `allreduce` is
        picked up automatically), or a bare callable v (n, t) -> K_hat v.
        Under the sharded backend n is the per-shard row count.
      B: (n, t) right-hand sides. CG state (residuals, directions,
        reductions) lives in B.dtype regardless of the operator's internal
        compute dtype — the mixed-precision path never touches it.
      precond_solve: v -> P^{-1} v; identity if None.
      tol: relative residual threshold ||r||/||b|| (paper: 1.0 for training,
        <= 0.01 for prediction solves).
      allreduce: sums partial scalar reductions over row shards; identity on
        one device. Defaults to A.allreduce for operator inputs.
      method: "standard" | "pipelined".
      x0: (n, t) initial guess — e.g. `PCGResult.state.solutions` from the
        previous optimizer step's solve against a nearby K_hat. None keeps
        the zero start and reproduces the x0-free loop bitwise (the r0 = B
        branch is the identical trace; no extra MVM is issued). The
        convergence norm stays ||r||/||b|| with b from B, so a warm start
        that begins nearly converged exits at `min_iters`.
      fused: use the operator's `fused_matvec_dots` — MVM and the
        iteration's reduction block from ONE kernel launch. None (default)
        engages it exactly where the operator reports
        `supports_fused_step` (the Pallas megakernel path); True forces
        the fused loop body onto any operator (the base column-batched
        fallback is numerically the same reductions); False forces the
        classic body. Bare-callable A always runs the classic body
        bitwise-unchanged — the golden-pinned trace.
      track_residuals: record the per-iteration relative residuals in
        `PCGResult.residuals` (an extra (max_iters, t) loop record). The
        residual norms are already computed every iteration for the
        convergence mask, so tracking adds only the extra record — but
        it DOES change the compiled program, so it is off by default and
        the False path's jaxpr is byte-identical to the pre-tracking one
        (pinned by tests/test_obs_v2.py).
    """
    fused_mvm = None
    if hasattr(A, "matvec"):
        mvm = A.matvec
        if allreduce is None:
            allreduce = A.allreduce
        if fused is not False and hasattr(A, "fused_matvec_dots"):
            if fused is True or getattr(A, "supports_fused_step", False):
                fused_mvm = A.fused_matvec_dots
    else:
        mvm = A
    if B.ndim == 1:
        res = pcg(A if fused_mvm is not None else mvm, B[:, None],
                  precond_solve, max_iters=max_iters,
                  min_iters=min_iters, tol=tol, allreduce=allreduce, method=method,
                  x0=None if x0 is None else x0[:, None], fused=fused,
                  track_residuals=track_residuals)
        return res._replace(solution=res.solution[:, 0])

    if precond_solve is None:
        precond_solve = _identity
    if allreduce is None:
        allreduce = _identity
    if method == "standard":
        with named_scope("pcg"):
            return _pcg_standard(mvm, B, precond_solve, max_iters, min_iters,
                                 tol, allreduce, x0, fused_mvm,
                                 track_residuals)
    if method == "pipelined":
        with named_scope("pcg"):
            return _pcg_pipelined(mvm, B, precond_solve, max_iters, min_iters,
                                  tol, allreduce, x0, fused_mvm,
                                  track_residuals)
    raise ValueError(f"unknown PCG method {method!r}")


def _safe_div(num, den):
    ok = jnp.abs(den) > 1e-30
    return jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)


def _warm_init(mvm, B, x0):
    """(u0, r0) for an optional initial guess.

    x0=None must keep the historical trace bitwise: u = 0, r = B, and no
    MVM is issued. With a guess, one extra MVM forms r0 = B - K x0.
    """
    if x0 is None:
        return jnp.zeros_like(B), B
    x0 = x0.astype(B.dtype)
    return x0, B - mvm(x0)


def _masked_loop(step, more, state, ref, max_iters, dtype, track_residuals):
    """Run `step` for at most `max_iters` iterations, leaving once `more`
    turns False.

    `step(state, j) -> (state, rows)` runs iteration j. `rows` is its
    (alpha, beta, active[, rel]) record, written to row j of preallocated
    `(max_iters, t)` buffers; rows of iterations never run keep alpha =
    beta = 0 and active = False, what an iteration with every column
    inactive records. `more(state, j)` says whether iteration j can have
    an active column; it runs in the loop's condition, apart from the
    body, so it adds no consumer to the body's reductions (which could
    change how the compiler fuses them, and so their rounding). `ref` is
    a (t,) reduction formed before the loop: the records take its type,
    sharding included, as the rows written into them have it. Returns
    (state, records, iterations run).
    """
    from repro.models.runtime_flags import layer_scan_unroll
    # the dry-run compiles the body with 1 and with 2 iterations in it to
    # extrapolate its cost; an iteration run past the exit is fully
    # masked, so the second changes no output
    unroll = layer_scan_unroll()
    if max_iters % unroll:
        unroll = 1

    def record(dt):
        return jnp.broadcast_to(jnp.zeros_like(ref, dt)[None],
                                (max_iters,) + ref.shape)

    recs = (record(dtype), record(dtype), record(bool))
    if track_residuals:
        recs = recs + (record(dtype),)

    def cond(carry):
        j, state, _ = carry
        return (j < max_iters) & more(state, j)

    def body(carry):
        j, state, recs = carry
        for _ in range(unroll):
            state, rows = step(state, j)
            recs = tuple(jax.lax.dynamic_update_index_in_dim(buf, row, j, 0)
                         for buf, row in zip(recs, rows))
            j = j + 1
        return j, state, recs

    ran, state, recs = jax.lax.while_loop(
        cond, body, (jnp.int32(0), state, recs))
    return state, recs, ran


def _fill_rows(buf, ran, row):
    """`buf` with every row from `ran` on set to `row`."""
    kept = jnp.arange(buf.shape[0])[:, None] < ran
    return jnp.where(kept, buf, row[None, :])


def _pcg_standard(mvm, B, precond_solve, max_iters, min_iters, tol, allreduce,
                  x0=None, fused_mvm=None, track_residuals=False):
    dtype = B.dtype

    def vdot(a, b):
        return allreduce(jnp.sum(a * b, axis=0))

    u, r = _warm_init(mvm, B, x0)
    traversals = jnp.int32(x0 is not None)
    z = precond_solve(r)
    # reduction 0: <r,z> and <b,b> fused (both available up front)
    init = allreduce(jnp.stack([jnp.sum(r * z, 0), jnp.sum(B * B, 0)]))
    rz, b_norm2 = init[0], jnp.maximum(init[1], 1e-30)
    rz0 = rz
    p = z

    def step(carry, j):
        u, r, z, p, rz, k, _ = carry
        if fused_mvm is None:
            with named_scope("pcg.matvec"):
                Kp = mvm(p)
            # reduction 1: <p, Kp> and <r, r> fused
            red1 = allreduce(
                jnp.stack([jnp.sum(p * Kp, 0), jnp.sum(r * r, 0)]))
            pKp, r_norm2 = red1[0], red1[1]
        else:
            # megakernel step: the MVM epilogue already holds the row tiles
            # of Kp in VMEM — <p, Kp> and <r, r> come out of the same launch
            with named_scope("pcg.fused_step"):
                Kp, dots = fused_mvm(p, r)
            red1 = allreduce(dots.astype(dtype))
            pKp, r_norm2 = red1[0], red1[2]
        rel = jnp.sqrt(r_norm2 / b_norm2)
        active = (rel > tol) | (j < min_iters)
        alpha = jnp.where(active, _safe_div(rz, pKp), 0.0)
        u = u + alpha * p
        r = r - alpha * Kp
        z_new = precond_solve(r)
        # reduction 2 (dependent on reduction 1's alpha): <r, z>
        rz_new = vdot(r, z_new)
        beta = jnp.where(active, _safe_div(rz_new, rz), 0.0)
        p = jnp.where(active, z_new + beta * p, p)
        z = jnp.where(active, z_new, z)
        rz = jnp.where(active, rz_new, rz)
        rows = (alpha.astype(dtype), beta.astype(dtype), active)
        if track_residuals:
            rows = rows + (rel.astype(dtype),)
        return (u, r, z, p, rz, k + 1, active), rows

    def more(carry, j):
        # ||r||^2 comes out of an iteration's MVM launch, so the loop
        # learns that every column is done only after running it
        return jnp.any(carry[-1])

    carry = (u, r, z, p, rz, traversals, jnp.ones_like(rz, bool))
    (u, r, _, _, _, traversals, _), recs, ran = _masked_loop(
        step, more, carry, rz0, max_iters, dtype, track_residuals)
    alphas, betas, actives = recs[:3]
    residuals = None
    if track_residuals:
        residuals = recs[3]
        if max_iters:
            # the loop left after an iteration with no column active: r
            # stands still from there, so each skipped row repeats the last
            residuals = _fill_rows(residuals, ran,
                                   residuals[jnp.maximum(ran - 1, 0)])
    rel = jnp.sqrt(vdot(r, r) / b_norm2)
    iters = jnp.sum(actives, axis=0)
    return PCGResult(u, alphas, betas, actives, rz0, rel, iters, traversals,
                     residuals)


def _pcg_pipelined(mvm, B, precond_solve, max_iters, min_iters, tol, allreduce,
                   x0=None, fused_mvm=None, track_residuals=False):
    """Chronopoulos–Gear CG: one fused all-reduce per iteration."""
    dtype = B.dtype

    def fused(r, u, w):
        # local partials for [<r,u>, <w,u>, <r,r>] then ONE allreduce
        part = jnp.stack([jnp.sum(r * u, 0), jnp.sum(w * u, 0), jnp.sum(r * r, 0)])
        red = allreduce(part)
        return red[0], red[1], red[2]

    def mvm_and_reductions(u_, r_):
        """w = K_hat u plus (gamma, delta, rr) — the Chronopoulos–Gear
        structure makes ALL three reductions formable alongside the MVM,
        so with an operator megakernel a warm iteration is one launch."""
        if fused_mvm is None:
            with named_scope("pcg.matvec"):
                w_ = mvm(u_)
            return (w_,) + fused(r_, u_, w_)
        with named_scope("pcg.fused_step"):
            w_, dots = fused_mvm(u_, r_)
        red = allreduce(dots.astype(dtype))
        return w_, red[1], red[0], red[2]

    x, r = _warm_init(mvm, B, x0)
    b_norm2 = jnp.maximum(allreduce(jnp.sum(B * B, 0)), 1e-30)
    u = precond_solve(r)
    w, gamma, delta, rr = mvm_and_reductions(u, r)
    traversals = jnp.int32(1 + (x0 is not None))
    rz0 = gamma
    p = jnp.zeros_like(B)
    s = jnp.zeros_like(B)
    alpha_prev = jnp.ones_like(gamma)
    gamma_prev = jnp.ones_like(gamma)

    def mask(rr, j):
        rel = jnp.sqrt(rr / b_norm2)
        return rel, (rel > tol) | (j < min_iters)

    def more(carry, j):
        # the mask needs only the carried rr, so the loop leaves before
        # launching an MVM that no column would use
        return jnp.any(mask(carry[8], j)[1])

    def step(carry, j):
        (x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev,
         k) = carry
        rel, active = mask(rr, j)
        first = j == 0
        beta = jnp.where(first, 0.0, _safe_div(gamma, gamma_prev))
        denom = delta - beta * gamma / jnp.where(first, 1.0, alpha_prev)
        alpha = jnp.where(active, _safe_div(gamma, denom), 0.0)
        beta = jnp.where(active, beta, 0.0)
        p = jnp.where(active, u + beta * p, p)
        s = jnp.where(active, w + beta * s, s)
        x = x + alpha * p
        r = r - alpha * s
        u_new = precond_solve(r)
        w_new, gamma_new, delta_new, rr_new = mvm_and_reductions(u_new, r)
        u = jnp.where(active, u_new, u)
        w = jnp.where(active, w_new, w)
        gamma_prev_n = jnp.where(active, gamma, gamma_prev)
        alpha_prev_n = jnp.where(active, alpha, alpha_prev)
        gamma = jnp.where(active, gamma_new, gamma)
        delta = jnp.where(active, delta_new, delta)
        rr = jnp.where(active, rr_new, rr)
        rows = (alpha.astype(dtype), beta.astype(dtype), active)
        if track_residuals:
            rows = rows + (rel.astype(dtype),)
        return ((x, r, u, w, p, s, gamma, delta, rr, gamma_prev_n,
                 alpha_prev_n, k + 1), rows)

    carry = (x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev,
             traversals)
    (x, r, _, _, _, _, _, _, rr, _, _, traversals), recs, ran = _masked_loop(
        step, more, carry, rz0, max_iters, dtype, track_residuals)
    alphas, betas, actives = recs[:3]
    residuals = None
    if track_residuals:
        # each skipped row would record the rel of the carried rr, which
        # no longer changes
        residuals = _fill_rows(recs[3], ran,
                               mask(rr, ran)[0].astype(dtype))
    rel = jnp.sqrt(allreduce(jnp.sum(r * r, 0)) / b_norm2)
    iters = jnp.sum(actives, axis=0)
    return PCGResult(x, alphas, betas, actives, rz0, rel, iters, traversals,
                     residuals)


def solve_tolerance_iters(tol: float) -> int:
    """Heuristic iteration cap for a requested tolerance (paper Sec. 3)."""
    if tol >= 1.0:
        return 20
    if tol >= 0.1:
        return 50
    if tol >= 0.01:
        return 100
    return 200
