"""PCG/mBCG: solve accuracy, pipelined equivalence, convergence masking."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep; deterministic fallback (conftest dir is on sys.path)
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import (
    dense_khat, init_params, kmvm, make_preconditioner, pcg,
)

P64 = dict(dtype=jnp.float64)


def _setup(rng, n=120, d=3, noise=0.3):
    X = jnp.asarray(rng.normal(size=(n, d)))
    params = init_params(noise=noise, **P64)
    Khat = dense_khat("matern32", X, params)
    mvm = lambda V: kmvm("matern32", X, V, params, row_block=32)
    return X, params, Khat, mvm


def test_pcg_matches_direct_solve(rng):
    X, params, Khat, mvm = _setup(rng)
    B = jnp.asarray(rng.normal(size=(X.shape[0], 3)))
    pre = make_preconditioner("matern32", X, params, 30)
    res = pcg(mvm, B, pre.solve, max_iters=200, tol=1e-10, min_iters=5)
    direct = jnp.linalg.solve(Khat, B)
    np.testing.assert_allclose(np.asarray(res.solution), np.asarray(direct),
                               atol=1e-6)
    assert np.all(np.asarray(res.rel_residual) < 1e-8)


def test_pipelined_equals_standard(rng):
    X, params, Khat, mvm = _setup(rng)
    B = jnp.asarray(rng.normal(size=(X.shape[0], 2)))
    pre = make_preconditioner("matern32", X, params, 30)
    r1 = pcg(mvm, B, pre.solve, max_iters=150, tol=1e-10, min_iters=5)
    r2 = pcg(mvm, B, pre.solve, max_iters=150, tol=1e-10, min_iters=5,
             method="pipelined")
    np.testing.assert_allclose(np.asarray(r1.solution),
                               np.asarray(r2.solution), atol=1e-6)


def test_preconditioner_reduces_iterations(rng):
    X, params, Khat, mvm = _setup(rng, n=200, noise=0.05)
    y = jnp.asarray(rng.normal(size=(X.shape[0], 1)))
    r_no = pcg(mvm, y, None, max_iters=300, tol=1e-6, min_iters=2)
    pre = make_preconditioner("matern32", X, params, 60)
    r_pre = pcg(mvm, y, pre.solve, max_iters=300, tol=1e-6, min_iters=2)
    assert int(r_pre.iterations[0]) < int(r_no.iterations[0])


def test_convergence_masking_freezes_columns(rng):
    """A converged column's coefficients are zeroed; others keep iterating."""
    X, params, Khat, mvm = _setup(rng)
    easy = np.zeros((X.shape[0], 1))
    easy[0] = 1e-3
    hard = rng.normal(size=(X.shape[0], 1))
    B = jnp.asarray(np.concatenate([easy, hard], 1))
    res = pcg(mvm, B, None, max_iters=100, tol=1e-4, min_iters=2)
    assert int(res.iterations[0]) <= int(res.iterations[1])
    # frozen iterations have alpha == 0
    n_active0 = int(res.iterations[0])
    assert np.allclose(np.asarray(res.alphas)[n_active0:, 0], 0.0)


def test_1d_rhs_roundtrip(rng):
    X, params, Khat, mvm = _setup(rng)
    y = jnp.asarray(rng.normal(size=X.shape[0]))
    res = pcg(mvm, y, None, max_iters=200, tol=1e-10, min_iters=5)
    assert res.solution.shape == y.shape
    np.testing.assert_allclose(np.asarray(res.solution),
                               np.asarray(jnp.linalg.solve(Khat, y)), atol=1e-6)


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**16), t=st.integers(1, 4),
       method=st.sampled_from(["standard", "pipelined"]))
def test_pcg_property_random_spd(seed, t, method):
    """Property: for any kernel SPD system, PCG @ tight tol == direct solve."""
    rng = np.random.default_rng(seed)
    X, params, Khat, mvm = _setup(rng, n=64, noise=0.5)
    B = jnp.asarray(rng.normal(size=(64, t)))
    res = pcg(mvm, B, None, max_iters=200, tol=1e-11, min_iters=5,
              method=method)
    np.testing.assert_allclose(np.asarray(res.solution),
                               np.asarray(jnp.linalg.solve(Khat, B)),
                               atol=1e-5)


def test_loose_tolerance_stops_early(rng):
    """Paper: eps = 1 training tolerance => far fewer iterations."""
    X, params, Khat, mvm = _setup(rng, n=200, noise=0.1)
    y = jnp.asarray(rng.normal(size=(X.shape[0], 1)))
    pre = make_preconditioner("matern32", X, params, 30)
    loose = pcg(mvm, y, pre.solve, max_iters=200, tol=1.0, min_iters=2)
    tight = pcg(mvm, y, pre.solve, max_iters=200, tol=1e-8, min_iters=2)
    assert int(loose.iterations[0]) < int(tight.iterations[0])


# ---------------------------------------------------------------------------
# warm starts (x0) — property tests + the x0=None bitwise guarantee
# ---------------------------------------------------------------------------
#
# _golden_pcg_* are VERBATIM frozen copies of the loops as they stood before
# the x0 argument existed (the "pre-PR" reference). They pin the guarantee
# that threading x0 through the solver changed nothing when x0 is None: the
# live solver must reproduce their solution AND the alpha/beta/rz0 traces
# (which the SLQ log-determinant consumes) bitwise.


def _golden_safe_div(num, den):
    ok = jnp.abs(den) > 1e-30
    return jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)


def _golden_pcg_standard(mvm, B, precond_solve, max_iters, min_iters, tol):
    dtype = B.dtype
    allreduce = lambda x: x

    def vdot(a, b):
        return allreduce(jnp.sum(a * b, axis=0))

    u = jnp.zeros_like(B)
    r = B
    z = precond_solve(r)
    init = allreduce(jnp.stack([jnp.sum(r * z, 0), jnp.sum(B * B, 0)]))
    rz, b_norm2 = init[0], jnp.maximum(init[1], 1e-30)
    rz0 = rz
    p = z

    def body(carry, j):
        u, r, z, p, rz = carry
        Kp = mvm(p)
        red1 = allreduce(jnp.stack([jnp.sum(p * Kp, 0), jnp.sum(r * r, 0)]))
        pKp, r_norm2 = red1[0], red1[1]
        rel = jnp.sqrt(r_norm2 / b_norm2)
        active = (rel > tol) | (j < min_iters)
        alpha = jnp.where(active, _golden_safe_div(rz, pKp), 0.0)
        u = u + alpha * p
        r = r - alpha * Kp
        z_new = precond_solve(r)
        rz_new = vdot(r, z_new)
        beta = jnp.where(active, _golden_safe_div(rz_new, rz), 0.0)
        p = jnp.where(active, z_new + beta * p, p)
        z = jnp.where(active, z_new, z)
        rz = jnp.where(active, rz_new, rz)
        return (u, r, z, p, rz), (alpha.astype(dtype), beta.astype(dtype), active)

    from repro.models.runtime_flags import layer_scan_unroll
    (u, r, _, _, _), (alphas, betas, actives) = jax.lax.scan(
        body, (u, r, z, p, rz), jnp.arange(max_iters),
        unroll=layer_scan_unroll())
    rel = jnp.sqrt(vdot(r, r) / b_norm2)
    iters = jnp.sum(actives, axis=0)
    return u, alphas, betas, actives, rz0, rel, iters


def _golden_pcg_pipelined(mvm, B, precond_solve, max_iters, min_iters, tol):
    dtype = B.dtype
    allreduce = lambda x: x

    def fused(r, u, w):
        part = jnp.stack([jnp.sum(r * u, 0), jnp.sum(w * u, 0), jnp.sum(r * r, 0)])
        red = allreduce(part)
        return red[0], red[1], red[2]

    x = jnp.zeros_like(B)
    r = B
    b_norm2 = jnp.maximum(allreduce(jnp.sum(B * B, 0)), 1e-30)
    u = precond_solve(r)
    w = mvm(u)
    gamma, delta, rr = fused(r, u, w)
    rz0 = gamma
    p = jnp.zeros_like(B)
    s = jnp.zeros_like(B)
    alpha_prev = jnp.ones_like(gamma)
    gamma_prev = jnp.ones_like(gamma)

    def body(carry, j):
        x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev = carry
        rel = jnp.sqrt(rr / b_norm2)
        active = (rel > tol) | (j < min_iters)
        first = j == 0
        beta = jnp.where(first, 0.0, _golden_safe_div(gamma, gamma_prev))
        denom = delta - beta * gamma / jnp.where(first, 1.0, alpha_prev)
        alpha = jnp.where(active, _golden_safe_div(gamma, denom), 0.0)
        beta = jnp.where(active, beta, 0.0)
        p = jnp.where(active, u + beta * p, p)
        s = jnp.where(active, w + beta * s, s)
        x = x + alpha * p
        r = r - alpha * s
        u_new = precond_solve(r)
        w_new = mvm(u_new)
        gamma_new, delta_new, rr_new = fused(r, u_new, w_new)
        u = jnp.where(active, u_new, u)
        w = jnp.where(active, w_new, w)
        gamma_prev_n = jnp.where(active, gamma, gamma_prev)
        alpha_prev_n = jnp.where(active, alpha, alpha_prev)
        gamma = jnp.where(active, gamma_new, gamma)
        delta = jnp.where(active, delta_new, delta)
        rr = jnp.where(active, rr_new, rr)
        return ((x, r, u, w, p, s, gamma, delta, rr, gamma_prev_n, alpha_prev_n),
                (alpha.astype(dtype), beta.astype(dtype), active))

    from repro.models.runtime_flags import layer_scan_unroll
    carry = (x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev)
    (x, r, *rest), (alphas, betas, actives) = jax.lax.scan(
        body, carry, jnp.arange(max_iters), unroll=layer_scan_unroll())
    rel = jnp.sqrt(jnp.sum(r * r, 0) / b_norm2)
    iters = jnp.sum(actives, axis=0)
    return x, alphas, betas, actives, rz0, rel, iters


_GOLDEN = {"standard": _golden_pcg_standard, "pipelined": _golden_pcg_pipelined}


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 2**16), t=st.integers(1, 3),
       method=st.sampled_from(["standard", "pipelined"]),
       tol=st.sampled_from([1.0, 1e-2, 1e-8]))
def test_pcg_x0_none_bitwise_matches_pre_pr_loop(seed, t, method, tol):
    """Property: x0=None (and x0=0, since K @ 0 == 0 exactly) reproduces the
    pre-x0 loop BITWISE — solution and the alpha/beta/active/rz0 traces the
    SLQ log-determinant estimator consumes."""
    rng = np.random.default_rng(seed)
    X, params, Khat, mvm = _setup(rng, n=72, noise=0.4)
    B = jnp.asarray(rng.normal(size=(72, t)))
    pre = make_preconditioner("matern32", X, params, 20)
    golden = _GOLDEN[method](mvm, B, pre.solve, 40, 3, tol)
    for x0 in (None, jnp.zeros_like(B)):
        res = pcg(mvm, B, pre.solve, max_iters=40, min_iters=3, tol=tol,
                  method=method, x0=x0)
        for got, want, name in zip(
                (res.solution, res.alphas, res.betas, res.active,
                 res.rz0, res.iterations),
                (golden[0], golden[1], golden[2], golden[3],
                 golden[4], golden[6]),
                ("solution", "alphas", "betas", "active", "rz0", "iters")):
            assert np.array_equal(np.asarray(got), np.asarray(want)), (
                method, "x0=0" if x0 is not None else "x0=None", name)


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**16), t=st.integers(1, 3),
       method=st.sampled_from(["standard", "pipelined"]),
       scale=st.floats(0.1, 10.0))
def test_pcg_arbitrary_x0_same_solution(seed, t, method, scale):
    """Property: an ARBITRARY initial guess converges to the zero-start
    solution at equal (tight) tolerance — warm starts change iteration
    counts, never the answer."""
    rng = np.random.default_rng(seed)
    X, params, Khat, mvm = _setup(rng, n=64, noise=0.5)
    B = jnp.asarray(rng.normal(size=(64, t)))
    x0 = jnp.asarray(scale * rng.normal(size=(64, t)))
    pre = make_preconditioner("matern32", X, params, 20)
    kw = dict(max_iters=200, min_iters=3, tol=1e-11, method=method)
    res_cold = pcg(mvm, B, pre.solve, **kw)
    res_warm = pcg(mvm, B, pre.solve, x0=x0, **kw)
    np.testing.assert_allclose(np.asarray(res_warm.solution),
                               np.asarray(res_cold.solution), atol=1e-6)
    # and both really solve the system
    np.testing.assert_allclose(np.asarray(res_warm.solution),
                               np.asarray(jnp.linalg.solve(Khat, B)),
                               atol=1e-5)


def test_pcg_near_converged_x0_exits_at_min_iters(rng):
    """Seeding with the exact solution leaves nothing to do: the relative
    residual collapses immediately and only the min_iters floor is applied."""
    X, params, Khat, mvm = _setup(rng)
    B = jnp.asarray(rng.normal(size=(X.shape[0], 2)))
    exact = jnp.linalg.solve(Khat, B)
    pre = make_preconditioner("matern32", X, params, 30)
    cold = pcg(mvm, B, pre.solve, max_iters=150, min_iters=2, tol=1e-8)
    warm = pcg(mvm, B, pre.solve, max_iters=150, min_iters=2, tol=1e-8,
               x0=exact)
    assert int(np.max(np.asarray(warm.iterations))) == 2
    assert int(np.min(np.asarray(cold.iterations))) > 2
    np.testing.assert_allclose(np.asarray(warm.solution), np.asarray(exact),
                               atol=1e-8)


def test_pcg_state_carries_solutions(rng):
    """PCGResult.state is the warm-start handle for the next call."""
    X, params, Khat, mvm = _setup(rng)
    B = jnp.asarray(rng.normal(size=(X.shape[0], 2)))
    res = pcg(mvm, B, None, max_iters=60, min_iters=3, tol=1e-6)
    state = res.state
    assert state.probes is None
    np.testing.assert_array_equal(np.asarray(state.solutions),
                                  np.asarray(res.solution))
    warm = pcg(mvm, B, None, max_iters=60, min_iters=2, tol=1e-6,
               x0=state.solutions)
    assert int(np.max(np.asarray(warm.iterations))) <= \
        int(np.max(np.asarray(res.iterations)))


# ---------------------------------------------------------------------------
# batched multi-RHS solves through KernelOperators (the fused-step surface)
# ---------------------------------------------------------------------------
#
# The MLL forward batches y and all SLQ probes into ONE (n, t) mBCG solve;
# on fused-capable operators each iteration is a single kernel launch that
# also produces the CG reductions. These properties pin the two guarantees
# that make that safe: (1) columns never couple — a batched solve equals t
# independent single-RHS solves; (2) the fused step is an implementation
# detail — opting out (fused=False) changes nothing beyond reduction
# summation order.

from repro.core import OperatorConfig, make_operator

OP_BACKENDS = ("dense", "partitioned", "pallas", "blocksparse")


def _operator(backend, X, params):
    plan = None
    if backend == "blocksparse":
        from repro.sparse import build_plan
        plan = build_plan("matern32", X, params, tile=32)
    return make_operator(
        OperatorConfig(kernel="matern32", backend=backend, row_block=32,
                       interpret=True, plan=plan), X, params)


@settings(deadline=None, max_examples=3)
@given(seed=st.integers(0, 2**16), t=st.integers(1, 8),
       method=st.sampled_from(["standard", "pipelined"]))
def test_batched_multirhs_matches_per_column(seed, t, method):
    """Property: one batched (n, t) solve == t single-RHS solves, column
    for column, <= 2e-5 in fp32 — on every backend, 1-8 RHS, both CG
    variants (the pallas rows run the fused megakernel step). Backends are
    looped in the body (not parametrize: the hypothesis shim's wrapper
    hides fixture-visible parameters from pytest)."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    params = init_params(noise=0.4, dtype=jnp.float32)
    B = jnp.asarray(rng.normal(size=(64, t)), jnp.float32)
    kw = dict(max_iters=120, min_iters=3, tol=1e-7, method=method)
    for backend in OP_BACKENDS:
        op = _operator(backend, X, params)
        batched = pcg(op, B, None, **kw)
        for j in range(t):
            single = pcg(op, B[:, j:j + 1], None, **kw)
            np.testing.assert_allclose(
                np.asarray(batched.solution[:, j]),
                np.asarray(single.solution[:, 0]), atol=2e-5,
                err_msg=f"{backend} col {j}/{t}")


@settings(deadline=None, max_examples=4)
@given(seed=st.integers(0, 2**16), t=st.integers(1, 8),
       method=st.sampled_from(["standard", "pipelined"]))
def test_fused_step_matches_classic_step(seed, t, method):
    """Property: the fused matvec+reductions step (pallas megakernel) and
    the classic two-launch step produce the same solve — solution AND the
    alpha/beta traces the SLQ log-determinant consumes."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    params = init_params(noise=0.4, dtype=jnp.float32)
    op = _operator("pallas", X, params)
    assert op.supports_fused_step
    B = jnp.asarray(rng.normal(size=(64, t)), jnp.float32)
    kw = dict(max_iters=100, min_iters=5, tol=1e-6, method=method)
    fused = pcg(op, B, None, fused=True, **kw)
    classic = pcg(op, B, None, fused=False, **kw)
    np.testing.assert_allclose(np.asarray(fused.solution),
                               np.asarray(classic.solution), atol=2e-5)
    # coefficient traces compare over the forced-active prefix only: past
    # min_iters the convergence mask may flip one iteration apart between
    # the two reduction orders, zeroing one trace but not the other
    np.testing.assert_allclose(np.asarray(fused.alphas)[:5],
                               np.asarray(classic.alphas)[:5],
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused.betas)[:5],
                               np.asarray(classic.betas)[:5],
                               rtol=1e-3, atol=1e-5)


def test_operator_solve_matches_direct(rng):
    """Batched operator solves land on the dense answer (fused default on
    the pallas backend, base-class fallback elsewhere)."""
    X = jnp.asarray(rng.normal(size=(72, 3)), jnp.float32)
    params = init_params(noise=0.4, dtype=jnp.float32)
    B = jnp.asarray(rng.normal(size=(72, 4)), jnp.float32)
    Khat64 = dense_khat("matern32", jnp.asarray(X, jnp.float64),
                        jax.tree.map(lambda a: jnp.asarray(a, jnp.float64),
                                     params))
    direct = np.asarray(jnp.linalg.solve(Khat64, jnp.asarray(B, jnp.float64)))
    for backend in OP_BACKENDS:
        op = _operator(backend, X, params)
        res = pcg(op, B, None, max_iters=200, min_iters=3, tol=1e-7)
        np.testing.assert_allclose(np.asarray(res.solution), direct,
                                   atol=3e-4, err_msg=backend)


# ---------------------------------------------------------------------------
# the early exit: the loop leaves once no column is active
# ---------------------------------------------------------------------------
#
# _fixed_trip_pcg_* are frozen copies of the loops as they stood before the
# early exit: `lax.scan`s over the whole trip count, with the x0 and
# track_residuals arguments (classic body, identity allreduce). An
# iteration in which no column is active changes no state and records
# alpha = beta = 0, active = False, so leaving the loop there must give
# every output bitwise — only the traversal count may differ.


def _fixed_trip_pcg_standard(mvm, B, precond_solve, max_iters, min_iters, tol,
                             x0, track_residuals):
    dtype = B.dtype

    def vdot(a, b):
        return jnp.sum(a * b, axis=0)

    if x0 is None:
        u, r = jnp.zeros_like(B), B
    else:
        u, r = x0, B - mvm(x0)
    z = precond_solve(r)
    init = jnp.stack([jnp.sum(r * z, 0), jnp.sum(B * B, 0)])
    rz, b_norm2 = init[0], jnp.maximum(init[1], 1e-30)
    rz0 = rz
    p = z

    def body(carry, j):
        u, r, z, p, rz = carry
        Kp = mvm(p)
        red1 = jnp.stack([jnp.sum(p * Kp, 0), jnp.sum(r * r, 0)])
        pKp, r_norm2 = red1[0], red1[1]
        rel = jnp.sqrt(r_norm2 / b_norm2)
        active = (rel > tol) | (j < min_iters)
        alpha = jnp.where(active, _golden_safe_div(rz, pKp), 0.0)
        u = u + alpha * p
        r = r - alpha * Kp
        z_new = precond_solve(r)
        rz_new = vdot(r, z_new)
        beta = jnp.where(active, _golden_safe_div(rz_new, rz), 0.0)
        p = jnp.where(active, z_new + beta * p, p)
        z = jnp.where(active, z_new, z)
        rz = jnp.where(active, rz_new, rz)
        ys = (alpha.astype(dtype), beta.astype(dtype), active)
        if track_residuals:
            ys = ys + (rel.astype(dtype),)
        return (u, r, z, p, rz), ys

    (u, r, _, _, _), ys = jax.lax.scan(body, (u, r, z, p, rz),
                                       jnp.arange(max_iters))
    rel = jnp.sqrt(vdot(r, r) / b_norm2)
    return (u, ys[0], ys[1], ys[2], rz0, rel, jnp.sum(ys[2], axis=0),
            ys[3] if track_residuals else None)


def _fixed_trip_pcg_pipelined(mvm, B, precond_solve, max_iters, min_iters,
                              tol, x0, track_residuals):
    dtype = B.dtype

    def fused(r, u, w):
        red = jnp.stack([jnp.sum(r * u, 0), jnp.sum(w * u, 0),
                         jnp.sum(r * r, 0)])
        return red[0], red[1], red[2]

    if x0 is None:
        x, r = jnp.zeros_like(B), B
    else:
        x, r = x0, B - mvm(x0)
    b_norm2 = jnp.maximum(jnp.sum(B * B, 0), 1e-30)
    u = precond_solve(r)
    w = mvm(u)
    gamma, delta, rr = fused(r, u, w)
    rz0 = gamma
    p = jnp.zeros_like(B)
    s = jnp.zeros_like(B)
    alpha_prev = jnp.ones_like(gamma)
    gamma_prev = jnp.ones_like(gamma)

    def body(carry, j):
        x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev = carry
        rel = jnp.sqrt(rr / b_norm2)
        active = (rel > tol) | (j < min_iters)
        first = j == 0
        beta = jnp.where(first, 0.0, _golden_safe_div(gamma, gamma_prev))
        denom = delta - beta * gamma / jnp.where(first, 1.0, alpha_prev)
        alpha = jnp.where(active, _golden_safe_div(gamma, denom), 0.0)
        beta = jnp.where(active, beta, 0.0)
        p = jnp.where(active, u + beta * p, p)
        s = jnp.where(active, w + beta * s, s)
        x = x + alpha * p
        r = r - alpha * s
        u_new = precond_solve(r)
        w_new = mvm(u_new)
        gamma_new, delta_new, rr_new = fused(r, u_new, w_new)
        u = jnp.where(active, u_new, u)
        w = jnp.where(active, w_new, w)
        gamma_prev_n = jnp.where(active, gamma, gamma_prev)
        alpha_prev_n = jnp.where(active, alpha, alpha_prev)
        gamma = jnp.where(active, gamma_new, gamma)
        delta = jnp.where(active, delta_new, delta)
        rr = jnp.where(active, rr_new, rr)
        ys = (alpha.astype(dtype), beta.astype(dtype), active)
        if track_residuals:
            ys = ys + (rel.astype(dtype),)
        return ((x, r, u, w, p, s, gamma, delta, rr, gamma_prev_n,
                 alpha_prev_n), ys)

    carry = (x, r, u, w, p, s, gamma, delta, rr, gamma_prev, alpha_prev)
    (x, r, *_), ys = jax.lax.scan(body, carry, jnp.arange(max_iters))
    rel = jnp.sqrt(jnp.sum(r * r, 0) / b_norm2)
    return (x, ys[0], ys[1], ys[2], rz0, rel, jnp.sum(ys[2], axis=0),
            ys[3] if track_residuals else None)


_FIXED_TRIP = {"standard": _fixed_trip_pcg_standard,
               "pipelined": _fixed_trip_pcg_pipelined}
_OUTPUTS = ("solution", "alphas", "betas", "active", "rz0", "rel_residual",
            "iterations", "residuals")


def _exit_problem(rng, warm):
    """A 3-column system whose columns converge at different iterations;
    with a warm start, column 0 starts at its solution."""
    X, params, Khat, mvm = _setup(rng, n=72, noise=0.4)
    B = jnp.asarray(rng.normal(size=(72, 3)))
    pre = make_preconditioner("matern32", X, params, 20)
    x0 = None
    if warm:
        guess = rng.normal(size=(72, 3))
        guess[:, 0] = np.asarray(jnp.linalg.solve(Khat, B[:, 0]))
        x0 = jnp.asarray(guess)
    return mvm, B, pre, x0


def _trip_count(method, max_iters, warm):
    """Traversals of the whole trip count: every iteration, the warm
    start's residual and the pipelined loop's pre-loop MVM."""
    return max_iters + warm + (method == "pipelined")


def _expected_traversals(method, iterations, max_iters, warm):
    """The standard loop learns that no column is active from the
    iteration's own MVM, so it runs one body more than the most iterations
    any column applied; the pipelined loop learns it before the MVM."""
    most = int(np.max(np.asarray(iterations)))
    bodies = min(most + 1, max_iters) if method == "standard" else most
    return bodies + warm + (method == "pipelined")


@pytest.mark.parametrize("track", [False, True])
@pytest.mark.parametrize("warm", [False, True])
@pytest.mark.parametrize("method", ["standard", "pipelined"])
def test_early_exit_bitwise_matches_fixed_trip_loop(rng, method, warm, track):
    """With max_iters far above what the columns need, the loop leaves
    early and still gives every output of the whole trip count bitwise."""
    mvm, B, pre, x0 = _exit_problem(rng, warm)
    max_iters, min_iters, tol = 60, 3, 1e-2
    res = pcg(mvm, B, pre.solve, max_iters=max_iters, min_iters=min_iters,
              tol=tol, method=method, x0=x0, track_residuals=track)
    want = _FIXED_TRIP[method](mvm, B, pre.solve, max_iters, min_iters, tol,
                               x0, track)
    got = (res.solution, res.alphas, res.betas, res.active, res.rz0,
           res.rel_residual, res.iterations, res.residuals)
    for g, w, name in zip(got, want, _OUTPUTS):
        if w is None:
            assert g is None, name
            continue
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    if x0 is None and not track:
        golden = _GOLDEN[method](mvm, B, pre.solve, max_iters, min_iters, tol)
        for g, w, name in zip(got, golden, _OUTPUTS):
            assert np.array_equal(np.asarray(g), np.asarray(w)), name
    iters = np.asarray(res.iterations)
    assert iters.min() < iters.max() < max_iters  # columns froze apart
    assert int(res.traversals) == _expected_traversals(
        method, iters, max_iters, warm)
    assert int(res.traversals) < _trip_count(method, max_iters, warm)


@pytest.mark.parametrize("track", [False, True])
@pytest.mark.parametrize("warm", [False, True])
@pytest.mark.parametrize("method", ["standard", "pipelined"])
def test_early_exit_traversals_are_the_matvecs_run(rng, method, warm, track):
    """`traversals` is what the matvec itself counts on the host."""
    mvm0, B, pre, x0 = _exit_problem(rng, warm)
    calls = []

    def mvm(v):
        jax.debug.callback(lambda: calls.append(1))
        return mvm0(v)

    max_iters = 60
    res = jax.jit(lambda B, x0: pcg(
        mvm, B, pre.solve, max_iters=max_iters, min_iters=3, tol=1e-2,
        method=method, x0=x0, track_residuals=track))(B, x0)
    jax.block_until_ready(res.solution)
    expect = _expected_traversals(method, res.iterations, max_iters, warm)
    assert int(res.traversals) == len(calls) == expect
    assert expect < _trip_count(method, max_iters, warm)


@pytest.mark.parametrize("track", [False, True])
@pytest.mark.parametrize("warm", [False, True])
@pytest.mark.parametrize("method", ["standard", "pipelined"])
def test_unconverged_solve_runs_every_iteration(rng, method, warm, track):
    """A tolerance no column reaches keeps every column active: the loop
    runs all max_iters iterations, as the fixed trip count did."""
    mvm, B, pre, x0 = _exit_problem(rng, warm)
    if x0 is not None:
        x0 = x0.at[:, 0].add(1.0)  # no column starts at its solution
    max_iters = 7
    res = pcg(mvm, B, pre.solve, max_iters=max_iters, min_iters=3,
              tol=1e-14, method=method, x0=x0, track_residuals=track)
    assert np.all(np.asarray(res.iterations) == max_iters)
    assert np.all(np.asarray(res.active))
    assert int(res.traversals) == _trip_count(method, max_iters, warm)
    want = _FIXED_TRIP[method](mvm, B, pre.solve, max_iters, 3, 1e-14, x0,
                               track)
    got = (res.solution, res.alphas, res.betas, res.active, res.rz0,
           res.rel_residual, res.iterations, res.residuals)
    for g, w, name in zip(got, want, _OUTPUTS):
        if w is not None:
            assert np.array_equal(np.asarray(g), np.asarray(w)), name
