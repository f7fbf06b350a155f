"""Cross-backend conformance matrix for the KernelOperator engine.

`tests/test_operators.py` spot-checks the operator contract; this module is
the full grid that makes solver-state reuse (and any future backend) safe
to ship: dense / partitioned / pallas(interpret) / sharded operators must
agree on matvec, diag, the MLL VALUE and — previously uncovered — the MLL
GRADIENTS, over kernel x dtype x shape grids.

The single-device backends share probes and preconditioner bitwise (those
are backend-independent code paths), so their MLL values and gradients may
differ only by matmul summation order — tight tolerances. The sharded
backend draws its probe chunks per-device (different probe SET), so its
trace-term-contaminated gradients are compared against the dense-Cholesky
oracle statistically, the way `test_distributed.py` does — but in-process
on a 1-device mesh so the whole matrix stays tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import (
    MLLConfig,
    OperatorConfig,
    dense_khat,
    dense_mll,
    exact_mll,
    init_params_for,
    make_operator,
)
from repro.core.distributed import (
    DistMLLConfig,
    dist_kmvm,
    make_geometry,
    make_mll_value_and_grad,
    replicate,
    shard_vector,
)

SINGLE_BACKENDS = ("dense", "partitioned", "pallas", "blocksparse")
# the last axis entry is a composable KernelSpec expression (KernelParams
# pytree; the Pallas backend runs it as ONE fused multi-component pass).
# The blocksparse backend runs every kernel here through its ALL-ACTIVE
# plan (none of these specs is compactly supported) on the gathered-grid
# Pallas kernel (interpret=True) — the golden pin that non-compact specs
# match the established backends; its compact-support behavior lives in
# tests/test_sparse.py.
KERNELS = ("rbf", "matern32", "matern52", "0.5*rbf + matern32")
DTYPES = ("float32", "float64")
SHAPES = ((64, 2), (96, 5))

# value/grad agreement scales with the COMPUTE precision: dense/partitioned
# differ from the oracle only by blocked-summation order in the operand
# dtype, while the Pallas kernel's contract is fp32 math at every operand
# dtype (`kernels.ops` casts f64 operands to fp32; returns V.dtype) — so
# pallas rows of the matrix are held to fp32-grade tolerances even on f64;
# blocksparse with interpret=True runs the same fp32 kernel-body contract.
VAL_TOL = {"float32": 3e-5, "float64": 1e-10}
MAT_TOL = {"float32": 2e-4, "float64": 1e-9}


def _compute_dtype(backend, dtype):
    return "float32" if backend in ("pallas", "blocksparse") else dtype


def _plan_for(backend, kernel, X, params, tile=32):
    if backend != "blocksparse":
        return None
    from repro.sparse import build_plan
    return build_plan(kernel, X, params, tile=tile)


def _problem(kernel, dtype, n, d, t=3, seed=0):
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    X = jnp.asarray(rng.normal(size=(n, d)), dt)
    V = jnp.asarray(rng.normal(size=(n, t)), dt)
    w = rng.normal(size=d)
    y = jnp.asarray(np.sin(np.asarray(X, np.float64) @ w)
                    + 0.1 * rng.normal(size=n), dt)
    # one dispatch rule with the model/launcher: GPParams for legacy kinds,
    # per-node KernelParams for the composite spec-expression axis
    params = init_params_for(kernel, noise=0.3, dtype=dt)
    return X, V, y, params


def _op(backend, kernel, X, params):
    return make_operator(
        OperatorConfig(kernel=kernel, backend=backend, row_block=32,
                       interpret=True,
                       plan=_plan_for(backend, kernel, X, params)), X, params)


def _mesh_geom(n, d):
    mesh = jax.make_mesh((1,), ("data",))
    return mesh, make_geometry(mesh, n, d, mode="1d", row_block=32)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"n{s[0]}d{s[1]}")
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_matvec_and_diag_conformance(kernel, dtype, shape):
    """All four backends reproduce the dense K_hat @ V and diag(K_hat)."""
    n, d = shape
    X, V, _, params = _problem(kernel, dtype, n, d)
    Khat = dense_khat(kernel, X, params)
    ref_mv = np.asarray(Khat @ V)
    ref_diag = np.asarray(jnp.diagonal(Khat))
    for backend in SINGLE_BACKENDS:
        tol = MAT_TOL[_compute_dtype(backend, dtype)]
        op = _op(backend, kernel, X, params)
        np.testing.assert_allclose(np.asarray(op.matvec(V)), ref_mv,
                                   rtol=tol, atol=tol, err_msg=backend)
        np.testing.assert_allclose(np.asarray(op.diag()), ref_diag,
                                   rtol=tol, atol=tol, err_msg=backend)
        assert op.matvec(V).dtype == V.dtype, backend
    tol = MAT_TOL[dtype]

    mesh, geom = _mesh_geom(n, d)
    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_kmvm(geom, kernel, Xr, Vl, params),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = f(replicate(mesh, X), shard_vector(mesh, geom, V))
    np.testing.assert_allclose(np.asarray(out), ref_mv, rtol=tol, atol=tol,
                               err_msg="sharded")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_mll_value_and_grad_conformance(kernel, dtype):
    """Single-device backends: identical probes + identical preconditioner
    => MLL values AND hyperparameter/X gradients agree to summation-order
    tolerance, and both track the dense-Cholesky oracle."""
    n, d = 96, 4
    X, _, y, params = _problem(kernel, dtype, n, d)
    key = jax.random.PRNGKey(0)

    vals, grads = {}, {}
    for backend in SINGLE_BACKENDS:
        # CG converges to the backend's COMPUTE precision floor (pallas is
        # fp32 math even on f64 operands), so tolerance follows it
        cdt = _compute_dtype(backend, dtype)
        cfg = MLLConfig(kernel=kernel, precond_rank=30, num_probes=16,
                        max_cg_iters=200,
                        cg_tol=1e-10 if cdt == "float64" else 1e-6,
                        row_block=32, backend=backend,
                        plan=_plan_for(backend, kernel, X, params))
        def value(p, x):
            v, _ = exact_mll(cfg, x, y, p, key)
            return v
        vals[backend] = float(value(params, X))
        grads[backend] = jax.grad(value, argnums=(0, 1))(params, X)

    ref_gp, ref_gx = grads["dense"]
    for backend in ("partitioned", "pallas"):
        cdt = _compute_dtype(backend, dtype)
        vtol = VAL_TOL[cdt] * max(1.0, abs(vals["dense"]))
        assert abs(vals[backend] - vals["dense"]) < vtol, (backend, vals)
        g_rtol = 5e-3 if cdt == "float32" else 1e-6
        g_atol = 5e-4 if cdt == "float32" else 1e-8
        gp, gx = grads[backend]
        for leaf_ref, leaf in zip(jax.tree.leaves(ref_gp),
                                  jax.tree.leaves(gp)):
            np.testing.assert_allclose(
                np.asarray(leaf), np.asarray(leaf_ref),
                rtol=g_rtol, atol=g_atol,
                err_msg=f"{backend} param grad")
        np.testing.assert_allclose(
            np.asarray(gx), np.asarray(ref_gx), rtol=g_rtol, atol=g_atol,
            err_msg=f"{backend} X grad")

    # and the operator MLL tracks the closed-form oracle (value; the grad
    # trace term is stochastic, so the oracle check lives on raw_mean which
    # the probes never touch)
    oracle = float(dense_mll(kernel, X, y, params))
    assert abs(vals["dense"] - oracle) < 5e-2 * abs(oracle) + 0.5
    g_oracle = jax.grad(lambda p: dense_mll(kernel, X, y, p))(params)
    assert abs(float(ref_gp.raw_mean) - float(g_oracle.raw_mean)) < \
        (1e-6 if dtype == "float64" else 1e-2)


@pytest.mark.parametrize("kernel", ("rbf", "matern32"))
def test_sharded_mll_value_and_grad_conformance(kernel):
    """The sharded backend (in-process, 1-device mesh) agrees with the
    dense-Cholesky oracle on the per-datum loss value and its gradients:
    exactly for the probe-free raw_mean, statistically for the
    trace-estimated leaves (same envelope as the 8-device subprocess
    test)."""
    n, d = 128, 4
    X, _, y, params = _problem(kernel, "float64", n, d)
    mesh, geom = _mesh_geom(n, d)
    cfg = DistMLLConfig(kernel=kernel, precond_rank=40, num_probes=64,
                        max_cg_iters=200, cg_tol=1e-8)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    loss, aux, grads = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                          replicate(mesh, params), jax.random.PRNGKey(0))

    oracle_loss, g_oracle = jax.value_and_grad(
        lambda p: -dense_mll(kernel, X, y, p) / n)(params)
    assert abs(float(loss) - float(oracle_loss)) < \
        2e-2 * abs(float(oracle_loss)) + 1e-3
    assert abs(float(grads.raw_mean) - float(g_oracle.raw_mean)) < 1e-6
    for fname in ("raw_lengthscale", "raw_outputscale", "raw_noise"):
        a, b = float(getattr(grads, fname)), float(getattr(g_oracle, fname))
        assert abs(a - b) < 0.15 * abs(b) + 0.02, (fname, a, b)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"n{s[0]}d{s[1]}")
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_fused_matvec_dots_conformance(kernel, dtype, shape):
    """The fused-CG step surface: every backend's `fused_matvec_dots(V, R)`
    returns (K_hat @ V, [<K_hat v, v>, <r, v>, <r, r>, <v, v>]) matching the
    dense reference — whether it runs the Pallas megakernel (pallas backend,
    single fused pass) or the base-class matvec+reduction fallback."""
    n, d = shape
    t = 3
    X, V, _, params = _problem(kernel, dtype, n, d, t=t)
    rng = np.random.default_rng(7)
    R = jnp.asarray(rng.normal(size=(n, t)), jnp.dtype(dtype))
    Khat = dense_khat(kernel, X, params)
    KV = Khat @ V
    ref_dots = np.asarray(jnp.stack([
        jnp.sum(KV * V, 0), jnp.sum(R * V, 0),
        jnp.sum(R * R, 0), jnp.sum(V * V, 0)]), np.float64)
    for backend in SINGLE_BACKENDS:
        tol = MAT_TOL[_compute_dtype(backend, dtype)]
        op = _op(backend, kernel, X, params)
        out, dots = op.fused_matvec_dots(V, R)
        assert out.dtype == V.dtype, backend
        np.testing.assert_allclose(np.asarray(out), np.asarray(KV),
                                   rtol=tol, atol=tol, err_msg=backend)
        # dot magnitudes scale with n: compare relatively
        np.testing.assert_allclose(
            np.asarray(dots, np.float64), ref_dots,
            rtol=10 * tol, atol=10 * tol * float(np.abs(ref_dots).max()),
            err_msg=f"{backend} dots")


@pytest.mark.parametrize("kernel", KERNELS)
def test_mll_fused_step_value_and_grad_conformance(kernel):
    """The matmat axis end-to-end: the pallas MLL with the fused megakernel
    step engaged (fused_cg=True — y and all probes in one (n, t+1) matmat
    per iteration, reductions fused into the launch) agrees with the same
    backend's classic step (fused_cg=False) on the VALUE and on the Eq. 2
    gradients (params and X) that flow through the merged quad-form
    backward."""
    n, d = 96, 4
    X, _, y, params = _problem(kernel, "float32", n, d)
    key = jax.random.PRNGKey(0)

    out = {}
    for fused in (False, True):
        cfg = MLLConfig(kernel=kernel, precond_rank=30, num_probes=8,
                        max_cg_iters=150, cg_tol=1e-6, row_block=32,
                        backend="pallas", fused_cg=fused)

        def value(p, x):
            v, _ = exact_mll(cfg, x, y, p, key)
            return v

        v, (gp, gx) = jax.value_and_grad(
            value, argnums=(0, 1))(params, X)
        out[fused] = (float(v), gp, gx)

    v0, gp0, gx0 = out[False]
    v1, gp1, gx1 = out[True]
    assert abs(v1 - v0) < 3e-5 * max(1.0, abs(v0)), (v0, v1)
    for a, b in zip(jax.tree.leaves(gp0), jax.tree.leaves(gp1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("overlap", (False, True))
@pytest.mark.parametrize("n", (128, 120))
def test_blocksparse_2d_mesh_conformance(n, overlap):
    """The blocksparse distributed MVM on a 2-D geometry (in-process (1, 1)
    data x model mesh — the col-axis code path with trivial extent, so the
    chunk-sliced mask + chunked contraction + psum_scatter wiring runs under
    tier-1) matches the dense K_hat @ V on every TRUE row, divisible
    (n=128) and padded (n=120, tile_multiple forces n_padded=128) alike."""
    from repro.core.distributed import pad_to_geometry
    from repro.sparse import (
        build_plan, dist_blocksparse_kmvm, morton_order, validate_dist_plan,
    )

    kernel, d, tile = "matern32 * wendland2", 2, 32
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.uniform(size=(n, d)), jnp.float64)
    V = jnp.asarray(rng.normal(size=(n, 3)), jnp.float64)
    params = init_params_for(kernel, noise=0.3, dtype=jnp.float64)
    Xs = X[jnp.asarray(morton_order(np.asarray(X)))]

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    geom = make_geometry(mesh, n, d, mode="2d", row_block=tile,
                         overlap=overlap, tile_multiple=tile)
    assert geom.has_pad == (n % tile != 0)
    Xp, Vp = pad_to_geometry(geom, Xs), pad_to_geometry(geom, V)
    plan = build_plan(kernel, Xp, params, tile=tile, assume_sorted=True)
    validate_dist_plan(geom, plan)

    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_blocksparse_kmvm(geom, kernel, Xr, Vl, params,
                                             plan),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = np.asarray(f(replicate(mesh, Xp), shard_vector(mesh, geom, Vp)))
    ref = np.asarray(dense_khat(kernel, Xs, params) @ V)
    np.testing.assert_allclose(out[:n], ref, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("dtype", DTYPES)
def test_mll_value_agreement_includes_sharded(dtype):
    """Value-level four-way agreement on one grid point: the sharded MLL
    (different probe SET, same estimator) lands within estimator noise of
    the single-device backends' shared value."""
    kernel, n, d = "matern32", 128, 3
    X, _, y, params = _problem(kernel, dtype, n, d)
    key = jax.random.PRNGKey(0)
    tight = 1e-10 if dtype == "float64" else 1e-6
    cfg = MLLConfig(kernel=kernel, precond_rank=40, num_probes=64,
                    max_cg_iters=200, cg_tol=tight, row_block=32,
                    backend="dense")
    v_dense = float(exact_mll(cfg, X, y, params, key)[0])

    mesh, geom = _mesh_geom(n, d)
    dcfg = DistMLLConfig(kernel=kernel, precond_rank=40, num_probes=64,
                         max_cg_iters=200, cg_tol=tight)
    vg = make_mll_value_and_grad(mesh, geom, dcfg)
    loss, _, _ = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                    replicate(mesh, params), key)
    v_sharded = -float(loss) * n
    assert abs(v_sharded - v_dense) < 2e-2 * abs(v_dense) + 0.5, \
        (v_sharded, v_dense)
