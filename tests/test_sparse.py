"""repro.sparse contract: tapers, the planner, and the blocksparse backend.

Property tests (hypothesis, shim fallback) for the Wendland taper leaves —
positive semi-definiteness at d <= 3 and EXACT compact support (bitwise
zero beyond the radius, which is what makes tile pruning exact) — plus the
plan's structural invariants, mask correctness of the blocksparse MVM /
MLL value / Eq. 2 gradients against the dense backend at fill < 1 (the
acceptance bar: <= 2e-5 fp32), the all-active golden pin for non-compact
specs, drift-triggered replanning, the predict-time cross-covariance
pruning, the artifact round trip, and the sharded 1-D composition.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep (conftest dir is on sys.path)
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import (
    MLLConfig,
    OperatorConfig,
    TAPER_KINDS,
    dense_khat,
    exact_mll,
    init_kernel_params,
    kernel_matrix,
    make_operator,
    parse_kernel,
    spec_expr,
    spec_from_json,
    spec_to_json,
)
from repro.sparse import (
    build_plan,
    morton_order,
    needs_replan,
    plan_is_safe,
    spec_support_radius,
)

SPEC = parse_kernel("matern32 * wendland2")


def _problem(n=384, d=2, seed=0, radius=0.15, spec=SPEC, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(size=(n, d)), dtype)
    w = rng.normal(size=d)
    y = jnp.asarray(np.sin(3 * np.asarray(X, np.float64) @ w)
                    + 0.1 * rng.normal(size=n), dtype)
    V = jnp.asarray(rng.normal(size=(n, 3)), dtype)
    params = init_kernel_params(spec, noise=0.3, radius=radius, dtype=dtype)
    return X, y, V, params


# ---------------------------------------------------------------------------
# taper leaves
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=8)
@given(kind=st.sampled_from(TAPER_KINDS), d=st.integers(1, 3),
       radius=st.floats(0.05, 2.0), seed=st.integers(0, 10_000))
def test_taper_compact_support_exact(kind, d, radius, seed):
    """k(x, z) is EXACTLY 0.0 (not merely tiny) at ||x - z|| >= R, and 1 on
    the diagonal — the bitwise-skip guarantee block pruning rests on."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(-1, 1, size=(48, d)), jnp.float32)
    params = init_kernel_params(parse_kernel(kind), radius=radius)
    K = np.asarray(kernel_matrix(parse_kernel(kind), X, X, params))
    D = np.sqrt(np.maximum(
        np.sum((np.asarray(X)[:, None] - np.asarray(X)[None]) ** 2, -1), 0))
    outside = D >= radius * 1.0001  # float32 radius rounding headroom
    assert np.all(K[outside] == 0.0), K[outside][np.nonzero(K[outside])][:5]
    # diag via the norm-expansion d2 carries fp32 cancellation noise whose
    # effect on phi scales like (|x|^2 eps) / R^2 — keep it loose
    np.testing.assert_allclose(np.diagonal(K), 1.0, atol=5e-4)
    inside = D <= radius * 0.999
    assert np.all(K[inside] > 0.0)


@settings(deadline=None, max_examples=6)
@given(expr=st.sampled_from(
    ("wendland2", "wendland4", "matern32 * wendland2", "rbf * wendland4",
     "0.5*rbf + matern52 * wendland2")),
    d=st.integers(1, 3), seed=st.integers(0, 10_000))
def test_taper_specs_psd(expr, d, seed):
    """Wendland tapers (PSD for d <= 3) stay PSD under the algebra's
    products and sums (Schur product theorem)."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(size=(40, d)), jnp.float64)
    spec = parse_kernel(expr)
    params = init_kernel_params(spec, radius=0.4, dtype=jnp.float64)
    K = np.asarray(kernel_matrix(spec, X, X, params))
    np.testing.assert_allclose(K, K.T, atol=1e-12)
    eigs = np.linalg.eigvalsh(K)
    assert eigs.min() > -1e-8, eigs.min()


def test_taper_parser_json_roundtrip():
    spec = parse_kernel("matern32 * wendland2 + 0.3*wendland4")
    assert parse_kernel(spec_expr(spec)) == spec
    assert spec_from_json(spec_to_json(spec)) == spec


def test_support_radius_semantics():
    """Product support = min over taper factors; Sum support = max over
    terms; any taper-free term makes the spec unbounded."""
    mk = lambda e, r: (parse_kernel(e), init_kernel_params(
        parse_kernel(e), radius=r))
    s, p = mk("matern32 * wendland2", 0.25)
    assert float(spec_support_radius(s, p)) == pytest.approx(0.25, rel=1e-5)
    s, p = mk("wendland2 * wendland4", 0.25)
    assert float(spec_support_radius(s, p)) == pytest.approx(0.25, rel=1e-5)
    s, p = mk("matern32 + rbf * wendland2", 0.25)
    assert not np.isfinite(float(spec_support_radius(s, p)))
    s, p = mk("matern32", 0.25)
    assert not np.isfinite(float(spec_support_radius(s, p)))


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


def test_plan_structure_and_pinning():
    X, _, _, params = _problem(n=384, radius=0.12)
    plan = build_plan(SPEC, X, params, tile=32)
    T = plan.num_tiles
    assert T == 12 and plan.n_pad == 384
    # sparsity actually happened, diagonal always active, mask symmetric
    assert 0.0 < plan.fill < 1.0
    pairs = set(zip(plan.pair_rows.tolist(), plan.pair_cols.tolist()))
    assert all((t, t) in pairs for t in range(T))
    assert all((j, i) in pairs for i, j in pairs)
    # pair list sorted by row; pair_first marks each row's first pair
    assert np.all(np.diff(plan.pair_rows) >= 0)
    firsts = np.nonzero(plan.pair_first)[0]
    assert len(firsts) == T
    # row grouping is consistent with the pair list
    assert plan.row_valid.sum() == plan.num_pairs
    # determinism: same inputs -> same digest (jit-cache identity)
    plan2 = build_plan(SPEC, X, params, tile=32)
    assert plan == plan2 and hash(plan) == hash(plan2)
    # morton order is a permutation and deterministic
    perm = morton_order(np.asarray(X))
    assert np.array_equal(np.sort(perm), np.arange(384))
    assert np.array_equal(perm, morton_order(np.asarray(X)))


def test_non_compact_plans_all_active():
    spec = parse_kernel("matern32")
    X, _, _, params = _problem(spec=spec)
    plan = build_plan(spec, X, params, tile=32)
    assert plan.fill == 1.0 and not plan.compact
    replan, _ = needs_replan(plan, jax.tree.map(lambda a: a + 3.0, params))
    assert not replan  # all-active masks cover any radius


def test_build_plan_rejects_tracers():
    X, _, _, params = _problem(n=64)
    with pytest.raises(ValueError, match="concrete"):
        jax.jit(lambda x: build_plan(SPEC, x, params, tile=32))(X)


def test_drift_triggers_replan():
    X, _, _, params = _problem(n=256, radius=0.2)
    plan = build_plan(SPEC, X, params, tile=32, margin=0.1)
    ok, drift = needs_replan(plan, params, kernel=SPEC)
    assert not ok and drift == 0.0
    # grow the support radius past the margin: correctness demands a replan
    drifted = jax.tree.map(lambda a: a + 0.5, params)
    ok, drift = needs_replan(plan, drifted, kernel=SPEC)
    assert ok and drift > 0.1
    assert not plan_is_safe(plan, SPEC, drifted)
    # within-margin wiggle: the widened mask still covers it
    small = jax.tree.map(lambda a: a + 1e-4, params)
    ok, _ = needs_replan(plan, small, kernel=SPEC)
    assert not ok and plan_is_safe(plan, SPEC, small)


# ---------------------------------------------------------------------------
# blocksparse MVM / MLL / gradients vs dense (the acceptance bar)
# ---------------------------------------------------------------------------


def _mk_op(X, params, plan, **over):
    cfg = OperatorConfig(kernel=SPEC, backend="blocksparse", plan=plan,
                         **over)
    return make_operator(cfg, X, params)


def test_blocksparse_matvec_matches_dense_at_partial_fill():
    X, _, V, params = _problem(n=512, radius=0.12)
    plan = build_plan(SPEC, X, params, tile=32)
    assert plan.fill < 1.0, plan
    ref = np.asarray(dense_khat(SPEC, X, params) @ V)
    out = np.asarray(_mk_op(X, params, plan).matvec(V))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(scale, 1.0))
    # 1-column / 1-D RHS squeeze contract
    out1 = np.asarray(_mk_op(X, params, plan).matvec(V[:, 0]))
    np.testing.assert_allclose(out1, ref[:, 0],
                               atol=2e-5 * max(scale, 1.0))


def test_blocksparse_pallas_grid_matches_masked_path():
    """The gathered-grid Pallas kernel (interpret) and the masked-
    partitioned path agree to the fused kernel's fp32 contract."""
    X, _, V, params = _problem(n=256, radius=0.15)
    plan = build_plan(SPEC, X, params, tile=32)
    masked = np.asarray(_mk_op(X, params, plan).matvec(V))
    pallas = np.asarray(_mk_op(X, params, plan, interpret=True).matvec(V))
    np.testing.assert_allclose(pallas, masked, atol=2e-4, rtol=2e-4)


def test_blocksparse_bf16_compute_path():
    X, _, V, params = _problem(n=256, radius=0.2)
    plan = build_plan(SPEC, X, params, tile=32)
    ref = np.asarray(_mk_op(X, params, plan).matvec(V))
    out = np.asarray(
        _mk_op(X, params, plan, compute_dtype="bfloat16").matvec(V))
    assert out.dtype == np.float32
    # bf16 operands, fp32 accumulation: error scales with the output
    # magnitude (pure-rtol asserts blow up on near-zero entries)
    np.testing.assert_allclose(out, ref, atol=5e-2 * np.abs(ref).max())


def test_blocksparse_mll_value_and_grads_match_dense():
    """MLL value and the Eq. 2 gradients (hyperparameters AND X) through
    the blocksparse forward + its fill-proportional backward stay within
    2e-5 (fp32, relative) of the dense backend under shared probes."""
    X, y, _, params = _problem(n=320, radius=0.15)
    plan = build_plan(SPEC, X, params, tile=32)
    assert plan.fill < 1.0
    key = jax.random.PRNGKey(0)
    vals, grads = {}, {}
    for backend in ("dense", "blocksparse"):
        cfg = MLLConfig(kernel=SPEC, precond_rank=30, num_probes=16,
                        max_cg_iters=200, cg_tol=1e-6, row_block=32,
                        backend=backend,
                        plan=plan if backend == "blocksparse" else None)
        def value(p, x, cfg=cfg):
            return exact_mll(cfg, x, y, p, key)[0]
        vals[backend] = float(value(params, X))
        grads[backend] = jax.grad(value, argnums=(0, 1))(params, X)
    assert abs(vals["blocksparse"] - vals["dense"]) <= \
        2e-5 * max(1.0, abs(vals["dense"]))
    (gp_d, gx_d), (gp_b, gx_b) = grads["dense"], grads["blocksparse"]
    for ld, lb in zip(jax.tree.leaves(gp_d), jax.tree.leaves(gp_b)):
        tol = 2e-5 * max(1.0, float(jnp.max(jnp.abs(ld))))
        np.testing.assert_allclose(np.asarray(lb), np.asarray(ld), atol=tol)
    tol = 2e-5 * max(1.0, float(jnp.max(jnp.abs(gx_d))))
    np.testing.assert_allclose(np.asarray(gx_b), np.asarray(gx_d), atol=tol)


def test_non_compact_spec_pinned_to_partitioned_backend():
    """blocksparse on a non-compact spec (all-active plan) stays pinned to
    the partitioned backend's results."""
    spec = parse_kernel("0.5*rbf + matern32")
    X, _, V, params = _problem(n=256, spec=spec)
    plan = build_plan(spec, X, params, tile=32)
    assert plan.fill == 1.0
    ref = make_operator(OperatorConfig(kernel=spec, backend="partitioned",
                                       row_block=32), X, params).matvec(V)
    out = make_operator(OperatorConfig(kernel=spec, backend="blocksparse",
                                       plan=plan), X, params).matvec(V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_trainer_replans_on_drift():
    """fit_exact_gp with a tiny drift threshold replans (cold restarts)
    every step; a huge threshold keeps the warm-start engine warm."""
    from repro.core import ExactGP, ExactGPConfig
    from repro.train.gp_trainer import GPTrainConfig, fit_exact_gp

    X, y, _, _ = _problem(n=128, radius=0.3)
    gp = ExactGP(ExactGPConfig(kernel=SPEC, precond_rank=20, row_block=32,
                               train_max_cg_iters=30, backend="blocksparse"))
    res_tight = fit_exact_gp(
        gp, X, y, method="adam",
        cfg=GPTrainConfig(plain_adam_steps=3, drift_threshold=1e-6))
    assert [t["mode"] for t in res_tight.telemetry] == ["cold"] * 3
    res_loose = fit_exact_gp(
        gp, X, y, method="adam",
        cfg=GPTrainConfig(plain_adam_steps=3, drift_threshold=100.0,
                          refresh_every=100))
    assert [t["mode"] for t in res_loose.telemetry] == \
        ["cold", "warm", "warm"]
    assert all(np.isfinite(res_loose.loss_trace))


# ---------------------------------------------------------------------------
# predict-time pruning + serving round trip
# ---------------------------------------------------------------------------


def test_cross_matvec_prunes_and_matches_dense():
    X, _, V, params = _problem(n=256, radius=0.2)
    plan = build_plan(SPEC, X, params, tile=32)
    op = _mk_op(X, params, plan)
    rng = np.random.default_rng(3)
    Z = jnp.asarray(rng.uniform(size=(40, 2)) * 0.3, jnp.float32)
    ref = np.asarray(kernel_matrix(SPEC, Z, X, params) @ V)
    np.testing.assert_allclose(np.asarray(op.cross_matvec(Z, V)), ref,
                               atol=2e-5)
    # queries beyond the support of every tile: exactly zero
    far = np.asarray(op.cross_matvec(Z + 100.0, V))
    assert np.all(far == 0.0)


def test_artifact_roundtrip_and_engine_parity(tmp_path):
    from repro.serve.artifact import fit_posterior, load_artifact, \
        save_artifact
    from repro.serve.engine import PredictionEngine

    X, y, _, params = _problem(n=256, radius=0.25)
    op = _mk_op(X, params, None, row_block=64)
    art = fit_posterior(op, y, jax.random.PRNGKey(0), precond_rank=30,
                        lanczos_rank=64, max_cg_iters=200)
    save_artifact(str(tmp_path), art)
    art2 = load_artifact(str(tmp_path))
    # the plan is rebuilt from (kernel, X, params) and digest-verified
    assert art2.config.plan == op.config.plan
    assert art2.meta["sparse_plan"]["digest"] == op.config.plan.digest
    eng = PredictionEngine(art2, chunk_size=64)
    assert eng.backend == "blocksparse" and eng.sort_queries
    rng = np.random.default_rng(1)
    Xq = jnp.asarray(rng.uniform(size=(100, 2)), jnp.float32)
    mean, var = eng.predict(Xq)
    eng_ref = PredictionEngine(art, backend="partitioned", chunk_size=64,
                               sort_queries=False)
    mean_r, var_r = eng_ref.predict(Xq)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_r),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r),
                               atol=5e-5)


# ---------------------------------------------------------------------------
# sharded composition (in-process 1-device mesh; the 8-device subprocess
# engines are the slow suite's job)
# ---------------------------------------------------------------------------


def test_sharded_blocksparse_matches_dense():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.distributed import make_geometry, replicate, \
        shard_vector
    from repro.sparse import dist_blocksparse_kmvm

    X, _, V, params = _problem(n=256, radius=0.2)
    Xs = X[jnp.asarray(morton_order(np.asarray(X)))]
    plan = build_plan(SPEC, Xs, params, tile=32, assume_sorted=True)
    mesh = jax.make_mesh((1,), ("data",))
    geom = make_geometry(mesh, 256, 2, mode="1d", row_block=32)
    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_blocksparse_kmvm(geom, SPEC, Xr, Vl, params,
                                             plan),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = f(replicate(mesh, Xs), shard_vector(mesh, geom, V))
    ref = dense_khat(SPEC, Xs, params) @ V
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5 * max(scale, 1.0))


def test_sharded_blocksparse_contract_validation():
    from repro.core.distributed import make_geometry
    from repro.sparse import validate_dist_plan

    X, _, _, params = _problem(n=256, radius=0.2)
    mesh = jax.make_mesh((1,), ("data",))
    geom = make_geometry(mesh, 256, 2, mode="1d", row_block=32)
    # unsorted plan (real Morton permutation) is rejected
    plan_unsorted = build_plan(SPEC, X, params, tile=32)
    if not np.array_equal(plan_unsorted.perm, np.arange(256)):
        with pytest.raises(ValueError, match="PRE-SORTED"):
            validate_dist_plan(geom, plan_unsorted)
    # the plan must tile the PADDED layout exactly — a plan built on a
    # different row count (the old silent-truncation hazard) is rejected
    # with the pad-the-data recipe
    Xs = X[jnp.asarray(morton_order(np.asarray(X)))]
    plan_big = build_plan(SPEC, Xs[:250], params, tile=32,
                          assume_sorted=True)
    with pytest.raises(ValueError, match="pad_to_geometry"):
        validate_dist_plan(geom, plan_big)
    # per-device chunks must hold whole plan tiles (the 2-D chunk-sliced
    # mask gathers tile-granular): n_local=32 cannot hold tile=64
    plan_ok = build_plan(SPEC, Xs, params, tile=64, assume_sorted=True)
    geom_8dev = geom._replace(d_row=8, row_sizes=(8,))
    assert geom_8dev.n_local == 32
    with pytest.raises(ValueError, match="tile_multiple"):
        validate_dist_plan(geom_8dev, plan_ok)
