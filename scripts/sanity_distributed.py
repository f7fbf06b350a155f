"""Distributed engine sanity: 8 fake devices, 1-D and 2-D modes vs dense oracle.

``--quick`` runs the tier-1 CI smoke: the 2-D blocksparse mini-fit plus the
non-divisible-n padded case (small probe/iteration budgets, assertion-gated).
The default full run adds the dense MLL/grad/pivchol oracle comparisons.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core import dense_khat, dense_mll, init_params, parse_kernel
from repro.core.distributed import (
    DistMLLConfig, dist_kmvm, make_dist_preconditioner, make_geometry,
    make_mean_cache_solve, make_mll_value_and_grad, pad_to_geometry,
    replicate, shard_vector,
)
from repro.core.kernels_math import init_kernel_params
from repro.sparse import (
    build_plan, dist_blocksparse_kmvm, morton_order, validate_dist_plan,
)

QUICK = "--quick" in sys.argv

mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)


def full_oracle_checks():
    n, d = 256, 6
    X = jnp.asarray(rng.normal(size=(n, d)))
    y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                    + 0.1 * rng.normal(size=n))
    params = init_params(noise=0.2, dtype=jnp.float64)
    Khat = dense_khat("matern32", X, params)

    for mode in ("1d", "2d"):
        geom = make_geometry(mesh, n, d, mode=mode, row_block=32)
        V = jnp.asarray(rng.normal(size=(n, 3)))

        def local_mvm(Xr, V_loc):
            return dist_kmvm(geom, "matern32", Xr, V_loc, params)

        f = jax.jit(shard_map(local_mvm, mesh=mesh,
                              in_specs=(P(), geom.vector_pspec()),
                              out_specs=geom.vector_pspec(), check_vma=False))
        out = f(replicate(mesh, X), shard_vector(mesh, geom, V))
        print(f"[{mode}] dist kmvm err:", float(jnp.max(jnp.abs(out - Khat @ V))))

        # distributed pivoted cholesky == single-device pivoted cholesky
        from repro.core import pivoted_cholesky
        def local_pc(Xr):
            pre = make_dist_preconditioner(geom, "matern32", Xr, params, 40)
            return pre.L_local, pre.chol_inner
        g = jax.jit(shard_map(local_pc, mesh=mesh, in_specs=(P(),),
                              out_specs=(geom.vector_pspec(), P()),
                              check_vma=False))
        L_dist, chol = g(replicate(mesh, X))
        L_ref = pivoted_cholesky("matern32", X, params, 40)
        # pivoted cholesky columns are sign/order-deterministic -> exact match
        print(f"[{mode}] dist pivchol err:",
              float(jnp.max(jnp.abs(jnp.abs(L_dist) - jnp.abs(L_ref)))))

        cfg = DistMLLConfig(kernel="matern32", precond_rank=40, num_probes=64,
                            max_cg_iters=150, cg_tol=1e-6)
        vg = make_mll_value_and_grad(mesh, geom, cfg)
        key = jax.random.PRNGKey(0)
        loss, aux, grads = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                              replicate(mesh, params), key)
        val_dense = dense_mll("matern32", X, y, params)
        print(f"[{mode}] dist mll: {-float(loss)*n:.4f} dense: {float(val_dense):.4f}")
        g_dense = jax.grad(lambda p: -dense_mll("matern32", X, y, p) / n)(params)
        for fname in grads._fields:
            a, b = np.asarray(getattr(grads, fname)), np.asarray(getattr(g_dense, fname))
            print(f"  grad {fname}: dist={a:.5f} dense={b:.5f}")

        solve = make_mean_cache_solve(mesh, geom, cfg, tol=1e-10, max_iters=400)
        a_cache, rel = solve(replicate(mesh, X), shard_vector(mesh, geom, y), params)
        direct = jnp.linalg.solve(Khat, y)
        print(f"[{mode}] mean-cache solve err:",
              float(jnp.max(jnp.abs(a_cache - direct))))


def blocksparse_2d_minifit():
    """2-D mesh blocksparse: MVM oracle check + a short MLL fit loop."""
    spec = parse_kernel("matern32 * wendland2")
    n, d, tile = 384, 2, 16
    X = jnp.asarray(rng.uniform(size=(n, d)))
    # fp64 params: with fp32 params XLA fuses the f32->f64 promotion
    # differently under jit vs eager (~1e-7/entry), which would swamp the
    # exactness assertion below
    params = init_kernel_params(spec, noise=0.3, radius=0.35,
                                dtype=jnp.float64)
    Xs = X[jnp.asarray(morton_order(np.asarray(X)))]
    y = jnp.asarray(np.sin(3.0 * np.asarray(Xs).sum(axis=1))
                    + 0.1 * rng.normal(size=n))

    geom = make_geometry(mesh, n, d, mode="2d", row_block=tile,
                         overlap=True, tile_multiple=tile)
    Xp, yp = pad_to_geometry(geom, Xs), pad_to_geometry(geom, y)
    plan = build_plan(spec, Xp, params, tile=tile, assume_sorted=True)
    validate_dist_plan(geom, plan)

    V = jnp.asarray(rng.normal(size=(n, 3)))
    Vp = pad_to_geometry(geom, V)
    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_blocksparse_kmvm(geom, spec, Xr, Vl, params, plan),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = np.asarray(f(replicate(mesh, Xp), shard_vector(mesh, geom, Vp)))
    ref = np.asarray(dense_khat(spec, Xs, params)) @ np.asarray(V)
    err = float(np.abs(out[:n] - ref).max())
    print(f"[2d blocksparse] kmvm err: {err:.2e} (fill {plan.fill:.3f})")
    assert err < 1e-8, f"2-D blocksparse MVM disagrees with dense: {err}"

    # mini-fit: a few MLL+grad steps must run and improve the loss
    cfg = DistMLLConfig(kernel=spec, precond_rank=20, num_probes=4,
                        max_cg_iters=25, cg_tol=1e-6,
                        backend="blocksparse", plan=plan)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    key = jax.random.PRNGKey(1)
    Xr, yl = replicate(mesh, Xp), shard_vector(mesh, geom, yp)
    p = params
    losses = []
    for i in range(3):
        loss, aux, grads = vg(Xr, yl, replicate(mesh, p), key)
        losses.append(float(loss))
        p = jax.tree.map(lambda a, g: a - 0.1 * g, p, grads)
    print(f"[2d blocksparse] mini-fit losses: "
          + " -> ".join(f"{l:.4f}" for l in losses))
    assert np.isfinite(losses).all(), "mini-fit produced non-finite loss"
    assert losses[-1] < losses[0], "mini-fit loss did not improve"


def nondivisible_padded_case():
    """n=250 on a (4,2) mesh: padded geometry, no rows dropped."""
    n, d = 250, 4
    X = jnp.asarray(rng.normal(size=(n, d)))
    y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                    + 0.1 * rng.normal(size=n))
    params = init_params(noise=0.25, dtype=jnp.float64)
    Khat = dense_khat("matern32", X, params)

    for mode in ("1d", "2d"):
        for overlap in ((False, True) if mode == "2d" else (False,)):
            geom = make_geometry(mesh, n, d, mode=mode, row_block=32,
                                 overlap=overlap)
            assert geom.has_pad and geom.n_padded > n
            Xp = pad_to_geometry(geom, X)
            V = jnp.asarray(rng.normal(size=(n, 2)))
            Vp = pad_to_geometry(geom, V)

            def local_mvm(Xr, V_loc):
                return dist_kmvm(geom, "matern32", Xr, V_loc, params)

            f = jax.jit(shard_map(local_mvm, mesh=mesh,
                                  in_specs=(P(), geom.vector_pspec()),
                                  out_specs=geom.vector_pspec(),
                                  check_vma=False))
            out = np.asarray(f(replicate(mesh, Xp),
                               shard_vector(mesh, geom, Vp)))
            err = float(np.abs(out[:n] - np.asarray(Khat @ V)).max())
            tag = f"[{mode}{'+overlap' if overlap else ''}]"
            print(f"{tag} padded n={n} kmvm err: {err:.2e} "
                  f"(padded to {geom.n_padded})")
            assert err < 1e-10, f"padded MVM wrong on true rows: {err}"

        geom = make_geometry(mesh, n, d, mode=mode, row_block=32)
        cfg = DistMLLConfig(kernel="matern32", precond_rank=20, num_probes=8,
                            max_cg_iters=60, cg_tol=1e-6)
        solve = make_mean_cache_solve(mesh, geom, cfg, tol=1e-10,
                                      max_iters=300)
        Xp = pad_to_geometry(geom, X)
        a_cache, rel = solve(replicate(mesh, Xp),
                             shard_vector(mesh, geom, y), params)
        assert a_cache.shape[0] == n, "mean cache must cover every true row"
        direct = jnp.linalg.solve(Khat, y)
        err = float(jnp.max(jnp.abs(a_cache - direct)))
        print(f"[{mode}] padded n={n} mean-cache solve err: {err:.2e}")
        assert err < 1e-6, f"padded mean-cache solve wrong: {err}"


if not QUICK:
    full_oracle_checks()
blocksparse_2d_minifit()
nondivisible_padded_case()
print("OK")
