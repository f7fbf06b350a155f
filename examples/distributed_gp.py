"""End-to-end driver: DISTRIBUTED exact-GP training on a device mesh.

This is the million-point recipe at demo scale: the same
`repro.core.distributed` engine the multi-pod dry-run lowers at n = 2^20 on
512 chips, here executed for real on 8 fake CPU devices at n = 8192 —
row-sharded kernel partitions, distributed pivoted-Cholesky preconditioner,
PCG with convergence masking and an early exit, custom-VJP hyperparameter
gradients, tight-tolerance distributed mean-cache solve, then sub-second
single-device predictions from the cache (paper Table 2 pattern) — and
finally the mesh-solved posterior saved as a `repro.serve` artifact and
served through the chunked PredictionEngine.

    PYTHONPATH=src python examples/distributed_gp.py [--mode 2d]
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import init_params, kernel_matrix, rmse
from repro.core.distributed import (
    DistMLLConfig, make_geometry, make_mean_cache_solve,
    make_mll_value_and_grad, replicate, shard_vector,
)
from repro.data import make_regression_dataset
from repro.optim import adam_init, adam_update


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="2d", choices=("1d", "2d"),
                    help="1d = paper-faithful row partitioning; "
                         "2d = beyond-paper row x column partitioning")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"mode={args.mode}")

    s = make_regression_dataset("protein", max_points=18432)
    n = (s.X_train.shape[0] // 8) * 8
    X = jnp.asarray(s.X_train[:n], jnp.float32)
    y = jnp.asarray(s.y_train[:n], jnp.float32)
    Xt = jnp.asarray(s.X_test[:1000], jnp.float32)
    yt = jnp.asarray(s.y_test[:1000], jnp.float32)
    print(f"n={n} d={X.shape[1]}")

    geom = make_geometry(mesh, n, X.shape[1], mode=args.mode, row_block=512)
    cfg = DistMLLConfig(kernel="matern32", precond_rank=50, num_probes=8,
                        max_cg_iters=25, cg_tol=1.0)   # paper: eps=1 training
    vg = make_mll_value_and_grad(mesh, geom, cfg)

    params = init_params(noise=0.3, dtype=jnp.float32)
    Xr, ys = replicate(mesh, X), shard_vector(mesh, geom, y)
    state = adam_init(params)
    for step in range(args.steps):
        t0 = time.time()
        loss, aux, grads = vg(Xr, ys, replicate(mesh, params),
                              jax.random.PRNGKey(step))
        params, state = adam_update(params, grads, state, 0.1)
        print(f"step {step}: nll/n={float(loss):.4f} "
              f"cg_iters={int(aux[2][0])} ({time.time() - t0:.1f}s)")

    # one-time tight-tolerance precomputation (distributed), then O(n)
    # single-device predictions from the cache
    solve = make_mean_cache_solve(mesh, geom, cfg, tol=0.01, max_iters=200)
    t0 = time.time()
    a_cache, rel = solve(Xr, ys, replicate(mesh, params))
    print(f"mean-cache solve: rel_residual={float(rel[0]):.2e} "
          f"({time.time() - t0:.1f}s)")

    t0 = time.time()
    Kstar = kernel_matrix("matern32", Xt, X, params)
    mean = Kstar @ a_cache + params.raw_mean
    jax.block_until_ready(mean)
    print(f"1000 predictions: rmse={float(rmse(mean, yt)):.4f} "
          f"({(time.time() - t0) * 1e3:.0f} ms)")

    # the mesh-solved mean cache becomes a durable, servable artifact: only
    # the Lanczos variance pass runs here (the tight solve is NOT redone),
    # then the engine restores it onto a single-device partitioned backend
    from repro.core import OperatorConfig, make_operator
    from repro.serve import (PredictionEngine, load_artifact,
                             posterior_from_mean_cache, save_artifact)

    op = make_operator(OperatorConfig(kernel="matern32",
                                      backend="partitioned", row_block=512),
                       X, params)
    art = posterior_from_mean_cache(op, a_cache, jax.random.PRNGKey(1), y=y,
                                    lanczos_rank=64, solve_rel_residual=rel[0])
    save_artifact("artifacts/distributed_gp", art)
    engine = PredictionEngine(load_artifact("artifacts/distributed_gp"),
                              chunk_size=512)
    t0 = time.time()
    mean_e, _ = engine.predict(Xt)
    print(f"engine (restored artifact): rmse={float(rmse(mean_e, yt)):.4f} "
          f"({(time.time() - t0) * 1e3:.0f} ms incl. variance)")


if __name__ == "__main__":
    main()
