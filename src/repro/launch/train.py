"""Cluster-style training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch <id> [--full] ...

On this CPU container it runs reduced configs end-to-end with the same
train_step, fault-tolerant loop and checkpoint layout a TPU deployment
uses; on real hardware the only changes are --full (exact assigned config),
the mesh shape, and jax.distributed.initialize() (multi-host bring-up, done
here when JAX_COORDINATOR_ADDRESS is set).

GP workloads: --arch gp-exact-1m trains the paper's exact GP with the
distributed engine (1d = paper-faithful, 2d = beyond-paper layout).
"""

from __future__ import annotations

import argparse
import os
from typing import NamedTuple

import jax

from repro.launch.runtime import setup_runtime


def _maybe_init_distributed():
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()  # multi-host: env-driven bring-up


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--data", type=int, default=None, help="mesh data size")
    ap.add_argument("--model", type=int, default=1, help="mesh model size")
    ap.add_argument("--ckpt", default="checkpoints")
    ap.add_argument("--gp-mode", default="2d", choices=("1d", "2d"))
    ap.add_argument("--gp-n", type=int, default=8192)
    ap.add_argument("--gp-kernel", default="matern32",
                    help="kernel: a stationary kind (matern32) or a "
                         "composable spec expression, e.g. "
                         "'0.5*rbf + matern32' or 'scale(rq)*linear' "
                         "(see repro.core.kernels_math.parse_kernel)")
    ap.add_argument("--gp-backend", default="partitioned",
                    choices=("partitioned", "pallas", "blocksparse"),
                    help="inner KernelOperator backend per device tile; "
                         "blocksparse = distance-pruned MVMs for "
                         "compactly-supported specs (Morton-sorts the "
                         "data; composes with --gp-mode 1d AND 2d; see "
                         "repro.sparse)")
    ap.add_argument("--gp-overlap", action="store_true",
                    help="ring-pipeline the per-iteration gather against "
                         "the local tile compute (collective-matmul "
                         "chunking; see repro.core.distributed)")
    ap.add_argument("--gp-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="operator compute dtype (bf16 = MXU fast path)")
    ap.add_argument("--gp-refresh-every", type=int, default=5,
                    help="warm-start engine: rebuild the preconditioner + "
                         "redraw SLQ probes every K optimizer steps "
                         "(0 = disable warm starts, every step cold)")
    ap.add_argument("--gp-drift-threshold", type=float, default=0.1,
                    help="relative hyperparameter drift that forces a "
                         "preconditioner refresh before the schedule does")
    ap.add_argument("--save-artifact", default="",
                    help="directory: persist a servable repro.serve "
                         "PosteriorArtifact after GP training")
    ap.add_argument("--obs-trace", default="",
                    help="path: write a repro.obs span-trace JSONL for this "
                         "run (render with `python -m repro.launch."
                         "obs_report <path>`); equivalent to setting "
                         "REPRO_OBS_TRACE")
    args = ap.parse_args()
    _maybe_init_distributed()
    setup_runtime()

    if args.obs_trace:
        from repro import obs

        obs.enable_tracing(args.obs_trace)

    if args.arch == "gp-exact-1m":
        return _train_gp(args)

    from repro.data.tokens import TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import init_train_state, make_train_step
    from repro.models import count_params, get_arch
    from repro.train.trainer import TrainLoopConfig, run_train_loop

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced(ce_chunk=args.seq, attn_chunk=args.seq)
    mesh = make_host_mesh(data=args.data, model=args.model)
    print(f"[train] arch={cfg.name} params={count_params(cfg):,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    step = jax.jit(make_train_step(cfg, mesh, lr=args.lr), donate_argnums=0)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    pipe = TokenPipeline(mesh, cfg.vocab, args.batch, args.seq)
    batches = ({"tokens": b.tokens, "targets": b.targets} for b in pipe)
    loop = TrainLoopConfig(total_steps=args.steps,
                           ckpt_dir=os.path.join(args.ckpt, cfg.name),
                           ckpt_every=100, log_every=10,
                           tokens_per_step=args.batch * args.seq)
    try:
        res = run_train_loop(step, state, batches, loop)
    finally:
        pipe.close()
    print(f"[train] done: {res.steps_run} steps, {res.skipped} skipped")


def prepare_gp_data(mesh, X_host, y_host, *, backend, gp_mode, kernel,
                    params, margin=0.1, overlap=False, row_block=1024,
                    tile=256):
    """(geom, X, y, plan) for the distributed engine — NO point dropped.

    Every row of (X_host, y_host) trains: non-divisible n pads the layout
    with masked rows (see `DistGeometry`) instead of truncating. The
    blocksparse path Morton-sorts the data, pads, and builds the plan on
    the padded array so every per-device chunk owns whole tiles; `tile`
    shrinks automatically when the dataset is smaller than one tile per
    device. Returned X/y carry geom.n_padded rows; rows [geom.n:] are
    zero pad, excluded from every solve.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import make_geometry, pad_to_geometry

    n, d = X_host.shape
    if backend == "blocksparse":
        from repro.sparse import build_plan, morton_order

        if n < mesh.devices.size * tile:
            tile = 8
        perm = morton_order(np.asarray(X_host))
        geom = make_geometry(mesh, n, d, mode=gp_mode, row_block=row_block,
                             overlap=overlap, tile_multiple=tile)
        X = pad_to_geometry(geom, jnp.asarray(
            np.asarray(X_host)[perm], jnp.float32))
        y = pad_to_geometry(geom, jnp.asarray(
            np.asarray(y_host)[perm], jnp.float32))
        plan = build_plan(kernel, X, params, tile=tile, margin=margin,
                          assume_sorted=True)
        return geom, X, y, plan
    geom = make_geometry(mesh, n, d, mode=gp_mode, row_block=row_block,
                         overlap=overlap)
    X = pad_to_geometry(geom, jnp.asarray(X_host, jnp.float32))
    y = pad_to_geometry(geom, jnp.asarray(y_host, jnp.float32))
    return geom, X, y, None


class GPTrainRun(NamedTuple):
    """What `train_gp` leaves behind: the trained hyperparameters, the
    padded device-resident data, and one record per optimizer step."""

    params: object
    X: jax.Array          # (geom.n_padded, d); rows [geom.n:] are pad
    y: jax.Array          # (geom.n_padded,)
    geom: object          # DistGeometry
    cfg: object           # DistMLLConfig
    plan: object          # SparsePlan or None
    history: list         # per step: loss, grads (host), mode, cg_iters,
                          # seconds (wall, ended by block_until_ready)


def train_gp(mesh, X_host, y_host, workload, *, steps: int,
             refresh_every: int = 5,
             drift_threshold: float = 0.1) -> GPTrainRun:
    """Adam on the exact-GP MLL with the distributed warm-start engine.

    workload: a `configs.gp_exact_1m.GPWorkloadConfig` — kernel, backend,
    compute dtype, mesh mode, overlap and the solver widths (precond rank,
    probes, CG iterations). Every row of (X_host, y_host) trains.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import KERNEL_KINDS, init_params_for, parse_kernel, spec_expr
    from repro.core.distributed import (
        DistMLLConfig, replicate, shard_vector,
    )
    from repro.optim import adam_init, adam_update
    from repro.train.solver_state import DistWarmStartEngine, WarmStartConfig

    gp_dtype = workload.compute_dtype
    # legacy stationary kinds train the flat GPParams (the paper's setup);
    # any other expression parses to a KernelSpec + per-node KernelParams
    # (one dispatch rule for model/launcher/tests: init_params_for)
    kernel = workload.kernel if workload.kernel in KERNEL_KINDS \
        else parse_kernel(workload.kernel)
    params = init_params_for(kernel, noise=0.3, dtype=jnp.float32)
    kernel_desc = kernel if isinstance(kernel, str) else spec_expr(kernel)

    geom, X, y, plan = prepare_gp_data(
        mesh, X_host, y_host, backend=workload.backend,
        gp_mode=workload.mode, kernel=kernel, params=params,
        margin=drift_threshold, overlap=workload.overlap,
        row_block=workload.row_block)
    n = geom.n
    assert n == X_host.shape[0], "no training point may be dropped"
    if plan is not None:
        print(f"[train-gp] sparsity plan: {plan}")
    if geom.has_pad:
        print(f"[train-gp] padded layout: {geom.pad_rows} masked rows "
              f"({n} -> {geom.n_padded})")
    cfg = DistMLLConfig(kernel=kernel, precond_rank=workload.precond_rank,
                        num_probes=workload.num_probes,
                        max_cg_iters=workload.train_cg_iters, cg_tol=1.0,
                        backend=workload.backend, compute_dtype=gp_dtype,
                        plan=plan)
    warm = WarmStartConfig(enabled=refresh_every > 0,
                           refresh_every=max(refresh_every, 1),
                           drift_threshold=drift_threshold)
    engine = DistWarmStartEngine(mesh, geom, cfg, warm)
    state = adam_init(params)
    history: list = []
    Xr, ys = replicate(mesh, X), shard_vector(mesh, geom, y)
    print(f"[train-gp] n={n} kernel={kernel_desc} mode={workload.mode} "
          f"backend={workload.backend} "
          f"dtype={gp_dtype or 'float32'} refresh_every={refresh_every} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    for step_i in range(steps):
        if plan is not None:
            from repro.sparse import build_plan, needs_replan

            replan, _drift = needs_replan(plan, params, drift_threshold,
                                          kernel=kernel)
            if replan:
                plan = build_plan(kernel, X, params, tile=plan.tile,
                                  margin=drift_threshold, assume_sorted=True)
                cfg = cfg._replace(plan=plan)
                engine = DistWarmStartEngine(mesh, geom, cfg, warm)
                print(f"[train-gp] step {step_i}: replanned sparsity "
                      f"(drift={_drift:.3f}, fill={plan.fill:.3f})")
        loss, aux, grads = engine.step(Xr, ys, params,
                                       jax.random.PRNGKey(step_i))
        params, state = adam_update(params, grads, state, 0.1)
        t = engine.telemetry[-1]
        history.append({"step": step_i, "loss": float(loss),
                        "grads": jax.tree.map(np.asarray,
                                              jax.device_get(grads)),
                        "mode": t["mode"], "cg_iters": t["cg_iters"],
                        "refreshed": t["refreshed"],
                        "seconds": t["seconds"]})
        print(f"[train-gp] step {step_i}: nll/n={float(loss):.4f} "
              f"solve={t['mode']} cg_iters={t['cg_iters']} "
              f"drift={t['drift']:.3f} dt={t['seconds']:.2f}s")
    total = sum(h["cg_iters"] for h in history)
    refreshes = sum(h["refreshed"] for h in history)
    print(f"[train-gp] solver telemetry: total_cg_iters={total} "
          f"precond_refreshes={refreshes} steps={steps}")
    return GPTrainRun(params=params, X=X, y=y, geom=geom, cfg=cfg,
                      plan=plan, history=history)


def _train_gp(args):
    from repro.configs.gp_exact_1m import CONFIG
    from repro.data import make_regression_dataset
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=args.data, model=args.model)
    s = make_regression_dataset("houseelectric", max_points=args.gp_n * 3)
    workload = CONFIG._replace(
        kernel=args.gp_kernel, mode=args.gp_mode, backend=args.gp_backend,
        compute_dtype=None if args.gp_dtype == "float32" else args.gp_dtype,
        overlap=args.gp_overlap)
    run = train_gp(mesh, s.X_train, s.y_train, workload, steps=args.steps,
                   refresh_every=args.gp_refresh_every,
                   drift_threshold=args.gp_drift_threshold)

    if args.save_artifact:
        # mesh-trained hyperparameters -> a servable single-host artifact
        # (the engine re-binds any backend at restore time); the posterior
        # is fit on the TRUE rows only — pad rows are layout, not data
        from repro.core import OperatorConfig, make_operator
        from repro.serve.artifact import fit_posterior, save_artifact

        n, cfg, params = run.geom.n, run.cfg, run.params
        X_true, y_true = run.X[:n], run.y[:n]
        assert X_true.shape[0] == s.X_train.shape[0], \
            "artifact must cover every original training row"
        art_plan = None
        if run.plan is not None:
            from repro.sparse import build_plan

            art_plan = build_plan(cfg.kernel, X_true, params,
                                  tile=run.plan.tile,
                                  margin=args.gp_drift_threshold,
                                  assume_sorted=True)
        op = make_operator(
            OperatorConfig(kernel=cfg.kernel, backend=args.gp_backend,
                           compute_dtype=cfg.compute_dtype, plan=art_plan),
            X_true, params)
        art = fit_posterior(op, y_true, jax.random.PRNGKey(args.steps),
                            precond_rank=cfg.precond_rank)
        print(f"[train-gp] artifact: {save_artifact(args.save_artifact, art)} "
              f"(rel_residual={art.meta['solve_rel_residual']:.2e})")


if __name__ == "__main__":
    main()
