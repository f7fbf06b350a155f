"""GP serving launcher: fit-or-load a posterior artifact, serve traffic.

    PYTHONPATH=src python -m repro.launch.serve_gp --backend partitioned \
        [--artifact artifacts/gp] [--n 2048] [--requests 200] \
        [--scheduler continuous] [--models 2] [--observe 64]

End-to-end path of `repro.serve`: fit the paper's exact GP (or load a saved
PosteriorArtifact), restore it onto the requested KernelOperator backend,
verify the chunked engine against the unchunked predcache reference, then
drive synthetic concurrent query traffic through the chosen scheduler —
`--scheduler closed` is the MicroBatcher (size/deadline barrier),
`--scheduler continuous` the pipelined multi-model ServeFleet — and report
p50/p99 request latency and QPS (per model, under the fleet). `--models N`
makes N posteriors resident; `--observe M` absorbs M streaming observations
through `fleet.observe()` afterwards and prints the incremental-update vs
cold-refit wall-clock. CPU runs use reduced sizes; the same flags serve a
TPU host (`--backend pallas --dtype bfloat16`).
"""

from __future__ import annotations

import argparse
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ExactGP, ExactGPConfig
from repro.core.predcache import predict_mean, predict_var_cached
from repro.data import make_regression_dataset
from repro.launch.runtime import setup_runtime
from repro.serve import (
    BatcherConfig, FleetConfig, MicroBatcher, PredictionEngine,
    SchedulerConfig, ServeFleet, fit_posterior, load_artifact, save_artifact,
)
from repro.train.gp_trainer import GPTrainConfig, fit_exact_gp


def _fit_or_load(args):
    if args.artifact:
        try:
            art = load_artifact(args.artifact)
            print(f"[serve-gp] loaded artifact: n={art.n} "
                  f"r={art.lanczos_rank} from {args.artifact}")
            return art
        except FileNotFoundError:
            print(f"[serve-gp] no artifact under {args.artifact!r}; fitting")

    s = make_regression_dataset(args.dataset, max_points=args.n * 9 // 4)
    n = min(args.n, s.X_train.shape[0])
    X = jnp.asarray(s.X_train[:n], jnp.float32)
    y = jnp.asarray(s.y_train[:n], jnp.float32)
    gp = ExactGP(ExactGPConfig(
        kernel="matern32", backend=args.backend, row_block=512,
        precond_rank=min(100, max(20, n // 20)),
        lanczos_rank=min(128, n // 2),
        compute_dtype=args.dtype if args.dtype != "float32" else None))
    cfg = GPTrainConfig(pretrain_subset=min(n, 512), pretrain_lbfgs_steps=3,
                        pretrain_adam_steps=3, finetune_adam_steps=2)
    t0 = time.time()
    res = fit_exact_gp(gp, X, y, cfg=cfg)
    print(f"[serve-gp] fit n={n} d={X.shape[1]} in {time.time() - t0:.1f}s "
          f"(final loss {res.loss_trace[-1]:.4f})")
    t0 = time.time()
    art = fit_posterior(gp.operator(X, res.params), y, jax.random.PRNGKey(0),
                        precond_rank=gp.config.precond_rank,
                        lanczos_rank=gp.config.lanczos_rank,
                        pred_tol=gp.config.pred_cg_tol,
                        max_cg_iters=gp.config.pred_max_cg_iters)
    print(f"[serve-gp] precompute {time.time() - t0:.1f}s "
          f"rel_residual={art.meta['solve_rel_residual']:.2e}")
    if args.artifact:
        print(f"[serve-gp] saved artifact: {save_artifact(args.artifact, art)}")
    return art


def verify_engine(engine: PredictionEngine,
                  Xq: jax.Array) -> tuple[float, float]:
    """(mean, var) max rel. error of the chunked engine vs the unchunked
    predcache reference on the SAME operator (the acceptance oracle)."""
    mean, var = engine.predict(Xq)
    cache = engine.artifact.cache()
    ref_m = predict_mean(engine.op, Xq, cache)
    ref_v = predict_var_cached(engine.op, Xq, cache,
                               include_noise=engine.include_noise)
    # scale-relative: max |delta| over the reference scale (element-wise
    # relative error is meaningless where the whitened mean crosses zero)
    return (float(jnp.max(jnp.abs(mean - ref_m)) / jnp.max(jnp.abs(ref_m))),
            float(jnp.max(jnp.abs(var - ref_v)) / jnp.max(jnp.abs(ref_v))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="partitioned",
                    choices=("dense", "partitioned", "pallas"))
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="engine cross-MVM compute dtype")
    ap.add_argument("--dataset", default="bike")
    ap.add_argument("--n", type=int, default=2048, help="train points to fit")
    ap.add_argument("--artifact", default="",
                    help="artifact dir: load if complete, else fit + save")
    ap.add_argument("--chunk", type=int, default=256,
                    help="engine test-set chunk (rows per launch)")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--points-per-request", type=int, default=8)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=128)
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="closed-scheduler accumulation deadline")
    ap.add_argument("--scheduler", default="closed",
                    choices=("closed", "continuous"))
    ap.add_argument("--models", type=int, default=1,
                    help="resident posteriors (continuous scheduler only; "
                         "model i is fit on a shrinking row subset)")
    ap.add_argument("--workers", type=int, default=2,
                    help="continuous-scheduler launcher threads")
    ap.add_argument("--observe", type=int, default=0,
                    help="streaming rows to absorb via fleet.observe() "
                         "after traffic (prints update vs cold-refit cost)")
    ap.add_argument("--slo-target-ms", type=float, default=None,
                    help="per-request latency SLO (continuous scheduler): "
                         "breaches count into serve.slo_breach.<model> and "
                         "the per-model burn rate is printed")
    args = ap.parse_args()
    setup_runtime()

    art = _fit_or_load(args)
    engine = PredictionEngine(
        art, backend=args.backend, chunk_size=args.chunk,
        compute_dtype=args.dtype if args.dtype != "float32" else None)
    engine.warmup()

    rng = np.random.default_rng(0)
    d = art.X.shape[1]
    # query pool: train-point perturbations (in-distribution traffic)
    pool = np.asarray(art.X)[rng.integers(0, art.n, size=2048)]
    pool = pool + 0.1 * rng.standard_normal(pool.shape).astype(pool.dtype)

    rel = max(verify_engine(engine, jnp.asarray(pool[:512])))
    exact_path = engine.config.compute_dtype is None
    print(f"[serve-gp] engine vs unchunked reference: max rel err {rel:.2e} "
          f"({'exact fp32 path, bound 1e-5' if exact_path else 'bf16 path'})")
    if exact_path and not rel <= 1e-5:
        raise SystemExit(f"verification FAILED: rel err {rel:.2e} > 1e-5")

    ppr = args.points_per_request
    queries = [pool[rng.integers(0, pool.shape[0], size=ppr)]
               for _ in range(args.requests)]

    if args.scheduler == "closed":
        batcher = MicroBatcher(engine, BatcherConfig(
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            bucket_sizes=(16, 64, args.max_batch)))

        def client(q):
            t0 = time.perf_counter()
            batcher.predict(q)
            return time.perf_counter() - t0

        with ThreadPoolExecutor(args.clients) as ex:
            t0 = time.perf_counter()
            lats = np.asarray(list(ex.map(client, queries)))
            wall = time.perf_counter() - t0
        batcher.close()
        counters = batcher
        s = obs.latency_summary(lats, wall)
    else:
        fleet, names = _make_fleet(args, art)
        engine = None  # fleet owns the engines now

        def client(iq):
            i, q = iq
            t0 = time.perf_counter()
            fleet.predict(names[i % len(names)], q)
            return time.perf_counter() - t0

        with ThreadPoolExecutor(args.clients) as ex:
            t0 = time.perf_counter()
            lats = np.asarray(list(ex.map(client, enumerate(queries))))
            wall = time.perf_counter() - t0
        counters = fleet.batcher
        s = obs.latency_summary(lats, wall)

    print(f"[serve-gp] {args.requests} requests x {ppr} pts "
          f"({args.clients} clients, backend={args.backend}, "
          f"chunk={args.chunk}, scheduler={args.scheduler}, "
          f"models={args.models}): p50={s['p50_ms']:.1f} ms "
          f"p99={s['p99_ms']:.1f} ms"
          f"{' (interpolated)' if s['p99_interpolated'] else ''} "
          f"max={s['max_ms']:.1f} ms qps={s['qps']:.1f}")
    print(f"[serve-gp] {counters.batches_run} device launches, "
          f"{counters.requests_served / max(counters.batches_run, 1):.1f} "
          f"req/launch, {counters.rows_padded} padded rows")
    bh = obs.histogram("serve.batch_rows").summary()
    if bh["count"]:
        print(f"[serve-gp] batch rows: p50={bh['p50']:.0f} "
              f"p99={bh['p99']:.0f} max={bh['max']:.0f} "
              f"(n={bh['count']})")

    if args.scheduler == "continuous":
        for name, slo in sorted(fleet.stats().items()):
            if slo["count"]:
                burn = (f" slo_breaches={slo['breaches']} "
                        f"burn={slo['burn_rate']:.1%}"
                        if "burn_rate" in slo else "")
                print(f"[serve-gp]   {name}: {slo['count']} reqs "
                      f"p50={slo['p50_ms']:.1f} ms p99={slo['p99_ms']:.1f} "
                      f"ms qps={slo['qps']:.1f}{burn}")
        if args.observe:
            _observe_demo(args, art, fleet, names[0], pool, rng)
        fleet.close()


def _make_fleet(args, art) -> tuple[ServeFleet, list]:
    """ServeFleet with `--models` resident posteriors: model 0 is the
    fitted/loaded artifact; model i > 0 refits the posterior caches on a
    row subset (distinct content digest, same hyperparameters)."""
    from repro.core.operators import make_operator

    arts = {"m0": art}
    base_cfg = art.config._replace(geom=None, plan=None,
                                   backend=args.backend)
    for i in range(1, args.models):
        ni = max(256, art.n - 256 * i)
        op_i = make_operator(base_cfg, art.X[:ni], art.params)
        arts[f"m{i}"] = fit_posterior(
            op_i, art.y[:ni], jax.random.PRNGKey(100 + i),
            precond_rank=min(100, max(10, ni // 20)),
            lanczos_rank=min(art.lanczos_rank, ni // 2))
    fleet = ServeFleet(FleetConfig(
        capacity=max(args.models, 1), chunk_size=args.chunk,
        backend=args.backend,
        scheduler=SchedulerConfig(max_batch=args.max_batch,
                                  bucket_sizes=(16, 64, args.max_batch),
                                  num_workers=args.workers),
        slo_target_ms=args.slo_target_ms))
    for name, a in arts.items():
        fleet.register(name, a)
    return fleet, list(arts)


def _observe_demo(args, art, fleet: ServeFleet, name: str, pool, rng) -> None:
    """Absorb `--observe` rows into one model and price it against a cold
    refit of the posterior caches on the same extended data."""
    from repro.core.operators import make_operator

    if not art.meta.get("has_y", False):
        print("[serve-gp] --observe skipped: artifact has no training "
              "targets (meta['has_y'] is False)")
        return
    m = args.observe
    Xn = jnp.asarray(pool[:m], art.X.dtype)
    mean_n, _ = fleet.predict(name, Xn)
    yn = (jnp.asarray(mean_n).reshape(-1)
          + 0.05 * jnp.asarray(rng.standard_normal(m), art.y.dtype))
    t0 = time.perf_counter()
    digest = fleet.observe(name, Xn, yn)
    upd_s = time.perf_counter() - t0
    base_cfg = art.config._replace(geom=None, plan=None,
                                   backend=args.backend)
    X_ext = jnp.concatenate([art.X, Xn], axis=0)
    y_ext = jnp.concatenate([art.y, yn], axis=0)
    op_ext = make_operator(base_cfg, X_ext, art.params)
    t0 = time.perf_counter()
    fit_posterior(op_ext, y_ext, jax.random.PRNGKey(9),
                  precond_rank=int(art.meta.get("precond_rank", 100)),
                  lanczos_rank=art.lanczos_rank)
    refit_s = time.perf_counter() - t0
    print(f"[serve-gp] observe(m={m}) on {name}: update {upd_s * 1e3:.0f} ms"
          f" vs cold refit {refit_s * 1e3:.0f} ms "
          f"({upd_s / refit_s:.1%}); new digest {digest[:12]}")


if __name__ == "__main__":
    main()
