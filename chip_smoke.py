"""Bring-up check of the exact-GP train -> precompute -> serve path on a TPU.

    python chip_smoke.py                 # one chip: the whole main path
    python chip_smoke.py --n 1048576 --steps 1 --train-only
    python chip_smoke.py --chips 4       # four chips: the distributed step

One chip (the default) runs, through the entry points a user calls, on the
`houseelectric` analogue (d = 9) made from `--seed`:

  1. the fused K_hat @ V (pallas) and the partitioned one on all n columns,
     a few hundred rows checked against float64 numpy on the host;
  2. at n = 2048, the training step's MLL value and gradients against a
     float64 dense Cholesky (finite differences) on the host;
  3. at n = 2048, `serve.fit_posterior` (tight mean solve, full-rank
     Lanczos) and a pallas `PredictionEngine`: served mean and variance
     against the exact float64 posterior (dense Cholesky) on the host;
  4. `launch.train.train_gp` at the `gp-exact-1m` widths (matern32, rank
     100 preconditioner, 8 probes, 20 CG iterations, pallas, fp32) for
     `--steps` Adam steps on a 1x1 mesh; losses and gradients must be
     finite;
  5. `serve.fit_posterior` on the trained hyperparameters, saved and
     restored into a pallas `PredictionEngine`, checked against the
     unchunked prediction-cache reference, then a few dozen requests
     through the continuous batcher; test RMSE must beat the trivial
     predictor's by a wide margin.

`--chips 4` runs only the distributed MLL value-and-grad step at the same
data on a 2x2 mesh (overlap off and on) and a 4x1 1-D mesh, against the
same step on one chip in this process. Overlap on/off must be bitwise
equal; the meshes draw their SLQ probes per device, so they agree with
the one-chip step within the estimator's error only.

The script fails (exit 2) before anything else when JAX finds no TPU. Any
failed check exits 1. Only a run in which every check passed prints, as its
last line, {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# the small-n MLL check runs the training step with a tight solve, so what
# is left is the stochastic estimators' error at 8 probes: on the CPU at
# n = 2048 (30 draws over 3 data seeds) it stayed below 0.009 (value) and
# 0.028 (gradients, max abs error over the largest reference gradient)
MLL_VALUE_RTOL = 3e-2
MLL_GRAD_TOL = 8e-2
# fp32 K_hat @ V: the tolerance the CPU conformance suite holds fp32 to
MVM_RTOL = MVM_ATOL = 2e-4
SERVE_RTOL = 1e-5          # the engine-vs-reference bound of launch.serve_gp
# served mean/var at n = 2048 vs the exact float64 posterior, with a tight
# mean solve and a full-rank Lanczos cache so that precision is what is
# left: on the CPU (3 data seeds) the errors stayed below 1.2e-5 (mean)
# and 1.9e-6 (var); with the kernel matmuls on bf16 operands, as a float32
# matmul at DEFAULT precision runs on a TPU, they were 3.6e-3 and 3.4e-3
SMALL_LANCZOS_RANK = 2048
SMALL_PRED_TOL = 1e-5
SERVE_FP64_MEAN_TOL = SERVE_FP64_VAR_TOL = 1e-4
RMSE_VS_TRIVIAL = 0.5      # test RMSE must be below half the trivial one's
# a mesh draws each device's probe chunk from its own key, so against one
# chip the SLQ/trace estimators agree only statistically, with a spread
# that shrinks like 1/sqrt(n): the CPU on four host devices showed 5.6e-3
# (loss) and 8.5e-3 (gradients) at n = 4096, 1.7e-3 and 8e-3 at n = 16384.
# The y-solve (quad = y^T K^-1 y) uses no probe and must agree tightly.
MESH_LOSS_RTOL = 1e-2
MESH_GRAD_TOL = 5e-2
MESH_QUAD_RTOL = 1e-4
# 2x2 and 4x1 give chunk c the same rows and so the same probes: they
# differ by summation order only (the CPU showed 1e-7)
LAYOUT_LOSS_RTOL = 1e-4
LAYOUT_GRAD_TOL = 1e-3


# -- compile accounting -----------------------------------------------------


class CompileClock:
    """Seconds of XLA compilation (persistent-cache reads included) and
    persistent-cache hits, from jax.monitoring. Python tracing is left
    out: nested jits report it once per level."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


class Phase:
    """Wall and compile seconds of one phase; the body must end in
    block_until_ready so that the wall time covers the device work."""

    totals: dict = {}

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.c0 = self.clock.seconds
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        Phase.totals[self.name] = (wall, comp)
        status = "" if exc_type is None else " FAILED"
        print(f"[phase] {self.name}: wall={wall:.3f}s compile={comp:.3f}s"
              f"{status}")


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def record(self, name: str, err: float, limit: float, what: str) -> None:
        ok = bool(np.isfinite(err)) and err <= limit
        if not ok:
            self.failed.append(name)
        print(f"[check] {name}: {what} = {err:.3e} (limit {limit:.1e}) "
              f"{'PASS' if ok else 'FAIL'}")

    def require(self, name: str, ok: bool, detail: str) -> None:
        if not ok:
            self.failed.append(name)
        print(f"[check] {name}: {detail} {'PASS' if ok else 'FAIL'}")


# -- float64 host references ------------------------------------------------


def _softplus(x):
    return np.logaddexp(0.0, np.float64(x))


def _raw(params) -> dict:
    return {k: np.float64(np.asarray(v)) for k, v in params._asdict().items()}


def matern32_np(A, B, raw) -> np.ndarray:
    """outputscale * (1 + sqrt3 r) exp(-sqrt3 r), r = |a - b| / lengthscale."""
    ls, os_ = _softplus(raw["raw_lengthscale"]), _softplus(raw["raw_outputscale"])
    A, B = np.asarray(A, np.float64) / ls, np.asarray(B, np.float64) / ls
    d2 = (A * A).sum(1)[:, None] + (B * B).sum(1)[None, :] - 2.0 * A @ B.T
    a = np.sqrt(3.0 * np.maximum(d2, 0.0))
    return os_ * (1.0 + a) * np.exp(-a)


def khat_rows_np(X, rows, V, raw, noise_floor=1e-4, chunk=1 << 16):
    """K_hat[rows, :] @ V in float64, K never held whole."""
    X, V = np.asarray(X), np.asarray(V, np.float64)
    out = np.zeros((len(rows), V.shape[1]))
    for s in range(0, X.shape[0], chunk):
        out += matern32_np(X[rows], X[s:s + chunk], raw) @ V[s:s + chunk]
    sigma2 = _softplus(raw["raw_noise"]) + noise_floor
    return out + sigma2 * V[rows]


def posterior_np(raw, X, y, Xq, noise_floor=1e-4):
    """Exact predictive mean and variance (noise included), float64 dense
    Cholesky."""
    n = X.shape[0]
    sigma2 = _softplus(raw["raw_noise"]) + noise_floor
    K = matern32_np(X, X, raw)
    K[np.diag_indices(n)] += sigma2
    L = np.linalg.cholesky(K)
    Ks = matern32_np(Xq, X, raw)                        # (m, n)
    W = np.linalg.solve(L, Ks.T)                        # L^-1 K_x*
    alpha = np.linalg.solve(L.T, np.linalg.solve(
        L, np.asarray(y, np.float64) - raw["raw_mean"]))
    mean = raw["raw_mean"] + Ks @ alpha
    var = _softplus(raw["raw_outputscale"]) - (W * W).sum(0) + sigma2
    return mean, var


def mll_np(raw, X, y, noise_floor=1e-4) -> float:
    """Exact log marginal likelihood, float64 dense Cholesky."""
    n = X.shape[0]
    K = matern32_np(X, X, raw)
    K[np.diag_indices(n)] += _softplus(raw["raw_noise"]) + noise_floor
    L = np.linalg.cholesky(K)
    yc = np.asarray(y, np.float64) - raw["raw_mean"]
    alpha = np.linalg.solve(L.T, np.linalg.solve(L, yc))
    return -0.5 * (yc @ alpha + 2.0 * np.log(np.diag(L)).sum()
                   + n * np.log(2.0 * np.pi))


# -- phases -----------------------------------------------------------------


def make_data(n: int, seed: int):
    from repro.data import make_regression_dataset

    s = make_regression_dataset("houseelectric", seed=seed,
                                max_points=-(-n * 9 // 4))
    return (s.X_train[:n].astype(np.float32), s.y_train[:n].astype(np.float32),
            s.X_test.astype(np.float32), s.y_test.astype(np.float32))


def check_mvm(checks, clock, X, params, seed, workload, rows=256):
    """The fused (pallas) and partitioned fp32 K_hat @ V over all n columns
    against float64 numpy on `rows` of its rows."""
    import jax.numpy as jnp

    from repro.core import OperatorConfig, make_operator

    n = X.shape[0]
    rng = np.random.default_rng(seed)
    V = rng.standard_normal((n, 1 + workload.num_probes)).astype(np.float32)
    idx = np.sort(rng.choice(n, rows, replace=False))
    ref = khat_rows_np(X, idx, V, _raw(params))
    Xd, Vd = jnp.asarray(X), jnp.asarray(V)
    for backend in ("pallas", "partitioned"):
        cfg = OperatorConfig(kernel=workload.kernel, backend=backend)
        mv = jax.jit(lambda X, V, p: make_operator(cfg, X, p).matvec(V))
        with Phase(f"mvm_{backend}", clock):
            out = jax.block_until_ready(mv(Xd, Vd, params))
        got = np.asarray(out)[idx].astype(np.float64)
        viol = np.max(np.abs(got - ref) / (MVM_ATOL + MVM_RTOL * np.abs(ref)))
        print(f"[mvm] {backend}: n={n} rows={rows} t={V.shape[1]} "
              f"max|err|={np.max(np.abs(got - ref)):.3e} "
              f"max|ref|={np.max(np.abs(ref)):.3e}")
        checks.record(f"khat_v_{backend}_vs_fp64", float(viol), 1.0,
                      f"max |err| / ({MVM_ATOL:g} + {MVM_RTOL:g}|ref|)")


def check_small_mll(checks, clock, X, y, workload, n=2048):
    """The training step's value and gradients at small n against a
    float64 dense Cholesky; the solve is run tight so the estimator's
    error is all that remains."""
    import jax.numpy as jnp

    from repro.core import init_params_for
    from repro.core.distributed import DistMLLConfig, replicate, shard_vector
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import prepare_gp_data
    from repro.train.solver_state import DistWarmStartEngine, WarmStartConfig

    Xs, ys = X[:n], y[:n]
    params = init_params_for(workload.kernel, noise=0.3, dtype=jnp.float32)
    mesh = make_host_mesh(data=1, model=1)
    geom, Xp, yp, _ = prepare_gp_data(
        mesh, Xs, ys, backend=workload.backend, gp_mode=workload.mode,
        kernel=workload.kernel, params=params, row_block=workload.row_block)
    cfg = DistMLLConfig(kernel=workload.kernel,
                        precond_rank=workload.precond_rank,
                        num_probes=workload.num_probes, max_cg_iters=100,
                        cg_tol=1e-4, backend=workload.backend)
    engine = DistWarmStartEngine(mesh, geom, cfg, WarmStartConfig(False))
    with Phase("mll_small", clock):
        loss, aux, grads = engine.step(
            replicate(mesh, Xp), shard_vector(mesh, geom, yp), params,
            jax.random.PRNGKey(0))
        jax.block_until_ready(grads)
    raw = _raw(params)
    ref = -mll_np(raw, Xs, ys) / n
    h = 1e-4
    ref_g = {}
    for k in raw:
        up, dn = dict(raw), dict(raw)
        up[k] += h
        dn[k] -= h
        ref_g[k] = -(mll_np(up, Xs, ys) - mll_np(dn, Xs, ys)) / (2 * h * n)
    got_g = _raw(grads)
    g_err = max(abs(got_g[k] - ref_g[k]) for k in raw)
    g_scale = max(abs(v) for v in ref_g.values())
    print(f"[mll] n={n}: loss={float(loss):.6f} ref={ref:.6f} "
          f"cg_iters={int(np.max(np.asarray(aux.cg_iterations)))}")
    print(f"[mll] grads {({k: round(float(v), 6) for k, v in got_g.items()})}"
          f" ref {({k: round(float(v), 6) for k, v in ref_g.items()})}")
    checks.record("mll_value_vs_fp64_cholesky",
                  abs(float(loss) - ref) / abs(ref), MLL_VALUE_RTOL,
                  "|loss - ref| / |ref|")
    checks.record("mll_grads_vs_fp64_cholesky", g_err / g_scale,
                  MLL_GRAD_TOL, "max |g - g_ref| / max |g_ref|")


def check_small_serve(checks, clock, X, y, X_test, workload, seed, n=2048):
    """`fit_posterior` and a pallas `PredictionEngine` at small n against
    the exact float64 posterior: the mean solve, the Lanczos variance
    cache and the served cross-covariances, all on the chip."""
    import jax.numpy as jnp

    from repro.core import OperatorConfig, init_params_for, make_operator
    from repro.serve import PredictionEngine, fit_posterior

    params = init_params_for(workload.kernel, noise=0.3, dtype=jnp.float32)
    op = make_operator(OperatorConfig(kernel=workload.kernel,
                                      backend=workload.backend),
                       jnp.asarray(X[:n]), params)
    rng = np.random.default_rng(seed)
    Xq = X_test[rng.choice(len(X_test), 512, replace=False)]
    with Phase("serve_small", clock):
        art = fit_posterior(op, jnp.asarray(y[:n]), jax.random.PRNGKey(seed),
                            precond_rank=workload.precond_rank,
                            lanczos_rank=SMALL_LANCZOS_RANK,
                            pred_tol=SMALL_PRED_TOL, max_cg_iters=400)
        engine = PredictionEngine(art, backend="pallas", chunk_size=256)
        mean, var = jax.block_until_ready(engine.predict(jnp.asarray(Xq)))
    ref_m, ref_v = posterior_np(_raw(params), X[:n], y[:n], Xq)
    err_m = np.max(np.abs(np.asarray(mean, np.float64) - ref_m))
    err_v = np.max(np.abs(np.asarray(var, np.float64) - ref_v))
    print(f"[serve-small] n={n} lanczos_rank={art.meta['lanczos_rank']} "
          f"max|mean err|={err_m:.3e} max|var err|={err_v:.3e}")
    checks.record("served_mean_vs_fp64_posterior",
                  err_m / np.max(np.abs(ref_m)), SERVE_FP64_MEAN_TOL,
                  "max |mean - ref| / max |ref|")
    checks.record("served_var_vs_fp64_posterior",
                  err_v / np.max(np.abs(ref_v)), SERVE_FP64_VAR_TOL,
                  "max |var - ref| / max |ref|")


def train(checks, clock, X, y, workload, steps):
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_gp

    mesh = make_host_mesh(data=1, model=1)
    with Phase("train", clock):
        run = train_gp(mesh, X, y, workload, steps=steps)
        jax.block_until_ready(run.params)
    for h in run.history:
        print(f"[train] step {h['step']} ({h['mode']}): "
              f"wall={h['seconds']:.3f}s loss={h['loss']:.6f}")
    finite = all(np.isfinite(h["loss"]) and all(
        np.all(np.isfinite(g)) for g in jax.tree.leaves(h["grads"]))
        for h in run.history)
    checks.require("train_losses_and_grads_finite", finite,
                   f"{len(run.history)} steps:")
    return run


def serve(checks, clock, run, X_test, y_test, y_train, workload, seed):
    import jax.numpy as jnp

    from repro.core import OperatorConfig, make_operator
    from repro.launch.serve_gp import verify_engine
    from repro.serve import (ContinuousBatcher, PredictionEngine,
                             SchedulerConfig, fit_posterior, save_artifact)

    n = run.geom.n
    op = make_operator(OperatorConfig(kernel=run.cfg.kernel,
                                      backend=workload.backend),
                       run.X[:n], run.params)
    with Phase("precompute", clock):
        art = fit_posterior(op, run.y[:n], jax.random.PRNGKey(seed),
                            precond_rank=workload.precond_rank,
                            max_cg_iters=workload.pred_cg_iters)
        jax.block_until_ready((art.mean_cache, art.var_Q))
    print(f"[precompute] n={n} lanczos_rank={art.meta['lanczos_rank']} "
          f"mean-solve rel residual={art.meta['solve_rel_residual']:.3e}")

    root = os.path.join(HERE, "artifacts")
    os.makedirs(root, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=root) as adir:
        with Phase("save_restore", clock):
            save_artifact(adir, art)
            engine = PredictionEngine.from_dir(adir, backend="pallas",
                                               chunk_size=256)
            engine.warmup()
    rng = np.random.default_rng(seed)
    Xq = jnp.asarray(X_test[rng.choice(len(X_test), 512, replace=False)])
    with Phase("verify", clock):
        rel_mean, rel_var = verify_engine(engine, Xq)
    checks.record("served_mean_vs_unchunked_reference", rel_mean,
                  SERVE_RTOL, "max |mean - ref| / max |ref|")
    checks.record("served_var_vs_unchunked_reference", rel_var, SERVE_RTOL,
                  "max |var - ref| / max |ref|")

    requests, rows = 48, 16
    picks = [rng.choice(len(X_test), rows, replace=False)
             for _ in range(requests)]
    batcher = ContinuousBatcher(engine, SchedulerConfig(
        max_batch=128, bucket_sizes=(16, 64, 128), num_workers=2))

    def client(idx):
        t0 = time.perf_counter()
        mean, var = batcher.predict(X_test[idx], timeout=600)
        return idx, mean, var, time.perf_counter() - t0

    try:
        with Phase("serve", clock):
            with ThreadPoolExecutor(8) as ex:
                out = list(ex.map(client, picks))
    finally:
        batcher.close()
    lat = np.array([o[3] for o in out]) * 1e3
    idx = np.concatenate([o[0] for o in out])
    mean = np.concatenate([o[1] for o in out])
    var = np.concatenate([o[2] for o in out])
    print(f"[serve] {requests} requests x {rows} points in "
          f"{batcher.batches_run} launches; latency p50={np.median(lat):.1f} "
          f"ms max={lat.max():.1f} ms")
    rmse = float(np.sqrt(np.mean((mean - y_test[idx]) ** 2)))
    trivial = float(np.sqrt(np.mean((y_test[idx] - y_train.mean()) ** 2)))
    checks.require("served_values_finite",
                   bool(np.all(np.isfinite(mean)) and np.all(var > 0)),
                   f"{mean.size} means finite, variances > 0:")
    checks.record("test_rmse_vs_trivial", rmse / trivial, RMSE_VS_TRIVIAL,
                  f"rmse {rmse:.4f} / trivial {trivial:.4f}")


def mesh_phase(checks, clock, X, y, workload, seed):
    """The distributed MLL step on 2x2 (overlap off/on) and 4x1 (1-D)
    against the same step on one chip."""
    import jax.numpy as jnp

    from repro.core import init_params_for
    from repro.core.distributed import (DistMLLConfig,
                                        make_mll_value_and_grad, replicate,
                                        shard_vector)
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import prepare_gp_data

    params = init_params_for(workload.kernel, noise=0.3, dtype=jnp.float32)
    cfg = DistMLLConfig(kernel=workload.kernel,
                        precond_rank=workload.precond_rank,
                        num_probes=workload.num_probes,
                        max_cg_iters=workload.train_cg_iters, cg_tol=1.0,
                        backend=workload.backend)
    key = jax.random.PRNGKey(seed)
    layouts = (("1chip", (1, 1), "2d", False),
               ("2x2", (2, 2), "2d", False),
               ("2x2_overlap", (2, 2), "2d", True),
               ("4x1_1d", (4, 1), "1d", False))
    res = {}
    for name, shape, mode, overlap in layouts:
        mesh = make_host_mesh(*shape)
        geom, Xp, yp, _ = prepare_gp_data(
            mesh, X, y, backend=workload.backend, gp_mode=mode,
            kernel=workload.kernel, params=params, overlap=overlap,
            row_block=workload.row_block)
        step = make_mll_value_and_grad(mesh, geom, cfg)
        args = (replicate(mesh, Xp), shard_vector(mesh, geom, yp),
                replicate(mesh, params), key)
        with Phase(f"mll_step_{name}", clock):
            loss, aux, grads = jax.block_until_ready(step(*args))
        res[name] = (np.asarray(loss), [np.asarray(a) for a in aux],
                     [np.asarray(g) for g in jax.tree.leaves(grads)])
        print(f"[mesh] {name}: loss={float(loss):.6f} logdet={float(aux[0]):.3f}"
              f" quad={float(aux[1]):.3f} grads="
              f"{[round(float(g), 6) for g in res[name][2]]}")

    a, b = res["2x2"], res["2x2_overlap"]
    same = (np.array_equal(a[0], b[0])
            and all(np.array_equal(x, z) for x, z in zip(a[1], b[1]))
            and all(np.array_equal(x, z) for x, z in zip(a[2], b[2])))
    checks.require("overlap_on_off_bitwise", same,
                   "2x2 loss, aux and grads identical with overlap on/off:")
    def compare(name, ref_name, loss_tol, grad_tol):
        (loss, aux, grads), ref = res[name], res[ref_name]
        checks.record(f"{name}_quad_vs_{ref_name}",
                      float(abs(aux[1] - ref[1][1]) / abs(ref[1][1])),
                      MESH_QUAD_RTOL,
                      f"|quad - quad_{ref_name}| / |quad_{ref_name}|")
        checks.record(f"{name}_loss_vs_{ref_name}",
                      float(abs(loss - ref[0]) / abs(ref[0])), loss_tol,
                      f"|loss - loss_{ref_name}| / |loss_{ref_name}|")
        g_err = max(float(np.max(np.abs(g - r)))
                    for g, r in zip(grads, ref[2]))
        g_scale = max(float(np.max(np.abs(r))) for r in ref[2])
        checks.record(f"{name}_grads_vs_{ref_name}", g_err / g_scale,
                      grad_tol, f"max |g - g_{ref_name}| / max |g_{ref_name}|")

    compare("2x2", "1chip", MESH_LOSS_RTOL, MESH_GRAD_TOL)
    compare("4x1_1d", "1chip", MESH_LOSS_RTOL, MESH_GRAD_TOL)
    compare("4x1_1d", "2x2", LAYOUT_LOSS_RTOL, LAYOUT_GRAD_TOL)


# -- main -------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=262144,
                    help="training points (1048576 = the gp-exact-1m size)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train-only", action="store_true",
                    help="stop after training (for sizes whose posterior "
                         "precompute outlasts a run)")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX reports "
              f"{len(devices)} device(s) on platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    return run(args, devices)


def run(args, devices) -> int:
    # one line at a time, so the launcher's own prints interleave in order
    sys.stdout.reconfigure(line_buffering=True)
    sys.path.insert(0, os.path.join(HERE, "src"))
    import importlib.metadata

    import jaxlib

    from repro.configs.gp_exact_1m import CONFIG
    from repro.launch.runtime import setup_runtime

    cache_dir = setup_runtime()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    dev = devices[0]
    print(f"[env] jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}")
    print(f"[env] device_kind={dev.device_kind!r} platform={dev.platform} "
          f"count={len(devices)} using={args.chips}")
    print(f"[env] compile cache: {cache_dir}")

    clock = CompileClock()
    checks = Checks()
    workload = CONFIG._replace(backend="pallas", compute_dtype=None)
    with Phase("data", clock):
        X, y, X_test, y_test = make_data(args.n, args.seed)
    print(f"[data] houseelectric analogue seed={args.seed}: n={X.shape[0]} "
          f"d={X.shape[1]} test={X_test.shape[0]}")

    if args.chips == 4:
        mesh_phase(checks, clock, X, y, workload, args.seed)
    else:
        import jax.numpy as jnp

        from repro.core import init_params_for

        params0 = init_params_for(workload.kernel, noise=0.3,
                                  dtype=jnp.float32)
        check_mvm(checks, clock, X, params0, args.seed, workload)
        check_small_mll(checks, clock, X, y, workload)
        check_small_serve(checks, clock, X, y, X_test, workload, args.seed)
        run = train(checks, clock, X, y, workload, args.steps)
        if not args.train_only:
            serve(checks, clock, run, X_test, y_test, y, workload, args.seed)

    total_wall = sum(w for w, _ in Phase.totals.values())
    print(f"[summary] phases wall={total_wall:.3f}s "
          f"compile={clock.seconds:.3f}s persistent-cache hits="
          f"{clock.cache_hits}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[summary] peak device memory "
              f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB")
    if checks.failed:
        print(f"[summary] FAILED checks: {', '.join(checks.failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
