"""Open-loop prediction requests: the `serve_open_loop` kind of traffic.

The served posterior is the program's: a `repro.serve.PosteriorArtifact`
of the configuration's n training points, served by
`PredictionEngine(backend=...)` behind a `ContinuousBatcher` with the
program's default `SchedulerConfig`. Its caches (the mean cache a, the
Lanczos basis Q and the Cholesky factor L of the tridiagonal T) are drawn
on the device from the seed at the shapes `fit_posterior` gives, since a
real precompute at this n would outlast any set-up:

* a ~ N(0, 1), scaled so that the served means have unit spread over a
  calibration set of test points;
* Q ~ N(0, 1 / n) of shape (n, lanczos_rank), nearly orthonormal columns;
* L = c (I + 0.1 G / sqrt(r)) with G strictly lower triangular N(0, 1),
  and c chosen so that the largest variance reduction diag(P T^-1 P^T),
  P = K(Z, X) Q, over the calibration set is `var_reduction_max` of the
  prior variance. Every served variance is then positive.

Arrivals are an open loop: `rate_per_s * seconds` requests of
`rows_per_request` test points each, due at a Poisson schedule. The gaps
are the exponential distribution's quantiles at (i + 0.5) / N, shuffled by
the seed, so every seed offers the same set of gaps in another order. A
request is sent when due, whether or not earlier ones have finished, and
its latency runs from when it was due to its reply. A request that fails,
or has no reply `drain_s` after the window closes, counts as infinitely
late. `predict_p95_ms` is the 95th percentile over all requests.

A traffic file may ask for several resident posteriors (`models`, each
drawn from the seed as above, each its own engine and model in the
batcher) with Zipf popularity (`zipf_s`): each request then names the
posterior it asks.

Once the window has closed, a sample of the answered requests drawn from
the seed is checked against the reference's posterior at HIGHEST:
`mean_gap` is the largest error of a served mean over the largest
|reference mean - prior mean| of the sample, and `var_gap` the largest
relative error of a served variance.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from chipbench import datagen
from chipbench.common import (Checks, Profile, annotate, inv_softplus,
                              load_reference, log, memory_peak_bytes)

def raw_params(post: dict) -> dict:
    return {"raw_lengthscale": inv_softplus(post["lengthscale"]),
            "raw_outputscale": inv_softplus(post["outputscale"]),
            "raw_noise": inv_softplus(post["noise"]),
            "raw_mean": float(post["mean"])}


def make_posterior(ctx, X_train, X_cal, index: int = 0):
    """(raw params, mean_cache, var_Q, var_T_chol) of resident posterior
    `index`, drawn from the seed and calibrated on X_cal with the
    reference."""
    import jax
    import jax.numpy as jnp

    cfg = ctx.config
    ref = load_reference(cfg["reference"])
    raw = raw_params(cfg["posterior"])
    h = ref.hyper(raw, cfg["noise_floor"])
    n, r = X_train.shape[0], cfg["lanczos_rank"]
    key = jax.random.fold_in(datagen.seed_key(ctx.seed), 1 + index)

    @jax.jit
    def draw(key):
        ka, kq, kg = jax.random.split(key, 3)
        a = jax.random.normal(ka, (n,), jnp.float32)
        Q = jax.random.normal(kq, (n, r), jnp.float32) / jnp.sqrt(
            jnp.float32(n))
        G = jnp.tril(jax.random.normal(kg, (r, r), jnp.float32), -1)
        L0 = jnp.eye(r, dtype=jnp.float32) + 0.1 * G / jnp.sqrt(
            jnp.float32(r))
        return a, Q, L0

    a, Q, L0 = draw(key)
    block = ctx.traffic["reference_block"]
    KV = ref.cross_matvec(X_cal, X_train, jnp.concatenate([a[:, None], Q], 1),
                          h, block=block)
    spread = float(jnp.std(KV[:, 0]))
    W = jax.scipy.linalg.solve_triangular(L0, KV[:, 1:].T, lower=True)
    corr0 = float(jnp.max(jnp.sum(W * W, axis=0)))
    target = ctx.traffic["var_reduction_max"] * float(h.outputscale)
    c = math.sqrt(corr0 / target)
    log(f"[setup] posterior: mean spread {spread!r} -> 1, largest variance "
        f"reduction {corr0!r} -> {target!r} (c={c!r})")
    return raw, a / spread, Q, c * L0


class Schedule:
    """Due times (s after the window opens) and query rows per request."""

    def __init__(self, tr: dict, seconds: float, seed: int, pool: int):
        rate = float(tr["rate_per_s"])
        count = max(1, int(round(rate * seconds)))
        rng = np.random.default_rng(seed)
        q = (np.arange(count) + 0.5) / count
        gaps = -np.log1p(-q) / rate
        rng.shuffle(gaps)
        self.due = np.cumsum(gaps) - gaps[0]
        self.rows = rng.integers(0, pool, size=(count, tr["rows_per_request"]))
        # which resident posterior each request asks (Zipf popularity)
        models = int(tr.get("models", 1))
        weight = 1.0 / np.arange(1, models + 1) ** float(tr.get("zipf_s", 0))
        self.model = rng.choice(models, size=count, p=weight / weight.sum())

    def __len__(self) -> int:
        return len(self.due)


class _Traced:
    """The engine with each launch annotated, for a traced run only."""

    def __init__(self, engine):
        self._engine = engine

    def predict(self, X):
        with annotate("launch", True):
            return self._engine.predict(X)


def build_engine(ctx, X_train, raw, mean_cache, var_Q, var_T_chol):
    import jax.numpy as jnp

    from repro.core import OperatorConfig
    from repro.core.kernels_math import GPParams
    from repro.serve import PredictionEngine
    from repro.serve.artifact import PosteriorArtifact

    cfg = ctx.config
    params = GPParams(**{k: jnp.float32(v) for k, v in raw.items()})
    art = PosteriorArtifact(
        config=OperatorConfig(kernel=cfg["kernel"], backend=cfg["backend"],
                              noise_floor=cfg["noise_floor"]),
        params=params, X=X_train,
        y=jnp.zeros((X_train.shape[0],), jnp.float32),
        mean_cache=mean_cache, var_Q=var_Q, var_T_chol=var_T_chol,
        solve_rel_residual=jnp.zeros((1,), jnp.float32),
        meta={"n": int(X_train.shape[0]), "d": int(X_train.shape[1]),
              "lanczos_rank": int(var_Q.shape[1]), "has_y": False})
    return PredictionEngine(art, backend=cfg["backend"],
                            chunk_size=ctx.traffic["chunk_size"])


def warm_up(engine, d: int, buckets) -> None:
    """Every block shape the batcher can ship, once."""
    import jax

    for rows in buckets:
        jax.block_until_ready(engine.predict(np.zeros((rows, d), np.float32)))


def drive(ctx, batcher, pool_host, sched: Schedule):
    """The open loop; returns (latency ms per request, lateness s per
    request, answers {i: (mean, var)}, failed count, window seconds)."""
    n = len(sched)
    done = np.full(n, np.nan)
    answers: dict = {}
    errors = [0]
    lock = threading.Lock()
    trace = ctx.trace

    def on_done(i, fut):
        t = time.perf_counter()
        with annotate("reply", trace):
            try:
                mean, var = fut.result()
                answers[i] = (mean, var)
                done[i] = t
            except Exception:
                with lock:
                    errors[0] += 1

    queries = [pool_host[r] for r in sched.rows]
    names = [model_name(m) for m in sched.model]
    late = np.zeros(n)
    t0 = time.perf_counter()
    due_abs = t0 + sched.due
    for i in range(n):
        wait = due_abs[i] - time.perf_counter()
        if wait > 0:
            with annotate("await_arrival", trace):
                time.sleep(wait)
        with annotate("submit", trace):
            fut = batcher.submit(queries[i], model=names[i])
        late[i] = time.perf_counter() - due_abs[i]
        fut.add_done_callback(lambda f, i=i: on_done(i, f))
    t_close = time.perf_counter()
    deadline = t_close + ctx.traffic["drain_s"]
    with annotate("drain", trace):
        while np.isnan(done).any() and time.perf_counter() < deadline:
            if errors[0] + int(np.isfinite(done).sum()) >= n:
                break
            time.sleep(0.001)
    window_s = t_close - t0
    lat = (done - due_abs) * 1e3
    lat[np.isnan(lat)] = np.inf
    return lat, late, answers, int(np.isinf(lat).sum()), window_s


class Served:
    """Everything set-up leaves for the window and the check."""

    def __init__(self, ctx, engine_factory=None):
        import jax.numpy as jnp

        from repro.serve import ContinuousBatcher, SchedulerConfig

        cfg, tr = ctx.config, ctx.traffic
        data = datagen.make(ctx.seed,
                            total=datagen.total_for_train(cfg["n"]),
                            d=cfg["d"], with_targets=False)
        self.X_train = data.X_train
        rng = np.random.default_rng(ctx.seed)
        pick = rng.choice(data.X_test.shape[0],
                          tr["query_pool"] + tr["calibration_rows"],
                          replace=False)
        chosen = data.X_test[jnp.asarray(pick)]
        X_cal = chosen[tr["query_pool"]:]
        models = int(tr.get("models", 1))
        self.posts = [make_posterior(ctx, self.X_train, X_cal, m)
                      for m in range(models)]
        self.pool_host = np.asarray(chosen[:tr["query_pool"]])
        del data
        factory = engine_factory or build_engine
        sched_cfg = SchedulerConfig()
        served = {}
        for m, post in enumerate(self.posts):
            engine = factory(ctx, self.X_train, *post)
            warm_up(engine, cfg["d"], sched_cfg.bucket_sizes)
            served[model_name(m)] = _Traced(engine) if ctx.trace else engine
        self.batcher = ContinuousBatcher(served, sched_cfg)

    def window(self, ctx, sched):
        """The open loop over one schedule: a dict of what it measured."""
        b = self.batcher
        b0, r0 = b.batches_run, b.rows_served
        lat, late, answers, failed, window_s = drive(ctx, b,
                                                     self.pool_host, sched)
        return {"lat": lat, "late": late, "answers": answers,
                "failed": failed, "window_s": window_s,
                "launches": b.batches_run - b0, "rows": b.rows_served - r0}

    def close(self) -> None:
        self.batcher.close()
        self.batcher = None


def summary(w: dict) -> str:
    lat, late = w["lat"], w["late"]
    finite = lat[np.isfinite(lat)]
    p50 = np.percentile(finite, 50) if finite.size else float("nan")
    return (f"{len(lat)} requests in {w['window_s']:.3f}s, {w['failed']} "
            f"failed, {w['launches']} launches of {w['rows']} rows; latency "
            f"ms p50={p50:.3f} p95={np.percentile(lat, 95):.3f} "
            f"p99={np.percentile(lat, 99):.3f} max={lat.max():.3f}; "
            f"generator lateness ms p50={np.percentile(late, 50) * 1e3:.3f} "
            f"p99={np.percentile(late, 99) * 1e3:.3f} "
            f"max={late.max() * 1e3:.3f}")


def run(ctx, clock, engine_factory=None) -> dict:
    import gc

    tr = ctx.traffic
    st = Served(ctx, engine_factory)
    sched = Schedule(tr, ctx.seconds, ctx.seed, tr["query_pool"])
    setup_s = time.perf_counter() - ctx.t_start
    log(f"[setup] n={st.X_train.shape[0]} requests={len(sched)} at "
        f"{tr['rate_per_s']}/s; setup_s={setup_s:.3f} "
        f"compile={clock.seconds:.3f}s compiles={clock.compiles} "
        f"cache_hits={clock.cache_hits}")
    clock.mark()
    try:
        with Profile(ctx) as prof:
            w = st.window(ctx, sched)
    finally:
        st.close()
    log(f"[window] {summary(w)}; compiles_in_window={clock.since_mark}")
    mem = memory_peak_bytes(ctx.devices)
    gc.collect()

    checks = Checks(ctx.limits)
    check(ctx, st.X_train, st.posts, st.pool_host, sched, w["answers"],
          checks)
    p95 = float(np.percentile(w["lat"], 95))
    return {
        "e2e": {"setup_s": setup_s,
                "predict_p95_ms": p95 if math.isfinite(p95) else 1e12},
        "attempted": len(sched), "failed": w["failed"], "checks": checks,
        "memory_peak_bytes": mem, "profile": prof.path,
        "layer_ctx": {"kind": "serve", "launches": w["launches"],
                      "rows": w["rows"], "window_host_s": w["window_s"]},
    }


def model_name(m: int) -> str:
    """The batcher's name of resident posterior m (the default for one)."""
    from repro.serve import ContinuousBatcher

    return ContinuousBatcher.DEFAULT if m == 0 else f"posterior{m}"


def check(ctx, X_train, posts, pool_host, sched, answers,
          checks: Checks) -> None:
    import jax.numpy as jnp

    cfg, tr = ctx.config, ctx.traffic
    ref = load_reference(cfg["reference"])
    rng = np.random.default_rng(ctx.seed + 1)
    k = min(tr["check_requests"], len(sched))
    sample = np.sort(rng.choice(len(sched), k, replace=False))
    missing = [int(i) for i in sample if int(i) not in answers]
    if missing:
        log(f"[check] {len(missing)} sampled requests have no answer")
        checks.record("mean_gap", math.inf)
        checks.record("var_gap", math.inf)
        return
    mean_gap = var_gap = 0.0
    for m, (raw, mean_cache, var_Q, var_T_chol) in enumerate(posts):
        mine = [int(i) for i in sample if sched.model[i] == m]
        if not mine:
            continue
        h = ref.hyper(raw, cfg["noise_floor"])
        Z = np.concatenate([pool_host[sched.rows[i]] for i in mine])
        got_m = np.concatenate([answers[i][0] for i in mine])
        got_v = np.concatenate([answers[i][1] for i in mine])
        ref_m, ref_v = ref.posterior(jnp.asarray(Z), X_train, mean_cache,
                                     var_Q, var_T_chol, h,
                                     block=tr["reference_block"])
        ref_m = np.asarray(ref_m, np.float64)
        ref_v = np.asarray(ref_v, np.float64)
        scale = np.max(np.abs(ref_m - float(h.mean)))
        mean_gap = max(mean_gap,
                       float(np.max(np.abs(got_m - ref_m)) / scale))
        var_gap = max(var_gap, float(np.max(np.abs(got_v - ref_v) / ref_v)))
        log(f"[check] posterior {m}: {len(mine)} requests ({Z.shape[0]} "
            f"rows); reference variance in [{ref_v.min()!r}, "
            f"{ref_v.max()!r}]")
    log(f"[check] {k} requests: mean_gap {mean_gap!r} var_gap {var_gap!r}")
    checks.record("mean_gap", mean_gap)
    checks.record("var_gap", var_gap)
