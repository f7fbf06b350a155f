"""Observability spine: tracing, metrics, report, and the no-op contract.

The load-bearing claims, each pinned here:
  * spans nest and round-trip through the Chrome-trace JSONL, and the
    self-time attribution in `repro.obs.report` partitions wall-clock
    exactly (the Table-2 identity);
  * DISABLED tracing is a true no-op — `maybe_wrap` returns the function
    itself, `span` returns the shared null singleton, zero events reach
    the sink, and (the jit contract) enabling obs around a jitted solve
    causes NO retraces and NO numerics change;
  * device-side counts reach the registry via RETURNED AUX only — the
    engine's telemetry is registry-backed and per-RHS iteration counts
    match MLLAux;
  * the phased (traced) engine dispatch agrees with the single-jit step;
  * enabled-mode overhead on a small fit is bounded;
  * the shared serve summary helper matches np.percentile, and BENCH
    JSONs carry the meta + metrics blocks.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_gp_data
from repro import obs
from repro.core import ExactGP, ExactGPConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.report import assign_self_times, load_trace, phase_breakdown
from repro.train.gp_trainer import GPTrainConfig, fit_exact_gp
from repro.train.solver_state import WarmStartConfig, WarmStartEngine


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and a clean registry."""
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs.registry().reset()
    yield
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs.registry().reset()


def _gp(**kw):
    base = dict(kernel="matern32", backend="partitioned", row_block=48,
                precond_rank=20, num_probes=4, train_max_cg_iters=20)
    base.update(kw)
    return ExactGP(ExactGPConfig(**base))


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------


def test_spans_nest_and_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.trace_session(path):
        with obs.span("outer", tag="a"):
            with obs.span("inner"):
                pass
            with obs.span("inner"):
                pass
        obs.counter("cg.iters").inc(7)
    # one JSON object per line; loads as Chrome events
    events, snap = load_trace(path)
    assert snap["cg.iters"] == 7
    spans = assign_self_times(events)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["inner"]) == 2
    (outer,) = by_name["outer"]
    assert outer.args == {"tag": "a"}
    # containment: children lie inside the parent; parent self excludes them
    for s in by_name["inner"]:
        assert outer.ts <= s.ts and s.ts + s.dur <= outer.ts + outer.dur
        assert s.depth == 1
    child_dur = sum(s.dur for s in by_name["inner"])
    assert outer.self_us == pytest.approx(outer.dur - child_dur)


def test_span_set_attaches_attrs():
    obs.enable_tracing(None)  # in-memory sink
    with obs.span("step") as sp:
        sp.set(cg_iters=12)
    (ev,) = obs.drain_events()
    obs.disable_tracing(snapshot_metrics=False)
    assert ev["name"] == "step" and ev["args"]["cg_iters"] == 12


def test_disabled_mode_is_true_noop():
    assert not obs.tracing_enabled()

    def f(x):
        return x + 1

    # identity wrap: the instrumented call site pays literally nothing
    assert obs.maybe_wrap("f", f) is f
    # shared null singleton, not a fresh object per call
    assert obs.span("a") is obs.span("b")
    with obs.span("nothing") as sp:
        sp.set(ignored=1)
    obs.instant("nothing")
    obs.counter_event("nothing", v=1)
    assert obs.drain_events() == []


def test_trace_session_restores_disabled(tmp_path):
    with obs.trace_session(str(tmp_path / "t.jsonl")):
        assert obs.tracing_enabled()
    assert not obs.tracing_enabled()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    reg.gauge("g").set(0.25)
    h = reg.histogram("h")
    for v in range(100):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["g"] == 0.25
    assert snap["h"]["count"] == 100
    assert snap["h"]["p50"] == pytest.approx(np.percentile(np.arange(100), 50))
    assert reg.counter("c") is c  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("c")
    reg.reset("c")
    assert reg.counter("c").value == 0 and reg.gauge("g").value == 0.25


def test_histogram_decimation_keeps_order_statistics():
    h = MetricsRegistry().histogram("h")
    h.max_samples = 256
    vals = np.random.default_rng(0).standard_normal(10_000)
    h.observe_many(vals)
    assert h.count == 10_000
    p50, p99 = h.percentiles((50, 99))
    e50, e99 = np.percentile(vals, (50, 99))
    assert abs(p50 - e50) < 0.1 and abs(p99 - e99) < 0.35


def test_latency_summary_matches_percentiles():
    lats = np.abs(np.random.default_rng(1).standard_normal(500)) * 0.01
    s = obs.latency_summary(lats, wall_s=2.0)
    p50, p99 = np.percentile(lats, (50, 99)) * 1e3
    assert s["p50_ms"] == pytest.approx(p50)
    assert s["p99_ms"] == pytest.approx(p99)
    assert s["qps"] == pytest.approx(250.0)
    assert s["count"] == 500
    empty = obs.latency_summary([])
    assert empty["count"] == 0 and np.isnan(empty["p50_ms"])


def test_record_solver_step_keeps_legacy_telemetry_shape():
    reg = MetricsRegistry()
    entry = obs.record_solver_step(mode="warm", iters_per_rhs=[3, 2, 2],
                                   drift=0.05, seconds=0.5, launches=12,
                                   hbm_bytes=1e6, reg=reg)
    # pre-obs consumers read these exact keys (launch/train, verbose prints)
    assert entry["mode"] == "warm" and entry["refreshed"] is False
    assert entry["cg_iters"] == 7 and entry["drift"] == 0.05
    assert entry["cg_iters_per_rhs"] == [3, 2, 2]
    snap = reg.snapshot()
    assert snap["solver.steps.warm"] == 1 and snap["cg.iters"] == 7
    assert snap["mvm.matmat_launches"] == 12


def test_cost_model_backends():
    n, d, r, iters = 1024, 4, 5, 20
    part = obs.mll_step_cost(n, d, r, iters, backend="partitioned",
                             row_block=256)
    # the whole trip count: max_iters forward traversals, 4 slabs each
    assert part.launches == iters * 4 + 4
    assert part.hbm_bytes == pytest.approx(
        n * n * 8.0 * iters + n * n * 8.0 * 2.5)
    pallas = obs.mll_step_cost(n, d, r, iters, backend="pallas", bm=256)
    assert pallas.launches < part.launches  # megakernel: 1 launch/traversal
    assert pallas.hbm_bytes < part.hbm_bytes  # slab never hits HBM
    sparse = obs.mll_step_cost(n, d, r, iters, backend="blocksparse",
                               fill=0.25)
    assert sparse.hbm_bytes == pytest.approx(
        0.25 * n * n * 8.0 * iters + 0.25 * n * n * 8.0 * 2.5)
    warm = obs.mll_step_cost(n, d, r, iters, backend="partitioned",
                             row_block=256, warm_init=True)
    assert warm.traversals == part.traversals + 1


# ---------------------------------------------------------------------------
# jit contract: returned aux, no retraces, no numerics change
# ---------------------------------------------------------------------------


def test_counters_accumulate_via_returned_aux_under_jit():
    traces = {"n": 0}

    @jax.jit
    def solve(x):
        traces["n"] += 1  # python side-effect: counts retraces
        # iteration count leaves the jit as RETURNED AUX
        return x * 2.0, jnp.asarray([3, 2], jnp.int32)

    c = obs.counter("cg.iters")
    for _ in range(3):
        out, aux = solve(jnp.ones(4))
        jax.block_until_ready(out)
        c.inc(int(np.sum(np.asarray(aux))))  # host-side, post-fence
    assert c.value == 15
    assert traces["n"] == 1  # recording never retraced


def test_enabling_obs_causes_no_retrace_and_no_numerics_change(rng):
    X, y = make_gp_data(rng, n=96, d=3)
    gp = _gp(row_block=32)
    traces = {"n": 0}
    mllc = gp.config.mll_config()

    from repro.core.mll import exact_mll

    @jax.jit
    def loss(p, k):
        traces["n"] += 1
        (v, aux) = exact_mll(mllc, X, y, p, k)
        return v

    params = gp.init_params(3, dtype=X.dtype)
    k = jax.random.PRNGKey(0)
    v0 = loss(params, k)
    assert traces["n"] == 1
    obs.enable_tracing(None)
    with obs.span("traced_region"):
        v1 = loss(params, k)
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    v2 = loss(params, k)
    assert traces["n"] == 1  # tracing on/off: zero retraces
    # bitwise: same compiled executable, same inputs
    assert float(v0) == float(v1) == float(v2)


def test_phased_dispatch_matches_single_jit(rng):
    X, y = make_gp_data(rng, n=128, d=3)
    cfg = _gp(row_block=32).config.mll_config()
    # huge drift threshold: the schedule alone decides the mode sequence
    warm = WarmStartConfig(enabled=True, refresh_every=2,
                           drift_threshold=10.0)
    key = jax.random.PRNGKey(0)

    def run(traced: bool):
        obs.registry().reset()
        eng = WarmStartEngine(cfg, warm)
        params = _gp().init_params(3, dtype=X.dtype)
        out = []
        if traced:
            obs.enable_tracing(None)
        try:
            for i in range(4):
                loss, aux, g = eng.step(X, y, params, key)
                out.append((float(loss), float(aux.logdet),
                            np.asarray(aux.cg_iterations).sum()))
                params = jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)
        finally:
            if traced:
                obs.disable_tracing(snapshot_metrics=False)
                obs.drain_events()
        return out, [t["mode"] for t in eng.telemetry]

    plain, modes_plain = run(False)
    phased, modes_phased = run(True)
    assert modes_plain == modes_phased == ["cold", "warm", "refresh", "warm"]
    for (l0, ld0, it0), (l1, ld1, it1) in zip(plain, phased):
        # same math, different jit partitioning: fp-identical inputs but
        # XLA may fuse differently, so allow a hair of slack
        assert l0 == pytest.approx(l1, rel=1e-8)
        assert ld0 == pytest.approx(ld1, rel=1e-8)
        assert abs(it0 - it1) <= 2


def test_engine_telemetry_is_registry_backed(rng):
    X, y = make_gp_data(rng, n=96, d=3)
    cfg = _gp(row_block=32).config.mll_config()
    eng = WarmStartEngine(cfg, WarmStartConfig(enabled=True, refresh_every=3))
    params = _gp().init_params(3, dtype=X.dtype)
    for i in range(3):
        _, aux, _ = eng.step(X, y, params, jax.random.PRNGKey(i))
        t = eng.telemetry[-1]
        # per-RHS counts come straight from the returned MLLAux
        assert t["cg_iters_per_rhs"] == [
            int(v) for v in np.asarray(aux.cg_iterations)]
        assert t["cg_iters"] == sum(t["cg_iters_per_rhs"])
        assert t["mvm_launches"] > 0 and t["hbm_bytes_modeled"] > 0
    snap = obs.registry().snapshot()
    assert snap["solver.steps.cold"] == 1 and snap["solver.steps.warm"] == 2
    assert snap["cg.iters"] == sum(t["cg_iters"] for t in eng.telemetry)
    assert snap["cg.iters_per_rhs"]["count"] == 3 * (cfg.num_probes + 1)


def test_fit_telemetry_modes_and_overhead(rng):
    """fit_exact_gp telemetry sources the registry; enabled-mode tracing
    does not blow up the fit cost (generous bound: spans are host-side
    timers, but the phased dispatch loses some jit fusion)."""
    import time

    X, y = make_gp_data(rng, n=128, d=3)
    gp = _gp(row_block=32)
    cfg = GPTrainConfig(plain_adam_steps=3, refresh_every=2, seed=0)

    t0 = time.perf_counter()
    res0 = fit_exact_gp(gp, X, y, cfg=cfg, method="adam")
    base_s = time.perf_counter() - t0

    obs.registry().reset()
    obs.enable_tracing(None)
    t0 = time.perf_counter()
    res1 = fit_exact_gp(gp, X, y, cfg=cfg, method="adam")
    traced_s = time.perf_counter() - t0
    obs.disable_tracing(snapshot_metrics=False)
    events = obs.drain_events()

    assert [t["mode"] for t in res0.telemetry] == \
           [t["mode"] for t in res1.telemetry] == ["cold", "warm", "refresh"]
    assert res1.loss_trace[-1] == pytest.approx(res0.loss_trace[-1],
                                                rel=1e-8)
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"fit_exact_gp", "mll_step", "precond_build", "cg_solve",
            "slq_logdet", "eq2_backward"} <= names
    # overhead guard: phased compile + spans; generous for a 1-core CI box
    assert traced_s < 5.0 * base_s + 10.0, (traced_s, base_s)


def test_phase_table_covers_wall_clock(rng):
    X, y = make_gp_data(rng, n=128, d=3)
    gp = _gp(row_block=32)
    obs.enable_tracing(None)
    fit_exact_gp(gp, X, y, cfg=GPTrainConfig(plain_adam_steps=2, seed=0),
                 method="adam")
    obs.disable_tracing(snapshot_metrics=False)
    spans = assign_self_times(
        [e for e in obs.drain_events() if e.get("ph") == "X"])
    rows, wall = phase_breakdown(spans, root="fit_exact_gp")
    covered = sum(r.self_ms for r in rows)
    # acceptance: within 10%; the attribution is exact, so hold 1%
    assert wall > 0 and abs(covered - wall) <= 0.01 * wall


# ---------------------------------------------------------------------------
# satellites: autotune counters, bench meta, serve metrics
# ---------------------------------------------------------------------------


def test_autotune_counters(tmp_path):
    from repro.kernels import autotune

    autotune.clear_memo()
    components = (("matern32",),)
    calls = []

    def measure(bm, bn):
        calls.append((bm, bn))
        return 1.0 if (bm, bn) != (256, 256) else 0.5

    args = dict(compute_dtype="float32", interpret=True,
                candidates=((128, 128), (256, 256)), measure=measure,
                cache_dir=str(tmp_path))
    choice = autotune.autotune_tiles(components, 512, 512, 4, 9, **args)
    assert choice == (256, 256) and len(calls) == 2
    snap = obs.registry().snapshot()
    assert snap["autotune.misses"] == 1 and snap["autotune.sweeps"] == 1
    assert snap["autotune.sweep_ms"]["count"] == 1
    # memo hit: no new sweep
    assert autotune.autotune_tiles(components, 512, 512, 4, 9,
                                   **args) == choice
    snap = obs.registry().snapshot()
    assert snap["autotune.hits"] == 1 and snap["autotune.sweeps"] == 1
    # disk hit after memo clear
    autotune.clear_memo()
    assert autotune.autotune_tiles(components, 512, 512, 4, 9,
                                   **args) == choice
    assert obs.registry().snapshot()["autotune.hits"] == 2
    assert len(calls) == 2  # measure never re-ran


def test_bench_json_meta_and_metrics(tmp_path, monkeypatch):
    from benchmarks import common

    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    obs.counter("cg.iters").inc(42)
    common.write_rows("unit", ["a", "b"], [[1, 2.5], [3, 4.0]])
    with open(tmp_path / "BENCH_unit.json") as f:
        out = json.load(f)
    meta = out["meta"]
    for k in ("git_sha", "jax_version", "jaxlib_version", "device_kind",
              "device_count", "platform", "interpret_mode", "timestamp_utc"):
        assert k in meta, k
    assert meta["device_count"] >= 1
    assert isinstance(meta["interpret_mode"], bool)
    assert out["metrics"]["cg.iters"] == 42
    assert out["records"][0] == {"a": 1, "b": 2.5}


def test_serve_batching_metrics(rng):
    from repro.serve.batching import BatcherConfig, MicroBatcher

    class FakeEngine:
        def predict(self, X):
            return np.zeros(X.shape[0]), np.ones(X.shape[0])

    with MicroBatcher(FakeEngine(), BatcherConfig(
            max_batch=8, max_wait_ms=5.0, bucket_sizes=(8, 16))) as b:
        futs = [b.submit(np.zeros((2, 3))) for _ in range(8)]
        for f in futs:
            mean, var = f.result(timeout=10)
            assert mean.shape == (2,)
    snap = obs.registry().snapshot()
    assert snap["serve.batch_rows"]["count"] >= 1
    assert snap["serve.request_wait_ms"]["count"] == 8
    assert snap["serve.queue_depth"] is not None
    # rows histogram sums to the rows actually served
    assert obs.histogram("serve.batch_rows").sum == 16


def test_slq_with_aux(rng):
    from repro.core.operators import OperatorConfig, make_operator
    from repro.core.slq import SLQAux, slq_logdet
    from repro.core import init_params

    X, _ = make_gp_data(rng, n=64, d=2)
    params = init_params(noise=0.3, dtype=X.dtype)
    op = make_operator(OperatorConfig(kernel="matern32",
                                      backend="partitioned", row_block=32),
                       X, params)
    ld, aux = slq_logdet(op, jax.random.PRNGKey(0), num_probes=4,
                         precond_rank=10, max_iters=30, tol=1e-6,
                         with_aux=True)
    assert isinstance(aux, SLQAux) and aux.num_probes == 4
    assert aux.iterations.shape == (4,) and np.all(
        np.asarray(aux.iterations) > 0)
    ld_plain = slq_logdet(op, jax.random.PRNGKey(0), num_probes=4,
                          precond_rank=10, max_iters=30, tol=1e-6)
    assert float(ld) == float(ld_plain)
