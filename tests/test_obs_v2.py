"""Measurement plane (obs v2): measured-vs-modeled accounting, request
tracing, health events, and the BENCH regression gate.

Pinned contracts, one section each:
  * signal flush — SIGINT/SIGTERM flush the trace sink AND chain the
    previously installed handler (a killed serve keeps its trace tail);
  * SLOTracker — read-time pruning (QPS decays after traffic stops) and
    target/burn accounting; latency_summary reports max_ms and flags the
    p99 interpolation below 100 samples;
  * report robustness — truncated JSONL lines, unclosed (dur-less) spans,
    and partially-overlapping siblings degrade without corrupting the
    self-time attribution; request flows get their own section;
  * health — every sentinel in check_solver_step fires on a synthetic aux
    that exhibits it, the JSONL sink round-trips past garbled lines, and
    enabling health flips the engine's residual tracking (returned-aux
    only: the disabled path stays the default compiled program);
  * regress — self-diff is clean, out-of-tolerance regressions fail,
    improvements never do (one-sided), '±' cells parse, identity matching
    survives reordering, and the obs_diff CLI exits 0/1/2 accordingly;
  * measure — phase spans aggregate into the measured-vs-modeled table
    and the per-phase cost split sums back to the step cost.
"""

import copy
import json
import os
import signal

import jax
import numpy as np
import pytest

from conftest import make_gp_data
from repro import obs
from repro.obs import health as obs_health
from repro.obs import regress
from repro.obs.measure import format_model_comparison, phase_model_comparison
from repro.obs.metrics import SLOTracker
from repro.obs.report import (
    assign_self_times,
    load_trace,
    phase_breakdown,
    request_breakdown,
    split_request_spans,
)
from repro.train.solver_state import WarmStartConfig, WarmStartEngine


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs_health.disable_health()
    obs_health.drain_health_events()
    obs.registry().reset()
    yield
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs_health.disable_health()
    obs_health.drain_health_events()
    obs.registry().reset()


# ---------------------------------------------------------------------------
# signal flush
# ---------------------------------------------------------------------------


def test_signal_flush_chains_previous_handler(tmp_path):
    from repro.obs import trace as trace_mod

    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    st = trace_mod._STATE
    hooked, handlers = st._signals_hooked, dict(st._prev_handlers)
    st._signals_hooked, st._prev_handlers = False, {}
    path = str(tmp_path / "t.jsonl")
    try:
        obs.enable_tracing(path)
        with obs.span("work"):
            pass
        os.kill(os.getpid(), signal.SIGTERM)
        # our handler flushed the sink, then chained the previous one
        assert seen == [signal.SIGTERM]
        assert not obs.tracing_enabled()
        events, _ = load_trace(path)
        assert any(e.get("name") == "work" for e in events)
    finally:
        signal.signal(signal.SIGTERM, prev)
        st._signals_hooked, st._prev_handlers = hooked, handlers


# ---------------------------------------------------------------------------
# SLO tracker + latency summary
# ---------------------------------------------------------------------------


def test_slo_tracker_target_and_burn():
    t = SLOTracker("s", window_s=10.0, target_ms=50.0)
    breached = [t.record(0.1 if i % 2 else 0.01, now=100.0 + i)
                for i in range(10)]
    assert breached == [False, True] * 5
    s = t.summary(now=109.0)
    assert s["target_ms"] == 50.0
    assert s["breaches"] == 5
    assert s["burn_rate"] == pytest.approx(0.5)
    t.reset()
    assert t.summary(now=109.0)["breaches"] == 0


def test_slo_tracker_prunes_at_read_time():
    t = SLOTracker("s", window_s=10.0)
    for i in range(20):
        t.record(0.01, now=100.0 + i * 0.1)
    assert t.summary(now=102.0)["qps"] > 0
    # traffic stopped: a later READ must see the window decay to empty,
    # not the stale last-burst rate
    s = t.summary(now=1000.0)
    assert s["qps"] == 0.0
    assert len(t._times) == 0  # deque pruned, memory O(recent)


def test_latency_summary_max_and_interpolation_flag():
    s = obs.latency_summary([0.01] * 50)
    assert s["max_ms"] == pytest.approx(10.0)
    assert s["p99_interpolated"] is True  # < 100 samples
    s = obs.latency_summary(np.linspace(0.001, 0.1, 200))
    assert s["p99_interpolated"] is False
    assert s["max_ms"] == pytest.approx(100.0)
    empty = obs.latency_summary([])
    assert empty["p99_interpolated"] is True and np.isnan(empty["max_ms"])


# ---------------------------------------------------------------------------
# report robustness on malformed traces
# ---------------------------------------------------------------------------


def _ev(name, ts, dur, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": tid, "args": args}


def test_load_trace_skips_truncated_lines(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_text("\n".join([
        json.dumps(_ev("a", 0.0, 100.0)),
        '{"name": "b", "ph": "X", "ts": 5',   # killed mid-write
        "not json at all",
        "[1, 2, 3]",                          # json, but not an event
        json.dumps(_ev("c", 10.0, 20.0)),
    ]) + "\n")
    events, _ = load_trace(str(path))
    assert [e["name"] for e in events] == ["a", "c"]


def test_unclosed_spans_are_dropped_not_fatal():
    events = [
        _ev("root", 0.0, 100.0),
        {"name": "unclosed", "ph": "X", "ts": 10.0, "tid": 1},  # no dur
        _ev("child", 20.0, 30.0),
    ]
    spans = assign_self_times(events)
    assert {s.name for s in spans} == {"root", "child"}
    root = next(s for s in spans if s.name == "root")
    assert root.self_us == pytest.approx(70.0)


def test_overlapping_sibling_debits_only_the_overlap():
    # straddler starts inside root but ends after it: only the 20us of
    # overlap may be debited from root's self time
    spans = assign_self_times([
        _ev("root", 0.0, 100.0),
        _ev("straddler", 80.0, 50.0),
    ])
    root = next(s for s in spans if s.name == "root")
    assert root.self_us == pytest.approx(80.0)
    # and self times stay non-negative even when straddlers pile up
    spans = assign_self_times([
        _ev("root", 0.0, 100.0),
        _ev("s1", 50.0, 200.0),
        _ev("s2", 60.0, 300.0),
    ])
    assert all(s.self_us >= 0.0 for s in spans)


def test_request_spans_split_out_of_phase_table():
    events = [
        _ev("fit", 0.0, 1000.0, tid=7),
        _ev("serve_request", 100.0, 500.0, tid="req:r1", model="m0"),
        _ev("serve_queue", 100.0, 200.0, tid="req:r1"),
        _ev("serve_solve", 300.0, 250.0, tid="req:r1"),
    ]
    spans = assign_self_times(events)
    phase_spans, req_spans = split_request_spans(spans)
    assert {s.name for s in phase_spans} == {"fit"}
    rows, wall = phase_breakdown(phase_spans, root="fit")
    assert wall == pytest.approx(1.0)  # request flow doesn't inflate wall
    rows = request_breakdown(req_spans)
    assert len(rows) == 1
    r = rows[0]
    assert r["model"] == "m0" and r["count"] == 1
    assert r["p50_ms"] == pytest.approx(0.5)
    assert r["queue_ms_mean"] == pytest.approx(0.2)
    assert r["solve_ms_mean"] == pytest.approx(0.25)


def test_continuous_batcher_emits_request_flow(rng):
    from repro.serve.batching import ContinuousBatcher, SchedulerConfig

    class FakeEngine:
        def predict(self, X):
            return np.zeros(X.shape[0]), np.ones(X.shape[0])

    obs.enable_tracing(None)
    with ContinuousBatcher(FakeEngine(),
                           SchedulerConfig(max_batch=8)) as cb:
        futs = [cb.submit(np.zeros((2, 3))) for _ in range(5)]
        for f in futs:
            f.result(timeout=10)
    events = obs.drain_events()
    obs.disable_tracing(snapshot_metrics=False)
    spans = assign_self_times([e for e in events if e.get("ph") == "X"])
    _, req_spans = split_request_spans(spans)
    rows = request_breakdown(req_spans)
    assert rows and sum(r["count"] for r in rows) == 5
    # parent/child containment per request tid
    by_tid = {}
    for s in req_spans:
        by_tid.setdefault(s.tid, []).append(s)
    assert len(by_tid) == 5
    for tid, spans_t in by_tid.items():
        names = {s.name for s in spans_t}
        assert names == {"serve_request", "serve_queue", "serve_solve"}
        parent = next(s for s in spans_t if s.name == "serve_request")
        for s in spans_t:
            assert s.ts >= parent.ts - 1
            assert s.ts + s.dur <= parent.ts + parent.dur + 1
    snap = obs.registry().snapshot()
    assert snap["serve.queue_depth.default"] is not None
    assert snap["serve.inflight"] == 0
    assert "serve.deficit.default" in snap


def test_request_ids_unique_and_disabled_path_free():
    a, b = obs.next_request_id(), obs.next_request_id()
    assert a != b and a.startswith("r")
    # complete_event with tracing off: no buffered events
    obs.complete_event("serve_request", 0.0, 1.0, tid="req:x")
    assert obs.drain_events() == []


# ---------------------------------------------------------------------------
# health events
# ---------------------------------------------------------------------------


def test_health_sentinels_fire_on_synthetic_aux():
    obs_health.enable_health(None)
    # NaN short-circuits (trajectory checks would only re-trip)
    kinds = obs_health.check_solver_step(
        step=0, mode="warm", tol=1e-2, max_iters=10,
        iters_per_rhs=[5], rel_residual=[float("nan")])
    assert kinds == ["cg.nan"]
    # exhausted trip count while unconverged
    kinds = obs_health.check_solver_step(
        step=1, mode="warm", tol=1e-2, max_iters=10,
        iters_per_rhs=[10], rel_residual=[0.5])
    assert kinds == ["cg.max_iters"]
    # divergence: final residual far above the trajectory minimum
    traj = np.array([[1.0], [0.01], [0.5]])
    kinds = obs_health.check_solver_step(
        step=2, mode="warm", tol=1e-2, max_iters=10,
        iters_per_rhs=[3], rel_residual=[0.5], residuals=traj)
    assert "cg.divergence" in kinds
    # stagnation: a barely-moving window while unconverged
    traj = np.linspace(0.5, 0.49, 15)[:, None]
    kinds = obs_health.check_solver_step(
        step=3, mode="warm", tol=1e-2, max_iters=20,
        iters_per_rhs=[15], rel_residual=[0.49], residuals=traj)
    assert kinds == ["cg.stagnation"]
    # a healthy converged solve emits nothing
    traj = np.geomspace(1.0, 1e-8, 12)[:, None]
    kinds = obs_health.check_solver_step(
        step=4, mode="warm", tol=1e-2, max_iters=20,
        iters_per_rhs=[12], rel_residual=[1e-8], residuals=traj)
    assert kinds == []
    events = obs_health.drain_health_events()
    assert [e["kind"] for e in events] == \
        ["cg.nan", "cg.max_iters", "cg.divergence", "cg.stagnation"]
    assert events[0]["severity"] == "error"
    # counters fired regardless of the sink
    snap = obs.registry().snapshot()
    assert snap["health.cg.nan"] == 1 and snap["health.cg.stagnation"] == 1


def test_health_jsonl_roundtrip_skips_garbage(tmp_path):
    path = str(tmp_path / "h.jsonl")
    obs_health.enable_health(path)
    obs_health.emit("cg.max_iters", step=3, columns=[0])
    obs_health.precond_stale(step=4, drift=0.5, threshold=0.1)
    obs_health.sparse_replan(step=5, fill_before=0.3, fill_after=0.4)
    obs_health.disable_health()
    with open(path, "a") as f:
        f.write('{"kind": "cg.na')  # process died mid-write
    events = obs_health.load_health(path)
    assert [e["kind"] for e in events] == \
        ["cg.max_iters", "precond.stale", "sparse.replan"]
    summary = obs_health.summarize_health(events)
    assert summary["precond.stale"]["count"] == 1
    assert summary["sparse.replan"]["severity"] == "info"
    assert summary["sparse.replan"]["last"]["fill_after"] == 0.4


def test_health_enables_engine_residual_tracking(rng):
    from repro.core import ExactGP, ExactGPConfig

    X, y = make_gp_data(rng, n=96, d=3)
    gp = ExactGP(ExactGPConfig(kernel="matern32", backend="partitioned",
                               row_block=32, precond_rank=20, num_probes=4,
                               train_max_cg_iters=20))
    params = gp.init_params(3, dtype=X.dtype)
    cfg = gp.config.mll_config()
    warm = WarmStartConfig(enabled=True, refresh_every=3)

    # default: residual trajectories are NOT requested (aux stays None —
    # the compiled program is the seed one)
    eng0 = WarmStartEngine(cfg, warm)
    assert eng0.track_residuals is False
    loss0, aux0, _ = eng0.step(X, y, params, jax.random.PRNGKey(0))
    assert aux0.residuals is None

    # health on at construction: tracking flips on via returned aux
    obs_health.enable_health(None)
    try:
        eng1 = WarmStartEngine(cfg, warm)
        assert eng1.track_residuals is True
        loss1, aux1, _ = eng1.step(X, y, params, jax.random.PRNGKey(0))
        assert aux1.residuals is not None
        assert aux1.residuals.shape[1] == cfg.num_probes + 1
        # same math — the extra scan output does not perturb the solve
        assert float(loss1) == pytest.approx(float(loss0), rel=1e-10)
        traj = np.asarray(aux1.residuals)
        it0 = int(np.asarray(aux1.cg_iterations)[0])
        assert traj[it0 - 1, 0] <= traj[0, 0]  # residual decayed
    finally:
        obs_health.disable_health()
        obs_health.drain_health_events()


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


def _bench():
    return {
        "bench": "unit",
        "header": ["backend", "max_batch", "rmse", "fit_s", "qps", "wins"],
        "records": [
            {"backend": "dense", "max_batch": 32, "rmse": 0.5,
             "fit_s": "10.0±1.0", "qps": 100.0, "wins": 3},
            {"backend": "pallas", "max_batch": 256, "rmse": 0.4,
             "fit_s": 12.0, "qps": "-", "wins": 1},
        ],
    }


def test_parse_value_forms():
    assert regress.parse_value(3) == 3.0
    assert regress.parse_value("3.2±0.1") == pytest.approx(3.2)
    assert regress.parse_value("7.5") == 7.5
    assert regress.parse_value("-") is None
    assert regress.parse_value("") is None
    assert regress.parse_value(None) is None
    assert regress.parse_value(True) is None
    assert regress.parse_value("fast") is None


def test_schema_classification():
    assert regress.rule_for("backend") is None          # identity
    assert regress.rule_for("max_batch") is None        # identity
    assert regress.rule_for("rmse").direction == "lower"
    assert regress.rule_for("fit_s").direction == "lower"
    assert regress.rule_for("qps").direction == "higher"
    assert regress.rule_for("wins").direction == "info"  # never gated
    assert regress.rule_for("cg_iters").direction == "lower"
    assert regress.rule_for("saved_pct").direction == "higher"


def test_self_diff_is_clean_and_order_independent():
    base = _bench()
    cur = copy.deepcopy(base)
    cur["records"].reverse()  # identity matching, not positional
    r = regress.compare_bench(base, cur)
    assert r.checked > 0
    assert not r.regressions and not r.warnings


def test_regressions_one_sided_with_tolerance():
    base = _bench()
    cur = copy.deepcopy(base)
    cur["records"][0]["fit_s"] = 100.0  # 10x slower: out of tolerance
    r = regress.compare_bench(base, cur)
    assert [f.column for f in r.regressions] == ["fit_s"]
    assert r.regressions[0].record.startswith("backend=dense")
    # 10x FASTER never fails (direction-aware)
    cur["records"][0]["fit_s"] = 1.0
    r = regress.compare_bench(base, cur)
    assert not r.regressions
    # a drop past the (generous) timing tolerance reads as an improvement
    cur["records"][0]["rmse"] = 0.1
    r = regress.compare_bench(base, cur)
    assert "rmse" in [f.column for f in r.improvements]
    # within tolerance (rel 0.5 on _s): no finding at all
    cur["records"][0]["rmse"] = 0.5
    cur["records"][0]["fit_s"] = 12.0
    r = regress.compare_bench(base, cur)
    assert not r.regressions and not r.improvements
    # higher-is-better gates the other direction
    cur = copy.deepcopy(base)
    cur["records"][0]["qps"] = 10.0
    assert [f.column for f in regress.compare_bench(base, cur).regressions] \
        == ["qps"]
    # tol_scale loosens the gate (CI knob)
    cur = copy.deepcopy(base)
    cur["records"][0]["fit_s"] = 28.0
    assert regress.compare_bench(base, cur).regressions
    assert not regress.compare_bench(base, cur, tol_scale=3.0).regressions


def test_missing_records_and_columns_warn_not_fail():
    base = _bench()
    cur = copy.deepcopy(base)
    cur["records"][1]["backend"] = "renamed"  # identity no longer matches
    cur["records"][0]["rmse"] = "oops"
    r = regress.compare_bench(base, cur)
    assert not r.regressions
    assert len(r.warnings) == 2
    report = regress.format_diff([r])
    assert "warning" in report and "unit" in report


def test_info_columns_never_gate():
    base = _bench()
    cur = copy.deepcopy(base)
    cur["records"][0]["wins"] = 0  # flipped win indicator: descriptive only
    r = regress.compare_bench(base, cur)
    assert not r.regressions and not r.improvements


def test_obs_diff_cli_exit_codes(tmp_path):
    from repro.launch.obs_diff import main as obs_diff_main

    base_dir, cur_dir = tmp_path / "base", tmp_path / "cur"
    base_dir.mkdir(), cur_dir.mkdir()
    (base_dir / "BENCH_unit.json").write_text(json.dumps(_bench()))
    (cur_dir / "BENCH_unit.json").write_text(json.dumps(_bench()))
    report = tmp_path / "report.md"
    rc = obs_diff_main([str(cur_dir), "--baseline", str(base_dir),
                        "--report", str(report)])
    assert rc == 0
    assert "regressions: 0" in report.read_text()
    # perturb past tolerance -> exit 1
    bad = _bench()
    bad["records"][0]["fit_s"] = 100.0
    (cur_dir / "BENCH_unit.json").write_text(json.dumps(bad))
    assert obs_diff_main([str(cur_dir), "--baseline", str(base_dir)]) == 1
    # nothing comparable -> exit 2 (a misconfigured CI gate must not pass)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs_diff_main([str(empty), "--baseline", str(base_dir)]) == 2


# ---------------------------------------------------------------------------
# measured vs modeled
# ---------------------------------------------------------------------------


def test_phase_model_comparison_aggregates_spans():
    span = _ev("cg_solve", 0.0, 5000.0, measured_ms=5.0,
               modeled_hbm_bytes=1e9, backend="dense", modeled_launches=3)
    other = _ev("misc", 0.0, 10.0)  # no modeled args: ignored
    rows = phase_model_comparison([span, span, other],
                                  device_kind="TPU v5 lite")
    assert len(rows) == 1
    r = rows[0]
    assert r["backend"] == "dense" and r["phase"] == "cg_solve"
    assert r["steps"] == 2
    assert r["measured_ms"] == pytest.approx(10.0)
    # 2 GB at the v5e's published 819 GB/s
    assert r["modeled_ms"] == pytest.approx(2e9 / 819e9 * 1e3)
    assert r["ratio"] == pytest.approx(10.0 / (2e9 / 819e9 * 1e3))
    assert r["modeled_launches"] == 6
    text = format_model_comparison(rows, device_kind="TPU v5 lite")
    assert "cg_solve" in text and "ratio" in text and "819" in text
    assert "no phase spans" in format_model_comparison([])
    # a device kind without published peaks is an error, not a default
    with pytest.raises(KeyError, match="no published peaks"):
        phase_model_comparison([span], device_kind="cpu")


def test_traced_fit_produces_model_comparison(rng):
    from repro.core import ExactGP, ExactGPConfig

    X, y = make_gp_data(rng, n=96, d=3)
    gp = ExactGP(ExactGPConfig(kernel="matern32", backend="partitioned",
                               row_block=32, precond_rank=20, num_probes=4,
                               train_max_cg_iters=20))
    params = gp.init_params(3, dtype=X.dtype)
    eng = WarmStartEngine(gp.config.mll_config(),
                          WarmStartConfig(enabled=True, refresh_every=2))
    obs.enable_tracing(None)
    try:
        for i in range(2):
            eng.step(X, y, params, jax.random.PRNGKey(i))
    finally:
        obs.disable_tracing(snapshot_metrics=False)
        events = obs.drain_events()
    rows = phase_model_comparison(events)
    phases = {r["phase"] for r in rows}
    assert {"cg_solve", "eq2_backward"} <= phases
    assert all(r["measured_ms"] > 0 for r in rows)
    assert all(r["modeled_gb"] >= 0 for r in rows)
    # the engine's telemetry carries the same measured split
    t = eng.telemetry[-1]
    assert "measured_phase_ms" in t
    assert set(t["measured_phase_ms"]) == \
        {"precond_build", "cg_solve", "slq_logdet", "eq2_backward"}
    snap = obs.registry().snapshot()
    assert snap["phase.cg_solve_ms"]["count"] == 2


def test_phase_costs_sum_to_step_cost():
    kw = dict(backend="partitioned", row_block=256)
    phases = obs.mll_phase_costs(1024, 4, 5, 20, **kw)
    full = obs.mll_step_cost(1024, 4, 5, 20, **kw)
    assert set(phases) == {"precond_build", "cg_solve", "slq_logdet",
                           "eq2_backward"}
    assert phases["cg_solve"].hbm_bytes + phases["eq2_backward"].hbm_bytes \
        == pytest.approx(full.hbm_bytes)
    assert phases["cg_solve"].launches + phases["eq2_backward"].launches \
        == full.launches
    # rank-50 preconditioner build prices its slab touches
    withp = obs.mll_phase_costs(1024, 4, 5, 20, precond_rank=50, **kw)
    assert withp["precond_build"].hbm_bytes > 0


def test_collective_microbench_single_device_degrades():
    from repro.obs.measure import collective_microbench, \
        format_collective_bench

    rows = collective_microbench()
    if jax.device_count() == 1:
        assert rows == []
        assert "single device" in format_collective_bench(rows)
    else:
        assert rows and all(r["achieved_gbps"] > 0 for r in rows)
