"""What a device profile of the training step can read, with nothing
switched on.

  * the phase scopes are always in the programs: the lowered HLO of the
    sharded cold, warm and refresh steps carries `pcg`, `eq2_backward` and
    (where the step builds a preconditioner) `precond_build` in its
    op_name metadata, and the fused kernel's operand preparation carries
    `kmvm.prep`;
  * while `jax.profiler` collects, an `obs.span` is a `repro.<name>` event
    on the host timeline with its attrs as stats, and with neither the
    profiler nor the JSONL sink on it is the shared null span;
  * `PCGResult.traversals` counts the operator applications the solve
    executed (checked against a count the matvec itself keeps), and the
    engine puts it, with the most iterations any column applied, on its
    `mll_step` span and into its telemetry.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import init_params_for
from repro.core.pcg import pcg

N, D = 96, 3
MAX_ITERS = 6


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs.registry().reset()
    yield
    obs.disable_tracing(snapshot_metrics=False)
    obs.drain_events()
    obs.registry().reset()


def _data(rng, n=N, d=D):
    X = jnp.asarray(rng.normal(size=(n, d)))
    y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                    + 0.1 * rng.normal(size=n))
    return X, y


def _dist_setup(rng, max_iters=MAX_ITERS):
    from repro.core.distributed import (DistMLLConfig, make_geometry,
                                        make_warm_mll_step, replicate,
                                        shard_vector)
    from repro.launch.mesh import make_host_mesh

    X, y = _data(rng)
    mesh = make_host_mesh(data=1, model=1)
    geom = make_geometry(mesh, N, D, mode="2d", row_block=48)
    cfg = DistMLLConfig(precond_rank=10, num_probes=4,
                        max_cg_iters=max_iters, cg_tol=1.0)
    params = init_params_for("matern32", noise=0.3, dtype=X.dtype)
    args = (replicate(mesh, X), shard_vector(mesh, geom, y),
            replicate(mesh, params), jax.random.PRNGKey(0))
    return mesh, geom, cfg, args, make_warm_mll_step(mesh, geom, cfg)


def _op_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


# ---------------------------------------------------------------------------
# phase scopes, always on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,scopes", [
    ("cold", ("pcg", "precond_build", "eq2_backward")),
    ("warm", ("pcg", "eq2_backward")),
    ("refresh", ("pcg", "precond_build", "eq2_backward")),
])
def test_sharded_step_hlo_carries_phase_scopes(rng, mode, scopes):
    assert not obs.profiling_enabled()
    assert "REPRO_OBS_PROFILE" not in os.environ
    _, _, _, args, fns = _dist_setup(rng)
    state = fns.cold(*args)[3]
    extra = () if mode == "cold" else (state,)
    text = _op_names(getattr(fns, mode).lower(*args, *extra))
    for scope in scopes:
        assert f"/{scope}/" in text, (mode, scope)
    # a warm step reuses the carried preconditioner: nothing builds one
    assert ("/precond_build/" in text) == ("precond_build" in scopes)


@pytest.mark.parametrize("entry", ["kmvm_block", "kmvm_fused_matmat"])
def test_kernel_operand_preparation_carries_its_scope(rng, entry):
    from repro.kernels import ops

    X, _ = _data(rng, n=64)
    V = jnp.asarray(rng.normal(size=(64, 2)))
    params = init_params_for("matern32", noise=0.3, dtype=X.dtype)
    if entry == "kmvm_block":
        def fn(X, V):
            return ops.kmvm_block("matern32", X, X, V, params, bm=32, bn=32,
                                  interpret=True)
    else:
        def fn(X, V):
            return ops.kmvm_fused_matmat("matern32", X, V, V, params, bm=32,
                                         bn=32, interpret=True)[0]
    text = _op_names(jax.jit(fn).lower(X, V))
    assert "/kmvm.prep/" in text


# ---------------------------------------------------------------------------
# host spans on the profiler's timeline
# ---------------------------------------------------------------------------


def _host_events(logdir, prefix):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out += [(e.name, dict(e.stats), e.start_ns, e.end_ns)
                    for e in line.events if e.name.startswith(prefix)]
    return out


def test_span_is_a_profiler_event_with_its_stats(tmp_path):
    assert not obs.tracing_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer", mode="warm") as sp:
            with obs.span("outer.inner"):
                pass
            sp.set(cg_iters_max=3, traversals=21, drift=0.25)
    finally:
        jax.profiler.stop_trace()
    events = {name: (stats, s, e)
              for name, stats, s, e in _host_events(str(tmp_path), "repro.")}
    assert set(events) == {"repro.outer", "repro.outer.inner"}
    stats, s, e = events["repro.outer"]
    assert stats["mode"] == "warm" and stats["cg_iters_max"] == 3
    assert stats["traversals"] == 21 and stats["drift"] == 0.25
    _, s_in, e_in = events["repro.outer.inner"]
    assert s <= s_in <= e_in <= e
    # the profiler alone writes nothing to the JSONL sink
    assert obs.drain_events() == []


def test_span_is_the_null_singleton_with_profiler_and_sink_off():
    assert not obs.tracing_enabled()
    assert obs.span("a") is obs.span("b")
    with obs.span("a") as sp:
        assert sp.set(x=1) is sp


# ---------------------------------------------------------------------------
# the solver's traversal counter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["standard", "pipelined"])
@pytest.mark.parametrize("warm", [False, True])
def test_pcg_traversals_count_the_matvecs_executed(rng, method, warm):
    n, t = 48, 3
    A = rng.normal(size=(n, n))
    K = jnp.asarray(A @ A.T / n + np.eye(n))
    B = jnp.asarray(rng.normal(size=(n, t)))
    x0 = jnp.asarray(rng.normal(size=(n, t))) if warm else None
    calls = []

    def mvm(v):
        # the matvec counts itself on the host, once per execution
        jax.debug.callback(lambda: calls.append(1))
        return K @ v

    res = jax.jit(lambda B, x0: pcg(mvm, B, max_iters=MAX_ITERS,
                                    method=method, x0=x0))(B, x0)
    jax.block_until_ready(res.solution)
    # the loop leaves once no column is active: the standard loop learns
    # it from the iteration's own MVM (one body more than the most
    # iterations a column applied), the pipelined loop before launching
    # it; a warm start's residual and the pipelined pre-loop MVM add one
    most = int(np.max(np.asarray(res.iterations)))
    if method == "standard":
        expect = min(most + 1, MAX_ITERS) + warm
    else:
        expect = most + 1 + warm
    assert res.traversals.dtype == jnp.int32
    assert int(res.traversals) == len(calls) == expect


def test_traversals_reach_the_mll_step_span_and_telemetry(rng):
    from repro.train.solver_state import DistWarmStartEngine, WarmStartConfig

    mesh, geom, cfg, args, _ = _dist_setup(rng)
    X, y, _, key = args
    params = init_params_for("matern32", noise=0.3, dtype=jnp.float64)
    eng = DistWarmStartEngine(mesh, geom, cfg,
                              WarmStartConfig(enabled=True, refresh_every=2,
                                              drift_threshold=10.0))
    obs.enable_tracing(None)
    auxes = [eng.step(X, y, params, key)[1] for _ in range(3)]
    obs.disable_tracing(snapshot_metrics=False)
    events = [e for e in obs.drain_events() if e.get("ph") == "X"]
    steps = [e for e in events if e["name"] == "mll_step"]
    modes = [t["mode"] for t in eng.telemetry]
    assert modes == ["cold", "warm", "refresh"]
    # the standard loop leaves after the first iteration in which no
    # column was active (at most max_iters bodies); a warm start's
    # residual B - K x0 is one traversal more
    most = [int(np.max(np.asarray(a.cg_iterations))) for a in auxes]
    expect = [min(it + 1, MAX_ITERS) + (m != "cold")
              for it, m in zip(most, modes)]
    assert [int(a.traversals) for a in auxes] == expect
    assert [t["traversals"] for t in eng.telemetry] == expect
    assert [e["args"]["traversals"] for e in steps] == expect
    # what the exit left out of the whole trip count
    saved = [MAX_ITERS + (m != "cold") - e for m, e in zip(modes, expect)]
    assert [t["traversals_saved"] for t in eng.telemetry] == saved
    assert [e["args"]["traversals_saved"] for e in steps] == saved
    for t, e, aux in zip(eng.telemetry, steps, auxes):
        most = int(np.max(np.asarray(aux.cg_iterations)))
        assert t["cg_iters_max"] == e["args"]["cg_iters_max"] == most
        assert e["args"]["mode"] == t["mode"]
    snap = obs.registry().snapshot()
    assert snap["cg.traversals"] == sum(expect)
    assert snap["cg.traversals_saved"] == sum(saved)
    # each step's three children lie inside its span
    for name in ("mll_step.dispatch", "mll_step.wait",
                 "mll_step.bookkeeping"):
        kids = [e for e in events if e["name"] == name]
        assert len(kids) == 3
        for k, s in zip(kids, steps):
            assert s["ts"] <= k["ts"] and \
                k["ts"] + k["dur"] <= s["ts"] + s["dur"] + 1e-3
