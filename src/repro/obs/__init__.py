"""`repro.obs` — unified tracing, metrics, and profiling for the GP spine.

One observability surface for the three questions the paper's timing
claims force: where did this solve spend its WALL CLOCK (span tracing ->
`repro.launch.obs_report` per-phase tables), what did it COUNT (metrics
registry: CG iterations, step modes, autotune hits, sparsity fill, serve
batch distributions), and what did the DEVICE do (the jax.profiler
bridge: HLO phase scopes, always on, and host spans that reach the
profiler's timeline while it collects). See the submodule docstrings for
the contracts; the headline one: everything here is a strict no-op on
the default path — tracing off means identity-wrapped functions and zero
events, metrics touch only host code after `block_until_ready`, and
nothing ever runs inside jit (device values arrive via returned aux).

    from repro import obs
    with obs.trace_session("trace.jsonl"):
        fit_exact_gp(...)
    # then: python -m repro.launch.obs_report trace.jsonl

v2 adds the measurement plane: `measure` (measured-vs-modeled per-phase
comparison + timed-collective micro-harness), `health` (solver health
events: CG stagnation/divergence/NaN sentinels, preconditioner staleness,
replans), and `regress` (noise-aware BENCH-JSON diffing behind
`launch/obs_diff`, the CI perf gate).

Env knobs: REPRO_OBS_TRACE=<path.jsonl> (enable span tracing),
REPRO_OBS_PROFILE=1 (enable jax.profiler step annotations + memory gauges),
REPRO_OBS_HEALTH=<path.jsonl> (enable the solver health-event sink).
"""

from . import health
from . import measure
from . import regress
from .costmodel import (
    CollectiveCost,
    StepCost,
    dist_collective_cost,
    mll_phase_costs,
    mll_step_cost,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    SLOTracker,
    counter,
    gauge,
    histogram,
    latency_summary,
    record_solver_step,
    registry,
    slo,
)
from .profiling import (
    disable_profiling,
    enable_profiling,
    memory_snapshot,
    named_scope,
    profiling_enabled,
    step_annotation,
)
from .trace import (
    complete_event,
    counter_event,
    disable_tracing,
    drain_events,
    enable_tracing,
    instant,
    maybe_wrap,
    next_request_id,
    span,
    trace_session,
    tracing_enabled,
)

__all__ = [
    "health", "measure", "regress",
    "CollectiveCost", "StepCost", "dist_collective_cost",
    "mll_phase_costs", "mll_step_cost",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SLOTracker",
    "counter", "gauge", "histogram", "latency_summary",
    "record_solver_step", "registry", "slo",
    "disable_profiling", "enable_profiling", "memory_snapshot",
    "named_scope", "profiling_enabled", "step_annotation",
    "complete_event", "counter_event", "disable_tracing", "drain_events",
    "enable_tracing", "instant", "maybe_wrap", "next_request_id", "span",
    "trace_session", "tracing_enabled",
]
