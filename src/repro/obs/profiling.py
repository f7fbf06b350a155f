"""`jax.profiler` integration for the GP spine.

Host spans (`repro.obs.trace`) answer "which phase took how long"; this
module answers "what did the DEVICE do inside that phase":

* `named_scope(name)` — an HLO name scope around a jit-path phase
  (`precond_build`, `pcg`, `pcg.matvec`, `slq_logdet`, `eq2_backward`,
  `kmvm.prep`). Always on: a scope is op_name metadata on the ops it
  encloses and changes no op, fusion or number, so the profiled program
  and the timed one are the same program, and a device trace attributes
  every op to its phase by its op_name.
* `step_annotation(step)` — `jax.profiler.StepTraceAnnotation` around
  each trainer step, so TensorBoard's trace viewer groups device ops by
  optimizer step (`repro.train.gp_trainer` wraps its full-data steps).
* `memory_snapshot(tag)` — device memory stats at stage boundaries,
  recorded as `mem.<device_kind>.bytes_in_use` gauges plus a Chrome
  counter event in the active trace (CPU backends without memory_stats
  degrade to a silent no-op).

The last two are opt-in (`enable_profiling()`, or `REPRO_OBS_PROFILE=1`
in the environment) and no-ops otherwise. Host spans reach the profiler's
timeline by themselves while it collects (`repro.obs.trace.span`).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any

import jax

from . import metrics, trace

_ENABLED = False
_NULL = contextlib.nullcontext()


def profiling_enabled() -> bool:
    return _ENABLED


def enable_profiling() -> None:
    global _ENABLED
    _ENABLED = True


def disable_profiling() -> None:
    global _ENABLED
    _ENABLED = False


def step_annotation(step: int):
    """StepTraceAnnotation for one trainer step (TensorBoard step grouping)."""
    if not _ENABLED:
        return _NULL
    return jax.profiler.StepTraceAnnotation("train_step", step_num=step)


def named_scope(name: str):
    """HLO name scope for jit-path code — `pcg`/`mll` wrap their phases."""
    return jax.named_scope(name)


def memory_snapshot(tag: str) -> dict[str, Any]:
    """Record per-device memory stats at a stage boundary.

    Returns {device_label: bytes_in_use} (empty when the backend exposes
    no stats — CPU). Gauges: `mem.<tag>.<device_label>.bytes_in_use`;
    also emits a Chrome counter event into any active trace.
    """
    if not _ENABLED:
        return {}
    out: dict[str, Any] = {}
    for dev in jax.local_devices():
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            pass
        if not stats:
            continue
        label = f"{dev.platform}{dev.id}"
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            continue
        out[label] = in_use
        metrics.gauge(f"mem.{tag}.{label}.bytes_in_use").set(int(in_use))
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            metrics.gauge(f"mem.{tag}.{label}.peak_bytes").set(int(peak))
    if out:
        trace.counter_event(f"mem.{tag}", **out)
    return out


_env = os.environ.get("REPRO_OBS_PROFILE")
if _env and _env not in ("0", "false", "False"):
    enable_profiling()
