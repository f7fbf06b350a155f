"""What every kind of cell shares: the run's context, compile accounting,
host annotations, device facts and the correctness checks' record."""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Context(NamedTuple):
    workload: dict     # the cell's entry of BENCHMARK.json
    config: dict       # chipbench/configs/<config>.json
    traffic: dict      # chipbench/traffic/<traffic>.json
    limits: dict       # chipbench/limits/<workload>.json
    seed: int
    seconds: float
    trace: bool
    t_start: float     # perf_counter at process start
    devices: list      # the chips this cell uses
    trace_dir: str     # where a traced run's profile goes


def log(msg: str) -> None:
    print(msg, flush=True)


def inv_softplus(x: float) -> float:
    """The raw value whose softplus is x > 0."""
    import math

    return x + math.log(-math.expm1(-x))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reference(name: str):
    """A configuration's plain reference: chipbench/reference/<name>.py."""
    import importlib

    return importlib.import_module(f"chipbench.reference.{name}")


class CompileClock:
    """Seconds of XLA compilation and compile events, from jax.monitoring.
    `mark()` starts counting the window's compilations, which should be 0."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._mark = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def mark(self) -> None:
        self._mark = self.compiles

    @property
    def since_mark(self) -> int:
        return self.compiles - self._mark


def annotate(name: str, on: bool):
    """A profiler annotation `bench.<name>` in a traced run, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


class Checks:
    """Numbers compared against their limits; each limit comes from
    chipbench/limits/<workload>.json."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows: list[tuple[str, float, float]] = []

    def record(self, name: str, value: float) -> None:
        limit = float(self.limits[name]["limit"])
        self.rows.append((name, float(value), limit))

    @property
    def ok(self) -> bool:
        import math

        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print_stderr(self) -> None:
        for n, v, lim in self.rows:
            ok = v <= lim
            print(f"check {n} = {v!r} limit {lim!r} "
                  f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)


def is_correct(out: dict) -> bool:
    """A run is correct when every number compared is within its limit and
    no step or request failed."""
    return out["checks"].ok and out["failed"] == 0


class Profile:
    """The window under jax.profiler in a traced run, and the window's
    annotation either way."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.path = ctx.trace_dir if ctx.trace else None

    def __enter__(self):
        import jax

        if self.ctx.trace:
            jax.profiler.start_trace(self.path)
        self._ann = annotate("window", self.ctx.trace)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._ann.__exit__(*exc)
        if self.ctx.trace:
            jax.profiler.stop_trace()
        return False
