"""Span-based structured tracing with a zero-overhead disabled path.

The paper's headline claim is a *time* claim (1M+ points, < 2h), and its
evidence is timing decompositions (Table 2, Fig. 2). This module is the
measurement half of reproducing that: host-side spans around every phase
of the solver/trainer/serve paths, emitted as Chrome-trace-event-
compatible JSONL that `repro.launch.obs_report` turns into a per-phase
breakdown table.

Design constraints (all load-bearing):

* **Zero overhead when disabled.** `span()` with tracing and the
  profiler off returns a shared no-op singleton — no allocation, no time
  syscall, no lock.
  `maybe_wrap(name, fn)` returns `fn` ITSELF (identity) when tracing is
  off at wrap time, so wrapped hot paths pay literally nothing. The
  default state is disabled; nothing in the repo flips it implicitly.
* **Host-side only.** Spans time host wall-clock between `block_until_
  ready` fences. Nothing here runs inside jit — device-side accounting
  travels through returned aux (PCGResult.iterations, MLLAux) and is
  recorded into the metrics registry AFTER the step completes. No host
  callbacks, no retraces, no numerics changes (pinned by
  tests/test_obs.py).
* **On the profiler's clock too.** While `jax.profiler` collects, a span
  also opens a `jax.profiler.TraceAnnotation` named `repro.<name>` whose
  stats are the span's attrs (`set()` forwards them), so the host's phases
  lie on the same timeline as the device ops of a profile. Whether the
  profiler collects is one `TraceMe.is_enabled()` call; with neither it
  nor the JSONL sink on, `span()` is still the shared null singleton.
* **Chrome-compatible events.** One JSON object per line; each span is a
  complete ("ph": "X") event with microsecond ts/dur, pid/tid, and an
  `args` dict. Nesting is implicit in ts/dur containment per tid (how
  Chrome infers stacks), which `obs.report` exploits for self-time
  attribution. `jq -s . trace.jsonl > trace.json` yields a file
  chrome://tracing / Perfetto loads directly.

Enable programmatically (`enable_tracing(path)` / `trace_session(path)`)
or via the environment: `REPRO_OBS_TRACE=/path/to/trace.jsonl` turns
tracing on at import for any entry point (launchers, benchmarks, CI) with
an atexit flush; SIGINT/SIGTERM handlers (chained onto any existing ones)
flush the sink too, so a killed serve process keeps its buffered tail.
`disable_tracing()` appends a final metrics-registry snapshot event so one
file carries the whole observation.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import signal
import threading
import time
from typing import Any

from jax.profiler import TraceAnnotation

# the name prefix of a span on the profiler's timeline
PROFILER_PREFIX = "repro."


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


class _TraceState:
    """Process-global sink. `enabled` is the ONLY thing the fast path reads."""

    def __init__(self):
        self.enabled = False
        self.path: str | None = None
        self.events: list[dict] = []     # buffered events (in-memory mode)
        self.lock = threading.Lock()
        self._file = None
        self._atexit_registered = False
        self._signals_hooked = False
        self._prev_handlers: dict[int, Any] = {}


_STATE = _TraceState()


class _NullSpan:
    """The disabled-mode span: a reusable, stateless no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):  # matches _Span.set
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """An open span; emits one complete event on exit to the JSONL sink
    (when on) and is a profiler annotation (when the profiler collects)."""

    __slots__ = ("name", "args", "_t0", "_tm")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self._tm = (TraceAnnotation(PROFILER_PREFIX + name, **args)
                    if TraceAnnotation.is_enabled() else None)
        self._t0 = _now_us()

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. iteration counts
        known only after block_until_ready)."""
        self.args.update(attrs)
        if self._tm is not None:
            self._tm.set_metadata(**attrs)
        return self

    def __enter__(self):
        if self._tm is not None:
            self._tm.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_us()
        if self._tm is not None:
            self._tm.__exit__(exc_type, exc, tb)
        if not _STATE.enabled:
            return False
        _emit({
            "name": self.name,
            "ph": "X",
            "ts": self._t0,
            "dur": t1 - self._t0,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": self.args,
        })
        return False


def _emit(event: dict) -> None:
    st = _STATE
    with st.lock:
        if not st.enabled:
            return
        if st._file is not None:
            st._file.write(json.dumps(event) + "\n")
        else:
            st.events.append(event)


def tracing_enabled() -> bool:
    return _STATE.enabled


def span(name: str, **attrs: Any):
    """Context manager timing a named phase. No-op singleton when neither
    the JSONL sink nor the profiler is on.

    Usage: `with obs.span("mll_step", mode="warm") as sp: ...;
    sp.set(cg_iters=7)` — attrs land in the event's `args`, and in the
    stats of the profiler's `repro.mll_step` event.
    """
    if not _STATE.enabled and not TraceAnnotation.is_enabled():
        return _NULL_SPAN
    return _Span(name, attrs)


def instant(name: str, **attrs: Any) -> None:
    """A zero-duration marker event (Chrome "i" phase)."""
    if not _STATE.enabled:
        return
    _emit({"name": name, "ph": "i", "ts": _now_us(), "s": "t",
           "pid": os.getpid(), "tid": threading.get_ident(), "args": attrs})


def counter_event(name: str, **values: float) -> None:
    """A Chrome counter ("C") sample — e.g. device memory at a boundary."""
    if not _STATE.enabled:
        return
    _emit({"name": name, "ph": "C", "ts": _now_us(), "pid": os.getpid(),
           "args": values})


def complete_event(name: str, ts_us: float, dur_us: float,
                   tid: int | str | None = None, **attrs: Any) -> None:
    """Emit a complete ("X") event retroactively from recorded timestamps.

    Live `_Span`s stamp `tid` with the emitting thread, which is right for
    phase nesting but wrong for logical flows that HOP threads (a serve
    request crosses the caller thread, the scheduler, and a worker).
    Request-scoped tracing records (ts, dur) pairs as the request moves and
    emits them here on completion, onto a synthetic per-request `tid` so
    ts/dur containment reconstructs the request's queue/solve stack without
    polluting any real thread's phase attribution.
    """
    if not _STATE.enabled:
        return
    _emit({"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
           "pid": os.getpid(),
           "tid": threading.get_ident() if tid is None else tid,
           "args": attrs})


_REQUEST_IDS = itertools.count(1)


def next_request_id() -> str:
    """Mint a process-unique serve request ID ("r1", "r2", ...)."""
    return f"r{next(_REQUEST_IDS)}"


def maybe_wrap(name: str, fn):
    """Span-wrap `fn` — IDENTITY (returns `fn` itself) when tracing is
    disabled at wrap time, so instrumented call sites are free by default.
    """
    if not _STATE.enabled:
        return fn

    def wrapped(*a, **kw):
        with span(name):
            return fn(*a, **kw)

    wrapped.__name__ = getattr(fn, "__name__", name)
    wrapped.__wrapped__ = fn
    return wrapped


def enable_tracing(path: str | None = None) -> None:
    """Turn the sink on. `path` streams JSONL lines to a file (parent dirs
    created); None buffers events in memory (`drain_events`/tests)."""
    st = _STATE
    with st.lock:
        if st._file is not None:
            st._file.close()
            st._file = None
        st.path = path
        st.events = []
        if path is not None:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            st._file = open(path, "w")
        st.enabled = True
        if not st._atexit_registered:
            atexit.register(_atexit_flush)
            st._atexit_registered = True
    _hook_signals()


def disable_tracing(snapshot_metrics: bool = True) -> str | None:
    """Flush + close the sink; returns the trace path (None for memory
    mode). Appends a final `repro.metrics` metadata event carrying the
    metrics-registry snapshot, so one JSONL file holds spans AND counters
    (obs_report reads both)."""
    st = _STATE
    if not st.enabled:
        return st.path
    if snapshot_metrics:
        from . import metrics as _metrics  # local: avoid import cycle

        snap = _metrics.registry().snapshot()
        if snap:
            _emit({"name": "repro.metrics", "ph": "M", "ts": _now_us(),
                   "pid": os.getpid(), "args": snap})
    with st.lock:
        st.enabled = False
        if st._file is not None:
            st._file.close()
            st._file = None
    return st.path


def drain_events() -> list[dict]:
    """Memory-mode accessor: pop and return all buffered events."""
    st = _STATE
    with st.lock:
        ev, st.events = st.events, []
        return ev


class trace_session:
    """`with trace_session(path): ...` — enable, run, flush-and-close."""

    def __init__(self, path: str | None):
        self.path = path

    def __enter__(self):
        enable_tracing(self.path)
        return self

    def __exit__(self, *exc):
        disable_tracing()
        return False


def _atexit_flush() -> None:
    try:
        disable_tracing()
    except Exception:
        pass


def _signal_flush(signum, frame) -> None:
    """Flush the sink, then defer to whatever handler was installed before
    us (KeyboardInterrupt for SIGINT, process death for SIGTERM). atexit
    does not run when a process dies on an unhandled SIGTERM, so without
    this a killed `serve_gp` loses the buffered tail of its trace."""
    _atexit_flush()
    prev = _STATE._prev_handlers.get(signum)
    if callable(prev):
        prev(signum, frame)
    else:
        # SIG_DFL / SIG_IGN / None: restore and re-raise so the default
        # semantics (exit code 128+signum, shell job control) still apply.
        signal.signal(signum, signal.SIG_DFL)
        signal.raise_signal(signum)


def _hook_signals() -> None:
    """Install flushing SIGINT/SIGTERM handlers, chaining the existing
    ones. Only possible from the main thread (signal.signal raises
    ValueError elsewhere) — atexit still covers those callers."""
    st = _STATE
    if st._signals_hooked:
        return
    try:
        for signum in (signal.SIGINT, signal.SIGTERM):
            st._prev_handlers[signum] = signal.signal(signum, _signal_flush)
        st._signals_hooked = True
    except ValueError:
        pass


# Environment hook: REPRO_OBS_TRACE=path enables tracing for any entry
# point without code changes (launchers, benchmarks, CI nightly).
_env_path = os.environ.get("REPRO_OBS_TRACE")
if _env_path:
    enable_tracing(_env_path)
