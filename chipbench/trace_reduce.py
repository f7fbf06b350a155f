"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace is the `.xplane.pb` that `jax.profiler` writes for the measured
window. Of it the reduction keeps three things:

* the device operations, per chip: the events of the line named
  `XLA Ops` on each `/device:TPU:<i>` plane (name, start, end, in ns).
  An event's name there is the HLO instruction's text
  (`%kmvm_pallas.13 = f32[1024,128]{...} custom-call(...)`); the
  reduction keeps the instruction's name (`kmvm_pallas.13`) and its result
  shape (`f32[1024,128]`). Control flow (`while`, `conditional`, `call`)
  is dropped: its event spans the operations of its body, which have
  events of their own. The asynchronous operations of the line `Async XLA Ops`
  (collectives and copies in flight) are kept apart;
* the benchmark's own host annotations: events whose name starts with
  `bench.` on any host line (the window itself is `bench.window`, and
  steps, optimizer updates, request submissions and replies have their
  own);
* nothing else. The program's own spans are not read.

From those it computes, per chip and then averaged over the chips:

* busy time: the length of the union of the device operations' intervals
  inside the window (operations of one chip may overlap, so a plain sum
  would count some time twice);
* idle share: 1 - busy / window;
* the summed device time of the operations whose name matches a pattern
  (a kernel's time);
* exposed collective time: the part of the collectives' intervals (leaf or
  asynchronous) during which no other leaf operation runs on that chip;
* the breakdown: the operations that took most device time, and the
  longest idle gaps, each named after the innermost benchmark annotation
  that was open on the host at the gap's middle.

Everything here is plain Python over lists of intervals, so it is tested
on hand-built traces (`chipbench/tests/test_trace_reduce.py`).
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"
ASYNC_LINE = "Async XLA Ops"
# the program's fused Pallas kernels (repro.kernels.kmvm: `kmvm_pallas`,
# `kmvm_pallas_dots`, `kmvm_pallas_chunk`), named after their jitted entry
KMVM = re.compile(r"^kmvm")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")
_HLO = re.compile(r"^%?([^\s=]+)(?:\s*=\s*(\S+))?")
_ROWS = re.compile(r"^\w+\[(\d+)")


class Event(NamedTuple):
    name: str
    start: int       # ns
    end: int         # ns
    shape: str = ""  # result shape of a device operation, e.g. f32[8,128]


class Trace(NamedTuple):
    devices: dict      # chip index -> [Event] leaf device operations
    annotations: list  # [Event] benchmark host annotations
    window: Event      # the measured window (bench.window)
    asyncs: dict = {}  # chip index -> [Event] asynchronous operations


def hlo_event(text: str, start: int, end: int) -> Event:
    """An Event from an `XLA Ops` event: instruction name and shape."""
    m = _HLO.match(text)
    name, shape = (m.group(1), m.group(2) or "") if m else (text, "")
    return Event(name, start, end, shape)


def leading_dim(ev: Event) -> int | None:
    """The first dimension of an operation's result (its rows), if any."""
    m = _ROWS.match(ev.shape)
    return int(m.group(1)) if m else None


def leaves(events) -> list:
    """The events left once control flow is dropped: a `while`, a
    `conditional` or a `call` spans the operations of its body, which have
    events of their own."""
    return sorted((e for e in events if not CONTROL.match(e.name)),
                  key=lambda e: e.start)


# -- reading ----------------------------------------------------------------


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an .xplane.pb (or the directory the profiler wrote)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    asyncs: dict[int, list] = {}
    annotations: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                into = {OPS_LINE: devices, ASYNC_LINE: asyncs}.get(line.name)
                if into is None:
                    continue
                into.setdefault(int(m.group(1)), []).extend(
                    hlo_event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif plane.name.startswith("/host"):
                annotations.extend(
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX))
    return from_events(devices, annotations, asyncs)


def from_events(devices: dict, annotations: list,
                asyncs: dict | None = None) -> Trace:
    windows = [a for a in annotations if a.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found "
                         f"{len(windows)}")
    return Trace({k: leaves(v) for k, v in devices.items()},
                 sorted(annotations, key=lambda e: e.start), windows[0],
                 {k: sorted(v, key=lambda e: e.start)
                  for k, v in (asyncs or {}).items()})


# -- interval arithmetic ----------------------------------------------------


def clip(events, lo: int, hi: int) -> list:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(e._replace(start=s, end=t))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[int]] = []
    for s, t in sorted((e[1], e[2]) if isinstance(e, Event) else e
                       for e in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def length(intervals) -> int:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the disjoint sorted intervals `a` not covered by `b`."""
    b = union(b)
    out = []
    for s, t in a:
        cur = s
        for bs, bt in b:
            if bt <= cur or bs >= t:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, bt)
        if cur < t:
            out.append((cur, t))
    return out


# -- per-chip numbers -------------------------------------------------------


def window_ops(trace: Trace) -> dict:
    w = trace.window
    return {k: clip(v, w.start, w.end) for k, v in trace.devices.items()}


def window_s(trace: Trace) -> float:
    return (trace.window.end - trace.window.start) * 1e-9


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the chips that ran."""
    ops = window_ops(trace)
    if not ops:
        return 0.0
    return sum(length(union(v)) for v in ops.values()) * 1e-9 / len(ops)


def idle_share(trace: Trace) -> float | None:
    ops = window_ops(trace)
    if not ops or window_s(trace) <= 0:
        return None
    return 1.0 - busy_s(trace) / window_s(trace)


def op_time_s(trace: Trace, pattern: re.Pattern) -> dict[int, float]:
    """Per chip: summed device seconds of the window's operations whose
    name matches `pattern`."""
    return {k: sum(e.end - e.start for e in v if pattern.search(e.name))
            * 1e-9 for k, v in window_ops(trace).items()}


def op_events(trace: Trace, pattern: re.Pattern) -> dict[int, list]:
    """Per chip: the window's operations whose name matches `pattern`."""
    return {k: [e for e in v if pattern.search(e.name)]
            for k, v in window_ops(trace).items()}


def op_rows(trace: Trace, pattern: re.Pattern) -> dict[int, int]:
    """Per chip: the summed leading dimension (rows) of the results of the
    window's operations whose name matches `pattern`."""
    return {k: sum(leading_dim(e) or 0 for e in v if pattern.search(e.name))
            for k, v in window_ops(trace).items()}


def exposed_collective_s(trace: Trace,
                         pattern: re.Pattern = COLLECTIVE) -> dict[int, float]:
    """Per chip: seconds in which a collective (a leaf operation, or an
    asynchronous one in flight) runs and no other leaf operation does."""
    w = trace.window
    out = {}
    for k, v in window_ops(trace).items():
        flying = clip(trace.asyncs.get(k, []), w.start, w.end)
        coll = union([e for e in v if pattern.search(e.name)]
                     + [e for e in flying if pattern.search(e.name)])
        other = [e for e in v if not pattern.search(e.name)]
        out[k] = length(subtract(coll, other)) * 1e-9
    return out


# -- breakdown --------------------------------------------------------------


def _host_activity(trace: Trace, t: int) -> str:
    """The innermost benchmark annotation open at time t (the window when
    no other is)."""
    best = None
    for a in trace.annotations:
        if a.start <= t < a.end and (best is None or
                                     a.end - a.start < best.end - best.start):
            best = a
    return best.name if best is not None else "outside"


def idle_gaps(trace: Trace, chip: int | None = None) -> list:
    """[(host activity, seconds)] of every idle gap in the window on one
    chip (the lowest index by default), longest first."""
    ops = window_ops(trace)
    if not ops:
        return []
    chip = min(ops) if chip is None else chip
    w = trace.window
    gaps = subtract([(w.start, w.end)], ops[chip])
    named = [(_host_activity(trace, (s + t) // 2), (t - s) * 1e-9)
             for s, t in gaps]
    return sorted(named, key=lambda g: -g[1])


def top_ops(trace: Trace, k: int = 10) -> list:
    """[(operation name, device seconds averaged over chips)] of the k
    operations that took most device time in the window."""
    ops = window_ops(trace)
    if not ops:
        return []
    total: dict[str, float] = {}
    for v in ops.values():
        for e in v:
            total[e.name] = total.get(e.name, 0.0) + (e.end - e.start) * 1e-9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs / len(ops)] for name, secs in ranked]


def breakdown(trace: Trace, k: int = 10) -> dict:
    return {"device_ops": top_ops(trace, k),
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace)[:k]]}
