"""Read the program's own instrumentation from a traced run's profile.

`trace_reduce` reads the device operations by name and the benchmark's
`bench.*` annotations. This module reads what the program itself puts in
the same `.xplane.pb`:

* each device operation's scope path: the op_name metadata of its HLO
  instruction, which the profiler keeps as the `tf_op` stat of the
  operation's event metadata on the device plane, e.g.
  `jit(local_warm)/pcg/while/body/closed_call/pcg.matvec/.../kmvm.prep/pad:`.
  Its components are the `jax.named_scope`s the program opens
  (`repro.obs.named_scope`): `precond_build`, `pcg`, `pcg.matvec`,
  `slq_logdet`, `eq2_backward` and `kmvm.prep`;
* the program's host spans: the events named `repro.<span>` on the host
  planes (`repro.obs.span` opens one while the profiler collects), with
  their stats (`mll_step` carries `cg_iters_max` and `traversals`).

`jax.profiler.ProfileData` gives an event's own stats but not its
metadata's, where `tf_op` lives, so the file is read here by a small
decoder of the protobuf wire format (the `XSpace` message of tsl's
`xplane.proto`), which needs nothing beyond the standard library. A file
is read once per path, however many readers ask. An event's times are
those `ProfileData` gives (line timestamp plus offset), so they compare
with `trace_reduce`'s.

Two log lines back the per-layer metrics: the share of the window's busy
device time that the program's phase scopes cover, and every idle gap of
1 ms or more in the window, cut into what the host was doing in it (the
innermost program span, else the benchmark annotation, open then).

Tested on hand-built traces and on a profile recorded on the CPU
(`chipbench/tests/test_program_trace.py`).
"""

from __future__ import annotations

import os
import struct
from typing import NamedTuple

from chipbench import trace_reduce
from chipbench.common import log

SPAN_PREFIX = "repro."
# the phase scopes whose device time the per-layer metrics read, and
# slq_logdet, which no metric reads but which the coverage counts
PHASES = ("pcg", "eq2_backward", "precond_build", "kmvm.prep", "slq_logdet")
GAP_S = 1e-3


class Op(NamedTuple):
    name: str        # HLO instruction name, e.g. pad.112
    start: int       # ns
    end: int         # ns
    scope: tuple     # op_name components, e.g. ("jit(local_warm)", "pcg", ...)


class Span(NamedTuple):
    name: str        # e.g. repro.mll_step
    start: int       # ns
    end: int         # ns
    stats: dict


class ProgramTrace(NamedTuple):
    devices: dict    # chip index -> [Op], control flow dropped, by start
    spans: list      # [Span] the program's host spans, by start


# -- the protobuf wire format -------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = b & 0x7F, 7
    i += 1
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, start: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) range for a length-delimited field, raw bytes for a
    fixed-width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            yield field, bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            yield field, bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf, r) -> str:
    return bytes(buf[r[0]:r[1]]).decode("utf-8", "replace")


def _stat(buf, r, stat_names: dict) -> tuple[str, object]:
    """(name, value) of an XStat; a reference names another stat's name."""
    key, value = None, None
    for f, v in _fields(buf, *r):
        if f == 1:
            key = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = bytes(buf[v[0]:v[1]])
        elif f == 7:
            value = stat_names.get(v, str(v))
    return key, value


def _map_entry(buf, r) -> tuple[int, tuple]:
    key, value = 0, None
    for f, v in _fields(buf, *r):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class _Plane:
    """The parts of one XPlane this module reads: its name, its lines'
    byte ranges, and its event and stat metadata."""

    def __init__(self, buf, r):
        self.buf = buf
        self.name = ""
        self.lines, meta_ranges, stat_ranges = [], [], []
        for f, v in _fields(buf, *r):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                self.lines.append(v)
            elif f == 4:
                meta_ranges.append(v)
            elif f == 5:
                stat_ranges.append(v)
        self.stat_names = {}
        for er in stat_ranges:
            k, v = _map_entry(buf, er)
            for f, x in _fields(buf, *v):
                if f == 2:
                    self.stat_names[k] = _text(buf, x)
        self.meta_ranges = meta_ranges

    def event_metadata(self, with_stats: bool) -> dict:
        """metadata id -> (name, {stat: value}), the stats decoded only
        when asked for."""
        buf, out = self.buf, {}
        for er in self.meta_ranges:
            k, v = _map_entry(buf, er)
            name, stat_ranges = "", []
            for f, x in _fields(buf, *v):
                if f == 2:
                    name = _text(buf, x)
                elif f == 5 and with_stats:
                    stat_ranges.append(x)
            out[k] = (name, dict(_stat(buf, sr, self.stat_names)
                                 for sr in stat_ranges))
        return out

    def line_events(self, keep_line, keep_event, with_stats: bool):
        """(metadata id, start ns, end ns, {stat: value}) of the events of
        the lines `keep_line(name)` and the metadata ids `keep_event(id)`
        accepts."""
        buf = self.buf
        for lr in self.lines:
            name, ts, events = "", 0, []
            for f, v in _fields(buf, *lr):
                if f == 2:
                    name = _text(buf, v)
                elif f == 3:
                    ts = _signed(v)
                elif f == 4:
                    events.append(v)
            if not keep_line(name):
                continue
            for er in events:
                mid, off, dur, stat_ranges = 0, 0, 0, []
                for f, v in _fields(buf, *er):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = _signed(v)
                    elif f == 3:
                        dur = _signed(v)
                    elif f == 4 and with_stats:
                        stat_ranges.append(v)
                if not keep_event(mid):
                    continue
                # whole ns, as ProfileData has them
                start = ts + off // 1000
                stats = dict(_stat(buf, sr, self.stat_names)
                             for sr in stat_ranges)
                yield mid, start, start + dur // 1000, stats


def _planes(buf):
    return [_Plane(buf, v) for f, v in _fields(buf, 0, len(buf)) if f == 1]


# -- reading -------------------------------------------------------------------


def scope_of(tf_op: str) -> tuple:
    """The op_name components of a `tf_op` stat (which ends in ':')."""
    return tuple(c for c in tf_op.rstrip(":").split("/") if c)


def parse(data: bytes) -> ProgramTrace:
    """The device operations with their scopes, and the program's host
    spans, of a serialized XSpace."""
    buf = memoryview(data)
    devices: dict[int, list] = {}
    spans: list = []
    for plane in _planes(buf):
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m is not None:
            meta = plane.event_metadata(with_stats=True)
            ops = devices.setdefault(int(m.group(1)), [])
            for mid, s, t, _ in plane.line_events(
                    lambda n: n == trace_reduce.OPS_LINE,
                    lambda i: True, with_stats=False):
                name, stats = meta.get(mid, ("", {}))
                ev = trace_reduce.hlo_event(name, s, t)
                ops.append(Op(ev.name, s, t,
                              scope_of(str(stats.get("tf_op", "")))))
        elif plane.name.startswith("/host"):
            meta = plane.event_metadata(with_stats=False)
            ids = {k for k, (name, _) in meta.items()
                   if name.startswith(SPAN_PREFIX)}
            if not ids:
                continue
            for mid, s, t, stats in plane.line_events(
                    lambda n: True, lambda i: i in ids, with_stats=True):
                spans.append(Span(meta[mid][0], s, t, stats))
    return ProgramTrace(
        {k: trace_reduce.leaves(v) for k, v in devices.items()},
        sorted(spans, key=lambda e: e.start))


_CACHE: dict = {}


def load(path: str) -> ProgramTrace:
    """The program trace of an .xplane.pb, or of the newest one under a
    directory; read once per path."""
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if path not in _CACHE:
        with open(path, "rb") as f:
            _CACHE[path] = parse(f.read())
    return _CACHE[path]


_REPORTED: set = set()


def for_run(trace, ctx) -> ProgramTrace:
    """The program trace of a traced run (its profile in ctx.trace_dir);
    the first call for a run logs the phases' coverage and the idle gaps."""
    pt = load(ctx.trace_dir)
    key = (ctx.trace_dir, trace.window)
    if key not in _REPORTED:
        _REPORTED.add(key)
        report(pt, trace)
    return pt


# -- per-window numbers ----------------------------------------------------------


def window_ops(pt: ProgramTrace, trace) -> dict:
    w = trace.window
    return {k: trace_reduce.clip(v, w.start, w.end)
            for k, v in pt.devices.items()}


def has_scopes(pt: ProgramTrace, trace) -> bool:
    """Whether any of the window's operations lies under a phase scope (a
    program that opens none gives nothing to read)."""
    return any(set(PHASES) & set(e.scope)
               for v in window_ops(pt, trace).values() for e in v)


def scope_s(pt: ProgramTrace, trace, scope: str) -> dict:
    """Per chip: summed device seconds of the window's operations under
    `scope` (a component of their op_name, at any depth)."""
    return {k: sum(e.end - e.start for e in v if scope in e.scope) * 1e-9
            for k, v in window_ops(pt, trace).items()}


def scope_ms_per_step(pt: ProgramTrace, trace, scope: str, steps) -> \
        float | None:
    """Device ms per window step under `scope`, averaged over the chips;
    None when the window has no steps or the program opens no scope."""
    if not steps or not has_scopes(pt, trace):
        return None
    per_chip = scope_s(pt, trace, scope)
    return 1e3 * sum(per_chip.values()) / len(per_chip) / steps


def window_spans(pt: ProgramTrace, trace, name: str) -> list:
    """The program's host spans `repro.<name>` inside the window."""
    w, full = trace.window, SPAN_PREFIX + name
    return [s for s in pt.spans
            if s.name == full and w.start <= s.start and s.end <= w.end]


def coverage(pt: ProgramTrace, trace) -> tuple[float | None, list]:
    """(share of the window's busy device time under any of PHASES,
    averaged over the chips; the uncovered operations that took most
    time, [(name, seconds)] on the lowest chip)."""
    ops = window_ops(pt, trace)
    if not ops:
        return None, []
    shares = []
    for v in ops.values():
        busy = trace_reduce.length(trace_reduce.union(
            [(e.start, e.end) for e in v]))
        under = trace_reduce.length(trace_reduce.union(
            [(e.start, e.end) for e in v if set(PHASES) & set(e.scope)]))
        shares.append(under / busy if busy else 0.0)
    rest: dict[str, float] = {}
    for e in ops[min(ops)]:
        if not set(PHASES) & set(e.scope):
            key = "/".join(e.scope) or e.name
            rest[key] = rest.get(key, 0.0) + (e.end - e.start) * 1e-9
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:5]
    return sum(shares) / len(shares), top


def _innermost(events, t: int):
    best = None
    for a in events:
        if a.start <= t < a.end and (best is None or
                                     a.end - a.start < best.end - best.start):
            best = a
    return best


def _has_span_child(ann, annotations, spans) -> bool:
    """Whether a program span lies under the benchmark annotation `ann`
    with no other benchmark annotation between them."""
    inner = [a for a in annotations if a is not ann
             and ann.start <= a.start and a.end <= ann.end]
    return any(ann.start <= p.start and p.end <= ann.end
               and not any(a.start <= p.start and p.end <= a.end
                           for a in inner)
               for p in spans)


def _activity(pt: ProgramTrace, trace, t: int) -> str:
    """What the host was doing at time t: the innermost program span open
    then; else the innermost benchmark annotation, marked `(outside the
    program's spans)` when a program span lies directly under it (so
    the time is the program's host code that no span names) and left
    unmarked when none does (the benchmark's own code)."""
    span = _innermost(pt.spans, t)
    if span is not None:
        return span.name
    ann = _innermost(trace.annotations, t)
    if ann is None:
        return "outside"
    if _has_span_child(ann, trace.annotations, pt.spans):
        return ann.name + " (outside the program's spans)"
    return ann.name


def idle_gaps(pt: ProgramTrace, trace, min_s: float = GAP_S) -> list:
    """[(offset into the window s, gap s, [(host activity, s)])] of the
    idle gaps of at least min_s on the lowest chip, in time order. A gap
    is cut where a program span or a benchmark annotation opens or
    closes, and each piece is named after the host's activity in it
    (`_activity`); neighbouring pieces of one activity are merged."""
    ops = window_ops(pt, trace)
    if not ops:
        return []
    w = trace.window
    busy = [(e.start, e.end) for e in ops[min(ops)]]
    edges = sorted({x for e in list(pt.spans) + list(trace.annotations)
                    for x in (e.start, e.end)})
    out = []
    for s, t in trace_reduce.subtract([(w.start, w.end)], busy):
        if (t - s) * 1e-9 < min_s:
            continue
        cuts = [s] + [x for x in edges if s < x < t] + [t]
        parts: list = []
        for a, b in zip(cuts, cuts[1:]):
            what = _activity(pt, trace, (a + b) // 2)
            if parts and parts[-1][0] == what:
                parts[-1][1] += (b - a) * 1e-9
            else:
                parts.append([what, (b - a) * 1e-9])
        out.append(((s - w.start) * 1e-9, (t - s) * 1e-9,
                    [tuple(p) for p in parts]))
    return out


def report(pt: ProgramTrace, trace) -> None:
    share, top = coverage(pt, trace)
    if share is None:
        log("[program_trace] no device operations in the window")
        return
    per = {p: sum(scope_s(pt, trace, p).values()) / len(pt.devices)
           for p in PHASES}
    log(f"[program_trace] phase scopes cover {100.0 * share!r}% of the "
        f"window's busy device time; seconds per chip "
        + ", ".join(f"{p} {s!r}" for p, s in per.items())
        + "; uncovered, most first: "
        + ", ".join(f"{n} {s!r}" for n, s in top))
    gaps = idle_gaps(pt, trace)
    log(f"[program_trace] {len(gaps)} idle gaps of {1e3 * GAP_S:g} ms or "
        f"more in the window (offset s, ms: host activity ms, ...): "
        + "; ".join(f"{o:.4f} {1e3 * g:.3f}: " + ", ".join(
            f"{what} {1e3 * x:.3f}" for what, x in parts)
            for o, g, parts in gaps))
