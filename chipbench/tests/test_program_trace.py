"""chipbench.program_trace on hand-built traces: a serialized XSpace
written here field by field, ProgramTraces built by hand, and a profile
recorded on the CPU."""

import struct

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.program_trace import Op, ProgramTrace, Span
from chipbench.trace_reduce import Event

# -- a minimal protobuf writer, for the wire-format tests -----------------------


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _len(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _str(field: int, s: str) -> bytes:
    return _len(field, s.encode())


def _double(field: int, x: float) -> bytes:
    return _varint(field << 3 | 1) + struct.pack("<d", x)


def _plane(name, lines, event_meta, stat_names) -> bytes:
    """An XPlane: lines [(name, timestamp_ns, [event bytes])], event
    metadata {id: (name, [stat bytes])}, stat metadata {id: name}."""
    out = _str(2, name)
    for lname, ts, events in lines:
        body = _str(2, lname) + _int(3, ts)
        for ev in events:
            body += _len(4, ev)
        out += _len(3, body)
    for mid, (mname, stats) in event_meta.items():
        meta = _int(1, mid) + _str(2, mname) + b"".join(
            _len(5, s) for s in stats)
        out += _len(4, _int(1, mid) + _len(2, meta))
    for sid, sname in stat_names.items():
        out += _len(5, _int(1, sid) + _len(2, _int(1, sid) + _str(2, sname)))
    return out


def _event(mid, off_ps, dur_ps, stats=()) -> bytes:
    return (_int(1, mid) + _int(2, off_ps) + _int(3, dur_ps)
            + b"".join(_len(4, s) for s in stats))


TF_OP, MODE, ITERS, DRIFT, WARM = 1, 2, 3, 4, 5


def _space() -> bytes:
    stats = {TF_OP: "tf_op", MODE: "mode", ITERS: "cg_iters_max",
             DRIFT: "drift", WARM: "warm"}
    dev_meta = {
        10: ("%while.5 = (s32[]) while()", []),
        11: ("%kmvm_pallas.13 = f32[1024,128]{1,0} custom-call()",
             [_int(1, TF_OP) + _str(5, "jit(local_warm)/pcg/while/body/"
                                    "jit(kmvm_pallas)/pallas_call:")]),
        12: ("%pad.7 = f32[65536,128]{1,0} pad()",
             [_int(1, TF_OP) + _str(5, "jit(local_warm)/pcg/while/body/"
                                    "pcg.matvec/kmvm.prep/jit(_pad)/pad:")]),
        13: ("%fusion.3 = f32[9]{0} fusion()",
             [_int(1, TF_OP) + _str(5, "jit(local_warm)/eq2_backward/mul:")]),
        14: ("%copy.1 = f32[9]{0} copy()", []),
    }
    device = _plane("/device:TPU:0", [
        ("XLA Ops", 1000, [
            _event(10, 0, 900_000),
            _event(11, 1_500, 100_400),           # [1001, 1101)
            _event(12, 101_999, 49_999),          # [1101, 1150)
            _event(13, 300_000, 20_000),          # [1300, 1320)
            _event(14, 400_000, 10_000)]),        # [1400, 1410)
        ("Steps", 1000, [_event(14, 0, 5_000)])],
        dev_meta, stats)
    host_meta = {
        20: ("repro.mll_step", []),
        21: ("python3 frame", []),
        22: ("warm", []),                         # a stat value by reference
    }
    host = _plane("/host:CPU", [
        ("python3", 2000, [
            _event(21, 0, 999_000),
            _event(20, 5_000, 600_000, [
                _int(1, ITERS) + _int(4, 3),
                _int(1, DRIFT) + _double(2, 0.25),
                _int(1, MODE) + _int(7, WARM)])])],
        host_meta, stats)
    other = _plane("/host:metadata", [], {}, {})
    return _len(1, device) + _len(1, host) + _len(1, other)


def test_wire_format_gives_scopes_times_and_spans():
    p = pt.parse(_space())
    ops = p.devices[0]
    # control flow dropped; other lines than XLA Ops ignored
    assert [o.name for o in ops] == ["kmvm_pallas.13", "pad.7", "fusion.3",
                                     "copy.1"]
    # whole ns, line timestamp plus offset, as ProfileData has them
    assert [(o.start, o.end) for o in ops] == [
        (1001, 1101), (1101, 1150), (1300, 1320), (1400, 1410)]
    assert ops[0].scope == ("jit(local_warm)", "pcg", "while", "body",
                            "jit(kmvm_pallas)", "pallas_call")
    assert "kmvm.prep" in ops[1].scope and "pcg" in ops[1].scope
    assert ops[3].scope == ()
    (span,) = p.spans
    assert span.name == "repro.mll_step"
    assert (span.start, span.end) == (2005, 2605)
    assert span.stats == {"cg_iters_max": 3, "drift": 0.25, "mode": "warm"}


def test_negative_varints_read_as_signed():
    buf = _int(3, -5)
    assert list(pt._fields(buf, 0, len(buf))) == [(3, (1 << 64) - 5)]
    assert pt._signed((1 << 64) - 5) == -5


# -- per-window numbers, on hand-built traces ------------------------------------


def _scoped(*scope):
    return ("jit(local_warm)",) + scope


def _trace():
    """Two chips; the window is [0, 100) ns."""
    dev = {0: [Op("kmvm_pallas.13", 0, 40, _scoped("pcg", "pcg.matvec")),
               Op("pad.1", 40, 50, _scoped("pcg", "kmvm.prep")),
               Op("fusion.2", 60, 70, _scoped("eq2_backward")),
               Op("fusion.3", 70, 75, _scoped("slq_logdet")),
               Op("copy.4", 80, 90, _scoped()),
               Op("fusion.5", 95, 130, _scoped("precond_build"))],
           1: [Op("kmvm_pallas.13", 0, 20, _scoped("pcg")),
               Op("fusion.2", 20, 30, _scoped("eq2_backward"))]}
    spans = [Span("repro.mll_step", 0, 52, {"cg_iters_max": 3}),
             Span("repro.adam_update", 52, 58, {}),
             Span("repro.mll_step", 60, 99, {"cg_iters_max": 1}),
             Span("repro.adam_update", 99, 120, {})]
    ann = [Event("bench.window", 0, 100),
           Event("bench.step", 0, 52), Event("bench.adam", 52, 58),
           Event("bench.loss_to_host", 58, 60),
           Event("bench.step", 60, 100)]
    trace = tr.from_events({k: [Event(o.name, o.start, o.end) for o in v]
                            for k, v in dev.items()}, ann)
    return ProgramTrace(dev, spans), trace


def test_scope_time_per_step_nests_and_averages_over_chips():
    p, trace = _trace()
    # pcg holds the prep under it: chip 0 50 ns, chip 1 20 ns; 2 steps
    assert pt.scope_ms_per_step(p, trace, "pcg", 2) == \
        pytest.approx(1e3 * 35e-9 / 2)
    assert pt.scope_ms_per_step(p, trace, "kmvm.prep", 2) == \
        pytest.approx(1e3 * 5e-9 / 2)
    assert pt.scope_ms_per_step(p, trace, "eq2_backward", 1) == \
        pytest.approx(1e3 * 10e-9)
    # clipped to the window: precond_build runs 5 ns of 35 inside it
    assert pt.scope_ms_per_step(p, trace, "precond_build", 1) == \
        pytest.approx(1e3 * 2.5e-9)
    assert pt.scope_ms_per_step(p, trace, "pcg", 0) is None


def test_a_program_without_scopes_gives_nothing():
    p, trace = _trace()
    bare = ProgramTrace({k: [o._replace(scope=("jit(local_warm)", "while"))
                             for o in v] for k, v in p.devices.items()}, [])
    assert not pt.has_scopes(bare, trace)
    assert pt.scope_ms_per_step(bare, trace, "pcg", 2) is None
    assert pt.window_spans(bare, trace, "mll_step") == []


def test_coverage_is_the_union_under_the_phases():
    p, trace = _trace()
    share, top = pt.coverage(p, trace)
    # chip 0: busy 80 ns, 70 of them under a phase; chip 1: all 30
    assert share == pytest.approx((70 / 80 + 1.0) / 2)
    assert top == [("jit(local_warm)", pytest.approx(10e-9))]


def test_window_spans_lie_inside_the_window():
    p, trace = _trace()
    assert [s.stats["cg_iters_max"]
            for s in pt.window_spans(p, trace, "mll_step")] == [3, 1]
    assert len(pt.window_spans(p, trace, "adam_update")) == 1


def test_idle_gaps_name_the_program_span_or_the_benchmark():
    p, trace = _trace()

    def ns(gaps):
        return [(round(o * 1e9), round(g * 1e9),
                 [(w, round(x * 1e9)) for w, x in parts])
                for o, g, parts in gaps]

    # chip 0 is idle over [50, 60), [75, 80) and [90, 95); the first gap
    # spans the end of a step, the optimizer and the benchmark's own code
    assert ns(pt.idle_gaps(p, trace, min_s=1e-9)) == [
        (50, 10, [("repro.mll_step", 2), ("repro.adam_update", 6),
                  ("bench.loss_to_host", 2)]),
        (75, 5, [("repro.mll_step", 5)]),
        (90, 5, [("repro.mll_step", 5)])]
    assert [g[:2] for g in ns(pt.idle_gaps(p, trace, min_s=6e-9))] == \
        [(50, 10)]
    # with no program span open: under a benchmark annotation that holds
    # a program span elsewhere, the time is the program's host code
    # outside its spans; under one that holds none, the benchmark's own
    short = ProgramTrace(p.devices, [Span("repro.mll_step", 0, 45, {}),
                                     Span("repro.mll_step", 60, 70, {})])
    outside = "bench.step (outside the program's spans)"
    assert ns(pt.idle_gaps(short, trace, min_s=1e-9)) == [
        (50, 10, [(outside, 2), ("bench.adam", 6),
                  ("bench.loss_to_host", 2)]),
        (75, 5, [(outside, 5)]),
        (90, 5, [(outside, 5)])]


def test_readers_are_cached_per_path(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space())
    calls = []
    real = pt.parse
    monkeypatch.setattr(pt, "parse", lambda b: calls.append(1) or real(b))
    monkeypatch.setattr(pt, "_CACHE", {})
    a = pt.load(str(tmp_path))
    b = pt.load(str(path))
    assert a is b and len(calls) == 1


# -- a profile recorded on the CPU -------------------------------------------------


def test_recorded_profile_keeps_the_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("mll_step", mode="warm") as sp:
            f(x).block_until_ready()
            sp.set(cg_iters_max=2, traversals=21)
    jax.profiler.stop_trace()
    p = pt.load(str(tmp_path))
    trace = tr.load(str(tmp_path))
    (span,) = pt.window_spans(p, trace, "mll_step")
    assert span.stats == {"mode": "warm", "cg_iters_max": 2,
                          "traversals": 21}
    # the CPU has no TPU plane: no device operations, nothing to cover
    assert p.devices == {}
    assert pt.coverage(p, trace) == (None, [])
