"""chipbench.trace_reduce on hand-built traces, and on a recorded one."""

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event


def _trace():
    # one chip; times in ns; the window is [0, 100)
    ops = {0: [Event("while.55", 0, 130),              # control flow
               Event("fusion.1", 0, 10),
               Event("kmvm_pallas.13", 5, 20, "f32[1024,128]"),
               Event("all-gather.3", 25, 35),
               Event("fusion.2", 30, 32),              # hides 2 ns of it
               Event("fusion.9", 95, 130)]}            # runs past the end
    ann = [Event("bench.window", 0, 100),
           Event("bench.step", 0, 50),
           Event("bench.adam", 50, 60),
           Event("bench.step", 60, 100)]
    return tr.from_events(ops, ann)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    # [0, 20) + [25, 35) + [95, 100)
    assert tr.busy_s(t) == pytest.approx(35e-9)
    assert tr.window_s(t) == pytest.approx(100e-9)
    assert tr.idle_share(t) == pytest.approx(0.65)


def test_kernel_time_count_and_rows():
    t = _trace()
    assert tr.op_time_s(t, tr.KMVM) == {0: pytest.approx(15e-9)}
    assert [e.name for e in tr.op_events(t, tr.KMVM)[0]] == ["kmvm_pallas.13"]
    assert tr.op_rows(t, tr.KMVM) == {0: 1024}


def test_hlo_text_gives_name_and_shape():
    e = tr.hlo_event("%kmvm_pallas.3 = f32[256,128]{1,0:T(8,128)S(1)} "
                     "custom-call(f32[1,2]{1,0} %copy-done.5)", 3, 9)
    assert (e.name, e.start, e.end) == ("kmvm_pallas.3", 3, 9)
    assert tr.leading_dim(e) == 256
    loop = tr.hlo_event("%while.55 = (s32[]{:T(128)}, f32[9]{0}) while()",
                        0, 1)
    assert loop.name == "while.55" and tr.leading_dim(loop) is None
    assert tr.leaves([loop, e]) == [e]


def test_async_collective_in_flight_counts_where_nothing_computes():
    ops = {0: [Event("fusion.1", 0, 10), Event("fusion.2", 20, 30)]}
    asyncs = {0: [Event("all-gather-start.1", 5, 25),
                  Event("copy-start.2", 0, 40)]}
    t = tr.from_events(ops, [Event("bench.window", 0, 40)], asyncs)
    # the gather is in flight over [5, 25); compute covers all but [10, 20)
    assert tr.exposed_collective_s(t) == {0: pytest.approx(10e-9)}


def test_exposed_collective_leaves_out_overlapped_compute():
    t = _trace()
    assert tr.exposed_collective_s(t) == {0: pytest.approx(8e-9)}


def test_idle_gaps_are_named_after_the_host_annotation():
    t = _trace()
    gaps = tr.idle_gaps(t)
    # [35, 95) has its middle at 65, inside the second bench.step;
    # [20, 25) at 22, inside the first
    assert gaps == [("bench.step", pytest.approx(60e-9)),
                    ("bench.step", pytest.approx(5e-9))]


def test_breakdown_ranks_ops_and_averages_over_chips():
    ops = {0: [Event("a", 0, 40), Event("b", 40, 50)],
           1: [Event("a", 0, 20), Event("b", 20, 50)]}
    t = tr.from_events(ops, [Event("bench.window", 0, 100)])
    b = tr.breakdown(t)
    assert b["device_ops"] == [["a", pytest.approx(30e-9)],
                               ["b", pytest.approx(20e-9)]]
    assert b["idle_gaps"] == [["bench.window", pytest.approx(50e-9)]]
    assert tr.busy_s(t) == pytest.approx(50e-9)


def test_two_chips_exposed_collectives_per_chip():
    ops = {0: [Event("all-reduce.1", 0, 10)],
           1: [Event("all-reduce.1", 0, 10), Event("fusion", 0, 10)]}
    t = tr.from_events(ops, [Event("bench.window", 0, 10)])
    assert tr.exposed_collective_s(t) == {0: pytest.approx(10e-9),
                                          1: pytest.approx(0.0)}


def test_subtract_and_union():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_a_trace_needs_exactly_one_window():
    with pytest.raises(ValueError):
        tr.from_events({}, [])


def test_recorded_trace_keeps_the_benchmark_annotations(tmp_path):
    """A real profile, recorded here on the CPU: the host annotations come
    back with the window, and no TPU plane is mistaken for a chip."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = [a.name for a in t.annotations]
    assert names.count("bench.window") == 1 and "bench.step" in names
    assert t.devices == {}
    assert tr.idle_share(t) is None
    w = t.window
    step = [a for a in t.annotations if a.name == "bench.step"][0]
    assert w.start <= step.start <= step.end <= w.end
