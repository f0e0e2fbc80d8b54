"""benchmark/trace_reduce.py on a trace this test records on the CPU,
and on a TPU-shaped trace written out by hand."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import trace_reduce
from benchmark.trace_reduce import Op, Span, Trace


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Four devices, two phases of jitted sums under bench.* spans, with
    host sleeps between calls so that the devices sit idle."""
    import time

    d = str(tmp_path_factory.mktemp("trace"))
    devs = jax.devices()[:4]
    xs = [jax.device_put(jnp.ones((256, 256)), dv) for dv in devs]
    f = jax.jit(lambda a: (a @ a).sum())
    for x in xs:
        f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for phase in ("small", "large"):
            with jax.profiler.TraceAnnotation("bench.block", phase=phase):
                for i in range(5):
                    with jax.profiler.TraceAnnotation(
                            "bench.call", phase=phase, bytes=64):
                        jax.block_until_ready([f(x) for x in xs])
                    time.sleep(0.002)
    jax.profiler.stop_trace()
    return trace_reduce.load(d)


def test_bench_recorded_trace_has_spans_ops_and_devices(recorded):
    tr = recorded
    assert len(tr.spans_named("bench.window")) == 1
    assert len(tr.spans_named("bench.call")) == 10
    assert len(tr.spans_named("bench.call", phase="small")) == 5
    assert tr.spans_named("bench.call")[0].args["bytes"] == 64
    assert len(tr.devices) == 4
    assert all(o.end >= o.start for o in tr.ops)


def test_bench_recorded_busy_and_gaps_add_up(recorded):
    tr = recorded
    w = tr.spans_named("bench.window")[0]
    busy = tr.busy_ns(w.start, w.end)
    assert 0 < busy < w.end - w.start
    gaps = tr.idle_gaps(w.start, w.end, n=100)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(
        w.end - w.start - busy, rel=1e-6)
    # the sleeps sit inside the blocks, after each call
    assert any(name.startswith("bench.block[") for name, _ in gaps)
    calls = tr.spans_named("bench.call")
    assert tr.busy_in(calls) <= busy + 1
    top = tr.top_ops(w.start, w.end)
    assert top and all(s > 0 for _, s in top)


def test_bench_busy_is_the_union_averaged_over_devices():
    ops = [Op("TPU:0", "a.1", "m", 0, 10, "", False),
           Op("TPU:0", "b.2", "m", 5, 20, "", True),   # overlaps a.1
           Op("TPU:0", "c.3", "m", 30, 40, "", False),
           Op("TPU:1", "a.1", "m", 0, 40, "", False)]
    tr = Trace(ops, [Span("bench.window", 0, 50, {})], [])
    assert tr.busy_ns(0, 50) == pytest.approx((30 + 40) / 2)
    assert tr.busy_ns(15, 35) == pytest.approx((5 + 5 + 20) / 2)
    assert tr.busy_ns(41, 50) == 0
    # async ops count as busy but not among the top ops
    assert [k for k, _ in tr.top_ops(0, 50)] == ["a", "c"]
    assert tr.op_ns(0, 50, lambda o: o.name.startswith("a")) == 25
    gaps = dict(tr.idle_gaps(0, 50))
    assert sum(gaps.values()) * 1e9 == pytest.approx(50 - 35)


def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def test_bench_tpu_planes_are_read_as_a_v5e_trace_lays_them_out():
    """Planes and lines as a v5e trace names them (chip run, PR 22)."""
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("Steps", [_event("0", 100, 900)]),
        _line("XLA Modules", [_event("jit_step_local(123)", 100, 900)]),
        _line("XLA Ops", [
            _event("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                   100, 300),
            _event("%jvp__.8 = (f32[2]{0}) custom-call(f32[2]{0} %x), "
                   'custom_call_target="tpu_custom_call"', 500, 200)]),
        _line("Async XLA Ops", [_event("%copy-start.3 = f32[8]{0} x",
                                       350, 100)])])
    core = types.SimpleNamespace(name="/device:TPU:0 SparseCore 0", lines=[
        _line("XLA Ops", [_event("%x.1 = f32[] y", 0, 5)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("python3", [_event("bench.window", 50, 1000),
                          _event("bench.step", 60, 30, i=0),
                          _event("PjitFunction(step_local)", 61, 20)]),
        _line("main/300", [_event("ExecuteHelper", 62, 5)])])
    tr = trace_reduce.from_profile(types.SimpleNamespace(
        planes=[dev, core, host]))
    assert tr.devices == ["TPU:0"]
    assert [(o.name, o.module, o.is_async) for o in tr.ops] == [
        ("fusion.12", "jit_step_local", False),
        ("copy-start.3", "jit_step_local", True),
        ("jvp__.8", "jit_step_local", False)]
    assert tr.ops[2].hlo and not tr.ops[0].hlo
    assert tr.spans_named("bench.step", i=0)
    assert [h.name for h in tr.host] == ["PjitFunction(step_local)"]
    assert tr.busy_ns(50, 1050) == 300 + 50 + 200
    gaps = dict(tr.idle_gaps(50, 1050))
    assert gaps["bench.step > PjitFunction(step_local)"] == \
        pytest.approx(50e-9)


def test_bench_load_finds_no_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.load(str(tmp_path))


def test_bench_device_clock_is_moved_onto_the_host_clock():
    """A v5e's device clocks read about 1.7 ms early against the host
    (chip run, PR 22): the run ids of the host's enqueue and completion
    events bound the offset, and the device's ops are moved by it."""
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Modules", [_event("jit_body(1)", 100, 100, run_id=5)]),
        _line("XLA Ops", [_event("%psum.7 = f32[4]{0} all-reduce(x)",
                                 110, 80)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("python3", [_event("bench.call", 1000, 300, bytes=64)]),
        _line("q/1", [_event("DoEnqueueProgram", 1100, 10, run_id=5,
                             device_ordinal=0)]),
        _line("f/2", [_event("CompleteCallbacks", 1250, 10, run_id=5,
                             device_ordinal=0)])])
    tr = trace_reduce.from_profile(types.SimpleNamespace(planes=[dev, host]))
    # device end 200 <= completion 1250 + off, start 100 >= 1100 + off:
    # off in [-1050, -1000], and the middle is taken
    (op,) = tr.ops
    assert (op.start, op.end) == (110 + 1025, 190 + 1025)
    assert tr.busy_in(tr.spans_named("bench.call")) == 80
