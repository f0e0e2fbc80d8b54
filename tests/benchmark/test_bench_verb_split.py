"""The readers of the program's own spans and kernel names: the verb
split (``benchmark/verb_split.py``) on a trace this test records on the
CPU, and the five readers on TPU-shaped traces written out by hand."""

import math
import time

import numpy as np
import pytest

import jax

from benchmark import harness, trace_reduce, verb_split
from benchmark.trace_reduce import HostEvent, Op, Span, Trace

PARTS = ("self", "dispatch", "wait")
VERB_READERS = {"verb_self_us": "self", "verb_dispatch_us": "dispatch",
                "verb_wait_us": "wait"}
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _reader(name):
    return harness.load_module("metrics", name)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """mesh_world() allreduces on four devices, both phases, each call
    under a bench.call span as the coll driver makes them."""
    from ompi_tpu.parallel import mesh_world

    d = str(tmp_path_factory.mktemp("trace"))
    world = mesh_world(jax.devices()[:4])
    xs = {"small": world.shard(np.ones((4, 16), np.float32)),
          "large": world.shard(np.ones((4, 1 << 14), np.float32))}
    for x in xs.values():
        world.allreduce(x).block_until_ready()
        world.allreduce(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for phase, x in xs.items():
            with jax.profiler.TraceAnnotation("bench.block", phase=phase):
                for _ in range(40):
                    with jax.profiler.TraceAnnotation(
                            "bench.call", verb="allreduce", phase=phase,
                            bytes=x.nbytes // 4):
                        world.allreduce(x).block_until_ready()
    time.sleep(0.2)
    jax.profiler.stop_trace()
    return trace_reduce.load(d)


@pytest.mark.parametrize("phase", verb_split.PHASES)
def test_bench_verb_split_adds_up_on_a_recorded_trace(recorded, phase):
    got = verb_split.split(recorded, "allreduce", phase)
    assert got is not None
    assert all(math.isfinite(got[p]) and got[p] >= 0 for p in PARTS)
    assert got["dispatch"] > 0
    parts = sum(got[p] for p in PARTS)
    assert parts == pytest.approx(got["call"], rel=0.10)
    assert parts + got["entry"] == pytest.approx(got["call"], rel=1e-9)


def test_bench_verb_split_needs_the_span_in_the_calls(recorded):
    assert verb_split.split(recorded, "bcast", "small") is None
    assert verb_split.split(recorded, "allreduce", "tiny") is None


def test_bench_verb_readers_stay_silent_on_the_cpu(recorded):
    """On the CPU backend the executable runs inside the dispatch call."""
    record = {"verb": "allreduce",
              "phases": {"small": {"time_s": 1.0, "calls": 40}}}
    for name in VERB_READERS:
        assert _reader(name).read(recorded, record, None,
                                  {"platform": "cpu"}) is None


def _call(t, entry, own, nested, wait, phase="small"):
    """One bench.call at ``t`` and its host events: the comm span, and
    in it PjitFunction with a child of its own and a second event."""
    s = t + entry
    v = s + own + sum(nested)
    events = [HostEvent("comm.allreduce", s, v)]
    at = s + own
    for i, n in enumerate(nested):
        events.append(HostEvent(f"PjitFunction(body{i})", at, at + n))
        events.append(HostEvent("ParseArguments", at, at + n / 2))
        at += n
    return (Span("bench.call", t, v + wait,
                 {"phase": phase, "verb": "allreduce"}), events)


def _verb_trace(with_spans=True):
    spans, host = [Span("bench.window", 0, 1e9, {})], []
    t = 1000.0
    for phase, (own, nested, wait) in (
            ("small", (10e3, (300e3, 200e3), 350e3)),
            ("large", (12e3, (400e3,), 900e3))):
        for _ in range(4):
            call, evs = _call(t, 2e3, own, nested, wait, phase)
            spans.append(call)
            host += evs if with_spans else evs[1:]
            t = call.end + 5e3
    ops = [Op("TPU:0", "all-reduce.1", "jit_body", 10, 20, "", False)]
    return Trace(ops, spans, host)


RECORD = {"verb": "allreduce",
          "phases": {"small": {"time_s": 0.0034, "calls": 4}}}


def test_bench_verb_readers_on_a_tpu_shaped_trace():
    tr = _verb_trace()
    got = {n: _reader(n).read(tr, RECORD, None, TPU) for n in VERB_READERS}
    assert got["verb_self_us"]["value"] == pytest.approx(10)
    assert got["verb_self_us"]["large"] == pytest.approx(12)
    assert got["verb_self_us"]["entry_us"] == pytest.approx(2)
    assert got["verb_self_us"]["call_us"] == pytest.approx(850)
    assert got["verb_dispatch_us"] == pytest.approx(
        {"value": 500, "large": 400})
    assert got["verb_wait_us"] == pytest.approx({"value": 350, "large": 900})


def test_bench_verb_readers_find_nothing_without_the_span():
    """A program that puts no comm.<verb> span in the trace."""
    tr = _verb_trace(with_spans=False)
    for name in VERB_READERS:
        assert _reader(name).read(tr, RECORD, None, TPU) is None


def test_bench_verb_readers_need_99_percent_of_the_calls():
    tr = _verb_trace()
    first = tr.spans_named("bench.call", phase="small")[0]
    host = [h for h in tr.host
            if not (h.name == "comm.allreduce" and h.start < first.end)]
    tr = Trace(tr.ops, tr.spans, host)
    assert verb_split.split(tr, "allreduce", "small") is None
    assert verb_split.split(tr, "allreduce", "large") is not None


def _kernel(name, start, dur):
    return Op("TPU:0", name, "jit_step_local", start, start + dur,
              f'%{name} = f32[2]{{0}} custom-call(f32[2]{{0}} %x), '
              'custom_call_target="tpu_custom_call"', False)


def test_bench_flash_readers_read_the_kernels_by_name():
    ops = [_kernel("jvp_flash_fwd_.1", 100, 3e6),
           _kernel("transpose_jvp_flash_dq__.2", 4e6, 2e6),
           _kernel("transpose_jvp_flash_dkv__.3", 7e6, 5e6),
           _kernel("jvp_flash_fwd_.1", 20e6, 3e6),
           Op("TPU:0", "flash_fwd_like.4", "jit_step_local", 30e6, 31e6,
              "", False),                               # not a custom call
           _kernel("jvp__.5", 40e6, 1e6)]               # unnamed kernel
    tr = Trace(ops, [Span("bench.window", 0, 50e6, {})], [])
    record = {"steps": 2}
    fwd = _reader("flash_fwd_ms").read(tr, record, None, TPU)
    bwd = _reader("flash_bwd_ms").read(tr, record, None, TPU)
    assert fwd == pytest.approx(3.0)
    assert bwd == pytest.approx(3.5)


def test_bench_flash_readers_find_nothing_in_unnamed_kernels():
    tr = Trace([_kernel("jvp__.8", 100, 3e6),
                _kernel("transpose_jvp___.9", 4e6, 2e6)],
               [Span("bench.window", 0, 50e6, {})], [])
    for name in ("flash_fwd_ms", "flash_bwd_ms"):
        assert _reader(name).read(tr, {"steps": 1}, None, TPU) is None
