"""Cross-layer span tracing: ring recording, Chrome-trace/Perfetto
export, compile-cache telemetry, and the trace_lint schema gate.

Reference points: MPI-4 §14.3.8 events (the MPI_T mirror), the mpisync
alignment workflow (tools/trace_merge.py), PERUSE-style layer hooks.
"""

import json
import time

import numpy as np
import pytest

import jax

import ompi_tpu
from ompi_tpu import COMM_WORLD
from ompi_tpu.mca.var import all_pvars, set_var
from ompi_tpu.parallel import mesh_world
from ompi_tpu.runtime import trace

from tools.trace_lint import lint_events, lint_file
from tools.trace_merge import load_offsets, merge

W = 8


@pytest.fixture
def tracing():
    set_var("trace", "enable", True)
    trace.reset()
    try:
        yield
    finally:
        set_var("trace", "enable", False)
        trace.reset()


def _open_spans_at(events, target):
    """Names of spans open (per this pid/tid) when ``target``'s B begins."""
    stack = []
    for e in sorted((e for e in events if e["ph"] in ("B", "E")
                     and e["tid"] == target["tid"]),
                    key=lambda e: e["ts"]):
        if e is target:
            return list(stack)
        if e["ph"] == "B":
            stack.append(e["name"])
        elif stack and stack[-1] == e["name"]:
            stack.pop()
    raise AssertionError("target event not found")


def test_trace_allreduce_and_pt2pt_export(tracing, tmp_path):
    """The acceptance scenario: Allreduce on a mesh comm produces nested
    comm.allreduce -> coll.xla.dispatch -> coll.xla.compile spans (the
    compile on the FIRST call only, the cache-hit pvar bumping on the
    second), Send/Recv produce pml.send spans, and the export is valid
    Chrome-trace JSON."""
    from ompi_tpu.coll.xla import stats

    world = mesh_world(jax.devices()[:W])  # fresh comm: cold jit cache
    x = world.shard(np.ones((W, 4), np.float32))
    misses0 = stats.misses
    world.allreduce(x)                     # miss -> trace+compile span
    assert stats.misses == misses0 + 1
    hits0 = stats.hits
    world.allreduce(x)                     # resolved fast path: a hit
    assert stats.hits > hits0
    pv = all_pvars()
    assert pv["coll_xla_cache_hits"].value == stats.hits
    assert pv["coll_xla_cache_misses"].value == stats.misses
    assert pv["coll_xla_compile_time_us"].value > 0

    buf = np.zeros(4, np.float64)
    COMM_WORLD.Send(np.ones(4, np.float64), dest=0, tag=9)
    COMM_WORLD.Recv(buf, source=0, tag=9)

    path = trace.export(str(tmp_path / "trace-rank0.json"))
    assert lint_file(path) == []           # the schema gate
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    for required in ("comm.allreduce", "coll.xla.dispatch",
                     "coll.xla.compile", "pml.send", "pml.recv"):
        assert required in names, required

    # B/E pairing + monotonic timestamps over the real event stream
    timed = [e for e in events if e["ph"] != "M"]
    ts = [e["ts"] for e in timed]
    assert ts == sorted(ts)
    assert all(t >= 0 for t in ts)
    bs = [e for e in timed if e["ph"] == "B"]
    es = [e for e in timed if e["ph"] == "E"]
    assert len(bs) == len(es)

    # compile fired exactly once (second call was a cache hit) and was
    # nested inside comm.allreduce -> coll.xla.dispatch
    compiles = [e for e in timed
                if e["name"] == "coll.xla.compile" and e["ph"] == "B"]
    assert len(compiles) == 1
    open_at_compile = _open_spans_at(timed, compiles[0])
    assert "comm.allreduce" in open_at_compile
    assert open_at_compile[-1] == "coll.xla.dispatch"


def test_trace_disabled_records_nothing():
    trace.reset()
    assert not trace.enabled()
    out = np.zeros(2, np.float32)
    COMM_WORLD.Allreduce(np.ones(2, np.float32), out)
    COMM_WORLD.Send(np.ones(1, np.float64), dest=0, tag=8)
    COMM_WORLD.Recv(np.zeros(1, np.float64), source=0, tag=8)
    assert trace.snapshot() == []
    assert trace.buffered_events() == 0


def test_ring_overflow_drops_oldest_and_stays_wellformed(tracing,
                                                         tmp_path):
    set_var("trace", "buffer_events", 64)
    trace.reset()
    try:
        for i in range(200):
            with trace.span("t.outer", cat="test", i=i):
                with trace.span("t.inner", cat="test"):
                    pass
        assert trace.dropped_events() > 0
        assert all_pvars()["trace_dropped_events"].value > 0
        path = trace.export(str(tmp_path / "overflow.json"))
        # eviction orphans old E events; the exporter must still emit
        # valid pairing the linter (and Perfetto) accept
        assert lint_file(path) == []
    finally:
        set_var("trace", "buffer_events", 65536)
        trace.reset()


def test_ring_overflow_banner_and_metadata(tracing, tmp_path, capfd):
    """Silent truncation must be visible: the finalize path show_helps
    a ring-overflow banner and the export carries the dropped count in
    its metadata (otherData.dropped_events)."""
    set_var("trace", "buffer_events", 16)
    trace.reset()
    try:
        for i in range(64):
            trace.instant(f"e{i}", cat="test")
        dropped = trace.dropped_events()
        assert dropped > 0
        assert trace._warn_overflow() == dropped
        err = capfd.readouterr().err
        assert "ring buffers wrapped" in err
        assert str(dropped) in err
        path = trace.export(str(tmp_path / "overflow-meta.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["otherData"]["dropped_events"] == dropped
    finally:
        set_var("trace", "buffer_events", 65536)
        trace.reset()


def test_trace_spans_mirror_onto_mpit_events(tracing):
    """The MPI_T surface sees the same stream the file export records
    (MPI-4 §14.3.8: typed event sources with immutable instances)."""
    from ompi_tpu import mpit

    mpit.init_thread()
    seen = []
    try:
        h_b = mpit.event_handle_alloc(
            mpit.event_get_index("trace_span_begin"),
            lambda inst: seen.append(("B", inst.data["name"])))
        h_e = mpit.event_handle_alloc(
            mpit.event_get_index("trace_span_end"),
            lambda inst: seen.append(("E", inst.data["name"])))
        with trace.span("unit.mpit", cat="test"):
            pass
        h_b.free()
        h_e.free()
    finally:
        mpit.finalize()
    assert ("B", "unit.mpit") in seen
    assert ("E", "unit.mpit") in seen


def test_trace_lint_rejects_malformed(tmp_path):
    # mismatched B/E names
    bad = {"traceEvents": [
        {"name": "a", "cat": "t", "ph": "B", "ts": 2.0, "pid": 0,
         "tid": 1},
        {"name": "b", "cat": "t", "ph": "E", "ts": 3.0, "pid": 0,
         "tid": 1},
    ]}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    assert lint_file(str(p)) != []
    # unknown phase / missing ts / negative ts / unclosed B
    assert lint_events([{"ph": "Z", "name": "x"}])
    assert lint_events([{"ph": "B", "name": "x", "pid": 0, "tid": 0}])
    assert lint_events([{"ph": "i", "name": "x", "ts": -1.0, "pid": 0}])
    assert lint_events([{"ph": "B", "name": "x", "ts": 1.0, "pid": 0,
                         "tid": 0}])
    # timestamps running backwards within a (pid, tid) stream
    assert lint_events([
        {"ph": "B", "name": "x", "ts": 5.0, "pid": 0, "tid": 0},
        {"ph": "E", "name": "x", "ts": 1.0, "pid": 0, "tid": 0},
    ])
    # and the clean case really is clean
    assert lint_events([
        {"ph": "B", "name": "x", "ts": 1.0, "pid": 0, "tid": 0},
        {"ph": "E", "name": "x", "ts": 2.0, "pid": 0, "tid": 0},
    ]) == []


def test_trace_merge_aligns_ranks(tmp_path):
    """Multi-rank merge: mpisync offsets shift each rank onto rank 0's
    clock; the merged file keeps one process track per rank and stays
    lint-clean."""
    def rank_doc(rank, t0_us):
        return {"traceEvents": [
            {"name": "comm.allreduce", "cat": "comm", "ph": "B",
             "ts": t0_us, "pid": rank, "tid": 1},
            {"name": "comm.allreduce", "cat": "comm", "ph": "E",
             "ts": t0_us + 5.0, "pid": rank, "tid": 1},
        ], "otherData": {"rank": rank}}

    p0 = tmp_path / "trace-rank0.json"
    p1 = tmp_path / "trace-rank1.json"
    p0.write_text(json.dumps(rank_doc(0, 100.0)))
    # rank 1's clock runs 1ms ahead: same instant reads 1000us later
    p1.write_text(json.dumps(rank_doc(1, 1100.0)))
    offs = tmp_path / "offsets.json"
    offs.write_text(json.dumps({"0": 0.0, "1": 0.001}))
    merged = merge([str(p0), str(p1)], load_offsets(str(offs)))
    evs = merged["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    b0 = next(e for e in evs if e["pid"] == 0 and e["ph"] == "B")
    b1 = next(e for e in evs if e["pid"] == 1 and e["ph"] == "B")
    assert abs(b0["ts"] - b1["ts"]) < 1e-6  # aligned to the same instant
    out = tmp_path / "merged.json"
    out.write_text(json.dumps(merged))
    assert lint_file(str(out)) == []
    # mpisync's human-readable table parses as an offsets source too
    txt = tmp_path / "mpisync.txt"
    txt.write_text("mpisync rank 0: offset +0.000000e+00 s  rtt 1e-06 s\n"
                   "mpisync rank 1: offset +1.000000e-03 s  rtt 1e-06 s\n")
    assert load_offsets(str(txt)) == {0: 0.0, 1: 0.001}


def test_progress_iterations_traced(tracing):
    """Progress-loop iterations that handle events become spans."""
    from ompi_tpu.runtime.progress import progress

    buf = np.zeros(1, np.float64)
    req = COMM_WORLD.Irecv(buf, source=0, tag=31)
    COMM_WORLD.Send(np.ones(1, np.float64), dest=0, tag=31)
    req.Wait()
    # drive one explicit poll so at least the idle path is exercised
    progress()
    names = {ev[2] for _tid, ev in trace.snapshot()}
    # the self-btl delivery may complete inline or through the progress
    # engine; either way the pml layers must have recorded
    assert "pml.send" in names
    assert "pml.recv" in names


# ------------------------------------------------------ the profiler sink
def _profile(tmp_path, body):
    """Run ``body`` under ``jax.profiler.start_trace``; the host events
    of the thread that ran it, as (name, start, end, stats)."""
    import glob

    d = str(tmp_path / "prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the host's C++ events only
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [ln for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines]
    mine = [ln for ln in lines if any(e.name == "unit.outer"
                                      for e in ln.events)]
    assert len(mine) == 1, [ln.name for ln in lines]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats)) for e in mine[0].events]


@pytest.fixture
def warm_world():
    """A 4-device world whose allreduce already runs its fast path."""
    world = mesh_world(jax.devices()[:4])
    x = world.shard(np.ones((4, 8), np.float32))
    world.allreduce(x).block_until_ready()
    world.allreduce(x).block_until_ready()
    return world, x


def test_profiler_sink_alone_puts_the_verb_span_in_the_device_trace(
        warm_world, tmp_path):
    """trace_enable off, a profiler collecting: one comm.allreduce in the
    .xplane.pb, inside the enclosing annotation and holding JAX's
    dispatch; nothing in the rings, no MPI_T event, no export file."""
    from ompi_tpu import mpit

    world, x = warm_world
    trace.reset()
    assert not trace.enabled()
    set_var("trace", "dir", str(tmp_path / "rings"))
    mpit.init_thread()
    seen = []
    h = mpit.event_handle_alloc(mpit.event_get_index("trace_span_begin"),
                                lambda inst: seen.append(inst))

    def body():
        assert trace.enabled()
        with jax.profiler.TraceAnnotation("unit.outer"):
            world.allreduce(x).block_until_ready()

    try:
        evs = _profile(tmp_path, body)
        trace._maybe_export()
    finally:
        h.free()
        mpit.finalize()
        set_var("trace", "dir", "")
    assert not trace.enabled()
    (outer,) = [e for e in evs if e[0] == "unit.outer"]
    spans = [e for e in evs if e[0] == "comm.allreduce"]
    assert len(spans) == 1
    (_, s, t, _), = spans
    assert outer[1] <= s and t <= outer[2]
    assert any(n.startswith("PjitFunction") and s <= a and b <= t
               for n, a, b, _ in evs)
    assert trace.snapshot() == [] and trace.buffered_events() == 0
    assert seen == []
    assert not (tmp_path / "rings").exists()


def test_both_sinks_get_the_span(tracing, warm_world, tmp_path):
    world, x = warm_world
    trace.reset()

    def body():
        with jax.profiler.TraceAnnotation("unit.outer"):
            world.allreduce(x).block_until_ready()
            with trace.step(3):
                pass

    evs = _profile(tmp_path, body)
    assert [e[0] for e in evs].count("comm.allreduce") == 1
    (step,) = [e for e in evs if e[0] == "trace.step"]
    assert step[3].get("step_num") == 3
    ring = [(ev[0], ev[2]) for _tid, ev in trace.snapshot()]
    assert ring.count(("B", "comm.allreduce")) == 1
    assert ring.count(("E", "comm.allreduce")) == 1
    assert ("B", "trace.step") in ring


def test_neither_sink_records_nothing(warm_world):
    world, x = warm_world
    trace.reset()
    assert not trace.enabled()
    with trace.span("unit.off", cat="test"):
        world.allreduce(x).block_until_ready()
    trace.record_span("unit.after", trace.now(), trace.now())
    trace.instant("unit.instant")
    trace.counter("unit.counter", 1)
    assert trace.snapshot() == [] and trace.buffered_events() == 0


def test_ring_only_events_stay_out_of_the_profiler(tmp_path):
    """Retroactive spans, instants and counters do not reach a profiler;
    with trace_enable off they record nothing at all."""
    trace.reset()

    def body():
        with jax.profiler.TraceAnnotation("unit.outer"):
            t0 = trace.now()
            trace.record_span("unit.after", t0, trace.now())
            trace.instant("unit.instant")
            trace.counter("unit.counter", 1)

    evs = _profile(tmp_path, body)
    assert {e[0] for e in evs} == {"unit.outer"}
    assert trace.buffered_events() == 0


def test_span_closes_the_sinks_it_opened(tracing):
    """A toggle inside a span leaves its B/E pairing whole: the sinks
    are chosen at enter and closed at exit."""
    with trace.span("unit.toggle", cat="test"):
        set_var("trace", "enable", False)
    set_var("trace", "enable", True)
    with trace.span("unit.late", cat="test"):
        pass
    ring = [(ev[0], ev[2]) for _tid, ev in trace.snapshot()]
    assert ring == [("B", "unit.toggle"), ("E", "unit.toggle"),
                    ("B", "unit.late"), ("E", "unit.late")]
    set_var("trace", "enable", False)
    trace.reset()
    with trace.span("unit.off", cat="test"):
        set_var("trace", "enable", True)
    assert trace.snapshot() == []
