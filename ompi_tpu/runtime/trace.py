"""Cross-layer span tracing with Chrome-trace/Perfetto export.

Reference points: the MPI-4 §14.3.8 event surface (mpit.py carries the
handle/callback side), OMPI's PERUSE request hooks, and the per-rank
timeline files the mpisync tool (ompi/tools/mpisync) exists to align.
Design here:

- **Spans**, not samples: every instrumented layer wraps its hot section
  in ``with trace.span("pml.send", ...)`` — nested begin/end ("ph": B/E)
  events carrying rank (pid), thread (tid), category, and args.
- **Lock-free recording**: each thread owns a pre-sized ring buffer
  (the reference analog: PERUSE/OTF2 per-thread event buffers). Append
  is a GIL-atomic list store — no lock, no allocation beyond the event
  tuple; when the ring wraps, the OLDEST events are overwritten and
  counted as dropped.
- **Two sinks, one gate**: the rings above, on while the live MCA Var
  ``trace_enable`` is (same discipline as spc.record — set_var stays
  live); and the JAX profiler, whenever a profiler session is
  collecting (``jax.profiler.start_trace``, TensorBoard, Perfetto). A
  span there is a ``jax.profiler.TraceAnnotation`` of the same name,
  stamped on the profiler's clock on the calling thread's line, so it
  lies on one timeline with the device ops; ``step`` is a
  ``StepTraceAnnotation``. ``trace.enabled()`` is true while either
  sink is on; instrumentation sites guard with ``if trace.enabled():``
  so the disabled fast path costs one branch, one attribute load and
  the profiler's own C++ check.
- **MPI_T integration**: span begin/end also fire the ``trace_span_begin``
  / ``trace_span_end`` MPI_T event types (mpit.py), so a tool attached
  through the MPI_T surface sees the identical stream without touching
  the file exporter. A tool can flip the ``trace_enable`` cvar through
  an MPI_T cvar handle to turn the stream on at runtime.
- **Export at finalize**: one valid Chrome-trace JSON file per rank
  (``trace-rank<N>.json`` in ``trace_dir``), loadable in Perfetto /
  chrome://tracing. ``tools/trace_merge.py`` merges multi-rank files
  onto a shared timeline using mpisync clock offsets; timestamps are
  ``time.monotonic_ns`` so the offsets apply directly.

Enable the rings with ``OMPI_TPU_MCA_trace_enable=1`` (or ``--mca
trace_enable 1`` through mpirun, or ``set_var("trace", "enable",
True)``); the profiler sink needs no var. Only spans reach the
profiler: retroactive spans, instants and counters are ring-only.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ompi_tpu.mca.var import register_var, register_pvar
from ompi_tpu.utils.show_help import register_topic, show_help

register_topic(
    "trace", "ring-overflow",
    "The trace ring buffers wrapped: {dropped} events were overwritten\n"
    "before export (oldest first) — the exported timeline is TRUNCATED\n"
    "at its old end. Raise --mca trace_buffer_events (currently {cap}\n"
    "events per thread) or trace a shorter window. The exact count is\n"
    "also in the export's otherData.dropped_events field and the\n"
    "trace_dropped_events pvar.")

_enable_var = register_var(
    "trace", "enable", False,
    help="Record cross-layer spans into per-thread ring buffers and "
         "export Chrome-trace JSON at finalize", level=3)
_dir_var = register_var(
    "trace", "dir", "", typ=str,
    help="Directory for the per-rank trace-rank<N>.json export. Empty "
         "(default) = a per-job subdir of the system temp dir "
         "(ompi-tpu-trace-<launcher pid>) — NOT the CWD, which "
         "littered repo checkouts with trace files every procmode run "
         "(the metrics_dir PR 13 fix, applied to traces). "
         "tools/trace_merge.py finds the newest such dir by default "
         "(mpidiag reads stall dumps under metrics_dir, not here); "
         "point this somewhere durable to keep exports", level=3)
_cap_var = register_var(
    "trace", "buffer_events", 65536,
    help="Ring-buffer capacity (events) per thread; the oldest events "
         "are overwritten (and counted dropped) when a ring wraps",
    level=5)


def _profiler_probe() -> bool:
    """The profiler sink's gate until ``jax.profiler`` is loaded: no
    session can be collecting before then. Once it is, this rebinds
    ``_profiling`` to ``TraceAnnotation.is_enabled`` (a C++ static)
    and ``_Annotation``/``_StepAnnotation`` to the profiler's context
    managers — the package itself never imports JAX for tracing."""
    global _profiling, _Annotation, _StepAnnotation
    prof = sys.modules.get("jax.profiler")
    if prof is None or not hasattr(prof, "StepTraceAnnotation"):
        return False  # absent, or still being imported
    _Annotation = prof.TraceAnnotation
    _StepAnnotation = prof.StepTraceAnnotation
    _profiling = _Annotation.is_enabled
    return _profiling()


_profiling = _profiler_probe
_Annotation = _StepAnnotation = None


def enabled() -> bool:
    """True while either sink is on: one attribute load off the live Var
    (spc.record discipline), then the profiler's own check —
    instrumentation sites guard their span setup with this."""
    return _enable_var._value or _profiling()


def now() -> int:
    """Trace clock: monotonic ns, the same base mpisync measures offsets
    against, so trace_merge can shift ranks onto rank 0's timeline."""
    return time.monotonic_ns()


# ------------------------------------------------------------------ rings
class _Ring:
    __slots__ = ("buf", "cap", "pos", "full", "dropped", "tid")

    def __init__(self, cap: int, tid: int):
        self.buf: List[Optional[tuple]] = [None] * cap
        self.cap = cap
        self.pos = 0
        self.full = False
        self.dropped = 0
        self.tid = tid


_reg_lock = threading.Lock()
_rings: List[_Ring] = []
_tls = threading.local()


def _ring() -> _Ring:
    r = getattr(_tls, "ring", None)
    if r is None:
        cap = max(int(_cap_var._value), 16)
        r = _Ring(cap, threading.get_ident())
        with _reg_lock:
            _rings.append(r)
        _tls.ring = r
    return r


def _record(ph: str, name: str, cat: str, ts: int,
            args: Optional[Dict[str, Any]]) -> None:
    """Append one event. GIL-atomic list store: no lock on the hot path
    (each thread writes only its own ring; export snapshots under the
    registry lock)."""
    r = _ring()
    buf = r.buf
    pos = r.pos
    if pos >= len(buf):  # a concurrent reset() shrank the ring
        pos = 0
    if r.full:
        r.dropped += 1
    buf[pos] = (ph, ts, name, cat, args)
    pos += 1
    if pos >= len(buf):
        r.full = True
        pos = 0
    r.pos = pos


# ------------------------------------------------------------------ spans
class span:
    """``with trace.span("coll.xla.dispatch", cat="coll", verb="allreduce")``
    — into the rings, a B event at enter and an E at exit, mirrored onto
    the MPI_T event stream; into a collecting profiler, a
    ``TraceAnnotation(name, **args)``. Call sites guard with ``if
    trace.enabled():`` so construction only happens when tracing is on.
    The sinks are chosen once, at enter, and the same ones closed at
    exit: a mid-span toggle of either must not break B/E pairing."""

    __slots__ = ("name", "cat", "args", "_ring", "_ann")

    def __init__(self, name: str, cat: str = "", **args: Any):
        self.name = name
        self.cat = cat
        self.args = args or None

    def _annotation(self):
        return _Annotation(self.name, **(self.args or {}))

    def __enter__(self):
        self._ring = _enable_var._value
        if self._ring:
            _record("B", self.name, self.cat, time.monotonic_ns(),
                    self.args)
            _emit_mpit("span_begin", self.name, self.cat)
        self._ann = None
        if _profiling():
            self._ann = self._annotation()
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._ring:
            _record("E", self.name, self.cat, time.monotonic_ns(), None)
            _emit_mpit("span_end", self.name, self.cat)
        return False


class _StepSpan(span):
    """``trace.step``'s span: a ``StepTraceAnnotation`` in the profiler,
    so its step view cuts where tools/mpicrit.py does."""

    __slots__ = ()

    def _annotation(self):
        return _StepAnnotation(self.name, step_num=self.args["step"])


def step(n: int) -> "span":
    """Step marker: ``with trace.step(n):`` brackets ONE training or
    serving step on this rank. tools/mpicrit.py cuts the merged
    cross-rank timeline at these spans and walks each step's critical
    path, so every rank must bracket the SAME logical step with the
    same ``n`` (serve/harness drives this automatically from its state
    step counter; examples/bench call it around their own loops). Call
    sites guard with ``if trace.enabled():`` like any span site."""
    return _StepSpan("trace.step", cat="step", step=int(n))


def record_span(name: str, t0: int, t1: int, cat: str = "",
                **args: Any) -> None:
    """Retroactive span from saved ``now()`` timestamps — for sites that
    only decide to record after the fact (a progress iteration that
    handled zero events is noise; one that delivered is signal).
    Ring-only: the profiler takes no event after the fact."""
    if not _enable_var._value:
        return
    _record("B", name, cat, t0, args or None)
    _record("E", name, cat, t1, None)
    _emit_mpit("span_begin", name, cat)
    _emit_mpit("span_end", name, cat)


def instant(name: str, cat: str = "", **args: Any) -> None:
    """Point event ("ph": "i") — one-off occurrences, not durations.
    Ring-only."""
    if _enable_var._value:
        _record("i", name, cat, time.monotonic_ns(), args or None)


def counter(name: str, value, cat: str = "") -> None:
    """Counter track ("ph": "C"): Perfetto renders these as a graph.
    Ring-only."""
    if _enable_var._value:
        _record("C", name, cat, time.monotonic_ns(), {name: value})


def wrap_span(name: str, cat: str, fn):
    """Wrap a callable in a span — the verb-layer hook for dispatch
    tables that hand the function out rather than calling it inline."""

    def traced(*a, **kw):
        with span(name, cat):
            return fn(*a, **kw)

    return traced


def _emit_mpit(kind: str, name: str, cat: str) -> None:
    from ompi_tpu import mpit

    # GIL-safe unlocked probe first: emit() takes the process-global
    # event lock even with no subscribers, which would serialize every
    # span across threads — exactly what the per-thread rings avoid
    if mpit._event_handles.get(f"trace_{kind}"):
        mpit.emit("trace", kind, name=name, cat=cat)


# ----------------------------------------------------------------- export
def _rank() -> int:
    # UNIVERSE rank (job base + local rank): a respawned replacement is
    # world rank 0 of ITS spawn job but shares the parent job's export
    # dirs — keying exports by the local rank made its
    # stall/metrics/trace files collide with the original rank 0's
    # (last writer wins, the replacement's forensics evidence vanished
    # — found triaging the preempt soak seeds). Universe ranks are also
    # what mpidiag's blame edges name, so the merged walk can reach the
    # replacement's dump.
    try:
        base = int(os.environ.get("OMPI_TPU_BASE", "0"))  # mpilint: disable=raw-environ — job-offset identity for the export filename
        return base + int(os.environ.get("OMPI_TPU_RANK", "0"))  # mpilint: disable=raw-environ — rank identity for the export filename
    except ValueError:
        return 0


def _collect() -> List[Tuple[int, tuple]]:
    """(tid, event) pairs from every ring, oldest-first per ring."""
    with _reg_lock:
        rings = list(_rings)
    out = []
    for r in rings:
        # snapshot: ring order is [pos:] + [:pos] once wrapped
        evs = (r.buf[r.pos:] + r.buf[:r.pos]) if r.full \
            else r.buf[:r.pos]
        out.extend((r.tid, ev) for ev in evs if ev is not None)
    return out


def _sanitize(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Enforce well-formed B/E pairing per (pid, tid). Ring overwrite can
    evict a B whose E survives (drop the E) or an E whose B survives
    (close the B synthetically at the last seen timestamp) — the export
    must stay loadable either way."""
    events.sort(key=lambda e: e["ts"])
    out: List[Dict[str, Any]] = []
    stacks: Dict[tuple, List[Dict[str, Any]]] = {}
    last_ts = 0.0
    for ev in events:
        last_ts = max(last_ts, ev["ts"])
        ph = ev["ph"]
        if ph not in ("B", "E"):
            out.append(ev)
            continue
        key = (ev["pid"], ev["tid"])
        stack = stacks.setdefault(key, [])
        if ph == "B":
            stack.append(ev)
            out.append(ev)
        else:
            if stack and stack[-1]["name"] == ev["name"]:
                stack.pop()
                out.append(ev)
            # else: orphan E (its B was evicted) — drop it
    for stack in stacks.values():
        for b in reversed(stack):  # innermost closes first
            out.append({"name": b["name"], "cat": b["cat"], "ph": "E",
                        "ts": last_ts, "pid": b["pid"], "tid": b["tid"]})
    return out


def default_trace_dir() -> str:
    """Where exports land when ``trace_dir`` is unset: a per-JOB subdir
    of the system temp dir, keyed by the launcher pid so every rank of
    one mpirun shares it and tools/trace_merge.py can merge the rank
    files (the metrics.default_snapshot_dir discipline — two concurrent
    jobs on one host must not overwrite each other's trace-rank0.json);
    singletons key by their own pid."""
    import tempfile

    job = os.environ.get("OMPI_TPU_LAUNCHER_PID") or str(os.getpid())  # mpilint: disable=raw-environ — launcher/job identity (the wireup pdeathsig key), not config
    return os.path.join(tempfile.gettempdir(), f"ompi-tpu-trace-{job}")


def export(path: Optional[str] = None) -> str:
    """Write everything recorded so far as Chrome-trace JSON (the
    "JSON Object Format": traceEvents + metadata); returns the path."""
    rank = _rank()
    if path is None:
        base = _dir_var._value or default_trace_dir()
        try:
            os.makedirs(base, exist_ok=True)
        except OSError:
            base = "."  # unwritable temp dir: last-resort CWD
        path = os.path.join(base, f"trace-rank{rank}.json")
    events = []
    for tid, (ph, ts, name, cat, args) in _collect():
        ev: Dict[str, Any] = {"name": name, "cat": cat or "default",
                              "ph": ph, "ts": ts / 1000.0,
                              "pid": rank, "tid": tid}
        if args:
            ev["args"] = args
        events.append(ev)
    events = _sanitize(events)
    with _reg_lock:
        tids = sorted({r.tid for r in _rings})
        dropped = sum(r.dropped for r in _rings)
    meta: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": rank,
        "args": {"name": f"rank {rank}"}}]
    for tid in tids:
        meta.append({"name": "thread_name", "ph": "M", "pid": rank,
                     "tid": tid, "args": {"name": f"thread-{tid}"}})
    doc = {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": {"rank": rank, "dropped_events": dropped,
                      "clock": "monotonic_ns"},
    }
    # atomic rename (shared writer discipline, utils/fsio): the
    # abort/fatal path (export_on_fatal) and the finalize export may
    # both write this file, and a merge tool must never read a torn
    # one. default=str: span args are arbitrary caller values (numpy
    # ints ride in from user tags/counts) — stringify anything JSON
    # can't take rather than lose the rank's whole trace to a TypeError
    from ompi_tpu.utils.fsio import atomic_write_json

    return atomic_write_json(path, doc, default=str)


def snapshot() -> List[Tuple[int, tuple]]:
    """Raw (tid, event) view for tests/tools."""
    return _collect()


def dropped_events() -> int:
    with _reg_lock:
        return sum(r.dropped for r in _rings)


def _warn_overflow() -> int:
    """show_help the ring-overflow banner when events were lost; returns
    the dropped count (the export's otherData.dropped_events mirror)."""
    d = dropped_events()
    if d:
        show_help("trace", "ring-overflow", dropped=d,
                  cap=int(_cap_var._value))
    return d


def buffered_events() -> int:
    with _reg_lock:
        return sum(r.cap if r.full else r.pos for r in _rings)


def reset() -> None:
    """Clear every ring (and re-size to the current buffer_events cvar).
    Rings stay registered so threads keep their thread-local handle."""
    cap = max(int(_cap_var._value), 16)
    with _reg_lock:
        for r in _rings:
            r.cap = cap
            r.buf = [None] * cap
            r.pos = 0
            r.full = False
            r.dropped = 0


register_pvar("trace", "dropped_events", dropped_events,
              help="Events lost to ring-buffer wrap across all threads")
register_pvar("trace", "buffered_events", buffered_events,
              help="Events currently held in the trace ring buffers")

_exported = False
_fatal_exporting = [False]


def export_on_fatal() -> None:
    """Abort/fatal-path export: flush the flight-recorder rings NOW.

    A clean exit reaches :func:`_maybe_export` through finalize/atexit,
    but an ``os._exit`` after MPI_Abort — or an unhandled exception
    killing the progress thread just before the job is torn down —
    never runs atexit, and the entire ring was lost. Re-entrancy
    guarded (an export failure aborting again must not recurse), never
    raises, and does NOT mark the finalize export done: a later clean
    export holds strictly more events and atomically replaces this
    file."""
    with _reg_lock:
        if _fatal_exporting[0]:
            return
        _fatal_exporting[0] = True
    try:
        if not buffered_events():
            return
        try:
            _warn_overflow()
        except Exception:
            pass
        export()
    except Exception:
        pass  # evidence is best-effort on the way down
    finally:
        with _reg_lock:
            _fatal_exporting[0] = False


def _maybe_export() -> None:
    """Finalize/exit hook: export once, whenever anything was recorded —
    a tool may have enabled tracing for a window through an MPI_T cvar
    handle and flipped it back off; those buffered spans must not be
    silently discarded because the cvar reads False at exit."""
    global _exported
    if _exported or not buffered_events():
        return
    _exported = True
    try:
        # silent truncation must be visible — but a broken stderr
        # (atexit with the pipe reader gone) must not cost the export
        _warn_overflow()
    except Exception:
        pass
    try:
        export()
    except Exception:
        # never let a trace-export failure poison finalize/atexit
        import traceback

        traceback.print_exc()


from ompi_tpu.hook import register_hook  # noqa: E402

register_hook("finalize_bottom", _maybe_export)
# mesh-mode scripts never call Finalize (no Init to match) — atexit is
# their export path. Registered at import: state.py's atexit Finalize is
# registered later, so (LIFO) Finalize-time spans land before we export.
atexit.register(_maybe_export)
