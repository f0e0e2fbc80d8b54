"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

The trace is read through ``jax.profiler.ProfileData``. What comes out:

- device ops: every operation that ran on a device, with its device,
  op name, module and interval. On a TPU these are the events of each
  ``/device:TPU:<n>`` plane's "XLA Ops" and "Async XLA Ops" lines,
  named by their HLO text; on the CPU backend (the tests), host-thread
  events that carry an ``hlo_op`` stat.
- host spans: the benchmark's own ``jax.profiler.TraceAnnotation``s,
  whose names start with ``bench.``, with their arguments.
- host events of the thread that holds those spans, to say what the
  host was doing during a device's idle gap.

Times are in nanoseconds on the host's clock: each device's events are
moved onto it by an offset that the runs' host-side enqueue and
completion events bound. Busy time is the union of a device's op
intervals, averaged over the devices.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional

SPAN_PREFIX = "bench."
# a TPU plane's lines of operations: synchronous ones, and the
# asynchronous copies and collectives that run beside them
DEVICE_LINES = ("XLA Ops", "Async XLA Ops")


class Op(NamedTuple):
    device: str
    name: str    # the HLO instruction's name, e.g. "fusion.12"
    module: str  # e.g. "jit_step_local"
    start: float
    end: float
    hlo: str     # the instruction's text, kept for custom calls only
    is_async: bool  # an asynchronous copy or collective, in flight


def _op_name(text: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12'."""
    return text.split(" = ", 1)[0].lstrip("%")


def kind_of(name: str) -> str:
    """An op name without its numeric suffix: 'fusion.12' -> 'fusion'."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _op(device: str, text: str, module: str, start: float, end: float,
        is_async: bool = False):
    return Op(device, _op_name(text), module.split("(", 1)[0], start, end,
              text if "custom-call" in text else "", is_async)


class Span(NamedTuple):
    name: str
    start: float
    end: float
    args: Dict[str, object]


class HostEvent(NamedTuple):
    name: str
    start: float
    end: float


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except Exception:  # an event whose stats the reader cannot decode
        return {}


def _union(intervals: Iterable) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(starts, ends, before, t: float) -> float:
    """Busy time up to ``t`` of merged intervals."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return 0.0
    return before[i] + min(ends[i], t) - starts[i]


class Trace:
    """Device ops, the benchmark's host spans and the host events of
    the thread that holds them."""

    def __init__(self, ops: List[Op], spans: List[Span],
                 host: List[HostEvent]):
        self.ops = sorted(ops, key=lambda o: o.start)
        self.spans = sorted(spans, key=lambda s: s.start)
        self.host = sorted(host, key=lambda h: h.start)
        self._host_starts = [h.start for h in self.host]
        self._span_starts = [s.start for s in self.spans]
        # each span's innermost enclosing span (-1: none), by a sweep
        self._parent, stack = [], []
        for i, s in enumerate(self.spans):
            while stack and self.spans[stack[-1]].end < s.start:
                stack.pop()
            self._parent.append(stack[-1] if stack else -1)
            stack.append(i)
        self.devices = sorted({o.device for o in self.ops})
        self._busy = {d: _union((o.start, o.end) for o in self.ops
                                if o.device == d) for d in self.devices}
        # per device: interval starts, ends, and busy time before each
        self._index = {}
        for d, ivs in self._busy.items():
            before, acc = [], 0.0
            for s, e in ivs:
                before.append(acc)
                acc += e - s
            self._index[d] = ([s for s, _ in ivs], [e for _, e in ivs],
                              before)

    # ----------------------------------------------------------- spans
    def spans_named(self, name: str, **args) -> List[Span]:
        """Spans called ``name`` whose arguments include ``args``."""
        return [s for s in self.spans if s.name == name and all(
            s.args.get(k) == v for k, v in args.items())]

    # ------------------------------------------------------------ busy
    def busy_ns(self, t0: float, t1: float) -> float:
        """Time in [t0, t1] in which an op ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for starts, ends, before in self._index.values():
            tot += _covered(starts, ends, before, t1) - \
                _covered(starts, ends, before, t0)
        return tot / len(self.devices)

    def busy_in(self, spans: Iterable[Span]) -> float:
        """Busy time summed over disjoint spans."""
        return sum(self.busy_ns(s.start, s.end) for s in spans)

    def op_ns(self, t0: float, t1: float,
              match: Callable[[Op], bool]) -> float:
        """Summed duration of the ops in [t0, t1] that ``match``,
        averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(o.end - o.start for o in self.ops
                  if o.start >= t0 and o.end <= t1 and match(o))
        return tot / len(self.devices)

    # ------------------------------------------------------- breakdown
    def top_ops(self, t0: float, t1: float, n: int = 10):
        """[(op kind, seconds)] of the kinds of synchronous op that
        took most time in [t0, t1], per device on average."""
        tot: Dict[str, float] = collections.Counter()
        for o in self.ops:
            if o.end > t0 and o.start < t1 and not o.is_async:
                tot[kind_of(o.name)] += min(o.end, t1) - max(o.start, t0)
        k = max(len(self.devices), 1)
        return [[name, v / k / 1e9] for name, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def _doing(self, t: float) -> str:
        """What the host thread was doing at ``t``: the innermost host
        event that covers it, under the innermost benchmark span."""
        j = bisect.bisect_right(self._span_starts, t) - 1
        while j >= 0 and (self.spans[j].end < t or
                          self.spans[j].name == SPAN_PREFIX + "window"):
            j = self._parent[j]
        span = self.spans[j] if j >= 0 else None
        i = bisect.bisect_right(self._host_starts, t)
        inner = None
        for h in reversed(self.host[max(0, i - 256):i]):
            if h.end >= t:
                inner = h
                break
        where = "no span" if span is None else span.name + (
            f"[{span.args['phase']}]" if "phase" in span.args else "")
        return f"{where} > {inner.name if inner else 'no host event'}"

    def idle_gaps(self, t0: float, t1: float, n: int = 10):
        """[(what the host was doing, seconds)]: the devices' idle time
        in [t0, t1], summed by the host's activity at each gap's
        middle, per device on average, longest first."""
        tot: Dict[str, float] = collections.Counter()
        for ivs in self._busy.values():
            prev = t0
            for s, e in ivs + [[t1, t1]]:
                s, e = max(s, t0), min(e, t1)
                if e < t0:
                    continue
                if s > prev:
                    tot[self._doing((prev + s) / 2)] += s - prev
                prev = max(prev, e)
                if prev >= t1:
                    break
        k = max(len(self.devices), 1)
        return [[name, v / k / 1e9] for name, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _device_of(plane_name: str) -> Optional[str]:
    """'/device:TPU:3' -> 'TPU:3'; None for host and non-core planes."""
    if not plane_name.startswith("/device:"):
        return None
    rest = plane_name[len("/device:"):]
    parts = rest.split(":")
    if len(parts) != 2 or not parts[1].isdigit():
        return None
    return rest


def _offsets(modules, enqueued, completed) -> Dict[str, float]:
    """Each device clock's offset from the host clock, in ns. A run's
    module starts on the device after the host enqueued it and ends
    before the host hears that it completed; over all runs that bounds
    the offset from both sides, and the middle is taken. (A v5e's four
    device clocks read 1.6-1.8 ms early against the host: chip run,
    PR 22.)"""
    out = {}
    for dev, mods in modules.items():
        ordinal = int(dev.split(":")[1])
        lo, hi = [], []
        for run, start, end, _ in mods:
            key = (run, ordinal)
            if key in enqueued and key in completed:
                hi.append(start - enqueued[key])
                lo.append(end - completed[key])
        out[dev] = (max(lo) + min(hi)) / 2 if lo else 0.0
    return out


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``; device times are moved
    onto the host's clock."""
    ops: List[Op] = []
    spans: List[Span] = []
    threads: Dict[str, List[HostEvent]] = {}
    span_thread = None
    modules: Dict[str, list] = {}
    device_events: Dict[str, list] = {}
    enqueued: Dict[tuple, float] = {}
    completed: Dict[tuple, float] = {}
    for plane in pd.planes:
        dev = _device_of(plane.name)
        if dev is not None:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = sorted(
                        (_stats(e).get("run_id"), e.start_ns,
                         e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
                elif line.name in DEVICE_LINES:
                    device_events.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         line.name != "XLA Ops") for e in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = threads.setdefault(plane.name + "/" + line.name, [])
            for e in line.events:
                st = _stats(e)
                end = e.start_ns + e.duration_ns
                if "hlo_op" in st:
                    ops.append(_op(f"cpu:{st.get('device_ordinal', 0)}",
                                   e.name, str(st.get("hlo_module", "")),
                                   e.start_ns, end))
                    continue
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, e.start_ns, end, st))
                    span_thread = plane.name + "/" + line.name
                    continue
                if "run_id" in st and "device_ordinal" in st:
                    key = (st["run_id"], st["device_ordinal"])
                    if e.name == "DoEnqueueProgram":
                        enqueued[key] = e.start_ns
                    elif e.name == "CompleteCallbacks":
                        completed[key] = e.start_ns
                evs.append(HostEvent(e.name, e.start_ns, end))
    offsets = _offsets(modules, enqueued, completed)
    for dev, evs in device_events.items():
        off = offsets.get(dev, 0.0)
        mods = [(s - off, e - off, name) for _, s, e, name in
                modules.get(dev, [])]
        starts = [m[0] for m in mods]
        for text, s, e, is_async in evs:
            s, e = s - off, e - off
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
            ops.append(_op(dev, text, mod, s, e, is_async))
    host = threads.get(span_thread, []) if span_thread else []
    return Trace(ops, spans, host)


def xplane_file(logdir: str) -> str:
    """The newest ``.xplane.pb`` under ``logdir``."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(xplane_file(logdir)))
