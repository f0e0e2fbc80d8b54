"""The harness: finds a cell's files by name, runs its driver once, and
assembles the result line.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration, whose file BENCHMARK.json gives, and a traffic mix,
``benchmark/traffic/<traffic>.json``, whose ``driver`` key names
``benchmark/drivers/<driver>.py``. The limits of its correctness check
are in ``benchmark/cells/<cell>.json``. Each per-layer metric is read
by ``benchmark/metrics/<metric>.py``. Adding any of them adds files and
edits none.

A driver module has three functions:

- ``setup(ctx) -> state``: build, place and warm up everything the
  window uses, and drive the timed path through the steps that are
  checked. Counted as set-up.
- ``measure(ctx, state) -> record``: the measured window, inside
  ``ctx.span("window")``. The record holds ``window_s``, ``attempted``,
  ``failed`` and ``metrics`` (end-to-end, by name), and whatever its
  per-layer readers need.
- ``check(ctx, state, record) -> [Check]``: after the window, with the
  program's state freed, compare with the plain reference.

A driver may also have ``traced(ctx, state, record)``: work that only
the traced run does after the window, such as a comparator.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Check(NamedTuple):
    """One number compared with the reference, and its limit: the run
    is correct where ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name`` in ``root``'s BENCHMARK.json, with its
    configuration, traffic, limits and metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in moved and _reports(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(root, cfg_entry["file"])),
        traffic=_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(HERE, "cells", name + ".json"))["limits"],
        end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, loaded by path (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prepare_jax(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program the run compiles, however small."""
    path = os.path.join(root, ".jax_cache")
    # the program's own cache helper takes this directory from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; exits non-zero, printing no
    result, where JAX finds no TPU or fewer chips."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"benchmark: no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU: JAX reports platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX reports {len(devs)}")
    return devs[:chips]


class _Compiles:
    """Counts the executables JAX compiles or loads from its cache."""

    def __init__(self):
        import jax

        self.n = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Context:
    """What a driver gets: the cell, the seed, the window's length, the
    devices, and spans that name its calls in the traced run."""

    def __init__(self, cell: Cell, devices, seed: int, seconds: float,
                 trace: bool):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.limits = cell.limits
        self.devices = list(devices)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self._null = contextlib.nullcontext()
        if self.trace:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def span(self, name: str, **args):
        """A host span ``bench.<name>`` in the traced run; nothing
        otherwise."""
        if not self.trace:
            return self._null
        return self._annotation("bench." + name, **args)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell: Cell, devices, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """One run of ``cell``: set-up, the window, the traced extras, the
    correctness check and, with ``trace``, the per-layer readers.
    ``t_start`` is the process's start on the ``perf_counter`` clock."""
    import jax

    driver = load_module("drivers", cell.traffic["driver"])
    ctx = Context(cell, devices, seed, seconds, trace)
    compiles = _Compiles()
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    before = compiles.n
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        record = driver.measure(ctx, state)
        in_window = compiles.n - before
        if trace and hasattr(driver, "traced"):
            driver.traced(ctx, state, record)
    finally:
        if trace:
            # the device's last events reach the trace some time after
            # they ran (seen on a v5e: the last 1.5 ms were missing)
            time.sleep(0.5)
            jax.profiler.stop_trace()
    mem = memory_peak(devices)
    t_check = time.perf_counter()
    checks = driver.check(ctx, state, record)
    check_s = time.perf_counter() - t_check
    del state
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        metrics, breakdown = _read_trace(cell, logdir, record, device)
        shutil.rmtree(logdir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": record["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    _say(f"benchmark: {cell.name} seed {seed}: set-up {setup_s!r} s, "
         f"window {record['window_s']!r} s, {record['attempted']} "
         f"attempted, {record['failed']} failed, "
         f"{in_window} compiles in the window, check {check_s!r} s")
    for c in checks:
        _say(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
             f"{'ok' if c.ok else 'FAIL'}")
    out = {"correct": bool(checks) and all(c.ok for c in checks),
           "attempted": record["attempted"], "failed": record["failed"],
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def _read_trace(cell: Cell, logdir: str, record, device):
    from benchmark import trace_reduce

    tr = trace_reduce.load(logdir)
    win = tr.spans_named("bench.window")
    if not win or not tr.devices:
        raise RuntimeError("the trace holds no window span or no device op")
    t0, t1 = win[0].start, win[0].end
    device["busy_s"] = tr.busy_ns(t0, t1) / 1e9
    device["window_s"] = (t1 - t0) / 1e9
    metrics = {}
    for m in cell.per_layer:
        reader = load_module("metrics", m["name"])
        value = reader.read(tr, record, cell, device)
        if value is None:
            continue
        if isinstance(value, dict):
            metrics[m["name"]] = dict(value, unit=m["unit"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, {"device_ops": tr.top_ops(t0, t1),
                     "idle_gaps": tr.idle_gaps(t0, t1)}
