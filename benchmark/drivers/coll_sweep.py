"""OSU-style sweep of one blocking MPI collective over the mesh-mode
world communicator (``parallel.mesh_world()``), from one caller in a
closed loop.

Traffic keys: ``verb``; ``dtype`` (the configuration's MPI datatype);
``phases``, each with its per-rank sizes in bytes and the end-to-end
metric it feeds (``busbw``: bus bytes over call time, in GB/s;
``latency_us``: call time per call); ``block_s``, the least length of a
block of back-to-back calls of one phase (the phases alternate block by
block; sizes come in a seeded order within each round of a block);
``value_bound``, the magnitude of the seeded integer data, small enough
that every float32 sum over the ranks is exact; ``samples``, ranges of
call numbers from which the check draws the answers it keeps; and
``comparator``, the raw XLA collective the traced run times beside the
library at one size.

Every call is timed as a whole, from the verb call to the return of
``block_until_ready``; a block's time is read once, over all its calls.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict

import numpy as np

from benchmark import flops
from benchmark.harness import Check
from benchmark.reference import coll as ref


def _shape(verb: str, n: int, elems: int):
    if verb == "alltoall":
        return (n, n, elems // n)
    return (n, elems)


def setup(ctx) -> Dict[str, Any]:
    from ompi_tpu.parallel import mesh_world

    t = ctx.traffic
    verb = t["verb"]
    world = mesh_world(ctx.devices)
    n = len(ctx.devices)
    stated = np.dtype(t["dtype"])
    compute = t.get("compute_dtype", t["dtype"])
    bound = int(t["value_bound"])
    if n * bound > 2 ** 24:
        raise ValueError("value_bound too large for exact float32 sums")
    elems = {b: b // stated.itemsize
             for ph in t["phases"].values() for b in ph["bytes"]}
    rng = np.random.default_rng(ctx.seed)
    host = rng.integers(-bound, bound, size=(n, max(elems.values())),
                        dtype=np.int32).astype(stated)
    fn = getattr(world, verb)
    root = 0
    if verb == "bcast":
        root = int(rng.integers(n))
        fn = functools.partial(world.bcast, root=root)
    phases = []
    order_rng = np.random.default_rng([ctx.seed, 1])
    pick_rng = np.random.default_rng([ctx.seed, 2])
    for name, ph in t["phases"].items():
        sizes = list(ph["bytes"])
        xs = [world.shard(host[:, :elems[b]].reshape(
            _shape(verb, n, elems[b])).astype(compute)) for b in sizes]
        for x in xs:  # compile, then run once from the executable cache
            fn(x).block_until_ready()
            fn(x).block_until_ready()
        phases.append({
            "name": name, "metric": ph["metric"], "kind": ph["kind"],
            "sizes": sizes, "xs": xs,
            "bus": [flops.coll_bus_bytes(verb, b, n) for b in sizes],
            "orders": [order_rng.permutation(len(sizes)).tolist()
                       for _ in range(256)],
            "picks": [{int(pick_rng.integers(lo, hi + 1))
                       for lo, hi in t["samples"]} for _ in sizes],
        })
    st = {"world": world, "fn": fn, "verb": verb, "n": n, "root": root,
          "host": host, "elems": elems, "phases": phases}
    cmp = t.get("comparator")
    if ctx.trace and cmp:
        st["raw"] = _raw(world, verb)
        st["cmp_x"] = world.shard(host[:, :elems[cmp["bytes"]]].reshape(
            _shape(verb, n, elems[cmp["bytes"]])).astype(compute))
        st["raw"](st["cmp_x"]).block_until_ready()
        fn(st["cmp_x"]).block_until_ready()
    return st


def _raw(world, verb: str):
    """The raw XLA collective the library's verb lowers to."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    if verb != "allreduce":
        raise ValueError(f"no raw comparator for verb {verb!r}")
    axis = world.axis

    def raw_psum(b):
        return lax.psum(b, axis)

    return jax.jit(jax.shard_map(raw_psum, mesh=world.mesh,
                                 in_specs=P(axis), out_specs=P(axis)))


def _block(ph, fn, verb, span, block_s, kept, last, perf):
    """Whole rounds of one phase's sizes, each round in its seeded order,
    back to back until ``block_s`` has passed; adds the block to the
    phase's totals and keeps the answers the check will compare."""
    name, xs, sizes = ph["name"], ph["xs"], ph["sizes"]
    orders, picks, bus, cnt = ph["orders"], ph["picks"], ph["bus"], \
        ph["count"]
    enq = busb = 0.0
    calls = 0
    with span("block", phase=name):
        b0 = perf()
        while True:
            order = orders[ph["rounds"] % len(orders)]
            ph["rounds"] += 1
            for i in order:
                with span("call", verb=verb, bytes=sizes[i], phase=name):
                    c0 = perf()
                    out = fn(xs[i])
                    enq += perf() - c0
                    out.block_until_ready()
                cnt[i] += 1
                if cnt[i] in picks[i]:
                    kept.append((name, i, out))
                last[name, i] = out
                busb += bus[i]
            calls += len(order)
            if perf() - b0 >= block_s:
                break
        b1 = perf()
    tot = ph["totals"]
    tot["time_s"] += b1 - b0
    tot["calls"] += calls
    tot["bus_bytes"] += busb
    tot["enqueue_s"] += enq
    tot["blocks"] += 1
    return b1


def measure(ctx, st) -> Dict[str, Any]:
    """The phases' blocks in turn until ``seconds`` have passed."""
    block_s, perf = float(ctx.traffic["block_s"]), time.perf_counter
    for ph in st["phases"]:
        ph.update(rounds=0, count=[0] * len(ph["sizes"]),
                  totals={"time_s": 0.0, "calls": 0, "bus_bytes": 0.0,
                          "enqueue_s": 0.0, "blocks": 0})
    kept, last = [], {}
    with ctx.span("window"):
        w0 = perf()
        done = False
        while not done:
            for ph in st["phases"]:
                b1 = _block(ph, st["fn"], st["verb"], ctx.span, block_s,
                            kept, last, perf)
                if b1 - w0 >= ctx.seconds:
                    done = True
                    break
        window_s = perf() - w0
    kept.extend((name, i, out) for (name, i), out in last.items())
    totals = {ph["name"]: ph["totals"] for ph in st["phases"]}
    metrics = {}
    for ph in st["phases"]:
        tot = ph["totals"]
        if ph["kind"] == "busbw":
            metrics[ph["metric"]] = tot["bus_bytes"] / tot["time_s"] / 1e9
        else:
            metrics[ph["metric"]] = tot["time_s"] / tot["calls"] * 1e6
    st["kept"] = kept
    return {"window_s": window_s, "metrics": metrics, "phases": totals,
            "attempted": sum(t["calls"] for t in totals.values()),
            "failed": 0, "ranks": st["n"], "verb": st["verb"]}


def traced(ctx, st, record) -> None:
    """The library's verb beside the raw collective at one size,
    interleaved, after the window."""
    cmp = ctx.traffic.get("comparator")
    if not cmp:
        return
    x, fn, raw = st["cmp_x"], st["fn"], st["raw"]
    for _ in range(int(cmp["calls"])):
        with ctx.span("cmp", impl="lib", bytes=cmp["bytes"]):
            fn(x).block_until_ready()
        with ctx.span("cmp", impl="raw", bytes=cmp["bytes"]):
            raw(x).block_until_ready()
    record["comparator"] = dict(cmp)


def check(ctx, st, record):
    """Every kept answer against numpy: the largest absolute gap. The
    data are integers, so a sound run is exact. Each answer is read
    back, compared and freed in turn."""
    phases = {p["name"]: p for p in st["phases"]}
    by_size: Dict[int, list] = {}
    for name, i, out in st.pop("kept"):
        by_size.setdefault(phases[name]["sizes"][i], []).append(out)
    host, verb, root, n = st["host"], st["verb"], st["root"], st["n"]
    worst, wrong = 0.0, 0
    for b, outs in by_size.items():
        data = host[:, :st["elems"][b]].reshape(_shape(verb, n,
                                                       st["elems"][b]))
        want = ref.expected(verb, data, root).astype(np.float32)
        while outs:
            got = np.asarray(outs.pop()).astype(np.float32)
            gap = float(np.max(np.abs(got - want)))
            if not gap <= 0.0:
                wrong += 1
            worst = max(worst, gap) if np.isfinite(gap) else float("inf")
    record["failed"] = wrong
    st.clear()
    return [Check("max_abs_err", worst, float(ctx.limits["max_abs_err"]))]
