"""Readings that set a cell's correctness limits; not part of a run.

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 7,8,9 [--seconds 2]

For every seed of ``--seeds``, the numbers a sound run of the program
compares (the lower readings). For every seed of ``--control-seeds``,
the same numbers for the control: the reference in the precision below
the one the configuration states, put in the program's place, and for
training cells the planted faults that need a run. One JSON line per
reading on standard output. The limits are then set by hand, between
the largest lower and the smallest upper reading, in
``benchmark/cells/<name>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

CONTROL_DTYPE = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def coll_readings(cell, devices, seeds, control_seeds, seconds):
    """Program and control (the verb computed in the precision below
    the configuration's) through the driver's own set-up, window and
    check."""
    from benchmark import harness

    drv = harness.load_module("drivers", cell.traffic["driver"])
    low = dict(cell.traffic, compute_dtype=CONTROL_DTYPE[
        cell.traffic["dtype"]])
    for kind, traffic, ss in (("program", cell.traffic, seeds),
                              ("control", low, control_seeds)):
        for seed in ss:
            ctx = harness.Context(cell._replace(traffic=traffic), devices,
                                  seed, seconds, False)
            st = drv.setup(ctx)
            rec = drv.measure(ctx, st)
            checks = drv.check(ctx, st, rec)
            yield {"kind": kind, "seed": seed, "attempted": rec["attempted"],
                   **{c.name: c.value for c in checks}}


def train_readings(cell, devices, seeds, control_seeds, seconds):
    """Program: set-up's three checked steps against the reference.
    Control: the reference with its matmuls in the precision below the
    configuration's bf16, in the program's place. Fault: the reference
    trained on half of each batch."""
    import ml_dtypes

    from benchmark import harness
    from benchmark.drivers import train_step as drv

    for seed in seeds:
        ctx = harness.Context(cell, devices, seed, seconds, False)
        st = drv.setup(ctx)
        checks = drv.check(ctx, st, {})
        yield {"kind": "program", "seed": seed,
               **{c.name: c.value for c in checks}}
    s = drv.shape(cell.config, cell.traffic)
    low = getattr(ml_dtypes, CONTROL_DTYPE["bfloat16"])
    for seed in control_seeds:
        ctx = harness.Context(cell, devices, seed, seconds, False)
        want = drv.reference(ctx, s)
        for kind, kw in (("control", {"mm_dtype": low}),
                         ("fault_half_batch",
                          {"keep_rows": int(cell.traffic["batch"]) // 2})):
            got = drv.reference(ctx, s, **kw)
            yield {"kind": kind, "seed": seed,
                   **drv.readings(*got, *want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark import harness

    cell = harness.load_cell(a.workload, root)
    harness.prepare_jax(root)
    devices = harness.tpu_devices(cell.chips)
    fn = {"coll_sweep": coll_readings,
          "train_step": train_readings}[cell.traffic["driver"]]
    t0 = time.perf_counter()
    for r in fn(cell, devices, a.seeds, a.control_seeds, a.seconds):
        r["t_s"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
