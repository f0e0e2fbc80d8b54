"""Readings that set the sparse-expert cell's correctness limits; not
part of a run. ``calibrate.py``'s counterpart for the ``moe_train_step``
driver, whose faults are its own.

    python3 benchmark/calibrate_moe.py --workload mellum2.seq8192 \
        --seeds 1,2,... --control-seeds 7,8

For every seed of ``--seeds``, the numbers a sound run of the program
compares (set-up's three checked steps against the reference). For
every seed of ``--control-seeds``, the same numbers for the control
(the reference with float8 e4m3 matmul operands, the precision below
the configuration's bfloat16) and for the planted faults: half the
batch's tokens, the sliding layers run as full causal, and the router's
renormalisation left out, each computed by the reference. One JSON line
per reading on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import ml_dtypes

    from benchmark import harness

    cell = harness.load_cell(a.workload, root)
    harness.prepare_jax(root)
    devices = harness.tpu_devices(cell.chips)
    drv = harness.load_module("drivers", cell.traffic["driver"])
    t0 = time.perf_counter()

    def say(row):
        row["t_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)

    for seed in a.seeds:
        ctx = harness.Context(cell, devices, seed, 0.0, False)
        checks = drv.check(ctx, drv.setup(ctx), {})
        say({"kind": "program", "seed": seed,
             **{c.name: c.value for c in checks}})
    for seed in a.control_seeds:
        ctx = harness.Context(cell, devices, seed, 0.0, False)
        for kind, got in drv.calibration(ctx, ml_dtypes.float8_e4m3fn):
            say({"kind": kind, "seed": seed, **got})
    return 0


if __name__ == "__main__":
    sys.exit(main())
