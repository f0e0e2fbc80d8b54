"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up is timed from this process's start. The last line of standard
output is the result, as BENCHMARK.json's contract describes; the
numbers compared with the reference, each beside its limit, are the
last lines of standard error. Without the cell's TPU chips it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark import harness

    cell = harness.load_cell(a.workload, root)
    harness.prepare_jax(root)
    devices = harness.tpu_devices(cell.chips)
    out = harness.run(cell, devices, a.seed, a.seconds, bool(a.trace),
                      T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
