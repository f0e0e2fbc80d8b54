"""The control of each cell's correctness check comes out not correct:
the computation in the precision below the one the configuration
states. At tiny sizes; calibrate.py runs the same at the cell's size on
the chip."""

import ml_dtypes

from benchmark import calibrate, harness
from benchmark.drivers import train_step
from conftest import run_cell


def test_bench_coll_control_in_bfloat16_is_not_correct(tiny_coll):
    cell, devs = tiny_coll
    low = dict(cell.traffic, compute_dtype=calibrate.CONTROL_DTYPE[
        cell.traffic["dtype"]])
    out = run_cell(cell._replace(traffic=low), devs)
    assert out["correct"] is False
    assert out["checks"]["max_abs_err"]["value"] > 0


def test_bench_train_control_fails_a_limit(tiny_train):
    cell, devs = tiny_train
    ctx = harness.Context(cell, devs, 2 ** 31 + 5, 1.0, False)
    s = train_step.shape(cell.config, cell.traffic)
    want = train_step.reference(ctx, s)
    got = train_step.reference(ctx, s, mm_dtype=getattr(
        ml_dtypes, calibrate.CONTROL_DTYPE["bfloat16"]))
    r = train_step.readings(*got, *want)
    assert any(r[k] > cell.limits[k] for k in r), r


def test_bench_calibrate_reads_program_control_and_fault(tiny_train):
    cell, devs = tiny_train
    rows = list(calibrate.train_readings(cell, devs, [11], [12], 0.5))
    kinds = [r["kind"] for r in rows]
    assert kinds == ["program", "control", "fault_half_batch"]
    prog = rows[0]
    assert all(prog[k] <= cell.limits[k] for k in cell.limits), prog
