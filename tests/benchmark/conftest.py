"""Tiny cells for the CPU rehearsals: the benchmark's own cells, with
their traffic and configuration cut to sizes the CPU runs in seconds.
The harness's look for a TPU is skipped: the tests call harness.run
with the CPU's virtual devices."""

import time

import pytest

import jax

from benchmark import harness, peaks

# sound tiny runs read loss_gap ~1e-3, grad_gap ~4e-3, change_gap ~2e-3
# on the CPU (bf16 matmuls against the float32 reference): the limits
# of the tiny cell sit above them, below the control and the faults
TINY_TRAIN_LIMITS = {"loss_gap": 5e-3, "grad_gap": 0.02, "change_gap": 0.02}


@pytest.fixture
def tiny_coll():
    cell = harness.load_cell("osu_allreduce.sweep")
    t = dict(cell.traffic, block_s=0.05,
             comparator={"bytes": 65536, "calls": 3})
    t["phases"] = {
        "small": dict(t["phases"]["small"], bytes=[4, 64, 1024]),
        "large": dict(t["phases"]["large"], bytes=[16384, 65536])}
    return cell._replace(traffic=t), jax.devices()[:4]


@pytest.fixture
def tiny_train(monkeypatch):
    cell = harness.load_cell("flagship.seq1024")
    c = dict(cell.config, vocab=256, d_model=64, n_heads=2, n_layers=2,
             d_ff=128)
    t = dict(cell.traffic, batch=4, seq_len=32, batches=4, ref_rows=2)
    # the CPU has no published peak; train_mfu needs one to read
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return (cell._replace(config=c, traffic=t, limits=TINY_TRAIN_LIMITS),
            jax.devices()[:1])


def run_cell(cell, devices, seed=2 ** 31 + 77, seconds=0.5, trace=False):
    return harness.run(cell, devices, seed, seconds, trace,
                       time.perf_counter())
