"""Both drivers end to end at tiny sizes on virtual CPU devices, through
their correctness comparisons; BENCHMARK.json against the files it
names; run.py refusing to run without the cell's TPU chips."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from conftest import run_cell

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_bench_coll_cell_runs_and_is_correct(tiny_coll):
    out = run_cell(*tiny_coll)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"coll_busbw", "coll_small_us", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 4
    assert out["checks"] == {"max_abs_err": {"value": 0.0, "limit": 0.0}}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("verb", ["bcast", "allgather", "alltoall"])
def test_bench_coll_driver_takes_the_other_osu_verbs(tiny_coll, verb):
    """A later OSU cell is data only: the driver and the numpy reference
    already cover these verbs."""
    cell, devs = tiny_coll
    t = dict(cell.traffic, verb=verb)
    del t["comparator"]
    t["phases"] = {"small": dict(t["phases"]["small"], bytes=[64, 1024]),
                   "large": dict(t["phases"]["large"], bytes=[16384])}
    out = run_cell(cell._replace(traffic=t), devs)
    assert out["correct"] and out["checks"]["max_abs_err"]["value"] == 0


def test_bench_coll_cell_traced(tiny_coll):
    out = run_cell(*tiny_coll, trace=True)
    assert out["correct"]
    m = out["metrics"]
    # the 64 MiB readers find no 64 MiB call at this size and stay silent
    assert set(m) == {"verb_enqueue_us", "device_idle.coll_small"}
    assert 0 <= m["device_idle.coll_small"]["value"] <= 100
    d = out["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert 0 < len(out["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("trace", [False, True])
def test_bench_train_cell_runs_and_is_correct(tiny_train, trace):
    out = run_cell(*tiny_train, trace=trace)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    if trace:
        m = out["metrics"]
        # no Pallas kernel runs on the CPU: the flash readers are silent
        assert set(m) == {"train_mfu", "device_idle.train"}
        assert 0 < m["train_mfu"]["value"] < 100
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_bench_benchmark_json_names_files_that_exist():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "cells", w["name"] + ".json"))
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "drivers", cell.traffic["driver"] + ".py"))
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "flagship.seq1024", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_bench_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_bench_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
