"""Process-mode integration: real multi-rank jobs via the mpirun launcher.

Reference analog: single-host multi-rank over sm/tcp/self BTLs — the
default MTT/mpi4py CI shape (SURVEY.md §4 "Multi-node without a cluster").
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env():
    """Environment for test subprocesses (launcher + ranks): the repo
    first on PYTHONPATH, the cpu backend, no inherited rank identity.
    Rank processes in these tests are host-transport only; the few that
    use jax get the cpu backend lazily (~1s). conftest's compile cache
    directory reaches them through JAX_COMPILATION_CACHE_DIR."""
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)  # never inherit rank identity
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + pp)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_mpi(np_, script, *args, timeout=120, mca=()):
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun", "-np", str(np_)]
    for k, v in mca:
        cmd += ["--mca", k, str(v)]
    cmd += [script, *args]
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout, env=subprocess_env())


def test_with_tpu_refuses_several_ranks(capsys):
    """One chip belongs to one process: --with-tpu runs one rank."""
    from ompi_tpu.tools import mpirun

    with pytest.raises(SystemExit) as e:
        mpirun.main(["-np", "2", "--with-tpu", "examples/ring.py"])
    assert e.value.code == 2
    assert "one rank" in capsys.readouterr().err


def test_ring_4_ranks():
    """BASELINE.json ladder config #1 (reference: examples/ring_c.c)."""
    r = run_mpi(4, "examples/ring.py")
    assert r.returncode == 0, r.stderr
    assert "Process 0 decremented value: 0" in r.stdout
    assert r.stdout.count("exiting") == 4


def test_collectives_4_ranks():
    r = run_mpi(4, "tests/procmode/check_collectives.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COLLECTIVES-OK") == 4


def test_collectives_3_ranks_nonpow2():
    r = run_mpi(3, "tests/procmode/check_collectives.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COLLECTIVES-OK") == 3


def test_collectives_2_ranks_no_progress_thread():
    """Polling-only progress (reference: default opal_progress without the
    async thread)."""
    r = run_mpi(2, "tests/procmode/check_collectives.py",
                mca=(("runtime_progress_thread", "0"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COLLECTIVES-OK") == 2
