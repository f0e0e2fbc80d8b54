"""Test configuration.

Collective/sharding tests run on a virtual 8-device CPU mesh (the
deterministic fake-mesh backend SURVEY.md §4 calls for — the reference's
accelerator/null + btl/template pattern, applied to the whole device layer).
Must run before jax is imported anywhere.
"""

import os
import sys

# The suite runs on the CPU with 8 virtual devices, the same way here
# and on a machine with a chip (chip_smoke.py, not the tests, drives the
# chip). Set before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Metrics snapshots go to a throwaway dir, never the repo checkout:
# procmode subprocesses inherit this env var, so a test that enables
# the metrics plane can't litter metrics-rank<N>.json into the CWD.
# Tests that care about the location still win — they set the env key
# (or the cvar) explicitly on their own child env / registry.
import tempfile

os.environ.setdefault(
    "OMPI_TPU_MCA_metrics_dir",
    tempfile.mkdtemp(prefix="ompi-tpu-test-metrics-"))

# Trace exports likewise (the check_crash procmode proof used to drop
# trace-rank0.json into the launch CWD — the repo root): tests that
# enable tracing write to a throwaway dir unless they choose one.
os.environ.setdefault(
    "OMPI_TPU_MCA_trace_dir",
    tempfile.mkdtemp(prefix="ompi-tpu-test-trace-"))

# Persistent compile cache: the suite's wall time is dominated by XLA
# CPU compiles of the big shard_map programs (train step, multislice);
# a repeat run hits the cache. Procmode children inherit the directory
# through JAX_COMPILATION_CACHE_DIR.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ompi_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()
