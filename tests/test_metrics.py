"""Live metrics plane: registry, straggler detection, Prometheus export,
mpitop.

Reference points: ompi_spc.c + MPI_T pvar sessions (the sampling
surface), pml/monitoring (per-peer accounting), the Prometheus text
exposition format (promexport's validator encodes the promtool grammar
rules the export must satisfy).
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from ompi_tpu import COMM_WORLD
from ompi_tpu.core.datatype import BYTE
from ompi_tpu.mca.var import all_pvars, all_vars, set_var
from ompi_tpu.runtime import metrics, spc

from tools.promexport import validate
from tests.test_process_mode import REPO, run_mpi, subprocess_env


@pytest.fixture
def clean_metrics():
    metrics.reset_for_testing()
    yield metrics
    set_var("metrics", "enable", False)
    metrics.stop_http()
    metrics.reset_for_testing()


# ------------------------------------------------------------- registry
def test_histogram_log2_buckets(clean_metrics):
    h = metrics.histogram("lat")
    for v in (0, 1, 2, 3, 1000):
        h.observe(v)
    assert h.count == 5
    assert h.sum == 1006.0
    # tight log2 placement: le edges 1, 2, 4, ..., value v lands in the
    # first bucket with v <= le
    assert h.counts[0] == 2          # 0 and 1 -> le=1
    assert h.counts[1] == 1          # 2 -> le=2
    assert h.counts[2] == 1          # 3 -> le=4
    assert h.counts[10] == 1         # 1000 -> le=1024
    assert h.quantile(0.5) == 2.0
    # fractional values ceil to the covering edge: 4.7 > 4 -> le=8
    h.observe(4.7)
    assert h.counts[3] == 1


def test_histogram_overflow_bucket(clean_metrics):
    h = metrics.histogram("big")
    h.observe(10 ** 12)  # beyond every finite edge
    assert h.counts[-1] == 1
    assert h.edges()[-1] == float("inf")
    # a quantile landing in the overflow bucket has no finite edge —
    # it must say so, not fabricate 2^nbuckets
    assert h.quantile(0.99) == float("inf")


def test_histogram_labels_are_distinct_series(clean_metrics):
    metrics.observe("lat", 5.0, peer=1)
    metrics.observe("lat", 7.0, peer=2)
    assert metrics.histogram("lat", peer=1).count == 1
    assert metrics.histogram("lat", peer=2).count == 1


def test_ewma_update(clean_metrics):
    e = metrics.ewma("w")
    assert e.update(10.0, alpha=0.5) == 10.0   # first sample seeds
    assert e.update(20.0, alpha=0.5) == 15.0
    assert e.n == 2


def test_gauges(clean_metrics):
    metrics.gauge_set("g", 1.5)
    metrics.gauge_set("g", 2.5, verb="allreduce")
    assert metrics.gauge_get("g") == 1.5
    assert metrics.gauge_get("g", verb="allreduce") == 2.5


def test_snapshot_is_the_unified_surface(clean_metrics):
    spc.record("metrics_test_counter")
    metrics.gauge_set("g", 3.0)
    metrics.observe("lat", 2.0, peer=0)
    metrics.ewma_update("w", 5.0)
    snap = metrics.snapshot()
    assert snap["counters"]["metrics_test_counter"] == 1
    assert "metrics_straggler_trips" in snap["pvars"]
    # spc counters already ride snap["counters"]; the lazy spc_* pvar
    # mirrors must not double-report
    assert not any(k.startswith("spc_") for k in snap["pvars"])
    assert {"name": "g", "labels": {}, "value": 3.0} in snap["gauges"]
    assert any(h["name"] == "lat" and h["count"] == 1
               for h in snap["histograms"])
    assert any(e["name"] == "w" and e["value"] == 5.0
               for e in snap["ewmas"])


def test_export_json(tmp_path, clean_metrics):
    set_var("metrics", "dir", str(tmp_path))
    try:
        metrics.gauge_set("g", 1.0)
        path = metrics.export_json()
        assert os.path.basename(path).startswith("metrics-rank")
        with open(path) as f:
            snap = json.load(f)
        assert snap["rank"] == 0 and "counters" in snap
    finally:
        set_var("metrics", "dir", ".")


# ------------------------------------------------- straggler detection
def test_straggler_tracker_flags_the_laggard_only(clean_metrics):
    tr = metrics.StragglerTracker()
    trips = []
    for idx in range(8):
        base = idx * 1_000_000
        trips += tr.record(9, idx, 0, base, 10, 3)
        trips += tr.record(9, idx, 1, base + 30_000, 11, 3)
        trips += tr.record(9, idx, 2, base + 300, 12, 3)
    # default threshold 10000us / min 5 samples: rank 1 trips exactly
    # once (latched), ranks 0/2 never. Skew is vs the MEDIAN entrant
    # (rank 2 at base+300), so the laggard reads 29700, the early
    # ranks clamp to 0.
    assert [(r, w) for r, w, _s, _v in trips] == [(1, 11)]
    r, w, skew, ewma = trips[0]
    assert skew == 29700.0 and ewma > 10000.0


def test_straggler_trip_rearms_after_decay(clean_metrics):
    tr = metrics.StragglerTracker()
    trips = []

    def round_(idx, lag_us):
        base = idx * 1_000_000
        trips.extend(tr.record(9, idx, 0, base, 10, 2))
        trips.extend(tr.record(9, idx, 1, base + lag_us, 11, 2))

    idx = 0
    for _ in range(6):          # drive the EWMA over the threshold
        round_(idx, 30_000)
        idx += 1
    assert len(trips) == 1      # latched: no banner cascade
    for _ in range(6):          # decay below threshold/2 -> re-arm
        round_(idx, 0)
        idx += 1
    round_(idx, 30_000)         # a NEW episode must report again
    assert len(trips) == 2


def test_tracker_eviction_sheds_the_stale_comm_not_the_live_one(
        clean_metrics):
    """A silent rank on one comm must not starve another comm's
    actively-filling rows: eviction drops the longest-PENDING row
    (insertion order), not min((cid, idx))."""
    tr = metrics.StragglerTracker()
    for idx in range(tr.window + 8):   # cid 7: rank 1 never stamps
        tr.record(7, idx, 0, idx * 1000, 0, 2)
    # the world comm (lower cid) still completes rows and folds skew
    trips = []
    for idx in range(6):
        base = idx * 1_000_000
        trips += tr.record(0, idx, 0, base, 0, 2)
        trips += tr.record(0, idx, 1, base + 30_000, 1, 2)
    assert [(r, w) for r, w, _s, _v in trips] == [(1, 1)]
    assert len(tr._rows) <= tr.window + 1


def test_dead_cid_state_is_reclaimed(clean_metrics):
    """Comm-churny jobs (per-step Split/Free) must not leak straggler
    state per dead cid: a stamp for a vanished comm drops its rows,
    latches, call index, and skew EWMAs."""
    metrics.ewma_update("coll_entry_skew_us", 9.0, cid=77, rank=1)
    metrics._idx[77] = 5
    metrics._tracker._rows[(77, 4)] = {0: (1, 0)}
    metrics._tracker._nsamp[(77, 1)] = 3
    metrics._tracker._tripped.add((77, 1))
    metrics._forget_cid(77)
    assert 77 not in metrics._idx
    assert not any(k[0] == 77 for k in metrics._tracker._rows)
    assert not any(k[0] == 77 for k in metrics._tracker._nsamp)
    assert not any(k[0] == 77 for k in metrics._tracker._tripped)
    assert not any(e["labels"].get("cid") == "77"
                   for e in metrics.snapshot()["ewmas"])


def test_comm_free_reclaims_straggler_state(clean_metrics):
    """ProcComm.Free must release the metrics plane's per-cid state on
    every rank — the root's late-stamp cleanup alone never fires for a
    comm that finished its collectives before dying."""
    set_var("metrics", "enable", True)
    dup = COMM_WORLD.Dup()
    metrics._idx[dup.cid] = 3
    metrics.ewma_update("coll_entry_skew_us", 5.0, cid=dup.cid, rank=0)
    dup.Free()
    assert dup.cid not in metrics._idx
    assert not any(e["labels"].get("cid") == str(dup.cid)
                   for e in metrics.snapshot()["ewmas"])


def test_trip_local_counts_and_banner(clean_metrics, capfd):
    before = int(all_pvars()["metrics_straggler_trips"].value)
    metrics._trip_local(3, 12345.0, 23456.0, "  rank 9 entered late")
    assert all_pvars()["metrics_straggler_trips"].value == before + 1
    assert spc.get("metrics_straggler_trip") >= 1
    err = capfd.readouterr().err
    assert "STRAGGLER" in err and "rank 9 entered late" in err


def test_coll_entry_is_noop_on_singleton_world(clean_metrics):
    set_var("metrics", "enable", True)
    out = np.zeros(2, np.float32)
    COMM_WORLD.Allreduce(np.ones(2, np.float32), out)  # size-1 world
    assert out[0] == 1.0
    assert metrics._tracker._rows == {}


def test_procmode_straggler_flags_only_the_laggard():
    """The acceptance scenario: 3 ranks, chaos-delay on rank 1's deliver
    funnel (PR 3 ft/inject), the skew EWMA deterministically trips the
    pvar + show_help on the laggard — and only there."""
    r = run_mpi(3, "tests/procmode/check_metrics.py", "30", timeout=240,
                mca=(("metrics_enable", "1"),
                     ("metrics_straggler_threshold_us", "20000"),
                     ("ft_inject_plan", "delay(0,1,ms=60,side=recv)"),
                     ("coll_sm_enable", "0"),
                     ("metrics_dir", "/tmp")))
    assert r.returncode == 0, r.stdout + r.stderr
    assert re.search(r"rank 1: METRICS-TRIPS=[1-9]", r.stdout), \
        r.stdout + r.stderr
    assert "rank 0: METRICS-TRIPS=0" in r.stdout, r.stdout + r.stderr
    assert "rank 2: METRICS-TRIPS=0" in r.stdout, r.stdout + r.stderr
    assert "STRAGGLER" in r.stderr  # the laggard's show_help banner


# ------------------------------------------------------ pml/monitoring
class _FakeReq:
    def __init__(self, src=0, nbytes=0):
        class _St:
            pass

        self.status = _St()
        self.status.source = src
        self.status._nbytes = nbytes

    def add_completion_callback(self, fn):
        fn(self)


class _FakePml:
    my_rank = 0

    def isend(self, buf, count, datatype, dst, tag, cid, qos=None):
        return _FakeReq()

    def irecv(self, buf, count, datatype, src, tag, cid):
        return _FakeReq(src=src, nbytes=count * datatype.size)


def test_monitoring_feeds_latency_histograms(clean_metrics):
    from ompi_tpu.pml.monitoring import MonitoringPml

    set_var("metrics", "enable", True)
    m = MonitoringPml(_FakePml())
    m.isend(b"xxxx", 4, BYTE, 1, 0, 0)
    m.irecv(bytearray(4), 4, BYTE, 2, 0, 0)
    assert metrics.histogram("pml_send_latency_us", peer=1).count == 1
    assert metrics.histogram("pml_recv_latency_us", peer=2).count == 1
    # system-plane traffic stays out of the histograms
    m.isend(b"x", 1, BYTE, 1, -4500, 0)
    assert metrics.histogram("pml_send_latency_us", peer=1).count == 1


def test_monitoring_matrix_sampler(clean_metrics):
    from ompi_tpu.pml.monitoring import MonitoringPml

    m = MonitoringPml(_FakePml())
    m._bump(1, "tx", 100)
    m._bump(2, "rx", 7)
    snap = metrics.snapshot()
    rows = snap["samplers"]["pml_comm_matrix"]
    assert {"src": 0, "dst": 1, "msgs": 1, "bytes": 100} in rows
    assert {"src": 2, "dst": 0, "msgs": 1, "bytes": 7} in rows


def test_matrix_merges_self_traffic(clean_metrics):
    """A rank's self-sends bump both the tx and rx counters of the SAME
    (me, me) edge — two rows would render duplicate Prometheus samples
    that the --check gate rejects."""
    from ompi_tpu.pml.monitoring import MonitoringPml

    m = MonitoringPml(_FakePml())
    m._bump(0, "tx", 10)
    m._bump(0, "rx", 10)
    assert m.matrix() == [{"src": 0, "dst": 0, "msgs": 1, "bytes": 10}]
    assert validate(metrics.render_prometheus()) == []


def test_monitoring_disabled_metrics_costs_nothing(clean_metrics):
    from ompi_tpu.pml.monitoring import MonitoringPml

    m = MonitoringPml(_FakePml())
    m.isend(b"xxxx", 4, BYTE, 1, 0, 0)  # metrics disabled
    assert metrics.snapshot()["histograms"] == []


# --------------------------------------------------- prometheus export
def test_prometheus_render_parses_under_the_grammar(clean_metrics):
    spc.record("allreduce")
    metrics.gauge_set("bench_prologue_us", 1.94)
    metrics.observe("pml_send_latency_us", 3.2, peer=1)
    metrics.observe("pml_send_latency_us", 900.0, peer=1)
    metrics.ewma_update("coll_entry_skew_us", 42.0, cid=0, rank=1)
    text = metrics.render_prometheus()
    assert validate(text) == []
    assert 'ompi_metrics_bench_prologue_us{rank="0"} 1.94' in text
    assert "ompi_metrics_pml_send_latency_us_bucket" in text
    assert 'le="+Inf"' in text
    assert "ompi_metrics_coll_entry_skew_us_ewma" in text
    assert "# TYPE ompi_metrics_pml_send_latency_us histogram" in text


def test_prometheus_merges_ranks_without_collisions(clean_metrics):
    metrics.gauge_set("g", 1.0)
    a = metrics.snapshot()
    b = metrics.snapshot()
    b["rank"] = 1
    text = metrics.render_prometheus([a, b])
    assert validate(text) == []
    assert 'ompi_metrics_g{rank="0"} 1.0' in text
    assert 'ompi_metrics_g{rank="1"} 1.0' in text


def test_prometheus_root_skew_series_keep_their_subject_rank(
        clean_metrics):
    """The comm root exports EVERY member's skew EWMA; the exporting
    rank must not overwrite the series' own `rank` label (observed:
    all members collapsed onto rank="0" as duplicate samples)."""
    for r in (0, 1, 2):
        metrics.ewma_update("coll_entry_skew_us", 100.0 * r,
                            cid=0, rank=r)
    text = metrics.render_prometheus()
    assert validate(text) == []
    for r in (1, 2):
        assert (f'ompi_metrics_coll_entry_skew_us_ewma'
                f'{{cid="0",rank="{r}"}}') in text


def test_prometheus_matrix_rows(clean_metrics):
    from ompi_tpu.pml.monitoring import MonitoringPml

    m = MonitoringPml(_FakePml())
    m._bump(1, "tx", 64)
    text = metrics.render_prometheus()
    assert validate(text) == []
    assert 'ompi_pml_peer_bytes{dst="1",rank="0",src="0"} 64.0' in text


def test_validator_rejects_bad_text():
    # the grammar rules promtool enforces, one probe each
    assert validate("1bad{} 1.0\n")                 # bad metric name
    assert validate('m{le="x} 1.0\n')               # unterminated label
    assert validate("m 1.0\nm 2.0\n")               # duplicate sample
    assert validate("# TYPE m bogus\nm 1.0\n")      # unknown type
    assert validate("m 1.0\n# TYPE m gauge\n")      # TYPE after samples
    assert validate("m 1.0\nother 1.0\nm 2.0\n")    # split family group
    assert validate("m notanumber\n")               # bad value
    assert validate('m{a="1",a="2"} 1.0\n')         # duplicate label name
    # histogram: missing +Inf bucket
    assert validate('# TYPE h histogram\nh_bucket{le="1.0"} 1.0\n'
                    "h_sum 1.0\nh_count 1.0\n")
    # histogram: non-cumulative buckets
    assert validate('# TYPE h histogram\nh_bucket{le="1.0"} 5.0\n'
                    'h_bucket{le="+Inf"} 3.0\nh_sum 1.0\nh_count 3.0\n')
    # histogram: +Inf bucket != count
    assert validate('# TYPE h histogram\nh_bucket{le="+Inf"} 3.0\n'
                    "h_sum 1.0\nh_count 4.0\n")
    # and a clean minimal exposition parses clean
    assert validate("# HELP m ok\n# TYPE m gauge\n"
                    'm{a="b"} 1.0\nm{a="c"} 2.0\n') == []


def test_promexport_cli_check_and_render(tmp_path, clean_metrics):
    set_var("metrics", "dir", str(tmp_path))
    try:
        metrics.gauge_set("g", 4.2)
        metrics.observe("lat", 3.0, peer=1)
        path = metrics.export_json()
    finally:
        set_var("metrics", "dir", ".")
    out = tmp_path / "out.prom"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "promexport.py"),
         path, "--check", "-o", str(out)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=subprocess_env())
    assert r.returncode == 0, r.stdout + r.stderr
    assert "render clean" in r.stdout
    text = out.read_text()
    assert validate(text) == []
    assert "ompi_metrics_g" in text


def test_http_endpoint_serves_metrics_and_json(clean_metrics):
    set_var("metrics", "enable", True)
    metrics.gauge_set("g", 1.0)
    try:
        port = metrics.start_http(0)  # ephemeral port
    except OSError:
        pytest.skip("cannot bind 127.0.0.1 in this environment")
    try:
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert validate(body) == []
        assert "ompi_metrics_g" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/json", timeout=30) as resp:
            snap = json.loads(resp.read())
        assert "counters" in snap and "pvars" in snap
    finally:
        metrics.stop_http()


def test_bench_numbers_flow_into_the_export(clean_metrics):
    """Gauges a measuring tool sets (tools/reshardplan.py --bench does)
    reach the Prometheus export, labels and all, in valid text
    format."""
    metrics.gauge_set("bench_prologue_us", 1.94)
    metrics.gauge_set("bench_layer_overhead_us", 2.5, verb="allreduce")
    text = metrics.render_prometheus()
    assert validate(text) == []
    assert 'ompi_metrics_bench_prologue_us{rank="0"} 1.94' in text
    assert ('ompi_metrics_bench_layer_overhead_us'
            '{rank="0",verb="allreduce"} 2.5') in text


# --------------------------------------------------------------- tools
def test_mpitop_once_renders_per_rank_rows(tmp_path, clean_metrics):
    set_var("metrics", "dir", str(tmp_path))
    try:
        metrics.observe("pml_send_latency_us", 50.0, peer=1)
        metrics.ewma_update("coll_entry_skew_us", 123.0, cid=0, rank=1)
        metrics.export_json()
        snap = metrics.snapshot()
        snap["rank"] = 1
        (tmp_path / "metrics-rank1.json").write_text(
            json.dumps(snap, default=str))
    finally:
        set_var("metrics", "dir", ".")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mpitop.py"),
         "--once", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=subprocess_env())
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RANK" in r.stdout
    assert re.search(r"^\s+0\s", r.stdout, re.M), r.stdout
    assert re.search(r"^\s+1\s", r.stdout, re.M), r.stdout
    assert "123" in r.stdout  # rank 1's skew EWMA from the root snapshot


def test_mpitop_once_without_snapshots_exits_nonzero(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mpitop.py"),
         "--once", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=subprocess_env())
    assert r.returncode == 1
    assert "no metrics-rank" in r.stderr


def test_info_lists_metrics_vars():
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.info", "--param",
         "metrics", "--level", "9"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=subprocess_env())
    assert r.returncode == 0, r.stderr
    for var in ("metrics_enable", "metrics_straggler_threshold_us",
                "metrics_hist_buckets", "metrics_http_port",
                "metrics_snapshot_period"):
        assert var in r.stdout, var
    assert "pml_monitoring_enable" in all_vars()  # info loads it too


def test_metrics_cvars_registered():
    vars_ = all_vars()
    assert vars_["metrics_enable"].default is False
    assert vars_["metrics_straggler_threshold_us"].typ is float
    assert vars_["metrics_http_port"].default == 0  # endpoint off by default


# ------------------------------------------------- snapshot dir (PR 13)
def test_default_snapshot_dir_is_per_job_under_tempdir(monkeypatch):
    """With metrics_dir unset, snapshots land in a per-JOB temp subdir
    (keyed by the launcher pid every rank shares; own pid for
    singletons) — never the CWD, which littered repo checkouts, and
    never the flat temp dir, where two concurrent jobs would overwrite
    each other's metrics-rank0.json."""
    import tempfile

    from ompi_tpu.runtime import metrics

    monkeypatch.setenv("OMPI_TPU_LAUNCHER_PID", "12345")
    d = metrics.default_snapshot_dir()
    assert d == os.path.join(tempfile.gettempdir(),
                             "ompi-tpu-metrics-12345")
    monkeypatch.delenv("OMPI_TPU_LAUNCHER_PID")
    assert metrics.default_snapshot_dir().endswith(
        f"ompi-tpu-metrics-{os.getpid()}")


def test_export_json_defaults_off_the_cwd(monkeypatch):
    from ompi_tpu.mca.var import get_var, set_var
    from ompi_tpu.runtime import metrics

    monkeypatch.setenv("OMPI_TPU_LAUNCHER_PID", str(os.getpid()))
    old = get_var("metrics", "dir")
    set_var("metrics", "dir", "")
    try:
        path = metrics.export_json()
        assert os.path.dirname(path) == metrics.default_snapshot_dir()
        assert os.path.exists(path)
        os.remove(path)
    finally:
        set_var("metrics", "dir", old)


def test_mpitop_default_dir_finds_newest_job_dir(monkeypatch, tmp_path):
    import tempfile as _tf

    from tools import mpitop

    monkeypatch.setattr(_tf, "gettempdir", lambda: str(tmp_path))
    assert mpitop._default_dir() == "."  # no candidates: old behavior
    a = tmp_path / "ompi-tpu-metrics-100"
    b = tmp_path / "ompi-tpu-metrics-200"
    a.mkdir()
    b.mkdir()
    os.utime(a, (1, 1))
    assert mpitop._default_dir() == str(b)
