"""Fabric link telemetry (runtime/linkmodel.py + the btl_tcp conn
estimators): passive Jacobson/Karn SRTT off the reliability envelope's
ack clock, per-(peer, QoS class) delivered goodput, directional
loss_ppm, the RTT-adaptive retransmit timer, the -4900 idle-link probe,
and the consumers (detector journal, hier BDP floor, mpinet verdicts).

Covers the in-process loopback state machines white-box (fabricated
retained frames drive _rel_ack_rx/_rel_tick deterministically — no
sleep-calibrated RTTs), the registry/export surface, and the procmode
proofs driven through mpirun (tests/procmode/check_linkmodel.py):
injected 60ms delay localized to the one slow edge, injected corruption
charged to the faulted DIRECTION only, mpinet --check naming that edge,
and bitwise equality with telemetry on vs off.
"""

import json
import re
import subprocess
import sys
import time

import pytest

import ompi_tpu.btl.tcp  # registers the btl_tcp reliability cvars
from ompi_tpu import qos
from ompi_tpu.ft import inject
from ompi_tpu.mca.var import all_pvars, all_vars, set_var
from ompi_tpu.pml.base import pack_header
from ompi_tpu.runtime import linkmodel

from tests.test_process_mode import REPO, run_mpi, subprocess_env

TCP_ONLY = (("btl_btl", "^sm"),)
LM = (("linkmodel_enable", "1"),)

HDR = pack_header(1, 7, 0, 3, 1, 4, 0, 0)
HDR_LAT = pack_header(1, 7, 0, 3, 1, 4, 0, 0, qos=qos.LATENCY)


@pytest.fixture
def clean_inject():
    yield inject
    inject.uninstall()


@pytest.fixture
def link_knobs():
    names = ("reliable", "retx_timeout_ms", "retx_adaptive",
             "rtt_min_samples", "link_backoff_ms")
    prev = {n: all_vars()[f"btl_tcp_{n}"].value for n in names}
    yield
    for n, v in prev.items():
        set_var("btl_tcp", n, v)


@pytest.fixture
def lm_on():
    """Enable the telemetry plane around one test, with registry
    isolation and the real tcp source restored after (fake-source
    tests rebind it)."""
    prev = linkmodel._enable_var._value
    set_var("linkmodel", "enable", True)
    linkmodel.reset_for_testing()
    yield linkmodel
    set_var("linkmodel", "enable", prev)
    linkmodel.register_source(ompi_tpu.btl.tcp._linkmodel_rows)
    linkmodel.reset_for_testing()


def _pump(btls, until, timeout=8.0):
    t0 = time.monotonic()
    while not until():
        for b in btls:
            b.progress()
        if time.monotonic() - t0 > timeout:
            raise TimeoutError("loopback pump timed out")
        time.sleep(0.001)


def _pair(got_a, got_b):
    from ompi_tpu.btl.tcp import TcpBtl

    a = TcpBtl(lambda h, p: got_a.append((bytes(h), bytes(p))), my_rank=0)
    b = TcpBtl(lambda h, p: got_b.append((bytes(h), bytes(p))), my_rank=7)
    b.set_peers({0: f"127.0.0.1:{a.port}"})
    a.set_peers({7: f"127.0.0.1:{b.port}"})
    return a, b


def _established(got_a, got_b):
    """Pair with the 7 -> 0 conn established, enveloped, and drained."""
    a, b = _pair(got_a, got_b)
    b.send(0, HDR, b"warmup")
    _pump([a, b], lambda: len(got_a) == 1)
    conn = b.conns[0]
    assert conn.rel
    _pump([a, b], lambda: not conn.retx, timeout=3.0)
    return a, b, conn


def _fabricate(conn, ages, karn=()):
    """Retain fake already-sent frames (10 wire bytes each, class
    NORMAL) aged ``ages`` seconds; mark the given indices Karn."""
    now = time.monotonic()
    seqs = []
    with conn.wlock:
        for i, age in enumerate(ages):
            conn.tx_seq += 1
            conn.retx[conn.tx_seq] = (10, [], now - age, 0)
            conn.retx_bytes += 10
            seqs.append(conn.tx_seq)
            if i in karn:
                conn.karn.add(conn.tx_seq)
    return seqs


# ------------------------------------------------------ passive estimator
def test_passive_srtt_samples_on_ack(link_knobs):
    """Plain traffic yields Karn-accepted samples with no extra wire
    bytes: the ack that releases a retained frame IS the measurement."""
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        for i in range(4):
            b.send(0, HDR, b"rtt-%d" % i)
            _pump([a, b], lambda: not conn.retx, timeout=3.0)
        assert conn.rtt_n >= 1
        assert 0.0 < conn.srtt < 1.0  # loopback: sane, not garbage
        assert conn.rttvar >= 0.0
    finally:
        a.finalize()
        b.finalize()


def test_ack_batch_samples_youngest_frame(link_knobs):
    """One cumulative ack releasing a batch contributes ONE sample —
    the youngest frame's (least ack-coalescing skew)."""
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        n0, srtt0 = conn.rtt_n, conn.srtt
        _fabricate(conn, ages=[0.8, 0.2])
        b._rel_ack_rx(conn, conn.tx_seq)
        assert conn.rtt_n == n0 + 1
        # folded toward 0.2s (the youngest), not 0.8s
        assert conn.srtt < srtt0 + 0.3, (srtt0, conn.srtt)
        assert not conn.retx
    finally:
        a.finalize()
        b.finalize()


def test_karn_filter_rejects_retransmitted_samples(link_knobs):
    """An ack covering a RETRANSMITTED frame is ambiguous about which
    copy it acknowledges — Karn discards it; the batch falls back to
    the youngest clean frame, or contributes nothing at all."""
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        n0 = conn.rtt_n
        # youngest is Karn-marked: the clean OLDER frame is the sample
        _fabricate(conn, ages=[0.5, 0.1], karn=(1,))
        b._rel_ack_rx(conn, conn.tx_seq)
        assert conn.rtt_n == n0 + 1
        assert conn.srtt > 0.05  # pulled up toward the 0.5s clean frame
        assert not conn.karn     # consumed at release, never leaked
        # whole batch retransmitted: NO sample
        n1, srtt1, var1 = conn.rtt_n, conn.srtt, conn.rttvar
        _fabricate(conn, ages=[0.9, 0.9], karn=(0, 1))
        b._rel_ack_rx(conn, conn.tx_seq)
        assert (conn.rtt_n, conn.srtt, conn.rttvar) == (n1, srtt1, var1)
        assert not conn.karn
    finally:
        a.finalize()
        b.finalize()


def test_goodput_credits_acked_bytes_per_class(link_knobs, lm_on):
    """Delivered goodput is per-(peer, class) over ACKED wire bytes —
    latency traffic never pollutes the normal-class rate and an idle
    class reads zero."""
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        linkmodel._fold(force=True)  # arm the per-edge rate baseline
        time.sleep(0.06)             # > _FOLD_MIN_S: next fold rates a dt
        for i in range(12):
            b.send(0, HDR, b"n" * 256)
            b.send(0, HDR_LAT, b"l" * 64)
        _pump([a, b], lambda: not conn.retx, timeout=3.0)
        assert conn.acked_b[qos.NORMAL] > conn.acked_b[qos.LATENCY] > 0
        assert conn.acked_b[qos.BULK] == 0
        linkmodel._fold(force=True)
        row = linkmodel.edge(0)
        assert row is not None
        assert row["goodput_bps"]["normal"] > 0.0
        assert row["goodput_bps"]["latency"] > 0.0
        assert row["goodput_bps"]["bulk"] == 0.0
        assert row["loss_ppm"] == 0.0
    finally:
        a.finalize()
        b.finalize()


# -------------------------------------------------- RTT-adaptive retx timer
def test_conn_timeout_adaptive_bounds(link_knobs):
    """min(ceiling, max(floor, srtt + 4*rttvar)): fast links come down
    off the cvar ceiling, slow links ride their own RTO under it, and
    the ceiling/floor clamp both ends."""
    set_var("btl_tcp", "reliable", 1)
    set_var("btl_tcp", "retx_adaptive", 1)
    set_var("btl_tcp", "rtt_min_samples", 8)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        # below min samples: the fixed ceiling applies untouched
        conn.srtt, conn.rttvar, conn.rtt_n = 0.002, 0.0005, 7
        assert b._conn_timeout(conn, 0.2) == 0.2
        # fast link, warmed: floor wins over srtt + 4*rttvar
        conn.rtt_n = 8
        assert b._conn_timeout(conn, 0.2) == pytest.approx(0.025)
        # mid link: the classic RTO, under the ceiling
        conn.srtt, conn.rttvar = 0.060, 0.010
        assert b._conn_timeout(conn, 0.2) == pytest.approx(0.100)
        # slow link: ceilinged by the cvar, never above it
        conn.srtt = 0.500
        assert b._conn_timeout(conn, 0.2) == 0.2
        # feature off: fixed timer semantics are untouched
        set_var("btl_tcp", "retx_adaptive", 0)
        conn.srtt, conn.rttvar = 0.002, 0.0005
        assert b._conn_timeout(conn, 0.2) == 0.2
    finally:
        a.finalize()
        b.finalize()


def test_adaptive_timer_heals_drop_before_fixed_ceiling(
        clean_inject, link_knobs):
    """Fast-link-sooner: with a wan-sized 4s ceiling, a warmed loopback
    conn retransmits a dropped frame off srtt + 4*rttvar (floored at
    25ms) — delivery completes orders of magnitude before the fixed
    timer would have fired."""
    set_var("btl_tcp", "reliable", 1)
    set_var("btl_tcp", "retx_timeout_ms", 4000.0)
    set_var("btl_tcp", "retx_adaptive", 1)
    set_var("btl_tcp", "rtt_min_samples", 4)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        while conn.rtt_n < 4:
            # warm in bursts of 8: the receiver acks a full batch
            # immediately, so the samples read the WIRE RTT — a lone
            # frame waits out the periodic ack timer (which scales
            # with the very ceiling under test) and would poison the
            # estimator with ack-coalescing delay
            for j in range(8):
                b.send(0, HDR, b"warm-%d" % j)
            _pump([a, b], lambda: not conn.retx, timeout=3.0)
        assert conn.srtt < 0.01, conn.srtt  # warmed to loopback reality
        delivered = len(got_a)
        inject.install("drop(7,0,nth=2)")
        t0 = time.monotonic()
        b.send(0, HDR, b"fast-0")
        b.send(0, HDR, b"fast-1")  # dropped: only the timer can heal it
        _pump([a, b], lambda: len(got_a) == delivered + 2, timeout=3.5)
        assert time.monotonic() - t0 < 2.0  # the 4s ceiling never ran
        assert conn.retx_n >= 1
    finally:
        a.finalize()
        b.finalize()


def test_slow_link_no_spurious_strikes(link_knobs):
    """A slow link's inflated SRTT must HOLD the timer: a frame in
    flight for less than the link's own RTO is not loss, even when a
    fixed 40ms timer would already have struck."""
    set_var("btl_tcp", "reliable", 1)
    set_var("btl_tcp", "retx_adaptive", 1)
    set_var("btl_tcp", "rtt_min_samples", 8)
    set_var("btl_tcp", "retx_timeout_ms", 1000.0)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    sent = []
    real_transmit = b._rel_transmit
    try:
        conn.srtt, conn.rttvar, conn.rtt_n = 0.300, 0.010, 20
        _fabricate(conn, ages=[0.1])  # in flight 100ms on a 300ms link
        b._rel_transmit = lambda c, vecs, cls: sent.append(cls)
        b._rel_tick(time.monotonic())
        assert not sent and conn.retx_strikes == 0 and conn.retx_n == 0
        # the SAME aged frame on a FAST link is a timeout: the timer
        # adapts per conn, not per process
        conn.srtt, conn.rttvar = 0.001, 0.001
        b._rel_tick(time.monotonic())
        assert sent and conn.retx_strikes == 1 and conn.retx_n == 1
    finally:
        b._rel_transmit = real_transmit
        with conn.wlock:
            conn.retx.clear()  # fabricated frames must not outlive us
            conn.retx_bytes = 0
        a.finalize()
        b.finalize()


def test_nack_rate_limit_holds_storm_serves_new_hole(link_knobs):
    """Within the NACK rate limit, a repeat NACK for the hole just
    resent is the same storm and resends nothing; a NACK whose floor
    shows that hole filled names a new loss and is served at once."""
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    sent = []
    real_transmit = b._rel_transmit
    try:
        seqs = _fabricate(conn, ages=[0.0, 0.0, 0.0, 0.0])
        b._rel_transmit = lambda c, vecs, cls: sent.append(cls)
        floor = seqs[0] - 1  # the receiver lost the oldest frame
        b._rel_retransmit(conn, floor)
        assert len(sent) == 4 and conn.nack_retx_n == 4
        b._rel_retransmit(conn, floor)  # same storm: held
        assert len(sent) == 4 and conn.nack_retx_n == 4
        # the hole filled, a later frame was lost: resend the rest now
        b._rel_ack_rx(conn, seqs[1])
        b._rel_retransmit(conn, seqs[1])
        assert len(sent) == 6 and conn.nack_retx_n == 6
    finally:
        b._rel_transmit = real_transmit
        with conn.wlock:
            conn.retx.clear()  # fabricated frames must not outlive us
            conn.retx_bytes = 0
        a.finalize()
        b.finalize()


# ------------------------------------------------------------ active probe
class _FakePml:
    my_rank = 0

    def __init__(self):
        self.sent = []

    def isend(self, payload, nbytes, dtype, dst, tag, cid):
        self.sent.append((dst, tag, bytes(payload[:nbytes])))


def test_probe_round_pings_idle_links_only(lm_on):
    """A link that moved frames since the last round is measured
    passively for free — only IDLE established links get the echo."""
    rows = [{"peer": 3, "state": "est", "tx_frames": 5},
            {"peer": 4, "state": "degraded", "tx_frames": 9}]
    linkmodel.register_source(lambda: [dict(r) for r in rows])
    pml = _FakePml()
    assert linkmodel.probe_round(time.monotonic(), pml) == []  # baseline
    assert linkmodel.probe_round(time.monotonic(), pml) == [3]  # idle
    dst, tag, payload = pml.sent[0]
    assert (dst, tag) == (3, linkmodel.LINKPROBE_TAG)
    assert json.loads(payload)["op"] == "ping"
    rows[0]["tx_frames"] = 6  # traffic moved: passive coverage resumed
    assert linkmodel.probe_round(time.monotonic(), pml) == []
    assert all_pvars()["linkmodel_probes_sent"].value == 1


def test_probe_echo_handler_replies_pong(lm_on, monkeypatch):
    import ompi_tpu.pml.base as pml_base

    pml = _FakePml()
    monkeypatch.setattr(pml_base, "world_pml", lambda: pml)
    linkmodel._on_probe(None, json.dumps(
        {"op": "ping", "src": 5, "n": 2}).encode())
    dst, tag, payload = pml.sent[0]
    assert (dst, tag) == (5, linkmodel.LINKPROBE_TAG)
    assert json.loads(payload) == {"op": "pong", "n": 2}
    # a pong terminates: the envelope ack already did the measuring
    linkmodel._on_probe(None, json.dumps({"op": "pong", "n": 2}).encode())
    assert len(pml.sent) == 1
    linkmodel._on_probe(None, b"not json")  # transport thread: no raise


def test_probe_poll_disabled_and_cadence(lm_on, monkeypatch):
    """The progress slot is self-gated: off-plane or zero cadence costs
    one Var load and touches nothing; with a cadence it fires at most
    once per period."""
    calls = []
    linkmodel.register_source(lambda: calls.append(1) or [])
    set_var("linkmodel", "probe_ms", 0.0)
    assert linkmodel._probe_poll() == 0
    assert not calls  # opt-in: passive only by default
    set_var("linkmodel", "enable", False)
    set_var("linkmodel", "probe_ms", 5.0)
    assert linkmodel._probe_poll() == 0
    assert not calls  # disabled plane: the cadence never arms
    set_var("linkmodel", "enable", True)
    linkmodel._probe_next[0] = 0.0
    # pin the no-world case: an earlier in-process test may have left a
    # live world_pml, and this assertion is about the singleton path
    from ompi_tpu.pml import base as pml_base

    monkeypatch.setattr(pml_base, "world_pml", lambda: None)
    linkmodel._probe_poll()  # no pml: still no probe
    assert calls == []
    set_var("linkmodel", "probe_ms", 0.0)


def test_disabled_path_never_calls_registry(link_knobs, monkeypatch):
    """linkmodel_enable=0: the datapath's only telemetry cost is the
    one live-Var load — the registry hook must never fire."""
    assert not linkmodel._enable_var._value  # default off
    monkeypatch.setattr(
        linkmodel, "note_rtt_sample",
        lambda *a, **k: pytest.fail("registry hook on disabled path"))
    set_var("btl_tcp", "reliable", 1)
    got_a, got_b = [], []
    a, b, conn = _established(got_a, got_b)
    try:
        b.send(0, HDR, b"quiet")
        _pump([a, b], lambda: not conn.retx, timeout=3.0)
        assert conn.rtt_n >= 1  # the conn estimator still runs (retx
        # timer feeds on it) — only the export plane stays silent
    finally:
        a.finalize()
        b.finalize()


# ------------------------------------------------------ registry/consumers
def test_cvars_pvars_sampler_registered():
    vars_ = all_vars()
    for name in ("linkmodel_enable", "linkmodel_probe_ms",
                 "linkmodel_rtt_degraded_us",
                 "linkmodel_loss_degraded_ppm", "btl_tcp_retx_adaptive",
                 "btl_tcp_rtt_min_samples"):
        assert name in vars_, name
    pv = all_pvars()
    for name in ("linkmodel_rtt_samples", "linkmodel_probes_sent",
                 "linkmodel_edges", "linkmodel_srtt_max_us",
                 "linkmodel_goodput_bps"):
        assert name in pv, name
    from ompi_tpu.runtime import metrics

    # an earlier test's metrics.reset_for_testing() may have wiped the
    # sampler registry — the binding is re-invokable for exactly this
    linkmodel.register_linkmodel_sampler()
    snap = metrics.snapshot()
    lm = snap["samplers"]["btl_tcp_linkmodel"]
    assert set(lm) == {"edges", "probes_sent", "rtt_samples"}


def test_probe_tag_classifies_latency():
    """qos_tag_map default: an RTT probe queued behind bulk would
    measure the queue, not the wire."""
    assert qos.classify(linkmodel.LINKPROBE_TAG, 0) == qos.LATENCY


def test_degraded_verdict_thresholds(lm_on):
    healthy = {"state": "est", "rtt_samples": 9, "srtt_us": 900.0,
               "loss_ppm": 0.0}
    assert not linkmodel.degraded(healthy)
    assert linkmodel.degraded(dict(healthy, srtt_us=60000.0))
    assert linkmodel.degraded(dict(healthy, loss_ppm=9000.0))
    assert linkmodel.degraded(dict(healthy, state="degraded"))
    # no samples yet: a zero-srtt edge must not read healthy-by-zero
    # nor degraded-by-noise
    assert not linkmodel.degraded(
        dict(healthy, rtt_samples=0, srtt_us=0.0))
    # loss verdict is statistically gated: one corruption blip's
    # go-back-N resend burst on a near-idle edge is a huge ppm RATIO
    # but not a sustained loss RATE
    noisy = dict(healthy, loss_ppm=285714.0, nack_retx_n=2, tx_frames=7)
    assert not linkmodel.degraded(noisy)
    assert not linkmodel.degraded(
        dict(noisy, nack_retx_n=1, tx_frames=100))   # one event, any N
    assert linkmodel.degraded(
        dict(noisy, loss_ppm=90000.0, nack_retx_n=9, tx_frames=100))
    from tools import mpinet

    assert not mpinet.degraded(noisy, 50000.0, 5000.0)
    assert mpinet.degraded(
        dict(noisy, loss_ppm=90000.0, nack_retx_n=9, tx_frames=100),
        50000.0, 5000.0)


def test_cross_floor_bytes_bdp(lm_on):
    """The hier consumer: measured BDP (goodput/8 * srtt) maxed across
    edges becomes the composition min_bytes floor."""
    m = linkmodel.LinkModel(5)
    m.rtt_samples, m.srtt_us = 6, 10000.0          # 10ms
    m.goodput_bps = [8e9, 0.0, 0.0]                # 1 GB/s
    with linkmodel._lock:
        linkmodel._models[5] = m
    linkmodel.register_source(lambda: [])  # fold must not clobber it
    assert linkmodel.cross_floor_bytes() == pytest.approx(
        10_000_000, rel=0.01)
    from ompi_tpu.coll.hier import decide

    assert decide.link_floor_bytes() == linkmodel.cross_floor_bytes()
    set_var("linkmodel", "enable", False)
    assert linkmodel.cross_floor_bytes() == 0  # disabled: no floor
    assert decide.link_floor_bytes() == 0
    set_var("linkmodel", "enable", True)


def test_detector_journal_carries_link_snapshot():
    from ompi_tpu.ft import detector

    detector._reset_for_testing()
    try:
        stats = {"srtt_us": 72000.0, "rtt_samples": 11,
                 "loss_ppm": 8000.0, "goodput_bps": 1.5e9}
        detector.note_link_degraded(3, link=stats)
        detector.note_link_degraded(3)  # tick-driven repeat: deduped
        detector.note_link_restored(3, link=dict(stats, loss_ppm=0.0))
        ev = detector._fx_debug_state()["link_events"]
        assert [e["event"] for e in ev] == ["degraded", "restored"]
        assert ev[0]["rank"] == 3
        assert ev[0]["link"]["srtt_us"] == 72000.0
        assert ev[1]["link"]["loss_ppm"] == 0.0
    finally:
        detector._reset_for_testing()


def test_mpinet_check_and_render(tmp_path):
    """tools/mpinet.py offline: merge, matrix render, --check verdict
    naming the degraded edge, and the no-snapshots hint."""
    from tools import mpinet

    def snap(rank, edges):
        (tmp_path / f"metrics-rank{rank}.json").write_text(json.dumps(
            {"rank": rank,
             "samplers": {"btl_tcp_linkmodel": {"edges": edges}}}))

    good = {"srtt_us": 800.0, "rttvar_us": 100.0, "rtt_samples": 40,
            "goodput_bps": {"normal": 2e9, "latency": 0.0, "bulk": 0.0},
            "loss_ppm": 0.0, "rx_loss_ppm": 0.0, "queue_delay_us": 0.0,
            "state": "est"}
    snap(0, [dict(good, src=0, dst=1, srtt_us=65000.0),
             dict(good, src=0, dst=2)])
    snap(1, [dict(good, src=1, dst=0)])
    snaps = mpinet.read_snapshots(str(tmp_path))
    edges = mpinet.merge_edges(snaps)
    assert set(edges) == {(0, 1), (0, 2), (1, 0)}
    lines, code = mpinet.check(edges, 50000.0, 5000.0)
    assert code == 2
    assert len(lines) == 1 and "link 0->1" in lines[0] \
        and "srtt 65.0ms" in lines[0]
    assert mpinet.main(["--dir", str(tmp_path), "--check"]) == 2
    assert mpinet.main(["--dir", str(tmp_path)]) == 0  # weathermap
    frame = mpinet.render(snaps, edges, 50000.0, 5000.0)
    assert "RTT-MS" in frame and "LOSS-PPM" in frame \
        and "*65.0" in frame  # degraded cell flagged
    assert mpinet.main(["--dir", str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------- procmode proof
def test_linkmodel_delay_localizes_srtt(link_knobs):
    """60ms injected on the 0->1 wire only: rank 0's edge ->1 reads
    >= 48ms while ->2 stays under 30ms (the estimator localizes)."""
    r = run_mpi(3, "tests/procmode/check_linkmodel.py", "delay",
                mca=TCP_ONLY + LM +
                (("ft_inject_plan", "delay(0,1,ms=60)"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("LINKDELAY-OK") == 3, r.stdout + r.stderr


def test_linkmodel_corrupt_directional_and_mpinet_names_edge(
        tmp_path, link_knobs):
    """Corruption on 0->1 charges ONLY that direction's loss_ppm, and
    mpinet --check over the exported snapshots names exactly that
    edge (exit 2, the degraded verdict)."""
    r = run_mpi(3, "tests/procmode/check_linkmodel.py", "corrupt",
                mca=TCP_ONLY + LM + (
                    ("ft_inject_plan", "corrupt(0,1,nth=3)"),
                    ("btl_tcp_retx_adaptive", "0"),  # isolate the signal
                    ("metrics_enable", "1"),
                    ("metrics_dir", str(tmp_path))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("LINKCORRUPT-OK") == 3, r.stdout + r.stderr
    chk = subprocess.run(
        [sys.executable, "tools/mpinet.py", "--check",
         "--dir", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=60,
        env=subprocess_env())
    assert chk.returncode == 2, chk.stdout + chk.stderr
    assert "link 0->1" in chk.stdout, chk.stdout
    assert "0->2" not in chk.stdout and "1->0" not in chk.stdout, \
        chk.stdout


def test_linkmodel_is_a_pure_observer_bitwise(link_knobs):
    """Telemetry + active probe on vs everything off: every delivered
    payload and the allreduce result must be bitwise identical."""
    def digests(mca):
        r = run_mpi(3, "tests/procmode/check_linkmodel.py", "equal",
                    mca=TCP_ONLY + mca)
        assert r.returncode == 0, r.stdout + r.stderr
        # regex, not line-splitting: the launcher's output pump can
        # glue two ranks' lines when their writes land in one chunk
        out = sorted(re.findall(r"LINKMODEL-EQ digest=([0-9a-f]{64})",
                                r.stdout))
        assert len(out) == 3, r.stdout
        return out

    on = digests(LM + (("linkmodel_probe_ms", "20"),
                       ("metrics_enable", "1")))
    off = digests(())
    assert on == off, (on, off)


@pytest.mark.slow
@pytest.mark.parametrize("rep", range(5))
def test_linkmodel_delay_deterministic_sweep(rep, link_knobs):
    """ISSUE acceptance: the delay-localization verdict must hold 5/5
    (a 60ms signal against a loopback noise floor leaves no room for
    a flaky estimator)."""
    r = run_mpi(3, "tests/procmode/check_linkmodel.py", "delay",
                mca=TCP_ONLY + LM +
                (("ft_inject_plan", "delay(0,1,ms=60)"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("LINKDELAY-OK") == 3, r.stdout + r.stderr
