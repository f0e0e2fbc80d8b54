"""QoS traffic shaping: classification, header stamping, per-class seq
planes, system-blob segmentation/reassembly, handshake negotiation, and
the tcp btl's weighted-deficit scheduler (ompi_tpu/qos.py + the shaped
send path of btl/tcp.py).

Unit level: fake sockets and a fake loopback btl make the scheduler and
the pml reassembly provable without subprocesses. The end-to-end p99
A/B under a real replication storm lives in
tests/procmode/check_qos.py.
"""

import errno
import os
import socket
import struct
import sys
import time

import numpy as np
import pytest

from ompi_tpu import qos
from ompi_tpu.comm.communicator import Communicator, _live_comms
from ompi_tpu.core.datatype import BYTE
from ompi_tpu.core.group import Group
from ompi_tpu.mca.var import all_pvars, all_vars, set_var
from ompi_tpu.pml.base import EAGER, HDR_SIZE, Header, pack_header
from ompi_tpu.pml.ob1 import Ob1Pml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PV = all_pvars()


@pytest.fixture(autouse=True)
def _shape_cvars():
    yield
    # settle the global by-class gauges even when a test died mid-queue
    from ompi_tpu.btl import tcp as _T

    for i in range(3):
        _T._qbytes[i] = 0
        _T._qpeak[i] = 0
    set_var("btl_tcp", "shape_enable", 0)
    set_var("btl_tcp", "shape_segment_bytes", 262144)
    set_var("btl_tcp", "shape_max_defer_bytes", 4 << 20)
    set_var("btl_tcp", "shape_weights", "8,4,1")
    set_var("btl_tcp", "shape_quantum_bytes", 1 << 16)
    # the registered default: a later test file on this worker (the
    # linkmodel probe tag, -4900) reads it
    set_var("qos", "tag_map", all_vars()["qos_tag_map"].default)
    qos.reset_for_testing()


# ------------------------------------------------------------ header bits
def test_header_qos_bits_roundtrip():
    for cls in (qos.NORMAL, qos.LATENCY, qos.BULK):
        h = Header(pack_header(EAGER, 3, 17, 7, 5, 10, 2, 9, qos=cls))
        assert (h.kind, h.qos) == (EAGER, cls)
        assert (h.src, h.cid, h.tag, h.seq, h.nbytes, h.offset,
                h.msgid) == (3, 17, 7, 5, 10, 2, 9)
    # default stamp is NORMAL=0: bit-identical to the pre-QoS framing
    assert pack_header(EAGER, 1, 0, 0, 1, 0, 0, 0) == \
        pack_header(EAGER, 1, 0, 0, 1, 0, 0, 0, qos=0)


# ---------------------------------------------------------- classification
def test_tag_map_demotes_background_planes():
    set_var("btl_tcp", "shape_enable", 1)
    assert qos.classify(-4600, 0) == qos.BULK      # diskless ckpt
    assert qos.classify(-4500, 0) == qos.BULK      # metrics shipping
    assert qos.classify(-4243, 0) == qos.LATENCY   # heartbeats
    assert qos.classify(-4400, 0) == qos.NORMAL    # unlisted system tag
    assert qos.classify(5, 123) == qos.NORMAL      # user default
    assert PV["qos_stamped_bulk"].value >= 2
    assert PV["qos_stamped_latency"].value >= 1


def test_tag_map_cvar_rewrite_takes_effect():
    set_var("qos", "tag_map", "-4400:bulk")
    assert qos.classify(-4400, 0) == qos.BULK
    assert qos.classify(-4600, 0) == qos.NORMAL  # map replaced, not merged


def test_recovery_planes_classify_bulk_by_default():
    """The DEFAULT map demotes the recovery state-movement planes:
    respawn state delivery (RESPAWN_STATE_TAG 4242), the diskless
    parity/buddy-blob exchange (4243), and reshard rounds (4300) ride
    BULK — positive tags resolve through the map only when listed."""
    from ompi_tpu.ft.recovery import RESPAWN_STATE_TAG
    from ompi_tpu.mca.var import all_vars
    from ompi_tpu.reshard.exec import RESHARD_TAG

    set_var("qos", "tag_map", all_vars()["qos_tag_map"].default)
    assert qos.classify(RESPAWN_STATE_TAG, 0) == qos.BULK
    assert qos.classify(4243, 0) == qos.BULK
    assert qos.classify(RESHARD_TAG, 0) == qos.BULK
    assert qos.classify(-4800, 0) == qos.LATENCY  # forensics dumps
    assert qos.classify(4244, 0) == qos.NORMAL    # unlisted user tag
    # positive-tag entries apply ONLY on the plane-free user cid: a
    # derived plane's internal tag sequence (the NBC allocator counts
    # up from 0 per comm — its 4243rd schedule uses tag 4242) must not
    # collide with the recovery entries and silently ride BULK
    from ompi_tpu.coll.sched import NBC_CID_BIT

    assert qos.classify(RESPAWN_STATE_TAG, 7 | NBC_CID_BIT) == qos.NORMAL
    assert qos.classify(RESHARD_TAG, 7 | NBC_CID_BIT) == qos.NORMAL


def test_listed_recovery_tag_beats_comm_override():
    """A mapped positive tag wins over the per-comm class: an operator
    promoting a comm to LATENCY must not drag the recovery bytes on it
    up too (the map entry is the ONLY boundary that sees them)."""
    from ompi_tpu.ft.recovery import RESPAWN_STATE_TAG
    from ompi_tpu.mca.var import all_vars

    set_var("qos", "tag_map", all_vars()["qos_tag_map"].default)
    comm = Communicator(Group([0]), 613, name="qos-recovery")
    _live_comms[613] = comm
    try:
        comm.Set_qos_class("latency")
        assert qos.classify(5, 613) == qos.LATENCY
        assert qos.classify(RESPAWN_STATE_TAG, 613) == qos.BULK
    finally:
        _live_comms.pop(613, None)


def test_comm_attr_override_and_derived_planes():
    comm = Communicator(Group([0]), 611, name="qos-test")
    _live_comms[611] = comm
    try:
        assert qos.classify(5, 611) == qos.NORMAL
        comm.Set_qos_class("bulk")
        assert comm.Get_qos_class() == "bulk"
        assert qos.classify(5, 611) == qos.BULK
        # derived cid planes (NBC/partitioned/collective bits) inherit
        assert qos.classify(5, 611 | (1 << 28)) == qos.BULK
        # dup-style attr copy inherits through the keyval copy hook
        dup = Communicator(Group([0]), 612, name="qos-dup")
        comm._copy_attrs_to(dup)
        _live_comms[612] = dup
        assert qos.classify(5, 612) == qos.BULK
        # replacing/deleting the attr invalidates the cache
        comm.Set_qos_class("latency")
        assert qos.classify(5, 611) == qos.LATENCY
        comm.Delete_attr(qos.comm_keyval())
        assert qos.classify(5, 611) == qos.NORMAL
    finally:
        _live_comms.pop(611, None)
        _live_comms.pop(612, None)


def test_resolve_rejects_unknown_class():
    with pytest.raises(ValueError):
        qos.resolve("turbo")
    with pytest.raises(ValueError):
        qos.resolve(7)


# ------------------------------------------- segmentation + per-class seq
class _LoopBtl:
    """Delivers frames straight back into a pml (src stays the sender's
    rank in the header, so dst-rank routing is irrelevant)."""

    eager_limit = 65536

    def __init__(self, pml):
        self.pml = pml
        self.frames = []

    def send(self, peer, hdr, payload):
        self.frames.append((bytes(hdr), bytes(payload)))
        self.pml.handle_incoming(hdr, payload)


def test_system_blob_segmentation_reassembly():
    set_var("btl_tcp", "shape_enable", 1)
    set_var("btl_tcp", "shape_segment_bytes", 1 << 16)
    pml = Ob1Pml(my_rank=0)
    btl = _LoopBtl(pml)
    pml.add_endpoint(1, btl)
    got = []
    pml.register_system_handler(-4600, lambda h, pl: got.append(bytes(pl)))
    blob = np.frombuffer(bytes(range(256)) * 1024, np.uint8)  # 256KB
    before = PV["qos_segments"].value
    pml.isend(blob, blob.size, BYTE, 1, -4600, 0)
    assert len(btl.frames) == 4
    assert got == [blob.tobytes()]
    assert PV["qos_segments"].value - before == 4
    # every sub-frame: BULK class, shared msgid, advancing offsets,
    # nbytes = blob total, consecutive seqs on the BULK plane
    hdrs = [Header(h) for h, _ in btl.frames]
    assert all(h.qos == qos.BULK and h.nbytes == blob.size for h in hdrs)
    assert len({h.msgid for h in hdrs}) == 1 and hdrs[0].msgid != 0
    assert [h.offset for h in hdrs] == [i << 16 for i in range(4)]
    assert [h.seq for h in hdrs] == [1, 2, 3, 4]
    # a failover redelivery of one segment is dropped by the seq gate,
    # not double-XORed into a reassembly
    pml.handle_incoming(*btl.frames[0])
    assert got == [blob.tobytes()]
    assert not pml._sys_reasm


def test_unshaped_system_blob_stays_monolithic():
    pml = Ob1Pml(my_rank=0)
    btl = _LoopBtl(pml)
    pml.add_endpoint(1, btl)
    got = []
    pml.register_system_handler(-4600, lambda h, pl: got.append(bytes(pl)))
    blob = np.zeros(300000, np.uint8)
    pml.isend(blob, blob.size, BYTE, 1, -4600, 0)
    assert len(btl.frames) == 1 and len(got) == 1
    assert Header(btl.frames[0][0]).qos == qos.NORMAL


def test_per_class_seq_planes_are_independent():
    """A LATENCY frame stamped after BULK frames must deliver without
    waiting out a BULK gap — the per-(peer, class) continuity gates are
    the receive-side mirror of the shaped wire order."""
    pml = Ob1Pml(my_rank=0)

    def frame(seq, cls, tag, val):
        payload = np.array([val], np.int64).tobytes()
        return (pack_header(EAGER, 5, 0, tag, seq, len(payload), 0, 0,
                            qos=cls), payload)

    from ompi_tpu.core.datatype import INT64

    b1 = np.zeros(1, np.int64)
    b2 = np.zeros(1, np.int64)
    r1 = pml.irecv(b1, 1, INT64, 5, 1, 0)
    r2 = pml.irecv(b2, 1, INT64, 5, 2, 0)
    # bulk seq 1 is "in flight" (never arrives yet); latency seq 1
    # arrives and must deliver immediately on its own plane
    pml.handle_incoming(*frame(1, qos.LATENCY, 2, 222))
    assert r2.is_complete and b2[0] == 222
    assert not r1.is_complete
    pml.handle_incoming(*frame(1, qos.BULK, 1, 111))
    assert r1.is_complete and b1[0] == 111


def test_peer_failure_purges_partial_reassembly():
    set_var("btl_tcp", "shape_enable", 1)
    set_var("btl_tcp", "shape_segment_bytes", 1 << 12)
    pml = Ob1Pml(my_rank=0)
    # hand-deliver HALF a segmented blob from rank 5
    total = 1 << 13
    hdr = pack_header(EAGER, 5, 0, -4600, 1, total, 0, 77, qos=qos.BULK)
    pml.handle_incoming(hdr, bytes(1 << 12))
    assert (5, 77) in pml._sys_reasm
    set_var("ft", "enable", False)
    pml._on_peer_failed(5)
    assert not pml._sys_reasm


def test_bulk_rendezvous_frag_clamped_to_segment():
    """BULK rendezvous DATA frames ride the segment granularity so a
    LATENCY frame can preempt between fragments."""
    set_var("btl_tcp", "shape_enable", 1)
    set_var("btl_tcp", "shape_segment_bytes", 1 << 16)
    pml = Ob1Pml(my_rank=0)

    class _Sink:
        eager_limit = 1024
        frames = []

        def send(self, peer, hdr, payload):
            self.frames.append((bytes(hdr),
                                bytes(payload) if len(payload) else b""))

    sink = _Sink()
    pml.add_endpoint(1, sink)
    data = np.zeros(1 << 18, np.uint8)  # 256KB rendezvous
    sreq = pml.isend(data, data.size, BYTE, 1, 5, 0, qos=qos.BULK)
    assert Header(sink.frames[0][0]).kind != EAGER  # RTS went out
    # fake the receiver's CTS (offset slot carries the sender msgid)
    from ompi_tpu.pml.base import RNDV_CTS, RNDV_DATA

    cts = pack_header(RNDV_CTS, 1, 0, 5, 0, data.size, sreq.msgid, 99)
    pml.handle_incoming(cts, b"")
    datas = [f for f in sink.frames
             if Header(f[0]).kind == RNDV_DATA]
    assert len(datas) == 4  # 256KB / 64KB segment clamp
    assert all(Header(h).qos == qos.BULK for h, _ in datas)
    assert sreq.is_complete


# ----------------------------------------------------- shaped tcp sending
class _FakeSock:
    """Accepts ``budget`` bytes per flush window, then EAGAIN."""

    def __init__(self):
        self.wire = bytearray()
        self.budget = 0

    def sendmsg(self, vecs):
        take = min(self.budget, sum(len(v) for v in vecs))
        if take == 0:
            e = socket.error()
            e.errno = errno.EAGAIN
            raise e
        left = take
        for v in vecs:
            nb = min(len(v), left)
            self.wire += (bytes(v[:nb]) if isinstance(v, memoryview)
                          else bytes(v)[:nb])
            left -= nb
            if left == 0:
                break
        self.budget -= take
        return take

    def close(self):
        pass


def _wire_classes(wire: bytes):
    off = 0
    order = []
    while off < len(wire):
        total = struct.unpack_from("<I", wire, off)[0] & ((1 << 31) - 1)
        order.append(Header(wire[off + 4:off + 4 + HDR_SIZE]).qos)
        off += 4 + total
    return order


def _shaped_pair():
    from ompi_tpu.btl import tcp as T

    btl = T.TcpBtl(lambda h, p: None, my_rank=0)
    conn = T._Conn(_FakeSock(), peer=1)
    conn.peer_q = True
    btl.conns[1] = conn
    btl.peers = {1: "x:0"}
    return btl, conn


def _frame(tag, seq, cls, payload):
    return (pack_header(EAGER, 0, 0, tag, seq, len(payload), 0, 0,
                        qos=cls), payload)


def test_latency_preempts_queued_bulk():
    set_var("btl_tcp", "shape_enable", 1)
    btl, conn = _shaped_pair()
    before = PV["btl_tcp_shape_preemptions"].value
    for i in range(5):
        btl.send(1, *_frame(7, i + 1, qos.BULK, bytes(200)))
    assert PV["btl_tcp_shape_queued_bulk"].value > 0
    btl.send(1, *_frame(8, 1, qos.LATENCY, b"URGENT"))
    with conn.wlock:
        conn.sock.budget = 10 ** 9
        btl._flush_shaped(conn)
    order = _wire_classes(bytes(conn.sock.wire))
    assert order[0] == qos.LATENCY and order[1:] == [qos.BULK] * 5
    assert PV["btl_tcp_shape_preemptions"].value > before
    assert PV["btl_tcp_shape_queued_bulk"].value == 0
    assert PV["btl_tcp_shape_queued_latency"].value == 0
    assert PV["btl_tcp_shape_peak_queued_bulk"].value > 0
    btl.finalize()


def test_starvation_bound_serves_bulk():
    """Continuous latency traffic cannot defer a queued BULK frame past
    btl_tcp_shape_max_defer_bytes."""
    set_var("btl_tcp", "shape_enable", 1)
    set_var("btl_tcp", "shape_max_defer_bytes", 2048)
    btl, conn = _shaped_pair()
    btl.send(1, *_frame(7, 100, qos.BULK, bytes(300)))
    for i in range(40):
        btl.send(1, *_frame(8, 101 + i, qos.LATENCY, bytes(300)))
    for _ in range(200):
        with conn.wlock:
            conn.sock.budget = max(conn.sock.budget, 400)
            btl._flush_shaped(conn)
            if conn.cur is None and not any(conn.wqs):
                break
    order = _wire_classes(bytes(conn.sock.wire))
    bulk_pos = order.index(qos.BULK)
    fsz = 4 + HDR_SIZE + 300
    assert 0 < bulk_pos < len(order) - 1
    assert bulk_pos * fsz <= 2048 + 2 * fsz
    btl.finalize()


def test_partial_frame_finishes_before_preemption():
    """A frame with bytes already on the wire is unpreemptible (TCP
    frames are contiguous); one the kernel took nothing of is still
    schedulable."""
    set_var("btl_tcp", "shape_enable", 1)
    btl, conn = _shaped_pair()
    conn.sock.budget = 100  # partial: frame is 4+49+300 bytes
    btl.send(1, *_frame(9, 1, qos.BULK, bytes(300)))
    btl.send(1, *_frame(9, 2, qos.LATENCY, bytes(10)))
    for _ in range(50):
        with conn.wlock:
            conn.sock.budget = max(conn.sock.budget, 200)
            btl._flush_shaped(conn)
            if conn.cur is None and not any(conn.wqs):
                break
    assert _wire_classes(bytes(conn.sock.wire)) == [qos.BULK, qos.LATENCY]
    btl.finalize()


def test_weighted_deficit_ratio():
    """With both classes permanently backlogged, served bytes track the
    configured weights (8:1 latency:bulk by default config here 4:1)."""
    set_var("btl_tcp", "shape_enable", 1)
    set_var("btl_tcp", "shape_weights", "4,2,1")
    set_var("btl_tcp", "shape_quantum_bytes", 512)
    set_var("btl_tcp", "shape_max_defer_bytes", 0)  # pure DRR
    btl, conn = _shaped_pair()
    for i in range(60):
        btl.send(1, *_frame(7, i + 1, qos.BULK, bytes(300)))
    for i in range(60):
        btl.send(1, *_frame(8, i + 1, qos.LATENCY, bytes(300)))
    with conn.wlock:
        conn.sock.budget = 40 * (4 + HDR_SIZE + 300)
        btl._flush_shaped(conn)
    order = _wire_classes(bytes(conn.sock.wire))
    lat = sum(1 for c in order if c == qos.LATENCY)
    bulk = sum(1 for c in order if c == qos.BULK)
    assert bulk > 0, "pure DRR still serves the light class"
    assert 2.0 <= lat / bulk <= 8.0, (lat, bulk)
    btl.finalize()


def test_shape_flip_residue_drains_fifo():
    """Flipping shape_enable off with shaped backlog must not strand
    or reorder-within-class the queued frames."""
    set_var("btl_tcp", "shape_enable", 1)
    btl, conn = _shaped_pair()
    for i in range(3):
        btl.send(1, *_frame(7, i + 1, qos.BULK, bytes(100)))
    set_var("btl_tcp", "shape_enable", 0)
    btl.send(1, *_frame(7, 4, qos.NORMAL, bytes(100)))
    with conn.wlock:
        conn.sock.budget = 10 ** 9
        btl._flush_locked(conn)
    order = _wire_classes(bytes(conn.sock.wire))
    assert len(order) == 4
    assert order[:3] == [qos.BULK] * 3  # within-class FIFO preserved
    assert PV["btl_tcp_shape_queued_bulk"].value == 0
    btl.finalize()


def test_conn_failure_settles_gauges():
    set_var("btl_tcp", "shape_enable", 1)
    btl, conn = _shaped_pair()
    for i in range(4):
        btl.send(1, *_frame(7, i + 1, qos.BULK, bytes(500)))
    assert PV["btl_tcp_shape_queued_bulk"].value > 0
    btl._conn_failed(conn, OSError("boom"))
    assert PV["btl_tcp_shape_queued_bulk"].value == 0
    assert conn.cur is None


# ------------------------------------------------------------- negotiation
def test_handshake_negotiates_qos_capability():
    from ompi_tpu.btl.tcp import TcpBtl

    got = []
    a = TcpBtl(lambda h, p: None, my_rank=0)
    b = TcpBtl(lambda h, p: got.append((bytes(h), bytes(p))), my_rank=1)
    a.set_peers({1: f"127.0.0.1:{b.port}"})
    b.set_peers({0: f"127.0.0.1:{a.port}"})
    try:
        a.send(1, *_frame(7, 1, qos.NORMAL, b"ping"))
        deadline = time.time() + 10
        while len(got) < 1 and time.time() < deadline:
            a.progress()
            b.progress()
        assert got, "frame never delivered"
        conn_a = a.conns[1]
        while conn_a.await_ack and time.time() < deadline:
            a.progress()
            b.progress()
        # capability word advertised by the connector, acked by the
        # acceptor — both sides now know the peer handles class bits
        assert conn_a.peer_q and conn_a.peer_z
        assert b.conns[0].peer_q
    finally:
        a.finalize()
        b.finalize()


# ------------------------------------------------------- round-engine qos
def test_round_qos_and_plane_reach_the_pml():
    from ompi_tpu.coll.sched import Round, _issue, _RoundState

    calls = {"send": [], "recv": []}

    class _Pml:
        def isend(self, data, nbytes, dt, dst, tag, cid, qos=None):
            calls["send"].append((tag, qos))
            from ompi_tpu.core.request import CompletedRequest

            return CompletedRequest()

        def irecv(self, buf, nbytes, dt, src, tag, cid):
            calls["recv"].append(tag)
            from ompi_tpu.core.request import CompletedRequest

            return CompletedRequest()

    class _Comm:
        pml = _Pml()

        class group:
            @staticmethod
            def world_rank(x):
                return x

    rnd = Round(sends=[(np.zeros(8, np.uint8), 1)],
                recvs=[(8, 1, np.zeros(8, np.uint8))],
                ordered=False, qos=qos.BULK, plane=1)
    _issue(_Comm(), rnd, 5, 99, _RoundState())
    want_tag = 5 | (1 << 56)
    assert calls["send"] == [(want_tag, qos.BULK)]
    assert calls["recv"] == [want_tag]
    # plane 0 stays on the bare tag (wire-compat with ad-hoc schedules)
    rnd0 = Round(sends=[(np.zeros(8, np.uint8), 1)])
    _issue(_Comm(), rnd0, 5, 99, _RoundState())
    assert calls["send"][-1] == (5, None)


# ------------------------------------------------------------ registration
def test_cvar_pvar_registration():
    cvars = all_vars()
    for name in ("btl_tcp_shape_enable", "btl_tcp_shape_segment_bytes",
                 "btl_tcp_shape_quantum_bytes", "btl_tcp_shape_weights",
                 "btl_tcp_shape_max_defer_bytes", "qos_tag_map"):
        assert name in cvars, name
    for name in ("qos_stamped_normal", "qos_stamped_latency",
                 "qos_stamped_bulk", "qos_segments", "qos_reassembled",
                 "btl_tcp_shape_queued_latency",
                 "btl_tcp_shape_queued_normal",
                 "btl_tcp_shape_queued_bulk",
                 "btl_tcp_shape_preemptions", "btl_tcp_shape_enqueued"):
        assert name in PV, name


def test_prom_render_and_mpitop_cell():
    """The by-class sampler renders as a valid family and feeds the
    mpitop column."""
    import importlib.util

    from ompi_tpu.btl import tcp as T
    from ompi_tpu.runtime import metrics

    old = T._qbytes[qos.BULK]
    T._qbytes[qos.BULK] = 4096
    # an earlier test's metrics.reset_for_testing() may have wiped the
    # sampler registry — the binding is re-invokable for exactly this
    T.register_shape_sampler()
    try:
        text = metrics.render_prometheus()
        assert ('ompi_metrics_btl_tcp_shape_queued_bytes_by_class'
                '{class="bulk"') in text
        spec = importlib.util.spec_from_file_location(
            "promexport", os.path.join(REPO, "tools", "promexport.py"))
        pe = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pe)
        assert pe.validate(text) == []
        spec2 = importlib.util.spec_from_file_location(
            "mpitop", os.path.join(REPO, "tools", "mpitop.py"))
        mt = importlib.util.module_from_spec(spec2)
        spec2.loader.exec_module(mt)
        assert mt.qos_queued(metrics.snapshot()) == "0/0/4"
    finally:
        T._qbytes[qos.BULK] = old


# --------------------------------------------------------------- procmode
sys.path.insert(0, REPO)
from tests.test_quant import run_mpi  # noqa: E402


def test_qos_procmode_ab():
    """3 ranks: foreground 4KB-allreduce p99 under a 64MB replication
    storm improves >= 2x with shaping on, bulk completes, results
    bitwise-equal across modes incl. persist pipelining under chaos."""
    r = run_mpi(3, "tests/procmode/check_qos.py", timeout=420,
                mca=(("metrics_enable", "1"), ("btl_btl", "^sm"),
                     ("btl_tcp_sndbuf", str(256 << 10)),
                     ("btl_tcp_rcvbuf", str(256 << 10))))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.count("QOS-OK") == 3
    assert r.stdout.count("QOS-EQ") == 3
    assert r.stdout.count("QOS-PERSIST-EQ") == 3
    assert r.stdout.count("QOS-BULK") == 3


def test_qos_procmode_sever():
    """Severed mid-blob with shaping on: the sender raises, the
    receiver converts through pml_peer_timeout, the partial reassembly
    is purged (the PR 3 watchdog regression under shaping)."""
    r = run_mpi(2, "tests/procmode/check_qos.py", "sever", timeout=180,
                mca=(("pml_peer_timeout", "2.0"),
                     ("pml_pipeline_depth", str(2 << 20)),
                     ("btl_btl", "^sm")))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SEVER-RECV-OK" in r.stdout
    assert "SEVER-SEND-OK" in r.stdout
    assert "SEVER-PURGE-OK" in r.stdout
    assert r.stdout.count("QOS-OK") == 2
