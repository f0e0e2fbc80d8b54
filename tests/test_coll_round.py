"""Collective round engine: zero-copy datapath, pooled-recv ownership,
and round windowing (coll/sched.py, PR 10).

Unit level: a fake loopback pml drives the real engine so the ownership
contract (recycle on completion / Round.free, DISCARD on failure) and
the window semantics are provable without subprocesses. End-to-end
numbers + bitwise checks against a numpy reference live in
tests/procmode/check_coll_round.py.
"""

import threading
import time
from collections import deque

import numpy as np
import pytest

from ompi_tpu.coll import sched
from ompi_tpu.coll.sched import NbcRequest, Round, run_blocking
from ompi_tpu.core.errors import MPIError, ERR_INTERN
from ompi_tpu.core.request import Request
from ompi_tpu.mca.var import all_pvars, all_vars, set_var
from ompi_tpu.runtime import mpool

TAG = -77
CID = 9001
# a size class no other test in this file uses, so pool-accounting
# assertions are exact (other files in the same worker may park blocks
# of it: tests that count recycling start from an empty free list)
NB = 3000
CLS = mpool.size_class(NB)


# --------------------------------------------------------- fake loopback
class _Group:
    def world_rank(self, x):
        return x


class _Router:
    def __init__(self):
        self.lock = threading.Lock()
        self.mail = {}     # (dst, src, tag, cid) -> deque[bytes]
        self.wait = {}     # (dst, src, tag, cid) -> deque[(req, view)]

    def posted(self, dst):
        with self.lock:
            return sum(len(q) for (d, *_), q in self.wait.items()
                       if d == dst)


class _FakePml:
    """Loopback pml: sends copy their payload at send time (the wire),
    recvs land in the posted view. ``fail_recv_from`` completes any
    matching recv with an error instead of data."""

    def __init__(self, router, rank, fail_recv_from=()):
        self.router = router
        self.rank = rank
        self.fail_recv_from = set(fail_recv_from)

    def isend(self, data, nbytes, dt, dst, tag, cid, qos=None):
        req = Request()
        payload = np.ascontiguousarray(data).tobytes()
        key = (dst, self.rank, tag, cid)
        deliver = None
        with self.router.lock:
            q = self.router.wait.get(key)
            if q:
                deliver = q.popleft()
            else:
                self.router.mail.setdefault(key, deque()).append(payload)
        if deliver is not None:
            rreq, view = deliver
            view[:len(payload)] = np.frombuffer(payload, np.uint8)
            rreq._set_complete(0)
        req._set_complete(0)
        return req

    def irecv(self, buf, nbytes, dt, src, tag, cid):
        req = Request()
        if src in self.fail_recv_from:
            req._set_complete(ERR_INTERN)
            return req
        view = np.asarray(buf).view(np.uint8)[:nbytes]
        key = (self.rank, src, tag, cid)
        payload = None
        with self.router.lock:
            q = self.router.mail.get(key)
            if q:
                payload = q.popleft()
            else:
                self.router.wait.setdefault(key, deque()).append(
                    (req, view))
        if payload is not None:
            view[:len(payload)] = np.frombuffer(payload, np.uint8)
            req._set_complete(0)
        return req


class _FakeComm:
    def __init__(self, router, rank, size, **pml_kw):
        self.rank = rank
        self.size = size
        self.cid = CID
        self.pml = _FakePml(router, rank, **pml_kw)
        self.group = _Group()


def _pair(**kw0):
    router = _Router()
    return _FakeComm(router, 0, 2, **kw0), _FakeComm(router, 1, 2), router


def _pool_state():
    pool = mpool.class_pool(NB)
    with pool._plock:
        return pool, pool.outstanding, len(pool._free)


# ------------------------------------------------------------- ownership
def test_pooled_recv_recycles_on_completion():
    """Clean completion returns every pooled block to its free list;
    a second identical schedule is served from the pool (hits grow)."""
    c0, c1, _ = _pair()

    def gen(comm):
        bufs = yield Round(sends=[(np.arange(NB, dtype=np.uint8), 1)],
                           recvs=[(NB, 1)])
        assert bufs[0][3] == 3

    def peer(comm):
        bufs = yield Round(sends=[(np.arange(NB, dtype=np.uint8), 0)],
                           recvs=[(NB, 0)])

    pool = mpool.class_pool(NB)
    with pool._plock:
        pool._free.clear()  # both recvs below must allocate
    pool, out0, free0 = _pool_state()
    hits0 = sched._ctr["pool_hits"]
    t = threading.Thread(target=run_blocking,
                         args=(c1, peer(c1), TAG, CID))
    t.start()
    run_blocking(c0, gen(c0), TAG, CID)
    t.join()
    pool, out1, free1 = _pool_state()
    assert out1 == out0          # every block settled
    assert free1 >= free0 + 1    # ...by recycling, not discard
    t = threading.Thread(target=run_blocking,
                         args=(c1, peer(c1), TAG, CID))
    t.start()
    run_blocking(c0, gen(c0), TAG, CID)
    t.join()
    assert sched._ctr["pool_hits"] > hits0


def test_failed_schedule_discards_blocks_never_recycles():
    """A failing round DISCARDS its pooled blocks (the dying-conn
    lesson): outstanding settles but the free list must NOT grow."""
    c0, _, _ = _pair(fail_recv_from={1})

    def gen(comm):
        yield Round(recvs=[(NB, 1)])

    pool, out0, free0 = _pool_state()
    with pytest.raises(MPIError):
        run_blocking(c0, gen(c0), TAG, CID)
    pool, out1, free1 = _pool_state()
    assert out1 == out0
    # a block served from the free list and then discarded leaves the
    # list one SHORTER; a fresh-allocated one leaves it unchanged —
    # either way it must never grow (that would be a recycle)
    assert free1 <= free0


def test_round_free_recycles_early():
    """Round.free hands blocks back mid-schedule — the segmented ring's
    steady state: the NEXT round's alloc is a pool hit."""
    c0, c1, _ = _pair()

    def gen(comm):
        hits0 = sched._ctr["pool_hits"]
        bufs = yield Round(sends=[(np.zeros(NB, np.uint8), 1)],
                           recvs=[(NB, 1)])
        bufs2 = yield Round(sends=[(np.zeros(NB, np.uint8), 1)],
                            recvs=[(NB, 1)], free=bufs)
        assert sched._ctr["pool_hits"] > hits0

    def peer(comm):
        for _ in range(2):
            bufs = yield Round(sends=[(np.zeros(NB, np.uint8), 0)],
                               recvs=[(NB, 0)])

    t = threading.Thread(target=run_blocking,
                         args=(c1, peer(c1), TAG, CID))
    t.start()
    run_blocking(c0, gen(c0), TAG, CID)
    t.join()


def test_nbc_error_midschedule_discards_and_completes():
    """An NbcRequest whose child fails mid-schedule completes with the
    error and discards (never recycles) its pooled blocks."""
    router = _Router()
    c0 = _FakeComm(router, 0, 2, fail_recv_from={1})
    c0._nbc_seq = 0

    def gen(comm):
        # round 1: a pooled recv that will fail
        yield Round(recvs=[(NB, 1)])
        raise AssertionError("schedule must not advance past the error")

    pool, out0, free0 = _pool_state()
    req = NbcRequest(c0, gen(c0))
    with pytest.raises(MPIError):
        req.Wait()
    pool, out1, free1 = _pool_state()
    assert out1 == out0
    assert free1 <= free0  # discarded, never recycled


# ------------------------------------------------------------- windowing
def test_unordered_rounds_stay_in_flight():
    """With coll_round_window=4 the engine posts unordered rounds
    without waiting: all three recvs are live before the peer sends a
    byte. An ordered round is a barrier (lockstep fallback)."""
    c0, c1, router = _pair()
    set_var("coll_round", "window", 4)
    posted = []

    def gen(comm):
        dests = [np.zeros(64, np.uint8) for _ in range(3)]
        for i in range(3):
            yield Round(recvs=[(64, 1, dests[i])], ordered=False)
            posted.append(router.posted(0))
        yield Round(sends=[(np.zeros(0, np.uint8), 1)])  # flush marker
        for i, d in enumerate(dests):
            assert d[0] == i + 1  # results visible after the barrier

    def feeder():
        while router.posted(0) < 3:
            time.sleep(0.001)
        for i in range(3):
            c1.pml.isend(np.full(64, i + 1, np.uint8), 64, None, 0,
                         TAG, CID)

    t = threading.Thread(target=feeder)
    t.start()
    w0 = sched._ctr["windowed"]
    run_blocking(c0, gen(c0), TAG, CID)
    # drain the flush marker so the router is clean for other tests
    c1.pml.irecv(np.zeros(0, np.uint8), 0, None, 0, TAG, CID)
    t.join()
    set_var("coll_round", "window", 4)
    assert posted == [1, 2, 3]  # no barrier between unordered rounds
    assert sched._ctr["windowed"] >= w0 + 3


def test_window_one_is_lockstep():
    """window=1 restores the barrier-per-round engine: the second
    unordered round is not posted until the first completes."""
    c0, c1, router = _pair()
    set_var("coll_round", "window", 1)
    try:
        state = {"max_live": 0}

        def gen(comm):
            for i in range(3):
                yield Round(recvs=[(64, 1, np.zeros(64, np.uint8))],
                            ordered=False)
                state["max_live"] = max(state["max_live"],
                                        router.posted(0))

        def feeder():
            for _ in range(3):
                while router.posted(0) < 1:
                    time.sleep(0.001)
                c1.pml.isend(np.zeros(64, np.uint8), 64, None, 0,
                             TAG, CID)

        t = threading.Thread(target=feeder)
        t.start()
        run_blocking(c0, gen(c0), TAG, CID)
        t.join()
        assert state["max_live"] <= 1
    finally:
        set_var("coll_round", "window", 4)


def test_nbc_windowed_rounds_and_completion():
    """NbcRequest keeps unordered rounds in flight (no advance-blocking
    barrier) and completes once all of them retire."""
    c0, c1, router = _pair()
    c0._nbc_seq = 0
    set_var("coll_round", "window", 4)
    dests = [np.zeros(8, np.uint8) for _ in range(3)]

    def gen(comm):
        for i in range(3):
            yield Round(sends=[(np.full(8, i + 1, np.uint8), 1)],
                        recvs=[(8, 1, dests[i])], ordered=False)

    req = NbcRequest(c0, gen(c0))
    # the generator ran to exhaustion without any peer traffic: all
    # three rounds are posted concurrently
    assert router.posted(0) == 3
    assert not req.is_complete
    nbc_cid = CID | sched.NBC_CID_BIT
    for i in range(3):
        c1.pml.isend(np.full(8, 10 * (i + 1), np.uint8), 8, None, 0,
                     0, nbc_cid)
        c1.pml.irecv(np.zeros(8, np.uint8), 8, None, 0, 0, nbc_cid)
    req.Wait()
    for i, d in enumerate(dests):
        assert d[0] == 10 * (i + 1)


def test_nbc_empty_ordered_round_is_a_barrier():
    """A request-less ordered round (a pure drain point, e.g. one that
    only carries Round.free) must still act as a barrier in NbcRequest,
    matching run_blocking: the generator may not resume past it while
    windowed rounds are in flight."""
    c0, c1, router = _pair()
    c0._nbc_seq = 0
    set_var("coll_round", "window", 4)
    dest = np.zeros(8, np.uint8)
    resumed = []

    def gen(comm):
        yield Round(recvs=[(8, 1, dest)], ordered=False)
        yield Round()  # empty ordered round: barrier on resume
        resumed.append(dest[0])  # result must be visible here

    req = NbcRequest(c0, gen(c0))
    assert not resumed  # parked on the barrier, recv still in flight
    assert not req.is_complete
    nbc_cid = CID | sched.NBC_CID_BIT
    c1.pml.isend(np.full(8, 42, np.uint8), 8, None, 0, 0, nbc_cid)
    req.Wait()
    assert resumed == [42]


# ------------------------------------------------------- zero-copy sends
def test_contiguous_send_is_borrowed_not_copied():
    """A contiguous send payload travels as a borrowed view: the copy
    counter must not move. A strided source pays one counted copy."""
    c0, c1, router = _pair()

    def gen(comm, data):
        yield Round(sends=[(data, 1)])

    c1.pml.irecv(np.zeros(256, np.uint8), 256, None, 0, TAG, CID)
    cp0 = sched._ctr["copied"]
    run_blocking(c0, gen(c0, np.zeros(256, np.uint8)), TAG, CID)
    assert sched._ctr["copied"] == cp0
    c1.pml.irecv(np.zeros(256, np.uint8), 256, None, 0, TAG, CID)
    strided = np.zeros(512, np.uint8)[::2]
    run_blocking(c0, gen(c0, strided), TAG, CID)
    assert sched._ctr["copied"] == cp0 + 256


# ----------------------------------------------------------- registration
def test_cvars_and_pvars_registered():
    vars_ = all_vars()
    assert "coll_round_window" in vars_
    assert vars_["coll_round_window"].default == 4
    pv = all_pvars()
    for name in ("coll_round_bytes_copied", "coll_round_bytes_moved",
                 "coll_round_pool_hits", "coll_round_windowed"):
        assert name in pv, name
        assert isinstance(pv[name].value, int)


def test_info_cli_lists_coll_round_surface(capsys):
    from ompi_tpu.tools.info import main as info_main

    info_main(["--level", "9", "--param", "coll_round", "--pvars"])
    out = capsys.readouterr().out
    assert "coll_round_window" in out
    assert "coll_round_bytes_copied" in out
    assert "coll_round_pool_hits" in out


# -------------------------------------------------------------- procmode
def _run_mpi(np_, mca=()):
    from tests.test_process_mode import run_mpi

    return run_mpi(np_, "tests/procmode/check_coll_round.py",
                   timeout=240,
                   mca=(("coll_coll", "^sm,adapt,han,hier,quant"),)
                   + tuple(mca))


def test_coll_round_procmode_ab_and_window():
    """End-to-end gate: copies-per-byte-moved under its bound, pool
    hits in steady state, windowed alltoall, and every swept verb
    bitwise equal to the numpy reference, lockstep and windowed."""
    r = _run_mpi(4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COLLROUND-OK") == 4
    assert r.stdout.count("COLLROUND-EQ") == 4


def test_coll_round_chaos_delay_dup_windowed():
    """Window >1 over the real tcp wire under chaos delay+dup with idle
    parks armed: the seq gate absorbs duplicates, parks don't lose
    wakeups, results stay bitwise-correct."""
    r = _run_mpi(3, mca=(
        ("btl_btl", "^sm"),
        ("ft_inject_plan", "delay(0,1,ms=5,side=recv);dup(0,1,nth=3)"),
        ("runtime_idle_block_us", 500000)))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COLLROUND-OK") == 3
