"""Zero-copy vectored tcp datapath + idle-blocking progress.

Covers the write-queue/sendmsg path (ownership, integrity under
backlog, jumbo-frame rx growth), the measured copy counters and their
bound, the idle-block select park (fd wake, poke wake, timeout,
poll-only cap, lost-wakeup recheck), the thread-safe progress cadence,
and the hot-copy lint rule. The end-to-end numbers live in
tests/procmode/check_p2p.py.
"""

import threading
import time

import numpy as np
import pytest

import ompi_tpu.pml.ob1  # registers pml vars
from ompi_tpu.btl.tcp import TcpBtl, _ctr
from ompi_tpu.mca.var import all_pvars, all_vars, set_var
from ompi_tpu.pml.base import HDR_SIZE, pack_header
from ompi_tpu.runtime import progress as P

HDR = pack_header(1, 0, 0, 5, 1, 5, 0, 0)


@pytest.fixture
def tcp_pair():
    got = []
    a = TcpBtl(lambda h, p: got.append((bytes(h), bytes(p))), my_rank=0)
    b = TcpBtl(lambda h, p: got.append((bytes(h), bytes(p))), my_rank=1)
    a.set_peers({1: f"127.0.0.1:{b.port}"})
    b.set_peers({0: f"127.0.0.1:{a.port}"})
    yield a, b, got
    a.finalize()
    b.finalize()


def _pump(btls, pred, t=10):
    t0 = time.time()
    while not pred() and time.time() - t0 < t:
        for x in btls:
            x.progress()


# ------------------------------------------------------------- write path
def test_small_send_is_zero_copy(tcp_pair):
    """An uncontended small send goes straight to the kernel as one
    vectored push: no payload copy, one sendmsg."""
    a, b, got = tcp_pair
    payload = np.frombuffer(b"hello", np.uint8)
    c0, w0 = _ctr["copied"], _ctr["writev"]
    a.send(1, HDR, payload)
    _pump([a, b], lambda: got)
    assert got[0][1] == b"hello"
    assert _ctr["copied"] == c0          # zero copies
    assert _ctr["writev"] == w0 + 1      # one vectored syscall


def test_backlog_copies_once_and_stays_exact(tcp_pair):
    """Under backpressure the unsent remainder is copied ONCE into the
    owned queue — the caller's buffer can be reused immediately — and
    the stream stays byte-exact."""
    a, b, got = tcp_pair
    payload = np.arange(1 << 20, dtype=np.uint8) % 199
    expect = payload.tobytes()
    c0 = _ctr["copied"]
    scratch = payload.copy()
    for _ in range(24):  # no draining: forces EAGAIN queueing
        a.send(1, HDR, scratch)
    scratch[:] = 0  # caller reuses its buffer — queued bytes are owned
    _pump([a, b], lambda: len(got) >= 24, t=30)
    assert len(got) == 24
    assert all(g[1] == expect for g in got)
    assert _ctr["copied"] > c0  # the backlog really was owned


def test_rx_jumbo_frame_grows_past_pool_block(tcp_pair):
    """A frame larger than the rx pool block grows into a private
    buffer and is delivered intact; the conn then reacquires a pooled
    block."""
    a, b, got = tcp_pair
    big = (np.arange(3 << 20, dtype=np.int64) % 251).astype(np.uint8)
    a.send(1, HDR, big)
    _pump([a, b], lambda: got, t=30)
    assert got[0][1] == big.tobytes()


def test_noncontiguous_payload_falls_back_to_copy(tcp_pair):
    """A strided source can't be viewed flat: the send path owns it
    with one counted copy and the bytes are right."""
    a, b, got = tcp_pair
    arr = np.arange(64, dtype=np.uint8)[::2]
    c0 = _ctr["copied"]
    a.send(1, HDR, arr)
    _pump([a, b], lambda: got)
    assert got[0][1] == arr.tobytes()
    assert _ctr["copied"] == c0 + arr.nbytes


# The lowest copies per wire byte the copying datapath this one
# replaced ever measured on this workload (1.999565475918218, the same
# in 12 of 12 runs): the old gate asked that path for at least twice
# the vectored path's copies, so half of it is the most it allowed.
LEGACY_COPIES_PER_WIRE_BYTE = 1.999565


def test_copy_mode_ab_is_measured_and_worse(tcp_pair):
    """The vectored path's copies per wire byte, measured from the
    counters on eight 64 KiB sends, stay under half the lowest the
    copying datapath it replaced measured (count-based, deterministic
    by construction)."""
    a, b, got = tcp_pair
    payload = np.zeros(1 << 16, np.uint8)
    c0, w0 = _ctr["copied"], _ctr["wire"]
    for _ in range(8):
        a.send(1, HDR, payload)
    _pump([a, b], lambda: len(got) >= 8, t=30)
    assert len(got) == 8
    ratio = (_ctr["copied"] - c0) / max(_ctr["wire"] - w0, 1)
    assert _ctr["wire"] > w0
    assert ratio <= LEGACY_COPIES_PER_WIRE_BYTE / 2, ratio


# -------------------------------------------------------------- idle block
@pytest.fixture
def idle_env(tcp_pair):
    a, b, got = tcp_pair
    P.register_progress(a.progress)
    P.register_progress(b.progress)
    P.set_idle_sources([a.idle_fds, b.idle_fds])
    yield a, b, got
    P.unregister_progress(a.progress)
    P.unregister_progress(b.progress)
    P.set_idle_sources([])
    set_var("runtime", "idle_block_us", 50000)


def test_frame_wakes_parked_progress_until(idle_env):
    """A frame arriving while progress_until is parked in select wakes
    it within the poll budget — no missed-wakeup hang, no waiting out
    the park interval."""
    a, b, got = idle_env
    set_var("runtime", "idle_block_us", 3_000_000)  # 3s park cap
    before = all_pvars()["runtime_progress_idle_blocks"].value

    def late():
        time.sleep(0.4)
        a.send(1, HDR, b"wake")

    t = threading.Thread(target=late)
    t.start()
    t0 = time.monotonic()
    ok = P.progress_until(lambda: bool(got), timeout=10)
    el = time.monotonic() - t0
    t.join()
    assert ok and got[0][1] == b"wake"
    assert el < 1.5, f"woke in {el:.3f}s — parked past the frame"
    assert all_pvars()["runtime_progress_idle_blocks"].value > before


def test_progress_until_timeout_honored_under_long_cap(idle_env):
    set_var("runtime", "idle_block_us", 3_000_000)
    t0 = time.monotonic()
    assert not P.progress_until(lambda: False, timeout=0.3)
    el = time.monotonic() - t0
    assert 0.25 < el < 1.5, el


def test_poke_wakes_parked_wait(idle_env):
    """Off-transport producers wake a parked wait via the self-pipe
    (the request-completion poke rides the same path)."""
    set_var("runtime", "idle_block_us", 3_000_000)
    flag = []

    def poker():
        time.sleep(0.3)
        flag.append(1)
        P.poke()

    t = threading.Thread(target=poker)
    t.start()
    t0 = time.monotonic()
    assert P.progress_until(lambda: bool(flag), timeout=10)
    el = time.monotonic() - t0
    t.join()
    assert el < 1.2, el


def test_poll_only_source_caps_the_park(idle_env):
    """A poll-only transport (None source, the sm rings) bounds every
    park at the caller's legacy interval — sm latency is unchanged."""
    a, b, _ = idle_env
    P.set_idle_sources([a.idle_fds, None])
    set_var("runtime", "idle_block_us", 3_000_000)
    t0 = time.monotonic()
    P.progress_until(lambda: False, timeout=0.08)
    assert time.monotonic() - t0 < 1.0


def test_idle_block_disabled_restores_sleep_backoff(idle_env):
    set_var("runtime", "idle_block_us", 0)
    before = all_pvars()["runtime_progress_idle_blocks"].value
    P.progress_until(lambda: False, timeout=0.05)
    assert all_pvars()["runtime_progress_idle_blocks"].value == before


def test_progress_thread_parks_and_stops_fast(idle_env):
    set_var("runtime", "idle_block_us", 3_000_000)
    before = all_pvars()["runtime_progress_idle_blocks"].value
    pt = P.ProgressThread()
    pt.start()
    time.sleep(0.6)  # hot window drains, then it must park
    t0 = time.monotonic()
    pt.stop()
    el = time.monotonic() - t0
    assert el < 1.0, f"stop() took {el:.2f}s — the poke missed the park"
    assert all_pvars()["runtime_progress_idle_blocks"].value > before


def test_progress_cadence_is_exact_under_threads():
    """Satellite: the every-8th low-priority cadence is thread-safe.
    The old bare `_call_count += 1` raced between the app thread and
    the ProgressThread, so the cadence could stall or double-fire;
    itertools.count draws are atomic, making the firing count an exact
    function of the counter values drawn in the window."""
    lock = threading.Lock()
    calls = [0]

    def low():
        with lock:
            calls[0] += 1
        return 0

    P.register_progress(low, low_priority=True)
    try:
        c_before = next(P._call_count)
        f0 = calls[0]
        threads = [threading.Thread(
            target=lambda: [P.progress() for _ in range(200)])
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fired = calls[0] - f0
        c_after = next(P._call_count)
        # exact count of multiples of 8 drawn in (c_before, c_after)
        expected = (c_after - 1) // 8 - c_before // 8
        # +-2: an unrelated progress caller can straddle the sampling
        # edges; the pre-fix race lost/duplicated fires proportionally
        # to contention, far outside this band
        assert abs(fired - expected) <= 2, (fired, expected)
        assert fired >= (4 * 200) // 8 - 2
    finally:
        P.unregister_progress(low)


# ------------------------------------------------------------ registered
def test_datapath_cvars_and_pvars_registered():
    vars_ = all_vars()
    for name in ("btl_tcp_writev_max_vecs", "runtime_idle_block_us"):
        assert name in vars_, name
    assert vars_["runtime_idle_block_us"].default == 50000
    pvars = all_pvars()
    for name in ("btl_tcp_bytes_copied", "btl_tcp_writev_calls",
                 "btl_tcp_wire_bytes", "runtime_progress_idle_blocks",
                 "mpool_pool_blocks", "mpool_pool_bytes",
                 "mpool_pool_hits", "mpool_pool_misses"):
        assert name in pvars, name


def test_class_pool_park_budget_caps_big_classes():
    """The free list keeps at most _CLASS_PARK_BYTES of parked BYTES
    per class (not max_free blocks): a burst of jumbo-class recvs must
    not pin max_free * 8 MiB of idle memory for process lifetime."""
    from ompi_tpu.runtime import mpool

    cls = 1 << 23  # 8 MiB class: budget allows 4 parked, not 8
    pool = mpool.class_pool(cls)
    want = max(1, min(8, mpool._CLASS_PARK_BYTES // cls))
    assert pool.max_free == want == 4
    blocks = [pool.acquire() for _ in range(6)]
    base_free = 0  # parked beyond the budget is the bug being pinned
    for b in blocks:
        pool.release(b)
    assert len(pool._free) == min(base_free + 6, pool.max_free)
    assert pool.outstanding == 0
    pool._free.clear()  # do not pin 32 MiB across the rest of the run


def test_pool_discard_accounts_without_recycling():
    """discard settles the accounting pvars exactly like release but
    never parks the block: a teardown path racing an in-flight reader
    must not let the pool hand that block to someone else."""
    from ompi_tpu.mca.var import all_pvars
    from ompi_tpu.runtime import mpool

    pool = mpool.BufferPool(4096, max_free=4)
    try:
        pv = all_pvars()
        blocks0 = pv["mpool_pool_blocks"].value
        bytes0 = pv["mpool_pool_bytes"].value
        blk = pool.acquire()
        assert pv["mpool_pool_blocks"].value == blocks0 + 1
        assert pv["mpool_pool_bytes"].value == bytes0 + 4096
        pool.discard(blk)
        # accounted as gone...
        assert pv["mpool_pool_blocks"].value == blocks0
        assert pv["mpool_pool_bytes"].value == bytes0
        # ...and NOT recycled: the next acquire allocates fresh
        assert pool._free == []
        nxt, hit = pool.acquire_pair()
        assert hit is False
        assert nxt is not blk
        pool.release(nxt)
    finally:
        pool.close()


def test_acquire_pair_settles_exactly_once():
    """One acquire_pair, one settle: a second settle of the same block
    (the mpiown double-settle class) must not drive outstanding
    negative or double-park the block."""
    from ompi_tpu.runtime import mpool

    pool = mpool.BufferPool(1024, max_free=4)
    try:
        a, hit_a = pool.acquire_pair()
        assert hit_a is False and pool.misses == 1
        assert pool.outstanding == 1
        pool.release(a)
        assert pool.outstanding == 0
        assert len(pool._free) == 1
        # the buggy second settle: accounting must clamp, not corrupt —
        # the same object parked twice would hand one block to TWO
        # acquirers
        pool.release(a)
        assert pool.outstanding == 0
        assert len(pool._free) == 1
        b, hit_b = pool.acquire_pair()
        assert hit_b is True and pool.hits == 1
        assert b is a
        pool.discard(b)
        assert pool.outstanding == 0
    finally:
        pool.close()


def test_info_cli_lists_datapath_surface(capsys):
    from ompi_tpu.tools.info import main as info_main

    info_main(["--level", "9", "--param", "btl_tcp"])
    out = capsys.readouterr().out
    assert "btl_tcp_writev_max_vecs" in out
    info_main(["--level", "9", "--param", "runtime"])
    out = capsys.readouterr().out
    assert "runtime_idle_block_us" in out


def test_btl_idle_contract():
    from ompi_tpu.btl.base import Btl
    from ompi_tpu.btl.self_btl import SelfBtl
    from ompi_tpu.btl.sm import SmBtl

    assert Btl.NEEDS_POLL is True          # conservative default
    assert SmBtl.NEEDS_POLL is True        # ring polling caps the park
    assert SelfBtl.NEEDS_POLL is False     # inline delivery
    assert TcpBtl.NEEDS_POLL is False      # fd-driven
    b = TcpBtl(lambda h, p: None, my_rank=0)
    try:
        rfds, wfds = b.idle_fds()
        assert b.listener.fileno() in rfds and wfds == []
    finally:
        b.finalize()
        assert b.idle_fds() == ([], [])


def test_owned_boundary_copy():
    from ompi_tpu.pml.ob1 import _owned

    view = memoryview(bytearray(b"abc"))
    out = _owned(view)
    assert isinstance(out, bytes) and out == b"abc"
    blob = b"xyz"
    assert _owned(blob) is blob  # owned stays un-copied


# ---------------------------------------------------------- procmode proof
def test_p2p_procmode_zero_copy_and_idle_block():
    """End to end over real sockets: correctness, copies-per-wire-byte
    measured from the pvars under each rank's bound, and a quiet rank's
    progress loop provably parked in select. Count-based gates only
    (noise discipline: the stripe-test lesson)."""
    from tests.test_process_mode import run_mpi

    r = run_mpi(2, "tests/procmode/check_p2p.py", timeout=150,
                mca=(("btl_btl", "^sm"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("P2P-CORRECT") == 2, r.stdout + r.stderr
    assert r.stdout.count("P2P-OK") == 2, r.stdout + r.stderr


# ------------------------------------------------------------- lint rule
def test_mpilint_hot_copy_rule():
    """Satellite: the hot-copy rule flags the three copy-tax shapes in
    datapath modules, honors suppressions, and ignores cold modules."""
    from ompi_tpu.analysis.lint import lint_source

    bad = (
        "def _drain(self, conn, data):\n"
        "    conn.rbuf += data\n"
        "    hdr = bytes(conn.rbuf[0:49])\n"
        "    payload = bytes(memoryview(data))\n")
    got = lint_source(bad, "ompi_tpu/btl/tcp.py")
    assert sum(1 for f in got if f.rule == "hot-copy") == 3, got
    # same source in a non-datapath module: silent
    assert not [f for f in lint_source(bad, "ompi_tpu/coll/basic.py")
                if f.rule == "hot-copy"]
    suppressed = (
        "def _drain(self, conn, data):\n"
        "    conn.rbuf += data  # mpilint: disable=hot-copy — boundary\n")
    assert not [f for f in lint_source(suppressed, "ompi_tpu/btl/tcp.py")
                if f.rule == "hot-copy"]
