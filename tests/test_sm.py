"""Shared-memory transport: ring data plane units + multi-rank integration.

Reference: opal/mca/btl/sm FIFOs/fastboxes (btl_sm_sendi.c, btl_sm_fbox.h)
and the lock-free fifo stress tests of test/class/opal_fifo.c.
"""

import mmap
import random

import numpy as np
import pytest

from ompi_tpu.native import get_lib
from ompi_tpu.native.ring import HDR_BYTES, SmRing
from ompi_tpu.pml.base import HDR_SIZE as HDR_BYTES_PML
from tests.test_process_mode import run_mpi

NATIVE = get_lib() is not None
IMPLS = [True, False] if NATIVE else [False]


@pytest.fixture(params=IMPLS, ids=["native", "python"][: len(IMPLS)])
def ring(request):
    mm = mmap.mmap(-1, 1 << 16)
    r = SmRing(mm, 0, 1 << 16, use_native=request.param)
    r.init()
    return r


def test_native_library_builds():
    """The C++ data plane must exist in this environment (g++ is in the
    image); the Python fallback is for degraded installs only."""
    assert NATIVE


def test_native_rebuild_keyed_on_source_hash(tmp_path):
    """A library is reused only when its stamp matches the sources it
    is built from; a copied-in library with other sources, or none, is
    rebuilt whatever its mtime."""
    from ompi_tpu.native import compile_so, is_built

    src = tmp_path / "lib.c"
    src.write_text("int f(void) { return 1; }\n")
    dest = str(tmp_path / "lib.so")
    cmd = ["cc", "-shared", "-fPIC"]
    assert not is_built(cmd, [str(src)], dest)
    assert compile_so(cmd, [str(src)], dest) == dest
    assert is_built(cmd, [str(src)], dest)
    src.write_text("int f(void) { return 2; }\n")
    assert not is_built(cmd, [str(src)], dest)
    assert not is_built(cmd + ["-O2"], [str(src)], dest)


def test_ring_roundtrip(ring):
    assert ring.push(b"HDRX", b"payload") == 1
    assert ring.used() > 0
    assert ring.pop() == b"HDRXpayload"
    assert ring.pop() is None
    assert ring.used() == 0


def test_ring_empty_and_oversize(ring):
    assert ring.pop() is None
    assert ring.push(b"", b"x" * (1 << 17)) == -1  # can never fit
    cap = ring.capacity
    assert ring.push(b"", b"x" * (cap - 15)) == -1  # need+8 > cap


def test_ring_fill_then_full(ring):
    blob = b"y" * 1000
    pushed = 0
    while ring.push(b"HH", blob) == 1:
        pushed += 1
    assert pushed > 50  # ~64k / 1010
    assert ring.push(b"HH", blob) == 0  # full, retryable
    for _ in range(pushed):
        assert ring.pop() == b"HH" + blob
    assert ring.pop() is None


def test_ring_wraparound_stress(ring):
    """Varied frame sizes force WRAP sentinels at every alignment
    (reference: opal_fifo.c lock-free stress)."""
    rng = random.Random(7)
    sent, got = [], []
    for i in range(4000):
        data = bytes([i % 256]) * rng.randrange(1, 3000)
        if ring.push(b"ZZ", data) == 1:
            sent.append(b"ZZ" + data)
        else:
            f = ring.pop()
            assert f is not None
            got.append(f)
        if rng.random() < 0.3:
            f = ring.pop()
            if f is not None:
                got.append(f)
    while (f := ring.pop()) is not None:
        got.append(f)
    assert got == sent


@pytest.mark.skipif(not NATIVE, reason="needs the C++ data plane")
def test_ring_cross_implementation():
    """A Python-side producer and C++ consumer (and vice versa) must
    interoperate byte-for-byte — same mmap layout."""
    mm = mmap.mmap(-1, 1 << 14)
    py = SmRing(mm, 0, 1 << 14, use_native=False)
    py.init()
    cc = SmRing(mm, 0, 1 << 14, use_native=True)
    for i in range(200):
        assert py.push(b"AB", bytes([i]) * 97) == 1 or True
        f = cc.pop()
        if f is not None:
            assert f[:2] == b"AB"
    while cc.pop() is not None:
        pass
    assert cc.push(b"XY", b"z" * 513) == 1
    assert py.pop() == b"XY" + b"z" * 513


def test_ring_numpy_payload(ring):
    arr = np.arange(100, dtype=np.float64)
    assert ring.push(b"NP", arr) == 1
    f = ring.pop()
    np.testing.assert_array_equal(np.frombuffer(f[2:], np.float64), arr)


def test_sm_oversized_frame_with_backlog():
    """An over-ring-size frame sent while the pending queue is non-empty
    must spill to the overflow path, not queue inline — an inline frame
    that can never fit would wedge _flush() and the peer's channel
    forever (r2 advisor finding)."""
    from ompi_tpu.btl.sm import SmBtl
    from ompi_tpu.mca.var import get_var, set_var

    saved = get_var("btl_sm", "ring_bytes")
    set_var("btl_sm", "ring_bytes", 4096)
    got = []
    try:
        a = SmBtl(lambda h, p: None, my_rank=0, n_ranks=2)
        b = SmBtl(lambda h, p: got.append((bytes(h), bytes(p))),
                  my_rank=1, n_ranks=2)
        try:
            a.set_peers({1: b.seg_path})
            b.set_peers({0: a.seg_path})
            small = b"s" * 512
            hdr = b"H" * HDR_BYTES_PML
            # fill the tiny ring until sends start queueing
            for i in range(16):
                a.send(1, hdr, small)
            assert a._pending[1], "expected a backlog for this test"
            big = b"B" * 16384  # can never fit a 4KB ring
            a.send(1, hdr, big)
            tail = b"t" * 100
            a.send(1, hdr, tail)
            for _ in range(200):
                a.progress()
                b.progress()
                if len(got) == 18:
                    break
            payloads = [p for _, p in got]
            assert len(got) == 18, f"only {len(got)} frames delivered"
            assert payloads[:16] == [small] * 16
            assert payloads[16] == big  # ordered, via overflow spill
            assert payloads[17] == tail
        finally:
            a.finalize()
            b.finalize()
    finally:
        set_var("btl_sm", "ring_bytes", saved)


# ---------------------------------------------------------- multi-rank
def test_sm_procmode_4_ranks():
    r = run_mpi(4, "tests/procmode/check_sm.py",
                mca=(("btl", "sm,self"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("SM-OK") == 4


def test_sm_procmode_python_fallback():
    # rarely flakes under full-suite load on slow hosts (~1/300 runs,
    # scheduler-starved wireup); one retry with the first failure kept
    # for diagnosis — two consecutive failures still fail the test
    r = run_mpi(2, "tests/procmode/check_sm.py",
                mca=(("btl", "sm,self"), ("btl_sm_use_native", "0")))
    if r.returncode != 0 or r.stdout.count("SM-OK") != 2:
        first = f"FIRST ATTEMPT rc={r.returncode}\n{r.stdout}{r.stderr}"
        r = run_mpi(2, "tests/procmode/check_sm.py",
                    mca=(("btl", "sm,self"), ("btl_sm_use_native", "0")))
        assert r.returncode == 0, first + "\nRETRY:\n" + r.stdout + r.stderr
    assert r.stdout.count("SM-OK") == 2, r.stdout + r.stderr


def test_sm_selected_by_default_over_tcp():
    """Without --mca btl, same-host peers must pick sm (priority 30) over
    tcp (20) — the reference's default single-node transport."""
    r = run_mpi(2, "tests/procmode/check_sm.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("SM-OK") == 2


def test_bml_failover_sm_to_tcp():
    """The sm channel dies mid-job; the pml rebinds the peer to tcp and
    eager + rendezvous traffic keeps flowing (reference:
    mca_bml_r2_del_btl ejecting a failed module)."""
    r = run_mpi(2, "tests/procmode/check_failover.py",
                mca=(("btl_sm_fail_after", "8"),))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("FAILOVER-OK") == 2
