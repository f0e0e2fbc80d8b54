"""mpiown: the static buffer-ownership / zero-copy lifetime gate.

Tier-1 runs the ownership pass over the whole ``ompi_tpu`` package and
demands zero findings — every pool block acquired anywhere in the tree
is settled on every path, every owning attribute store is declared
(``# owns:``), every read-only send view is declared (``# borrows:``),
and every deliberate deviation carries a justified
``# mpiown: disable=<rule> — why`` suppression. The self-test (one
seeded-bad snippet per rule plus the derive-parity check over the real
tree) proves every rule can actually fire and that the swept module
set cannot silently shrink.

The two regression tests at the bottom pin the REAL bugs the first
tree sweep surfaced: the tcp rx-regrow spurious release and the
persist non-commutative-allreduce staging leak.
"""

import errno
import json
import os
import socket
import subprocess
import sys
import types

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "ompi_tpu")
sys.path.insert(0, REPO)

from ompi_tpu.analysis import ownership, pkgmodel  # noqa: E402
from ompi_tpu.analysis.report import format_finding  # noqa: E402
from tools import mpiown  # noqa: E402


# ------------------------------------------------------------ tier-1 gate
def test_tree_clean():
    """The CI gate: zero ownership findings over the package."""
    findings = mpiown.analyze_paths([PKG])
    assert findings == [], "\n" + "\n".join(
        format_finding(f) for f in findings)


def test_every_rule_fires_and_derive_parity_holds():
    _findings, missed, parity = mpiown.self_test()
    assert missed == []
    assert parity == []


def test_rule_table_covers_analyzer_and_common():
    assert set(mpiown.SELF_TEST_SNIPPETS) == set(mpiown.RULES)
    assert set(ownership.RULES) <= set(mpiown.RULES)
    assert "bare-suppression" in mpiown.RULES
    assert "parse-error" in mpiown.RULES


def test_derive_parity_flags_both_directions():
    """derive_parity is symmetric: a curated module the conventions no
    longer match is `missing`; pool traffic in an unrecorded module is
    `unlisted` — either direction fails the self-test."""
    real = pkgmodel.load_package([PKG], tool=ownership.TOOL)
    derived = ownership.derive_datapath(real)
    assert set(ownership.OWNERSHIP_MODULES) == derived
    # a synthetic tree with pool traffic in a module not in the record
    src = "def go(pool):\n    b = pool.acquire()\n    pool.release(b)\n"
    pkg = pkgmodel.load_source(src, "ompi_tpu/osc/window.py",
                               tool=ownership.TOOL)
    missing, unlisted = ownership.derive_parity(pkg)
    assert "osc/window.py" in unlisted
    assert missing == set(ownership.OWNERSHIP_MODULES)


# ----------------------------------------------------------------- the CLI
def test_self_test_cli_exits_one_with_all_rules_firing():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mpiown", "--self-test"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    for rule in mpiown.RULES:
        assert f"[{rule}]" in r.stderr, f"rule {rule} missing from output"
    assert "derive parity holds" in r.stdout


def test_cli_clean_tree_exits_zero():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mpiown", "ompi_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_cli_json_output_is_scriptable():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mpiown", "--json", "ompi_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["clean"] is True
    assert doc["findings"] == []


def test_cli_bad_path_exits_two():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mpiown", "no/such/dir"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 2


# ------------------------------------------------------------- rule units
def test_pool_leak_on_fallthrough():
    src = "def go(pool):\n    block = pool.acquire()\n"
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["pool-leak"]
    assert got[0].line == 2  # reported at the acquire site


def test_pool_leak_on_except_edge():
    src = (
        "def go(pool, sink):\n"
        "    block = pool.acquire()\n"
        "    try:\n"
        "        sink.push(block)\n"
        "    except RuntimeError:\n"
        "        return None\n"
        "    pool.release(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["pool-leak"]


def test_settled_on_every_path_is_clean():
    src = (
        "def go(pool, sink):\n"
        "    block = pool.acquire()\n"
        "    try:\n"
        "        sink.push(block)\n"
        "    except RuntimeError:\n"
        "        pool.discard(block)\n"
        "        return None\n"
        "    pool.release(block)\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_return_transfers_ownership():
    src = (
        "def lease(pool):\n"
        "    block = pool.acquire()\n"
        "    return block\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_acquire_pair_tuple_target_tracks_block():
    src = (
        "def go(pool):\n"
        "    block, hit = pool.acquire_pair()\n"
        "    pool.release(block)\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/coll/x.py") == []


def test_lock_acquire_is_not_an_obligation():
    src = (
        "def go(lock, sem):\n"
        "    lock.acquire()\n"
        "    sem.release()\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/pml/x.py") == []


def test_recycle_on_failure_in_except_handler():
    src = (
        "def drain(pool, conn):\n"
        "    block = pool.acquire()\n"
        "    try:\n"
        "        conn.recv_into(block)\n"
        "    except OSError:\n"
        "        pool.release(block)\n"
        "        return\n"
        "    pool.discard(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["recycle-on-failure"]


def test_recycle_on_failure_in_failure_named_function():
    src = (
        "def _conn_failed(pool, block):\n"
        "    pool.release(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["recycle-on-failure"]


def test_failure_context_propagates_to_same_module_callees():
    """fail() delegating to a helper keeps the failure verdict: the
    helper's recycle is still a finding."""
    src = (
        "def fail(pool, block):\n"
        "    _drop(pool, block)\n"
        "def _drop(pool, block):\n"
        "    pool.release(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/coll/x.py")
    assert [f.rule for f in got] == ["recycle-on-failure"]


def test_discard_on_failure_is_clean():
    src = (
        "def _conn_failed(pool, block):\n"
        "    pool.discard(block)\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_double_settle_on_one_path():
    src = (
        "def go(pool):\n"
        "    block = pool.acquire()\n"
        "    pool.release(block)\n"
        "    pool.discard(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/coll/x.py")
    assert [f.rule for f in got] == ["double-settle"]


def test_settle_on_disjoint_branches_is_clean():
    src = (
        "def go(pool, ok):\n"
        "    block = pool.acquire()\n"
        "    if ok:\n"
        "        pool.release(block)\n"
        "    else:\n"
        "        pool.discard(block)\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/coll/x.py") == []


def test_escaping_view_store_into_self():
    src = (
        "class Ring:\n"
        "    def park(self, pool):\n"
        "        block = pool.acquire()\n"
        "        view = memoryview(block)\n"
        "        self.stash = view\n"
        "        pool.release(block)\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["escaping-view"]


def test_copied_view_does_not_escape():
    src = (
        "class Ring:\n"
        "    def park(self, pool):\n"
        "        block = pool.acquire()\n"
        "        view = memoryview(block)\n"
        "        self.stash = bytes(view)\n"
        "        pool.release(block)\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_borrow_mutation_through_declared_send_view():
    src = (
        "def corrupt(buf):\n"
        "    v = memoryview(buf)  # borrows: buf\n"
        "    v[0] = 1\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/pml/x.py")
    assert [f.rule for f in got] == ["borrow-mutation"]


def test_undeclared_view_may_be_written():
    """Only a # borrows:-DECLARED view is read-only; the rx parse path
    legitimately writes through its own views."""
    src = (
        "def compact(buf):\n"
        "    v = memoryview(buf)\n"
        "    v[0] = 1\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/pml/x.py") == []


# ---------------------------------------------------- annotation semantics
def test_owns_annotation_transfers_obligation_on_acquire_line():
    src = (
        "class C:\n"
        "    def stage(self, pool):\n"
        "        self.block = pool.acquire()  # owns: block\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_owns_annotation_on_the_store_line():
    src = (
        "class C:\n"
        "    def stage(self, pool):\n"
        "        block = pool.acquire()\n"
        "        self.held.append((pool, block))  # owns: held\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/coll/x.py") == []


def test_unannotated_attribute_acquire_is_a_leak():
    src = (
        "class C:\n"
        "    def stage(self, pool):\n"
        "        self.block = pool.acquire()\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["pool-leak"]


def test_justified_suppression_silences_only_that_rule():
    src = (
        "def go(pool):\n"
        "    block = pool.acquire()"
        "  # mpiown: disable=pool-leak — test fixture\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/btl/x.py") == []


def test_bare_suppression_is_itself_a_finding():
    src = (
        "def go(pool):\n"
        "    block = pool.acquire()  # mpiown: disable=pool-leak\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["bare-suppression"]


def test_wrong_rule_suppression_does_not_silence():
    src = (
        "def go(pool):\n"
        "    block = pool.acquire()"
        "  # mpiown: disable=double-settle — wrong rule\n"
    )
    got = mpiown.analyze_source(src, "ompi_tpu/btl/x.py")
    assert [f.rule for f in got] == ["pool-leak"]


def test_multi_rule_suppression_applies_every_rule():
    """The satellite fix: `disable=a,b — why` must suppress BOTH rules
    (the old greedy parse swallowed the separator and applied only the
    first)."""
    src = (
        "def go(pool):\n"
        "    block = pool.acquire()\n"
        "    pool.release(block)\n"
        "    pool.release(block)"
        "  # mpiown: disable=double-settle,pool-leak — fixture\n"
    )
    assert mpiown.analyze_source(src, "ompi_tpu/coll/x.py") == []


def test_parse_error_is_a_finding():
    got = mpiown.analyze_source("def broken(:\n", "ompi_tpu/coll/x.py")
    assert [f.rule for f in got] == ["parse-error"]


# --------------------------------------- regressions for the real fixes
def test_rx_regrow_does_not_release_unpooled_buffer():
    """Real fix #1 (found by the first mpiown sweep of btl/tcp.py): the
    _drain regrow path released whatever buffer was full — including a
    PRIVATE already-grown bytearray (a second jumbo outgrowing the
    first, or legacy-residue adoption that exactly filled its grown
    buffer) — spuriously decrementing _rx_pool.outstanding for a block
    the pool never handed out. Only a pool-sized block may go back."""
    from ompi_tpu.btl import tcp as btl_tcp

    # a legitimately-outstanding block, so a spurious release would
    # really decrement (the guard `outstanding > 0` would not mask it)
    held = btl_tcp._rx_pool.acquire()
    try:
        before = btl_tcp._rx_pool.outstanding

        class EagainSock:
            def recv_into(self, mv):
                raise socket.error(errno.EAGAIN, "try again")

        grown = bytearray(2 * btl_tcp._RX_BLOCK)  # private, NOT pooled
        conn = types.SimpleNamespace(
            sock=EagainSock(), rxb=grown,
            rstart=0, rend=len(grown))
        n = btl_tcp.TcpBtl._drain(object.__new__(btl_tcp.TcpBtl), conn)
        assert n == 0
        # the buffer regrew privately...
        assert len(conn.rxb) == 4 * btl_tcp._RX_BLOCK
        assert conn.rend == 2 * btl_tcp._RX_BLOCK
        # ...and the pool's accounting was NOT touched
        assert btl_tcp._rx_pool.outstanding == before
    finally:
        btl_tcp._rx_pool.release(held)


def test_rx_regrow_still_releases_the_pooled_block():
    """The guard must not over-correct: a pool-SIZED block that fills
    (first jumbo grow) still goes back to the pool exactly once."""
    from ompi_tpu.btl import tcp as btl_tcp

    block = btl_tcp._rx_pool.acquire()
    before = btl_tcp._rx_pool.outstanding

    class EagainSock:
        def recv_into(self, mv):
            raise socket.error(errno.EAGAIN, "try again")

    conn = types.SimpleNamespace(
        sock=EagainSock(), rxb=block,
        rstart=0, rend=len(block))
    btl_tcp.TcpBtl._drain(object.__new__(btl_tcp.TcpBtl), conn)
    assert len(conn.rxb) == 2 * btl_tcp._RX_BLOCK  # grew past the pool
    assert btl_tcp._rx_pool.outstanding == before - 1


def test_persist_noncommutative_fallback_settles_builder_blocks(
        monkeypatch):
    """Real fix #2 (found by the first mpiown sweep of coll/persist.py):
    _b_allreduce's non-commutative branch acquires fan-in staging into
    b.held via _reduce_into, then bailed `return None` when the bcast
    leg could not freeze — leaking the held blocks for process life (no
    finalizer exists yet; the _Builder is a local). The fallback now
    settles them through _Builder.abort()."""
    from ompi_tpu.coll import persist
    from ompi_tpu.runtime import mpool

    class FakeOp:
        commutative = False

    class FakeComm:
        size = 2
        rank = 0

    recv = np.zeros(1024, np.float64)  # 8 KiB staging: poolable class
    pool = mpool.class_pool(recv.nbytes)
    assert pool is not None
    before = pool.outstanding
    monkeypatch.setattr(persist, "_b_bcast", lambda *a, **k: None)
    out = persist._b_allreduce(FakeComm(), None, recv, FakeOp())
    assert out is None            # still falls back to re-issue
    assert pool.outstanding == before  # ...without leaking staging


def test_builder_abort_recycles_all_held_blocks():
    from ompi_tpu.coll import persist
    from ompi_tpu.runtime import mpool

    b = persist._Builder()
    pool = mpool.class_pool(4096)
    before = pool.outstanding
    b.block(4096)
    b.block(4096)
    assert pool.outstanding == before + 2
    b.abort()
    assert b.held == []
    assert pool.outstanding == before
