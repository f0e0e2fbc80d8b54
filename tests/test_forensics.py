"""Stall-forensics plane: introspection contract, sentinel, mpidiag
blame analysis, abort-path trace export, era timeout detail, mpitop
stall column, and the two procmode proofs.

The introspection-contract test is the satellite guard: every module
registering a ``debug_state()`` provider must return JSON-serializable,
bounded output under an active workload — a new subsystem can't
silently ship broken dumps.
"""

import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from ompi_tpu import COMM_SELF, COMM_WORLD  # noqa: E402
from ompi_tpu.core.errors import MPIError, ERR_PENDING  # noqa: E402
from ompi_tpu.mca.var import all_pvars, all_vars, get_var, set_var  # noqa: E402
from ompi_tpu.runtime import forensics as fx  # noqa: E402
from ompi_tpu.runtime import trace as _trace  # noqa: E402
from ompi_tpu.runtime.progress import progress_until  # noqa: E402
from tests.test_process_mode import subprocess_env  # noqa: E402

import mpidiag  # noqa: E402
import mpitop  # noqa: E402


def run_mpi(np_, script, *args, timeout=180, mca=(), env_extra=()):
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun", "-np", str(np_)]
    for k, v in mca:
        cmd += ["--mca", k, str(v)]
    cmd += [script, *args]
    env = subprocess_env()
    env.update(dict(env_extra))
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout, env=env)


@pytest.fixture
def restore_vars():
    saved = {}

    def save(fw, name):
        saved[(fw, name)] = get_var(fw, name)

    yield save
    for (fw, name), v in saved.items():
        set_var(fw, name, v)
    fx.reset_for_testing()


# -------------------------------------------------- introspection contract
def test_every_provider_json_serializable_under_workload():
    """The contract itself: with real traffic in flight AND pathological
    queue depth, every registered provider returns JSON-serializable
    output with no tracebacks and no unbounded fields."""
    x = np.ones(256, np.float32)
    out = np.zeros(256, np.float32)
    COMM_SELF.Sendrecv(x, 0, 7, out, 0, 7)
    # pathological pending state: far more posted receives than CAP
    pend = [COMM_WORLD.Irecv(np.zeros(4), 0, 1000 + i)
            for i in range(3 * fx.CAP)]
    try:
        state = fx.debug_state()
        json.dumps(state)  # no TypeError = serializable
        assert "pml" in state and "runtime.progress" in state
        pml = state["pml"]
        assert "error" not in pml
        posted = pml["matching"]["posted"]
        assert len(posted) <= fx.CAP  # bounded
        assert pml["matching"]["posted_omitted"] >= 2 * fx.CAP
        assert pml["matching"]["n_posted"] >= 3 * fx.CAP
    finally:
        for r in pend:
            assert COMM_WORLD.pml.cancel_recv(r)
            r.Wait()


def test_broken_provider_isolated_not_fatal():
    def bad():
        raise RuntimeError("boom")

    fx.register_provider("test.broken", bad)
    try:
        state = fx.debug_state()
        assert state["test.broken"]["error"].startswith("RuntimeError")
        json.dumps(state)
    finally:
        with fx._lock:
            fx._providers.pop("test.broken", None)


def test_provider_rebind_latest_wins():
    fx.register_provider("test.rebind", lambda: {"v": 1})
    fx.register_provider("test.rebind", lambda: {"v": 2})
    try:
        assert fx.debug_state()["test.rebind"] == {"v": 2}
    finally:
        with fx._lock:
            fx._providers.pop("test.rebind", None)


def test_none_provider_skipped():
    fx.register_provider("test.none", lambda: None)
    try:
        assert "test.none" not in fx.debug_state()
    finally:
        with fx._lock:
            fx._providers.pop("test.none", None)


def test_clip_bounds():
    assert fx.clip(list(range(200))) == list(range(fx.CAP))
    assert fx.clip([]) == []
    assert fx.clip(iter(range(200))) == list(range(fx.CAP))


def test_ob1_clip_keeps_oldest_and_counts_omitted():
    """CAP clipping must keep the OLDEST entries (the blame walk keys
    on the oldest blocked recv) and say how many it dropped — dict
    insertion order silently dropped the oldest past CAP (review)."""
    pml = COMM_WORLD.pml
    now = time.monotonic()
    fakes = {}
    for i in range(fx.CAP + 8):
        st = types.SimpleNamespace(source=0, _nbytes=4)
        # inserted newest-first: insertion-order clipping would keep
        # exactly the WRONG end of the queue
        fakes[10_000_000 + i] = types.SimpleNamespace(
            tag=i, cid=0, status=st, _recv_bytes=0,
            _fx_born=now - i)  # entry i is i seconds old
    pml._active_recvs.update(fakes)
    try:
        d = pml.debug_state()
        active = d["active_recvs"]
        assert len(active) <= fx.CAP
        assert d["active_recvs_omitted"] >= 8
        got = {a["tag"] for a in active if a["msgid"] >= 10_000_000}
        # the CAP oldest fakes survive; the 8 newest are the omitted
        assert got == set(range(8, fx.CAP + 8))
        assert "flowing_sends_omitted" in d
    finally:
        for m in fakes:
            pml._active_recvs.pop(m, None)


def test_sched_and_era_providers_count_omitted():
    """Every clipped provider list carries its omitted count — the
    forensics contract the CAP doc promises (review finding: sched
    blocking/nbc and era rounds truncated silently)."""
    from ompi_tpu.coll import sched as _sched
    from ompi_tpu.ft.era import EraEngine

    now = time.monotonic()
    keys = [f"fx-test-{i}" for i in range(fx.CAP + 3)]
    with _sched._fx_lock:
        for i, k in enumerate(keys):
            _sched._live_blocking[k] = {"born": now, "tag": i}
    try:
        d = _sched._fx_debug_state()
        assert len(d["blocking"]) == fx.CAP
        assert d["blocking_omitted"] >= 3
        assert d["nbc_inflight_omitted"] == 0
    finally:
        with _sched._fx_lock:
            for k in keys:
                _sched._live_blocking.pop(k, None)

    eng = EraEngine(_DummyPml())
    for seq in range(fx.CAP + 5):
        eng._state(55, seq)
    d = eng.debug_state()
    assert len(d["rounds"]) == fx.CAP
    assert d["rounds_omitted"] == 5


# ----------------------------------------------------------- the sentinel
def test_sentinel_latches_dumps_and_rearms(tmp_path, restore_vars):
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 60.0)
    set_var("forensics", "enable", True)
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    stalled = COMM_WORLD.Irecv(np.zeros(4), 0, 4242)  # never matched
    try:
        assert progress_until(lambda: fx._sentinel.latched, timeout=8.0)
        assert fx._trips[0] == trips0 + 1
        assert int(all_pvars()["forensics_stall_latched"].value) == 1
        path = tmp_path / "stall-rank0.json"
        assert path.exists()
        doc = json.loads(path.read_text())
        assert "stall-sentinel" in doc["reason"]
        assert doc["stall"]["latched"]
        posted = doc["subsystems"]["pml"]["matching"]["posted"]
        assert any(p["tag"] == 4242 for p in posted)
    finally:
        assert COMM_WORLD.pml.cancel_recv(stalled)
        stalled.Wait()
    # the cancel completion re-arms the latch
    assert progress_until(lambda: not fx._sentinel.latched, timeout=8.0)
    assert int(all_pvars()["forensics_stall_latched"].value) == 0


def test_sentinel_idle_is_not_a_stall(restore_vars, tmp_path):
    """No pending work => no latch, however long nothing completes."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 40.0)
    set_var("forensics", "enable", True)
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline:
        progress_until(lambda: False, timeout=0.05)
    assert fx._trips[0] == trips0
    assert not fx._sentinel.latched


def test_fresh_work_after_idle_is_not_an_instant_stall(tmp_path,
                                                       restore_vars):
    """The idle clock must stay fresh WHILE idle: after a long quiet
    stretch, newly-posted work gets the full threshold before a latch
    — a threshold-stale clock latched ~immediately on the first
    operation after idling (4th review pass)."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 400.0)
    set_var("forensics", "enable", True)
    fx.reset_for_testing()
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    # idle well past the threshold, with the sentinel polling
    deadline = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        progress_until(lambda: False, timeout=0.05)
    stalled = COMM_WORLD.Irecv(np.zeros(4), 0, 4243)
    try:
        # a quarter-threshold later: must NOT have latched yet
        deadline = time.monotonic() + 0.1
        while time.monotonic() < deadline:
            progress_until(lambda: False, timeout=0.02)
        assert not fx._sentinel.latched
        assert fx._trips[0] == trips0
        # ... but the genuine stall still latches after the threshold
        assert progress_until(lambda: fx._sentinel.latched, timeout=8.0)
    finally:
        assert COMM_WORLD.pml.cancel_recv(stalled)
        stalled.Wait()


def test_reenable_after_disabled_stretch_is_not_an_instant_stall(
        tmp_path, restore_vars):
    """forensics_enable 1 -> 0 -> 1 through a cvar write on a live job:
    while disabled the completion tick is unbound, so the idle clock
    goes stale by the whole window — the rebind hook must reset it or
    the first poll that finds any pending work latches a healthy job
    instantly (5th review pass)."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 400.0)
    set_var("forensics", "enable", True)
    fx.reset_for_testing()
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    set_var("forensics", "enable", False)
    time.sleep(1.0)  # disabled stretch well past the threshold
    set_var("forensics", "enable", True)
    stalled = COMM_WORLD.Irecv(np.zeros(4), 0, 4244)
    try:
        # a quarter-threshold later: must NOT have latched yet
        deadline = time.monotonic() + 0.1
        while time.monotonic() < deadline:
            progress_until(lambda: False, timeout=0.02)
        assert not fx._sentinel.latched
        assert fx._trips[0] == trips0
        # ... but the genuine stall still latches after the threshold
        assert progress_until(lambda: fx._sentinel.latched, timeout=8.0)
    finally:
        assert COMM_WORLD.pml.cancel_recv(stalled)
        stalled.Wait()


def test_undriven_poll_gap_is_idle_not_stall(tmp_path, restore_vars):
    """With no progress driver (runtime_progress_thread 0) nothing
    polls while the app computes outside MPI: the clock goes
    threshold-stale UNOBSERVED, and the first poll after fresh work is
    posted must treat the gap as idle time, not latch instantly — the
    sentinel can only measure time it was watching (review)."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 400.0)
    set_var("forensics", "enable", True)
    fx.reset_for_testing()
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    s = fx._sentinel
    s.poll()  # one watched poll, then an undriven stretch
    # simulate a 10s unobserved compute gap exactly as elapsed time
    # would leave the clocks: nothing polled, nothing completed
    with s._slock:
        s._last_change -= 10.0
        s._last_poll -= 10.0
        s._next_probe = 0.0
    stalled = COMM_WORLD.Irecv(np.zeros(4), 0, 4245)
    try:
        s.poll()  # first poll after the gap: idle, not a latch
        assert not s.latched
        assert fx._trips[0] == trips0
        # ...but a genuine stall still latches once it is WATCHED
        # past the threshold
        assert progress_until(lambda: s.latched, timeout=8.0)
    finally:
        assert COMM_WORLD.pml.cancel_recv(stalled)
        stalled.Wait()


def test_runtime_cvar_flip_arms_the_whole_plane(restore_vars,
                                                monkeypatch):
    """Flipping forensics_enable through a cvar write on a live job
    must arm the sentinel + SIGUSR1, not just the completion tick."""
    restore_vars("forensics", "enable")
    set_var("forensics", "enable", False)
    armed = []
    monkeypatch.setattr(fx, "arm_sentinel", lambda: armed.append("s"))
    monkeypatch.setattr(fx, "install_sigusr1",
                        lambda: armed.append("sig"))
    set_var("forensics", "enable", True)
    assert armed == ["s", "sig"]
    from ompi_tpu.core import request as _request

    assert _request._fx_note is fx.note_completion
    set_var("forensics", "enable", False)
    assert _request._fx_note is None


def test_completion_during_pending_probe_blocks_the_latch(
        restore_vars, tmp_path, monkeypatch):
    """A request that completes while poll() is inside the pending
    probes (which take contended subsystem locks — a wide window) must
    veto the latch: the entry snapshot is stale there and _last_comp
    only advances in the fold, so the guard must re-read the live
    counter (5th review pass)."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("forensics", "stall_threshold_ms")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "stall_threshold_ms", 40.0)
    set_var("forensics", "enable", True)
    fx.reset_for_testing()
    fx.arm_sentinel()
    trips0 = fx._trips[0]
    with fx._sentinel._slock:
        fx._sentinel._last_comp = fx._completions[0]
        fx._sentinel._last_change = time.monotonic() - 99.0
        fx._sentinel._next_probe = 0.0
        fx._sentinel.latched = False

    def pending_and_tick():
        fx._completions[0] += 1  # a request completes mid-probe
        return True

    monkeypatch.setattr(fx, "_work_pending", pending_and_tick)
    assert fx._sentinel.poll() == 0
    assert not fx._sentinel.latched
    assert fx._trips[0] == trips0
    # the next poll folds the tick: clock fresh, still no latch
    monkeypatch.setattr(fx, "_work_pending", lambda: True)
    assert fx._sentinel.poll() == 0
    assert not fx._sentinel.latched


def test_runtime_disable_clears_the_latch(restore_vars, tmp_path):
    """Silencing the plane (enable 1 -> 0) on a latched sentinel must
    clear the verdict: the completion tick is unbound, so nothing else
    ever could — the stall pvar and mpitop cell would otherwise report
    a latched stall with an unboundedly climbing age on a healthy job
    for the rest of the run (5th review pass)."""
    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "enable", True)
    fx.reset_for_testing()
    fx.arm_sentinel()
    with fx._sentinel._slock:
        fx._sentinel.latched = True
        fx._sentinel._last_comp = fx._completions[0]
        fx._sentinel._last_change = time.monotonic() - 99.0
    set_var("forensics", "enable", False)
    assert not fx._sentinel.latched
    assert int(all_pvars()["forensics_stall_latched"].value) == 0
    assert fx._sentinel.age() == 0.0
    # re-enable re-arms with a fresh clock
    set_var("forensics", "enable", True)
    assert fx._sentinel.armed
    assert not fx._sentinel.latched
    assert fx._sentinel.age() < 1.0


def test_legacy_wire_paths_stamp_rx_tx_evidence(restore_vars):
    """The wire paths stamp last_rx/last_tx while the forensics plane is
    armed — a dump on a moving link otherwise shows null wire-liveness,
    indistinguishable from a silent one (5th review pass)."""
    from ompi_tpu.btl.tcp import TcpBtl
    from ompi_tpu.pml.base import pack_header

    restore_vars("forensics", "enable")
    set_var("forensics", "enable", True)
    got = []
    a = TcpBtl(lambda h, p: got.append(bytes(p)), my_rank=0)
    b = TcpBtl(lambda h, p: got.append(bytes(p)), my_rank=1)
    try:
        a.set_peers({1: f"127.0.0.1:{b.port}"})
        b.set_peers({0: f"127.0.0.1:{a.port}"})
        hdr = pack_header(1, 0, 0, 5, 1, 5, 0, 0)
        a.send(1, hdr, np.frombuffer(b"hello", np.uint8))
        deadline = time.monotonic() + 10
        while not got and time.monotonic() < deadline:
            a.progress()
            b.progress()
        assert got == [b"hello"]
        assert any(c["last_tx_age_s"] is not None
                   for c in a.debug_state()["conns"])
        assert any(c["last_rx_age_s"] is not None
                   for c in b.debug_state()["conns"])
        # torn rx span (parser mid-compaction on the progress thread):
        # the dump must clamp, never record negative evidence
        conn = next(iter(b.conns.values()))
        r0, r1 = conn.rstart, conn.rend
        conn.rstart, conn.rend = 5000, 100
        try:
            assert all(c["rx_partial_bytes"] >= 0
                       for c in b.debug_state()["conns"])
        finally:
            conn.rstart, conn.rend = r0, r1
    finally:
        a.finalize()
        b.finalize()


def test_watchdog_dump_captures_pre_conversion_evidence(
        tmp_path, restore_vars):
    """The watchdog trigger must dump BEFORE _fail_requests pops the
    stale entries — afterwards the protocol state it exists to capture
    is gone (4th review pass)."""
    import threading as _threading

    from ompi_tpu.ft import detector as _det
    from ompi_tpu.pml.base import SendRequest
    from ompi_tpu.pml.ob1 import Ob1Pml

    restore_vars("metrics", "dir")
    restore_vars("forensics", "enable")
    restore_vars("pml", "peer_timeout")
    set_var("metrics", "dir", str(tmp_path))
    set_var("forensics", "enable", True)
    set_var("pml", "peer_timeout", 0.5)
    fx.reset_for_testing()  # clear the trigger rate limiter
    world_pml = COMM_WORLD.pml
    pml = Ob1Pml(my_rank=0)
    req = SendRequest(dst=3, tag=9, cid=0, nbytes=4096)
    req._pump_lock = _threading.RLock()
    req._wd_last = time.monotonic() - 10.0
    pml._pending_sends[77] = req
    pml._wd_next = 0.0
    try:
        assert pml._watchdog_poll() == 1
        assert req.is_complete  # the conversion still happened
        doc = json.loads((tmp_path / "stall-rank0.json").read_text())
        assert "pml-watchdog" in doc["reason"]
        pend = doc["subsystems"]["pml"]["pending_sends"]
        assert any(e["msgid"] == 77 and e["dst"] == 3
                   and e["stage"] == "rts-unanswered" for e in pend), \
            f"pre-conversion evidence missing: {pend}"
    finally:
        with _det._failed_lock:  # undo the watchdog's mark_failed(3)
            _det._failed.discard(3)
        # rebind the world pml's provider (the test pml took the slot)
        fx.register_provider(
            "pml", lambda: world_pml.debug_state())
        fx.register_pending_probe(
            "pml", lambda: (world_pml.engine.n_posted
                            + len(world_pml._pending_sends)
                            + len(world_pml._active_recvs)
                            + len(world_pml._flowing)))


def test_dump_state_verb_works_with_plane_disabled(tmp_path,
                                                   restore_vars):
    restore_vars("metrics", "dir")
    set_var("metrics", "dir", str(tmp_path))
    assert not fx.enabled()
    path = COMM_SELF.Dump_state(reason="unit")
    assert path == str(tmp_path / "stall-rank0.json")
    doc = json.loads(open(path).read())
    assert doc["reason"] == "unit"
    assert "pml" in doc["subsystems"]


def test_dump_rate_limit(tmp_path, restore_vars):
    restore_vars("metrics", "dir")
    set_var("metrics", "dir", str(tmp_path))
    assert fx.dump(reason="first") is not None
    assert fx.dump(reason="second", min_interval=30.0) is None
    assert fx.dump(reason="third") is not None  # unlimited path


def test_failed_dump_does_not_suppress_rate_limited_retry(
        tmp_path, restore_vars, monkeypatch):
    """A dump whose write fails (disk-full blip) must not advance the
    rate-limit stamp: the retry within min_interval is exactly the one
    that would have succeeded (5th review pass)."""
    from ompi_tpu.utils import fsio

    restore_vars("metrics", "dir")
    set_var("metrics", "dir", str(tmp_path))
    fx._last_dump_ts[0] = 0.0
    real = fsio.atomic_write_json
    fail = [True]

    def flaky(path, doc, **kw):
        if fail[0]:
            raise OSError("disk full")
        return real(path, doc, **kw)

    monkeypatch.setattr(fsio, "atomic_write_json", flaky)
    assert fx.dump(reason="failed", min_interval=30.0) is None
    fail[0] = False
    # the failed attempt must not have stamped: this retry lands
    assert fx.dump(reason="retry", min_interval=30.0) is not None
    doc = json.loads((tmp_path / "stall-rank0.json").read_text())
    assert doc["reason"] == "retry"
    # ... and the SUCCESS did stamp: an immediate third is suppressed
    assert fx.dump(reason="third", min_interval=30.0) is None


def test_trigger_requests_peers_even_when_local_dump_fails(
        tmp_path, restore_vars, monkeypatch):
    """The local-only fallback runs BOTH ways: a rank whose own disk is
    unwritable must still harvest every peer's evidence."""
    restore_vars("metrics", "dir")
    set_var("metrics", "dir", str(tmp_path))
    fx.reset_for_testing()
    asked = []
    monkeypatch.setattr(fx, "_request_all_peer_dumps",
                        lambda reason: asked.append(reason))
    monkeypatch.setattr(fx, "dump", lambda **kw: None)  # write fails
    assert fx.trigger("era-timeout: unit") is None
    assert asked == ["era-timeout: unit"]  # peers asked anyway
    # rate limit: an immediate re-trigger skips BOTH (peers were just
    # asked), instead of flooding per watchdog sweep
    assert fx.trigger("era-timeout: unit again") is None
    assert len(asked) == 1


def test_system_plane_completions_do_not_tick():
    """Heartbeats (every 200ms under ft_enable), era chatter, and the
    plane's own dump requests are system-plane sends — if their
    completions counted, an FT job's sentinel could never see a quiet
    period and the era-stall soak class would never latch (found by
    driving a real 2-rank era stall under ft_enable)."""

    class _Req:
        def __init__(self, tag):
            self.tag = tag

    base = fx._completions[0]
    fx.note_completion(_Req(-4243))   # heartbeat
    fx.note_completion(_Req(-4244))   # era
    fx.note_completion(_Req(fx.FORENSICS_TAG))  # our own dump request
    assert fx._completions[0] == base
    fx.note_completion(_Req(7))       # user traffic ticks
    fx.note_completion(None)          # tagless (coll/nbc) ticks
    assert fx._completions[0] == base + 2


def test_atomic_write_json_cleans_up_failed_tmp(tmp_path):
    from ompi_tpu.utils.fsio import atomic_write_json

    p = tmp_path / "out.json"
    assert atomic_write_json(str(p), {"a": 1}) == str(p)
    assert json.loads(p.read_text()) == {"a": 1}

    class Unserializable:
        pass

    with pytest.raises(TypeError):
        atomic_write_json(str(p), {"a": Unserializable()})
    # the failed write neither corrupted the file nor stranded a tmp
    assert json.loads(p.read_text()) == {"a": 1}
    assert os.listdir(tmp_path) == ["out.json"]


# ------------------------------------------------------------ mpidiag
def _dump(rank, pml=None, tcp=None, latched=False, reason="x"):
    return {"schema": 1, "rank": rank, "seq": 1, "reason": reason,
            "ts_ns": 0, "wall_time": 0.0,
            "stall": {"latched": latched,
                      "since_last_completion_s": 1.0},
            "subsystems": {"pml": pml or {}, "btl.tcp": tcp or {}}}


def test_mpidiag_blames_dropped_frame_edge():
    dumps = {
        1: _dump(1, latched=True, reason="stall-sentinel", pml={
            "matching": {"posted": [
                {"cid": 0, "src": 0, "tag": 7, "n": 1,
                 "oldest_pseq": 0, "oldest_age_s": 3.2}]},
            "expect_seq": {},
        }),
        0: _dump(0, pml={"matching": {"posted": []},
                         "seq_to": {"1:0": 4}}),
    }
    report = mpidiag.analyze(dumps)
    assert len(report["blames"]) == 1
    b = report["blames"][0]
    assert "rank 1 blocked on MATCH tag 7 cid 0 from rank 0" in b
    assert "stamped seq 4 on the normal plane" in b
    assert "expects 1" in b
    assert not report["cycles"]


def test_mpidiag_blames_rts_and_queue_position():
    dumps = {
        2: _dump(2, latched=True, pml={
            "matching": {"posted": [
                {"cid": 1, "src": 0, "tag": 9, "n": 1,
                 "oldest_pseq": 0, "oldest_age_s": 8.0}]},
        }),
        0: _dump(0, pml={
            "matching": {"posted": []},
            "pending_sends": [{"msgid": 3, "dst": 2, "tag": 9,
                               "cid": 1, "nbytes": 1 << 20,
                               "stage": "rts-unanswered",
                               "age_s": 8.0}],
        }, tcp={"conns": [
            {"peer": 2, "state": "established",
             "shaped_queues": {"bulk": {"frames": 3,
                                        "bytes": 48_000_000,
                                        "oldest_age_s": 8.0}}}]}),
    }
    report = mpidiag.analyze(dumps)
    b = report["blames"][0]
    assert "rank 2 blocked on MATCH tag 9 cid 1 from rank 0" in b
    assert "RTS" in b and "unanswered" in b
    assert "BULK queue" in b and "48.0MB" in b


def test_mpidiag_one_directional_wire_detail_renders_cleanly():
    """tx stamped but rx never (the seeded drop edge before any
    reverse traffic) must not render 'last rx never ago' (5th review
    pass)."""
    dumps = {
        1: _dump(1, latched=True, pml={
            "matching": {"posted": [
                {"cid": 0, "src": 0, "tag": 7, "n": 1,
                 "oldest_pseq": 0, "oldest_age_s": 3.2}]},
        }),
        0: _dump(0, pml={"matching": {"posted": []}},
                 tcp={"conns": [{"peer": 1, "state": "established",
                                 "last_tx_age_s": 0.4,
                                 "last_rx_age_s": None}]}),
    }
    b = mpidiag.analyze(dumps)["blames"][0]
    assert "last tx 0.4s ago, last rx never" in b
    assert "never ago" not in b


def test_mpidiag_detects_cycle():
    def side(rank, peer, latched=True):
        return _dump(rank, latched=latched, pml={
            "matching": {"posted": [
                {"cid": 0, "src": peer, "tag": 5, "n": 1,
                 "oldest_pseq": 0, "oldest_age_s": 2.0}]},
        })

    report = mpidiag.analyze({0: side(0, 1), 1: side(1, 0)})
    assert report["cycles"] == ["0 -> 1 -> 0"]
    assert "BLAME-CYCLE" in mpidiag.render(report)
    # healthy on-demand snapshots of a routine ring exchange show the
    # same edge shape (dumps are never simultaneous) — with no rank
    # stalled that must NOT read as a deadlock (4th review pass)
    healthy = mpidiag.analyze({0: side(0, 1, latched=False),
                               1: side(1, 0, latched=False)})
    assert not healthy["cycles"] and not healthy["blames"]
    assert "no stalled rank" in mpidiag.render(healthy)


def test_mpidiag_blames_auto_trigger_reasons():
    """Auto-trigger dumps (era timeout, watchdog, sanitizer deadlock)
    carry no sentinel latch — their reasons alone must select them for
    blame, or the era show_help's 'run mpidiag' advice prints a
    healthy verdict for 6 of the 8 motivating soak failures."""
    for reason in ("era-timeout: round 3 cid 0 waiting on coordinator",
                   "pml-watchdog: peer(s) [0] silent > 2.0s",
                   "sanitizer-deadlock: cycle 0 -> 1 -> 0"):
        dumps = {
            1: _dump(1, reason=reason, pml={
                "matching": {"posted": [
                    {"cid": 0, "src": 0, "tag": 7, "n": 1,
                     "oldest_pseq": 0, "oldest_age_s": 3.0}]}}),
            0: _dump(0, reason=f"peer-request: {reason} on rank 1",
                     pml={"matching": {"posted": []},
                          "seq_to": {"1:0": 2}}),
        }
        report = mpidiag.analyze(dumps)
        assert report["blames"], f"no blame for reason {reason!r}"
        assert "rank 1 blocked on MATCH tag 7" in report["blames"][0]


def test_mpidiag_era_vote_edges_skip_known_failed_voters():
    """era's phase-1 predicate is contribution-OR-DEATH: a known-failed
    voter is satisfied, not blocking. The coordinator's ERA-VOTE edges
    must skip dead members or the tie-break blames a dead rank with 'no
    dump' while the live stalled voter goes unreported (review)."""
    dump = _dump(1, latched=True, reason="stall-sentinel")
    dump["subsystems"]["ft.era"] = {"rounds": [{
        "cid": 0, "round": 3, "members": [0, 1, 2],
        "contribs": [1], "votes_outstanding": [0, 2],
        "decision": False, "in_progress": True, "age_s": 4.0}]}
    dump["subsystems"]["ft.detector"] = {"known_failed": [0]}
    edges = mpidiag.blocked_edges(1, dump)
    era = [e for e in edges if e.kind == "ERA-VOTE"]
    assert [e.peer for e in era] == [2]  # dead rank 0 skipped
    # and the blame walk follows the live voter's edge
    report = mpidiag.analyze({1: dump})
    assert "waiting on rank 2's vote" in report["blames"][0]


def test_mpidiag_mixed_latched_and_trigger_both_blamed():
    """A mixed stall — one rank sentinel-latched, another dumped by an
    auto trigger — must blame BOTH; the trigger scan used to run only
    when no rank latched (review finding), dropping the era rank's
    edge from exactly the mixed verdict the soak produces."""
    def blocked(rank, peer, **kw):
        return _dump(rank, pml={
            "matching": {"posted": [
                {"cid": 0, "src": peer, "tag": 7, "n": 1,
                 "oldest_pseq": 0, "oldest_age_s": 3.0}]}}, **kw)

    dumps = {
        0: blocked(0, 2, latched=True, reason="stall-sentinel"),
        2: blocked(2, 1, reason="era-timeout: round 3 cid 0"),
        1: _dump(1, reason="peer-request: stall-sentinel on rank 0",
                 pml={"matching": {"posted": []}}),
    }
    report = mpidiag.analyze(dumps)
    blamed = " ".join(report["blames"])
    assert "rank 0 blocked on MATCH tag 7 cid 0 from rank 2" in blamed
    assert "rank 2 blocked on MATCH tag 7 cid 0 from rank 1" in blamed
    # the healthy peer-request rank is still never blamed
    assert "rank 1 blocked" not in blamed


def test_mpidiag_offsets_shift_ages_onto_one_timeline():
    """--offsets must actually ALIGN ages (review finding: they were
    echoed into summaries and never applied): with rank 0's dump taken
    2s after rank 1's, rank 1's ages grow by the skew so both sides
    compare as of one instant; without offsets nothing moves."""
    def dumps():
        d = {
            1: _dump(1, latched=True, pml={
                "matching": {"posted": [
                    {"cid": 0, "src": 0, "tag": 7, "n": 1,
                     "oldest_pseq": 0, "oldest_age_s": 3.0}]}}),
            0: _dump(0, pml={"matching": {"posted": []},
                             "seq_to": {"1:0": 4}}),
        }
        d[1]["ts_ns"] = 0
        d[0]["ts_ns"] = int(2e9)  # dumped 2s later on the same clock
        return d

    plain = mpidiag.analyze(dumps())
    assert "(3.0s)" in plain["blames"][0]
    assert plain["ranks"][1]["dump_skew_s"] == 0.0

    aligned = mpidiag.analyze(dumps(), offsets={0: 0.0, 1: 0.0})
    assert "(5.0s)" in aligned["blames"][0]  # 3.0 + 2s dump skew
    assert aligned["ranks"][1]["dump_skew_s"] == 2.0
    assert aligned["ranks"][0]["dump_skew_s"] == 0.0
    assert aligned["ranks"][1]["since_last_completion_s"] == 3.0

    # a real clock offset folds in per the trace_merge convention
    # (ts0 = ts_r - offset_r): rank 0's clock reads 2s AHEAD, so the
    # dumps were actually simultaneous and nothing shifts
    sync = mpidiag.analyze(dumps(), offsets={0: 2.0, 1: 0.0})
    assert "(3.0s)" in sync["blames"][0]
    assert sync["ranks"][1]["dump_skew_s"] == 0.0


def test_era_agreement_counts_as_pending_work():
    """An in-progress agreement posts no pml requests — the era pending
    probe is what keeps the sentinel from classifying an era stall as
    idle. The probe counts entered-but-not-exited rounds only."""
    from ompi_tpu.ft.era import EraEngine, _AgreeState

    eng = EraEngine(_DummyPml())
    probe = fx._pending_probes["ft.era"]
    base = probe()
    st = eng._state(55, 0)
    with st.lock:
        st.members = [0, 1]
    assert probe() == base + 1  # entered, not exited
    st.done = True
    assert probe() == base     # exited (return OR raise)
    # handler-created states (members unknown) never count
    eng._state(55, 1)
    assert probe() == base


def _era_round(cid, rnd, members, contribs, outstanding,
               in_progress=True, decision=False):
    return {"cid": cid, "round": rnd, "members": members,
            "contribs": contribs, "votes_outstanding": outstanding,
            "in_progress": in_progress, "decision": decision,
            "age_s": 5.0}


def test_mpidiag_blames_era_stall_without_pml_edges():
    """The era-stall class (6 of 8 soak failures): agreement waits ride
    system handlers and post NO pml requests — the blame walk must
    follow the ft.era rounds, not declare the job healthy."""
    dumps = {
        0: _dump(0, latched=True, reason="stall-sentinel"),
        1: _dump(1, latched=True, reason="stall-sentinel"),
    }
    # rank 0 coordinates round 2 on cid 3, missing rank 1's vote;
    # rank 1 never entered the round (stuck above the agreement)
    dumps[0]["subsystems"]["ft.era"] = {"rounds": [
        _era_round(3, 2, [0, 1], [0], [1])]}
    dumps[1]["subsystems"]["ft.era"] = {"rounds": []}
    report = mpidiag.analyze(dumps)
    b = [x for x in report["blames"] if "rank 0 blocked" in x]
    assert b, report["blames"]
    assert "era agreement round 2 on cid 3" in b[0]
    assert "waiting on rank 1's vote" in b[0]
    assert "never entered agreement round 2" in b[0]
    assert "no stalled rank" not in mpidiag.render(report)


def test_mpidiag_handler_created_round_reads_as_never_entered():
    """Round state whose members is null was created by the background
    era handler from a peer's eager contribution — the rank never
    called agree(); blaming it as 'entered and exited' would send
    triage down the wrong path (5th review pass)."""
    dumps = {
        0: _dump(0, latched=True, reason="stall-sentinel"),
        2: _dump(2),
    }
    dumps[0]["subsystems"]["ft.era"] = {"rounds": [
        _era_round(3, 2, [0, 2], [0], [2])]}
    dumps[2]["subsystems"]["ft.era"] = {"rounds": [
        _era_round(3, 2, None, [3], None, in_progress=False)]}
    b = [x for x in mpidiag.analyze(dumps)["blames"]
         if "rank 0 blocked" in x][0]
    assert "never entered agreement round 2" in b
    assert "entered and exited" not in b


def test_mpidiag_era_member_blames_lost_decide():
    dumps = {
        1: _dump(1, latched=True, reason="stall-sentinel"),
        0: _dump(0),
    }
    # rank 1 is a member of round 4 waiting for rank 0's broadcast;
    # rank 0 already decided — the DECIDE frame was lost
    dumps[1]["subsystems"]["ft.era"] = {"rounds": [
        _era_round(3, 4, [0, 1], [1], [0])]}
    dumps[0]["subsystems"]["ft.era"] = {"rounds": [
        _era_round(3, 4, [0, 1], [0, 1], [], in_progress=False,
                   decision=True)]}
    b = mpidiag.analyze(dumps)["blames"][0]
    assert "waiting on rank 0's decision broadcast" in b
    assert "DECIDE frame" in b and "lost" in b


def test_mpidiag_peer_request_dumps_not_blamed():
    """Healthy peers' dumps inherit the requester's reason text; their
    routine in-flight receives must not be blamed when the stalled
    rank's own dump is missing."""
    dumps = {2: _dump(2, reason="peer-request: stall-sentinel on rank 1",
                      pml={"matching": {"posted": [
                          {"cid": 0, "src": 0, "tag": 7, "n": 1,
                           "oldest_pseq": 0, "oldest_age_s": 0.1}]}})}
    report = mpidiag.analyze(dumps)
    assert not report["blames"], report["blames"]


def test_mpidiag_latched_rank_without_edges_still_reported():
    report = mpidiag.analyze(
        {0: _dump(0, latched=True, reason="stall-sentinel")})
    assert report["blames"], "latched rank vanished from the verdict"
    assert "no pml/era waiting-on edge" in report["blames"][0]
    assert "no stalled rank" not in mpidiag.render(report)


def test_mpidiag_healthy_dumps_blame_nothing():
    report = mpidiag.analyze({0: _dump(0), 1: _dump(1)})
    assert not report["blames"] and not report["cycles"]
    assert "no stalled rank" in mpidiag.render(report)


def test_mpidiag_missing_peer_dump_local_fallback():
    dumps = {1: _dump(1, latched=True, pml={
        "matching": {"posted": [
            {"cid": 0, "src": 0, "tag": 7, "n": 1,
             "oldest_pseq": 0, "oldest_age_s": 3.0}]}})}
    b = mpidiag.analyze(dumps)["blames"][0]
    assert "no dump from rank 0" in b and "rank-local evidence" in b


def test_mpidiag_reads_dir_and_cli(tmp_path):
    for r in (0, 1):
        (tmp_path / f"stall-rank{r}.json").write_text(
            json.dumps(_dump(r)))
    dumps = mpidiag.read_dumps(str(tmp_path))
    assert sorted(dumps) == [0, 1]
    assert mpidiag.main(["--dir", str(tmp_path)]) == 0
    assert mpidiag.main(["--dir", str(tmp_path / "nope")]) == 1


# ------------------------------------------------------- mpitop column
def test_mpitop_stall_cell_sampler_and_pvar_fallback():
    snap = {"samplers": {"forensics_stall":
                         {"latched": 1, "age_s": 12.4}}}
    assert mpitop.stall_cell(snap) == "*12s"
    snap = {"samplers": {"forensics_stall":
                         {"latched": 0, "age_s": 3.0}}}
    assert mpitop.stall_cell(snap) == "3s"
    # pvar fallback (snapshot written before the sampler existed)
    snap = {"pvars": {"forensics_stall_latched": 1,
                      "forensics_last_completion_age_s": 7.0}}
    assert mpitop.stall_cell(snap) == "*7s"
    assert mpitop.stall_cell({"pvars": {}}) == ""


def test_stall_sampler_in_metrics_snapshot():
    from ompi_tpu.runtime import metrics as _metrics

    snap = _metrics.snapshot()
    row = snap["samplers"]["forensics_stall"]
    assert set(row) == {"latched", "age_s", "trips", "dumps"}


# ------------------------------------------------- abort/fatal exports
def test_trace_export_on_fatal_and_reentrancy(tmp_path, restore_vars):
    restore_vars("trace", "dir")
    restore_vars("trace", "enable")
    set_var("trace", "dir", str(tmp_path))
    set_var("trace", "enable", True)
    with _trace.span("unit.fatal", cat="test"):
        pass
    _trace.export_on_fatal()
    path = tmp_path / "trace-rank0.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    assert any(e.get("name") == "unit.fatal"
               for e in doc["traceEvents"])
    # does NOT consume the finalize export
    assert not _trace._exported
    # re-entrancy guard: a nested call while exporting is a no-op, and
    # the flag always resets
    assert not _trace._fatal_exporting[0]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_progress_thread_fatal_exports_ring(tmp_path, restore_vars):
    from ompi_tpu.runtime.progress import (ProgressThread,
                                           register_progress,
                                           unregister_progress)

    restore_vars("trace", "dir")
    restore_vars("trace", "enable")
    set_var("trace", "dir", str(tmp_path))
    set_var("trace", "enable", True)
    with _trace.span("unit.progress-fatal", cat="test"):
        pass

    def die():
        if threading.current_thread().name == "ompi-tpu-progress":
            raise SystemExit("seeded progress-thread death")
        return 0

    register_progress(die)
    t = ProgressThread(interval=0.001)
    try:
        t.start()
        deadline = time.monotonic() + 8.0
        while t._thread is not None and t._thread.is_alive() and \
                time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        unregister_progress(die)
        t.stop()
    path = tmp_path / "trace-rank0.json"
    assert path.exists(), "dying progress thread did not export rings"
    assert any(e.get("name") == "unit.progress-fatal"
               for e in json.loads(path.read_text())["traceEvents"])


# ------------------------------------------------------ era timeout detail
class _DummyPml:
    my_rank = 0

    def register_system_handler(self, tag, fn):
        pass

    def isend(self, *a, **kw):
        raise OSError("no wire in this unit test")


def test_era_timeout_names_round_bitmask_and_outstanding(restore_vars):
    from ompi_tpu.ft.era import EraEngine

    restore_vars("ft", "era_timeout")
    set_var("ft", "era_timeout", 0.2)

    class _Comm:
        cid = 77
        revoked = False

        class group:
            ranks = [0, 1]

    eng = EraEngine(_DummyPml())
    with pytest.raises(MPIError) as ei:
        eng.agree(_Comm(), 1)
    assert ei.value.code == ERR_PENDING
    msg = str(ei.value)
    assert "agreement round 0 on cid 77" in msg
    assert "participant bitmask 0x1" in msg  # only rank 0 contributed
    assert "votes outstanding [1]" in msg
    assert "members [0, 1]" in msg


def test_participant_bitmask_positional():
    from ompi_tpu.ft.era import _participant_bitmask

    assert _participant_bitmask([4, 9, 200], [4, 200]) == 0b101
    assert _participant_bitmask(None, [2, 5]) == (1 << 2) | (1 << 5)
    assert _participant_bitmask([1, 2], []) == 0


def test_era_timeout_topic_registered():
    from ompi_tpu.utils.show_help import _messages

    assert ("ft", "era-timeout") in _messages
    assert ("forensics", "stall") in _messages


# -------------------------------------------------------- registration
def test_cvars_pvars_registered():
    vs = all_vars()
    assert "forensics_enable" in vs
    assert "forensics_stall_threshold_ms" in vs
    pv = all_pvars()
    for name in ("forensics_stall_trips", "forensics_dumps",
                 "forensics_stall_latched",
                 "forensics_last_completion_age_s"):
        assert name in pv, name
        pv[name].value  # readable


def test_qos_tag_map_promotes_forensics_tag():
    from ompi_tpu import qos

    qos.reset_for_testing()
    try:
        assert qos._tag_class(fx.FORENSICS_TAG) == qos.LATENCY
    finally:
        qos.reset_for_testing()


def test_forensics_tag_in_mpiracer_registry():
    """The -4800 plane must appear in mpiracer's --json tag registry,
    handled and sent (the satellite's machine-checkable half)."""
    from ompi_tpu.analysis import pkgmodel, protocol

    pkg = pkgmodel.load_package([os.path.join(REPO, "ompi_tpu")])
    reg = protocol.registry_json(pkg)
    ent = [t for t in reg["tags"] if t["value"] == fx.FORENSICS_TAG]
    assert ent, "FORENSICS_TAG missing from the protocol registry"
    assert ent[0]["name"] == "FORENSICS_TAG"
    assert ent[0]["handled"] and ent[0]["sent"]


def test_info_cli_loads_forensics(capsys):
    from ompi_tpu.tools import info

    info.main(["--level", "9", "--param", "forensics"])
    out = capsys.readouterr().out
    assert "forensics_enable" in out
    assert "forensics_stall_threshold_ms" in out


# ---------------------------------------------------------- procmode
def test_procmode_seeded_stall_names_blocking_edge(tmp_path):
    """The acceptance gate: a drop-all stall on the 0 -> 1 edge produces
    per-rank dumps and a merged mpidiag blame naming the true blocking
    edge — 5/5 episodes deterministic."""
    r = run_mpi(3, "tests/procmode/check_forensics.py", "stall", "5",
                timeout=240,
                mca=(("btl_btl", "^sm"),
                     ("forensics_enable", "1"),
                     ("forensics_stall_threshold_ms", "400"),
                     ("ft_inject_plan", "drop(0,1,side=recv)")),
                env_extra=(("OMPI_TPU_MCA_metrics_dir",
                            str(tmp_path)),))
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    oks = [ln for ln in r.stdout.splitlines()
           if "FORENSICS-EP" in ln and "-OK" in ln]
    assert len(oks) == 5, r.stdout
    assert all("rank 1 blocked on MATCH" in ln for ln in oks), oks
    assert "FORENSICS-STALL-OK episodes=5" in r.stdout
    # the dumps stay on disk for post-mortem tooling
    diag = mpidiag.read_dumps(str(tmp_path))
    assert sorted(diag) == [0, 1, 2]


def test_procmode_ondemand_dump_clean(tmp_path):
    r = run_mpi(3, "tests/procmode/check_forensics.py", "ondemand",
                timeout=240,
                env_extra=(("OMPI_TPU_MCA_metrics_dir",
                            str(tmp_path)),))
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    assert r.stdout.count("FORENSICS-ONDEMAND-OK") == 3


def test_procmode_abort_exports_trace_ring(tmp_path):
    r = run_mpi(2, "tests/procmode/check_crash.py", timeout=240,
                mca=(("trace_enable", "1"),),
                env_extra=(("OMPI_TPU_MCA_trace_dir", str(tmp_path)),))
    assert r.returncode != 0  # the job aborted, as seeded
    path = tmp_path / "trace-rank1.json"
    assert path.exists(), f"abort lost the ring\n{r.stdout}\n{r.stderr}"
    doc = json.loads(path.read_text())
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "pml.send" in names  # real spans, not an empty shell
