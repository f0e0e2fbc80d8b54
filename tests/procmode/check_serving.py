"""Elastic serving under world-size churn, selected by argv[1].

``churn`` (default, 3 ranks, ft + diskless buddies armed, forensics
armed by the caller) — the composed proof ROADMAP item 4 asks for:
sustained open-loop traffic (one state step per arrival: a 4KB
allreduce verified bitwise against the seeded closed form, then a
diskless epoch commit) across THREE fault episodes in one run:

1. ``kill_respawn``  — comm rank 1 dies cold mid-stream; respawn
   recovery restores capacity, survivors roll back to the committed
   epoch, the replacement rejoins with the buddy replica and serves
   the rest of the run.
2. ``preempt_flush`` — the REPLACEMENT from episode 1 gets a
   preemption notice, flushes a final blob in the grace window, dies;
   respawn recovery skips the rollback (survivors keep live state,
   one-step skew forward-reconciled from the oracle).
3. ``kill_shrink``   — comm rank 2 dies cold; recovery DEGRADES:
   shrink to 2 ranks and live-reshard the committed epoch onto the
   shrunk world (each survivor serves its own blob + the replica it
   holds for the dead rank). Traffic finishes at reduced capacity.

The run must finish with exact arithmetic (every step bitwise-equal to
the closed form for its live membership; the final row-sharded state
audited against layout + accumulated sums), a measured RTO per fault
class read back from the metrics plane, and ZERO forensics stall trips
(any hang would have latched the sentinel and left an mpidiag-blamable
dump instead of a bare timeout — the caller checks).

``iso`` (3 ranks, shaping on, wire pinned) — recovery-traffic
isolation A/B: a respawn-state-delivery storm (6 CONCURRENT 64MB
rendezvous on the RESPAWN_STATE_TAG plane, 0 -> 1 edge; the sink
holds all six recv buffers, ~448MB resident with the pattern) under
the foreground step loop. Phase "uncls" strips the recovery planes from qos_tag_map (the
pre-PR default: recovery bytes ride NORMAL and contend head-on);
phase "bulk" restores the default map (recovery bytes BULK: clamped
DATA frags, deprioritized). Foreground p99 (coordinated-omission
corrected) must improve >= 2x with classification on — verdict
MIN-allreduced, stripe-style <= 3 attempts, correctness asserted on
every iteration of every attempt.

``steady`` (3 ranks) — no churn: N steps, SLO surface printed.
"""

import faulthandler
import signal as _signal
import sys
import threading
import time

import numpy as np

import ompi_tpu
from ompi_tpu import COMM_WORLD
from ompi_tpu.core.datatype import BYTE
from ompi_tpu.ft.recovery import RESPAWN_STATE_TAG, is_respawned, rejoin
from ompi_tpu.mca.var import all_pvars, get_var, set_var
from ompi_tpu.runtime import metrics
from ompi_tpu.serve import Episode, ServingHarness, SLOTracker
from ompi_tpu.serve import traffic as straffic

SELF = __file__
PHASE = 8          # applied state steps per phase/episode
SEED = 7
pv = all_pvars()


def _mk_harness(mode: str):
    """Fresh-or-rejoined harness (the respawn re-entry seam)."""
    if is_respawned():
        comm, state, meta = rejoin()
        assert state is not None, "newcomer received no state"
        h = ServingHarness(comm, seed=SEED, state=state,
                           respawn_command=SELF, respawn_args=(mode,))
        if meta.get("kind") == "final":
            # final-flush recovery: survivors are running the step-skew
            # reconcile — join it (our flushed state may be the ahead
            # or the behind copy)
            h.reconcile_live()
        return h, meta.get("kind", "-")
    from ompi_tpu.runtime.state import get_world

    comm = get_world()
    h = ServingHarness(comm, seed=SEED, respawn_command=SELF,
                       respawn_args=(mode,))
    h.commit_baseline()
    return h, None


def churn_mode() -> int:
    h, src = _mk_harness("churn")
    episodes = [
        (2 * PHASE, Episode("kill_respawn", victim=1, after=10)),
        (3 * PHASE, Episode("preempt_flush", victim=1, after=10,
                            grace_ms=800.0)),
        (4 * PHASE, Episode("kill_shrink", victim=2, after=10)),
    ]
    s = h.state_step()
    if not is_respawned():
        assert s == 0
        h.serve_until(PHASE)  # steady warmup: the SLO baseline
    else:
        # resume mid-script: finish the episode that spawned us WITHOUT
        # re-arming (our predecessor is already dead), then run the
        # rest of the schedule as a full member
        assert 0 < s < 4 * PHASE, s
        pending = [(t, ep) for t, ep in episodes if t > s]
        target = pending[0][0]
        h.serve_until(target)
        episodes = pending[1:]
    for target, ep in episodes:
        h.run_episode(ep, target - h.state_step(), seed=SEED)
    # --------------------------------------------------------- verdicts
    h.verify_state()
    comm = h.gate.comm
    me = comm.Get_rank()
    assert h.state_step() == 4 * PHASE, h.state_step()
    assert comm.Get_size() == 2, comm.Get_size()  # degraded world
    # RTO per fault class, read back from the METRICS plane (not the
    # driver's private history): every class this rank survived must
    # have a serve_rto_us{fault_class=...} histogram with samples
    snap = metrics.snapshot()
    rto_by_class = {
        hh["labels"]["fault_class"]: hh
        for hh in snap["histograms"] if hh["name"] == "serve_rto_us"}
    want_classes = {"kill_respawn", "preempt_flush", "kill_shrink"}
    if is_respawned():
        # a newcomer only witnesses the episodes after its spawn
        want_classes = {fc for fc in want_classes
                        if any(fc == e.fault_class for _t, e in episodes)}
    for fc in want_classes:
        assert fc in rto_by_class, (fc, sorted(rto_by_class))
        assert rto_by_class[fc]["count"] >= 1, fc
        assert rto_by_class[fc]["sum"] > 0, fc
    rtos = {fc: f"{hh['sum'] / max(hh['count'], 1):.0f}us"
            for fc, hh in sorted(rto_by_class.items())}
    # zero un-blamed hangs: a clean run latched NO stall (any hang
    # would have tripped the armed sentinel and dumped evidence first)
    assert pv["forensics_stall_trips"].value == 0
    assert pv["serve_steps"].value >= h.state_step() - s
    assert pv["serve_churn_recoveries"].value >= 1 or is_respawned()
    tr = h.tracker
    print(f"SERVING-RTO rank {me} {rtos}", flush=True)
    print(f"SERVING-SLO rank {me} p50={tr.p50():.0f}us "
          f"p99={tr.p99():.0f}us violations={tr.violations} "
          f"episodes={tr.episodes}", flush=True)
    print(f"SERVING-OK rank {me} steps={h.state_step()} "
          f"world={comm.Get_size()} src={src or 'origin'}", flush=True)
    ompi_tpu.Finalize()
    return 0


def steady_mode() -> int:
    h, _src = _mk_harness("steady")
    me = h.gate.comm.Get_rank()
    h.serve_until(PHASE)    # wireup/warmup: excluded from the SLO claim
    h.new_stream(mode="steady")
    h.serve_until(5 * PHASE)
    h.verify_state()
    tr = h.tracker
    assert pv["forensics_stall_trips"].value == 0
    if get_var("metrics", "enable"):
        # per-step critical-path breakdown (mean us per category from
        # the critpath histograms the harness fed)
        snap = metrics.snapshot()
        means = {}
        for cat in ("compute", "wire", "wait", "defer"):
            hh = [x for x in snap["histograms"]
                  if x["name"] == f"critpath_{cat}_us"]
            n = sum(x["count"] for x in hh)
            means[cat] = (sum(x["sum"] for x in hh) / n) if n else 0.0
        assert sum(x["count"] for x in snap["histograms"]
                   if x["name"] == "critpath_compute_us") >= 4 * PHASE
        print(f"SERVING-CRIT rank {me} "
              f"compute={means['compute']:.0f}us "
              f"wire={means['wire']:.0f}us wait={means['wait']:.0f}us "
              f"defer={means['defer']:.0f}us", flush=True)
    print(f"SERVING-SLO rank {me} p50={tr.p50():.0f}us "
          f"p99={tr.p99():.0f}us violations={tr.violations} "
          f"episodes={tr.episodes}", flush=True)
    print(f"SERVING-OK rank {me} steps={h.state_step()} "
          f"world={h.gate.comm.Get_size()} src=origin", flush=True)
    ompi_tpu.Finalize()
    return 0


# ------------------------------------------------- recovery-traffic A/B
BLOB = 64 << 20
N_BLOBS = 6
FG_STEPS = 60      # foreground arrivals per phase (floor)
PERIOD_US = 5000.0

_pat_memo = {}


def _pat() -> np.ndarray:
    """ONE shared 64MB pattern for every storm blob (six distinct
    patterns would be 384MB of resident arrays on the shipper; content
    is spot-checked per blob against the shared pattern instead)."""
    pat = _pat_memo.get(0)
    if pat is None:
        pat = _pat_memo[0] = np.arange(BLOB, dtype=np.uint8) + 11
    return pat


def _iso_phase(comm, tag: str, classified: bool):
    """One A/B phase: a respawn-state-delivery storm — N_BLOBS
    CONCURRENT 64MB rendezvous on the 0 -> 1 edge (recovery rebuilds
    ship every dead rank's state back-to-back; the merged backlog is
    the production shape, and single paced blobs stall the foreground
    by less than this 2-core host's ~130ms scheduler-noise p99 floor,
    measuring nothing) — under the foreground step loop on every rank.
    Returns the coordinated-omission-corrected foreground p99 (us)."""
    default_map = get_var("qos", "tag_map")
    if not classified:
        # strip the positive-tag recovery planes: state delivery rides
        # NORMAL and contends head-on (the pre-PR world)
        stripped = ",".join(p.strip() for p in default_map.split(",")
                            if p.strip().startswith("-"))
        set_var("qos", "tag_map", stripped)
    comm.Barrier()
    r = comm.Get_rank()
    tracker = SLOTracker(name="serve_step_us", period_us=PERIOD_US,
                         mode=tag)
    done = threading.Event()
    recv_ok = [0]
    storm_err = []

    def _guarded(body):
        # done.set() UNCONDITIONALLY and park the exception for the
        # main thread: a dying storm/sink daemon must fail the check
        # loudly, not strand every rank in the agreed-stop allreduce
        # until the caller's bare timeout (iso runs without forensics)
        def run():
            try:
                body()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                storm_err.append(e)
            finally:
                done.set()

        threading.Thread(target=run, daemon=True).start()

    if r == 0:
        dst = comm.group.world_rank(1)

        def storm():
            reqs = [comm.pml.isend(_pat(), BLOB, BYTE, dst,
                                   RESPAWN_STATE_TAG, comm.cid)
                    for _k in range(N_BLOBS)]
            for req in reqs:
                req.Wait()

        _guarded(storm)
    elif r == 1:
        src = comm.group.world_rank(0)

        def sink():
            bufs = [np.zeros(BLOB, np.uint8) for _k in range(N_BLOBS)]
            reqs = [comm.pml.irecv(b, BLOB, BYTE, src,
                                   RESPAWN_STATE_TAG, comm.cid)
                    for b in bufs]
            pat = _pat()
            for k, req in enumerate(reqs):
                req.Wait()
                buf = bufs[k]
                for lo in (0, BLOB // 2, BLOB - 4096):
                    assert np.array_equal(buf[lo:lo + 4096],
                                          pat[lo:lo + 4096]), \
                        f"recovery blob {k} corrupt at {lo} ({tag})"
                recv_ok[0] += 1

        _guarded(sink)
    else:
        done.set()
    gen = straffic.TrafficGen(tracker, seed=SEED, period_us=PERIOD_US)
    out = np.zeros(512)
    i = 0
    ready = np.zeros(1)
    agreed = np.zeros(1)
    while True:
        def one(_step):
            straffic.coll_step(comm, SEED, i, 512, out=out)

        gen.run(1, one, start_step=i)
        i += 1
        # agreed stop (MIN-allreduce: a rank-local break would tear the
        # next iteration's collectives — the PR 11/12 lesson)
        ready[0] = 1.0 if (i >= FG_STEPS and done.is_set()) else 0.0
        comm.Allreduce(ready, agreed, op=ompi_tpu.MIN)
        if agreed[0] > 0:
            break
    if storm_err:
        raise storm_err[0]
    if r == 1:
        assert recv_ok[0] == N_BLOBS, \
            f"recovery storm incomplete under {tag}: {recv_ok[0]}"
    set_var("qos", "tag_map", default_map)
    comm.Barrier()
    return tracker.p99()


def iso_mode() -> int:
    comm = COMM_WORLD
    r = comm.Get_rank()
    assert comm.Get_size() >= 3
    # wireup warmup (connections, pools, tuned tables) — unmeasured:
    # one warmup stall would backfill ~100 synthetic samples under the
    # coordinated-omission correction and drown a phase's distribution
    w = np.zeros(512)
    for k in range(10):
        straffic.coll_step(comm, SEED, k, 512, out=w)
    comm.Barrier()
    verdict = np.zeros(1)
    agreed = np.zeros(1)
    p99_u = p99_b = ratio = 0.0
    for attempt in range(3):
        p99_u = _iso_phase(comm, f"uncls{attempt}", classified=False)
        p99_b = _iso_phase(comm, f"bulk{attempt}", classified=True)
        ratio = p99_u / max(p99_b, 1e-9)
        verdict[0] = ratio
        comm.Allreduce(verdict, agreed, op=ompi_tpu.MIN)
        if agreed[0] >= 2.0:
            break
    if r == 0:
        # classification engaged: the storm frames were stamped BULK in
        # the classified phases (map-driven — no explicit qos override)
        assert pv["qos_stamped_bulk"].value > 0
    print(f"SERVING-ISO rank {r} uncls={p99_u:.0f}us bulk={p99_b:.0f}us "
          f"ratio={ratio:.2f}", flush=True)
    assert agreed[0] >= 2.0, \
        f"recovery-traffic isolation {agreed[0]:.2f}x < 2x"
    print(f"SERVING-OK rank {r} steps=iso world={comm.Get_size()} "
          f"src=origin", flush=True)
    ompi_tpu.Finalize()
    return 0


def main() -> int:
    # USR2, not USR1: churn mode arms forensics, whose wireup installs
    # its own SIGUSR1 dump handler and would clobber this one — the
    # traceback aid must work in exactly the mode most likely to hang
    faulthandler.register(_signal.SIGUSR2)
    mode = sys.argv[1] if len(sys.argv) > 1 else "churn"
    if mode == "churn":
        return churn_mode()
    if mode == "steady":
        return steady_mode()
    if mode == "iso":
        return iso_mode()
    print(f"unknown mode {mode}", flush=True)
    return 2


if __name__ == "__main__":
    sys.exit(main())
