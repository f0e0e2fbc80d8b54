"""Round-based collective schedules: the engine under both the tuned
blocking algorithms and the nonblocking (MPI_I*) collectives.

Reference: ompi/mca/coll/libnbc (12,429 LoC) expresses every nonblocking
collective as a DAG of send/recv/op/copy steps grouped into rounds
(NBC_Sched_send/recv/op, nbc_internal.h:156-161) progressed by
opal_progress. Redesign: an algorithm here is a Python *generator* that
yields ``Round`` objects (the communication steps) and performs local
compute between yields — the round barrier the reference encodes as
schedule delimiters falls out of generator suspension. One algorithm
definition serves both paths:

- blocking:   ``run_blocking`` drains the generator, waiting each round;
- nonblocking: ``NbcRequest`` issues each round and advances from request
  completion callbacks, so the schedule progresses from the progress
  engine/thread exactly like libnbc rounds do.

Traffic isolation: nonblocking schedules run in a dedicated CID plane
(NBC_CID_BIT) with a per-communicator sequence number as the tag, so
overlapping schedules on one communicator never cross-match (libnbc's
per-comm tag counter, nbc_internal.h SCHED tag logic).

Datapath discipline (the PR 9 btl contract, extended up to this layer):

- **sends are borrowed views** over the caller's packed/accumulator
  buffers — a payload is copied only when the source is genuinely
  non-contiguous, and that copy is counted;
- **recvs are pooled or land direct**: a ``(nbytes, src)`` recv draws a
  size-classed block from ``runtime/mpool.class_pool`` (recycled on
  clean completion or ``Round.free``; DISCARDED — never recycled — when
  the schedule fails, so a racing drain can't alias the next owner); a
  ``(nbytes, src, dest)`` recv unpacks straight into the caller's view
  (the final out/accumulator slice) with no staging at all;
- **windowing**: a ``Round(ordered=False)`` promises the generator
  neither reads the round's results nor touches its buffers until it
  RESUMES from the next ordered yield (or the schedule completes), so
  up to ``coll_round_window`` such rounds stay in flight instead of a
  full barrier per round — in both ``run_blocking`` and ``NbcRequest``.
  Unordered rounds to the SAME peer must be order-insensitive (the
  built-in user is alltoall pairwise: every round targets a distinct
  peer). An ordered round is a barrier on RESUME — its own sends/recvs
  are issued before the window drains (recvs pre-post), so they must
  not depend on in-flight unordered results; only when the generator
  resumes has every earlier round completed.
- **measured, not estimated**: ``coll_round_bytes_copied`` /
  ``bytes_moved`` / ``pool_hits`` / ``windowed`` pvars.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import (Callable, Dict, Generator, List, Optional, Sequence,
                    Tuple)

import numpy as np

from ompi_tpu.core.datatype import BYTE
from ompi_tpu.core.errors import MPIError, ERR_REQUEST
from ompi_tpu.core.request import Request
from ompi_tpu.mca.var import register_var, register_pvar
from ompi_tpu.runtime import forensics as _forensics
from ompi_tpu.runtime import mpool
from ompi_tpu.runtime import trace as _trace

# Distinct CID plane per traffic class: COLL_CID_BIT = 1<<30 (coll/basic),
# PART_CID_BIT = 1<<29 (pml/partitioned) — NBC takes 1<<28 so overlapping
# nonblocking schedules, partitioned transfers, and blocking collectives on
# the same communicator can never cross-match.
NBC_CID_BIT = 1 << 28

_window_var = register_var(
    "coll_round", "window", 4,
    help="Max unordered rounds kept in flight per schedule (1 = "
         "lockstep, the pre-PR-10 barrier-per-round behavior). Only "
         "rounds yielded with ordered=False window; an ordered round "
         "is a full barrier.", level=6)

# measured datapath counters (read via the coll_round_* pvars):
# copied = staging bytes the round engine/algorithms duplicated;
# moved  = payload bytes carried by round sends+recvs;
# pool_hits = recv blocks served from a size-class free list;
# windowed  = rounds issued without waiting (ordered=False, in-window).
# Bumps go through _bump: the app thread (run_blocking) and the
# progress thread (NbcRequest callbacks) both count, and an unlocked
# dict read-modify-write loses increments under that interleaving (the
# progress._call_count lesson) — the lock is per ROUND, not per byte,
# so the hot path pays one uncontended acquire per bump site.
_ctr = {"copied": 0, "moved": 0, "pool_hits": 0, "windowed": 0}
_ctr_lock = threading.Lock()


def _bump(key: str, n: int = 1) -> None:
    with _ctr_lock:
        _ctr[key] += n

register_pvar("coll_round", "bytes_copied", lambda: _ctr["copied"],
              help="Staging bytes copied by the collective round engine "
                   "and its algorithms")
register_pvar("coll_round", "bytes_moved", lambda: _ctr["moved"],
              help="Payload bytes carried by round sends+recvs — the "
                   "denominator of copies-per-byte-moved")
register_pvar("coll_round", "pool_hits", lambda: _ctr["pool_hits"],
              help="Round recv blocks served from the mpool size-class "
                   "free lists (steady-state recycling proof)")
register_pvar("coll_round", "windowed", lambda: _ctr["windowed"],
              help="Rounds issued without a barrier (ordered=False "
                   "inside the coll_round_window)")


# coll/persist imports this module, so the replay-counter handle binds
# lazily — a one-time memo, not a per-Start sys.modules lookup (Start
# latency is the pvar the persistent A/B measures)
_persist_mod = None


def _persist():
    global _persist_mod
    if _persist_mod is None:
        from ompi_tpu.coll import persist

        _persist_mod = persist
    return _persist_mod


# ------------------------------------------------------- stall forensics
# Live-schedule registry for the forensics provider: populated only
# while the plane is armed (one live-Var load per schedule otherwise).
# NbcRequests ride a WeakSet (they die with their requests); blocking
# schedules check in/out explicitly around the drive loop.
import weakref as _weakref  # noqa: E402

_fx_lock = threading.Lock()
_live_nbc: "_weakref.WeakSet" = _weakref.WeakSet()
_live_blocking: Dict[int, dict] = {}


def _fx_debug_state() -> dict:
    """Forensics provider: every in-flight schedule's round batches and
    window occupancy (what the schedule is waiting FOR), plus the
    datapath counters. NbcRequest fields are read under each request's
    own lock — the same lock its batch retirement holds."""
    now = time.monotonic()
    with _fx_lock:
        nbc = [r for r in _live_nbc]
        blocking = [dict(v) for v in _live_blocking.values()]
    reqs = []
    nbc_live = 0
    for r in nbc:
        if r._complete.is_set():
            continue
        nbc_live += 1
        if len(reqs) >= _forensics.CAP:
            continue
        with r._lock:
            waiting = ("round-self" if r._wait_self
                       else "ordered-barrier" if r._wait_batch is not None
                       else "window-full" if r._park_bufs is not None
                       else "schedule-done" if r._gen_done
                       else "advancing")
            reqs.append({"tag": r._tag, "cid": r._cid,
                         "inflight_batches": r._inflight,
                         "waiting": waiting,
                         "child_error": r._child_error,
                         "age_s": round(
                             now - getattr(r, "_fx_born", now), 3)})
    for b in blocking:
        b["age_s"] = round(now - b.pop("born"), 3)
    with _ctr_lock:
        counters = dict(_ctr)
    return {"window": int(_window_var._value),
            "nbc_inflight": reqs,
            "nbc_inflight_omitted": max(0, nbc_live - len(reqs)),
            "blocking": _forensics.clip(blocking),
            "blocking_omitted": max(0, len(blocking) - _forensics.CAP),
            "counters": counters}


_forensics.register_provider("coll.sched", _fx_debug_state)


def note_copied(nbytes: int) -> None:
    """Charge a staging copy to the round-engine copy budget."""
    _bump("copied", int(nbytes))


class Round:
    """One communication round: isend all ``sends``, irecv all ``recvs``,
    then hand the received payloads back to the generator in order.

    ``sends``  — (contiguous uint8 view, dst comm-rank): the engine
    borrows the view; the caller must not mutate it until the round (or,
    for unordered rounds, the schedule's next barrier) completes.
    ``recvs``  — (nbytes, src) for a pooled staging block, or
    (nbytes, src, dest_view) to land the payload directly in ``dest_view``
    (a writable contiguous uint8 view of exactly ``nbytes``).
    ``ordered`` — False marks the round independent: the engine may
    window it. Contract precision: an unordered round's results and
    buffers are guaranteed only when the generator RESUMES from the
    next ordered yield (or the schedule completes) — both engines issue
    an ordered round's sends/recvs BEFORE draining the window (the
    recvs pre-post), so the ordered round's own payloads must not
    depend on any in-flight unordered result.
    ``wait``   — (only meaningful with ``ordered=False``) the generator
    resumes as soon as THIS round's own sends/recvs complete, WITHOUT
    draining other in-flight unordered rounds: its own results are
    guaranteed at resume, everything else keeps flying. This is the
    cross-phase pipelining seam (coll/persist.py's chunked allreduce
    issues chunk k+1's reduce-scatter rounds while chunk k's allgather
    rounds are still in flight) — a full ``ordered`` barrier between
    the phases would serialize exactly the overlap the chunking buys.
    ``free``   — previously-received pooled views the generator is done
    with: recycled immediately instead of at schedule end (the
    segmented-ring steady-state path).
    ``qos``    — QoS class for this round's sends (ompi_tpu/qos.py;
    None = the pml's own classification). A schedule phase that tags
    its rounds BULK lets the shaped tcp btl interleave another phase's
    frames ahead of it instead of serializing them FIFO.
    ``plane``  — tag sub-plane (0-3): rounds on different planes match
    on distinct tags. REQUIRED whenever two phases of one schedule
    carry different QoS classes to the same peer: the shaped btl
    reorders across classes, and same-(cid, src, tag) frames arriving
    out of send order would bind to the wrong posted receives.
    ``chunk``  — pipeline-chunk ordinal (or None): purely descriptive
    trace stamp so coll/persist's chunked replays keep their stage
    structure visible in the merged timeline (the ``coll.round`` span
    tools/mpicrit.py groups wire edges under)."""

    __slots__ = ("sends", "recvs", "ordered", "wait", "free", "qos",
                 "plane", "chunk")

    def __init__(self,
                 sends: Sequence[Tuple[np.ndarray, int]] = (),
                 recvs: Sequence[Tuple] = (),
                 ordered: bool = True,
                 wait: bool = False,
                 free: Sequence[np.ndarray] = (),
                 qos: Optional[int] = None,
                 plane: int = 0,
                 chunk: Optional[int] = None):
        self.sends = list(sends)
        self.recvs = list(recvs)
        self.ordered = ordered
        self.wait = wait
        self.free = free
        self.qos = qos
        self.plane = plane
        self.chunk = chunk


Schedule = Generator[Round, List[np.ndarray], None]


class _RoundState:
    """Pool-block ownership for one schedule — the explicit contract:
    blocks recycle on clean completion (or early, via ``Round.free``);
    a failing/abandoned schedule DISCARDS them, never recycles (the
    PR 9 dying-conn lesson: an in-flight drain may still land in a
    block, and a recycled block would alias its next owner)."""

    __slots__ = ("_held", "rounds")

    def __init__(self):
        # id(view) -> (pool, block, view): the view keeps id() stable
        self._held: Dict[int, tuple] = {}
        # rounds issued so far — the trace-only ordinal stamped on
        # coll.round spans (per schedule, not per communicator)
        self.rounds = 0

    def alloc(self, nbytes: int) -> np.ndarray:
        pool = mpool.class_pool(nbytes)
        if pool is None:  # zero-byte tokens / jumbo past the class cap
            return np.empty(nbytes, dtype=np.uint8)
        block, hit = pool.acquire_pair()
        if hit:
            _bump("pool_hits")
        view = np.frombuffer(block, np.uint8, nbytes)
        self._held[id(view)] = (pool, block, view)  # owns: _held
        return view

    def free(self, views) -> None:
        for v in views:
            ent = self._held.pop(id(v), None)  # mpiracer: disable=cross-thread-race — a _RoundState belongs to ONE schedule; the single-driver _gen_running token (NbcRequest) serializes every resume that can reach free()
            if ent is not None:
                ent[0].release(ent[1])

    def release_all(self) -> None:
        held, self._held = self._held, {}
        for pool, block, _ in held.values():
            pool.release(block)

    def discard_all(self) -> None:
        held, self._held = self._held, {}
        for pool, block, _ in held.values():
            pool.discard(block)


def _issue(comm, rnd: Round, tag: int, cid: int, state: _RoundState):
    """Post the round's receives then sends. Returns
    (requests, recv_bufs)."""
    reqs = []
    bufs: List[np.ndarray] = []
    moved = 0
    tr = _trace.enabled()
    if tr:
        t0 = _trace.now()
    if rnd.plane:
        # tag sub-plane: far above the per-comm NBC sequence counters,
        # symmetric across ranks (both sides build the same rounds)
        tag = tag | (rnd.plane << 56)
    for rec in rnd.recvs:
        nbytes, src = rec[0], rec[1]
        dest = rec[2] if len(rec) > 2 else None
        moved += nbytes
        # a dest view means zero staging: the payload lands in place
        buf = dest if dest is not None else state.alloc(nbytes)
        bufs.append(buf)
        reqs.append(comm.pml.irecv(buf, nbytes, BYTE,
                                   comm.group.world_rank(src), tag, cid))
    for data, dst in rnd.sends:
        if not data.flags.c_contiguous:
            # the one allowed send-side staging copy: a genuinely
            # non-contiguous source can't be borrowed as a flat view
            data = np.ascontiguousarray(data)  # mpilint: disable=hot-copy — non-contiguous fallback, counted
            _bump("copied", data.nbytes)
        moved += data.nbytes
        reqs.append(comm.pml.isend(data, data.nbytes, BYTE,
                                   comm.group.world_rank(dst), tag, cid,
                                   qos=rnd.qos))
    _bump("moved", moved)
    if tr:
        # stage structure into the trace: (cid, tag, round, chunk,
        # plane) lets tools/mpicrit.py group the wire edges a round
        # produced under the schedule stage that issued them
        state.rounds += 1
        _trace.record_span("coll.round", t0, _trace.now(), cat="coll",
                           cid=cid, tag=tag, round=state.rounds,
                           chunk=rnd.chunk, plane=rnd.plane,
                           sends=len(rnd.sends), recvs=len(rnd.recvs))
    return reqs, bufs


def run_blocking(comm, gen: Schedule, tag: int, cid: int) -> None:
    """Drive a schedule to completion. Ordered rounds are barriers
    (every outstanding round drains first, then the round itself);
    unordered rounds stay in flight up to ``coll_round_window``. A
    failing request must not abandon outstanding requests mid-schedule
    (the Waitsome lesson): unwaited sends would cross-match the NEXT
    schedule on this communicator — wait them all, then surface the
    first error. Pool blocks recycle only on clean completion;
    any failure path discards them."""
    state = _RoundState()
    inflight: deque = deque()  # request lists of unordered rounds
    first_error: Optional[MPIError] = None
    fx_key = None
    if _forensics._enable_var._value:  # forensics check-in
        fx_key = id(state)
        with _fx_lock:
            _live_blocking[fx_key] = {"tag": tag, "cid": cid,
                                      "round": 0,
                                      "born": time.monotonic()}

    def retire(reqs) -> None:
        nonlocal first_error
        for r in reqs:
            try:
                r.Wait()
            except MPIError as e:
                if first_error is None:
                    first_error = e

    bufs: Optional[List[np.ndarray]] = None
    first = True
    try:
        while True:
            try:
                rnd = next(gen) if first else gen.send(bufs)
            except StopIteration:
                break
            first = False
            if fx_key is not None:
                with _fx_lock:
                    ent = _live_blocking.get(fx_key)
                    if ent is not None:
                        ent["round"] += 1
            if rnd.free:
                state.free(rnd.free)
            reqs, bufs = _issue(comm, rnd, tag, cid, state)
            window = _window_var._value
            if rnd.ordered or window <= 1:
                while inflight:
                    retire(inflight.popleft())
                retire(reqs)
            elif rnd.wait:
                # self-wait: this round's own results gate the resume,
                # earlier unordered rounds keep flying (the cross-phase
                # pipelining contract)
                if inflight:
                    _bump("windowed")
                retire(reqs)
            else:
                _bump("windowed")
                inflight.append(reqs)
                while len(inflight) >= max(1, window):
                    retire(inflight.popleft())
            if first_error is not None:
                raise first_error
        while inflight:
            retire(inflight.popleft())
        if first_error is not None:
            raise first_error
    except BaseException:
        while inflight:
            retire(inflight.popleft())
        state.discard_all()
        raise
    finally:
        if fx_key is not None:  # forensics check-out, every exit path
            with _fx_lock:
                _live_blocking.pop(fx_key, None)
    state.release_all()


def alloc_nbc_tag(comm) -> int:
    """Per-comm schedule sequence number; ranks agree because MPI requires
    collectives to be called in the same order on every member."""
    seq = getattr(comm, "_nbc_seq", 0)
    comm._nbc_seq = seq + 1
    return seq


class NbcRequest(Request):
    """A nonblocking collective in flight: advances its schedule from
    request completion callbacks (libnbc's NBC_Progress analog), keeping
    up to ``coll_round_window`` unordered rounds in flight.

    Concurrency contract: exactly one thread drives the generator at a
    time (``_gen_running``); every other mutation — child errors, batch
    retirement, park/resume decisions, the pool-block release on the
    completion path — happens under ``self._lock``. ``_child_error`` in
    particular is written ONLY under the lock (the pre-PR-10 engine
    wrote it unlocked from the progress thread while ``_advance`` read
    it mid-loop, so a losing error could be dropped)."""

    def __init__(self, comm, gen: Schedule):
        super().__init__()
        self._comm = comm
        self._gen = gen
        self._tag = alloc_nbc_tag(comm)
        self._cid = comm.cid | NBC_CID_BIT
        self._lock = threading.Lock()
        self._child_error = 0
        self._state = _RoundState()
        self._inflight = 0          # issued-but-unretired batches
        self._wait_batch = None     # ordered batch the generator awaits
        self._wait_self = False     # Round.wait: resume on the batch's
        #                             OWN retirement, not the window's
        self._park_bufs = None      # bufs pending a free window slot
        self._gen_done = False
        self._finishing = False
        self._gen_running = True
        if _forensics._enable_var._value:  # forensics registry
            self._fx_born = time.monotonic()
            with _fx_lock:
                _live_nbc.add(self)
        self._advance(None, first=True)

    # ------------------------------------------------------------ engine
    def _advance(self, bufs: Optional[List[np.ndarray]],
                 first: bool = False) -> None:
        # invariant: the caller claimed _gen_running under the lock
        while True:
            with self._lock:
                err = self._child_error
            if err:
                self._gen_stopped()
                return
            try:
                rnd = next(self._gen) if first else self._gen.send(bufs)
            except StopIteration:
                self._gen_stopped(done=True)
                return
            except MPIError as e:
                self._gen_stopped(done=True, code=e.code)
                return
            except Exception:
                # Rounds >= 2 run inside completion callbacks on the
                # progress thread; an escaped exception would kill it and
                # leave Wait() spinning forever. Fail the request instead.
                from ompi_tpu.core.errors import ERR_INTERN
                from ompi_tpu.utils.output import get_logger

                get_logger("coll.nbc").warning(
                    "schedule raised", exc_info=True)
                self._gen_stopped(done=True, code=ERR_INTERN)
                return
            first = False
            if rnd.free:
                with self._lock:
                    self._state.free(rnd.free)
            reqs, next_bufs = _issue(self._comm, rnd, self._tag,
                                     self._cid, self._state)
            window = max(1, _window_var._value)
            ordered = rnd.ordered or window <= 1
            wait_self = not ordered and rnd.wait
            if not reqs:
                if ordered:
                    # a request-less ordered round is still a barrier
                    # (run_blocking drains the window for it too):
                    # resume only once every in-flight batch retires
                    with self._lock:
                        if self._inflight > 0:
                            self._wait_batch = {"n": 0,
                                                "bufs": next_bufs}
                            self._gen_running = False
                            return
                bufs = next_bufs
                continue
            # Hold one extra token so synchronous completions loop here
            # instead of recursing through the callback.
            batch = {"n": len(reqs) + 1, "bufs": next_bufs}
            with self._lock:
                self._inflight += 1
            for r in reqs:
                r.add_completion_callback(
                    lambda r, b=batch: self._child_done(r, b))
            overlapped = False
            with self._lock:
                batch["n"] -= 1
                done_now = batch["n"] == 0
                if done_now:
                    self._inflight -= 1
                    barrier_ok = self._inflight == 0
                else:
                    barrier_ok = False
                if ordered:
                    if not (done_now and barrier_ok):
                        # resume when THIS batch and the whole window
                        # have drained (ordered == barrier)
                        self._wait_batch = batch
                        self._gen_running = False
                        return
                elif wait_self:
                    # Round.wait: this batch's own retirement gates the
                    # resume; other in-flight batches keep flying (they
                    # are the overlap the schedule asked for)
                    overlapped = self._inflight > (0 if done_now else 1)
                    if not done_now:
                        self._wait_batch = batch
                        self._wait_self = True
                        self._gen_running = False
                        if overlapped:
                            # _ctr_lock is a leaf lock: safe under _lock
                            _bump("windowed")
                        return
                elif not done_now and self._inflight >= window:
                    self._park_bufs = next_bufs
                    self._gen_running = False
                    return
            if (not ordered and not wait_self and not done_now) or \
                    (wait_self and overlapped):
                _bump("windowed")
            bufs = next_bufs

    def _child_done(self, r, batch) -> None:
        fire = None
        finish = None
        with self._lock:
            if r._error and not self._child_error:
                self._child_error = r._error
            batch["n"] -= 1
            if batch["n"] != 0:
                return
            self._inflight -= 1
            if self._gen_running or self._finishing:
                pass  # the driving thread observes the new state itself
            elif self._child_error:
                if self._inflight == 0:
                    self._finishing = True
                    finish = self._child_error
            elif self._wait_batch is not None:
                # ordered waits resume when the whole window drains; a
                # Round.wait batch resumes on its OWN retirement (the
                # just-retired batch is `batch`), leaving other rounds
                # in flight
                if self._inflight == 0 or \
                        (self._wait_self and batch is self._wait_batch):
                    fire = self._wait_batch["bufs"]
                    self._wait_batch = None
                    self._wait_self = False
                    self._gen_running = True
            elif self._park_bufs is not None and \
                    self._inflight < max(1, _window_var._value):
                fire = self._park_bufs
                self._park_bufs = None
                self._gen_running = True
                _bump("windowed")
            elif self._gen_done and self._inflight == 0:
                self._finishing = True
                finish = 0
        if finish is not None:
            self._finish_schedule(finish)
        elif fire is not None:
            self._advance(fire)

    def _gen_stopped(self, done: bool = False, code: int = 0) -> None:
        """The driving thread is leaving the advance loop: either the
        generator finished/raised (``done``) or a child error stops the
        schedule. Completion fires once every in-flight batch retires."""
        finish = None
        with self._lock:
            if code and not self._child_error:
                self._child_error = code
            if done:
                self._gen_done = True
            self._gen_running = False
            if self._inflight == 0 and not self._finishing:
                self._finishing = True
                finish = self._child_error
        if finish is not None:
            self._finish_schedule(finish)

    def _finish_schedule(self, err: int) -> None:
        """Terminal transition (exactly once): settle pool-block
        ownership — recycle on success, DISCARD on failure — then
        complete the request."""
        if err:
            self._state.discard_all()
            try:
                self._gen.close()
            except Exception:
                pass
        else:
            self._state.release_all()
        self._set_complete(err)


class PersistentCollRequest(Request):
    """Persistent collective (MPI_Allreduce_init & co, MPI-4).

    Reference: ompi/mca/coll/coll.h:545-620 declares the *_init third of the
    triple surface; libnbc builds the schedule at init and replays it per
    Start. Here ``issue`` is a thunk capturing the buffers/op/root that
    launches the activation: when the persistent-plan compiler
    (coll/persist.py) froze the lowering at init, it replays the frozen
    schedule; otherwise (``coll_persist_enable=0`` or an ineligible
    shape) it rebuilds and launches a fresh NbcRequest per Start — the
    pre-PR-11 re-issue path, kept verbatim as the A/B baseline. Tag
    consistency across ranks holds because MPI requires persistent
    starts (like every collective) to be identically ordered on all
    members, so the per-comm NBC sequence counter stays aligned."""

    def __init__(self, issue: Callable[[], Request],
                 name: str = "persistent collective"):
        super().__init__()
        self.persistent = True
        self._issue = issue
        self._name = name
        # Active state is distinct from completion: the request stays
        # *active* from Start until Wait/Test collects it, even though the
        # inner schedule may have completed microseconds after Start (MPI
        # 3.0 §3.9: a started persistent request must be completed by a
        # completion call before it can be restarted).
        self._active = False
        self._complete.set()  # inactive == complete (MPI semantics)

    def Start(self) -> "PersistentCollRequest":
        if self._active:
            raise MPIError(
                ERR_REQUEST,
                f"Start on still-active {self._name}: the previous "
                "activation must be completed by Wait/Test before a "
                "restart (MPI 3.0 §3.9)")
        self._active = True
        self._complete.clear()
        self._error = 0
        t0 = time.perf_counter()
        try:
            inner = self._issue()
        except BaseException:
            # a failed issue (revoked comm, bad schedule) must not wedge
            # the request: roll back to inactive so the error is
            # retryable and Wait doesn't spin forever
            self._active = False
            self._complete.set()
            raise
        # the A/B denominator: Start-call latency (issue decisions +
        # first-round launch) accumulated for BOTH the frozen-replay and
        # re-issue paths, so the replay win is measured from pvars
        p = _persist()
        p._starts[0] += 1
        p._replay_us[0] += (time.perf_counter() - t0) * 1e6

        def done(r):
            self.status = r.status
            self._set_complete(r._error)

        inner.add_completion_callback(done)
        return self

    def Free(self) -> None:
        """MPI_Request_free on an inactive persistent collective: retire
        the frozen plan so its held pool blocks return to their free
        lists (an active plan's are discarded — in-flight drains may
        still land in its views). The comm's Free covers requests the
        caller never frees."""
        box = getattr(self, "_persist_box", None)
        if box is not None and box[0] is not None:
            box[0].retire()
            box[0] = None

    def _finish(self, status) -> None:
        self._active = False
        super()._finish(status)


class JaxRequest(Request):
    """Mesh-path nonblocking collective: the jitted executable has been
    dispatched (jax dispatch is asynchronous); the request completes when
    the result buffers are ready. ``result`` holds the output array(s)."""

    def __init__(self, result):
        super().__init__()
        self.result = result
        self._set_dispatch_complete()

    def Start(self):
        raise MPIError(ERR_REQUEST, "not a persistent request")

    def _set_dispatch_complete(self):
        # Completion flag tracks device readiness lazily: Test polls
        # is_ready, Wait blocks on the buffer.
        pass

    @property
    def is_complete(self) -> bool:
        try:
            import jax

            leaves = jax.tree_util.tree_leaves(self.result)
            return all(
                x.is_ready() if hasattr(x, "is_ready") else True
                for x in leaves
            )
        except Exception:
            return True

    def Test(self, status=None) -> bool:
        if self.is_complete:
            if not self._complete.is_set():
                self._set_complete(0)
            self._finish(status)
            return True
        return False

    def Wait(self, status=None, timeout=None):
        import jax
        import time

        if timeout is None:
            jax.block_until_ready(self.result)
        else:
            deadline = time.monotonic() + timeout
            while not self.is_complete:
                if time.monotonic() > deadline:
                    from ompi_tpu.core.errors import ERR_PENDING

                    raise MPIError(ERR_PENDING, "Wait timed out")
                time.sleep(0.001)
        if not self._complete.is_set():
            self._set_complete(0)
        self._finish(status)


class MeshPersistentRequest(JaxRequest):
    """Persistent mesh collective (Allreduce_init & co on XlaComm).

    The TPU-native reading of MPI-4 persistence: the setup that init
    amortizes is trace+compile — XlaComm's init methods run one warm-up
    dispatch so every Start is a cached-executable dispatch only, and
    (PR 11) pre-freeze the resolved fast-table executable into
    ``dispatch`` so Start skips even the fast-dict lookup. jax operands
    are immutable, so "re-reads the buffer at Start" becomes an optional
    fresh operand argument (same shape/dtype/sharding triggers no
    retrace); omitted, the init-time operand is re-run. ``result`` holds
    the latest Start's output once Wait/Test observes completion.

    ``donate`` (armed by ``coll_persist_donate``) is a second
    executable compiled at init with the operand buffer DONATED to XLA:
    a ``Start(x)`` with a fresh operand consumes ``x`` (its buffer is
    reused for the output — the MPI-4 reading: the started buffer
    belongs to the operation until completion). The init-time operand is
    kept un-donated so operand-less restarts stay valid."""

    def __init__(self, comm, dispatch, x, frozen: bool = False,
                 donate=None):
        Request.__init__(self)
        self.persistent = True
        self._comm = comm
        self._dispatch = dispatch
        self._x = x
        self._frozen = frozen
        self._donate = donate
        self._active = False
        self.result = None
        self._complete.set()  # inactive == complete

    def Start(self, x=None):
        if self._active:
            raise MPIError(
                ERR_REQUEST,
                f"Start on still-active persistent mesh collective on "
                f"{self._comm.name}: complete it with Wait/Test first")
        self._comm._check_usable()  # revoked comms must not dispatch
        t0 = time.perf_counter()
        # dispatch before committing any state: a failed dispatch (bad
        # shape/sharding) must leave the request inactive with the
        # previous operand and result intact, not report stale data as
        # this Start's success
        if x is not None and self._donate is not None \
                and x is not self._x:
            # donated path: x is consumed; the init-time operand stays
            # bound (and un-donated) for operand-less restarts — which
            # is why passing the init operand itself routes to the
            # un-donated dispatch below instead of deleting it
            result = self._donate(x)
        else:
            result = self._dispatch(self._x if x is None else x)
            if x is not None:
                self._x = x
        p = _persist()
        p._starts[0] += 1
        p._replay_us[0] += (time.perf_counter() - t0) * 1e6
        self._active = True
        self._complete.clear()
        self._error = 0
        self.result = result
        return self

    def _finish(self, status) -> None:
        self._active = False
        super()._finish(status)
