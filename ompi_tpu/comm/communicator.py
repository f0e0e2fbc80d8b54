"""Communicators.

Reference: ompi/communicator (8,787 LoC) — comm objects own a group, a
context id (CID), an errhandler, attribute caching, and a per-comm
collectives table (comm->c_coll); point-to-point dispatches through the
PML (ompi/mpi/c/send.c.in:85 MCA_PML_CALL).

Two concrete kinds:
- ``ProcComm`` — process mode: this process *is* one rank; verbs take host
  buffers and run over pml/btl.
- ``XlaComm`` (ompi_tpu/parallel/mesh.py) — SPMD mesh mode: the single
  controller holds all ranks; collectives are XLA programs over the ICI
  mesh.

CID allocation is a distributed agreement in the reference
(comm_cid.c:61-109); here it is a MAX-allreduce over the parent
communicator, which serves the same purpose (all members agree on a fresh
id) in one round.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ompi_tpu.core import op as _op
from ompi_tpu.core.datatype import Datatype, BYTE, INT64, from_numpy_dtype
from ompi_tpu.core.errors import (
    MPIError,
    ERR_ARG,
    ERR_COMM,
    ERR_RANK,
    ERR_REVOKED,
    ERR_UNSUPPORTED_OPERATION,
    ERRORS_ARE_FATAL,
    Errhandler,
)
from ompi_tpu.core.group import Group
from ompi_tpu.core.request import Request
from ompi_tpu.core.status import Status
from ompi_tpu.coll import hier as _hier
from ompi_tpu.coll.hier import plan as _cplan
from ompi_tpu.runtime import peruse, spc
from ompi_tpu.runtime import metrics as _metrics
from ompi_tpu.runtime import sanitizer as _san
from ompi_tpu.runtime import trace as _trace

ANY_SOURCE = -1
ANY_TAG = -1
PROC_NULL = -2
UNDEFINED = -32766


class _Keyval:
    __slots__ = ("copy_fn", "delete_fn")

    def __init__(self, copy_fn, delete_fn):
        self.copy_fn = copy_fn
        self.delete_fn = delete_fn


_keyvals: Dict[int, _Keyval] = {}
_next_keyval = [100]
_ATTR_UNSET = object()  # distinguishes "not set" from a stored None


def parse_buffer(buf) -> Tuple[Any, int, Datatype]:
    """Accept ndarray | bytearray | [obj, datatype] | [obj, count, datatype]
    (mpi4py-style buffer specs) | jax.Array (send side, staged through
    host) | accelerator.DeviceBuffer (recv side, functional device
    update). Reference: the accelerator-buffer checks in every binding
    (pml_ob1_accelerator.c; coll/accelerator wrapper)."""
    if isinstance(buf, (list, tuple)):
        if len(buf) == 2:
            obj, dt = buf
            obj = _stage_device(obj)
            count = obj.size if hasattr(obj, "size") else len(obj)
            return obj, int(count), dt
        if len(buf) == 3:
            obj, count, dt = buf
            return _stage_device(obj), int(count), dt
        raise MPIError(ERR_ARG, "buffer spec must be [obj, [count,] datatype]")
    if isinstance(buf, np.ndarray):
        if buf.dtype.names:
            raise MPIError(ERR_ARG,
                           "structured arrays need an explicit datatype")
        return buf, buf.size, from_numpy_dtype(buf.dtype)
    if isinstance(buf, (bytearray, memoryview, bytes)):
        return buf, len(buf), BYTE
    staged = _stage_device(buf)
    if staged is not buf:
        return staged, staged.size, from_numpy_dtype(staged.dtype)
    raise MPIError(ERR_ARG, f"cannot infer buffer spec from {type(buf)}")


def _stage_device(obj):
    """Resolve device buffers for the host data path. Raw device arrays
    DTOH-stage to a READ-ONLY ndarray (they are immutable, so a recv into
    the staging copy must fail loudly); DeviceBuffer holders hand out
    their mutable staging array and conservatively invalidate the cached
    device view — we cannot tell read from write uses here, and a stale
    cache would be a correctness bug while an extra HTOD upload is only
    a cost."""
    from ompi_tpu.accelerator import DeviceBuffer, is_device_buffer, stage_to_host

    if isinstance(obj, DeviceBuffer):
        obj._mark_dirty()
        return obj.host
    if is_device_buffer(obj):
        return stage_to_host(obj)
    return obj


class Communicator:
    def __init__(self, group: Group, cid: int, name: str = ""):
        self.group = group
        self.cid = cid
        self.name = name or f"comm-{cid}"
        self.errhandler: Errhandler = ERRORS_ARE_FATAL
        self.attributes: Dict[int, Any] = {}
        self.revoked = False  # ULFM (reference: communicator.h:360-363)
        self.coll = None  # CollTable, set by subclasses after selection
        self.topo = None  # topology module (cart/graph), set by topo layer
        self._freed = False  # session liveness tracking (MPI-4 11.2.2)
        from ompi_tpu.mpit import emit  # MPI_T event (mpit.py)

        emit("comm", "created", name=self.name, cid=cid,
             size=group.size)

    # ------------------------------------------------------------- queries
    @property
    def size(self) -> int:
        return self.group.size

    def Get_size(self) -> int:
        return self.size

    def Get_group(self) -> Group:
        return self.group

    def Get_name(self) -> str:
        return self.name

    def Set_name(self, name: str) -> None:
        self.name = name

    def Get_errhandler(self) -> Errhandler:
        return self.errhandler

    def Set_errhandler(self, eh: Errhandler) -> None:
        self.errhandler = eh

    # ------------------------------------------------------ QoS override
    # Multi-tenant traffic shaping (ompi_tpu/qos.py): the override
    # rides a comm-attr keyval (so Dup inherits it and Free's attribute
    # sweep releases it) and applies to every frame of this
    # communicator and its derived cid planes while
    # btl_tcp_shape_enable is on.
    def Set_qos_class(self, cls) -> None:
        """Pin this communicator's traffic to QoS class ``cls``
        ('latency' / 'normal' / 'bulk'): a latency-critical serving
        comm is promoted past background planes, a replication comm is
        demoted below foreground collectives."""
        from ompi_tpu import qos as _qos

        _qos.set_comm_class(self, cls)

    def Get_qos_class(self) -> str:
        from ompi_tpu import qos as _qos

        return _qos.NAMES[_qos.get_comm_class(self)]

    # ------------------------------------------------- stall forensics
    def Dump_state(self, reason: str = "Dump_state") -> Optional[str]:
        """Debug verb: write this rank's full per-subsystem forensics
        dump (``stall-rank<N>.json`` under metrics_dir) and — in
        process mode — request the same from every member of this
        communicator over the forensics system plane. Works with the
        stall sentinel disabled (``forensics_enable`` gates only the
        automatic machinery); returns the local dump path, or None if
        the dump could not be written."""
        from ompi_tpu.runtime import forensics as _fx

        path = _fx.dump(reason=reason)
        pml = getattr(self, "pml", None)
        if pml is not None and self.size > 1:
            _fx.request_peer_dumps(pml, list(self.group.ranks), reason)
        return path

    def Set_attr(self, keyval: int, value: Any) -> None:
        # replacing a value fires the delete callback on the old one
        # (MPI_Comm_set_attr contract — the callback releases resources)
        if keyval in self.attributes:
            self.Delete_attr(keyval)
        self.attributes[keyval] = value

    def Get_attr(self, keyval: int) -> Any:
        return self.attributes.get(keyval)

    def Delete_attr(self, keyval: int) -> None:
        value = self.attributes.pop(keyval, _ATTR_UNSET)
        if value is _ATTR_UNSET:
            return
        kv = _keyvals.get(keyval)
        if kv is not None and kv.delete_fn is not None:
            kv.delete_fn(self, keyval, value)

    # MPI keyvals with copy/delete callbacks (reference: ompi/attribute,
    # 2,361 LoC — MPI_Comm_create_keyval / attr copy on MPI_Comm_dup).
    @staticmethod
    def Create_keyval(copy_fn=None, delete_fn=None) -> int:
        """copy_fn(comm, keyval, value) -> (keep: bool, new_value) runs
        at Dup; None = MPI_COMM_NULL_COPY_FN (attribute not inherited).
        delete_fn(comm, keyval, value) runs at Delete_attr/Free."""
        kvid = _next_keyval[0]
        _next_keyval[0] += 1
        _keyvals[kvid] = _Keyval(copy_fn, delete_fn)
        return kvid

    @staticmethod
    def Free_keyval(keyval: int) -> None:
        _keyvals.pop(keyval, None)

    def _copy_attrs_to(self, new: "Communicator") -> None:
        """Attribute inheritance at Dup (reference: ompi_attr_copy_all)."""
        for kvid, value in list(self.attributes.items()):
            kv = _keyvals.get(kvid)
            if kv is None or kv.copy_fn is None:
                continue  # NULL_COPY_FN: not inherited
            keep, newval = kv.copy_fn(self, kvid, value)
            if keep:
                new.attributes[kvid] = newval

    def _delete_all_attrs(self) -> None:
        for kvid in list(self.attributes):
            self.Delete_attr(kvid)

    def _check_usable(self) -> None:
        if self.revoked:
            raise MPIError(ERR_REVOKED, self.name)

    def _propagate_session(self, new) -> None:
        """Comms derived from a session-derived comm stay tracked by the
        session (MPI-4 11.2.2 liveness at Session.Finalize is
        transitive)."""
        sref = getattr(self, "_session", None)
        if sref is not None:
            s = sref()
            if s is not None and not s._finalized:
                s.track(new)


    # --------------------------------------------- topology (shared core)
    # Reference: ompi/mca/topo base accessors; the rank-specific pieces
    # (Get_coords/Shift/Sub) live on the concrete comm kinds.
    def Get_topology(self) -> int:
        return self.topo.kind if self.topo is not None else UNDEFINED

    def _cart(self):
        from ompi_tpu.topo import CartTopo

        if not isinstance(self.topo, CartTopo):
            from ompi_tpu.core.errors import ERR_TOPOLOGY

            raise MPIError(ERR_TOPOLOGY, "communicator has no cartesian "
                                         "topology")
        return self.topo

    def Get_dim(self) -> int:
        return self._cart().ndims

    def Get_cart_rank(self, coords) -> int:
        return self._cart().rank(coords)

    def _check_root(self, root: int) -> None:
        if not 0 <= root < self.size:
            raise MPIError(ERR_RANK, f"root {root} out of range")


class Intracomm(Communicator):
    def Agree(self, flag: int) -> int:
        """MPIX_Comm_agree — lives on the base so both comm kinds serve
        it: ProcComm runs the ERA engine, mesh comms (no pml) reduce to
        a BAND allreduce under the single controller."""
        from ompi_tpu.ft.agreement import agree

        return agree(self, flag)


class ProcComm(Intracomm):
    """Process-mode communicator: this process is rank ``self.rank``."""

    def __init__(self, group: Group, cid: int, pml, name: str = ""):
        super().__init__(group, cid, name)
        self.pml = pml
        self.rank = group.rank_of(pml.my_rank)
        # frozen dispatch plans (coll/hier/plan.py): verb -> CollPlan,
        # rebuilt on global-epoch misses, cleared at Free
        self._plans: Dict[str, Any] = {}
        from ompi_tpu.coll.base import select_coll

        self.coll = select_coll(self)
        _live_comms[cid] = self

    def Get_rank(self) -> int:
        return self.rank

    def _world_rank(self, comm_rank: int) -> int:
        return self.group.world_rank(comm_rank)

    # --------------------------------------------------------------- pt2pt
    def Isend(self, buf, dest: int, tag: int = 0) -> Request:
        self._check_usable()
        if dest == PROC_NULL:
            from ompi_tpu.core.request import CompletedRequest

            return CompletedRequest()
        obj, count, dt = parse_buffer(buf)
        wdest = self._world_rank(dest)
        spc.record_bytes("send", count * dt.size)
        if peruse.enabled:
            peruse.fire("send_posted", comm=self, dest=dest, tag=tag,
                        nbytes=count * dt.size)
        req = self.pml.isend(obj, count, dt, wdest, tag, self.cid)
        if peruse.enabled:
            req.add_completion_callback(
                lambda r: peruse.fire("request_complete", request=r))
        return req

    def Irecv(self, buf, source: int = ANY_SOURCE,
              tag: int = ANY_TAG) -> Request:
        self._check_usable()
        if source == PROC_NULL:
            from ompi_tpu.core.request import CompletedRequest

            r = CompletedRequest()
            r.status.source = PROC_NULL
            r.status.tag = ANY_TAG
            return r
        obj, count, dt = parse_buffer(buf)
        wsrc = source if source == ANY_SOURCE else self._world_rank(source)
        if peruse.enabled:
            peruse.fire("recv_posted", comm=self, source=source, tag=tag)
        req = self.pml.irecv(obj, count, dt, wsrc, tag, self.cid)
        # report comm-rank, not world-rank, in the status
        req.add_completion_callback(self._fix_status_source)
        if peruse.enabled:
            req.add_completion_callback(
                lambda r: peruse.fire("request_complete", request=r))
        return req

    def _fix_status_source(self, req) -> None:
        if req.status.source >= 0:
            req.status.source = self.group.rank_of(req.status.source)
        spc.record_bytes("recv", req.status._nbytes)

    def Send(self, buf, dest: int, tag: int = 0) -> None:
        self.Isend(buf, dest, tag).Wait()

    def Recv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG,
             status: Optional[Status] = None) -> None:
        self.Irecv(buf, source, tag).Wait(status)

    def Sendrecv(self, sendbuf, dest: int, sendtag: int, recvbuf,
                 source: int = ANY_SOURCE, recvtag: int = ANY_TAG,
                 status: Optional[Status] = None) -> None:
        rreq = self.Irecv(recvbuf, source, recvtag)
        sreq = self.Isend(sendbuf, dest, sendtag)
        sreq.Wait()
        rreq.Wait(status)

    def Probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
              status: Optional[Status] = None) -> None:
        from ompi_tpu.runtime.progress import progress_until

        progress_until(lambda: self.Iprobe(source, tag, status))

    def Iprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
               status: Optional[Status] = None) -> bool:
        self._check_usable()
        wsrc = source if source == ANY_SOURCE else self._world_rank(source)
        st = Status() if status is None else status
        ok = self.pml.iprobe(wsrc, tag, self.cid, st)
        if ok and st.source >= 0:
            st.source = self.group.rank_of(st.source)
        return ok

    def Mprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
               status: Optional[Status] = None):
        from ompi_tpu.runtime.progress import progress_until

        wsrc = source if source == ANY_SOURCE else self._world_rank(source)
        holder = [None]

        def claimed() -> bool:
            holder[0] = self.pml.improbe(wsrc, tag, self.cid, status)
            return holder[0] is not None

        progress_until(claimed)
        if status is not None and status.source >= 0:
            status.source = self.group.rank_of(status.source)
        return holder[0]

    def Mrecv(self, buf, message, status: Optional[Status] = None) -> None:
        obj, count, dt = parse_buffer(buf)
        if peruse.enabled:
            peruse.fire("recv_posted", comm=self, source=ANY_SOURCE,
                        tag=ANY_TAG)
        req = self.pml.mrecv(obj, count, dt, message)
        req.add_completion_callback(self._fix_status_source)
        if peruse.enabled:
            req.add_completion_callback(
                lambda r: peruse.fire("request_complete", request=r))
        req.Wait(status)

    def Send_init(self, buf, dest: int, tag: int = 0):
        from ompi_tpu.core.request import Prequest

        def start(preq):
            inner = self.Isend(buf, dest, tag)

            def done(r):
                preq.status = r.status
                preq._set_complete(r._error)

            inner.add_completion_callback(done)

        return Prequest(start)

    def Recv_init(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        from ompi_tpu.core.request import Prequest

        def start(preq):
            inner = self.Irecv(buf, source, tag)

            def done(r):
                preq.status = r.status
                preq._set_complete(r._error)

            inner.add_completion_callback(done)

        return Prequest(start)

    # ---------------------------------------------------------- collectives
    def _coll(self, op: str):
        # Frozen-plan dispatch (coll/hier/plan.py): the SPC record,
        # metrics entry stamp, sanitizer interposition, and trace span
        # are pre-bound into plan.fn at first dispatch, so the steady
        # state is ONE dict hit + an epoch compare (the 20-50us
        # per-verb layer tax re-did all of it per call). Stale-config
        # hazards are handled by invalidation: cvar watchers bump the
        # global epoch, Free clears the comm's plans, and revocation is
        # checked inside the frozen prologue.
        plan = self._plans.get(op)
        if plan is not None and plan.epoch == _cplan._EPOCH[0]:
            _hier._plan_hits[0] += 1
            return plan.fn
        plan = _cplan.build(self, op)
        self._plans[op] = plan
        return plan.fn

    def Barrier(self) -> None:
        self._coll("barrier")(self)

    def Bcast(self, buf, root: int = 0) -> None:
        self._check_root(root)
        self._coll("bcast")(self, buf, root)

    def Reduce(self, sendbuf, recvbuf, op: _op.Op = _op.SUM,
               root: int = 0) -> None:
        self._check_root(root)
        self._coll("reduce")(self, sendbuf, recvbuf, op, root)

    def Allreduce(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> None:
        self._coll("allreduce")(self, sendbuf, recvbuf, op)

    def Allgather(self, sendbuf, recvbuf) -> None:
        self._coll("allgather")(self, sendbuf, recvbuf)

    def Allgatherv(self, sendbuf, recvbuf, counts, displs=None) -> None:
        self._coll("allgatherv")(self, sendbuf, recvbuf, counts, displs)

    def Gather(self, sendbuf, recvbuf, root: int = 0) -> None:
        self._check_root(root)
        self._coll("gather")(self, sendbuf, recvbuf, root)

    def Gatherv(self, sendbuf, recvbuf, counts, displs=None,
                root: int = 0) -> None:
        self._check_root(root)
        self._coll("gatherv")(self, sendbuf, recvbuf, counts, displs, root)

    def Scatter(self, sendbuf, recvbuf, root: int = 0) -> None:
        self._check_root(root)
        self._coll("scatter")(self, sendbuf, recvbuf, root)

    def Scatterv(self, sendbuf, recvbuf, counts, displs=None,
                 root: int = 0) -> None:
        self._check_root(root)
        self._coll("scatterv")(self, sendbuf, recvbuf, counts, displs, root)

    def Alltoall(self, sendbuf, recvbuf) -> None:
        self._coll("alltoall")(self, sendbuf, recvbuf)

    def Alltoallv(self, sendbuf, recvbuf, sendcounts, sdispls,
                  recvcounts, rdispls) -> None:
        self._coll("alltoallv")(self, sendbuf, recvbuf, sendcounts, sdispls,
                                recvcounts, rdispls)

    def Alltoallw(self, sendbuf, recvbuf, sendcounts, sdispls, sendtypes,
                  recvcounts, rdispls, recvtypes) -> None:
        """Fully-general exchange: per-peer counts, BYTE displacements,
        and datatypes (MPI_Alltoallw)."""
        self._coll("alltoallw")(self, sendbuf, recvbuf, sendcounts,
                                sdispls, sendtypes, recvcounts, rdispls,
                                recvtypes)

    def Reduce_scatter(self, sendbuf, recvbuf, recvcounts,
                       op: _op.Op = _op.SUM) -> None:
        self._coll("reduce_scatter")(self, sendbuf, recvbuf, recvcounts, op)

    def Reduce_scatter_block(self, sendbuf, recvbuf,
                             op: _op.Op = _op.SUM) -> None:
        self._coll("reduce_scatter_block")(self, sendbuf, recvbuf, op)

    def Scan(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> None:
        self._coll("scan")(self, sendbuf, recvbuf, op)

    def Exscan(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> None:
        self._coll("exscan")(self, sendbuf, recvbuf, op)

    # ------------------------------------------------ nonblocking collectives
    # Reference: the MPI_I* surface (coll/libnbc); every verb returns a
    # Request progressed by the engine — overlap communication with compute.
    def Ibarrier(self) -> Request:
        return self._coll("ibarrier")(self)

    def Ibcast(self, buf, root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("ibcast")(self, buf, root)

    def Ireduce(self, sendbuf, recvbuf, op: _op.Op = _op.SUM,
                root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("ireduce")(self, sendbuf, recvbuf, op, root)

    def Iallreduce(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> Request:
        return self._coll("iallreduce")(self, sendbuf, recvbuf, op)

    def Iallgather(self, sendbuf, recvbuf) -> Request:
        return self._coll("iallgather")(self, sendbuf, recvbuf)

    def Iallgatherv(self, sendbuf, recvbuf, counts, displs=None) -> Request:
        return self._coll("iallgatherv")(self, sendbuf, recvbuf, counts,
                                         displs)

    def Ialltoall(self, sendbuf, recvbuf) -> Request:
        return self._coll("ialltoall")(self, sendbuf, recvbuf)

    def Ialltoallv(self, sendbuf, recvbuf, sendcounts, sdispls,
                   recvcounts, rdispls) -> Request:
        return self._coll("ialltoallv")(self, sendbuf, recvbuf, sendcounts,
                                        sdispls, recvcounts, rdispls)

    def Igatherv(self, sendbuf, recvbuf, counts, displs=None,
                 root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("igatherv")(self, sendbuf, recvbuf, counts,
                                      displs, root)

    def Iscatterv(self, sendbuf, recvbuf, counts, displs=None,
                  root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("iscatterv")(self, sendbuf, recvbuf, counts,
                                       displs, root)

    def Igather(self, sendbuf, recvbuf, root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("igather")(self, sendbuf, recvbuf, root)

    def Iscatter(self, sendbuf, recvbuf, root: int = 0) -> Request:
        self._check_root(root)
        return self._coll("iscatter")(self, sendbuf, recvbuf, root)

    def Ireduce_scatter_block(self, sendbuf, recvbuf,
                              op: _op.Op = _op.SUM) -> Request:
        return self._coll("ireduce_scatter_block")(self, sendbuf, recvbuf, op)

    def Iscan(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> Request:
        return self._coll("iscan")(self, sendbuf, recvbuf, op)

    def Iexscan(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> Request:
        return self._coll("iexscan")(self, sendbuf, recvbuf, op)

    # ------------------------------------------- persistent collectives
    # MPI-4's third of the coll triple surface (reference:
    # ompi/mca/coll/coll.h:545-620 *_init slots). Each init fixes the
    # buffers/op/root, compiles the ENTIRE lowering into a frozen
    # replayable plan (coll/persist.py: provider + algorithm decision,
    # pre-built round schedule, pre-pinned views, pre-acquired pool
    # blocks), and returns an inactive persistent request; every Start
    # replays that schedule against the *current* buffer contents. With
    # coll_persist_enable=0 — or for shapes the compiler declines —
    # Start re-issues the nonblocking schedule per activation (the
    # pre-PR-11 path, kept verbatim as the A/B baseline).
    def _pcoll(self, slot: str, *args) -> Request:
        from ompi_tpu.coll.sched import PersistentCollRequest
        from ompi_tpu.coll import persist as _persist

        self._check_usable()
        issue = self.coll.get(slot)
        box = [_persist.compile_plan(self, slot, args)
               if _persist.enabled() else None]

        def start_issue():
            if self.coll is None:  # freed comms must not replay
                raise MPIError(ERR_COMM,
                               "persistent Start on a freed communicator")
            self._check_usable()  # a revoked comm must fail at Start too
            spc.record(slot)      # each Start is one collective invocation
            if _metrics._enable_var._value:  # each Start enters the comm
                _metrics.on_coll_entry(self, slot)
            if _san._enable_var._value:  # every Start is one ordered call
                _san.on_collective(self, slot,
                                   _san._signature(slot, args))
            if _persist.enabled():
                plan = box[0]
                if plan is None or not _persist.valid(self, plan):
                    if plan is not None:
                        plan.retire()  # recycle an invalidated plan's blocks
                    plan = box[0] = _persist.compile_plan(self, slot, args)
                if plan.steps is not None:
                    return _persist.start(self, plan)
            return issue(self, *args)

        req = PersistentCollRequest(
            start_issue, name=f"persistent {slot[1:]} on {self.name}")
        req._persist_box = box  # Request_free retires the frozen plan
        return req

    def Barrier_init(self) -> Request:
        return self._pcoll("ibarrier")

    def Bcast_init(self, buf, root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("ibcast", buf, root)

    def Reduce_init(self, sendbuf, recvbuf, op: _op.Op = _op.SUM,
                    root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("ireduce", sendbuf, recvbuf, op, root)

    def Allreduce_init(self, sendbuf, recvbuf,
                       op: _op.Op = _op.SUM) -> Request:
        return self._pcoll("iallreduce", sendbuf, recvbuf, op)

    def Allgather_init(self, sendbuf, recvbuf) -> Request:
        return self._pcoll("iallgather", sendbuf, recvbuf)

    def Allgatherv_init(self, sendbuf, recvbuf, counts,
                        displs=None) -> Request:
        return self._pcoll("iallgatherv", sendbuf, recvbuf, counts, displs)

    def Alltoall_init(self, sendbuf, recvbuf) -> Request:
        return self._pcoll("ialltoall", sendbuf, recvbuf)

    def Alltoallv_init(self, sendbuf, recvbuf, sendcounts, sdispls,
                       recvcounts, rdispls) -> Request:
        return self._pcoll("ialltoallv", sendbuf, recvbuf, sendcounts,
                           sdispls, recvcounts, rdispls)

    def Gather_init(self, sendbuf, recvbuf, root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("igather", sendbuf, recvbuf, root)

    def Gatherv_init(self, sendbuf, recvbuf, counts, displs=None,
                     root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("igatherv", sendbuf, recvbuf, counts, displs,
                           root)

    def Scatter_init(self, sendbuf, recvbuf, root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("iscatter", sendbuf, recvbuf, root)

    def Scatterv_init(self, sendbuf, recvbuf, counts, displs=None,
                      root: int = 0) -> Request:
        self._check_root(root)
        return self._pcoll("iscatterv", sendbuf, recvbuf, counts, displs,
                           root)

    def Reduce_scatter_block_init(self, sendbuf, recvbuf,
                                  op: _op.Op = _op.SUM) -> Request:
        return self._pcoll("ireduce_scatter_block", sendbuf, recvbuf, op)

    def Scan_init(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> Request:
        return self._pcoll("iscan", sendbuf, recvbuf, op)

    def Exscan_init(self, sendbuf, recvbuf, op: _op.Op = _op.SUM) -> Request:
        return self._pcoll("iexscan", sendbuf, recvbuf, op)

    # ------------------------------------------------------ comm management
    def _alloc_cid(self) -> int:
        """Agree on a fresh CID: MAX-allreduce of the local next-free id
        (reference: the comm_cid.c distributed agreement)."""
        local = np.array([_next_local_cid()], dtype=np.int64)
        agreed = np.zeros(1, dtype=np.int64)
        with spc.suppressed():
            self.Allreduce(local, agreed, op=_op.MAX)
        _bump_local_cid(int(agreed[0]))
        return int(agreed[0])

    def Dup(self) -> "ProcComm":
        cid = self._alloc_cid()
        new = ProcComm(self.group, cid, self.pml, name=f"{self.name}-dup")
        self._copy_attrs_to(new)
        self._propagate_session(new)
        return new

    def Split(self, color: int, key: int = 0) -> Optional["ProcComm"]:
        """MPI_Comm_split: allgather (color, key), then local group math."""
        mine = np.array([color, key, self.rank], dtype=np.int64)
        allv = np.zeros(3 * self.size, dtype=np.int64)
        with spc.suppressed():
            self.Allgather(mine, allv)
        cid = self._alloc_cid()
        if color == UNDEFINED:
            return None
        triples = allv.reshape(self.size, 3)
        members = [t for t in triples if t[0] == color]
        members.sort(key=lambda t: (int(t[1]), int(t[2])))
        ranks = [self.group.world_rank(int(t[2])) for t in members]
        new = ProcComm(Group(ranks), cid, self.pml,
                       name=f"{self.name}-split{color}")
        self._propagate_session(new)
        return new

    def Create_group(self, group: Group, tag: int = 0) -> Optional["ProcComm"]:
        cid = self._alloc_cid()
        if group.rank_of(self.pml.my_rank) < 0:
            return None
        new = ProcComm(group, cid, self.pml, name=f"{self.name}-sub")
        self._propagate_session(new)
        return new

    def Create(self, group: Group) -> Optional["ProcComm"]:
        return self.Create_group(group)

    def Free(self) -> None:
        self._delete_all_attrs()
        # reclaim the straggler plane's per-comm state (call index,
        # tracker rows/latches, skew EWMAs) — unconditionally: a tool
        # may have enabled metrics for a window and flipped it back off,
        # and state recorded during the window must not outlive the comm.
        # The sweep also runs registered forget hooks (coll/hier's
        # decide-state reclaim rides it).
        _metrics._forget_cid(self.cid)
        self._plans.clear()  # frozen dispatch plans die with the comm  # mpiracer: disable=cross-thread-race — Free() is an app-thread verb on a comm with no outstanding traffic; plan slots are GIL-atomic dict entries
        if getattr(self, "_persist_live", None):
            # persistent plans pin pool blocks for the request lifetime;
            # a freed comm returns them (or discards an active plan's —
            # an in-flight drain may still land in its views)
            from ompi_tpu.coll import persist as _persist

            _persist.release_comm(self)
        self.coll = None
        self._freed = True

    # ------------------------------------------------------------ topology
    # Reference: ompi/mca/topo + the MPI cart/graph surface
    # (topo_base_cart_*.c); constructors return a NEW communicator
    # carrying the topology, like MPI_Cart_create.
    def Create_cart(self, dims, periods=None, reorder=False):
        from ompi_tpu.topo import cart_create_proc

        return cart_create_proc(self, dims, periods, reorder)

    def Create_graph(self, index, edges, reorder=False):
        from ompi_tpu.topo import graph_create_proc

        return graph_create_proc(self, index, edges, reorder)

    def Create_dist_graph_adjacent(self, sources, destinations,
                                   reorder=False):
        from ompi_tpu.topo import dist_graph_adjacent_proc

        return dist_graph_adjacent_proc(self, sources, destinations, reorder)

    def Get_topo(self):
        t = self._cart()
        return t.dims, t.periods, t.coords(self.rank)

    def Get_coords(self, rank: Optional[int] = None):
        return self._cart().coords(self.rank if rank is None else rank)

    def Shift(self, direction: int, disp: int = 1) -> Tuple[int, int]:
        """(source, dest) of a cart shift for THIS rank (MPI_Cart_shift)."""
        return self._cart().shift(self.rank, direction, disp)

    def Sub(self, remain_dims):
        """MPI_Cart_sub: split into sub-cart comms over the kept dims."""
        from ompi_tpu.topo import attach_sub_cart

        t = self._cart()
        colors, keys = t.sub_colors(remain_dims)
        sub = self.Split(colors[self.rank], keys[self.rank])
        if sub is not None:
            attach_sub_cart(sub, t, remain_dims)
        return sub

    def Get_neighbors(self, rank: Optional[int] = None):
        from ompi_tpu.topo import in_out_neighbors

        srcs, _ = in_out_neighbors(
            self.topo, self.rank if rank is None else rank)
        return srcs

    def Neighbor_allgather(self, sendbuf, recvbuf) -> None:
        self._coll("neighbor_allgather")(self, sendbuf, recvbuf)

    def Neighbor_alltoall(self, sendbuf, recvbuf) -> None:
        self._coll("neighbor_alltoall")(self, sendbuf, recvbuf)

    # -------------------------------------------------- dynamic processes
    def Spawn(self, command: str, args=(), maxprocs: int = 1,
              root: int = 0, info=None):
        """MPI_Comm_spawn: launch a child job, return the intercomm to it
        (reference: ompi/dpm/dpm.c)."""
        from ompi_tpu.runtime.dpm import spawn

        return spawn(self, command, args, maxprocs, root, info)

    def Create_intercomm(self, local_leader: int, peer_comm,
                         remote_leader: int, tag: int = 0):
        """MPI_Intercomm_create (reference: comm.c:1655)."""
        from ompi_tpu.comm.intercomm import Intercomm_create

        return Intercomm_create(self, local_leader, peer_comm,
                                remote_leader, tag)

    def Is_inter(self) -> bool:
        return False

    def Abort(self, errorcode: int = 1) -> None:
        """MPI_Abort: terminate the whole job now (reference:
        ompi_mpi_abort). ``os._exit`` never runs atexit, so everything
        the clean-exit hooks would have exported — the trace flight
        recorder, the metrics snapshot, a forensics dump when the
        plane is armed — is flushed HERE first, through the same
        atomic-rename writers; an MPIError escaping to Abort no longer
        loses the entire ring. This function does not return."""
        import os as _os

        from ompi_tpu.utils.output import get_logger

        get_logger("comm").error("MPI_Abort(%s) on %s", errorcode,
                                 self.name)
        _trace.export_on_fatal()
        try:
            if _metrics._enable_var._value:
                _metrics.export_json()
        except Exception:
            pass
        try:
            from ompi_tpu.runtime import forensics as _fx

            if _fx._enable_var._value:
                _fx.dump(reason=f"MPI_Abort({errorcode})")
        except Exception:
            pass
        try:
            from ompi_tpu.runtime import wireup as _wireup

            ctx = _wireup._ctx
            if ctx is not None:
                ctx["modex"].abort(
                    f"MPI_Abort({errorcode}) on {self.name}")
        except Exception:
            pass
        _os._exit(errorcode if errorcode else 1)

    # ULFM surface (reference: ompi/mpiext/ftmpi MPIX_Comm_*)
    def Revoke(self) -> None:
        from ompi_tpu.ft.revoke import revoke_comm

        revoke_comm(self)

    def Shrink(self) -> "ProcComm":
        from ompi_tpu.ft.revoke import shrink_comm

        return shrink_comm(self)


# Live communicator registry: cid -> comm, used by the ULFM revoke handler
# to flip remote-revocation state (reference: the framework-wide comm table
# ompi_comm_lookup uses for the same purpose).
import weakref

_live_comms: "weakref.WeakValueDictionary[int, ProcComm]" = (
    weakref.WeakValueDictionary()
)


def lookup_comm(cid: int) -> Optional[ProcComm]:
    return _live_comms.get(cid)


# Local CID counter (the per-process component of the CID agreement).
_cid_lock = threading.Lock()
_cid_next = 10


def _next_local_cid() -> int:
    with _cid_lock:
        return _cid_next


def _bump_local_cid(used: int) -> None:
    global _cid_next
    with _cid_lock:
        _cid_next = max(_cid_next, used) + 1
