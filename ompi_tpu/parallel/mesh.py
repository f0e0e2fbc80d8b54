"""Mesh-mode communicators: MPI_COMM_WORLD projected onto a jax.Mesh.

The TPU-native execution model (BASELINE.json north star): the single
controller owns a 1-D device mesh; MPI ranks are mesh positions; a
"distributed buffer" is a global jax.Array whose leading dim is the rank
dim, sharded over the mesh axis. Sub-communicators (Split / Create_group)
become ``axis_index_groups`` partitions, so *every* sub-communicator
collective is still one XLA collective over ICI — the communicator↔mesh
projection SURVEY.md §7 ranks as hard part 2.

Reference analogs: ompi/communicator/comm.c (split/dup/group math) with the
CID agreement replaced by driver-local allocation (single controller ⇒ no
distributed agreement needed — the reference needs comm_cid.c:61-109 only
because every rank allocates independently).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ompi_tpu.comm.communicator import Intracomm
from ompi_tpu.core import op as _op
from ompi_tpu.core.errors import (
    MPIError,
    ERR_ARG,
    ERR_RANK,
    ERR_UNSUPPORTED_OPERATION,
)
from ompi_tpu.core.group import Group
from ompi_tpu.runtime import spc
from ompi_tpu.runtime import trace as _tr

UNDEFINED = -32766

_next_mesh_cid = [100]


class XlaComm(Intracomm):
    """A communicator (or a color-family of communicators) on a device mesh.

    ``groups`` is None for the world comm, else a partition of all mesh
    positions; collectives act within each group independently — after a
    Split the one XlaComm object *is* every color's communicator, observed
    from the driver.
    """

    def __init__(self, mesh, axis: str, groups: Optional[Tuple[Tuple[int, ...], ...]] = None,
                 name: str = ""):
        self.mesh = mesh
        self.axis = axis
        self.world_size = int(mesh.shape[axis])
        if groups is not None:
            groups = tuple(tuple(int(r) for r in g) for g in groups)
            flat = sorted(r for g in groups for r in g)
            if flat != list(range(self.world_size)):
                raise MPIError(
                    ERR_ARG,
                    "groups must partition all mesh positions "
                    "(pad non-members as singleton groups)",
                )
        self.groups = groups
        # pos_map[global mesh position] = rank within its group;
        # singleton_mask marks padding groups excluded from schedules.
        pos = np.zeros(self.world_size, dtype=np.int32)
        single = np.zeros(self.world_size, dtype=bool)
        if groups is not None:
            for g in groups:
                for p, r in enumerate(g):
                    pos[r] = p
                    single[r] = len(g) == 1
        else:
            pos = np.arange(self.world_size, dtype=np.int32)
        self.pos_map = pos
        self.singleton_mask = single
        cid = _next_mesh_cid[0]
        _next_mesh_cid[0] += 1
        super().__init__(Group(range(self.world_size)), cid,
                         name or f"mesh-comm-{cid}")
        self._jit_cache = {}
        # (verb, args...) -> compiled-executable thunk: the per-comm
        # resolved fn table (reference: the comm->c_coll pointer chase of
        # ompi/mpi/c/allreduce.c.in:115, resolved once per verb+args).
        # Populated by each verb's first (slow) call; a hot call is ONE
        # dict hit + the dispatch. Fast paths skip argument validation —
        # the first call through the slow path did it.
        self._fast = {}
        from ompi_tpu.coll.base import select_coll
        from ompi_tpu.coll.xla import stats as _xla_stats

        # compile-cache telemetry: fast-table dispatches count as cache
        # hits (coll_xla_cache_hits pvar); misses/build time come from
        # XlaColl._cached
        self._cstats = _xla_stats
        self.coll = select_coll(self)

    # ------------------------------------------------------------- queries
    @property
    def size(self) -> int:
        """Group size: uniform across non-singleton colors (singletons are
        padding); raises if real colors differ in size."""
        if self.groups is None:
            return self.world_size
        sizes = {len(g) for g in self.groups if len(g) > 1}
        if not sizes:
            return 1
        if len(sizes) != 1:
            raise MPIError(
                ERR_UNSUPPORTED_OPERATION,
                "non-uniform color sizes: split into uniform colors or "
                "query per-color via .groups",
            )
        return next(iter(sizes))

    def Get_rank(self):
        raise MPIError(
            ERR_UNSUPPORTED_OPERATION,
            "mesh-mode driver holds all ranks; use jax.lax.axis_index "
            f"('{self.axis}') inside shard_map, or process mode for "
            "per-rank control flow",
        )

    def _require_uniform_groups(self, what: str) -> None:
        _ = self.size  # raises when non-uniform

    def _check_root(self, root: int) -> None:
        # root bounds must not force uniform sizes (rooted ops on
        # non-uniform splits are fine: the root is a group-local
        # position; groups smaller than root+1 have no such member and
        # their rows are unspecified, matching singleton-padding rules)
        if self.groups is None:
            limit = self.world_size
        else:
            limit = max((len(g) for g in self.groups), default=1)
        if not 0 <= root < limit:
            raise MPIError(ERR_RANK, f"root {root} out of range")

    # ------------------------------------------------------------ sharding
    def sharding(self, *rest_spec):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis, *rest_spec))

    def shard(self, x):
        """Place a [world, ...] array with the rank dim over the mesh."""
        import jax

        return jax.device_put(x, self.sharding())

    # ------------------------------------------- functional collectives
    def _slot(self, name: str):
        self._check_usable()
        spc.record(name)  # allreduce records in its own fast path instead
        return self._verb_fn(name)

    def _verb_fn(self, name: str):
        """Slot lookup, wrapped in the comm.<verb> span when tracing
        (the slow path; fast-table dispatches span through _hot)."""
        fn = self.coll.get(name)
        if _tr.enabled():
            return _tr.wrap_span("comm." + name, "comm", fn)
        return fn

    def _hot(self, verb: str, fn, *args):
        """Shared fast-path epilogue: SPC bump + compile-cache-hit
        count + the executable's call, all inside the comm.<verb> span
        (one branch when tracing is off — the dispatch-tax budget of the
        resolved table). Only the verb's fast-table lookup precedes it."""
        if _tr.enabled():
            with _tr.span("comm." + verb, cat="comm"):
                spc.record(verb)
                self._cstats.hits += 1
                return fn(*args)
        spc.record(verb)
        self._cstats.hits += 1
        return fn(*args)

    def _promote(self, fast_key, exec_key, wrap=None):
        """After a slow call, resolve the compiled executable into the
        fast table (no-op when a non-xla coll module owns the verb and
        didn't populate the shared _jit_cache layout)."""
        fn = self._jit_cache.get(exec_key)
        if fn is not None:
            self._fast[fast_key] = wrap(fn) if wrap is not None else fn

    def allreduce(self, x, op: _op.Op = _op.SUM):
        # hot path: ONE dict hit to the compiled executable — the r2
        # bench showed the 32KB point paying ~9us of Python prologue per
        # call, so everything else (usability check, tuple key build,
        # module imports) lives on the miss path
        fn = self._fast.get(("allreduce", op.uid))
        if fn is not None and not self.revoked:
            if op.is_pair:
                from ompi_tpu.coll.xla import _check_device_op

                _check_device_op(op, x)
            return self._hot("allreduce", fn, x)
        return self._allreduce_slow(x, op)

    def _allreduce_slow(self, x, op: _op.Op):
        self._check_usable()
        from ompi_tpu.coll.xla import cache_key, _check_device_op

        spc.record("allreduce")
        if op.name in _op.PAIR_OPS:
            # the cached executable retraces per shape, so the pair-layout
            # contract must hold on every call, not just the first
            _check_device_op(op, x)
        out = self._verb_fn("allreduce")(self, x, op)
        # a quant-negotiated comm caches its executable under a
        # discriminated key (coll/quant.py) so it can't collide with the
        # plain body XlaColl.reduce shares; prefer it when present
        qkey = cache_key("allreduce", op, extra=("quant",))
        if qkey in self._jit_cache:
            self._promote(("allreduce", op.uid), qkey)
        else:
            self._promote(("allreduce", op.uid), cache_key("allreduce", op))
        return out

    def reduce(self, x, op: _op.Op = _op.SUM, root: int = 0):
        # the mesh schedule computes the reduction on every group row, so
        # XlaColl.reduce shares allreduce's executable — but the fast key
        # is reduce's own, populated only by reduce's slow path (another
        # coll module may implement reduce differently)
        fn = self._fast.get(("reduce", op.uid, root))
        if fn is not None and not self.revoked:
            if op.is_pair:
                from ompi_tpu.coll.xla import _check_device_op

                _check_device_op(op, x)
            return self._hot("reduce", fn, x)
        self._check_usable()
        self._check_root(root)
        from ompi_tpu.coll.xla import cache_key

        spc.record("reduce")
        out = self._verb_fn("reduce")(self, x, op, root)
        self._promote(("reduce", op.uid, root),
                      cache_key("allreduce", op))
        return out

    def bcast(self, x, root: int = 0):
        fn = self._fast.get(("bcast", root))
        if fn is not None and not self.revoked:
            return self._hot("bcast", fn, x)
        self._check_usable()
        self._check_root(root)
        from ompi_tpu.coll.xla import cache_key

        spc.record("bcast")
        out = self._verb_fn("bcast")(self, x, root)
        import jax.numpy as jnp

        r = jnp.int32(root)
        self._promote(("bcast", root), cache_key("bcast"),
                      wrap=lambda f: (lambda a, _f=f, _r=r: _f(a, _r)))
        return out

    def allgather(self, x):
        fn = self._fast.get(("allgather",))
        if fn is not None and not self.revoked:
            return self._hot("allgather", fn, x)
        self._check_usable()
        from ompi_tpu.coll.xla import cache_key

        spc.record("allgather")
        out = self._verb_fn("allgather")(self, x)
        self._promote(("allgather",), cache_key("allgather"))
        return out

    def alltoall(self, x):
        fn = self._fast.get(("alltoall",))
        if fn is not None and not self.revoked:
            return self._hot("alltoall", fn, x)
        self._check_usable()
        from ompi_tpu.coll.xla import cache_key

        spc.record("alltoall")
        out = self._verb_fn("alltoall")(self, x)
        self._promote(("alltoall",), cache_key("alltoall"))
        return out

    def reduce_scatter(self, x, op: _op.Op = _op.SUM):
        fn = self._fast.get(("reduce_scatter", op.uid))
        if fn is not None and not self.revoked:
            return self._hot("reduce_scatter_block", fn, x)
        self._check_usable()
        from ompi_tpu.coll.xla import cache_key

        spc.record("reduce_scatter_block")
        out = self._verb_fn("reduce_scatter_block")(self, x, op)
        self._promote(("reduce_scatter", op.uid),
                      cache_key("reduce_scatter_block", op))
        return out

    def scan(self, x, op: _op.Op = _op.SUM):
        fn = self._fast.get(("scan", op.uid))
        if fn is not None and not self.revoked:
            if op.is_pair:
                from ompi_tpu.coll.xla import _check_device_op

                _check_device_op(op, x)
            return self._hot("scan", fn, x)
        from ompi_tpu.coll.xla import cache_key

        out = self._slot("scan")(self, x, op)
        self._promote(("scan", op.uid), cache_key("scan", op, (False,)))
        return out

    def exscan(self, x, op: _op.Op = _op.SUM):
        fn = self._fast.get(("exscan", op.uid))
        if fn is not None and not self.revoked:
            if op.is_pair:
                from ompi_tpu.coll.xla import _check_device_op

                _check_device_op(op, x)
            return self._hot("exscan", fn, x)
        from ompi_tpu.coll.xla import cache_key

        out = self._slot("exscan")(self, x, op)
        self._promote(("exscan", op.uid), cache_key("scan", op, (True,)))
        return out

    def barrier(self) -> None:
        fn = self._fast.get(("barrier",))
        if fn is not None and not self.revoked:
            self._hot("barrier", fn)
            return
        self._slot("barrier")(self)
        from ompi_tpu.coll.xla import cache_key

        f = self._jit_cache.get(cache_key("barrier"))
        if f is not None:
            import jax.numpy as jnp

            # the tiny psum input is constant: device_put it once and
            # close over it — a fast barrier is one dict hit + dispatch
            x = self.shard(jnp.ones((self.world_size, 1), jnp.int32))
            self._fast[("barrier",)] = \
                lambda _f=f, _x=x: _f(_x).block_until_ready()

    def gather(self, x, root: int = 0):
        fn = self._fast.get(("gather", root))
        if fn is not None and not self.revoked:
            return self._hot("gather", fn, x)
        self._check_root(root)
        from ompi_tpu.coll.xla import cache_key, XlaColl

        out = self._slot("gather")(self, x, root)
        # the mesh gather is the allgather strengthening (xla.py gather)
        # — a CROSS-verb exec key, so the promote must verify the xla
        # module actually owns the gather slot (another module's gather
        # could have real root-only semantics while a prior allgather
        # call populated the allgather executable independently)
        owner = getattr(self.coll.get("gather"), "__self__", None)
        if isinstance(owner, XlaColl):
            self._promote(("gather", root), cache_key("allgather"))
        return out

    def scatter(self, x, root: int = 0):
        fn = self._fast.get(("scatter", root))
        if fn is not None and not self.revoked:
            return self._hot("scatter", fn, x)
        self._check_root(root)
        from ompi_tpu.coll.xla import cache_key

        out = self._slot("scatter")(self, x, root)
        import jax.numpy as jnp

        r = jnp.int32(root)
        G = self.size

        def wrap(f):
            def fast(a, _f=f, _r=r, _G=G):
                # the slow path's shape contract must hold on EVERY call
                # (the cached jit would retrace and silently clamp)
                if a.ndim < 2 or a.shape[1] != _G:
                    raise MPIError(
                        ERR_ARG,
                        f"scatter expects [world, group_size={_G}, ...], "
                        f"got {tuple(a.shape)}")
                return _f(a, _r)
            return fast

        self._promote(("scatter", root), cache_key("scatter"), wrap=wrap)
        return out

    # MPI-style aliases
    Allreduce = allreduce
    Bcast = bcast
    Allgather = allgather
    Alltoall = alltoall
    Barrier = barrier

    # ------------------------------------ nonblocking collectives (MPI_I*)
    # jax dispatch is already asynchronous: the jitted executable is
    # enqueued and control returns before the collective completes on
    # device. The I* variants surface that as a Request whose ``result``
    # holds the output array — Wait() blocks on device readiness
    # (reference: coll/libnbc round schedules; here the "schedule" is the
    # XLA program and ICI does the progression).
    def _ireq(self, result):
        from ompi_tpu.coll.sched import JaxRequest

        return JaxRequest(result)

    def iallreduce(self, x, op: _op.Op = _op.SUM):
        return self._ireq(self.allreduce(x, op))

    def ibcast(self, x, root: int = 0):
        return self._ireq(self.bcast(x, root))

    def ireduce(self, x, op: _op.Op = _op.SUM, root: int = 0):
        return self._ireq(self.reduce(x, op, root))

    def iallgather(self, x):
        return self._ireq(self.allgather(x))

    def ialltoall(self, x):
        return self._ireq(self.alltoall(x))

    def ireduce_scatter(self, x, op: _op.Op = _op.SUM):
        return self._ireq(self.reduce_scatter(x, op))

    def ibarrier(self):
        # the barrier collective itself is the dispatched executable; by
        # the time dispatch returns the round is enqueued on every shard
        from ompi_tpu.core.request import CompletedRequest

        self.barrier()
        return CompletedRequest()

    # ------------------------------------ persistent collectives (X_init)
    # MPI-4's third of the triple surface, TPU-native: the setup that
    # persistence amortizes is trace+compile. init runs one warm-up
    # dispatch (populating the per-comm jit cache) and PRE-FREEZES the
    # resolved fast-table executable into the request (coll/persist's
    # frozen-lowering discipline: Start skips even the fast-dict lookup
    # and the dispatch decision tree — revocation stays checked). With
    # coll_persist_donate=1, init also compiles a donated-operand
    # executable so Start(x) lets XLA reuse x's buffer for the output
    # (x is consumed). Reference: ompi/mca/coll/coll.h:545-620.
    def _pcoll_init(self, verb: str, x, *args, fast_key=None):
        from ompi_tpu.coll.sched import MeshPersistentRequest
        from ompi_tpu.coll import persist as _persist

        fn = getattr(self, verb)
        fn(x, *args)  # warm-up: trace+compile now, dispatch-only later
        frozen = None
        if fast_key is not None and _persist._enable_var._value:
            # coll_persist_enable=0 keeps the pre-PR-11 per-Start verb
            # dispatch verbatim — the same A/B contract as proc mode
            frozen = self._fast.get(fast_key)
        donate = None
        if frozen is not None:
            _persist._plans[0] += 1
            # the frozen dispatch keeps the fast-path epilogue (_hot:
            # SPC record + cache-hit count + comm.<verb> span) — a
            # persistent Start is still one collective invocation
            spc_name = ("reduce_scatter_block" if verb == "reduce_scatter"
                        else verb)
            dispatch = (lambda a, _f=frozen, _v=spc_name:
                        self._hot(_v, _f, a))
            if _persist._donate_var._value:
                import jax
                import jax.numpy as jnp

                dexec = jax.jit(frozen, donate_argnums=0)
                # warm the donated executable on a throwaway operand so
                # the first Start(x) is dispatch-only (init owns the
                # compile); the init-time x itself is never donated
                dexec(jnp.zeros_like(x))
                donate = (lambda a, _f=dexec, _v=spc_name:
                          self._hot(_v, _f, a))
        else:
            dispatch = lambda op_x: fn(op_x, *args)  # noqa: E731
        return MeshPersistentRequest(self, dispatch, x,
                                     frozen=frozen is not None,
                                     donate=donate)

    @staticmethod
    def _op_key(op: _op.Op):
        # pair ops re-validate their layout per call on the fast path;
        # a frozen executable would skip that check, so they keep the
        # legacy per-Start dispatch
        return None if op.is_pair else op.uid

    def allreduce_init(self, x, op: _op.Op = _op.SUM):
        k = self._op_key(op)
        return self._pcoll_init(
            "allreduce", x, op,
            fast_key=None if k is None else ("allreduce", k))

    def bcast_init(self, x, root: int = 0):
        return self._pcoll_init("bcast", x, root,
                                fast_key=("bcast", root))

    def reduce_init(self, x, op: _op.Op = _op.SUM, root: int = 0):
        k = self._op_key(op)
        return self._pcoll_init(
            "reduce", x, op, root,
            fast_key=None if k is None else ("reduce", k, root))

    def allgather_init(self, x):
        return self._pcoll_init("allgather", x, fast_key=("allgather",))

    def alltoall_init(self, x):
        return self._pcoll_init("alltoall", x, fast_key=("alltoall",))

    def reduce_scatter_init(self, x, op: _op.Op = _op.SUM):
        k = self._op_key(op)
        return self._pcoll_init(
            "reduce_scatter", x, op,
            fast_key=None if k is None else ("reduce_scatter", k))

    def scan_init(self, x, op: _op.Op = _op.SUM):
        k = self._op_key(op)
        return self._pcoll_init(
            "scan", x, op, fast_key=None if k is None else ("scan", k))

    def exscan_init(self, x, op: _op.Op = _op.SUM):
        k = self._op_key(op)
        return self._pcoll_init(
            "exscan", x, op,
            fast_key=None if k is None else ("exscan", k))

    Allreduce_init = allreduce_init
    Bcast_init = bcast_init
    Reduce_init = reduce_init
    Allgather_init = allgather_init
    Alltoall_init = alltoall_init
    Reduce_scatter_init = reduce_scatter_init
    Reduce_scatter_block_init = reduce_scatter_init  # ProcComm's spelling
    Scan_init = scan_init
    Exscan_init = exscan_init

    # ---------------------------------------- partitioned pt2pt (MPI-4)
    def Psend_init(self, x, perm: Sequence[Tuple[int, int]],
                   partitions: int):
        """Partitioned transfer: [W, K, ...] buffer, K split into
        ``partitions`` segments, each dispatched by Pready as its own
        segment of the ppermute schedule (reference: part.h:163; see
        parallel/partitioned.py)."""
        from ompi_tpu.parallel.partitioned import MeshPartitionedRequest

        return MeshPartitionedRequest(self, x, perm, partitions)

    # single-controller collapse: one request serves both endpoints
    Precv_init = Psend_init

    # ------------------------------------------------------------- pt2pt
    def permute(self, x, perm: Sequence[Tuple[int, int]]):
        """Tag-free pt2pt: move rank-rows along (src, dst) pairs in comm
        (group-local) ranks."""
        if self.groups is None:
            global_perm = tuple((int(s), int(d)) for s, d in perm)
        else:
            # singleton padding groups have no in-group peers to permute
            global_perm = tuple(
                (g[int(s)], g[int(d)])
                for g in self.groups
                if len(g) > 1
                for s, d in perm
            )
        fn = self._fast.get(("permute", global_perm))
        if fn is not None and not self.revoked:
            return self._hot("permute", fn, x)
        # slow path mirrors _hot's accounting (spc + span) so the FIRST
        # permute per schedule — the trace+compile one — isn't the only
        # call missing from counters and the trace
        spc.record("permute")
        slow = self._slot_permute()
        if _tr.enabled():
            slow = _tr.wrap_span("comm.permute", "comm", slow)
        out = slow(self, x, global_perm)
        from ompi_tpu.coll.xla import cache_key

        self._promote(("permute", global_perm),
                      cache_key("permute", extra=(global_perm,)))
        return out

    def _slot_permute(self):
        # permute is not one of the 17 standard slots; fetch the xla module
        # directly (host comms get pt2pt via pml instead).
        from ompi_tpu.coll.xla import XlaCollComponent

        mod = XlaCollComponent._module
        if mod is None:
            raise MPIError(ERR_UNSUPPORTED_OPERATION, "no xla coll module")
        return mod.permute

    def shift(self, x, steps: int = 1):
        """Ring shift by `steps` within each group (MPI_Sendrecv around a
        ring — the ring_c example's traffic pattern)."""
        n = self.size
        perm = tuple((i, (i + steps) % n) for i in range(n))
        return self.permute(x, perm)

    # ---------------------------------------------------------- resharding
    def reshard(self, x, src_spec, dst_spec):
        """Redistribute the canonical [W, *local] distributed buffer
        between layouts, lowered to ONE coll/xla verb (allgather /
        alltoall / local slicing) by the reshard engine — never
        allgather-then-slice (reshard/exec.py mesh_reshard; the plan
        layer is ompi_tpu/reshard/plan.py). Not a resolved-table verb:
        each call re-derives the lowering (cache the result, or use the
        underlying verbs directly, for per-step resharding loops)."""
        from ompi_tpu.reshard.exec import mesh_reshard

        return mesh_reshard(self, x, src_spec, dst_spec)

    # ------------------------------------------------------------ topology
    # Reference: ompi/mca/topo projected TPU-native — cart coordinates are
    # a row-major reshape of the mesh axis, shifts are collective-permute
    # rings riding the ICI torus (periodic dims = wraparound links).
    def Create_cart(self, dims, periods=None, reorder=False) -> "XlaComm":
        from ompi_tpu.topo import CartTopo

        topo = CartTopo(dims, periods if periods is not None
                        else [False] * len(dims))
        if self.groups is not None:
            raise MPIError(ERR_UNSUPPORTED_OPERATION,
                           "create the cart from the whole-axis comm")
        if topo.size != self.world_size:
            raise MPIError(
                ERR_ARG,
                f"mesh cart must cover the whole axis: prod(dims)="
                f"{topo.size} != {self.world_size} devices")
        new = XlaComm(self.mesh, self.axis, None,
                      name=f"{self.name}-cart")
        new.topo = topo
        from ompi_tpu.topo import _reselect_coll

        _reselect_coll(new)
        return new

    def Get_topo(self):
        """(dims, periods, None): the driver holds every rank, so there
        is no calling-process coords entry (same 3-tuple arity as the
        host path)."""
        t = self._cart()
        return t.dims, t.periods, None

    def Get_coords(self, rank: int):
        return self._cart().coords(rank)

    def cart_shift(self, x, direction: int, disp: int = 1):
        """Data-level MPI_Cart_shift: every rank-row moves `disp` steps
        along `direction`; rows shifted in from non-periodic edges are
        zero (the ppermute boundary semantics standing in for
        MPI_PROC_NULL's undefined buffer)."""
        if self.groups is not None:
            raise MPIError(ERR_UNSUPPORTED_OPERATION,
                           "cart topologies cover the whole mesh axis")
        t = self._cart()
        pairs = []
        for r in range(self.world_size):
            _, dst = t.shift(r, direction, disp)
            if dst >= 0:
                pairs.append((r, dst))
        return self.permute(x, tuple(pairs))

    def Sub(self, remain_dims) -> "XlaComm":
        """MPI_Cart_sub: one Split materializing every sub-cart color."""
        from ompi_tpu.topo import attach_sub_cart

        t = self._cart()
        colors, keys = t.sub_colors(remain_dims)
        sub = self.Split(colors, keys)
        attach_sub_cart(sub, t, remain_dims)
        return sub

    def neighbor_allgather(self, x):
        """[W, ...] -> [W, K, ...]: slot k holds the k-th cart neighbor's
        row (zeros off non-periodic edges)."""
        fn = self._fast.get(("neighbor_allgather",))
        if fn is not None and not self.revoked:
            return self._hot("neighbor_allgather", fn, x)
        from ompi_tpu.coll.xla import cache_key

        out = self._slot("neighbor_allgather")(self, x)
        self._promote(("neighbor_allgather",),
                      cache_key("neighbor_allgather"))
        return out

    def neighbor_alltoall(self, x):
        """[W, K, ...] -> [W, K, ...]: block k goes to neighbor k."""
        fn = self._fast.get(("neighbor_alltoall",))
        if fn is not None and not self.revoked:
            return self._hot("neighbor_alltoall", fn, x)
        from ompi_tpu.coll.xla import cache_key

        out = self._slot("neighbor_alltoall")(self, x)
        K = 2 * len(self._cart().dims)

        def wrap(f):
            def fast(a, _f=f, _K=K):
                # slow path's K-block contract, re-checked per call (a
                # wrong block count would retrace into garbage/IndexError)
                if a.ndim < 2 or a.shape[1] != _K:
                    raise MPIError(
                        ERR_ARG,
                        f"neighbor_alltoall expects [world, {_K}, ...], "
                        f"got {tuple(a.shape)}")
                return _f(a)
            return fast

        self._promote(("neighbor_alltoall",),
                      cache_key("neighbor_alltoall"), wrap=wrap)
        return out

    Neighbor_allgather = neighbor_allgather
    Neighbor_alltoall = neighbor_alltoall

    # ------------------------------------------------------ comm management
    def Dup(self) -> "XlaComm":
        new = XlaComm(self.mesh, self.axis, self.groups,
                      name=f"{self.name}-dup")
        self._copy_attrs_to(new)
        return new

    def Split(self, colors: Sequence[int],
              keys: Optional[Sequence[int]] = None) -> "XlaComm":
        """MPI_Comm_split, driver-level: `colors[i]` / `keys[i]` are rank
        i's arguments; all colors are materialized at once as the groups
        partition of the returned comm."""
        if len(colors) != self.world_size:
            raise MPIError(ERR_ARG, "need one color per mesh position")
        keys = list(keys) if keys is not None else [0] * self.world_size
        by_color = {}
        for r, (c, k) in enumerate(zip(colors, keys)):
            by_color.setdefault(c, []).append((k, r))
        groups: List[Tuple[int, ...]] = []
        for c, members in sorted(by_color.items(),
                                 key=lambda kv: (kv[0] == UNDEFINED, kv[0])):
            members.sort()
            if c == UNDEFINED:
                groups.extend((r,) for _, r in members)  # singleton padding
            else:
                groups.append(tuple(r for _, r in members))
        return XlaComm(self.mesh, self.axis, tuple(groups),
                       name=f"{self.name}-split")

    def Create_group(self, ranks: Sequence[int]) -> "XlaComm":
        """Sub-communicator of a rank subset; non-members are padded as
        singleton groups (their rows are unspecified after collectives)."""
        member = set(int(r) for r in ranks)
        groups = [tuple(int(r) for r in ranks)]
        groups.extend((r,) for r in range(self.world_size) if r not in member)
        return XlaComm(self.mesh, self.axis, tuple(groups),
                       name=f"{self.name}-sub")

    def Free(self) -> None:
        self._delete_all_attrs()
        self._freed = True
        self._jit_cache.clear()
        self._fast.clear()
        self.coll = None


def mesh_world(devices=None, axis_name: str = "mpi_world") -> XlaComm:
    """Build the mesh-mode MPI_COMM_WORLD over all (or given) devices."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    mesh = Mesh(np.asarray(devices), (axis_name,))
    return XlaComm(mesh, axis_name, name="MESH_COMM_WORLD")
