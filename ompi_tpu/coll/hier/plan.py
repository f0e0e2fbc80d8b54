"""Frozen collective dispatch plans — the verb-layer dispatch-tax killer.

An early host-side measurement (not on the chip) put the per-verb
layer overhead at 20-50us on top of a ~1.8us stub prologue: every
``ProcComm._coll`` re-did the slot lookup and re-tested the
metrics/sanitizer/trace live Vars, and every enabled instrumentation
layer re-built its wrapper per call. A :class:`CollPlan` freezes all
of that at FIRST dispatch: the resolved module fn, the sanitizer/trace
interposition wrappers, and the metrics entry-stamp binding are
composed once into ``plan.fn``, so the steady state in
``ProcComm._coll`` is one dict hit + an epoch compare + execute
(reference analog: comm->c_coll is resolved once at selection; this
extends the idea through the instrumentation stack).

Correctness of the freeze rests on invalidation — a stale plan would
silently drop instrumentation a user just enabled (or keep paying for
one they disabled):

- **relevant cvar write** — :func:`mca.var.watch_var` callbacks on the
  metrics/sanitizer/trace enables and the ``coll_hier_*`` knobs bump
  the global plan epoch; every live plan misses on its next dispatch
  and rebuilds against the new config.
- **comm epoch bump** — plans live on the communicator
  (``comm._plans``) and die with it (``Free`` clears); revocation is
  checked inside the frozen prologue (one attribute load), so a ULFM
  revoke needs no invalidation round.
- **decide.py re-score** — an applied plan switch pops the affected
  verb's plan on every member at the agreed collective index
  (decide.sync), so the rebuilt plan binds the newly-chosen chain.

The dtype/count-class keying of hier compositions lives one level down:
plan.fn for a hier-owned slot is the composer's dispatcher, which keys
its pre-bound stage chains on (verb, dtype, count-class) in the decide
state (compose._stage_plan) — the comm epoch and verb are this cache's
key components.
"""

from __future__ import annotations

# plane member (hier/__init__ owns the note_* hooks): mpilint
# module-scan marker for the derived INSTR_IMPL set
MPILINT_INSTR_IMPL = True

from typing import Optional

from ompi_tpu.coll import hier as _hier
from ompi_tpu.core.errors import MPIError, ERR_REVOKED
from ompi_tpu.mca.var import watch_var
from ompi_tpu.runtime import spc as _spc

# Global plan epoch: a plan is live iff plan.epoch == _EPOCH[0]. A list
# slot (not an int module global) so the communicator fast path can
# compare against the live value through one stable attribute load.
_EPOCH = [1]


def epoch() -> int:
    return _EPOCH[0]


def invalidate(_var=None) -> None:
    """Bump the global epoch: every frozen plan in the process misses on
    its next dispatch and rebuilds (watch_var callback signature)."""
    _EPOCH[0] += 1


# Config whose value is frozen into plan.fn. File/env sources resolve
# before any plan can exist; programmatic set_var must invalidate.
for _fw, _name in (("metrics", "enable"), ("sanitizer", "enable"),
                   ("trace", "enable"),
                   ("coll_hier", "enable"), ("coll_hier", "selftune"),
                   ("coll_hier", "min_bytes"),
                   ("coll_hier", "rescore_interval"),
                   ("coll_hier", "retune_factor"),
                   ("coll_hier", "retune_min_us"),
                   ("coll_hier", "min_samples"),
                   ("coll_hier", "fake_nodes"),
                   ("coll_hier", "fake_slices")):
    watch_var(_fw, _name, invalidate)


class CollPlan:
    """One frozen dispatch chain for (comm, verb): epoch-validated in
    ``ProcComm._coll``, rebuilt by :func:`build` on any miss."""

    __slots__ = ("verb", "epoch", "fn", "provider")

    def __init__(self, verb: str, epoch_: int, fn, provider: str):
        self.verb = verb
        self.epoch = epoch_
        self.fn = fn
        self.provider = provider

    def __repr__(self) -> str:  # tools/info + debugging
        return (f"<CollPlan {self.verb} via {self.provider} "
                f"epoch={self.epoch}>")


def build(comm, verb: str) -> CollPlan:
    """Resolve + freeze the dispatch chain for one slot (the slow path
    of ``ProcComm._coll``). Mirrors the pre-plan per-call order exactly:
    usable check -> SPC record -> metrics entry stamp -> sanitizer
    signature capture -> trace span -> module fn."""
    from ompi_tpu.runtime import metrics as _metrics
    from ompi_tpu.runtime import sanitizer as _san
    from ompi_tpu.runtime import trace as _trace

    _hier._plan_misses[0] += 1
    # capture the epoch BEFORE reading any config: a concurrent set_var
    # then at worst forces one extra rebuild, never a stale plan
    ep = _EPOCH[0]
    inner = comm.coll.get(verb)  # raises for unprovided slots, as before
    provider = comm.coll.providers.get(verb, "?")
    if _san._enable_var._value:
        # per-call signature capture happens inside the wrapper;
        # wrap_coll itself is per-(comm, verb) stateless, so binding it
        # once here is the whole point of the freeze
        inner = _san.wrap_coll(comm, verb, inner)
    if _trace.enabled():
        inner = _trace.wrap_span(f"comm.{verb}", "comm", inner)

    if _metrics._enable_var._value:
        def fn(comm2, *args, _inner=inner, _verb=verb):
            if comm2.revoked:
                raise MPIError(ERR_REVOKED, comm2.name)
            _spc.record(_verb)
            # entry stamp for the straggler plane (suppressed-internal
            # calls are skipped inside, same as the pre-plan dispatch)
            _metrics.on_coll_entry(comm2, _verb)
            return _inner(comm2, *args)
    else:
        def fn(comm2, *args, _inner=inner, _verb=verb):
            if comm2.revoked:
                raise MPIError(ERR_REVOKED, comm2.name)
            _spc.record(_verb)
            return _inner(comm2, *args)

    return CollPlan(verb, ep, fn, provider)


def invalidate_comm(comm, verb: Optional[str] = None) -> None:
    """Drop one comm's plan(s): the decide.py re-score seam (one verb,
    on the agreed index) and the Free path (all)."""
    # persistent plans (coll/persist.py) freeze the same decisions one
    # level further out: any per-comm invalidation (decide.py re-score
    # switch, Free) must miss them too, on the same agreed index
    comm._persist_cepoch = getattr(comm, "_persist_cepoch", 0) + 1
    plans = getattr(comm, "_plans", None)
    if plans is None:
        return
    if verb is None:
        plans.clear()
    else:
        plans.pop(verb, None)
