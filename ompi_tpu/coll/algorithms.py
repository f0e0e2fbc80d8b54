"""Host collective algorithm library, expressed as round schedules.

Reference: ompi/mca/coll/base — allreduce {recursive doubling
coll_base_allreduce.c:134, ring :345, segmented ring :622}, binomial
bcast/reduce (coll_base_bcast.c, coll_base_reduce.c), bruck allgather
(coll_base_allgather.c), pairwise alltoall (coll_base_alltoall.c),
dissemination barrier. Every function is a generator yielding
``sched.Round`` objects (see coll/sched.py); the same definition backs the
blocking tuned path and the nonblocking MPI_I* path.

All algorithms are datatype-agnostic: payloads travel as convertor-packed
bytes; reductions view packed streams with the datatype's element dtype
(homogeneous or value/index pair typemaps, as in coll/basic).

Datapath discipline (the PR 9 borrowed-view contract, one layer up):
sends are contiguous VIEWS over the caller's packed/accumulator buffers;
receives land either in a pooled staging block (reduction operands) or
directly in their final location — a slice of the caller's receive
buffer or the ring accumulator — via the ``(nbytes, src, dest)`` recv
form. A staging copy happens only where the data genuinely cannot be
borrowed (non-contiguous layouts, the bruck rotation, padded ring
tails) and every such copy is charged to ``coll_round_bytes_copied``.

Reduction-bearing schedules (recursive doubling, ring, binomial reduce)
require a commutative op — the decision layer (coll/tuned.py) routes
non-commutative ops to the rank-ordered linear algorithms, matching the
reference's decision rules.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ompi_tpu.coll.basic import _np_reduce_typed, _typed_view
from ompi_tpu.coll import sched as _sched
from ompi_tpu.coll.sched import Round
from ompi_tpu.comm.communicator import parse_buffer
from ompi_tpu.core import op as _op
from ompi_tpu.core.convertor import (
    _as_byte_view as _as_bytes,
    pack as cv_pack,
    unpack as cv_unpack,
)
from ompi_tpu.core.datatype import Datatype


def _packed(buf):
    """Packed wire bytes of ``buf`` — the convertor's contiguous fast
    path is a borrowed view; only a genuinely non-contiguous pack output
    pays a counted staging copy."""
    obj, count, dt = parse_buffer(buf)
    data = cv_pack(obj, count, dt)
    if not data.flags.c_contiguous:
        _sched.note_copied(data.nbytes)
        data = np.ascontiguousarray(data)  # mpilint: disable=hot-copy — non-contiguous pack output, counted
    return data, count, dt


def _bytes(a: np.ndarray) -> np.ndarray:
    """Flat uint8 VIEW of ``a``; a non-contiguous source is the one
    counted fallback copy the borrowed-view contract allows."""
    if a.flags.c_contiguous:
        return a.view(np.uint8)
    _sched.note_copied(a.nbytes)
    return np.ascontiguousarray(a).view(np.uint8)  # mpilint: disable=hot-copy — non-contiguous fallback, counted


def _unpack_into(data: np.ndarray, buf) -> None:
    obj, count, dt = parse_buffer(buf)
    cv_unpack(_bytes(data), obj, count, dt)


def _direct_view(buf) -> Optional[np.ndarray]:
    """Flat uint8 view over the receive buffer so rounds can land
    payloads in their FINAL location (no staging, no final unpack), or
    None when staging is required: non-contiguous datatype or
    layout."""
    obj, count, dt = parse_buffer(buf)
    if dt.is_contiguous and isinstance(obj, np.ndarray) \
            and obj.flags.c_contiguous and obj.flags.writeable:
        return _as_bytes(obj)[:count * dt.size]
    return None


def _unpack_staging(data: np.ndarray, buf) -> None:
    """Final unpack from a STAGING array into the user's receive buffer
    — a counted copy (the direct-landing path skips it entirely)."""
    obj, count, dt = parse_buffer(buf)
    cv_unpack(data, obj, count, dt)
    _sched.note_copied(data.nbytes)


# ----------------------------------------------------------------- barrier
def barrier_dissemination(comm):
    """ceil(log2 n) zero-byte rounds (coll/base dissemination)."""
    n, r = comm.size, comm.rank
    token = np.zeros(0, dtype=np.uint8)
    d = 1
    while d < n:
        yield Round(sends=[(token, (r + d) % n)], recvs=[(0, (r - d) % n)])
        d <<= 1


# ------------------------------------------------------------------- bcast
def bcast_binomial(comm, buf, root: int):
    """Binomial tree (coll_base_bcast.c binomial). Non-root ranks with a
    contiguous buffer receive STRAIGHT into it and forward borrowed
    views of it — zero staging on the whole tree."""
    n, r = comm.size, comm.rank
    obj, count, dt = parse_buffer(buf)
    nbytes = count * dt.size
    vrank = (r - root) % n
    dest: Optional[np.ndarray] = None
    data: Optional[np.ndarray] = None
    if vrank == 0:
        data = _packed(buf)[0]
    else:
        mask = 1
        while not (vrank & mask):
            mask <<= 1
        src = (vrank - mask + root) % n
        dest = _direct_view(buf)
        if dest is not None:
            yield Round(recvs=[(nbytes, src, dest)])
            data = dest
        else:
            bufs = yield Round(recvs=[(nbytes, src)])
            data = bufs[0]
        # children live below the bit that connected us to our parent
        mask >>= 1
    if vrank == 0:
        mask = 1
        while mask < n:
            mask <<= 1
        mask >>= 1
    sends = []
    while mask > 0:
        if vrank + mask < n and not (vrank & mask):
            sends.append((data, (vrank + mask + root) % n))
        mask >>= 1
    if sends:
        yield Round(sends=sends)
    if vrank != 0 and dest is None:
        _unpack_staging(data, buf)


# ------------------------------------------------------------------ reduce
def reduce_linear(comm, sendbuf, recvbuf, op: _op.Op, root: int):
    """Rank-ordered linear fan-in — correct for non-commutative ops
    (coll/basic linear reduce). Contributions arrive in pooled blocks
    (they are reduction operands, not final data)."""
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    if r != root:
        yield Round(sends=[(packed, root)])
        return
    others = [i for i in range(n) if i != root]
    bufs = yield Round(recvs=[(packed.nbytes, i) for i in others])
    parts: List[np.ndarray] = [None] * n  # type: ignore[list-item]
    parts[root] = packed
    for i, b in zip(others, bufs):
        parts[i] = b
    acc = _typed_view(parts[0].copy(), dt)
    for i in range(1, n):
        acc = _np_reduce_typed(op, acc, _typed_view(parts[i], dt))
    _unpack_into(acc, recvbuf)


def reduce_binomial(comm, sendbuf, recvbuf, op: _op.Op, root: int):
    """Binomial fan-in for commutative ops (coll_base_reduce.c binomial):
    log2 n depth instead of the linear O(n) fan-in at the root."""
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    nb = packed.nbytes
    vrank = (r - root) % n
    children = []
    mask = 1
    while mask < n:
        if vrank & mask:
            break
        if vrank + mask < n:
            children.append((vrank + mask + root) % n)
        mask <<= 1
    acc = _typed_view(packed.copy(), dt)
    if children:
        bufs = yield Round(recvs=[(nb, c) for c in children])
        for b in bufs:
            acc = _np_reduce_typed(op, acc, _typed_view(b, dt))
    if vrank != 0:
        parent = (vrank - mask + root) % n
        yield Round(sends=[(_bytes(acc), parent)])
        return
    _unpack_into(acc, recvbuf)  # vrank 0 == root


# --------------------------------------------------------------- allreduce
def allreduce_recursive_doubling(comm, sendbuf, recvbuf, op: _op.Op):
    """Recursive doubling with the non-power-of-two fold-in pre/post phase
    (coll_base_allreduce.c:134)."""
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    nb = packed.nbytes
    acc = _typed_view(packed.copy(), dt)
    if n == 1:
        _unpack_into(acc, recvbuf)
        return
    pow2 = 1 << (n.bit_length() - 1)
    if pow2 > n:
        pow2 >>= 1
    rem = n - pow2
    # pre: the first 2*rem ranks fold pairwise so pow2 ranks remain
    if r < 2 * rem:
        if r % 2 == 0:
            yield Round(sends=[(_bytes(acc), r + 1)])
            newrank = -1
        else:
            bufs = yield Round(recvs=[(nb, r - 1)])
            acc = _np_reduce_typed(op, acc, _typed_view(bufs[0], dt))
            newrank = r // 2
    else:
        newrank = r - rem
    if newrank >= 0:
        mask = 1
        while mask < pow2:
            pn = newrank ^ mask
            partner = pn * 2 + 1 if pn < rem else pn + rem
            bufs = yield Round(sends=[(_bytes(acc), partner)],
                               recvs=[(nb, partner)])
            acc = _np_reduce_typed(op, acc, _typed_view(bufs[0], dt))
            mask <<= 1
    # post: hand results back to the folded-out even ranks
    if r < 2 * rem:
        if r % 2 == 1:
            yield Round(sends=[(_bytes(acc), r - 1)])
        else:
            bufs = yield Round(recvs=[(nb, r + 1)])
            acc = _typed_view(bufs[0], dt)
    _unpack_into(acc, recvbuf)


def allreduce_ring(comm, sendbuf, recvbuf, op: _op.Op, nseg: int = 1):
    """Ring allreduce: reduce-scatter ring + allgather ring
    (coll_base_allreduce.c:345); with ``nseg > 1`` the element space is
    split into segments whose rings run pipelined — segment s executes its
    step t in global round s + t, so communication of one segment overlaps
    reduction of the next (the segmented ring of :622).

    Datapath: the accumulator lives directly in the user's receive
    buffer when its layout allows (in-place reduction — no private copy,
    no final unpack), segments ALIAS it instead of staging into padded
    scratch (scratch only for a non-divisible tail, counted), allgather-
    phase blocks land in their final slot via dest-view recvs, and the
    reduce-scatter staging blocks recycle through ``Round.free`` each
    step — the pool's steady state."""
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    rdest = _direct_view(recvbuf)
    if rdest is not None and rdest.nbytes == packed.nbytes \
            and dt.np_dtype is not None:
        # accumulate in the receive buffer itself: seed it with the send
        # payload (free for IN_PLACE — packed already aliases recvbuf)
        if sendbuf is not None:
            rdest[:] = _bytes(packed)
        typed = rdest.view(dt.np_dtype)
        in_dest = True
    else:
        typed = _typed_view(packed.copy(), dt)
        in_dest = False
    if n == 1:
        if not in_dest:
            _unpack_into(typed, recvbuf)
        return
    total = typed.size
    nseg = max(1, min(int(nseg), max(1, total // n)))
    bounds = [total * s // nseg for s in range(nseg + 1)]
    segs = []  # [arr of n*k elements, k, orig_len, offset, staged]
    for s in range(nseg):
        a, b = bounds[s], bounds[s + 1]
        ln = b - a
        k = max(1, -(-ln // n))
        if ln == n * k:
            segs.append([typed[a:b], k, ln, a, False])  # alias, no copy
        else:
            # padded-tail fallback: a non-divisible segment stages into
            # padded scratch, counted
            arr = np.zeros(n * k, dtype=typed.dtype)
            arr[:ln] = typed[a:b]
            _sched.note_copied(ln * typed.itemsize)
            segs.append([arr, k, ln, a, True])
    steps = 2 * n - 2
    left, right = (r - 1) % n, (r + 1) % n
    done_blocks: List[np.ndarray] = []
    for g in range(steps + nseg - 1):
        sends, recvs, meta = [], [], []
        for s, (arr, k, ln, off, staged) in enumerate(segs):
            t = g - s
            if not (0 <= t < steps):
                continue
            isz = arr.itemsize
            if t < n - 1:  # reduce-scatter phase
                sb, rb = (r - t) % n, (r - t - 1) % n
                kind = "rs"
            else:          # allgather phase
                ag = t - (n - 1)
                sb, rb = (r + 1 - ag) % n, (r - ag) % n
                kind = "ag"
            sends.append((_bytes(arr[sb * k:(sb + 1) * k]), right))
            if kind == "ag":
                # the forwarded block IS final data: land it in place
                recvs.append((k * isz, left,
                              _bytes(arr[rb * k:(rb + 1) * k])))
            else:
                recvs.append((k * isz, left))
            meta.append((s, kind, rb))
        bufs = yield Round(sends=sends, recvs=recvs, free=done_blocks)
        done_blocks = []
        for (s, kind, rb), b in zip(meta, bufs):
            arr, k, ln, off, staged = segs[s]
            if kind == "rs":
                got = b.view(arr.dtype)
                blk = arr[rb * k:(rb + 1) * k]
                arr[rb * k:(rb + 1) * k] = _np_reduce_typed(op, blk, got)
                done_blocks.append(b)  # operand consumed: recycle next yield
            # (ag blocks landed in their final slot already)
    for arr, k, ln, off, staged in segs:
        if staged:  # padded-tail scratch folds back, counted
            typed[off:off + ln] = arr[:ln]
            _sched.note_copied(ln * typed.itemsize)
    if not in_dest:
        # the non-contiguous/pair-dtype fallback stages: its final
        # unpack is a counted copy the in-recvbuf path avoids
        _unpack_staging(_bytes(typed), recvbuf)


# --------------------------------------------------------------- allgather
def allgather_ring(comm, sendbuf, recvbuf):
    """n-1 rounds, each forwarding the block received last round
    (coll_base_allgather.c ring). Blocks land straight in the receive
    buffer and are forwarded as borrowed views of it."""
    n, r = comm.size, comm.rank
    block, _, _ = _packed(sendbuf)
    nb = block.nbytes
    dest = _direct_view(recvbuf)
    out = dest if dest is not None else np.empty(n * nb, dtype=np.uint8)
    out[r * nb:(r + 1) * nb] = block
    _sched.note_copied(nb)  # own-block placement
    cur = out[r * nb:(r + 1) * nb]
    for d in range(1, n):
        src = (r - d) % n
        slot = out[src * nb:(src + 1) * nb]
        if dest is not None:
            yield Round(sends=[(cur, (r + 1) % n)],
                        recvs=[(nb, (r - 1) % n, slot)])
            cur = slot
        else:
            bufs = yield Round(sends=[(cur, (r + 1) % n)],
                               recvs=[(nb, (r - 1) % n)])
            cur = bufs[0]
            out[src * nb:(src + 1) * nb] = cur
            _sched.note_copied(nb)
    if dest is None:
        _unpack_staging(out, recvbuf)


def allgather_bruck(comm, sendbuf, recvbuf):
    """Bruck: ceil(log2 n) rounds of doubling block trains
    (coll_base_allgather.c bruck) — latency-optimal for small messages.
    The train lives in ONE flat accumulator: each send is a contiguous
    view of its head, each recv lands at its tail; only the final bruck
    rotation copies (counted)."""
    n, r = comm.size, comm.rank
    block, _, _ = _packed(sendbuf)
    nb = block.nbytes
    accbuf = np.empty(n * nb, dtype=np.uint8)
    accbuf[:nb] = block
    _sched.note_copied(nb)
    dist = 1
    while dist < n:
        cnt = min(dist, n - dist)
        yield Round(
            sends=[(accbuf[:cnt * nb], (r - dist) % n)],
            recvs=[(cnt * nb, (r + dist) % n,
                    accbuf[dist * nb:(dist + cnt) * nb])])
        dist <<= 1
    dest = _direct_view(recvbuf)
    out = dest if dest is not None else np.empty(n * nb, dtype=np.uint8)
    for i in range(n):  # the bruck rotation: a genuine reorder, counted
        src = (r + i) % n
        out[src * nb:(src + 1) * nb] = accbuf[i * nb:(i + 1) * nb]
    _sched.note_copied(n * nb)
    if dest is None:
        _unpack_staging(out, recvbuf)


def allgatherv_ring(comm, sendbuf, recvbuf, counts, displs):
    n, r = comm.size, comm.rank
    block, _, _ = _packed(sendbuf)
    robj, rcount, rdt = parse_buffer(recvbuf)
    counts = list(counts)
    if displs is None:
        displs = np.cumsum([0] + counts[:-1]).tolist()
    esz = rdt.size
    dest = _direct_view(recvbuf)
    out = dest if dest is not None \
        else np.zeros(rcount * esz, dtype=np.uint8)
    out[displs[r] * esz:displs[r] * esz + block.nbytes] = block
    _sched.note_copied(block.nbytes)
    cur = out[displs[r] * esz:displs[r] * esz + block.nbytes]
    for d in range(1, n):
        src = (r - d) % n
        nb_src = counts[src] * esz
        slot = out[displs[src] * esz:displs[src] * esz + nb_src]
        if dest is not None:
            yield Round(sends=[(cur, (r + 1) % n)],
                        recvs=[(nb_src, (r - 1) % n, slot)])
            cur = slot
        else:
            bufs = yield Round(sends=[(cur, (r + 1) % n)],
                               recvs=[(nb_src, (r - 1) % n)])
            cur = bufs[0]
            out[displs[src] * esz:displs[src] * esz + nb_src] = cur
            _sched.note_copied(nb_src)
    if dest is None:
        cv_unpack(out, robj, rcount, rdt)
        _sched.note_copied(out.nbytes)


# ---------------------------------------------------------------- alltoall
def alltoall_pairwise(comm, sendbuf, recvbuf):
    """n-1 pairwise exchange rounds (coll_base_alltoall.c pairwise).
    Every round is INDEPENDENT — disjoint send slices of the packed
    buffer, disjoint landing slots in the receive buffer — so rounds
    are yielded ``ordered=False`` and up to ``coll_round_window`` stay
    in flight instead of a barrier per peer."""
    n, r = comm.size, comm.rank
    packed, _, _ = _packed(sendbuf)
    nb = packed.nbytes // n
    robj, rcount, rdt = parse_buffer(recvbuf)
    dest = _direct_view(recvbuf)
    out = dest if dest is not None \
        else np.empty(rcount * rdt.size, dtype=np.uint8)
    out[r * nb:(r + 1) * nb] = packed[r * nb:(r + 1) * nb]
    _sched.note_copied(nb)
    for d in range(1, n):
        dst, src = (r + d) % n, (r - d) % n
        chunk = _bytes(packed[dst * nb:(dst + 1) * nb])
        if dest is not None:
            yield Round(sends=[(chunk, dst)],
                        recvs=[(nb, src, out[src * nb:(src + 1) * nb])],
                        ordered=False)
        else:
            bufs = yield Round(sends=[(chunk, dst)], recvs=[(nb, src)])
            out[src * nb:(src + 1) * nb] = bufs[0]
            _sched.note_copied(nb)
    if dest is None:
        cv_unpack(out, robj, rcount, rdt)
        _sched.note_copied(out.nbytes)


def alltoallv_pairwise(comm, sendbuf, recvbuf, sendcounts, sdispls,
                       recvcounts, rdispls):
    """Pairwise exchange with per-peer counts/displacements (element
    units, matching the blocking basic.alltoallv semantics). Rounds are
    independent — disjoint send slices, disjoint landing slots — so
    they window ``ordered=False`` like the fixed-count pairwise."""
    n, r = comm.size, comm.rank
    packed, _, sdt = _packed(sendbuf)
    robj, rcount, rdt = parse_buffer(recvbuf)
    se, re_ = sdt.size, rdt.size
    dest = _direct_view(recvbuf)
    out = dest if dest is not None \
        else np.zeros(rcount * re_, dtype=np.uint8)
    own = packed[sdispls[r] * se:(sdispls[r] + sendcounts[r]) * se]
    out[rdispls[r] * re_:rdispls[r] * re_ + own.nbytes] = own
    _sched.note_copied(own.nbytes)
    for d in range(1, n):
        dst, src = (r + d) % n, (r - d) % n
        chunk = _bytes(packed[sdispls[dst] * se:
                              (sdispls[dst] + sendcounts[dst]) * se])
        nb_src = recvcounts[src] * re_
        off = rdispls[src] * re_
        if dest is not None:
            yield Round(sends=[(chunk, dst)],
                        recvs=[(nb_src, src, out[off:off + nb_src])],
                        ordered=False)
        else:
            bufs = yield Round(sends=[(chunk, dst)],
                               recvs=[(nb_src, src)])
            out[off:off + nb_src] = bufs[0]
            _sched.note_copied(nb_src)
    if dest is None:
        cv_unpack(out, robj, rcount, rdt)
        _sched.note_copied(out.nbytes)


# ----------------------------------------------------------- gather/scatter
def gather_linear(comm, sendbuf, recvbuf, root: int):
    n, r = comm.size, comm.rank
    block, _, _ = _packed(sendbuf)
    if r != root:
        yield Round(sends=[(block, root)])
        return
    nb = block.nbytes
    dest = _direct_view(recvbuf)
    out = dest if dest is not None else np.empty(n * nb, dtype=np.uint8)
    others = [i for i in range(n) if i != root]
    if dest is not None:
        yield Round(recvs=[(nb, i, out[i * nb:(i + 1) * nb])
                           for i in others])
    else:
        bufs = yield Round(recvs=[(nb, i) for i in others])
        for i, b in zip(others, bufs):
            out[i * nb:(i + 1) * nb] = b
            _sched.note_copied(nb)
    out[root * nb:(root + 1) * nb] = block
    _sched.note_copied(nb)
    if dest is None:
        _unpack_staging(out, recvbuf)


def gatherv_linear(comm, sendbuf, recvbuf, counts, displs, root: int):
    """Linear fan-in with per-rank counts/displacements (element units,
    the blocking basic.gatherv semantics): the root lands each block
    straight in its displacement slot."""
    n, r = comm.size, comm.rank
    block, _, _ = _packed(sendbuf)
    if r != root:
        yield Round(sends=[(block, root)])
        return
    robj, rcount, rdt = parse_buffer(recvbuf)
    counts = list(counts)
    if displs is None:
        displs = np.cumsum([0] + counts[:-1]).tolist()
    esz = rdt.size
    dest = _direct_view(recvbuf)
    out = dest if dest is not None \
        else np.zeros(rcount * esz, dtype=np.uint8)
    others = [i for i in range(n) if i != root]
    if dest is not None:
        yield Round(recvs=[(counts[i] * esz, i,
                            out[displs[i] * esz:
                                displs[i] * esz + counts[i] * esz])
                           for i in others])
    else:
        bufs = yield Round(recvs=[(counts[i] * esz, i) for i in others])
        for i, bb in zip(others, bufs):
            out[displs[i] * esz:displs[i] * esz + bb.nbytes] = bb
            _sched.note_copied(bb.nbytes)
    out[displs[root] * esz:displs[root] * esz + block.nbytes] = block
    _sched.note_copied(block.nbytes)
    if dest is None:
        _unpack_staging(out, recvbuf)


def scatterv_linear(comm, sendbuf, recvbuf, counts, displs, root: int):
    """Linear fan-out with per-rank counts/displacements (element
    units, the blocking basic.scatterv semantics)."""
    n, r = comm.size, comm.rank
    robj, rcount, rdt = parse_buffer(recvbuf)
    if r == root:
        packed, _, sdt = _packed(sendbuf)
        counts = list(counts)
        if displs is None:
            displs = np.cumsum([0] + counts[:-1]).tolist()
        esz = sdt.size
        sends = []
        for i in range(n):
            chunk = _bytes(packed[displs[i] * esz:
                                  (displs[i] + counts[i]) * esz])
            if i == root:
                cv_unpack(chunk, robj, rcount, rdt)
            else:
                sends.append((chunk, i))
        if sends:
            yield Round(sends=sends)
    else:
        nb = rcount * rdt.size
        dest = _direct_view(recvbuf)
        if dest is not None:
            yield Round(recvs=[(nb, root, dest)])
        else:
            bufs = yield Round(recvs=[(nb, root)])
            cv_unpack(bufs[0], robj, rcount, rdt)
            _sched.note_copied(nb)


def scatter_linear(comm, sendbuf, recvbuf, root: int):
    n, r = comm.size, comm.rank
    robj, rcount, rdt = parse_buffer(recvbuf)
    nb = rcount * rdt.size
    if r == root:
        packed, _, _ = _packed(sendbuf)
        sends = []
        for i in range(n):
            chunk = _bytes(packed[i * nb:(i + 1) * nb])
            if i == root:
                cv_unpack(chunk, robj, rcount, rdt)
            else:
                sends.append((chunk, i))
        if sends:
            yield Round(sends=sends)
    else:
        dest = _direct_view(recvbuf)
        if dest is not None:
            yield Round(recvs=[(nb, root, dest)])
        else:
            bufs = yield Round(recvs=[(nb, root)])
            cv_unpack(bufs[0], robj, rcount, rdt)
            _sched.note_copied(nb)


# -------------------------------------------------------------- scan family
def scan_linear(comm, sendbuf, recvbuf, op: _op.Op):
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    if r > 0:
        bufs = yield Round(recvs=[(packed.nbytes, r - 1)])
        acc = _np_reduce_typed(op, _typed_view(bufs[0], dt),
                               _typed_view(packed.copy(), dt))
    else:
        acc = _typed_view(packed.copy(), dt)
    if r < n - 1:
        yield Round(sends=[(_bytes(acc), r + 1)])
    _unpack_into(acc, recvbuf)


def exscan_linear(comm, sendbuf, recvbuf, op: _op.Op):
    n, r = comm.size, comm.rank
    packed, _, dt = _packed(recvbuf if sendbuf is None else sendbuf)
    prefix: Optional[np.ndarray] = None
    if r > 0:
        bufs = yield Round(recvs=[(packed.nbytes, r - 1)])
        prefix = bufs[0]
    if r < n - 1:
        if prefix is None:
            nxt = packed
        else:
            nxt = _bytes(_np_reduce_typed(op, _typed_view(prefix.copy(), dt),
                                          _typed_view(packed, dt)))
        yield Round(sends=[(nxt, r + 1)])
    if prefix is not None:
        _unpack_into(np.frombuffer(prefix, np.uint8), recvbuf)


# --------------------------------------------------------- compound schedules
def reduce_scatter_block_sched(comm, sendbuf, recvbuf, op: _op.Op):
    """reduce + scatter composition, as one schedule."""
    robj, rcount, rdt = parse_buffer(recvbuf)
    n = comm.size
    tmp_obj = np.empty(rcount * n * max(rdt.extent, 1), dtype=np.uint8)
    tmp = [tmp_obj, rcount * n, rdt]
    alg = reduce_binomial if op.commutative else reduce_linear
    yield from alg(comm, sendbuf, tmp, op, 0)
    yield from scatter_linear(comm, tmp, recvbuf, 0)
