"""Collective round-engine datapath + windowing proof.

The coll-layer analog of check_p2p.py: the zero-copy round engine
(borrowed-view sends, pooled/direct-landing recvs, ``ordered=False``
windowing). Every input is an integer-valued float64 or int64, so each
result has an exact closed form that numpy computes independently.

Claims, the first two count-based (deterministic):

- copies-per-byte-moved on a >= 1 MB allreduce + alltoall pair, from
  the coll_round_bytes_copied / bytes_moved pvars, under its bound;
- pool recycling (coll_round_pool_hits grows in steady state) and
  windowing (coll_round_windowed grows for the pairwise alltoall);
- every swept verb is BITWISE equal to the numpy reference in lockstep
  (window=1) and windowed (window=8) runs — including the nonblocking
  ialltoall/iallreduce path through NbcRequest — and so is the gate
  workload.

Run with components that contest the round-engine slots excluded:
``--mca coll_coll ^sm,adapt,han,hier,quant``.
"""

import sys

import numpy as np

import ompi_tpu
from ompi_tpu import COMM_WORLD
from ompi_tpu.core import op as mpi_op
from ompi_tpu.mca.var import all_pvars, set_var

comm = COMM_WORLD
r = comm.Get_rank()
n = comm.Get_size()
pv = all_pvars()

# 1.5 MB, divisible by any test world size (2/3/4) so the segmented
# ring's no-padding alias path is in play on every rank count
BIG = 196608
A2A = 32768 * n  # >= 1 MB of alltoall payload per rank at n >= 4
C = 8192         # sweep element count
# Half the lowest copies per byte moved the copying round engine this
# one replaced ever measured on the gate workload (1.2708333333333333
# at 4 ranks, 1.4 at 3, over 12 runs each): the old gate asked that
# engine for at least twice this engine's copies, so half of it is the
# most it allowed.
COPIES_BOUND = 1.270833 / 2


def ctr():
    return (pv["coll_round_bytes_copied"].value,
            pv["coll_round_bytes_moved"].value,
            pv["coll_round_pool_hits"].value,
            pv["coll_round_windowed"].value)


def sweep_x(rank):
    return np.arange(C, dtype=np.float64) + rank * 3 + 1


def a2a_expect(count, base):
    """Rank r's alltoall result when rank s sends
    ``arange(count) + s * base``: block s is rank s's block r."""
    k = count // n
    return np.concatenate([np.arange(r * k, (r + 1) * k) + s * base
                           for s in range(n)])


def big_pair():
    """The gate workload: ring allreduce + pairwise alltoall, >= 1 MB."""
    x = np.arange(BIG, dtype=np.float64) + r
    out = np.zeros(BIG, np.float64)
    comm.Allreduce(x, out)
    sx = (np.arange(A2A, dtype=np.float64) + r * 10).copy()
    sout = np.zeros(A2A, np.float64)
    comm.Alltoall(sx, sout)
    return out, sout


def sweep():
    """Every round-schedule verb on deterministic inputs; returns the
    flattened results for bitwise comparison across window settings."""
    res = []
    x = sweep_x(r)
    for algo in ("recursive_doubling", "ring", "ring_segmented"):
        set_var("coll_tuned", "allreduce_algorithm", algo)
        out = np.zeros(C, np.float64)
        comm.Allreduce(x, out)
        res.append(out.copy())
    set_var("coll_tuned", "allreduce_algorithm", "auto")
    for algo in ("ring", "bruck"):
        set_var("coll_tuned", "allgather_algorithm", algo)
        ag = np.zeros(n * C, np.float64)
        comm.Allgather(x, ag)
        res.append(ag.copy())
    set_var("coll_tuned", "allgather_algorithm", "auto")
    a2a_in = np.arange(n * 512, dtype=np.int64) + r * 1000
    a2a_out = np.zeros(n * 512, np.int64)
    comm.Alltoall(a2a_in, a2a_out)
    res.append(a2a_out.copy().view(np.float64))
    b = (np.arange(C, dtype=np.float64)
         if r == 0 else np.zeros(C, np.float64))
    comm.Bcast(b, root=0)
    res.append(b.copy())
    red = np.zeros(C, np.float64)
    comm.Reduce(x, red, op=mpi_op.MAX, root=n - 1)
    res.append(red.copy())
    rsb = np.zeros(C // n if C % n == 0 else 1, np.float64)
    if C % n == 0:
        comm.Reduce_scatter_block(x, rsb)
    res.append(rsb.copy())
    # the nonblocking path (NbcRequest windowing + pooled recvs)
    iar = np.zeros(C, np.float64)
    q1 = comm.Iallreduce(x, iar)
    ia2a = np.zeros(n * 512, np.int64)
    q2 = comm.Ialltoall(a2a_in, ia2a)
    q1.Wait()
    q2.Wait()
    res.append(iar.copy())
    res.append(ia2a.copy().view(np.float64))
    return np.concatenate(res)


def sweep_expect():
    """The sweep's results from numpy alone, in sweep()'s order."""
    xs = [sweep_x(s) for s in range(n)]
    total = np.sum(xs, axis=0)
    a2a = a2a_expect(n * 512, 1000).astype(np.int64).view(np.float64)
    k = C // n
    rsb = (total[r * k:(r + 1) * k] if C % n == 0
           else np.zeros(1, np.float64))
    red = (np.max(xs, axis=0) if r == n - 1
           else np.zeros(C, np.float64))
    return np.concatenate([total] * 3 + [np.concatenate(xs)] * 2
                          + [a2a, np.arange(C, dtype=np.float64), red,
                             rsb, total, a2a])


def big_pair_expect():
    total = np.sum([np.arange(BIG, dtype=np.float64) + s
                    for s in range(n)], axis=0)
    return total, a2a_expect(A2A, 10).astype(np.float64)


def main() -> int:
    # ----- bitwise equality: reference vs lockstep vs windowed ---------
    ref = sweep_expect()
    set_var("coll_round", "window", 1)
    lock = sweep()
    set_var("coll_round", "window", 8)
    win = sweep()
    np.testing.assert_array_equal(lock, ref)
    np.testing.assert_array_equal(win, ref)
    np.testing.assert_array_equal(lock, win)
    print(f"COLLROUND-EQ rank {r}", flush=True)

    # ----- count-based copy gate (deterministic) -----------------------
    big_pair()  # warm the pools / measure steady state
    comm.Barrier()
    c0, m0, h0, w0 = ctr()
    got = big_pair()
    comm.Barrier()
    c1, m1, h1, w1 = ctr()
    ratio = (c1 - c0) / max(m1 - m0, 1)
    pool_hits, windowed = h1 - h0, w1 - w0
    want = big_pair_expect()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    print(f"COLLROUND-COPIES rank {r} copies_per_byte_moved={ratio!r} "
          f"bound={COPIES_BOUND!r}", flush=True)
    print(f"COLLROUND-POOL rank {r} hits={pool_hits} "
          f"windowed={windowed}", flush=True)
    assert m1 > m0, "the gate workload moved no bytes"
    assert ratio <= COPIES_BOUND, (ratio, COPIES_BOUND)
    assert pool_hits > 0, "recv blocks never recycled"
    assert windowed > 0, "alltoall rounds never windowed"

    comm.Barrier()
    ompi_tpu.Finalize()
    print(f"COLLROUND-OK rank {r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
