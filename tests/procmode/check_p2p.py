"""Zero-copy vectored tcp datapath + idle-blocking proof.

Run with 2 ranks over tcp only (``--mca btl_btl ^sm``). Two claims,
both count-based (deterministic):

- copies-per-wire-byte at a 32 MB rendezvous, measured from the
  btl_tcp_bytes_copied / btl_tcp_wire_bytes pvars — not estimated —
  under each rank's bound;
- a quiet rank's progress loop parks in select
  (progress_idle_blocks > 0).
"""

import time

import numpy as np

from ompi_tpu import COMM_WORLD
from ompi_tpu.mca.var import all_pvars

comm = COMM_WORLD
r = comm.Get_rank()
assert comm.Get_size() == 2
peer = 1 - r
pv = all_pvars()


def _ctr():
    return (pv["btl_tcp_bytes_copied"].value,
            pv["btl_tcp_wire_bytes"].value,
            pv["btl_tcp_writev_calls"].value)


# the peer must really be on tcp, or the numbers measure nothing
assert type(comm.pml.endpoints[comm._world_rank(peer)]).__name__ \
    == "TcpBtl", "run with --mca btl_btl ^sm"

SMALL = 4096
K = 64        # outstanding small messages per direction
# Per rank, half the lowest copies per wire byte the copying datapath
# this one replaced ever measured on the 32 MB rendezvous (sender
# 2.999929909255432, receiver 1.000003367448467, over 12 runs): the old
# gate asked that path for at least twice this path's copies, so half
# of it is the most it allowed. The sender's ~1.0 is the one owned copy
# of what the kernel declines under backpressure.
COPIES_BOUND = {0: 2.999929 / 2, 1: 1.000003 / 2}
big = np.arange((32 << 20) // 8, dtype=np.float64)
dst_big = np.zeros_like(big)
small = np.zeros(SMALL, np.uint8)
dst_small = [np.zeros(SMALL, np.uint8) for _ in range(K)]


def small_batch():
    """K outstanding eager sends per direction."""
    if r == 0:
        sr = [comm.Isend(small, dest=1, tag=30 + i) for i in range(K)]
        rr = [comm.Irecv(dst_small[i], source=1, tag=130 + i)
              for i in range(K)]
    else:
        rr = [comm.Irecv(dst_small[i], source=0, tag=30 + i)
              for i in range(K)]
        sr = [comm.Isend(small, dest=0, tag=130 + i) for i in range(K)]
    for q in sr + rr:
        q.Wait()


def rendezvous():
    if r == 0:
        comm.Send(big, dest=1, tag=20)
    else:
        comm.Recv(dst_big, source=0, tag=20)


# correctness first — this must NEVER flake
rendezvous()
if r == 1:
    np.testing.assert_array_equal(dst_big, big)
    dst_big[:] = 0
small_batch()
for d in dst_small:
    np.testing.assert_array_equal(d, small)
print(f"P2P-CORRECT rank {r}", flush=True)

# copies-per-wire-byte, from pvars, over one 32 MB rendezvous
comm.Barrier()
c0, w0, _ = _ctr()
rendezvous()
comm.Barrier()
c1, w1, _ = _ctr()
ratio = (c1 - c0) / max(w1 - w0, 1)
if r == 1:
    np.testing.assert_array_equal(dst_big, big)
    dst_big[:] = 0
print(f"P2P-COPIES rank {r} copies_per_wire_byte={ratio!r} "
      f"bound={COPIES_BOUND[r]!r}", flush=True)
assert w1 > w0, "the rendezvous moved no wire bytes"
assert ratio <= COPIES_BOUND[r], (ratio, COPIES_BOUND[r])

# idle-blocking proof: go quiet and let the ProgressThread's backoff
# run cold — with tcp+self only (no poll-only transport) it must PARK
# in select rather than interval-poll
before = pv["runtime_progress_idle_blocks"].value
time.sleep(0.8)
blocks = pv["runtime_progress_idle_blocks"].value - before
writev = pv["btl_tcp_writev_calls"].value
print(f"P2P-IDLE rank {r} blocks={blocks} writev={writev}", flush=True)
assert blocks > 0, "progress loop never parked in select"
assert writev > 0
comm.Barrier()
print(f"P2P-OK rank {r}", flush=True)
