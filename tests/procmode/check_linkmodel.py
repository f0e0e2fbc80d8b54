"""Fabric-telemetry scenarios: the runtime/linkmodel.py estimators
(passive SRTT off the reliability envelope's ack clock, directional
loss_ppm, delivered goodput) exercised against DETERMINISTIC fault
injection, selected by argv[1]. All modes run with
``linkmodel_enable=1`` (wrapper-supplied) unless noted.

``delay`` — 3 ranks, ``delay(0,1,ms=60)``: every wire frame 0 -> 1
    sleeps 60ms inline AFTER the envelope's send-instant stamp, so the
    injected latency lands inside the RTT samples. Rank 0's edge ->1
    must read SRTT >= ~48ms while its edge ->2 stays under 30ms — the
    estimator attributes the slowdown to the ONE slow edge, 5/5
    deterministic (a 60ms signal against a loopback-noise floor).

``corrupt`` — 3 ranks, ``corrupt(0,1,nth=3)``: every 3rd frame 0 -> 1
    is bit-flipped in flight; the receiver CRC-rejects and NACKs, the
    sender retransmits. Directional attribution: rank 0's edge ->1
    shows loss_ppm past the degraded threshold, its edge ->2 and BOTH
    of the victims' reverse edges stay clean (the receiver's crc
    counts surface as rx_loss_ppm on ITS conn, never as outbound
    loss). The wrapper then points ``mpinet --check`` at the exported
    snapshots and asserts the verdict names exactly ``0->1``.

``equal`` — telemetry must be a pure observer: a deterministic
    ping-pong + allreduce stream prints a bitwise digest of every
    delivered payload; the wrapper runs it with linkmodel (and the
    active probe) on and off and asserts identical digests.

Reference analogs: check_link.py (reliability scenarios) — this file
is its telemetry sibling.
"""

import faulthandler
import os
import signal as _signal
import sys
import time

import numpy as np

ITERS = 24


def _pump(comm, r, peers_of_zero=(1, 2), iters=ITERS, words=64):
    """Rank 0 ping-pongs every listed peer each iteration — symmetric
    deterministic traffic on the 0->k edges (the edges the modes
    assert on)."""
    buf = np.zeros(words, np.int64)
    got = []
    for i in range(iters):
        if r == 0:
            for p in peers_of_zero:
                comm.Send(np.full(words, 1000 * p + i, np.int64),
                          dest=p, tag=i)
                comm.Recv(buf, source=p, tag=i)
                assert buf[0] == 2000 * p + i, (p, i, buf[0])
                got.append(buf.copy())
        elif r in peers_of_zero:
            comm.Recv(buf, source=0, tag=i)
            assert buf[0] == 1000 * r + i, (r, i, buf[0])
            got.append(buf.copy())
            comm.Send(np.full(words, 2000 * r + i, np.int64),
                      dest=0, tag=i)
    return got


def _edges_by_dst():
    from ompi_tpu.runtime import linkmodel

    linkmodel._fold(force=True)
    return {row["dst"]: row for row in linkmodel.edges()}


def delay_mode() -> int:
    import ompi_tpu
    from ompi_tpu import COMM_WORLD

    r = COMM_WORLD.Get_rank()
    _pump(COMM_WORLD, r)
    COMM_WORLD.Barrier()
    if r == 0:
        by_dst = _edges_by_dst()
        slow, fast = by_dst[1], by_dst[2]
        assert slow["rtt_samples"] > 0, slow
        assert fast["rtt_samples"] > 0, fast
        # 60ms injected on 0->1 only: the estimator must localize it
        assert slow["srtt_us"] >= 48000.0, slow
        assert fast["srtt_us"] < 30000.0, fast
    print(f"rank {r}: LINKDELAY-OK", flush=True)
    ompi_tpu.Finalize()
    return 0


def corrupt_mode() -> int:
    import ompi_tpu
    from ompi_tpu import COMM_WORLD
    from ompi_tpu.mca.var import get_var

    r = COMM_WORLD.Get_rank()
    # 2x the default pump: the loss VERDICT is statistically gated
    # (>= 3 retx over >= 32 frames), so the faulted edge must carry
    # enough traffic for its corruption rate to count as a measurement
    _pump(COMM_WORLD, r, iters=2 * ITERS)
    COMM_WORLD.Barrier()
    threshold = float(get_var("linkmodel", "loss_degraded_ppm"))
    by_dst = _edges_by_dst()
    if r == 0:
        # the faulted direction reads degraded...
        assert by_dst[1]["loss_ppm"] > threshold, by_dst[1]
        # ...and ONLY that direction: the clean edge stays clean
        assert by_dst[2]["loss_ppm"] == 0.0, by_dst[2]
    else:
        # the victims' outbound edges carry no retransmits — rank 1's
        # crc rejects are INBOUND evidence (rx_loss_ppm), and blaming
        # them on 1->0 would flag the healthy direction
        assert by_dst[0]["loss_ppm"] == 0.0, by_dst[0]
        if r == 1:
            assert by_dst[0]["rx_loss_ppm"] > 0.0, by_dst[0]
    print(f"rank {r}: LINKCORRUPT-OK", flush=True)
    ompi_tpu.Finalize()
    return 0


def equal_mode() -> int:
    import hashlib

    import ompi_tpu
    from ompi_tpu import COMM_WORLD

    r = COMM_WORLD.Get_rank()
    got = _pump(COMM_WORLD, r)
    contrib = np.arange(64, dtype=np.int64) + 100 * (r + 1)
    total = np.zeros_like(contrib)
    COMM_WORLD.Allreduce(contrib, total)
    h = hashlib.sha256()
    for b in got:
        h.update(b.tobytes())
    h.update(total.tobytes())
    # let a probe round or two fire when the wrapper enabled them (the
    # observer must not perturb the digest). Fixed barrier count — a
    # wall-clock loop would run a different number of barriers per
    # rank and deadlock the stragglers.
    for _ in range(10):
        time.sleep(0.02)
        COMM_WORLD.Barrier()
    print(f"rank {r}: LINKMODEL-EQ digest={h.hexdigest()}", flush=True)
    ompi_tpu.Finalize()
    return 0


def main() -> int:
    faulthandler.register(_signal.SIGUSR1)  # hang diagnosis: kill -USR1
    mode = sys.argv[1]
    if mode == "delay":
        return delay_mode()
    if mode == "corrupt":
        return corrupt_mode()
    if mode == "equal":
        return equal_mode()
    print(f"unknown mode {mode}", flush=True)
    return 2


if __name__ == "__main__":
    sys.exit(main())
