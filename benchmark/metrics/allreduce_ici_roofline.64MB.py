"""allreduce_ici_roofline.64MB: the least time of the window's 64 MiB
allreduce calls at the chip's published ICI bandwidth, 2(n−1)/n·S
bytes over the per-chip peak, over their device time in the trace, in
percent."""

from benchmark import flops
from benchmark.peaks import peaks

SIZE = 64 << 20


def read(tr, record, cell, device):
    calls = tr.spans_named("bench.call", phase="large", bytes=SIZE)
    busy = tr.busy_in(calls) / 1e9
    if not calls or busy <= 0:
        return None
    least = len(calls) * flops.coll_bus_bytes(
        "allreduce", SIZE, record["ranks"]) / peaks(device["kind"]).ici_bytes
    return least / busy * 100.0
