"""flash_attn_roofline: the least time of the window's flash-attention
kernels (forward, dq, dk/dv) at the chip's peaks over their time in
the trace, in percent. Each call's least time is the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth (flops.py,
the causal half only); ``bound`` says which of the two the summed
calls meet. Attention here is causal only on one sequence shard
(sp = 1); with a ring, the blocks' relation is not in the trace and
nothing is read."""

from benchmark import flops
from benchmark.peaks import peaks


def read(tr, record, cell, device):
    if cell.traffic["mesh"][1] != 1:
        return None
    w = tr.spans_named("bench.window")[0]
    pk = peaks(device["kind"])
    least = kernel = fl = nb = 0.0
    for o in tr.ops:
        if not o.hlo or o.start < w.start or o.end > w.end:
            continue
        k = flops.flash_kernel(o.hlo)
        if k is None:
            continue
        kind, bh, t, d, in_bytes = k
        cost = flops.FLASH_KERNELS[kind](bh, t, d, True, in_bytes)
        least += flops.least_time(cost, pk.flops, pk.hbm_bytes)[0]
        fl += cost.flops
        nb += cost.bytes
        kernel += (o.end - o.start) / 1e9
    if not kernel:
        return None
    bound = "compute" if fl / pk.flops >= nb / pk.hbm_bytes else "memory"
    return {"value": least / kernel * 100.0, "bound": bound}
