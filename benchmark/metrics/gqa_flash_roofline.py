"""gqa_flash_roofline: the least time of the window's grouped flash
kernels (``flash_gqa_*``, ``flash_win_*``) at the chip's peaks over
their time in the trace, in percent. Each call's least time is the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth
(``flops_moe.gqa_flash``: FLOPs over the band's or the causal half's
attending pairs, K/V bytes once per K/V head); ``bound`` says which of
the two the summed calls meet."""

from benchmark import flops, flops_moe
from benchmark.peaks import peaks

NAMES = ("flash_gqa_", "flash_win_")


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    pk = peaks(device["kind"])
    windows = {True: cell.config["sliding_window"], False: 0}
    least = kernel = fl = nb = 0.0
    for o in tr.ops:
        if not o.hlo or o.start < w.start or o.end > w.end or not any(
                n in o.name for n in NAMES):
            continue
        cost = flops_moe.gqa_flash_call(o.name, o.hlo, windows)
        if cost is None:
            continue
        least += flops.least_time(cost, pk.flops, pk.hbm_bytes)[0]
        fl += cost.flops
        nb += cost.bytes
        kernel += (o.end - o.start) / 1e9
    if not kernel:
        return None
    bound = "compute" if fl / pk.flops >= nb / pk.hbm_bytes else "memory"
    return {"value": least / kernel * 100.0, "bound": bound}
