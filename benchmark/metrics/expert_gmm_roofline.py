"""expert_gmm_roofline: the least time of the window's grouped-matmul
calls (``expert_gmm``, ``expert_gmm_dx``, ``expert_gmm_dw``) at the
chip's peaks over their time in the trace, in percent. A call's least
time is the larger of its FLOPs over the bf16 peak and its bytes over
HBM bandwidth (``flops_moe.gmm_call``), for the rows the step reports
routed to the experts held here: the window's rows over its steps and
layers, as each call serves one layer of one step. ``bound`` says which
of the two the summed calls meet."""

from benchmark import flops, flops_moe
from benchmark.peaks import peaks


def read(tr, record, cell, device):
    rows = record.get("moe_rows")
    if not rows or not record.get("steps"):
        return None
    per_call = sum(map(sum, rows)) / (record["steps"] * len(rows))
    w = tr.spans_named("bench.window")[0]
    pk = peaks(device["kind"])
    least = kernel = fl = nb = 0.0
    for o in tr.ops:
        if not o.hlo or o.start < w.start or o.end > w.end or \
                not o.name.startswith("expert_gmm"):
            continue
        cost = flops_moe.gmm_call(o.name, o.hlo, per_call)
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
