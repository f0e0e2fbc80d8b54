"""expert_gmm_ms: the grouped-matmul kernels' device time per step,
averaged over the window's steps: ``expert_gmm`` (forward, and again
where the backward recomputes the layer), ``expert_gmm_dx`` and
``expert_gmm_dw`` (the backward), each beside the sum by name."""

KINDS = ("expert_gmm_dx", "expert_gmm_dw", "expert_gmm")


def _kind(name):
    return next((k for k in KINDS if name.startswith(k)), None)


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    steps = record.get("steps")
    if not steps:
        return None
    out = {k: tr.op_ns(w.start, w.end, lambda o, k=k: bool(o.hlo) and
                       _kind(o.name) == k) / 1e6 / steps for k in KINDS}
    total = sum(out.values())
    if not total:
        return None
    return {"value": total, "fwd": out["expert_gmm"],
            "dx": out["expert_gmm_dx"], "dw": out["expert_gmm_dw"]}
