"""flash_fwd_ms: the forward flash-attention kernel's device time per
step, averaged over the window's steps. The kernel is the custom call
whose instruction name holds its ``pallas_call`` name, ``flash_fwd``
(``flash_fwd.N`` in a v5e trace)."""

KERNELS = ("flash_fwd",)


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    ns = tr.op_ns(w.start, w.end, lambda o: bool(o.hlo) and any(
        k in o.name for k in KERNELS))
    if not ns or not record.get("steps"):
        return None
    return ns / 1e6 / record["steps"]
