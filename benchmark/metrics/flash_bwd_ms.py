"""flash_bwd_ms: the backward flash-attention kernels' device time per
step, dq and dk/dv summed, averaged over the window's steps. The
kernels are the custom calls whose instruction names hold their
``pallas_call`` names, ``flash_dq`` and ``flash_dkv`` (``flash_dq.N``
and ``flash_dkv.N`` in a v5e trace)."""

KERNELS = ("flash_dq", "flash_dkv")


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    ns = tr.op_ns(w.start, w.end, lambda o: bool(o.hlo) and any(
        k in o.name for k in KERNELS))
    if not ns or not record.get("steps"):
        return None
    return ns / 1e6 / record["steps"]
