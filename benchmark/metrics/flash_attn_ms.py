"""flash_attn_ms: the flash-attention kernels' device time per step,
summed over forward, dq and dk/dv, averaged over the window's steps."""

from benchmark import flops


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    ns = tr.op_ns(w.start, w.end, lambda o: bool(o.hlo) and
                  flops.flash_kernel(o.hlo) is not None)
    if not ns or not record.get("steps"):
        return None
    return ns / 1e6 / record["steps"]
