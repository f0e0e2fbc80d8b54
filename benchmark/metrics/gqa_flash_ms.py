"""gqa_flash_ms: the grouped flash-attention kernels' device time per
step, forward and backward, averaged over the window's steps. They are
the custom calls whose instruction names hold their ``pallas_call``
names, ``flash_gqa_win_*`` (the sliding layers, ``window``) and
``flash_gqa_*`` (the full layers, ``full``); the flagship's
``flash_fwd`` and ``flash_dqkv`` are not among them."""

NAMES = ("flash_gqa_", "flash_win_")


def read(tr, record, cell, device):
    w = tr.spans_named("bench.window")[0]
    steps = record.get("steps")

    def ms(banded):
        ns = tr.op_ns(w.start, w.end, lambda o: bool(o.hlo) and any(
            n in o.name for n in NAMES) and ("win_" in o.name) == banded)
        return ns / 1e6 / steps

    if not steps:
        return None
    window, full = ms(True), ms(False)
    if not window + full:
        return None
    return {"value": window + full, "window": window, "full": full}
