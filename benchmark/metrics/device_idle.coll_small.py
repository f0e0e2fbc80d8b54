"""device_idle.coll_small: the devices' idle share during the
small-message blocks, 1 − busy/time over the blocks' host spans, in
percent."""


def read(tr, record, cell, device):
    blocks = tr.spans_named("bench.block", phase="small")
    total = sum(b.end - b.start for b in blocks)
    if not total:
        return None
    return (1.0 - tr.busy_in(blocks) / total) * 100.0
