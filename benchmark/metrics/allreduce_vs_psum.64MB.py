"""allreduce_vs_psum.64MB: device time of a raw ``lax.psum`` over that
of the library's allreduce, 64 MiB per rank, from the comparator calls
the traced run interleaves after the window (medians over the calls).
Above 1, the raw collective is slower."""

import statistics

SIZE = 64 << 20


def read(tr, record, cell, device):
    def times(impl):
        return [t for t in (tr.busy_in([s]) for s in tr.spans_named(
            "bench.cmp", impl=impl, bytes=SIZE)) if t > 0]

    lib, raw = times("lib"), times("raw")
    if not lib or not raw:
        return None
    return statistics.median(raw) / statistics.median(lib)
