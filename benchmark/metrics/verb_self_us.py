"""verb_self_us: the library's own front end in a verb call: the
program's ``comm.<verb>`` span less the host events directly nested in
it, averaged over the small phase's calls (``large``: over the large
phase's), from the traced run's device trace (``benchmark/verb_split.py``).

``entry_us`` is the part of each small call before the span opens (the
harness's clock read and the verb's fast-table lookup), and ``call_us``
the traced window's small-phase time per call, as ``coll_small_us``
counts it: entry, self, dispatch and wait add up to the ``bench.call``
span, and the rest of ``call_us`` is the loop between calls."""

from benchmark import verb_split


def read(tr, record, cell, device):
    out = verb_split.reading(tr, record, device, "self")
    if out is None:
        return None
    small = record["phases"]["small"]
    out["entry_us"] = verb_split.split(tr, record["verb"], "small")["entry"]
    out["call_us"] = small["time_s"] / small["calls"] * 1e6
    return out
