"""Split each blocking verb call of a phase in three, from the program's
own ``comm.<verb>`` span in the device trace.

The library opens that span (``ompi_tpu/runtime/trace.py``) in
``XlaComm._hot`` whenever a profiler is collecting, around its fast
path and the executable's call, so it lies on the thread that holds the
harness's ``bench.call`` spans, among the host events of the reduced
trace. For each call of the phase:

- self: the span's length less the host events directly nested in it
  (JAX's ``PjitFunction(...)`` and whatever else the library calls that
  JAX records): the library's own Python;
- dispatch: the summed length of those directly nested events;
- wait: the span's end (the verb returned) to the ``bench.call`` span's
  end (``block_until_ready`` returned): completion;
- entry: the ``bench.call`` span's start to the ``comm.<verb>`` span's
  start: the harness's clock read and the verb's fast-table lookup.

Entry, self, dispatch and wait add up to the ``bench.call`` span.

On the CPU backend the executable runs inside JAX's dispatch call
(``PjRtCpuExecutable::Execute`` holds the computation), so dispatch and
completion do not part there as on an accelerator: nothing is read.
"""

from __future__ import annotations

import bisect
from typing import Dict, Optional

PHASES = ("small", "large")


def _nested_ns(host, starts, i: int) -> float:
    """Summed length of the host events directly nested in ``host[i]``."""
    v = host[i]
    lo = bisect.bisect_left(starts, v.start)
    hi = bisect.bisect_right(starts, v.end)
    inner = sorted((h.start, -h.end) for k, h in enumerate(host[lo:hi], lo)
                   if k != i and h.end <= v.end)
    tot, edge = 0.0, v.start
    for s, neg_end in inner:
        if s >= edge:
            tot += -neg_end - s
            edge = -neg_end
    return tot


def split(tr, verb: str, phase: str) -> Optional[Dict[str, float]]:
    """Means over the phase's ``bench.call`` spans, in µs: ``entry``,
    ``self``, ``dispatch``, ``wait``, and ``call`` (the whole span);
    None where fewer than 99% of the calls hold a ``comm.<verb>`` span.
    Calls are matched to spans by bisection."""
    calls = tr.spans_named("bench.call", phase=phase)
    if not calls:
        return None
    host = tr.host
    starts = [h.start for h in host]
    mine = [i for i, h in enumerate(host) if h.name == "comm." + verb]
    mine_starts = [host[i].start for i in mine]
    tot = dict.fromkeys(("entry", "self", "dispatch", "wait", "call"), 0.0)
    n = 0
    for c in calls:
        j = bisect.bisect_left(mine_starts, c.start)
        if j == len(mine) or host[mine[j]].end > c.end:
            continue
        v = host[mine[j]]
        nested = _nested_ns(host, starts, mine[j])
        tot["entry"] += v.start - c.start
        tot["self"] += v.end - v.start - nested
        tot["dispatch"] += nested
        tot["wait"] += c.end - v.end
        tot["call"] += c.end - c.start
        n += 1
    if n < 0.99 * len(calls):
        return None
    return {k: v / n / 1e3 for k, v in tot.items()}


def reading(tr, record, device, part: str):
    """A reader's line: ``part`` of the small phase's calls as the
    value, and of the large phase's under ``large``."""
    verb = record.get("verb")
    if device["platform"] == "cpu" or not verb:
        return None
    small, large = (split(tr, verb, ph) for ph in PHASES)
    if small is None:
        return None
    out = {"value": small[part]}
    if large is not None:
        out["large"] = large[part]
    return out
