"""verb_wait_us: completion after the verb returns: the end of the
program's ``comm.<verb>`` span to the end of the harness's
``bench.call`` span, when ``block_until_ready`` has returned, averaged
over the small phase's calls (``large``: over the large phase's), from
the traced run's device trace (``benchmark/verb_split.py``)."""

from benchmark import verb_split


def read(tr, record, cell, device):
    return verb_split.reading(tr, record, device, "wait")
