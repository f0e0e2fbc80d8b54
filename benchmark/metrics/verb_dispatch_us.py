"""verb_dispatch_us: JAX's dispatch of a verb's executable: the summed
length of the host events directly nested in the program's
``comm.<verb>`` span (``PjitFunction(...)`` and what it calls),
averaged over the small phase's calls (``large``: over the large
phase's), from the traced run's device trace
(``benchmark/verb_split.py``)."""

from benchmark import verb_split


def read(tr, record, cell, device):
    return verb_split.reading(tr, record, device, "dispatch")
