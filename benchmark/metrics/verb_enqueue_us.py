"""verb_enqueue_us: the verb front end's cost on the host. The time
from the verb call to its return, before the block, summed over every
small-phase call of the window by the harness's clock and divided by
the calls."""


def read(tr, record, cell, device):
    small = record.get("phases", {}).get("small")
    if not small or not small["calls"]:
        return None
    return small["enqueue_s"] / small["calls"] * 1e6
