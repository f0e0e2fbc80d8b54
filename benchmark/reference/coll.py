"""Plain numpy references of the OSU blocking collectives, for
``data`` holding one row per rank (``[ranks, ...]``)."""

from __future__ import annotations

import numpy as np


def expected(verb: str, data: np.ndarray, root: int = 0) -> np.ndarray:
    """What every rank holds after ``verb``, stacked over ranks."""
    n = data.shape[0]
    if verb == "allreduce":
        # the data are integers small enough that any order of float32
        # additions is exact, so the sum is exact too
        total = data.astype(np.float64).sum(0).astype(data.dtype)
        return np.broadcast_to(total, data.shape)
    if verb == "bcast":
        return np.broadcast_to(data[root], data.shape)
    if verb == "allgather":
        return np.broadcast_to(data, (n,) + data.shape)
    if verb == "alltoall":
        return data.swapaxes(0, 1)
    raise ValueError(f"no reference for verb {verb!r}")
