"""In-mesh collective surface: MPI verbs over named mesh axes.

This is the framework's *interior* API — what code already running inside a
``shard_map`` region (models, pallas-adjacent ops) calls, with mesh axis
names standing in for communicators. The exterior surface (XlaComm) wraps
shard_map itself; these helpers are the same lowering one level down, so
model code and MPI code share one collective vocabulary.

Reference analog: the coll framework's op surface (coll.h:545-620), with
the communicator argument replaced by an axis name — an axis *is* a
communicator whose groups are "all index combinations of the other axes"
(how sub-communicators fall out of a torus for free — SURVEY.md §7 hard
part 2, solved by mesh construction instead of group lists).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

AxisName = Union[str, Tuple[str, ...]]


def allreduce(x, axis: AxisName, op: str = "sum"):
    """MPI_Allreduce inside shard_map. op: sum|max|min|mean."""
    from jax import lax

    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    raise ValueError(f"unsupported in-mesh op {op!r}")


def reduce_scatter(x, axis: AxisName, scatter_dim: int = 0, tiled: bool = True):
    """MPI_Reduce_scatter_block (psum_scatter)."""
    from jax import lax

    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                            tiled=tiled)


def allgather(x, axis: AxisName, concat_dim: int = 0, tiled: bool = True):
    """MPI_Allgather (all_gather)."""
    from jax import lax

    return lax.all_gather(x, axis, axis=concat_dim, tiled=tiled)


def alltoall(x, axis: AxisName, split_dim: int, concat_dim: int):
    """MPI_Alltoall (all_to_all)."""
    from jax import lax

    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def bcast(x, axis: AxisName, root: int = 0):
    """MPI_Bcast: everyone takes the root shard's value."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def permute(x, axis: AxisName, perm: Sequence[Tuple[int, int]]):
    """Tag-free pt2pt (collective permute)."""
    from jax import lax

    return lax.ppermute(x, axis, list(perm))


def shift(x, axis: AxisName, delta: int = 1):
    """Ring shift by +delta along the axis (the sendrecv-around-a-ring
    idiom; building block of every ring schedule here and in coll/xla)."""
    from jax import lax

    n = size(axis)
    perm = [(i, (i + delta) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def copy_to(x, axis: AxisName):
    """Identity forward, Allreduce backward (the tensor-parallel "f"
    operator). ONLY for shard_map regions running with check_vma=False:
    with the default replication-checked shard_map, jax's AD already
    inserts this psum automatically for replicated inputs, and adding it
    again double-counts gradients."""
    import jax
    from jax import lax

    @jax.custom_vjp
    def f(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f(x)


def rank(axis: AxisName):
    """MPI_Comm_rank along an axis."""
    from jax import lax

    return lax.axis_index(axis)


def size(axis: AxisName) -> int:
    """MPI_Comm_size along an axis (static)."""
    from jax import lax

    return lax.axis_size(axis)
