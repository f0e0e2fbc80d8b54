"""A sparse expert layer that holds some of the experts: the chip's share
of an expert-parallel layer.

The router keeps its full width: every token picks its top-k of all the
experts, and its weights are renormalised over those k. This layer then
computes what the experts it holds, ``first .. first + n_held - 1``, add
for the tokens routed to them; the absent experts' part is another
chip's. On one chip the layer runs without its exchange.

- ``router``: softmax over all experts in float32, top-k, weights
  renormalised over the k (``norm_topk_prob``).
- ``route``: sorts the (token, slot) assignments held here by expert,
  into a buffer where each expert's rows start on a ``TILE``-row tile.
  Every expert gets at least one tile, so the weight gradient writes
  all of them.
- Two heights, both dropless. ``compact_rows``: twice the even share of
  the held experts' assignments (2·N·k·n_held / E) and a tile per
  expert. ``buffer_rows``: the worst case, N·min(k, n_held) rows and a
  tile per expert. ``moe_layer`` takes the compact buffer whenever the
  routed tiles fit it, the worst case otherwise: a ``lax.switch`` on
  the device, so no token is ever dropped and the host never waits.
- ``expert_matmul``: a grouped matmul over the buffer (``expert_gmm``);
  its grid walks only the tiles that hold rows, so padding past them
  costs nothing. Its backward is ``expert_gmm_dx`` (the transposed
  weights) and ``expert_gmm_dw`` (the weights' gradient, per expert).
- ``dispatch`` and ``combine`` work on the buffer's rows: the dispatch
  gathers each row's token, the combine adds each valid row, by its
  slot's weight, into its token (a scatter-add), and each backward is
  the other's form.

``moe_layer`` runs them under the named scopes ``moe.router``,
``moe.dispatch``, ``moe.experts`` and ``moe.combine``, and computes the
taken branch's forward again in its backward, as ``jax.checkpoint``
would: only the layer's input, the weights and the integer routing are
kept, never the buffer. It returns the rows each held expert received.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.flash_attention import _pvary_to, _sds, _vma_union

TILE = 128  # buffer rows per grid step: the MXU's height
_W_BLOCK = 4 << 20  # bytes of one expert-weight block in VMEM
_VMEM_DEFAULT = 16 << 20


class Routing(NamedTuple):
    """Where each held (token, slot) assignment sits in the buffer."""
    held: jax.Array         # [N, k] bool: the slot's expert is held here
    row_token: jax.Array    # [M] int32: token of each buffer row (0: padding)
    row_slot: jax.Array     # [M] int32: flat slot of each row (0: padding)
    row_valid: jax.Array    # [M] bool: the row holds an assignment
    tile_expert: jax.Array  # [M / TILE] int32: expert of each tile
    n_tiles: jax.Array      # int32: tiles that hold rows
    counts: jax.Array       # [n_held] int32: rows each held expert received


def buffer_rows(n_tokens: int, top_k: int, n_held: int) -> int:
    """The worst case's height: every token's assignments held here (at
    most min(k, n_held) each) and a tile of padding per expert."""
    return n_tokens * min(top_k, n_held) + n_held * TILE


def compact_rows(n_tokens: int, top_k: int, n_held: int,
                 n_experts: int) -> int:
    """The compact buffer's height: twice the held experts' even share
    of the N·k assignments over all ``n_experts``, on whole tiles, and
    a tile per expert."""
    share = -(-2 * n_tokens * top_k * n_held // n_experts)
    return -(-share // TILE) * TILE + n_held * TILE


def router(h, w_router, top_k: int):
    """(weights [N, k] f32, experts [N, k] int32): softmax of h·W_r over
    all experts in float32, its top k, renormalised over them."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    w, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True), experts


def _held_onehot(experts, first: int, n_held: int):
    """(local expert of each flat slot, held [N*k] bool, one-hot [N*k,
    n_held] int32)."""
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    onehot = (local[:, None] == jnp.arange(n_held)[None]).astype(jnp.int32)
    return local, held, onehot


def _tiles(counts):
    """The tiles each held expert's rows fill: at least one."""
    return (jnp.maximum(counts, 1) + TILE - 1) // TILE


def tile_rows(counts):
    """The buffer rows the routing fills: each expert's rows on whole
    tiles."""
    return jnp.sum(_tiles(counts)) * TILE


def route(experts, first: int, n_held: int, m: int) -> Routing:
    """The layout of the assignments to experts ``first .. first +
    n_held - 1`` in a buffer of ``m`` rows: grouped by expert in token
    order, each expert's rows padded to whole tiles (at least one).
    Dropless where ``tile_rows`` fits ``m``."""
    n, k = experts.shape
    local, held, onehot = _held_onehot(experts, first, n_held)
    counts = jnp.sum(onehot, axis=0)
    # rank of each assignment among its expert's, in token order
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    tiles = _tiles(counts)
    ends = jnp.cumsum(tiles)
    start = (ends - tiles) * TILE
    row = jnp.where(held, start[jnp.clip(local, 0, n_held - 1)] + rank, m)
    slot = jnp.arange(n * k, dtype=jnp.int32)
    row_slot = jnp.zeros((m,), jnp.int32).at[row].set(slot, mode="drop")
    row_valid = jnp.zeros((m,), bool).at[row].set(True, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(m // TILE), side="right"),
        n_held - 1).astype(jnp.int32)
    return Routing(held=held.reshape(n, k), row_token=row_slot // k,
                   row_slot=row_slot, row_valid=row_valid,
                   tile_expert=tile_expert,
                   n_tiles=ends[-1].astype(jnp.int32), counts=counts)


# ------------------------------------------------------ grouped matmul
def _tile_n(n: int, k: int) -> int:
    """The widest output tile, a multiple of 128 dividing ``n``, whose
    [k, tile] bf16 weight block fits ``_W_BLOCK`` (``n`` itself where it
    is not a multiple of 128)."""
    if n % 128:
        return n
    best = 128
    for t in range(128, n + 1, 128):
        if n % t == 0 and k * t * 2 <= _W_BLOCK:
            best = t
    return best


def _params(nbytes: int):
    if nbytes <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(nbytes * 1.25))


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _gmm_kernel(te_ref, nt_ref, x_ref, w_ref, o_ref, *, transpose: bool):
    o_ref[...] = _dot(x_ref[...], w_ref[0],
                      ((1,), (1 if transpose else 0,))).astype(o_ref.dtype)


def gmm(x, w, tile_expert, n_tiles, transpose: bool = False,
        interpret: bool = False):
    """out [M, N] bf16: each TILE-row tile of x [M, K] times its expert's
    w [E, K, N] (``transpose``: w is [E, N, K], times its transpose),
    f32 accumulation. Tiles from ``n_tiles`` on are not visited and hold
    whatever was there."""
    m, kdim = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    tn = _tile_n(n, kdim)
    if transpose:
        w_spec = pl.BlockSpec((1, tn, kdim), lambda j, i, te, nt: (te[i], j, 0))
    else:
        w_spec = pl.BlockSpec((1, kdim, tn), lambda j, i, te, nt: (te[i], 0, j))
    nbytes = 2 * (TILE * kdim * 2 + kdim * tn * 2 + TILE * tn * 2)
    vma = _vma_union(x, w, tile_expert, n_tiles)
    x, w, tile_expert, n_tiles = (_pvary_to(a, vma)
                                  for a in (x, w, tile_expert, n_tiles))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # output tiles outer, the buffer's tiles inner: a weight block
            # is fetched again only where the expert changes
            grid=(n // tn, n_tiles),
            in_specs=[pl.BlockSpec((TILE, kdim),
                                   lambda j, i, te, nt: (i, 0)), w_spec],
            out_specs=pl.BlockSpec((TILE, tn), lambda j, i, te, nt: (i, j))),
        out_shape=_sds((m, n), jnp.bfloat16, vma),
        compiler_params=_params(nbytes),
        interpret=interpret,
        name="expert_gmm_dx" if transpose else "expert_gmm",
    )(tile_expert, n_tiles.reshape(1), x, w)


def _tgmm_kernel(te_ref, nt_ref, x_ref, dy_ref, o_ref):
    i = pl.program_id(1)
    first = (i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    o_ref[0] += _dot(x_ref[...], dy_ref[...], ((0,), (0,)))


def tgmm(x, dy, tile_expert, n_tiles, n_experts: int,
         interpret: bool = False):
    """dw [E, K, N] f32: per expert, the sum over its tiles of xᵀ·dy.
    An expert's tiles are consecutive and every expert has one, so each
    output block is zeroed at its first tile and written once."""
    m, kdim = x.shape
    n = dy.shape[1]
    tn = _tile_n(n, kdim)
    nbytes = 2 * (TILE * kdim * 2 + TILE * tn * 2 + kdim * tn * 4)
    vma = _vma_union(x, dy, tile_expert, n_tiles)
    x, dy, tile_expert, n_tiles = (_pvary_to(a, vma)
                                   for a in (x, dy, tile_expert, n_tiles))
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, n_tiles),
            in_specs=[pl.BlockSpec((TILE, kdim), lambda j, i, te, nt: (i, 0)),
                      pl.BlockSpec((TILE, tn), lambda j, i, te, nt: (i, j))],
            out_specs=pl.BlockSpec((1, kdim, tn),
                                   lambda j, i, te, nt: (te[i], 0, j))),
        out_shape=_sds((n_experts, kdim, n), jnp.float32, vma),
        compiler_params=_params(nbytes),
        interpret=interpret,
        name="expert_gmm_dw",
    )(tile_expert, n_tiles.reshape(1), x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel_matmul(x, w, tile_expert, n_tiles, interpret):
    return gmm(x, w.astype(jnp.bfloat16), tile_expert, n_tiles,
               interpret=interpret)


def expert_matmul(x, w, tile_expert, n_tiles, impl: str = "kernel"):
    """x [M, K] bf16 by tile times w [E, K, N] -> [M, N] bf16. ``impl``:
    "kernel" (the TPU's), "interpret" (the same kernels interpreted), or
    "xla", each tile against its expert's weights gathered, for backends
    without Pallas."""
    if impl == "xla":
        x3 = x.reshape(-1, TILE, x.shape[-1])
        return jnp.einsum("tmk,tkn->tmn", x3,
                          w.astype(jnp.bfloat16)[tile_expert],
                          preferred_element_type=jnp.float32).reshape(
                              x.shape[0], -1).astype(jnp.bfloat16)
    return _kernel_matmul(x, w, tile_expert, n_tiles, impl == "interpret")


def _em_fwd(x, w, tile_expert, n_tiles, interpret):
    wb = w.astype(jnp.bfloat16)
    return (gmm(x, wb, tile_expert, n_tiles, interpret=interpret),
            (x, wb, tile_expert, n_tiles, jnp.zeros((), w.dtype)))


def _em_bwd(interpret, res, dy):
    x, wb, tile_expert, n_tiles, w0 = res
    dy = dy.astype(jnp.bfloat16)
    dx = gmm(dy, wb, tile_expert, n_tiles, transpose=True,
             interpret=interpret)
    dw = tgmm(x, dy, tile_expert, n_tiles, wb.shape[0], interpret=interpret)
    # inside shard_map the weights may be replicated over mesh axes that
    # the rows vary on: their cotangent is the sum over those shards (the
    # psum AD inserts for a plain matmul; a custom_vjp is opaque to it)
    extra = tuple(jax.typeof(dw).vma - jax.typeof(wb).vma)
    if extra:
        dw = lax.psum(dw, extra)
    return dx.astype(x.dtype), dw.astype(w0.dtype), None, None


_kernel_matmul.defvjp(_em_fwd, _em_bwd)


# ------------------------------------------------ dispatch and combine
def _row_dest(r: Routing, n: int):
    """Each buffer row's token, and ``n`` (out of range: a scatter with
    ``mode="drop"`` skips it) for a padding row."""
    return jnp.where(r.row_valid, r.row_token, n)


def _scatter_rows(rows, r: Routing):
    """[N, D] f32: each valid buffer row of ``rows`` [M, D] added into
    its token; padding rows, whatever they hold, are skipped."""
    n = r.held.shape[0]
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[
        _row_dest(r, n)].add(rows.astype(jnp.float32), mode="drop")


@jax.custom_vjp
def dispatch(h, r: Routing):
    """The buffer [M, D]: each row the token it holds (padding: token
    0). Backward: each token sums its rows."""
    return h[r.row_token]


def _dispatch_fwd(h, r):
    return dispatch(h, r), (r, jnp.zeros((), h.dtype))


def _dispatch_bwd(res, g):
    r, h0 = res
    return _scatter_rows(g, r).astype(h0.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys, weights, r: Routing):
    """out [N, D] f32: each token's rows, by their slots' weights."""
    w_row = weights.reshape(-1)[r.row_slot]
    return _scatter_rows(w_row[:, None] * ys.astype(jnp.float32), r)


def _combine_fwd(ys, weights, r):
    return combine(ys, weights, r), (ys, weights, r)


def _combine_bwd(res, g):
    ys, weights, r = res
    n, k = r.held.shape
    g_row = g[r.row_token]
    w_row = weights.reshape(-1)[r.row_slot]
    dys = jnp.where(r.row_valid[:, None], w_row[:, None] * g_row,
                    0.0).astype(ys.dtype)
    # each held slot has one row: its weight's gradient is that row's
    # output against the token's cotangent
    dw_row = jnp.sum(ys.astype(jnp.float32) * g_row, axis=1)
    dw = jnp.zeros((n * k,), jnp.float32).at[
        jnp.where(r.row_valid, r.row_slot, n * k)].set(dw_row, mode="drop")
    return dys, dw.reshape(n, k).astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


def _experts(h, weights, r: Routing, w_gate, w_up, w_down, impl: str):
    with jax.named_scope("moe.dispatch"):
        xs = dispatch(h.astype(jnp.bfloat16), r)
    with jax.named_scope("moe.experts"):
        w_gu = jnp.concatenate([w_gate, w_up], axis=-1).astype(jnp.bfloat16)
        gu = expert_matmul(xs, w_gu, r.tile_expert, r.n_tiles, impl)
        f = w_gate.shape[-1]
        g, u = gu[:, :f].astype(jnp.float32), gu[:, f:].astype(jnp.float32)
        act = (jax.nn.silu(g) * u).astype(jnp.bfloat16)
        ys = expert_matmul(act, w_down.astype(jnp.bfloat16), r.tile_expert,
                           r.n_tiles, impl)
    with jax.named_scope("moe.combine"):
        return combine(ys, weights, r)


def _at(rows: int, first: int, impl: str):
    """The layer's held part in a buffer of ``rows``: routed, then the
    experts."""
    def layer(h, weights, experts, w_gate, w_up, w_down):
        with jax.named_scope("moe.dispatch"):
            r = route(experts, first, w_gate.shape[0], rows)
        return _experts(h, weights, r, w_gate, w_up, w_down, impl)
    return layer


def _vjp_at(rows: int, first: int, impl: str):
    """The held part's forward again and its backward, in a buffer of
    ``rows``: the cotangents of h, the weights and the experts'."""
    def layer_vjp(h, weights, experts, w_gate, w_up, w_down, g):
        def f(h, weights, w_gate, w_up, w_down):
            return _at(rows, first, impl)(h, weights, experts, w_gate, w_up,
                                          w_down)
        return jax.vjp(f, h, weights, w_gate, w_up, w_down)[1](g)
    return layer_vjp


def _branch(counts, heights):
    """0 where the routed tiles fit the first height, else the last."""
    return (tile_rows(counts) > heights[0]).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_part(heights, first, impl, counts, h, weights, experts, w_gate,
               w_up, w_down):
    """The held part at the first of ``heights`` that the routing fits.
    Its backward computes the forward again (as ``jax.checkpoint``
    would): only the inputs are kept, never the buffer."""
    return lax.switch(_branch(counts, heights),
                      [_at(m, first, impl) for m in heights],
                      h, weights, experts, w_gate, w_up, w_down)


def _held_fwd(heights, first, impl, counts, *args):
    return _held_part(heights, first, impl, counts, *args), (counts, args)


def _held_bwd(heights, first, impl, res, g):
    counts, args = res
    dh, dw, dg, du, dd = lax.switch(
        _branch(counts, heights), [_vjp_at(m, first, impl) for m in heights],
        *args, g)
    return None, dh, dw, None, dg, du, dd


_held_part.defvjp(_held_fwd, _held_bwd)


def moe_layer(h, w_router, w_gate, w_up, w_down, top_k: int,
              first: int = 0, impl=None):
    """The held experts' part of the layer for tokens h [N, D]: (out
    [N, D] f32, rows each held expert received [n_held] int32). w_router
    [D, E_all]; w_gate, w_up [n_held, D, F]; w_down [n_held, F, D]. The
    buffer is the compact one where the routed tiles fit it, else the
    worst case (one height where the compact is not the smaller). The
    grouped matmul is ``impl``'s (``expert_matmul``): by default the
    kernel on a TPU, XLA elsewhere."""
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    n, n_held = h.shape[0], w_gate.shape[0]
    with jax.named_scope("moe.router"):
        weights, experts = router(h, w_router, top_k)
    with jax.named_scope("moe.dispatch"):
        counts = jnp.sum(_held_onehot(experts, first, n_held)[2], axis=0)
    worst = buffer_rows(n, top_k, n_held)
    compact = compact_rows(n, top_k, n_held, w_router.shape[1])
    heights = (compact, worst) if compact < worst else (worst,)
    out = _held_part(heights, first, impl, counts, h, weights, experts,
                     w_gate, w_up, w_down)
    return out, counts
