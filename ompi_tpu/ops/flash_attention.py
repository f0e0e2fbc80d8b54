"""Pallas flash attention — the MXU-resident kernel under ring attention.

Reference analog: the hand-tuned SIMD op kernels of ompi/mca/op/avx
(op_avx_functions.c:31-39) — the place where the reference drops below its
portable C path for the hot loop. Here the hot loop is attention: the lax
formulation materializes the [B,H,T,T] score matrix in HBM (2GB at the
flagship shape — measured 13 TF/s effective), while this kernel streams
K/V tiles through VMEM with an online softmax; scores only ever exist at
[block_q, block_k] in fast memory.

Contract (shared with the lax fallback in ring_attention.py):

    flash_block(q, k, v, keep_full, keep_tri, sm_scale)
        -> out [B,Tq,H,D] float32 (normalized), lse [B,H,Tq] float32

- ``keep_full``/``keep_tri`` are traced 0/1 scalars selecting the ring
  block relation (full attend / causal triangle / neither) — they ride to
  SMEM so one compiled kernel serves every ring step.
- ``lse`` uses -1e30 (not -inf) as the empty-row sentinel: every exp/sub
  stays finite, so the ring's (out, lse) merge is AD-safe with no
  where-grad NaN traps.
- backward = custom_vjp with one Pallas kernel that RE-SCORES each
  (key tile, query tile) pair once from the saved (q, k, v, lse) and
  forms dq, dk and dv from it — the flash recompute trade: O(T)
  residuals instead of O(T^2).
- the lse cotangent is honored (it folds into the delta rows): the ring
  merge differentiates through exp(lse - lse_new), so g_lse != 0 mid-ring.

``flash_attention`` is the one-shard causal call with grouped K/V heads
(query head h reads K/V head h // group) and an optional sliding band
(query i sees key j iff 0 <= i - j < window). K/V stay [B, Hkv, T, D]:
the kernels read a K/V head's block for each of its query heads, and the
backward sums each group's dK and dV. Such calls carry their own
``pallas_call`` names (``flash_gqa_*``, ``flash_win_*``); the flagship's
keep ``flash_fwd`` and ``flash_dqkv``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30


def _vma_union(*xs):
    """Union of the operands' varying-mesh-axes sets (shard_map's vma
    tracking; empty outside shard_map)."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _pvary_to(x, vma):
    missing = tuple(vma - jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _sds(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pick_blocks(tq: int, tk: int, d: int) -> Tuple[int, int]:
    """Largest power-of-two tiles <= a head-dim-dependent cap that
    divide the shards (MXU-friendly: multiples of 128 when the sequence
    allows). Measured on v5e at T=1024: with d=64 the single 1024x1024
    tile beats 512x1024 by ~15% in-kernel (fewer grid invocations
    amortize the VPU softmax epilogue); with d=128 (full MXU
    contraction) the balance flips — 512x512 wins 16% because the
    dynamic causal bounds skip a quarter of the tile walk and the
    epilogue is relatively cheaper (r5 sweep: 2.64 vs 3.14 ms/layer
    fwd+bwd).

    The causal walk over these tiles is ``causal_walk``'s: tiles wholly
    below the diagonal unmasked, and a square diagonal tile in
    ``_DIAG_SUB``-wide sub-blocks that stop at the diagonal, each
    masking only its own square. Swept on v5e, one layer's fwd and the
    backward's then two kernels (dq; dk/dv) at [36, 8, 1024, 128] bf16:
    whole masked diagonal tiles 4.780 ms; off-diagonal tiles unmasked
    4.436; diagonal sub-blocks of 256 4.382, of 128 4.761 (128-row
    matmuls cost more than the elements they save, and dk/dv lost at
    both); with dk/dv scoring keys by queries, so that no matmul takes
    a transposed operand, 256 gives 4.269 and 128 4.252. 64 does not
    compile: lse rows are lane slices that start at multiples of 128.
    The one backward kernel that replaced the two keeps that
    orientation, so only its dq matmul takes a transposed operand: fwd
    1.119 + backward 1.922 ms, where the two kernels took 1.367 + 1.778
    (scoring queries by keys instead, so that dk and dv take the
    transposes: 1.968)."""
    cap = 1024 if d < 128 else 512
    bq = cap
    while bq > 1 and tq % bq:
        bq //= 2
    bk = cap
    while bk > 1 and tk % bk:
        bk //= 2
    return bq, bk


# a diagonal tile is walked in sub-blocks this wide (or whole, if
# narrower): the best of the sweep in _pick_blocks' docstring
_DIAG_SUB = 256


class Walk(NamedTuple):
    """The tile walk of a causal (keep_tri) call, per (b, h)."""

    block_q: int
    block_k: int
    sub: int      # diagonal sub-block width; 0 where the split is off
    visited: int  # score elements each of the two kernels computes
    masked: int   # of those, the elements that pass through the mask
    window: int = 0  # the band's width; 0: plain causal


def _tri_tiles(qi, block_q: int, block_k: int):
    """(tiles wholly at or below the diagonal, tiles touching it) for Q
    tile ``qi``, counted from KV tile 0 and not capped at the KV tiles."""
    full = (qi * block_q + 1) // block_k
    touch = (qi * block_q + block_q + block_k - 1) // block_k
    return full, touch


def _band_tiles(qi, block_q: int, block_k: int, window: int):
    """(first KV tile the band of Q tile ``qi`` touches, first KV tile
    wholly inside the band's lower edge), floored and not clamped."""
    lo = (qi * block_q - window + 1) // block_k
    lo_full = (qi * block_q + block_q - window + block_k - 1) // block_k
    return lo, lo_full


def causal_walk(tq: int, tk: int, d: int, window: int = 0) -> Walk:
    """The walk the kernels make for a Q shard of ``tq`` rows against a
    KV shard of ``tk`` with head dim ``d``: the blocks ``_pick_blocks``
    chooses, the diagonal sub-block (0 where the diagonal tile is not a
    square of whole 128-lane tiles, or a band narrower than a tile cuts
    it), and the elements a causal call scores and masks per (b, h), the
    same in both kernels. With a ``window`` the walk starts at the band's
    lower edge, whose tiles are masked whole. A full (keep_full) call
    scores tq*tk elements and masks none."""
    bq, bk = _pick_blocks(tq, tk, d)
    # lse rows are sliced on lanes: a sub-block spans whole 128-lane tiles
    square = bq == bk and tq == tk and bq % 128 == 0
    split = square and (not window or window >= bq)
    sub = min(_DIAG_SUB, bq) if split else 0
    n_kv = tk // bk
    visited = masked = 0
    for qi in range(tq // bq):
        full, touch = (min(x, n_kv) for x in _tri_tiles(qi, bq, bk))
        lo = lo_full = 0
        if window:
            lo, lo_full = _band_tiles(qi, bq, bk, window)
            lo = min(max(lo, 0), full)
            lo_full = min(max(lo_full, lo), full)
        visited += (full - lo) * bq * bk
        masked += (lo_full - lo) * bq * bk
        if sub:
            n = bq // sub
            visited += sub * sub * n * (n + 1) // 2
            masked += n * sub * sub
        else:
            visited += (touch - full) * bq * bk
            masked += (touch - full) * bq * bk
    return Walk(bq, bk, sub, visited, masked, window)


# a kernel asks for VMEM beyond the compiler's default scoped limit
# only as far as it needs, and never for more than _VMEM_MAX of the
# 128 MiB of a v5e core
_VMEM_DEFAULT = 16 << 20
_VMEM_MAX = 100 << 20


def _block_bytes(rows: int, d: int, nbytes: int) -> int:
    """VMEM bytes of a [rows, d] block: lanes are padded to 128."""
    return rows * -(-d // 128) * 128 * nbytes


def vmem_bytes(tq: int, tk: int, d: int, in_bytes: int) -> Tuple[int, int]:
    """(forward, backward) VMEM bytes of one (b, h) program: each block
    twice, for the pipeline's two buffers, and eight f32 score tiles of
    one pair. The forward holds a q tile, the whole k and v, an o tile
    and its lse rows; the backward the whole q, dO (f32), lse, delta and
    dq (f32), and a k, v, dk and dv tile."""
    bq, bk = _pick_blocks(tq, tk, d)
    scores = 8 * bq * bk * 4
    fwd = 2 * (_block_bytes(bq, d, in_bytes) + _block_bytes(bq, d, 4)
               + 2 * _block_bytes(tk, d, in_bytes) + 8 * bq * 4)
    bwd = 2 * (_block_bytes(tq, d, in_bytes) + 2 * _block_bytes(tq, d, 4)
               + 2 * 8 * tq * 4 + 2 * _block_bytes(bk, d, in_bytes)
               + 2 * _block_bytes(bk, d, 4))
    return fwd + scores, bwd + scores


def _vmem_params(nbytes: int):
    """A kernel's compiler params: the default where ``nbytes`` fits the
    default scoped limit (a limit set on a kernel is written into the
    ops around it too), else a limit of ``nbytes``."""
    if nbytes <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=nbytes)


def _causal(s, off, key_axis: int = 1, window: int = 0):
    """Scores ``s`` with every key after its query set to NEG_BIG, and
    with a ``window`` every key ``window`` or more before it too. Keys
    run along ``key_axis`` and queries along the other; ``off`` is the
    first query's index less the first key's."""
    keys = lax.broadcasted_iota(jnp.int32, s.shape, key_axis)
    queries = lax.broadcasted_iota(jnp.int32, s.shape, 1 - key_axis) + off
    keep = keys <= queries
    if window:
        keep = keep & (queries - keys < window)
    return jnp.where(keep, s, NEG_BIG)


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------- forward
def _q_walk(kfull, ktri, qi, walk: Walk, n_kv: int):
    """Dynamic KV-tile bounds for one Q tile: tiles [0, n_full) run
    unmasked and [n_full, hi) masked. All tiles unmasked when fully
    attending, the tiles touching the causal triangle when diagonal,
    none otherwise. DYNAMIC fori_loop bounds skip irrelevant tiles
    outright — the r3 kernel wrapped every tile in lax.cond and still
    paid the full T^2 tile walk — and split masked from unmasked tiles
    with no branch per tile (a per-tile lax.cond measured slower on v5e
    than masking every tile, r4 sweep)."""
    full, touch = _tri_tiles(qi, walk.block_q, walk.block_k)
    n_full = jnp.where(kfull, n_kv,
                       jnp.where(ktri, jnp.minimum(full, n_kv), 0))
    hi = jnp.where(kfull, n_kv,
                   jnp.where(ktri, jnp.minimum(touch, n_kv), 0))
    return n_full.astype(jnp.int32), hi.astype(jnp.int32)


def _fwd_kernel(kf_ref, kt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                walk: Walk, n_kv: int, sm_scale: float):
    block_q, block_k, sub = walk.block_q, walk.block_k, walk.sub
    window = walk.window
    qi = pl.program_id(1)
    kfull = kf_ref[0, 0] != 0.0
    ktri = kt_ref[0, 0] != 0.0
    q = q_ref[0].astype(jnp.bfloat16)  # [BQ, D]
    D = q_ref.shape[-1]

    def scores(qb, lo, width, off=None):
        """qb's scores against keys [lo, lo + width), causally masked
        when ``off`` is given, and those keys' values."""
        kb = k_ref[0, pl.ds(lo, width), :].astype(jnp.bfloat16)
        vb = v_ref[0, pl.ds(lo, width), :].astype(jnp.bfloat16)
        s = _dot(qb, kb, ((1,), (1,))) * sm_scale
        return (s if off is None else _causal(s, off, window=window)), vb

    def accumulate(parts, carry):
        """One online-softmax step over the (scores, values) parts of
        one row block."""
        acc, m, den = carry
        m_new = m
        for s, _ in parts:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        acc, den = acc * alpha, den * alpha
        for s, vb in parts:
            # no second where: masked entries hold NEG_BIG and every row
            # keeps >= 1 column, so exp underflows them to exactly 0
            p = jnp.exp(s - m_new)
            acc = acc + _dot(p.astype(jnp.bfloat16), vb, ((1,), (0,)))
            den = den + jnp.sum(p, axis=-1, keepdims=True)
        return acc, m_new, den

    def finish(rows, acc, m, den):
        o_ref[0, rows, :] = acc / jnp.maximum(den, 1e-30)
        lse = jnp.where(den[:, 0] > 0.0, m[:, 0] + jnp.log(den[:, 0]),
                        NEG_BIG)
        # lse rides in an 8-sublane broadcast layout (BH, 8, Tq): a
        # (1, BQ) tile would violate the TPU (8, 128) tiling rule
        lse_ref[0, :, rows] = lax.broadcast_in_dim(lse, (8, lse.shape[0]),
                                                   (1,))

    def full_body(i, carry):
        return accumulate([scores(q, i * block_k, block_k)], carry)

    def masked_body(i, carry):
        off = qi * block_q - i * block_k
        return accumulate([scores(q, i * block_k, block_k, off)], carry)

    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_BIG, jnp.float32)
    den0 = jnp.zeros((block_q, 1), jnp.float32)
    n_full, hi = _q_walk(kfull, ktri, qi, walk, n_kv)
    if window:
        # the band's lower edge: tiles [lo, lo_full) masked, then
        # [lo_full, n_full) unmasked (a band is causal: kfull is 0)
        lo, lo_full = _band_tiles(qi, block_q, block_k, window)
        lo = jnp.clip(lo, 0, n_full)
        lo_full = jnp.clip(lo_full, lo, n_full)
        carry = lax.fori_loop(lo, lo_full, masked_body, (acc0, m0, den0))
        carry = lax.fori_loop(lo_full, n_full, full_body, carry)
    else:
        carry = lax.fori_loop(0, n_full, full_body, (acc0, m0, den0))
    if not sub:
        finish(slice(None), *lax.fori_loop(n_full, hi, masked_body, carry))
        return
    diag = ktri & ~kfull
    pl.when(~diag)(lambda: finish(slice(None), *carry))

    @pl.when(diag)
    def _():
        # row sub-block r scores keys [0, (r+1)*sub) of the diagonal
        # tile; only its own sub x sub square crosses the diagonal
        base = qi * block_q
        for r in range(block_q // sub):
            rows = slice(r * sub, (r + 1) * sub)
            qr = q_ref[0, rows, :].astype(jnp.bfloat16)
            parts = [scores(qr, base, r * sub)] if r else []
            parts.append(scores(qr, base + r * sub, sub, 0))
            finish(rows, *accumulate(parts, tuple(x[rows] for x in carry)))


def _kv_map(group: int, tiled: bool):
    """Index map of a K/V block: query head ``bh`` reads K/V head
    ``bh // group``, its ``i``-th tile where ``tiled``, else whole."""
    def index(bh, i):
        b = bh if group == 1 else bh // group
        return (b, i, 0) if tiled else (b, 0, 0)
    return index


def _name(kind: str, group: int, window: int) -> str:
    """A kernel's pallas_call name: the flagship's plain causal calls
    keep ``flash_<kind>``; grouped or banded calls name themselves."""
    return "flash_" + ("gqa_" if group > 1 else "") + (
        "win_" if window else "") + kind


def _fwd_call(q3, k3, v3, kf, kt, sm_scale: float, walk: Walk,
              interpret: bool, group: int = 1):
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    block_q, block_k = walk.block_q, walk.block_k
    grid = (BH, Tq // block_q)
    kern = functools.partial(_fwd_kernel, walk=walk, n_kv=Tk // block_k,
                             sm_scale=sm_scale)
    in_bytes = max(x.dtype.itemsize for x in (q3, k3, v3))
    vma = _vma_union(q3, k3, v3, kf, kt)
    if vma:
        q3, k3, v3, kf, kt = (_pvary_to(x, vma)
                              for x in (q3, k3, v3, kf, kt))
    flags = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    kv = _kv_map(group, False)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=flags + [
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Tk, D), kv),
            pl.BlockSpec((1, Tk, D), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            _sds((BH, Tq, D), jnp.float32, vma),
            _sds((BH, 8, Tq), jnp.float32, vma),
        ],
        interpret=interpret,
        compiler_params=_vmem_params(vmem_bytes(Tq, Tk, D, in_bytes)[0]),
        name=_name("fwd", group, walk.window),
    )(kf, kt, q3, k3, v3)


# -------------------------------------------------------------- backward
def _bwd_kernel(kf_ref, kt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, *, walk: Walk, n_q: int,
                n_kv: int, sm_scale: float):
    """dq, dk and dv from one scoring of each (key tile, query tile)
    pair. The grid walks key tiles innermost; dq is one block per (b, h)
    that stays in VMEM across them, zeroed at the first and scaled at
    the last."""
    block_q, block_k, sub = walk.block_q, walk.block_k, walk.sub
    window = walk.window
    ki = pl.program_id(1)
    kfull = kf_ref[0, 0] != 0.0
    ktri = kt_ref[0, 0] != 0.0
    kb = k_ref[0].astype(jnp.bfloat16)            # [BK, D]
    vb = v_ref[0].astype(jnp.bfloat16)
    D = kb.shape[-1]

    @pl.when(ki == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

    def grad(kb, vb, lo, height, off=None, tail=0):
        """(dk, dv) of keys kb from queries [lo, lo + height), causally
        masked from key row ``tail`` on when ``off`` is given; those
        queries' dq from these keys is added into dq_ref. The scores
        are taken transposed, keys by queries, so that only the dq
        matmul takes a transposed operand and lse and delta are read as
        the lane rows they are stored in."""
        qb = q_ref[0, pl.ds(lo, height), :].astype(jnp.bfloat16)
        dob = do_ref[0, pl.ds(lo, height), :].astype(jnp.bfloat16)
        lse = lse_ref[0, 0:1, pl.ds(lo, height)]      # [1, height]
        delta = delta_ref[0, 0:1, pl.ds(lo, height)]
        st = _dot(kb, qb, ((1,), (1,))) * sm_scale
        if off is not None:
            # rows are whole vreg tiles on both sides of ``tail``: the
            # split and the concatenation move no data
            sq = _causal(st[tail:], off - tail, key_axis=0, window=window)
            st = jnp.concatenate([st[:tail], sq]) if tail else sq
        pt = jnp.exp(st - lse)  # masked entries underflow to exactly 0
        dv = _dot(pt.astype(jnp.bfloat16), dob, ((1,), (0,)))
        dst = pt * (_dot(vb, dob, ((1,), (1,))) - delta)
        dst = dst.astype(jnp.bfloat16)
        dk = _dot(dst, qb, ((1,), (0,)))
        dq_ref[0, pl.ds(lo, height), :] += _dot(dst, kb, ((0,), (0,)))
        return dk, dv

    def full_body(i, carry):
        dk, dv = grad(kb, vb, i * block_q, block_q)
        return carry[0] + dk, carry[1] + dv

    def masked_body(i, carry):
        dk, dv = grad(kb, vb, i * block_q, block_q,
                      i * block_q - ki * block_k)
        return carry[0] + dk, carry[1] + dv

    # q tiles [lo, lo_full) touch the diagonal and run masked, then
    # [lo_full, n_q) lie wholly below it; q tiles above the diagonal
    # attend to no key of this tile
    lo_tri = (ki * block_k) // block_q
    lo_full = ((ki + 1) * block_k + block_q - 2) // block_q
    lo = jnp.where(kfull, 0, jnp.where(ktri, lo_tri, n_q))
    lo_full = jnp.where(kfull, 0,
                        jnp.where(ktri, jnp.minimum(lo_full, n_q), n_q))
    lo, lo_full = lo.astype(jnp.int32), lo_full.astype(jnp.int32)
    zeros = jnp.zeros((block_k, D), jnp.float32)
    hi_full, carry = n_q, (zeros, zeros)
    if window:
        # with a band, q tiles [lo_full, hi_full) see every key of this
        # tile and [hi_full, hi) only some: the band's lower edge
        hi_full = jnp.clip((ki * block_k + window) // block_q, lo_full, n_q)
        hi = jnp.clip((ki * block_k + block_k + window + block_q - 2)
                      // block_q, hi_full, n_q)
        carry = lax.fori_loop(hi_full, hi, masked_body, carry)
    if not sub:
        dk, dv = lax.fori_loop(lo_full, hi_full, full_body,
                               lax.fori_loop(lo, lo_full, masked_body,
                                             carry))
        dk_ref[0] = dk * sm_scale
        dv_ref[0] = dv
    else:
        dk, dv = lax.fori_loop(lo_full, hi_full, full_body, carry)
        diag = ktri & ~kfull

        @pl.when(~diag)
        def _():
            dk_ref[0] = dk * sm_scale
            dv_ref[0] = dv

        @pl.when(diag)
        def _():
            # query sub-block r of the diagonal q tile reaches keys
            # [0, (r+1)*sub) of this tile; only its own sub x sub square
            # crosses the diagonal
            n = block_k // sub
            dks, dvs = [zeros[:sub]] * n, [zeros[:sub]] * n
            for r in range(n):
                a, b = grad(kb[:(r + 1) * sub], vb[:(r + 1) * sub],
                            ki * block_q + r * sub, sub, r * sub, r * sub)
                for c in range(r + 1):
                    dks[c] = dks[c] + a[c * sub:(c + 1) * sub]
                    dvs[c] = dvs[c] + b[c * sub:(c + 1) * sub]
            dk_ref[0] = (dk + jnp.concatenate(dks)) * sm_scale
            dv_ref[0] = dv + jnp.concatenate(dvs)

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0] = dq_ref[0] * sm_scale


def _bwd_call(q3, k3, v3, kf, kt, do3, lse, delta, sm_scale: float,
              walk: Walk, interpret: bool, group: int = 1):
    """dq [BH, Tq, D], and dk and dv per query head [BH, Tk, D] (a
    group's sum is the caller's), all f32."""
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    block_k = walk.block_k
    in_bytes = max(x.dtype.itemsize for x in (q3, k3, v3))
    vma = _vma_union(q3, k3, v3, kf, kt, do3, lse, delta)
    if vma:
        q3, k3, v3, kf, kt, do3, lse, delta = (
            _pvary_to(x, vma)
            for x in (q3, k3, v3, kf, kt, do3, lse, delta))
    flags = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    whole = lambda bh, ki: (bh, 0, 0)
    tile = lambda bh, ki: (bh, ki, 0)
    kv_tile = _kv_map(group, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, walk=walk, n_q=Tq // walk.block_q,
                          n_kv=Tk // block_k, sm_scale=sm_scale),
        grid=(BH, Tk // block_k),
        in_specs=flags + [
            pl.BlockSpec((1, Tq, D), whole),
            pl.BlockSpec((1, block_k, D), kv_tile),
            pl.BlockSpec((1, block_k, D), kv_tile),
            pl.BlockSpec((1, Tq, D), whole),
            pl.BlockSpec((1, 8, Tq), whole),
            pl.BlockSpec((1, 8, Tq), whole),
        ],
        # dq's block index is constant over the key tiles: it stays
        # resident and is written back once per (b, h)
        out_specs=[
            pl.BlockSpec((1, Tq, D), whole),
            pl.BlockSpec((1, block_k, D), tile),
            pl.BlockSpec((1, block_k, D), tile),
        ],
        out_shape=[
            _sds((BH, Tq, D), jnp.float32, vma),
            _sds((BH, Tk, D), jnp.float32, vma),
            _sds((BH, Tk, D), jnp.float32, vma),
        ],
        interpret=interpret,
        compiler_params=_vmem_params(vmem_bytes(Tq, Tk, D, in_bytes)[1]),
        name=_name("dqkv", group, walk.window),
    )(kf, kt, q3, k3, v3, do3, lse, delta)


# ------------------------------------------------------------ public API
def _to3(x, layout):
    """layout 'bthd': [B,T,H,D] -> [B*H,T,D] (a real transpose);
    layout 'bhtd': [B,H,T,D] -> [B*H,T,D] (a free reshape)."""
    if layout == "bhtd":
        B, H, T, D = x.shape
        return x.reshape(B * H, T, D)
    B, T, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)


def _from3(x, B, H, layout):
    BH, T, D = x.shape
    if layout == "bhtd":
        return x.reshape(B, H, T, D)
    return jnp.transpose(x.reshape(B, H, T, D), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, kf, kt, sm_scale, interpret, layout, window):
    out, _ = _flash_fwd(q, k, v, kf, kt, sm_scale, interpret, layout,
                        window)
    return out


def _flash_fwd(q, k, v, kf, kt, sm_scale, interpret, layout, window):
    if layout == "bhtd":
        B, H, Tq, D = q.shape
        Hkv, Tk = k.shape[1], k.shape[2]
    else:
        B, Tq, H, D = q.shape
        Tk, Hkv = k.shape[1], k.shape[2]
    walk = causal_walk(Tq, Tk, D, window)
    q3 = _to3(q, layout)
    k3 = _to3(k, layout)
    v3 = _to3(v, layout)
    o3, lse8 = _fwd_call(q3, k3, v3, kf, kt, sm_scale, walk, interpret,
                         H // Hkv)
    out = (_from3(o3, B, H, layout), lse8[:, 0, :].reshape(B, H, Tq))
    # the saved output rides in bf16: delta = rowsum(dO·O) tolerates the
    # rounding, and the f32 buffer would otherwise live across the whole
    # backward (134MB/layer at the flagship shape)
    return out, (q3, k3, v3, kf, kt, o3.astype(jnp.bfloat16), lse8, B, H)


def _flash_bwd(sm_scale, interpret, layout, window, res, g):
    q3, k3, v3, kf, kt, o3, lse8, B, H = res
    g_out, g_lse = g
    do3 = _to3(g_out, layout)
    walk = causal_walk(q3.shape[1], k3.shape[1], q3.shape[2], window)
    # delta rows fold BOTH cotangent sources: rowsum(dO*O) from the output
    # and -g_lse from the ring merge's exp(lse - lse_new) factors
    delta = jnp.sum(do3 * o3, axis=-1) - g_lse.reshape(q3.shape[0], -1)
    delta8 = jnp.broadcast_to(delta[:, None, :], lse8.shape)
    group = q3.shape[0] // k3.shape[0]
    dq3, dk3, dv3 = _bwd_call(q3, k3, v3, kf, kt, do3, lse8, delta8,
                              sm_scale, walk, interpret, group)
    if group > 1:
        # per query head -> per K/V head: the group's sum
        dk3, dv3 = (x.reshape(k3.shape[0], group, *x.shape[1:]).sum(1)
                    for x in (dk3, dv3))
    # the flags' zero cotangents keep the flags' own type: in the ring
    # they vary over the sp axis (axis_index), and a plain zeros((1, 1))
    # would not match it under shard_map
    Hkv = H // group
    return (_from3(dq3, B, H, layout).astype(q3.dtype),
            _from3(dk3, B, Hkv, layout).astype(k3.dtype),
            _from3(dv3, B, Hkv, layout).astype(v3.dtype),
            jnp.zeros_like(kf), jnp.zeros_like(kt))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_block(q, k, v, keep_full, keep_tri, sm_scale=None,
                interpret: bool = False, layout: str = "bthd"):
    """One Q-shard x KV-shard flash attention block pair.

    layout 'bthd' (default): q [B,Tq,H,D], k/v [B,Tk,H,D].
    layout 'bhtd' (fast path): q [B,H,Tq,D], k/v [B,H,Tk,D] — the kernel's
    native shape, so no transpose is emitted (the model should produce
    this layout directly). f32 in, bf16 on the MXU, f32 accumulation.
    keep_full / keep_tri: traced booleans/0-1 scalars for the ring block
    relation. Returns (out in the input layout, f32 normalized;
    lse [B,H,Tq] f32 with -1e30 empty sentinel).
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    kf = jnp.asarray(keep_full, jnp.float32).reshape(1, 1)
    kt = jnp.asarray(keep_tri, jnp.float32).reshape(1, 1)
    return _flash(q, k, v, kf, kt, float(sm_scale), bool(interpret),
                  str(layout), 0)


def flash_attention(q, k, v, window: int = 0, sm_scale=None,
                    interpret: bool = False):
    """Causal attention of one whole sequence, layout 'bhtd': q [B, H,
    T, D], k/v [B, Hkv, T, D] with H a multiple of Hkv, query head h
    reading K/V head h // (H / Hkv). With ``window`` > 0 query i sees key
    j iff 0 <= i - j < window. Returns out [B, H, T, D] f32, normalized.
    bf16 on the MXU, f32 accumulation."""
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads do not group over "
                         f"{k.shape[1]} K/V heads")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    kf = jnp.zeros((1, 1), jnp.float32)
    kt = jnp.ones((1, 1), jnp.float32)
    out, _ = _flash(q, k, v, kf, kt, float(sm_scale), bool(interpret),
                    "bhtd", int(window))
    return out


def flash_supported(q_shape, k_shape, layout: str = "bthd") -> bool:
    """Static gate: tiles must divide the shards, lse rows must be sliced
    in whole 128-lane tiles, query heads must group over the K/V heads,
    and both kernels must fit the VMEM a kernel may ask for (reckoned for
    f32 inputs, the widest they take). A grouped or banded call holds the
    same blocks per (b, h) program as a plain one: its K/V block is the
    group's, and dK, dV are per query head until the caller sums them."""
    if layout == "bhtd":
        B, H, Tq, D = q_shape
        Hkv, Tk = k_shape[1], k_shape[2]
    else:
        B, Tq, H, D = q_shape
        Tk, Hkv = k_shape[1], k_shape[2]
    if Tq < 8 or Tk < 8 or D % 8 or H % Hkv:
        return False
    bq, bk = _pick_blocks(Tq, Tk, D)
    if Tq % bq or Tk % bk or bq % 128 or bk < 8:
        return False
    return max(vmem_bytes(Tq, Tk, D, 4)) <= _VMEM_MAX
