"""Ring attention: sequence-parallel causal attention over a mesh axis.

Long-context is first-class here: a sequence of length S is sharded S/sp
per device along an ``sp`` mesh axis; K/V blocks rotate around the ring via
``ppermute`` while each device's Q block accumulates attention with a
running (flash-style) log-sum-exp — so the full S×S score matrix never
materializes and per-device memory is O(S/sp · S/sp).

Reference analog (SURVEY.md §5 "long-context"): the segmented-ring
allreduce / RDMA pipeline machinery — the same decomposition (segment,
rotate, overlap) expressed as an XLA program. XLA overlaps each ppermute
with the previous block's attention math on TPU (async collective-permute
over ICI), which is the double-buffering the reference gets from its
pipeline protocols.

Causality across blocks: with block index b_q on the Q side and the K/V
block visiting from b_kv, the block attends fully when b_kv < b_q, with a
triangular mask when b_kv == b_q, and not at all when b_kv > b_q (the
contribution is masked to -inf before the softmax accumulator).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np


def _block_attend(q, k, v, keep_full, keep_tri, sm_scale, mxu_dtype,
                  chunk: int):
    """One Q-block × KV-block partial attention, CHUNKED over the KV dim
    (flash-style): peak memory is O(Tq·chunk) instead of O(Tq·Tk), and
    with ``mxu_dtype=bfloat16`` both matmuls run at MXU rate with f32
    accumulation. Masks come from iota comparisons — the Tq×Tk boolean
    never materializes.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; keep_full / keep_tri: traced
    scalars selecting the block relation (full attend / causal triangle /
    neither). Returns (numerator [B, Tq, H, D], row_max [B, H, Tq],
    row_sum [B, H, Tq]).
    """
    import jax.numpy as jnp
    from jax import lax

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    chunk = min(chunk, Tk)
    while Tk % chunk:
        chunk //= 2  # Tk is a shard of a power-of-two-ish seq; stay exact
    n_chunks = Tk // chunk
    md = mxu_dtype or jnp.float32
    qm = q.astype(md)
    rows = jnp.arange(Tq)[:, None]  # global row index within the block

    def body(carry, c):
        acc, m, den = carry
        k_c = lax.dynamic_slice_in_dim(k, c * chunk, chunk, 1).astype(md)
        v_c = lax.dynamic_slice_in_dim(v, c * chunk, chunk, 1).astype(md)
        s = jnp.einsum("bqhd,bkhd->bhqk", qm, k_c,
                       preferred_element_type=jnp.float32) * sm_scale
        cols = c * chunk + jnp.arange(chunk)[None, :]
        keep = keep_full | (keep_tri & (cols <= rows))  # [Tq, chunk]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        m_p = jnp.max(s, axis=-1)  # [B, H, Tq]
        m_new = jnp.maximum(m, m_p)
        safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe[..., None])
        p = jnp.where(keep[None, None], p, 0.0)
        num_p = jnp.einsum("bhqk,bkhd->bqhd", p.astype(md), v_c,
                           preferred_element_type=jnp.float32)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - safe))
        acc = acc * _bhq_to_bqh1(alpha) + num_p
        den = den * alpha + jnp.sum(p, axis=-1)
        return (acc, m_new, den), None

    # seed the carry from a varying zero: inside shard_map the scan's
    # carry type must match the body output, which varies over the ring
    # axis (it depends on q) — a plain zeros() literal would be typed
    # unvarying and reject
    vzero = q[0, 0, 0, 0].astype(jnp.float32) * 0.0
    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32) + vzero
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32) + vzero
    den0 = jnp.zeros((B, H, Tq), jnp.float32) + vzero
    import jax

    # checkpoint the chunk body: backward re-scores the tile instead of
    # storing every chunk's probability matrix (the flash-backward
    # recompute — without this, scan AD keeps O(n_chunks · Tq · chunk)
    # residuals and training uses MORE memory than dense attention)
    (acc, m, den), _ = lax.scan(jax.checkpoint(body), (acc0, m0, den0),
                                jnp.arange(n_chunks))
    return acc, m, den


NEG_BIG = -1e30


def _lax_block(q, k, v, keep_full, keep_tri, sm_scale, mxu_dtype,
               chunk: int):
    """(out, lse) via the chunked lax path — the portable fallback behind
    the Pallas kernel (ops/flash_attention.py), sharing its contract:
    normalized out [B,Tq,H,D] f32 + lse [B,H,Tq] f32 with -1e30 empty
    sentinel."""
    import jax.numpy as jnp

    acc, m, den = _block_attend(q, k, v, keep_full, keep_tri, sm_scale,
                                mxu_dtype, chunk)
    # epsilon must survive SQUARING in f32 (the division VJP computes
    # -g*acc/den^2; (1e-30)^2 underflows to 0 and births NaNs on
    # fully-masked rows). Any attended row has den >= 1, so 1e-9 is free.
    out = acc / jnp.maximum(_bhq_to_bqh1(den), 1e-9)
    lse = jnp.where(den > 0.0,
                    jnp.where(jnp.isneginf(m), NEG_BIG, m) + jnp.log(
                        jnp.maximum(den, 1e-9)),
                    NEG_BIG)
    return out, lse


def use_flash_default(q_shape, k_shape, layout: str = "bthd") -> bool:
    """Pick the Pallas kernel when the default backend is a TPU and the
    shapes tile cleanly; the lax path covers everything else (CPU meshes,
    odd shapes)."""
    import jax

    from ompi_tpu.ops.flash_attention import flash_supported

    return jax.default_backend() == "tpu" and flash_supported(
        q_shape, k_shape, layout)


def ring_attention(q, k, v, axis_name: str, sp_size: int,
                   sm_scale: Optional[float] = None, causal: bool = True,
                   mxu_dtype=None, chunk: int = 512,
                   use_flash: Optional[bool] = None,
                   layout: str = "bthd"):
    """Sequence-parallel attention inside shard_map.

    q, k, v: local shards on each device of the ``axis_name`` ring
    (sp_size devices) — [B, S/sp, H, D] with layout 'bthd' (default) or
    [B, H, S/sp, D] with layout 'bhtd' (the kernel-native fast path: no
    transposes are emitted). Returns the local output shard in the input
    layout. Each ring step computes one Q-shard x KV-shard block pair —
    through the Pallas flash kernel on TPU (ops/flash_attention.py) or
    the chunked lax path elsewhere — and merges the partials in
    (out, lse) space, the flash-style log-sum-exp combine.
    ``mxu_dtype=jnp.bfloat16`` runs the lax path's matmuls at MXU rate
    (the kernel is always bf16-MXU with f32 accumulation); ``chunk``
    bounds the lax path's KV tile.
    """
    import jax.numpy as jnp
    from jax import lax

    if layout == "bhtd":
        B, H, T, D = q.shape
    else:
        B, T, H, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    if use_flash is None:
        use_flash = use_flash_default(q.shape, k.shape, layout)
    def one_block(k_blk, v_blk, keep_full, keep_tri):
        """One Q-shard x KV-shard block pair -> (out, lse), via the
        Pallas kernel or the chunked lax fallback."""
        if use_flash:
            from ompi_tpu.ops.flash_attention import flash_block

            return flash_block(q, k_blk, v_blk, keep_full, keep_tri,
                               sm_scale, layout=layout)
        if layout == "bhtd":
            # lax fallback is bthd-native; transpose at the boundary
            tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
            o_p, lse_p = _lax_block(tr(q), tr(k_blk), tr(v_blk),
                                    keep_full, keep_tri, sm_scale,
                                    mxu_dtype, chunk)
            return tr(o_p), lse_p
        return _lax_block(q, k_blk, v_blk, keep_full, keep_tri, sm_scale,
                          mxu_dtype, chunk)

    if sp_size == 1:
        # degenerate ring: one block pair, already normalized — skip the
        # (out, lse) merge entirely (its exp/logaddexp chain costs real
        # HBM traffic and makes g_lse live in backward for nothing)
        o, _ = one_block(k, v, jnp.bool_(not causal), jnp.bool_(causal))
        return o.astype(q.dtype)

    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    def lift(lse_bht):
        """[B,H,T] row stats broadcast against the output layout."""
        if layout == "bhtd":
            return lse_bht[..., None]
        return _bhq_to_bqh1(lse_bht)

    # running (out, lse) accumulators — vzero makes the carry vary over
    # the ring axis for shard_map's replication checker
    vzero = q.reshape(-1)[0].astype(jnp.float32) * 0.0
    out = jnp.zeros(q.shape, jnp.float32) + vzero
    lse = jnp.full((B, H, T), NEG_BIG, jnp.float32) + vzero

    kv = (k, v)

    for step in range(sp_size):
        kv_idx = (my - step) % sp_size  # whose block we hold this step
        k_blk, v_blk = kv
        if causal:
            # traced block relation: full attend / causal triangle / none
            keep_full = kv_idx < my
            keep_tri = kv_idx == my
        else:
            keep_full = jnp.bool_(True)
            keep_tri = jnp.bool_(False)
        o_p, lse_p = one_block(k_blk, v_blk, keep_full, keep_tri)
        # log-sum-exp merge of normalized partials (all finite: -1e30
        # sentinel keeps the exps and their gradients NaN-free)
        lse_new = jnp.logaddexp(lse, lse_p)
        out = (out * lift(jnp.exp(lse - lse_new)) +
               o_p * lift(jnp.exp(lse_p - lse_new)))
        lse = lse_new
        if step != sp_size - 1:
            kv = lax.ppermute(kv, axis_name, perm)

    return out.astype(q.dtype)


def _bhq_to_bqh1(x):
    """[B, H, T] -> [B, T, H, 1] for broadcasting against [B, T, H, D]."""
    return x.transpose(0, 2, 1)[..., None]


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                           causal: bool = True):
    """Driver-level entry: q/k/v are global [B, S, H, D] arrays sharded (or
    shardable) over ``axis_name`` on the sequence dim; returns the global
    attention output with the same sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp = int(mesh.shape[axis_name])
    spec = P(None, axis_name, None, None)

    def local(qb, kb, vb):
        return ring_attention(qb, kb, vb, axis_name, sp, causal=causal)

    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return jax.jit(sm)(q, k, v)


def reference_attention(q, k, v, causal: bool = True):
    """Dense O(S²) reference for testing (host/numpy-style, jax arrays)."""
    import jax.numpy as jnp

    B, S, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(float(D))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
