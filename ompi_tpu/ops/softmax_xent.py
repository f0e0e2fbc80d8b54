"""Chunked softmax cross-entropy: the loss-side flash trick.

The naive causal-LM loss materializes the full [B, T, V] logits tensor in
f32 (4.3 GB at the flagship shape) and lets AD keep it (or its softmax)
alive across the whole backward — at the HBM ceiling XLA starts spilling
and the measured cost was ~64 ms/step plus the memory pressure that
slowed attention down (r4 ablation, tools/profile_mfu.py).

This op streams the vocabulary projection in sequence chunks. Without
AD the scan scores each chunk and keeps only the loss. Under AD the
custom_vjp's forward rule computes the gradient in the same scan: the
loss is a scalar, so its cotangent only scales the result, and each
chunk's (softmax - onehot) rows feed the dx / dW matmuls while its
logits are live. Three vocabulary matmuls a chunk, none recomputed; the
backward scales the saved f32 (dx, dW) by the cotangent. Peak live
logits memory drops from O(B·T·V) to O(B·Tc·V) (fused linear
cross-entropy, as Liger-Kernel's).

Reference analog: the segmented-pipeline discipline of
ompi/mca/coll/base/coll_base_allreduce.c:622 (never hold the whole
message; stream segments through a bounded working set), applied to the
model's largest tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _chunk_count(T: int, chunk_t: int) -> int:
    c = min(chunk_t, T)
    while T % c:
        c //= 2
    return max(c, 1)


def _chunks(x, targets, chunk_t: int):
    """x [B, T, D] and targets [B, T] as [nc, B, Tc, ...] scan operands."""
    B, T, D = x.shape
    Tc = _chunk_count(T, chunk_t)
    nc = T // Tc
    return (x.reshape(B, nc, Tc, D).transpose(1, 0, 2, 3),
            targets.reshape(B, nc, Tc).transpose(1, 0, 2))


def _chunk_loss(xb, w, tb):
    """One chunk's logits [B, Tc, V] f32, per-row logsumexp and summed
    loss term."""
    logits = logits_matmul(xb, w)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    # gold logit via the gathered embedding ROW (a [B,Tc,D] gather +
    # rowwise dot), not take_along_axis over the [B,Tc,V] logits —
    # one fewer full pass over the chunk's largest tensor. Matmul
    # in the same bf16/f32-accum regime as logits_matmul so the
    # values agree bit-for-bit in spirit (tested to bf16 tolerance).
    wrows = w[tb].astype(jnp.bfloat16)  # [B, Tc, D]
    gold = jnp.einsum("btd,btd->bt", xb.astype(jnp.bfloat16), wrows,
                      preferred_element_type=jnp.float32)
    return logits, lse, jnp.sum(lse - gold)


def _vzero(x):
    # inside shard_map a scan carry must carry the body's varying
    # mesh-axes type (it depends on x), which a plain zeros literal lacks
    return x.reshape(-1)[0].astype(jnp.float32) * 0.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def softmax_xent_sum(x, w, targets, chunk_t: int = 128,
                     psum_axes: tuple = ()):
    """sum over (b, t) of [logsumexp_v(x·wᵀ) - (x·wᵀ)[target]].

    x: [B, T, D] features (any float dtype; matmuls run bf16 on the MXU
    with f32 accumulation), w: [V, D] output embedding, targets: [B, T]
    int. Returns a f32 scalar. ``chunk_t`` bounds the live logits to
    [B, chunk_t, V].

    Inside shard_map with x sharded over data axes and w replicated,
    pass those mesh axis names as ``psum_axes``: custom_vjp is opaque to
    the psum AD auto-inserts for replicated operands, so w's cotangent
    must be explicitly summed across the shards that saw different
    (b, t) cells. Omitting it outside shard_map is fine.
    """
    def body(tot, args):
        xb, tb = args
        return tot + _chunk_loss(xb, w, tb)[2], None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32) + _vzero(x),
                        _chunks(x, targets, chunk_t))
    return total


def logits_matmul(xc, w):
    """[B, T, D] x [V, D] -> [B, T, V] f32 (bf16 on the MXU) — the one
    vocab-projection einsum, shared by the streamed loss chunks and the
    model's dense inference path."""
    return jnp.einsum("btd,vd->btv", xc.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _xent_fwd(x, w, targets, chunk_t: int, psum_axes: tuple = ()):
    B, T, D = x.shape
    wb = w.astype(jnp.bfloat16)

    def body(carry, args):
        tot, dw = carry
        xb, tb = args
        logits, lse, loss = _chunk_loss(xb, w, tb)
        p = jnp.exp(logits - lse[..., None])  # softmax rows
        onehot = jax.nn.one_hot(tb, w.shape[0], dtype=p.dtype)
        d = (p - onehot).astype(jnp.bfloat16)  # [B, Tc, V]
        dx = jnp.einsum("btv,vd->btd", d, wb,
                        preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("btv,btd->vd", d, xb.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
        return (tot + loss, dw), dx

    vzero = _vzero(x)
    (total, dw), dxc = lax.scan(
        body, (jnp.zeros((), jnp.float32) + vzero,
               jnp.zeros(w.shape, jnp.float32) + vzero),
        _chunks(x, targets, chunk_t))
    dx = dxc.transpose(1, 0, 2, 3).reshape(B, T, D)
    # the 0-d zeros carry the primal dtypes to the cotangents' casts
    return total, (dx, dw, jnp.zeros((), x.dtype), jnp.zeros((), w.dtype))


def _xent_bwd(chunk_t: int, psum_axes: tuple, res, g):
    dx, dw, x0, w0 = res
    # w is replicated over the data axes x varies on (shard_map vma): its
    # cotangent must be the cross-shard SUM — the psum AD auto-inserts for
    # plain einsums, made explicit here because custom_vjp is opaque to it
    gf = g.astype(jnp.float32)
    dw = gf * dw  # fold the loss cotangent BEFORE the psum so the
    dx = gf * dx  # result's vma matches the replicated primal
    if psum_axes:
        dw = lax.psum(dw, tuple(psum_axes))
    return (dx.astype(x0.dtype), dw.astype(w0.dtype),
            np.zeros(dx.shape[:2], dtype=jax.dtypes.float0))


softmax_xent_sum.defvjp(_xent_fwd, _xent_bwd)


def reference_xent_sum(x, w, targets):
    """Dense O(B·T·V) reference for testing."""
    logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)
