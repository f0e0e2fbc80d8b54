"""Plain reference of Mellum2-12B-A2.5B's training step as the
``mellum2_ep8`` configuration cuts it, in float32 ``jax.numpy`` at
"highest" matmul precision: no Pallas kernel, no custom gradient, no
sharding, nothing imported from the program.

The model (JetBrains/Mellum2-12B-A2.5B-Instruct config.json): token
embedding, no positions added; per layer an RMSNorm (eps 1e-6), GQA
attention (query head h reads K/V head h // (H / Hkv)) with rotate-half
RoPE on q and k, causal on "full" layers with yarn frequencies and
their attention factor on cos and sin, causal within a band of
``window`` keys on "sliding" layers with default frequencies; an
output projection and a residual; a second RMSNorm and a sparse expert
layer: softmax over all the router's experts, top k, weights
renormalised over the k, SwiGLU experts (W_down(silu(h W_gate) ⊙ h W_up))
and a residual; a final RMSNorm, logits against an untied head; the
mean token cross-entropy; plain SGD. No biases.

Departures from the published model, the same as the program's (see
``benchmark/configs/mellum2_ep8.json``): the layers, experts and
vocabulary are this chip's share. Only the experts ``first ..
first + held - 1`` compute, densely over every token with a weight of
zero where a token did not pick them; what the absent experts add is
left out. The vocabulary is the slice the traffic draws from.
Positions count from 0 in every sequence. No router auxiliary loss and
no MTP head; SGD in place of AdamW.

Attention is computed in blocks of query rows under ``jax.checkpoint``,
so that the scores of a block, not of the whole sequence, are live;
each layer, and each expert within it, is recomputed in the backward
from its input, so that the reference fits on the chip once the
program's state is freed (the compiler reckons 2.91 GB of temporaries
for a v5e at the cell's size).

``mm_dtype`` rounds every matmul operand to that type (accumulating in
float32): the control of the correctness check. ``window_off`` runs the
sliding layers as full causal and ``renorm=False`` leaves the router's
renormalisation out: planted faults.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from benchmark.reference.lm import key_for, leaf_norms  # noqa: F401


class Shape(NamedTuple):
    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]   # "sliding" or "full", per layer
    window: int
    rope_theta: float
    yarn: Tuple[float, int, float, float, float]  # factor, original
    # max positions, beta_fast, beta_slow, attention factor
    n_experts: int                 # the router's width
    first_expert: int
    experts_held: int
    top_k: int
    d_expert: int
    seq_len: int
    lr: float
    eps: float = 1e-6


def init_params(key, s: Shape) -> Dict[str, Any]:
    """Seeded weights in the layout the program takes, float32: unit
    norm gains; the embedding drawn N(0, 1) (PyTorch's ``nn.Embedding``
    default); every other matrix normal over sqrt(fan-in), and the two
    that write into the residual stream (``wo``, ``w_down``) over a
    further sqrt(2 · layers), GPT-2's scaled residual init.

    So each token's own vector leads its residual stream and picks its
    experts, and the held experts' load is even, as a trained router's
    is. With the embedding at the other matrices' scale, a window's mean
    of values outweighs it: whole windows go to the same few experts,
    and the rows the held experts receive, the grouped matmul's work,
    swing from seed to seed."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(key, 2 + len(s.layer_types))
    D, H, Hkv, hd = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    E, F = s.experts_held, s.d_expert

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    resid = 1.0 / np.sqrt(2 * len(s.layer_types))
    blocks = []
    for i in range(len(s.layer_types)):
        k = jax.random.split(keys[2 + i], 8)
        blocks.append({
            "ln1": jnp.ones((D,), jnp.float32),
            "wq": normal(k[0], (D, H, hd), D),
            "wk": normal(k[1], (D, Hkv, hd), D),
            "wv": normal(k[2], (D, Hkv, hd), D),
            "wo": normal(k[3], (H, hd, D), H * hd) * resid,
            "ln2": jnp.ones((D,), jnp.float32),
            "router": normal(k[4], (D, s.n_experts), D),
            "w_gate": normal(k[5], (E, D, F), D),
            "w_up": normal(k[6], (E, D, F), D),
            "w_down": normal(k[7], (E, F, D), F) * resid,
        })
    return {"embed": normal(keys[0], (s.vocab, D), 1),
            "head": normal(keys[1], (s.vocab, D), D),
            "ln_f": jnp.ones((D,), jnp.float32),
            "blocks": blocks}


def token_batches(key, s: Shape, batch: int, count: int):
    """``count`` batches of uniform token ids from the vocabulary slice,
    [count, batch, T + 1]: tokens are [..., :T], targets [..., 1:]."""
    import jax

    return jax.random.randint(key, (count, batch, s.seq_len + 1), 0,
                              s.vocab, dtype=np.int32)


def _mm(spec, a, b, mm_dtype):
    import jax.numpy as jnp

    if mm_dtype is not None:
        a = a.astype(mm_dtype).astype(jnp.float32)
        b = b.astype(mm_dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def inv_freq(s: Shape, yarn: bool) -> np.ndarray:
    """The pairs' frequencies: θ^(−2i/d), or yarn's blend of them with
    θ^(−2i/d)/factor over a linear ramp between the correction dims of
    beta_fast and beta_slow rotations (HF ``_compute_yarn_parameters``)."""
    d, base = s.head_dim, s.rope_theta
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not yarn:
        return 1.0 / pos
    factor, orig, fast, slow, _ = s.yarn

    def dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)),
                                                     d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def _rope(x, s: Shape, yarn: bool):
    """x [B, heads, T, hd] rotated in the planes (i, i + hd/2)."""
    import jax.numpy as jnp

    T = x.shape[2]
    ang = np.arange(T)[:, None] * inv_freq(s, yarn)[None]
    scale = s.yarn[4] if yarn else 1.0
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1) * scale,
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1) * scale,
                      jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _attention(q, k, v, window: Optional[int], mm_dtype, rows: int):
    """Causal GQA attention, banded where ``window`` is given, in blocks
    of ``rows`` query rows. q [B, H, T, hd], k/v [B, Hkv, T, hd]."""
    import jax
    import jax.numpy as jnp

    B, H, T, hd = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, T, hd)
    rows = min(rows, T)
    j = jnp.arange(T)[None]

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, rows, axis=3)
        sc = _mm("bgjrd,bgtd->bgjrt", qb, k, mm_dtype) / np.sqrt(hd)
        i = start + jnp.arange(rows)[:, None]
        keep = j <= i
        if window is not None:
            keep = keep & (i - j < window)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _mm("bgjrt,bgtd->bgjrd", p, v, mm_dtype)

    out = jax.lax.map(block, jnp.arange(0, T, rows))  # [nb, B, g, j, r, d]
    return jnp.moveaxis(out, 0, 3).reshape(B, H, T, hd)


def moe(h, blk, s: Shape, mm_dtype=None, renorm: bool = True):
    """The held experts' part of the sparse layer for tokens h [N, D]:
    each held expert over every token, weighted by the token's routing
    weight for it (zero where the token did not pick it)."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(_mm("nd,de->ne", h, blk["router"], mm_dtype), -1)
    w, top = jax.lax.top_k(p, s.top_k)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def expert(out, xs):
        e, wg, wu, wd = xs
        c = jnp.sum(jnp.where(top == s.first_expert + e, w, 0.0), axis=-1)
        g = _mm("nd,df->nf", h, wg, mm_dtype)
        u = _mm("nd,df->nf", h, wu, mm_dtype)
        y = _mm("nf,fd->nd", jax.nn.silu(g) * u, wd, mm_dtype)
        return out + c[:, None] * y, None

    out, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(h),
                          (jnp.arange(s.experts_held), blk["w_gate"],
                           blk["w_up"], blk["w_down"]))
    return out


def _layer(x, blk, s: Shape, kind: str, mm_dtype, window_off: bool,
           renorm: bool, rows: int):
    B, T, _ = x.shape
    h = _rms(x, blk["ln1"], s.eps)
    q = _mm("btd,dhf->bhtf", h, blk["wq"], mm_dtype)
    k = _mm("btd,dhf->bhtf", h, blk["wk"], mm_dtype)
    v = _mm("btd,dhf->bhtf", h, blk["wv"], mm_dtype)
    yarn = kind == "full"
    q, k = _rope(q, s, yarn), _rope(k, s, yarn)
    window = None if yarn or window_off else s.window
    att = _attention(q, k, v, window, mm_dtype, rows)
    x = x + _mm("bhtf,hfd->btd", att, blk["wo"], mm_dtype)
    h2 = _rms(x, blk["ln2"], s.eps).reshape(B * T, -1)
    return x + moe(h2, blk, s, mm_dtype, renorm).reshape(B, T, -1)


def loss_sum(params, tokens, targets, s: Shape, mm_dtype=None,
             window_off: bool = False, renorm: bool = True,
             rows: int = 512):
    """Summed token cross-entropy of one block of sequences. Each layer
    runs under ``jax.checkpoint``: the backward recomputes it from its
    input, so that one layer's activations are live at a time."""
    import jax
    import jax.numpy as jnp

    x = params["embed"][tokens]
    for kind, blk in zip(s.layer_types, params["blocks"]):
        x = jax.checkpoint(functools.partial(
            _layer, s=s, kind=kind, mm_dtype=mm_dtype, window_off=window_off,
            renorm=renorm, rows=rows))(x, blk)
    x = _rms(x, params["ln_f"], s.eps)
    logits = _mm("btd,vd->btv", x, params["head"], mm_dtype)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(s: Shape, mm_dtype, window_off: bool, renorm: bool, rows: int):
    import jax

    def f(params, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_sum)(
                params, tokens, targets, s, mm_dtype, window_off, renorm,
                rows)
    return jax.jit(f)


def three_steps(params, batches, s: Shape, steps: int = 3, mm_dtype=None,
                keep_tokens: Optional[int] = None, window_off: bool = False,
                renorm: bool = True, rows: int = 512):
    """Plain SGD for ``steps`` steps on batches 0.. of ``batches``, one
    sequence at a time. Returns (losses, first gradient's leaf norms,
    leaf norms of the parameters' change after the last step).
    ``keep_tokens`` trains on the first tokens of each sequence only (a
    planted fault: half the batch's tokens); ``rows`` is the attention's
    block of query rows."""
    import jax

    fn = _grad_fn(s, mm_dtype, window_off, renorm, rows)
    p0 = p = params
    losses, g1 = [], None
    for i in range(steps):
        b = batches[i]
        if keep_tokens is not None:
            b = b[:, :keep_tokens + 1]
        total, grads = None, None
        for r in range(b.shape[0]):
            l, g = fn(p, b[r:r + 1, :-1], b[r:r + 1, 1:])
            total = l if total is None else total + l
            grads = g if grads is None else jax.tree.map(
                lambda a, c: a + c, grads, g)
        n = b.shape[0] * (b.shape[1] - 1)
        grads = jax.tree.map(lambda a: a / n, grads)
        losses.append(float(total) / n)
        if g1 is None:
            g1 = leaf_norms(grads)
        p = jax.tree.map(lambda a, d: a - s.lr * d, p, grads)
    change = leaf_norms(jax.tree.map(lambda a, c: a - c, p, p0))
    return losses, g1, change
