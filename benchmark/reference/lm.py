"""Plain reference of the flagship causal LM (models/transformer.py), in
float32 ``jax.numpy`` at "highest" matmul precision: no Pallas kernel,
no custom gradient, no sharding, nothing imported from the program.

The model: a tied embedding plus learned positions; per layer a
gain-only layer norm (eps 1e-6), causal multi-head attention with a
fused [D, H, 3·hd] qkv weight, an output projection, a residual, a
second gain-only norm and a ReLU MLP with a residual; a final norm and
logits against the embedding; the mean token cross-entropy; plain SGD.

``mm_dtype`` rounds every matmul operand to that type (accumulating in
float32): the control of the correctness check computes the reference
in a precision below the one the configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import numpy as np


class Shape(NamedTuple):
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    seq_len: int
    lr: float

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_params(key, s: Shape) -> Dict[str, Any]:
    """Seeded weights in the layout the program takes: normal draws
    scaled by 1/sqrt(fan-in), unit norm gains, float32."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(key, 2 + s.n_layers)
    D, F, H, hd = s.d_model, s.d_ff, s.n_heads, s.head_dim

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    blocks = []
    for i in range(s.n_layers):
        k1, k2, k3, k4 = jax.random.split(keys[2 + i], 4)
        blocks.append({
            "ln1": jnp.ones((D,), jnp.float32),
            "qkv": normal(k1, (D, H, 3 * hd), D),
            "wo": normal(k2, (D, D), D),
            "ln2": jnp.ones((D,), jnp.float32),
            "w1": normal(k3, (D, F), D),
            "w2": normal(k4, (F, D), F),
        })
    return {"embed": normal(keys[0], (s.vocab, D), D),
            "pos": normal(keys[1], (s.seq_len, D), D),
            "ln_f": jnp.ones((D,), jnp.float32),
            "blocks": blocks}


def token_batches(key, s: Shape, batch: int, count: int):
    """``count`` batches of uniform token ids, [count, batch, T + 1]:
    tokens are [..., :T] and next-token targets [..., 1:]."""
    import jax

    return jax.random.randint(key, (count, batch, s.seq_len + 1), 0,
                              s.vocab, dtype=np.int32)


def _mm(spec, a, b, mm_dtype):
    import jax.numpy as jnp

    if mm_dtype is not None:
        a = a.astype(mm_dtype).astype(jnp.float32)
        b = b.astype(mm_dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def _norm(x, g):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def loss_sum(params, tokens, targets, s: Shape, mm_dtype=None):
    """Summed token cross-entropy of one block of rows."""
    import jax
    import jax.numpy as jnp

    B, T = tokens.shape
    H, hd = s.n_heads, s.head_dim
    x = params["embed"][tokens] + params["pos"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for blk in params["blocks"]:
        h = _norm(x, blk["ln1"])
        qkv = _mm("btd,dhf->bhtf", h, blk["qkv"], mm_dtype)
        q, k, v = qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:]
        sc = _mm("bhqf,bhkf->bhqk", q, k, mm_dtype) / np.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        att = _mm("bhqk,bhkf->bhqf", jax.nn.softmax(sc, axis=-1), v,
                  mm_dtype)
        x = x + _mm("bhtf,hfd->btd", att,
                    blk["wo"].reshape(H, hd, s.d_model), mm_dtype)
        h2 = _norm(x, blk["ln2"])
        ff = jnp.maximum(_mm("btd,df->btf", h2, blk["w1"], mm_dtype), 0.0)
        x = x + _mm("btf,fd->btd", ff, blk["w2"], mm_dtype)
    x = _norm(x, params["ln_f"])
    logits = _mm("btd,vd->btv", x, params["embed"], mm_dtype)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _grad_block(s: Shape, mm_dtype):
    import jax

    def f(params, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_sum)(params, tokens, targets, s,
                                                mm_dtype)
    return jax.jit(f)


def loss_and_grad(params, tokens, targets, s: Shape, rows: int,
                  mm_dtype=None):
    """Mean loss and its gradient over the whole batch, computed in
    blocks of ``rows`` rows so that the float32 attention scores fit."""
    import jax

    B = tokens.shape[0]
    fn = _grad_block(s, mm_dtype)
    total, grads = None, None
    for r in range(0, B, rows):
        l, g = fn(params, tokens[r:r + rows], targets[r:r + rows])
        total = l if total is None else total + l
        grads = g if grads is None else jax.tree.map(
            lambda a, b: a + b, grads, g)
    n = B * tokens.shape[1]
    return total / n, jax.tree.map(lambda a: a / n, grads)


def leaf_norms(tree) -> np.ndarray:
    """Frobenius norm of every leaf, in ``jax.tree`` order."""
    import jax
    import jax.numpy as jnp

    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32))))) for a in jax.tree.leaves(tree)])


def three_steps(params, batches, s: Shape, rows: int, steps: int = 3,
                mm_dtype=None, keep_rows=None):
    """Plain SGD for ``steps`` steps on batches 0.. of ``batches``.
    Returns (losses, first gradient's leaf norms, leaf norms of the
    parameters' change after the last step). ``keep_rows`` trains on
    only the first rows of each batch (a planted fault)."""
    import jax

    p0 = params
    p = params
    losses, g1 = [], None
    for i in range(steps):
        b = batches[i]
        if keep_rows is not None:
            b = b[:keep_rows]
        loss, g = loss_and_grad(p, b[:, :-1], b[:, 1:], s, rows, mm_dtype)
        losses.append(float(loss))
        if g1 is None:
            g1 = leaf_norms(g)
        p = jax.tree.map(lambda a, d: a - s.lr * d, p, g)
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))
    return losses, g1, change


def key_for(seed: int, stream: int):
    """A JAX key from any whole-number seed (more than 32 bits allowed)
    and a stream number."""
    import jax

    word = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray) -> float:
    """max over kept leaves of |prog − ref| / max(ref, median ref): the
    gap between two leaf norms, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    floor = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)
    return float(np.max(gaps))


def moving_leaves(ref_grad: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the others move by round-off alone."""
    return ref_grad >= 1e-3 * np.median(ref_grad)
