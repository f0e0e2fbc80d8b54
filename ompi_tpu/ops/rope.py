"""Rotary position embedding (RoPE), rotate-half form, with the yarn
frequencies of long-context checkpoints.

A head vector x of width d is split into halves (x1, x2) and rotated by
angle t·f_i in the plane of pair (i, i + d/2):

    rope(x, t) = x · cos(t·f) + rotate_half(x) · sin(t·f),
    rotate_half(x) = (−x2, x1),

with cos and sin laid out as [f, f] over the d lanes. Default
frequencies are f_i = θ^(−2i/d). Yarn (Peng et al., arXiv:2309.00071,
as HF transformers' ``_compute_yarn_parameters`` computes it) blends the
original frequencies with ones divided by ``factor``, by a linear ramp
over the pairs between the correction dims of ``beta_fast`` and
``beta_slow`` rotations, and scales cos and sin by ``attention_factor``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import numpy as np


class Yarn(NamedTuple):
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    """The pair index whose wavelength makes ``rotations`` turns over
    ``max_pos`` positions."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) / (
        2 * math.log(base))


def yarn_ramp(dim: int, base: float, yarn: Yarn):
    """(low, high) pair indices of the ramp, floored and ceiled as HF
    truncates them, and clamped to [0, dim - 1]."""
    low = math.floor(_correction_dim(yarn.beta_fast, dim, base,
                                     yarn.original_max_position_embeddings))
    high = math.ceil(_correction_dim(yarn.beta_slow, dim, base,
                                     yarn.original_max_position_embeddings))
    return max(low, 0), min(high, dim - 1)


def inv_freq(dim: int, base: float, yarn: Optional[Yarn] = None):
    """float64 [dim/2] angular frequencies of the pairs."""
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos
    if yarn is None:
        return extrapolation
    interpolation = 1.0 / (yarn.factor * pos)
    low, high = yarn_ramp(dim, base, yarn)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the original frequency, 0: interpolated
    return interpolation * (1.0 - keep) + extrapolation * keep


def tables(positions, dim: int, base: float, yarn: Optional[Yarn] = None):
    """(cos, sin) float32 [T, dim] for integer ``positions`` [T], scaled
    by yarn's attention factor where given."""
    import jax.numpy as jnp

    f = jnp.asarray(inv_freq(dim, base, yarn), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * f[None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    scale = 1.0 if yarn is None else yarn.attention_factor
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply(x, cos, sin):
    """x [..., T, dim] rotated by the tables [T, dim], in float32, in
    x's dtype."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)
