"""Rotary positions (ops/rope.py): default and yarn frequencies against
their closed forms at Mellum2's values, and the rotation itself."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ompi_tpu.ops import rope

THETA, D = 500000.0, 128
YARN = rope.Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)


def test_default_frequencies_closed_form():
    i = np.arange(D // 2)
    np.testing.assert_allclose(rope.inv_freq(D, THETA),
                               THETA ** (-2.0 * i / D), rtol=1e-12)


def test_yarn_ramp_at_mellum_values():
    """beta_fast 32 and beta_slow 1 over 8192 original positions at
    θ 500000: the ramp runs between pairs 18 and 35."""
    d32 = D * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(THETA))
    d1 = D * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(THETA))
    assert (math.floor(d32), math.ceil(d1)) == (18, 35)
    assert rope.yarn_ramp(D, THETA, YARN) == (18, 35)


def test_yarn_frequencies_closed_form():
    """Pairs up to 18 keep θ^(−2i/d); from 35 on it is divided by the
    factor; between, the blend is linear in the pair index."""
    base = THETA ** (-2.0 * np.arange(D // 2) / D)
    f = rope.inv_freq(D, THETA, YARN)
    np.testing.assert_allclose(f[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(f[35:], base[35:] / 16.0, rtol=1e-12)
    for i in range(19, 35):
        r = (i - 18) / (35 - 18)
        np.testing.assert_allclose(f[i], base[i] * (1 - r) + base[i] / 16 * r,
                                   rtol=1e-12)


def test_tables_carry_the_attention_factor():
    pos = jnp.arange(5)
    cos, sin = rope.tables(pos, D, THETA, YARN)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, YARN.attention_factor ** 2,
                               rtol=1e-5)
    c0, s0 = rope.tables(pos, D, THETA)
    np.testing.assert_allclose(c0[:, :D // 2], c0[:, D // 2:])
    np.testing.assert_allclose(c0 ** 2 + s0 ** 2, 1.0, rtol=1e-5)


@pytest.mark.parametrize("yarn", [None, YARN], ids=["default", "yarn"])
def test_rotation_scores_depend_on_the_offset_only(yarn):
    """q·k after rotation at positions (m, n) equals the same at
    (m + s, n + s): RoPE encodes the relative position; each pair
    (i, i + d/2) turns in its plane by its own angle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (D,))
    k = jax.random.normal(ks[1], (D,))
    cos, sin = rope.tables(jnp.arange(64), D, THETA, yarn)

    def score(m, n):
        return jnp.dot(rope.apply(q[None], cos[m:m + 1], sin[m:m + 1])[0],
                       rope.apply(k[None], cos[n:n + 1], sin[n:n + 1])[0])

    np.testing.assert_allclose(score(9, 4), score(40, 35), rtol=2e-4)
    # one pair by hand: plane (3, 3 + d/2) at position 7
    f = rope.inv_freq(D, THETA, yarn)[3] * 7
    a = 1.0 if yarn is None else yarn.attention_factor
    x = rope.apply(q[None], cos[7:8], sin[7:8])[0]
    want = a * (q[3] * math.cos(f) - q[3 + D // 2] * math.sin(f))
    np.testing.assert_allclose(x[3], want, rtol=1e-4)


def test_program_and_reference_frequencies_agree():
    from benchmark.reference import mellum as ref

    s = ref.Shape(vocab=8, d_model=8, n_heads=1, n_kv_heads=1, head_dim=D,
                  layer_types=(), window=0, rope_theta=THETA,
                  yarn=tuple(YARN), n_experts=1, first_expert=0,
                  experts_held=1, top_k=1, d_expert=8, seq_len=8, lr=0.0)
    np.testing.assert_allclose(ref.inv_freq(s, True),
                               rope.inv_freq(D, THETA, YARN), rtol=1e-12)
    np.testing.assert_allclose(ref.inv_freq(s, False),
                               rope.inv_freq(D, THETA), rtol=1e-12)
