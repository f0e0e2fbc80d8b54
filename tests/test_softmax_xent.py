"""Chunked softmax cross-entropy (ops/softmax_xent.py) vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops.softmax_xent import softmax_xent_sum, reference_xent_sum


def _data(B=2, T=64, D=32, V=101, seed=0):
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, T, D), jnp.float32)
    w = jax.random.normal(kw, (V, D), jnp.float32)
    t = jax.random.randint(kt, (B, T), 0, V)
    return x, w, t


def _bf16_ref(x, w, t):
    # the chunked op scores in bf16 (MXU); compare against a reference
    # fed bf16-rounded inputs so tolerances stay tight
    f = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    return reference_xent_sum(f(x), f(w), t)


@pytest.mark.parametrize("chunk_t", [16, 64, 128])
def test_forward_matches_reference(chunk_t):
    x, w, t = _data()
    ours = float(softmax_xent_sum(x, w, t, chunk_t))
    ref = float(_bf16_ref(x, w, t))
    assert abs(ours - ref) < 1e-2 * max(abs(ref), 1.0)


def test_odd_t_falls_back_to_divisor_chunk():
    x, w, t = _data(T=48)  # 48 % 32 != 0 -> chunk shrinks to a divisor
    ours = float(softmax_xent_sum(x, w, t, 32))
    ref = float(_bf16_ref(x, w, t))
    assert abs(ours - ref) < 1e-2 * max(abs(ref), 1.0)


def test_grads_match_reference():
    x, w, t = _data()
    gx, gw = jax.grad(lambda a, b: softmax_xent_sum(a, b, t, 16),
                      argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda a, b: reference_xent_sum(a, b, t),
                      argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               atol=6e-2, rtol=6e-2)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               atol=6e-2, rtol=6e-2)


def test_sharded_grad_matches_single():
    """Under shard_map over (dp, sp), the embed cotangent must be the
    cross-shard sum (the explicit psum in _xent_bwd)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "sp"))
    x, w, t = _data(B=4, T=64)

    def local(x_, w_, t_):
        def lf(xx, ww):
            return softmax_xent_sum(xx, ww, t_, 16, ("dp", "sp"))
        loss, (gx, gw) = jax.value_and_grad(
            lambda xx, ww: lf(xx, ww), argnums=(0, 1))(x_, w_)
        from jax import lax

        return lax.psum(loss, ("dp", "sp")), gx, gw

    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("dp", "sp", None), P(), P("dp", "sp")),
                       out_specs=(P(), P("dp", "sp", None), P()))
    loss_sh, gx_sh, gw_sh = jax.jit(sm)(x, w, t)

    loss1 = reference_xent_sum(x, w, t)
    rx, rw = jax.grad(lambda a, b: reference_xent_sum(a, b, t),
                      argnums=(0, 1))(x, w)
    assert abs(float(loss_sh) - float(loss1)) < 1e-2 * abs(float(loss1))
    np.testing.assert_allclose(np.asarray(gx_sh), np.asarray(rx),
                               atol=6e-2, rtol=6e-2)
    np.testing.assert_allclose(np.asarray(gw_sh), np.asarray(rw),
                               atol=6e-2, rtol=6e-2)
