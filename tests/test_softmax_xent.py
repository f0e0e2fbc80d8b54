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


# (T, chunk_t): the chunk sizes, and 48 % 32 != 0 (chunk shrinks to 16)
GRAD_CASES = [(64, 16), (64, 64), (64, 128), (48, 32)]


@pytest.mark.parametrize("T,chunk_t", GRAD_CASES)
def test_grads_match_reference(T, chunk_t):
    """The fused rule's (loss, dx, dw) from value_and_grad against the
    dense reference, and its loss against the primal call's."""
    x, w, t = _data(T=T)
    loss, (gx, gw) = jax.value_and_grad(
        lambda a, b: softmax_xent_sum(a, b, t, chunk_t), argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda a, b: reference_xent_sum(a, b, t),
                      argnums=(0, 1))(x, w)
    ref = float(_bf16_ref(x, w, t))
    assert abs(float(loss) - ref) < 1e-2 * max(abs(ref), 1.0)
    np.testing.assert_allclose(float(loss),
                               float(softmax_xent_sum(x, w, t, chunk_t)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               atol=6e-2, rtol=6e-2)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               atol=6e-2, rtol=6e-2)


def _vocab_dots(jaxpr, V):
    """dot_generals in a jaxpr and its sub-jaxprs with an operand or a
    result of a vocabulary-sized dimension."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            n += any(V in s for s in shapes)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, ClosedJaxpr):
                    n += _vocab_dots(sub.jaxpr, V)
                elif isinstance(sub, Jaxpr):
                    n += _vocab_dots(sub, V)
    return n


def test_vocab_matmuls_per_chunk():
    """Under AD one scan scores each chunk and runs the dx and dW
    matmuls on the same logits: three vocabulary matmuls, none
    recomputed. The primal call without AD runs the scoring one only."""
    V = 101
    x, w, t = _data(V=V)
    vg = jax.make_jaxpr(jax.value_and_grad(
        lambda a, b: softmax_xent_sum(a, b, t, 16), argnums=(0, 1)))(x, w)
    assert _vocab_dots(vg.jaxpr, V) == 3
    primal = jax.make_jaxpr(lambda a, b: softmax_xent_sum(a, b, t, 16))(x, w)
    assert _vocab_dots(primal.jaxpr, V) == 1


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
