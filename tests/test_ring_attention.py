"""Ring attention (sequence parallelism) vs dense reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ompi_tpu.ops.ring_attention import (
    reference_attention,
    ring_attention_sharded,
)


@pytest.fixture(scope="module")
def mesh4():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4])
    return Mesh(devs, ("sp",))


def _qkv(B=2, S=32, H=4, D=16, seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    q = jax.random.normal(k1, (B, S, H, D), jnp.float32)
    kk = jax.random.normal(k2, (B, S, H, D), jnp.float32)
    v = jax.random.normal(k3, (B, S, H, D), jnp.float32)
    return q, kk, v


def test_ring_matches_dense_causal(mesh4):
    q, k, v = _qkv()
    want = np.asarray(reference_attention(q, k, v, causal=True))
    got = np.asarray(ring_attention_sharded(q, k, v, mesh4, "sp",
                                            causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_matches_dense_noncausal(mesh4):
    q, k, v = _qkv(seed=3)
    want = np.asarray(reference_attention(q, k, v, causal=False))
    got = np.asarray(ring_attention_sharded(q, k, v, mesh4, "sp",
                                            causal=False))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_long_sequence_8way():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    q, k, v = _qkv(B=1, S=64, H=2, D=8, seed=7)
    want = np.asarray(reference_attention(q, k, v, causal=True))
    got = np.asarray(ring_attention_sharded(q, k, v, mesh, "sp"))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_grad_flows(mesh4):
    """Backprop through the ppermute ring must work (training path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu.ops.ring_attention import ring_attention

    spec = P(None, "sp", None, None)
    q, k, v = _qkv(B=1, S=16, H=2, D=8)

    def loss(q, k, v):
        out = ring_attention(q, k, v, "sp", 4, causal=True)
        return jnp.sum(out * out)

    sm = jax.shard_map(
        lambda q, k, v: jax.grad(loss, argnums=0)(q, k, v),
        mesh=mesh4, in_specs=(spec, spec, spec), out_specs=spec)
    sh = NamedSharding(mesh4, spec)
    g = jax.jit(sm)(jax.device_put(q, sh), jax.device_put(k, sh),
                    jax.device_put(v, sh))
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_multi_chunk_flash_matches_dense():
    """chunk < Tk exercises the scan/checkpoint flash path the model's
    full-tile default skips (r2 review: was untested)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from ompi_tpu.ops.ring_attention import (
        reference_attention, ring_attention)
    B, S, H, D = 2, 32, 4, 16
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    want = np.asarray(reference_attention(q, k, v, causal=True))

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sp",))
    spec = P(None, "sp", None, None)

    def local(qb, kb, vb):
        return ring_attention(qb, kb, vb, "sp", 4, causal=True, chunk=2)

    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(spec,) * 3,
                               out_specs=spec))
    got = np.asarray(fn(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    # gradients flow through the checkpointed scan body
    def loss(qq):
        return jnp.sum(fn(qq, k, v) ** 2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
