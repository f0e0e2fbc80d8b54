"""Pallas flash attention kernel vs the dense reference (interpret mode
on CPU — the same kernel code the TPU path compiles; reference analog:
the op/avx kernel unit tests, ompi/mca/op/avx)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.ops.flash_attention import (causal_walk, flash_block,
                                           flash_supported)
from ompi_tpu.ops.ring_attention import reference_attention

# (B, T, H, D): a small geometry whose 64-wide diagonal tile is masked
# whole, and the flagship's per-head geometry, where 512-wide tiles
# split their diagonal into sub-blocks
GEOMS = {"t64_d16": (2, 64, 2, 16), "t1024_d128": (1, 1024, 2, 128)}
# four 512-wide key tiles: dq gathers from more than two of them
FOUR_TILES = {"t2048_d128": (1, 2048, 1, 128)}
# (keep_full, keep_tri) of the ring's three block relations
MODES = {"tri": (0.0, 1.0), "full": (1.0, 0.0), "none": (0.0, 0.0)}


@functools.cache
def _qkv(geom):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = {**GEOMS, **FOUR_TILES}[geom]
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.fixture(scope="module", params=list(GEOMS))
def qkv(request):
    return _qkv(request.param)


def _dense(q, k, v, mode):
    """(out, lse) of the block relation ``mode`` by the dense reference;
    an empty block gives zeros and the -1e30 sentinel."""
    B, T, H, D = q.shape
    if mode == "none":
        return jnp.zeros_like(q), jnp.full((B, H, T), -1e30)
    causal = mode == "tri"
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return (reference_attention(q, k, v, causal=causal),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("mode", list(MODES))
def test_flash_forward_matches_dense(qkv, mode):
    q, k, v = qkv
    out, lse = flash_block(q, k, v, *MODES[mode], interpret=True)
    ref_out, ref_lse = _dense(q, k, v, mode)
    assert lse.shape == ref_lse.shape
    tol = 0.0 if mode == "none" else 2e-2  # an empty block is exact
    np.testing.assert_allclose(out, ref_out, atol=tol, rtol=tol)
    np.testing.assert_allclose(lse, ref_lse, atol=tol, rtol=tol)


def test_flash_bhtd_layout_matches(qkv):
    q, k, v = qkv
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out_t, lse_t = flash_block(tr(q), tr(k), tr(v), 0.0, 1.0,
                               interpret=True, layout="bhtd")
    out, lse = flash_block(q, k, v, 0.0, 1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(tr(out_t)), np.asarray(out),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_t), np.asarray(lse),
                               atol=1e-5)


GRAD_CASES = [*((g, m) for g in GEOMS for m in MODES),
              *((g, "tri") for g in FOUR_TILES)]


@pytest.mark.parametrize("geom,mode", GRAD_CASES,
                         ids=["-".join(c) for c in GRAD_CASES])
def test_flash_grads_match_dense(geom, mode):
    """dq/dk/dv (incl. the lse cotangent path the ring merge exercises)
    against autodiff through the dense reference."""
    q, k, v = _qkv(geom)

    def floss(q_, k_, v_):
        o, l = flash_block(q_, k_, v_, *MODES[mode], interpret=True)
        return jnp.sum(o * o) + jnp.sum(jnp.tanh(l / 10.0))

    def rloss(q_, k_, v_):
        o, l = _dense(q_, k_, v_, mode)
        return jnp.sum(o * o) + jnp.sum(jnp.tanh(l / 10.0))

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(rloss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=6e-2, rtol=6e-2)


def test_ring_merge_with_flash_matches_dense():
    """Two flash blocks merged in (out, lse) space == dense attention
    over the concatenated sequence — the ring-attention combine."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (1, 32, 1, 16), jnp.float32)
               for kk in ks)
    k1, k2 = k[:, :16], k[:, 16:]
    v1, v2 = v[:, :16], v[:, 16:]
    o1, l1 = flash_block(q, k1, v1, 1.0, 0.0, interpret=True)
    o2, l2 = flash_block(q, k2, v2, 1.0, 0.0, interpret=True)
    ln = jnp.logaddexp(l1, l2)
    lift = lambda x: x.transpose(0, 2, 1)[..., None]
    merged = o1 * lift(jnp.exp(l1 - ln)) + o2 * lift(jnp.exp(l2 - ln))
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(merged, ref, atol=2e-2, rtol=2e-2)


def test_flash_backward_is_one_kernel():
    """The backward scores each pair once: one pallas_call beside the
    forward's, named as the benchmark's readers find it."""
    q, k, v = _qkv("t64_d16")
    grad = jax.grad(lambda *a: jnp.sum(flash_block(*a, 0.0, 1.0,
                                                   interpret=True)[0]),
                    argnums=(0, 1, 2))

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    jaxpr = jax.make_jaxpr(grad)(q, k, v).jaxpr
    names = [e.params["name"] for e in eqns(jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names == ["flash_fwd", "flash_dqkv"]


def test_flash_supported_gate():
    assert flash_supported((2, 1024, 4, 64), (2, 1024, 4, 64))
    assert not flash_supported((2, 7, 4, 64), (2, 7, 4, 64))  # odd seq
    assert flash_supported((2, 4, 1024, 64), (2, 4, 1024, 64),
                           layout="bhtd")
    # lse rows are sliced in whole 128-lane tiles: 64-row tiles fall back
    assert not flash_supported((2, 64, 2, 16), (2, 64, 2, 16))
    # K/V VMEM budget: enormous per-device KV must fall back
    assert not flash_supported((1, 256, 1, 128), (1, 1 << 20, 1, 128))
    # the backward holds the whole q, dO and dq of a (b, h): at D = 128
    # T = 24576 fits the VMEM a kernel may ask for, T = 32768 does not
    assert flash_supported((1, 24576, 1, 128), (1, 24576, 1, 128))
    assert not flash_supported((1, 32768, 1, 128), (1, 32768, 1, 128))


def test_vmem_bytes_counts_the_resident_blocks():
    """At the flagship's per-head shape both kernels fit the default
    scoped limit, so neither asks for more; the backward's whole q, dO
    and dq grow with T, the forward's whole k and v too."""
    fwd, bwd = fa.vmem_bytes(1024, 1024, 128, 2)
    assert max(fwd, bwd) <= fa._VMEM_DEFAULT
    assert fa._vmem_params(bwd) is None
    fwd8, bwd8 = fa.vmem_bytes(8192, 8192, 128, 2)
    # q bf16 + dO and dq f32 of 7168 more rows, twice; lse and delta rows
    assert bwd8 - bwd == 2 * 7168 * 128 * (2 + 2 * 4) + 2 * 2 * 8 * 7168 * 4
    assert fwd8 - fwd == 2 * 2 * 7168 * 128 * 2
    # a head dim under 128 lanes is padded to them
    assert fa._block_bytes(1024, 64, 2) == fa._block_bytes(1024, 128, 2)


@pytest.mark.parametrize("t,sub,visited", [
    (1024, 256, 655_360), (4096, 256, 8_912_896),
    (1024, 128, 589_824), (4096, 128, 8_650_752)])
def test_causal_walk_stops_at_the_diagonal(monkeypatch, t, sub, visited):
    """512-wide tiles, the diagonal's in sub-blocks (256, the kernels'
    choice, and 128, set here): the walk lies between the triangle and
    the whole tiles that touch it."""
    monkeypatch.setattr(fa, "_DIAG_SUB", sub)
    w = causal_walk(t, t, 128)
    n = t // 512
    assert (w.block_q, w.block_k, w.sub) == (512, 512, sub)
    whole = n * (n + 1) // 2 * 512 * 512     # 786,432 at T=1024
    triangle = t * (t + 1) // 2              # 524,800 at T=1024
    assert triangle < w.visited == visited < whole
    # only each sub-block's own square on the diagonal is masked
    assert w.masked == n * (512 // sub) * sub * sub


@pytest.mark.parametrize("tq,tk,d", [(64, 64, 16), (32, 16, 16),
                                     (1024, 512, 128)])
def test_causal_walk_masks_whole_tiles_off_the_split(tq, tk, d):
    """No split where the diagonal tile is not a square of whole
    sub-blocks: every tile touching the triangle is masked whole."""
    w = causal_walk(tq, tk, d)
    assert w.sub == 0
    touching = sum(min((qi * w.block_q + w.block_q + w.block_k - 1)
                       // w.block_k, tk // w.block_k)
                   for qi in range(tq // w.block_q))
    below = sum(min((qi * w.block_q + 1) // w.block_k, tk // w.block_k)
                for qi in range(tq // w.block_q))
    tile = w.block_q * w.block_k
    assert w.visited == touching * tile
    assert w.masked == (touching - below) * tile
