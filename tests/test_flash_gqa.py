"""The flash kernels with grouped K/V heads and a sliding band
(``flash_attention``), in interpret mode on the CPU, against dense
float32 attention; and the band's tile walk against a brute-force count
over the score elements."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.ops.flash_attention import causal_walk, flash_attention

D = 128
# (group, window, T): group 8 is Mellum's 32 query heads over 4 K/V
# heads; windows narrower than a 256- or 512-wide tile cut the diagonal
# tile, and at T = 1024 a window of a tile or more leaves the diagonal's
# sub-blocks intact beside a masked lower edge
CASES = [(g, w, t) for g in (1, 8) for w in (0, 64, 128) for t in (256, 512)]
CASES += [(8, 512, 1024), (8, 640, 1024)]


@functools.cache
def _qkv(group, t):
    ks = jax.random.split(jax.random.PRNGKey(group * 7 + t), 3)
    hkv = 2 if group == 1 else 1
    q = jax.random.normal(ks[0], (1, hkv * group, t, D), jnp.float32)
    k, v = (jax.random.normal(kk, (1, hkv, t, D), jnp.float32)
            for kk in ks[1:])
    return q, k, v


def dense(q, k, v, window):
    """Causal attention in float32 with K/V heads repeated over their
    group and the band, if any, masked."""
    g = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, g, axis=1) for x in (k, v))
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    keep = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _id(c):
    return f"g{c[0]}-w{c[1]}-t{c[2]}"


@pytest.mark.parametrize("group,window,t", CASES, ids=map(_id, CASES))
def test_gqa_flash_forward_matches_dense(group, window, t):
    q, k, v = _qkv(group, t)
    out = flash_attention(q, k, v, window, interpret=True)
    np.testing.assert_allclose(out, dense(q, k, v, window), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("group,window,t", CASES, ids=map(_id, CASES))
def test_gqa_flash_grads_match_dense(group, window, t):
    """dq, and dk and dv summed over each K/V head's group."""
    q, k, v = _qkv(group, t)
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    gf = jax.grad(loss(lambda *a: flash_attention(*a, window,
                                                  interpret=True)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda *a: dense(*a, window)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-2 * scale, rtol=0)


def _pallas_names(fn, *args):
    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    return [e.params["name"] for e in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("group,window,names", [
    (8, 0, ["flash_gqa_fwd", "flash_gqa_dqkv"]),
    (8, 64, ["flash_gqa_win_fwd", "flash_gqa_win_dqkv"]),
    (1, 64, ["flash_win_fwd", "flash_win_dqkv"])])
def test_gqa_flash_calls_carry_their_own_names(group, window, names):
    """Only the flagship's plain calls are named flash_fwd and
    flash_dqkv, and no new name holds either as a part."""
    q, k, v = _qkv(group, 256)
    grad = jax.grad(lambda *a: jnp.sum(flash_attention(*a, window,
                                                       interpret=True)),
                    argnums=(0, 1, 2))
    got = _pallas_names(grad, q, k, v)
    assert got == names
    assert not any(old in n for n in got
                   for old in ("flash_fwd", "flash_dqkv"))


def _brute_walk(tq, tk, bq, bk, sub, window):
    """(visited, masked) from the score elements themselves: a tile is
    visited where some pair in it attends and masked where some pair it
    visits does not; a diagonal tile split into sub-blocks visits the
    sub-blocks on or below the diagonal and masks those on it."""
    i = np.arange(tq)[:, None]
    j = np.arange(tk)[None]
    attend = (j <= i) & ((i - j < window) if window else True)
    visited = masked = 0
    for qi in range(tq // bq):
        for ki in range(tk // bk):
            a = attend[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            if not a.any():
                continue
            if sub and qi == ki and not a.all():
                n = bq // sub
                for r in range(n):
                    for c in range(r + 1):
                        visited += sub * sub
                        masked += sub * sub if c == r else 0
                continue
            visited += a.size
            masked += 0 if a.all() else a.size
    return visited, masked


@pytest.mark.parametrize("t,window", [(1024, 0), (4096, 0), (8192, 1024),
                                      (4096, 640), (2048, 512), (2048, 384),
                                      (512, 64), (256, 128), (64, 16)])
def test_causal_walk_counts_the_band(t, window):
    d = 16 if t == 64 else 128
    w = causal_walk(t, t, d, window)
    assert (w.visited, w.masked) == _brute_walk(t, t, w.block_q, w.block_k,
                                                w.sub, window)
    # the diagonal splits only where the band spans whole tiles
    assert bool(w.sub) == (w.block_q % 128 == 0 and
                           (not window or window >= w.block_q))


def test_causal_walk_band_at_mellum_shape():
    """T = 8192 and a 1024-wide band in 512-wide tiles: each query tile
    past the first two visits one masked edge tile, one whole tile and
    its split diagonal; a plain causal walk's count is unchanged."""
    w = causal_walk(8192, 8192, 128, 1024)
    diag = 256 * 256 * 3
    assert w.visited == 512 * 512 * (0 + 1 + 14 * 2) + 16 * diag
    assert w.masked == 14 * 512 * 512 + 16 * 2 * 256 * 256
    assert causal_walk(1024, 1024, 128) == fa.Walk(512, 512, 256, 655_360,
                                                   262_144, 0)


def test_flash_supported_grouped_gate():
    assert fa.flash_supported((1, 32, 8192, 128), (1, 4, 8192, 128),
                              layout="bhtd")
    # query heads must group evenly over the K/V heads
    assert not fa.flash_supported((1, 6, 1024, 128), (1, 4, 1024, 128),
                                  layout="bhtd")
    with pytest.raises(ValueError):
        flash_attention(*[jnp.zeros((1, h, 256, D)) for h in (6, 4, 4)])
