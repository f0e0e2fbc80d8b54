"""The expert layer (ops/moe.py) against the plain reference's
(benchmark/reference/mellum.py), with the grouped-matmul kernels
interpreted on the CPU and on XLA's path; the routing under skew; and
the expert-parallel share: the parts all the shares compute add up to
the uncut layer."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import mellum as ref
from ompi_tpu.ops import moe

D, F, E_ALL, K = 64, 128, 16, 4
E_WIDE = 32  # a router this wide makes the compact buffer the smaller


def _shape(held, first=0):
    return ref.Shape(vocab=8, d_model=D, n_heads=1, n_kv_heads=1,
                     head_dim=8, layer_types=(), window=0, rope_theta=1.0,
                     yarn=(1.0, 1, 1.0, 1.0, 1.0), n_experts=E_ALL,
                     first_expert=first, experts_held=held, top_k=K,
                     d_expert=F, seq_len=8, lr=0.0)


def _layer(n, skew, seed=0, n_experts=E_ALL, favoured=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (n, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, n_experts),
                               jnp.float32) / np.sqrt(D)
    if skew:
        # every token puts experts 0 .. favoured - 1 first: their rows
        # fill several tiles
        h = h + 2.0
        router = router.at[:, :favoured].add(0.5)
    blk = {"router": router,
           "w_gate": jax.random.normal(ks[2], (E_ALL, D, F)) / np.sqrt(D),
           "w_up": jax.random.normal(ks[3], (E_ALL, D, F)) / np.sqrt(D),
           "w_down": jax.random.normal(ks[4], (E_ALL, F, D)) / np.sqrt(F)}
    return h, blk


def _share(blk, first, held):
    return {k: (v if k == "router" else v[first:first + held])
            for k, v in blk.items()}


def _prog(h, blk, first, impl):
    return moe.moe_layer(h, blk["router"], blk["w_gate"], blk["w_up"],
                         blk["w_down"], K, first, impl=impl)


def _ref(h, blk, held, first=0):
    with jax.default_matmul_precision("highest"):
        return ref.moe(h, blk, _shape(held, first))


def _close(a, b, tol=2e-2):
    scale = float(jnp.max(jnp.abs(b)))
    np.testing.assert_allclose(a, b, atol=tol * scale, rtol=0)


def _eqns(jaxpr):
    from jax._src import core

    for e in jaxpr.eqns:
        yield e
        for sub in core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_route_skewed_drops_nothing():
    """All 256 tokens pick expert 0: it gets 256 rows over two tiles,
    every held assignment a row of its own within its expert's tiles,
    and each other held expert at least one (padding) tile."""
    h, blk = _layer(256, skew=True)
    _, experts = moe.router(h, blk["router"], K)
    assert bool(jnp.all(experts[:, 0] == 0))
    r = moe.route(experts, 0, 8, moe.buffer_rows(256, K, 8))
    held = np.asarray(r.held).reshape(-1)
    rows = np.flatnonzero(np.asarray(r.row_valid))
    slots = np.asarray(r.row_slot)[rows]
    # every held slot has exactly one row
    np.testing.assert_array_equal(np.sort(slots), np.flatnonzero(held))
    assert len(rows) == held.sum() == int(r.counts.sum())
    assert int(r.counts[0]) == 256
    np.testing.assert_array_equal(np.asarray(r.row_token)[rows], slots // K)
    te = np.asarray(r.tile_expert)
    e = np.asarray(experts).reshape(-1)[slots]
    np.testing.assert_array_equal(te[rows // moe.TILE], e)
    tiles = np.maximum(-(-np.asarray(r.counts) // moe.TILE), 1)
    assert int(r.n_tiles) == tiles.sum()
    assert r.row_token.shape[0] == moe.buffer_rows(256, K, 8)


@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("skew", [False, True], ids=["spread", "skewed"])
def test_moe_layer_matches_reference(impl, skew):
    """Output, rows and gradients of the 8 experts held (0-7 of 16)."""
    h, blk = _layer(256, skew)
    mine = _share(blk, 0, 8)

    def prog(h, b):
        out, _ = _prog(h, b, 0, impl)
        return out

    out, counts = _prog(h, mine, 0, impl)
    want = _ref(h, mine, 8)
    _close(out, want)
    _, experts = moe.router(h, blk["router"], K)
    np.testing.assert_array_equal(
        counts, [(np.asarray(experts) == e).sum() for e in range(8)])
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    gp = jax.grad(lambda *a: jnp.sum(prog(*a) * w), argnums=(0, 1))(h, mine)
    gr = jax.grad(lambda *a: jnp.sum(_ref(*a, 8) * w), argnums=(0, 1))(h,
                                                                       mine)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 3e-2)


def test_moe_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each, as four chips of the layer hold
    them: their outputs add up to the reference's layer with all 16."""
    h, blk = _layer(128, skew=False, seed=4)
    parts = [_prog(h, _share(blk, s, 4), s, "interpret")[0]
             for s in range(0, E_ALL, 4)]
    whole = _ref(h, blk, E_ALL)
    _close(sum(parts), whole)
    # and a share is not the whole: the check can fail
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


def test_router_renormalises_over_the_top_k():
    """The k largest of the softmax over all 16 experts, divided by
    their sum."""
    h, blk = _layer(64, skew=False)
    w, experts = moe.router(h, blk["router"], K)
    p = np.asarray(jax.nn.softmax(
        jnp.dot(h, blk["router"], precision="highest"), axis=-1))
    top = np.sort(p, axis=-1)[:, ::-1][:, :K]
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.take_along_axis(p, np.asarray(experts), -1), top, rtol=1e-6)


def test_grouped_matmul_kernel_names():
    """The three calls are named as the benchmark's readers find them."""
    h, blk = _layer(128, skew=False)
    mine = _share(blk, 0, 4)
    f = jax.grad(lambda b: jnp.sum(_prog(h, b, 0, "interpret")[0]))
    names = {e.params["name"] for e in _eqns(jax.make_jaxpr(f)(mine).jaxpr)
             if e.primitive.name == "pallas_call"}
    assert names == {"expert_gmm", "expert_gmm_dx", "expert_gmm_dw"}


@pytest.mark.parametrize("routing,held,n_experts,rows", [
    # 4 of 32 held, 256 tokens: compact 768 rows, worst case 1,536
    ("spread", 4, E_WIDE, 768),      # ~128 rows, 4 tiles: the compact
    ("skewed", 4, E_WIDE, 1536),     # all pick 0-3: 8 tiles, the worst
    ("all_held", E_ALL, E_ALL, 3072),  # compact 4,096 no smaller: one
])
def test_moe_layer_buffer_follows_the_routing(routing, held, n_experts,
                                              rows, monkeypatch):
    """The layer runs at the compact height where the routed tiles fit
    it and at the worst case where they do not, with one height where
    the compact is not the smaller; dropless either way: every held
    assignment has a row, and the output and the gradients of h, the
    router and the experts' weights match the reference's."""
    h, blk = _layer(256, routing == "skewed", n_experts=n_experts,
                    favoured=4)
    mine = _share(blk, 0, held)
    worst = moe.buffer_rows(256, K, held)
    compact = moe.compact_rows(256, K, held, n_experts)
    heights = []
    matmul = moe.expert_matmul

    def spy(x, *a):
        # runs on the device, so only in the branch the layer takes
        jax.debug.callback(functools.partial(heights.append, x.shape[0]))
        return matmul(x, *a)

    monkeypatch.setattr(moe, "expert_matmul", spy)

    def prog(h, b):
        return _prog(h, b, 0, "interpret")[0]

    out, counts = _prog(h, mine, 0, "interpret")
    w = jax.random.normal(jax.random.PRNGKey(9), out.shape)
    gp = jax.grad(lambda *a: jnp.sum(prog(*a) * w), argnums=(0, 1))(h, mine)
    jax.effects_barrier()
    assert heights and set(heights) == {rows}
    branches = any(e.primitive.name in ("cond", "switch") for e in
                   _eqns(jax.make_jaxpr(prog)(h, mine).jaxpr))
    assert branches == (compact < worst)
    fits = int(moe.tile_rows(counts)) <= compact
    assert rows == (compact if fits and compact < worst else worst)

    _, experts = moe.router(h, blk["router"], K)
    r = moe.route(experts, 0, held, rows)
    n_held = int(np.asarray(r.held).sum())
    assert int(np.asarray(r.row_valid).sum()) == n_held == int(counts.sum())
    assert int(r.n_tiles) * moe.TILE <= rows

    _close(out, _ref(h, mine, held))
    gr = jax.grad(lambda *a: jnp.sum(_ref(*a, held) * w),
                  argnums=(0, 1))(h, mine)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 3e-2)


@pytest.mark.parametrize("n,k,held,n_experts,compact,worst", [
    # Mellum2's cell: 8,192 tokens, top 8 of 64, this chip's 8 held
    (8192, 8, 8, 64, 17408, 66560),
    # twice the even share (171.4 rows) rounds up to whole tiles
    (100, 3, 2, 7, 512, 456),
])
def test_buffer_heights(n, k, held, n_experts, compact, worst):
    assert moe.compact_rows(n, k, held, n_experts) == compact
    assert moe.buffer_rows(n, k, held) == worst
