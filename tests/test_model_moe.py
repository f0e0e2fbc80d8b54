"""The sparse-expert configuration (``transformer.MoEConfig``) through
``make_train_step`` on the CPU, against the float32 reference
``benchmark/reference/mellum.py`` on seeded weights."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from benchmark.drivers import moe_train_step as drv
from benchmark.reference import lm as ref_lm
from benchmark.reference import mellum as ref
from ompi_tpu.models import transformer as tfm

S = ref.Shape(vocab=256, d_model=64, n_heads=4, n_kv_heads=1, head_dim=32,
              layer_types=("sliding", "sliding", "full"), window=16,
              rope_theta=500000.0,
              yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
              n_experts=16, first_expert=4, experts_held=4, top_k=4,
              d_expert=32, seq_len=64, lr=0.01)


def _mesh(dp=1, sp=1, tp=1):
    return Mesh(np.array(jax.devices()[:dp * sp * tp]).reshape(dp, sp, tp),
                ("dp", "sp", "tp"))


@pytest.fixture(scope="module")
def setup():
    params = ref.init_params(jax.random.PRNGKey(0), S)
    batches = ref.token_batches(jax.random.PRNGKey(1), S, 2, 3)
    return params, batches


def test_moe_step_matches_reference(setup):
    """Three steps: losses, the first gradient's and the change's leaf
    norms within the bf16 tolerances of the benchmark's tiny cell."""
    params, batches = setup
    step, _ = tfm.make_train_step(_mesh(), drv.model_config(S))
    p, losses = params, []
    for i in range(3):
        (loss, rows), p = step(p, batches[i, :, :-1], batches[i, :, 1:])
        losses.append(float(loss))
        if i == 0:
            g1 = ref_lm.leaf_norms(jax.tree.map(
                lambda a, b: (a - b) / S.lr, params, p))
    change = ref_lm.leaf_norms(jax.tree.map(lambda a, b: a - b, p, params))
    want = ref.three_steps(params, batches, S, rows=16)
    got = drv.readings(losses, g1, change, *want)
    assert got["loss_gap"] < 0.02 and got["grad_gap"] < 0.1 and \
        got["change_gap"] < 0.03, got


def test_moe_step_returns_rows_per_layer_and_expert(setup):
    """[layers, held experts] int32: each token puts at most min(k, held)
    of its k picks on the experts held here; 4 of 16 held at k = 4 is
    one a token on average."""
    params, batches = setup
    step, _ = tfm.make_train_step(_mesh(), drv.model_config(S))
    tokens = batches[0, :, :-1]
    (_, rows), _ = step(params, tokens, batches[0, :, 1:])
    rows = np.asarray(rows)
    assert rows.shape == (3, 4) and rows.dtype == np.int32
    n = tokens.size
    assert np.all(rows >= 0) and np.all(rows.sum(1) <= n * 4)
    assert np.all((rows.sum(1) > n // 4) & (rows.sum(1) < 3 * n))


def test_moe_step_dp2_matches_dp1(setup):
    """Two data shards: the loss, the rows and the update are the whole
    batch's, as on one."""
    params, batches = setup
    cfg = drv.model_config(S)
    outs = []
    for dp in (1, 2):
        step, place = tfm.make_train_step(_mesh(dp), cfg)
        p, t, g = place(params, batches[0, :, :-1], batches[0, :, 1:])
        outs.append(step(p, t, g))
    ((l1, r1), p1), ((l2, r2), p2) = outs
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    np.testing.assert_array_equal(r1, r2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("sp,tp", [(2, 1), (1, 2)])
def test_moe_step_refuses_sequence_and_tensor_shards(sp, tp):
    with pytest.raises(ValueError, match="sp = tp = 1"):
        tfm.make_train_step(_mesh(1, sp, tp), drv.model_config(S))


def test_moe_init_matches_the_reference_layout():
    cfg = drv.model_config(S)
    mine = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: ref.init_params(k, S),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(theirs)]
    assert jax.tree.structure(tfm.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, mine))
