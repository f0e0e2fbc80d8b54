"""The two cells beside the flagship's: ``mellum2.seq8192`` through the
``moe_train_step`` driver and ``flagship.seq4096`` through
``train_step``, each end to end at a tiny size on the CPU; the Mellum
check's control and planted faults; and ``flops_moe``'s yardstick."""

import dataclasses

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import flops_moe, harness, peaks
from benchmark.drivers import moe_train_step
from conftest import TINY_TRAIN_LIMITS, run_cell

# sound tiny runs read loss_gap 2.3e-4-7.4e-4, grad_gap 1.1e-3-1.8e-3
# and change_gap 6.1e-4-2.5e-3 on the CPU (seeds 2**31 + 77, 5,
# 9000000001, 12345, 3030000001, 77); the control reads at least
# 5.9e-3, 1.9e-2, 1.2e-2 (seeds 12345, 2**31 + 77, 5) and every fault
# more in one of them
TINY_MOE_LIMITS = {"loss_gap": 2e-3, "grad_gap": 6e-3, "change_gap": 6e-3}
SEED = 2 ** 31 + 77


@pytest.fixture
def tiny_mellum(monkeypatch):
    cell = harness.load_cell("mellum2.seq8192")
    c = dict(cell.config, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=1, head_dim=32, num_hidden_layers=2,
             layer_types=["sliding_attention", "full_attention"],
             sliding_window=16, router_experts=16, num_experts=4,
             num_experts_per_tok=4, moe_intermediate_size=32, vocab_size=256)
    t = dict(cell.traffic, seq_len=64, batches=4, ref_query_rows=16)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return (cell._replace(config=c, traffic=t, limits=TINY_MOE_LIMITS),
            jax.devices()[:1])


@pytest.mark.parametrize("trace", [False, True])
def test_bench_mellum_cell_runs_and_is_correct(tiny_mellum, trace):
    out = run_cell(*tiny_mellum, seed=SEED, trace=trace)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    if trace:
        m = out["metrics"]
        # no Pallas kernel runs on the CPU: the kernels' readers are
        # silent, and the rows the step reports still feed the MFU
        assert set(m) == {"moe_train_mfu", "device_idle.train"}
        assert 0 < m["moe_train_mfu"]["value"] < 100
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_bench_mellum_cell_lists_its_metrics():
    cell = harness.load_cell("mellum2.seq8192")
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "moe_train_mfu", "gqa_flash_ms", "gqa_flash_roofline",
        "expert_gmm_ms", "expert_gmm_roofline", "device_idle.train"}
    flagship = harness.load_cell("flagship.seq4096")
    assert {m["name"] for m in flagship.per_layer} == {
        m["name"] for m in harness.load_cell("flagship.seq1024").per_layer}


def test_bench_seq4096_cell_runs_and_is_correct(monkeypatch):
    """The cell is a traffic file for the flagship's driver: batch 9 ×
    seq 4096, learned positions sized to the traffic's seq_len."""
    cell = harness.load_cell("flagship.seq4096")
    assert (cell.traffic["batch"], cell.traffic["seq_len"]) == (9, 4096)
    c = dict(cell.config, vocab=256, d_model=64, n_heads=2, n_layers=2,
             d_ff=128)
    t = dict(cell.traffic, seq_len=64, batches=3)
    out = run_cell(cell._replace(config=c, traffic=t,
                                 limits=TINY_TRAIN_LIMITS),
                   jax.devices()[:1])
    assert out["correct"], out["checks"]


def _break_moe(monkeypatch, fault):
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.ops import moe

    orig = tfm.make_train_step
    if fault == "no_renorm":
        def router(h, w, k):  # the top k of the softmax, as they are
            p = jax.nn.softmax(h.astype(jnp.float32) @ w, axis=-1)
            return jax.lax.top_k(p, k)

        monkeypatch.setattr(moe, "router", router)

    def make(mesh, cfg):
        if fault == "window_removed":
            cfg = dataclasses.replace(cfg, window=0)
        step, place = orig(mesh, cfg)

        def broken(params, tokens, targets):
            if fault == "state_unchanged":
                out, _ = step(params, tokens, targets)
                return out, params
            if fault == "half_the_batch":  # the first half of each row
                h = tokens.shape[1] // 2
                return step(params, tokens[:, :h], targets[:, :h])
            return step(params, tokens, targets)

        return broken, place

    monkeypatch.setattr(tfm, "make_train_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "window_removed", "no_renorm"])
def test_bench_mellum_fault_is_not_correct(tiny_mellum, monkeypatch, fault):
    _break_moe(monkeypatch, fault)
    out = run_cell(*tiny_mellum, seed=SEED)
    assert out["correct"] is False, out["checks"]


def test_bench_mellum_control_and_faults_fail_a_limit(tiny_mellum):
    """calibrate_moe.py's readings at the tiny size: the reference in
    float8 e4m3 and each planted fault, against the sound reference."""
    cell, devs = tiny_mellum
    ctx = harness.Context(cell, devs, 12345, 0.0, False)
    rows = moe_train_step.calibration(ctx, ml_dtypes.float8_e4m3fn)
    assert [k for k, _ in rows] == ["control", "fault_half_batch",
                                    "fault_full_causal", "fault_no_renorm"]
    for kind, r in rows:
        assert any(r[k] > cell.limits[k] for k in r), (kind, r)


def test_flops_moe_pairs_match_a_brute_count():
    for t, w in [(64, 0), (64, 16), (100, 7), (8, 64)]:
        i, j = np.arange(t)[:, None], np.arange(t)[None]
        keep = (j <= i) & ((i - j < w) if w else True)
        assert flops_moe.attn_pairs(t, w) == keep.sum()


def test_flops_moe_step_at_the_cell():
    """About 18.8 TFLOP a step at 8,192 tokens with one held assignment
    a token a layer: projections and head 9.80, scores 6.55, experts
    2.44."""
    c = harness.load_cell("mellum2.seq8192").config
    assert flops_moe.dense_params(c) == 8 * (21233664 + 147456) + 28311552
    scores = flops_moe.train_flops_per_step(c, 1, 8192, 0) - \
        6.0 * flops_moe.dense_params(c) * 8192
    assert scores == pytest.approx(6.5536e12, rel=1e-3)
    total = flops_moe.train_flops_per_step(c, 1, 8192, 8 * 8192)
    assert total == pytest.approx(18.79e12, rel=1e-3)


def test_flops_moe_reads_calls_from_hlo_text():
    fwd = ('%flash_gqa_win_fwd.3 = (f32[32,8192,128]{2,1,0}, '
           'f32[32,8,8192]{2,1,0}) custom-call(f32[1,1]{1,0} %a, '
           'f32[1,1]{1,0} %b, bf16[32,8192,128]{2,1,0} %q, '
           'bf16[4,8192,128]{2,1,0} %k, bf16[4,8192,128]{2,1,0} %v), '
           'custom_call_target="tpu_custom_call"')
    cost = flops_moe.gqa_flash_call("flash_gqa_win_fwd.3", fwd,
                                    {True: 1024, False: 0})
    assert cost == flops_moe.gqa_flash("fwd", 32, 4, 8192, 128, 1024)
    assert cost.flops == 4 * 128 * 32 * flops_moe.attn_pairs(8192, 1024)
    gmm = ('%expert_gmm.1 = bf16[66560,1792]{1,0} custom-call(s32[520]{0} '
           '%te, s32[1]{0} %n, bf16[66560,2304]{1,0} %x, '
           'bf16[8,2304,1792]{2,1,0} %w), custom_call_target="tpu_custom_call"')
    assert flops_moe.gmm_call("expert_gmm.1", gmm, 1000.0) == (
        2.0 * 1000 * 2304 * 1792, 1000 * (2304 + 1792) * 2
        + 8 * 2304 * 1792 * 2)
    dw = ('%expert_gmm_dw.2 = f32[8,896,2304]{2,1,0} custom-call('
          's32[520]{0} %te, s32[1]{0} %n, bf16[66560,896]{1,0} %x, '
          'bf16[66560,2304]{1,0} %dy), custom_call_target="tpu_custom_call"')
    assert flops_moe.gmm_call("expert_gmm_dw.2", dw, 10.0) == (
        2.0 * 10 * 896 * 2304, 10 * (896 + 2304) * 2 + 8 * 896 * 2304 * 4)
