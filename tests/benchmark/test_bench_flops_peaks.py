"""benchmark/flops.py and benchmark/peaks.py against counts written out
by hand at small shapes."""

import pytest

from benchmark import flops
from benchmark.peaks import peaks

# the real forward kernel's HLO text as a v5e trace names it (chip run,
# PR 22), and the dq and dk/dv kernels in the same form
FWD = ('%jvp__.8 = (f32[288,1024,128]{2,1,0:T(8,128)}, f32[288,8,1024]'
       '{2,1,0:T(8,128)}) custom-call(f32[1,1]{1,0:T(1,128)} %constant.67, '
       'f32[1,1]{1,0:T(1,128)} %constant.66, bf16[288,1024,128]{2,1,0:T(8,'
       '128)(2,1)} %bitcast.1744, bf16[288,1024,128]{2,1,0:T(8,128)(2,1)S(1)'
       '} %bitcast.1743, bf16[288,1024,128]{2,1,0:T(8,128)(2,1)} %bitcast.'
       '1738), custom_call_target="tpu_custom_call", operand_layout_'
       'constraints={f32[1,1]{1,0}, f32[1,1]{1,0}, bf16[288,1024,128]{2,1,0'
       '}, bf16[288,1024,128]{2,1,0}, bf16[288,1024,128]{2,1,0}}, frontend_'
       'attributes={kernel_metadata={}}')
_BWD_IN = ('custom-call(f32[1,1]{1,0} %c.1, f32[1,1]{1,0} %c.2, '
           'bf16[288,1024,128]{2,1,0} %q, bf16[288,1024,128]{2,1,0} %k, '
           'bf16[288,1024,128]{2,1,0} %v, f32[288,1024,128]{2,1,0} %do, '
           'f32[288,8,1024]{2,1,0} %lse, f32[288,8,1024]{2,1,0} %delta), '
           'custom_call_target="tpu_custom_call"')
DQ = '%custom-call.140 = f32[288,1024,128]{2,1,0:T(8,128)} ' + _BWD_IN
DKV = ('%transpose_jvp___.17 = (f32[288,1024,128]{2,1,0}, '
       'f32[288,1024,128]{2,1,0}) ' + _BWD_IN)


def test_bench_lm_params_is_the_flagship_count():
    # vocab·D + T·D + D + L·(2D + 3D² + D² + 2·D·F)
    d, f, v, t, L = 1024, 4096, 32768, 1024, 8
    want = v * d + t * d + d + L * (2 * d + 4 * d * d + 2 * d * f)
    assert flops.lm_params(v, d, L, f, t) == want == 135_283_712


def test_bench_train_flops_per_token():
    n = flops.lm_params(32768, 1024, 8, 4096, 1024)
    assert flops.lm_train_flops_per_token(n, 8, 1024, 1024) == \
        6 * n + 12 * 8 * 1024 * 1024
    assert flops.lm_train_flops_per_token(10, 2, 3, 4) == 60 + 288


@pytest.mark.parametrize("t,causal,pairs", [
    (4, True, 10), (4, False, 16), (1, True, 1), (8, True, 36)])
def test_bench_causal_pairs(t, causal, pairs):
    assert flops.causal_pairs(t, t, causal) == pairs


def test_bench_causal_pairs_refuses_rectangles():
    with pytest.raises(ValueError):
        flops.causal_pairs(4, 8, True)


@pytest.mark.parametrize("kind,mults,writes_f32", [
    # matmuls per attending pair, and f32 [T, D] arrays written
    ("fwd", 2, 1), ("dq", 3, 1), ("dkv", 4, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_bench_flash_counts_by_hand(kind, mults, writes_f32, causal):
    bh, t, d = 3, 4, 8
    pairs = 10 if causal else 16    # the causal half, diagonal included
    cost = flops.FLASH_KERNELS[kind](bh, t, d, causal, 2)
    assert cost.flops == bh * pairs * mults * 2 * d
    qkv = 3 * t * d * 2             # bf16 q, k, v
    lse_rows = 8 * t * 4            # one f32 [8, T] row block
    if kind == "fwd":
        want = qkv + writes_f32 * t * d * 4 + lse_rows
    else:                            # + dO (f32), lse and delta rows
        want = qkv + t * d * 4 + 2 * lse_rows + writes_f32 * t * d * 4
    assert cost.bytes == bh * want


def test_bench_flash_causal_is_about_half():
    full = flops.flash_fwd(1, 1024, 128, causal=False).flops
    half = flops.flash_fwd(1, 1024, 128, causal=True).flops
    assert half == full * 1025 / 2048


def test_bench_least_time_names_its_bound():
    assert flops.least_time(flops.Cost(2e12, 1e9), 1e12, 1e9) == \
        (2.0, "compute")
    assert flops.least_time(flops.Cost(1e12, 3e9), 1e12, 1e9) == \
        (3.0, "memory")


@pytest.mark.parametrize("verb,want", [
    ("allreduce", 2 * 3 / 4 * 1000), ("allgather", 3 * 1000),
    ("alltoall", 3 / 4 * 1000), ("bcast", 1000)])
def test_bench_bus_bytes_follow_nccl_tests(verb, want):
    assert flops.coll_bus_bytes(verb, 1000, 4) == pytest.approx(want)


def test_bench_bus_bytes_unknown_verb():
    with pytest.raises(ValueError):
        flops.coll_bus_bytes("scan", 1000, 4)


@pytest.mark.parametrize("hlo,kind", [(FWD, "fwd"), (DQ, "dq"),
                                      (DKV, "dkv")])
def test_bench_flash_kernels_are_told_apart_by_signature(hlo, kind):
    assert flops.flash_kernel(hlo) == (kind, 288, 1024, 128, 2)


def test_bench_other_custom_calls_are_not_flash():
    assert flops.flash_kernel(
        '%custom-call.8 = f32[] custom-call(), custom_call_target='
        '"AllocateBuffer"') is None
    assert flops.flash_kernel(FWD.replace("tpu_custom_call", "x")) is None


def test_bench_v5e_peaks_are_the_published_ones():
    p = peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bytes, p.ici_bytes) == (197e12, 819e9, 200e9)


def test_bench_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        peaks("cpu")
