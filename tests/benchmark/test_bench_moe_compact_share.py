"""The ``moe_compact_share`` reader on TPU-shaped traces written out by
hand: grouped-matmul calls at the compact buffer's height (17,408 rows
at ``mellum2.seq8192``) and at the worst case's (66,560)."""

import pytest

from benchmark import harness
from benchmark.trace_reduce import Op, Span, Trace

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
WINDOW = Span("bench.window", 0, 100e6, {})


def _gmm(name, rows, start):
    """A call as the device trace prints it: the result, then the
    scalar-prefetch operands, the buffer and the weights."""
    if name.startswith("expert_gmm_dw"):
        res, last = "f32[8,2304,1792]{2,1,0}", f"bf16[{rows},1792]{{1,0}} %dy"
    else:
        res, last = f"bf16[{rows},1792]{{1,0}}", \
            "bf16[8,2304,1792]{2,1,0} %w"
    hlo = (f"%{name} = {res} custom-call(s32[{rows // 128}]{{0}} %te, "
           f"s32[1]{{0}} %n, bf16[{rows},2304]{{1,0}} %x, {last}), "
           'custom_call_target="tpu_custom_call"')
    return Op("TPU:0", name, "jit_step_local", start, start + 1e5, hlo,
              False)


def _read(ops):
    reader = harness.load_module("metrics", "moe_compact_share")
    cell = harness.load_cell("mellum2.seq8192")
    assert reader.worst_rows(cell.config, cell.traffic) == 66560
    return reader.read(Trace(ops, [WINDOW], []), {"steps": 1}, cell, TPU)


KINDS = ("expert_gmm.1", "expert_gmm_dx.2", "expert_gmm_dw.3")


@pytest.mark.parametrize("heights,share", [
    ([17408] * 6, 100.0),                  # every call compact
    ([66560] * 6, 0.0),                    # every call the worst case
    ([17408] * 3 + [66560] * 3, 50.0),     # one layer each
    ([17408] * 9 + [66560] * 3, 75.0),
])
def test_bench_moe_compact_share_reads_the_buffer_heights(heights, share):
    ops = [_gmm(KINDS[i % 3], m, 1e6 * (i + 1))
           for i, m in enumerate(heights)]
    # a call outside the window and a kernel of another name: not read
    ops += [_gmm("expert_gmm.9", 66560, 200e6),
            Op("TPU:0", "flash_gqa_fwd.4", "jit_step_local", 50e6, 51e6,
               _gmm("x", 66560, 0).hlo, False)]
    assert _read(ops) == pytest.approx(share)


def test_bench_moe_compact_share_is_silent_without_the_kernels():
    """The CPU's step, and a program without the grouped matmul, run no
    such call: the reader returns nothing and does not raise."""
    assert _read([Op("TPU:0", "fusion.1", "jit_step_local", 1e6, 2e6, "",
                     False)]) is None
