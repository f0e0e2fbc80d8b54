"""Operations and bytes computed from shapes: the yardstick for the MFU
and roofline readers. Kept with the benchmark so that no change to the
program can move it.

Matmul FLOPs only (2 per multiply-add); elementwise work runs on the VPU
and is not counted against the MXU peak.
"""

from __future__ import annotations

import re
from typing import NamedTuple


class Cost(NamedTuple):
    flops: float
    bytes: float


def lm_params(vocab: int, d_model: int, n_layers: int, d_ff: int,
              positions: int) -> int:
    """Parameters of the flagship causal LM (models/transformer.py):
    a tied embedding, learned positions, a gain-only final norm, and per
    layer two gain-only norms, a fused qkv, wo, w1 and w2."""
    per_layer = 2 * d_model + 3 * d_model * d_model + d_model * d_model \
        + 2 * d_model * d_ff
    return vocab * d_model + positions * d_model + d_model \
        + n_layers * per_layer


def lm_train_flops_per_token(n_params: int, n_layers: int, seq_len: int,
                             d_model: int) -> float:
    """Training FLOPs per token, forward and backward, no recomputation:
    6N for the weights plus 12·L·T·D for attention's scores and values
    (the PaLM appendix B estimate, also bench.py's)."""
    return 6.0 * n_params + 12.0 * n_layers * seq_len * d_model


def causal_pairs(tq: int, tk: int, causal: bool) -> int:
    """(query, key) pairs that attend: the lower triangle with its
    diagonal when causal and square, else all of them."""
    if not causal:
        return tq * tk
    if tq != tk:
        raise ValueError("causal attention is counted for square blocks")
    return tq * (tq + 1) // 2


def flash_fwd(bh: int, t: int, d: int, causal: bool = True,
              in_bytes: int = 2) -> Cost:
    """ops/flash_attention.py forward kernel: S = QKᵀ and O = PV over the
    attending pairs; reads q, k, v once, writes o (f32) and the
    8-sublane lse rows (f32)."""
    pairs = bh * causal_pairs(t, t, causal)
    flops = 2 * 2 * d * pairs
    nbytes = 3 * bh * t * d * in_bytes + bh * t * d * 4 + bh * 8 * t * 4
    return Cost(flops, nbytes)


def flash_dq(bh: int, t: int, d: int, causal: bool = True,
             in_bytes: int = 2) -> Cost:
    """dq kernel: re-scores S = QKᵀ, dP = dO·Vᵀ, dQ = dS·K; reads q, k,
    v, dO (f32), lse and delta rows (f32), writes dq (f32)."""
    pairs = bh * causal_pairs(t, t, causal)
    flops = 3 * 2 * d * pairs
    nbytes = 3 * bh * t * d * in_bytes + bh * t * d * 4 \
        + 2 * bh * 8 * t * 4 + bh * t * d * 4
    return Cost(flops, nbytes)


def flash_dkv(bh: int, t: int, d: int, causal: bool = True,
              in_bytes: int = 2) -> Cost:
    """dk/dv kernel: re-scores S, dV = Pᵀ·dO, dP = dO·Vᵀ, dK = dSᵀ·Q;
    reads what the dq kernel reads, writes dk and dv (f32)."""
    pairs = bh * causal_pairs(t, t, causal)
    flops = 4 * 2 * d * pairs
    nbytes = 3 * bh * t * d * in_bytes + bh * t * d * 4 \
        + 2 * bh * 8 * t * 4 + 2 * bh * t * d * 4
    return Cost(flops, nbytes)


FLASH_KERNELS = {"fwd": flash_fwd, "dq": flash_dq, "dkv": flash_dkv}


def least_time(cost: Cost, peak_flops: float, peak_bytes: float):
    """(seconds, bound): the larger of the compute and the memory time
    at the peaks, and which of the two it is."""
    t_c = cost.flops / peak_flops
    t_m = cost.bytes / peak_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def coll_bus_bytes(verb: str, nbytes: int, n: int) -> float:
    """Bus bytes of one call with ``nbytes`` per rank over ``n`` ranks,
    by nccl-tests' convention (doc/PERFORMANCE.md): allreduce
    2(n-1)/n·S; allgather and alltoall (n-1)/n of the per-rank total
    exchanged; bcast S."""
    if verb == "allreduce":
        return 2.0 * (n - 1) / n * nbytes
    if verb == "allgather":
        return (n - 1) * float(nbytes)
    if verb == "alltoall":
        return (n - 1) / n * nbytes
    if verb == "bcast":
        return float(nbytes)
    raise ValueError(f"no bus-byte rule for verb {verb!r}")


_SHAPE = re.compile(r"\b(bf16|f16|f32|s32|f8e4m3fn|s8)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "f8e4m3fn": 1, "s8": 1}


def _shapes(text: str):
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(text)]


def flash_kernel(hlo: str):
    """Which flash kernel a TPU custom call is, from the shapes in its
    HLO text: ``(kind, bh, t, d, in_bytes)``, or None for another
    custom call. Forward: (o [BH,T,D], lse [BH,8,T]) from q, k, v; dq:
    one [BH,T,D]; dk/dv: two [BH,T,D]. The kernels have no stable name
    yet, so their signature identifies them."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    head, _, rest = hlo.partition(" custom-call(")
    result = _shapes(head.partition(" = ")[2])
    operands = _shapes(rest.split("custom_call_target")[0])
    qkv = [s for dt, s in operands if len(s) == 3 and dt in ("bf16", "f16",
                                                              "f32")]
    if len(qkv) < 3 or not result or any(len(s) != 3 for _, s in result):
        return None
    bh, t, d = qkv[0]
    in_bytes = _BYTES[[dt for dt, s in operands if s == qkv[0]][0]]
    outs = [s for _, s in result]
    if outs == [(bh, t, d), (bh, 8, t)] and len(operands) == 5:
        return "fwd", bh, t, d, in_bytes
    if outs == [(bh, t, d)] and len(operands) == 8:
        return "dq", bh, t, d, in_bytes
    if outs == [(bh, t, d), (bh, t, d)] and len(operands) == 8:
        return "dkv", bh, t, d, in_bytes
    return None
