"""Operations and bytes of the sparse-expert configuration
(``mellum2_ep8``), computed from shapes and from the rows the step
reports: the yardstick for ``moe_train_mfu``, ``gqa_flash_roofline`` and
``expert_gmm_roofline``. Kept with the benchmark, beside ``flops.py``,
whose conventions it keeps: matmul FLOPs only, 2 per multiply-add.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmark.flops import Cost

_SHAPE = re.compile(r"\b(bf16|f32|s32)\[([\d,]*)\]")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array type in HLO text, in order."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(text)]


def operands_and_result(hlo: str):
    """(operand shapes, result shapes) of a custom call's HLO text."""
    head, _, rest = hlo.partition(" custom-call(")
    return (shapes(rest.split("custom_call_target")[0]),
            shapes(head.partition(" = ")[2]))


def attn_pairs(t: int, window: int = 0) -> int:
    """(query, key) pairs that attend in one sequence of ``t``: the
    causal half with its diagonal, or with a band of ``window`` keys
    (query i sees j iff 0 <= i - j < window) the band's."""
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_windows(config) -> List[int]:
    """Each layer's band (0: full causal)."""
    w = config["sliding_window"]
    return [w if k == "sliding_attention" else 0
            for k in config["layer_types"]]


def dense_params(config) -> int:
    """Parameters every token passes through as a matmul: per layer the
    q, k, v and output projections and the router (its full width), and
    the output head. The embedding is a lookup and the norms are no
    matmul."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    Hkv, hd = config["num_key_value_heads"], config["head_dim"]
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    router = D * config["router_experts"]
    return config["num_hidden_layers"] * (attn + router) + \
        config["vocab_size"] * D


def expert_params_per_row(config) -> int:
    """Gate, up and down of one expert: what one routed row passes."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def train_flops_per_step(config, batch: int, seq_len: int,
                         rows: float) -> float:
    """Model FLOPs of one training step, forward and backward, no
    recomputation: 6 × dense parameters × tokens, 6 × expert parameters
    × the rows routed to the experts held here, and the attention
    scores: 2 matmuls forward and 5 backward over each layer's attending
    pairs, per query head."""
    H, hd = config["num_attention_heads"], config["head_dim"]
    scores = sum(7 * 2 * hd * H * batch * attn_pairs(seq_len, w)
                 for w in layer_windows(config))
    return 6.0 * dense_params(config) * batch * seq_len + \
        6.0 * expert_params_per_row(config) * rows + scores


def gqa_flash(kind: str, bh: int, bhkv: int, t: int, d: int, window: int,
              in_bytes: int = 2) -> Cost:
    """A grouped (and banded) flash kernel's least work: FLOPs over the
    attending pairs of its ``bh`` query heads (forward: S = QKᵀ, O = PV;
    backward ``dqkv``: S, dP = dO·Vᵀ, dV, dK, dQ); bytes with q, dO, dq,
    o and the lse and delta rows per query head, and K, V, dK and dV
    once per K/V head."""
    pairs = bh * attn_pairs(t, window)
    rows = bh * 8 * t * 4  # one head's lse (or delta) rows, f32
    q = bh * t * d
    kv = bhkv * t * d
    if kind == "fwd":
        return Cost(2 * 2 * d * pairs,
                    q * in_bytes + 2 * kv * in_bytes + q * 4 + rows)
    return Cost(5 * 2 * d * pairs,
                q * in_bytes + 2 * kv * in_bytes + q * 4 + 2 * rows
                + q * 4 + 2 * kv * 4)


def gqa_flash_call(name: str, hlo: str, windows: Dict[bool, int]):
    """The Cost of a ``flash_gqa_*``/``flash_win_*`` call from its
    HLO text, or None: q is the first 3-d operand and K the second.
    ``windows`` maps banded (a ``win_`` name) to the band."""
    ops, _ = operands_and_result(hlo)
    three = [(dt, s) for dt, s in ops if len(s) == 3 and dt in ("bf16",
                                                                  "f32")]
    if len(three) < 2:
        return None
    (dt, (bh, t, d)), bhkv = three[0], three[1][1][0]
    in_bytes = 2 if dt == "bf16" else 4
    kind = "fwd" if name.split(".")[0].endswith("fwd") else "dqkv"
    return gqa_flash(kind, bh, bhkv, t, d, windows["win_" in name],
                     in_bytes)


def gmm_call(name: str, hlo: str, rows: float) -> Optional[Cost]:
    """A grouped-matmul call's least work for ``rows`` routed rows:
    ``expert_gmm``/``expert_gmm_dx`` read x [M, K] and the E experts'
    bf16 weights and write [M, N] bf16; ``expert_gmm_dw`` reads x and dy
    [M, N] and writes [E, K, N] f32. Only the routed rows count, not the
    padding of the buffer."""
    ops, res = operands_and_result(hlo)
    mats = [s for _, s in ops if len(s) >= 2]
    if len(mats) < 2 or not res:
        return None
    if name.startswith("expert_gmm_dw"):
        e, k, n = res[0][1]
        return Cost(2.0 * rows * k * n, rows * (k + n) * 2 + e * k * n * 4)
    k, n = mats[0][1], res[0][1][1]
    e = mats[1][0]
    return Cost(2.0 * rows * k * n, rows * (k + n) * 2 + e * k * n * 2)
