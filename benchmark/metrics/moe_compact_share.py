"""moe_compact_share: the share of the window's grouped-matmul calls
(``expert_gmm``, ``expert_gmm_dx``, ``expert_gmm_dw``) whose buffer is
shorter than the dropless worst case, in percent. The worst case holds
every token's assignments to the experts held here and a tile per
expert: N·min(k, held) + held·128 rows, for the N tokens of one data
shard's step, the top-k and the experts held of the cell's
configuration. A call's buffer height is its first two-dimensional
operand's first dimension, read from the call's HLO text as
``expert_gmm_roofline`` reads its operands. Silent where the window
holds no such call."""

from benchmark import flops_moe

TILE = 128  # the program's buffer rows per grid step


def worst_rows(config, traffic) -> int:
    dp = traffic.get("mesh", [1])[0]
    n = traffic["batch"] // dp * traffic["seq_len"]
    k, held = config["num_experts_per_tok"], config["num_experts"]
    return n * min(k, held) + held * TILE


def read(tr, record, cell, device):
    worst = worst_rows(cell.config, cell.traffic)
    w = tr.spans_named("bench.window")[0]
    calls = compact = 0
    for o in tr.ops:
        if not o.hlo or o.start < w.start or o.end > w.end or \
                not o.name.startswith("expert_gmm"):
            continue
        ops, _ = flops_moe.operands_and_result(o.hlo)
        buf = next((s for _, s in ops if len(s) == 2), None)
        if buf is None:
            continue
        calls += 1
        compact += buf[0] < worst
    if not calls:
        return None
    return 100.0 * compact / calls
