"""moe_train_mfu: the whole step's share of the chips' peak for the
sparse-expert configuration. Model FLOPs of a step (``flops_moe``: 6 ×
the attention, router and head parameters × tokens, 6 × the expert
parameters × the rows the step reports routed here, and the attention
scores over the attending pairs; no recomputation) times the traced
window's steps per second, over chips times the published bf16 peak,
in percent."""

from benchmark import flops_moe
from benchmark.peaks import peaks


def read(tr, record, cell, device):
    rate = record["metrics"].get("train_tokens_per_s")
    rows = record.get("moe_rows")
    if not rate or not rows or not record.get("steps"):
        return None
    t = cell.traffic
    per_step = flops_moe.train_flops_per_step(
        cell.config, t["batch"], t["seq_len"],
        sum(map(sum, rows)) / record["steps"])
    steps_per_s = rate / record["tokens_per_step"]
    return per_step * steps_per_s / (device["count"] *
                                      peaks(device["kind"]).flops) * 100.0
