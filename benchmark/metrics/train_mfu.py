"""train_mfu: the whole step's share of the chips' peak. Model FLOPs
per token (6N + 12·L·T·D, no recomputation) times the traced window's
tokens per second, over chips times the published bf16 peak, in
percent."""

from benchmark import flops
from benchmark.peaks import peaks


def read(tr, record, cell, device):
    c, t = cell.config, cell.traffic
    rate = record["metrics"].get("train_tokens_per_s")
    if not rate:
        return None
    n = flops.lm_params(c["vocab"], c["d_model"], c["n_layers"], c["d_ff"],
                        t["seq_len"])
    per_token = flops.lm_train_flops_per_token(n, c["n_layers"],
                                               t["seq_len"], c["d_model"])
    return per_token * rate / (device["count"] * peaks(device["kind"]).flops) \
        * 100.0
