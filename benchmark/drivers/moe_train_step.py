"""The sparse-expert train step (``models.transformer.make_train_step``
with a ``MoEConfig``), step after step on seeded token batches, with
parameters passed from each step to the next: the ``train_step``
driver's pattern and check, with the configuration's model.

Configuration keys are the published config.json's (``hidden_size``,
``layer_types``, ``rope_parameters``, ...), with ``num_experts`` the
experts held here from ``first_expert`` on and ``router_experts`` the
router's width. Traffic keys: ``batch`` and ``seq_len``; ``mesh``, the
(dp, sp, tp) layout (sp = tp = 1); ``batches``, how many seeded batches
are placed on the device during set-up and cycled; ``ref_query_rows``,
the query rows per block of the float32 reference's attention.

The step returns the rows each held expert received in each layer; the
window keeps them on the device and reads them after its last step, for
``moe_train_mfu`` and ``expert_gmm_roofline``, and hands each step's
total to the program's ``moe.rows`` counter.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

# ``traced`` (the compiler's memory account) is the train_step driver's
from benchmark.drivers.train_step import (CHECKED_STEPS, _norms, readings,
                                          traced)  # noqa: F401
from benchmark.harness import Check
from benchmark.reference import mellum as ref

LAYER_KIND = {"sliding_attention": "sliding", "full_attention": "full"}


def shape(config, traffic) -> ref.Shape:
    rp = config["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("the layers' rope_theta differ")
    yarn = (float(full["factor"]), int(full["original_max_position_embeddings"]),
            float(full["beta_fast"]), float(full["beta_slow"]),
            float(full["attention_factor"]))
    return ref.Shape(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=tuple(LAYER_KIND[k] for k in config["layer_types"]),
        window=config["sliding_window"], rope_theta=float(full["rope_theta"]),
        yarn=yarn, n_experts=config["router_experts"],
        first_expert=config["first_expert"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"], seq_len=traffic["seq_len"],
        lr=config["lr"], eps=config["rms_norm_eps"])


def model_config(s: ref.Shape):
    from ompi_tpu.models import transformer as tfm

    return tfm.MoEConfig(
        vocab=s.vocab, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        layer_types=s.layer_types, window=s.window, rope_theta=s.rope_theta,
        yarn=s.yarn, n_experts=s.n_experts, first_expert=s.first_expert,
        experts_held=s.experts_held, top_k=s.top_k, d_expert=s.d_expert,
        lr=s.lr, eps=s.eps)


def setup(ctx) -> Dict[str, Any]:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_tpu.models import transformer as tfm

    t = ctx.traffic
    s = shape(ctx.config, t)
    cfg = model_config(s)
    mesh = Mesh(np.array(ctx.devices).reshape(t["mesh"]), ("dp", "sp", "tp"))
    step, _ = tfm.make_train_step(mesh, cfg)
    pshard = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                          tfm.param_specs(cfg))
    tshard = NamedSharding(mesh, P("dp", "sp"))
    B, K = int(t["batch"]), int(t["batches"])
    params = jax.jit(lambda k: ref.init_params(k, s),
                     out_shardings=pshard)(ref.key_for(ctx.seed, 0))

    def split(k):
        b = ref.token_batches(k, s, B, K)
        return [b[i, :, :-1] for i in range(K)], [b[i, :, 1:]
                                                  for i in range(K)]

    toks, tgts = jax.jit(split, out_shardings=([tshard] * K, [tshard] * K))(
        ref.key_for(ctx.seed, 1))
    norms = jax.jit(_norms)
    p0 = params
    losses, p = [], p0
    for i in range(CHECKED_STEPS):
        (loss, _), p = step(p, toks[i], tgts[i])
        losses.append(loss)
        if i == 0:
            g1 = norms(p0, p)
    d3 = norms(p, p0)
    st = {"step": step, "params": p, "toks": toks, "tgts": tgts,
          "tokens_per_step": B * s.seq_len, "shape": s,
          "losses": [float(x) for x in losses],
          "grad_norms": np.asarray(g1) / s.lr,
          "change_norms": np.asarray(d3)}
    del params, p0  # freed unless the step handed them back
    return st


def measure(ctx, st) -> Dict[str, Any]:
    import jax

    from ompi_tpu.runtime import trace

    step, toks, tgts = st["step"], st["toks"], st["tgts"]
    K = len(toks)
    p = st["params"]
    span, perf = ctx.span, time.perf_counter
    losses, rows, prev = [], [], None
    i = CHECKED_STEPS
    with span("window"):
        w0 = perf()
        while True:
            with span("step", i=i):
                (loss, r), p = step(p, toks[i % K], tgts[i % K])
            losses.append(loss)
            rows.append(r)
            i += 1
            if prev is not None:
                prev.block_until_ready()
            prev = loss
            if perf() - w0 >= ctx.seconds:
                break
        jax.block_until_ready((loss, p))
        window_s = perf() - w0
    st["params"] = p
    rows = np.stack([np.asarray(r) for r in rows])  # [steps, L, E]
    for r in rows:
        trace.counter("moe.rows", int(r.sum()))
    steps = len(losses)
    failed = int(sum(not np.isfinite(float(x)) for x in losses))
    return {"window_s": window_s, "steps": steps, "attempted": steps,
            "failed": failed, "tokens_per_step": st["tokens_per_step"],
            "moe_rows": rows.sum(axis=0).tolist(),
            "metrics": {"train_tokens_per_s":
                        steps * st["tokens_per_step"] / window_s}}


def reference(ctx, s: ref.Shape, **kw):
    """The float32 reference's three steps from the seed's weights and
    batches (``kw`` plants the control's precision or a fault)."""
    params = ref.init_params(ref.key_for(ctx.seed, 0), s)
    batches = ref.token_batches(ref.key_for(ctx.seed, 1), s,
                                int(ctx.traffic["batch"]), CHECKED_STEPS)
    return ref.three_steps(params, batches, s, CHECKED_STEPS,
                           rows=int(ctx.traffic["ref_query_rows"]), **kw)


def check(ctx, st, record):
    import jax

    jax.tree.map(lambda a: a.delete(), (st.pop("params"), st.pop("toks"),
                                        st.pop("tgts")))
    st.pop("step")
    got = readings(st["losses"], st["grad_norms"], st["change_norms"],
                   *reference(ctx, st["shape"]))
    record["checked_losses"] = st["losses"]
    return [Check(k, v, float(ctx.limits[k])) for k, v in got.items()]


FAULTS = {"fault_half_batch": lambda s: {"keep_tokens": s.seq_len // 2},
          "fault_full_causal": lambda s: {"window_off": True},
          "fault_no_renorm": lambda s: {"renorm": False}}


def calibration(ctx, control_dtype):
    """[(kind, readings)] of the control (the reference's matmul
    operands in ``control_dtype``) and of each planted fault, each
    against the sound reference: what the limits must catch."""
    s = shape(ctx.config, ctx.traffic)
    want = reference(ctx, s)
    out = [("control", readings(*reference(ctx, s, mm_dtype=control_dtype),
                                *want))]
    for kind, kw in FAULTS.items():
        out.append((kind, readings(*reference(ctx, s, **kw(s)), *want)))
    return out
