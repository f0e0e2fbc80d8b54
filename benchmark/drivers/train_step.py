"""The flagship train step (``models.transformer.make_train_step``),
step after step on seeded token batches, with parameters passed from
each step to the next.

Traffic keys: ``batch`` and ``seq_len`` (tokens per step are their
product; the learned positions are sized to ``seq_len``); ``mesh``, the
(dp, sp, tp) layout over the cell's chips; ``batches``, how many seeded
batches are placed on the device during set-up and cycled; ``ref_rows``,
the rows per block of the float32 reference.

Set-up builds the step and its state and drives it through the three
steps that the check compares with the reference; the window continues
from there with the same call and feed, keeping one step queued behind
the one that runs.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict

import numpy as np

from benchmark.harness import Check
from benchmark.reference import lm as ref

CHECKED_STEPS = 3


def shape(config, traffic) -> ref.Shape:
    return ref.Shape(vocab=config["vocab"], d_model=config["d_model"],
                     n_heads=config["n_heads"], n_layers=config["n_layers"],
                     d_ff=config["d_ff"], seq_len=traffic["seq_len"],
                     lr=config["lr"])


def _norms(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y)))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def setup(ctx) -> Dict[str, Any]:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_tpu.models import transformer as tfm

    t = ctx.traffic
    s = shape(ctx.config, t)
    cfg = tfm.Config(vocab=s.vocab, d_model=s.d_model, n_heads=s.n_heads,
                     n_layers=s.n_layers, d_ff=s.d_ff, seq_len=s.seq_len,
                     lr=s.lr)
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
        loss, p = step(p, toks[i], tgts[i])
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

    step, toks, tgts = st["step"], st["toks"], st["tgts"]
    K = len(toks)
    p = st["params"]
    span, perf = ctx.span, time.perf_counter
    losses, prev = [], None
    i = CHECKED_STEPS
    with span("window"):
        w0 = perf()
        while True:
            with span("step", i=i):
                loss, p = step(p, toks[i % K], tgts[i % K])
            losses.append(loss)
            i += 1
            if prev is not None:
                prev.block_until_ready()
            prev = loss
            if perf() - w0 >= ctx.seconds:
                break
        jax.block_until_ready((loss, p))
        window_s = perf() - w0
    st["params"] = p
    steps = len(losses)
    failed = int(sum(not np.isfinite(float(x)) for x in losses))
    return {"window_s": window_s, "steps": steps, "attempted": steps,
            "failed": failed,
            "tokens_per_step": st["tokens_per_step"],
            "metrics": {"train_tokens_per_s":
                        steps * st["tokens_per_step"] / window_s}}


def traced(ctx, st, record) -> None:
    """The compiler's own account of the step's memory, beside the
    device's peak that the harness reads: the two disagree (PR 21) and
    neither is a metric yet. The traced run only, since it loads the
    step's executable a second time."""
    p = st["params"]
    ma = st["step"].lower(p, st["toks"][0], st["tgts"][0]).compile() \
        .memory_analysis()
    print(f"train_step: compiler temporaries {ma.temp_size_in_bytes} B, "
          f"arguments {ma.argument_size_in_bytes} B, outputs "
          f"{ma.output_size_in_bytes} B", file=sys.stderr, flush=True)


def readings(prog_losses, prog_grad, prog_change, ref_losses, ref_grad,
             ref_change) -> Dict[str, float]:
    """The three numbers compared: the largest gap of a step's loss, and
    the worst leaf's gap of the first gradient's norm and of the
    parameters' change after the checked steps."""
    keep = ref.moving_leaves(ref_grad)
    return {
        "loss_gap": float(np.max(np.abs(np.subtract(prog_losses,
                                                    ref_losses)))),
        "grad_gap": ref.worst_leaf_gap(prog_grad, ref_grad, keep),
        "change_gap": ref.worst_leaf_gap(prog_change, ref_change, keep),
    }


def reference(ctx, s: ref.Shape, **kw):
    """The float32 reference's three steps from the seed's weights and
    batches (``kw`` plants the control's precision or a fault)."""
    params = ref.init_params(ref.key_for(ctx.seed, 0), s)
    batches = ref.token_batches(ref.key_for(ctx.seed, 1), s,
                                int(ctx.traffic["batch"]), CHECKED_STEPS)
    return ref.three_steps(params, batches, s, int(ctx.traffic["ref_rows"]),
                           CHECKED_STEPS, **kw)


def check(ctx, st, record):
    import jax

    jax.tree.map(lambda a: a.delete(), (st.pop("params"), st.pop("toks"),
                                        st.pop("tgts")))
    st.pop("step")
    got = readings(st["losses"], st["grad_norms"], st["change_norms"],
                   *reference(ctx, st["shape"]))
    record["checked_losses"] = st["losses"]
    return [Check(k, v, float(ctx.limits[k])) for k, v in got.items()]
