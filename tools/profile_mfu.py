"""MFU ablation profiler: where does the flagship train step spend time?

Runs on the real chip. Every number is a K-step chained scan in ONE
program, scalar-readback synced, with the link RTT subtracted (the
bench.py methodology). Each ablation removes one cost center so the
deltas localize the non-MXU time.

Usage: python tools/profile_mfu.py [--ksteps 8]
"""

from __future__ import annotations

import sys
import time

import numpy as np


import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
from bench import _scalar_time  # one shared timing primitive


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh

    from ompi_tpu.models import transformer as tfm

    ksteps = 8
    if "--ksteps" in sys.argv:
        ksteps = int(sys.argv[sys.argv.index("--ksteps") + 1])

    dev = jax.devices()[0]
    print("device:", getattr(dev, "device_kind", dev), file=sys.stderr)

    cfg = tfm.FLAGSHIP
    batch = 32

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(
        0, cfg.vocab, size=(batch, cfg.seq_len)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, axis=1))

    rtt = _scalar_time(jax.jit(lambda x: jnp.sum(x)),
                       jnp.ones((8,), jnp.float32))
    print(f"rtt: {rtt*1e3:.1f} ms", file=sys.stderr)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    tokens = batch * cfg.seq_len
    flops = 6.0 * n_params * tokens \
        + 12.0 * cfg.n_layers * cfg.seq_len * cfg.d_model * tokens
    peak = 197e12

    def timed_chain(step_fn, p, t, g, label):
        def chain(p_, t_, g_):
            def body(carry, _):
                loss, newp = step_fn(carry, t_, g_)
                return newp, loss
            newp, losses = lax.scan(body, p_, None, length=ksteps)
            return jnp.sum(losses) + jnp.sum(newp["ln_f"])
        total = _scalar_time(jax.jit(chain), p, t, g)
        t_step = max(total - rtt, 1e-9) / ksteps
        mfu = flops / t_step / peak
        print(f"{label:32s} step={t_step*1e3:7.1f} ms  mfu={mfu:.3f}",
              file=sys.stderr)
        return t_step

    from jax.sharding import NamedSharding, PartitionSpec as P

    pspecs = tfm.param_specs(cfg)
    tok_spec = P("dp", "sp")

    def make_step(loss_mode="ce", attn_mode="flash", fwd_only=False):
        def loss_local(p, tk, tg):
            import ompi_tpu.ops.ring_attention as ra
            if attn_mode == "identity":
                orig = ra.ring_attention

                def fake_ring(q, k, v, *a, **kw):
                    return (q + k + v).astype(q.dtype)
                ra.ring_attention = fake_ring
                try:
                    logits = tfm.forward_local(p, tk, cfg, tp=1, sp=1,
                                               in_mesh=True)
                finally:
                    ra.ring_attention = orig
            elif attn_mode == "dense":
                import jax.numpy as _jnp
                from jax import lax as _lax
                orig = ra.ring_attention

                def dense_ring(q, k, v, *a, **kw):
                    B_, H_, T_, D_ = q.shape
                    s_ = _jnp.einsum(
                        "bhqd,bhkd->bhqk", q.astype(_jnp.bfloat16),
                        k.astype(_jnp.bfloat16),
                        preferred_element_type=_jnp.float32) / float(D_)**0.5
                    m_ = _lax.broadcasted_iota(_jnp.int32, (T_, T_), 1) <= \
                        _lax.broadcasted_iota(_jnp.int32, (T_, T_), 0)
                    s_ = _jnp.where(m_[None, None], s_, -1e30)
                    p_ = jax.nn.softmax(s_, axis=-1)
                    return _jnp.einsum(
                        "bhqk,bhkd->bhqd", p_.astype(_jnp.bfloat16),
                        v.astype(_jnp.bfloat16),
                        preferred_element_type=_jnp.float32).astype(q.dtype)
                ra.ring_attention = dense_ring
                try:
                    logits = tfm.forward_local(p, tk, cfg, tp=1, sp=1,
                                               in_mesh=True)
                finally:
                    ra.ring_attention = orig
            else:
                logits = tfm.forward_local(p, tk, cfg, tp=1, sp=1,
                                           in_mesh=True)
            denom = float(batch * cfg.seq_len)
            if loss_mode == "ce":
                logz = jnp.log(jnp.sum(jnp.exp(
                    logits - jnp.max(logits, -1, keepdims=True)), -1)) + \
                    jnp.max(logits, -1)
                gold = jnp.take_along_axis(
                    logits, tg[..., None], axis=-1)[..., 0]
                return jnp.sum(logz - gold) / denom
            return jnp.sum(logits * 1e-6) / denom

        def step_local(p, tk, tg):
            if fwd_only:
                loss = loss_local(p, tk, tg)
                # perturb params so the scan carry stays live
                newp = jax.tree.map(
                    lambda x: x * (1.0 + 1e-12 * loss), p)
                return loss, newp
            loss, grads = jax.value_and_grad(loss_local)(p, tk, tg)
            loss = lax.psum(loss, ("dp", "sp"))
            newp = jax.tree.map(
                lambda x, gr: (x - cfg.lr * gr).astype(x.dtype), p, grads)
            return loss, newp

        return jax.shard_map(step_local, mesh=mesh,
                             in_specs=(pspecs, tok_spec, tok_spec),
                             out_specs=(P(), pspecs))

    params_p = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, pspecs)
    sh = NamedSharding(mesh, tok_spec)
    toks_p = jax.device_put(toks, sh)
    tgts_p = jax.device_put(tgts, sh)

    t_full = timed_chain(make_step(), params_p, toks_p, tgts_p,
                         "full step (flash, CE)")
    timed_chain(make_step(loss_mode="sum"), params_p, toks_p, tgts_p,
                "no-CE loss (sum of logits)")
    timed_chain(make_step(attn_mode="identity"), params_p, toks_p, tgts_p,
                "identity attention")
    timed_chain(make_step(attn_mode="dense"), params_p, toks_p, tgts_p,
                "dense-xla attention")
    timed_chain(make_step(fwd_only=True), params_p, toks_p, tgts_p,
                "forward only")
    print(f"ideal matmul-bound step: {flops/peak*1e3:.1f} ms",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
