"""Flash-attention kernel microbenchmark at the flagship model shape.

Times (chained, RTT-subtracted) our Pallas kernel fwd and fwd+bwd against
alternatives, at B=32 H=16 T=1024 D=64 (one layer's worth of attention).
"""

from __future__ import annotations

import functools
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

    B, H, T, D = 32, 16, 1024, 64
    reps = 16

    rtt = _scalar_time(jax.jit(lambda x: jnp.sum(x)),
                       jnp.ones((8,), jnp.float32))
    print(f"rtt {rtt*1e3:.1f} ms", file=sys.stderr)

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, T, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, T, D), jnp.float32)

    # causal attention flops (counting only the lower triangle):
    # fwd = 2 matmuls * 2*T*T*D*0.5 each
    fwd_flops = B * H * 2 * T * T * D  # causal fwd
    bwd_flops = fwd_flops * 2.5
    peak = 197e12

    def timed(fn, label, flops):
        def chain(q_, k_, v_):
            def body(c, _):
                out = fn(c, k_, v_)
                return out.astype(c.dtype), None
            out, _ = lax.scan(body, q_, None, length=reps)
            return jnp.sum(out)
        t = max(_scalar_time(jax.jit(chain), q, k, v) - rtt, 1e-9) / reps
        print(f"{label:34s} {t*1e3:7.2f} ms  eff={flops/t/peak:.3f}",
              file=sys.stderr)
        return t

    # ---- ours fwd
    from ompi_tpu.ops.flash_attention import flash_block

    def ours_fwd(q_, k_, v_):
        o, _ = flash_block(q_, k_, v_, jnp.float32(0.0), jnp.float32(1.0),
                           layout="bhtd")
        return o

    timed(ours_fwd, "ours pallas fwd", fwd_flops)

    # ---- ours fwd+bwd
    def ours_grad(q_, k_, v_):
        def f(qq, kk_, vv):
            o, _ = flash_block(qq, kk_, vv, jnp.float32(0.0),
                               jnp.float32(1.0), layout="bhtd")
            return jnp.sum(o * 1e-3)
        g = jax.grad(f)(q_, k_, v_)
        return q_ + g

    timed(ours_grad, "ours pallas fwd+bwd", fwd_flops + bwd_flops)

    # ---- jax reference TPU flash kernel (library, not ours)
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash)

        def ref_fwd(q_, k_, v_):
            return jax_flash(q_, k_, v_, causal=True,
                             sm_scale=1.0 / np.sqrt(D))

        timed(ref_fwd, "jax library flash fwd", fwd_flops)

        def ref_grad(q_, k_, v_):
            def f(qq, kk_, vv):
                return jnp.sum(ref_fwd(qq, kk_, vv) * 1e-3)
            g = jax.grad(f)(q_, k_, v_)
            return q_ + g

        timed(ref_grad, "jax library flash fwd+bwd",
              fwd_flops + bwd_flops)
    except Exception as e:  # pragma: no cover
        print("jax library flash unavailable:", e, file=sys.stderr)

    # ---- plain XLA dense attention (bf16 scores)
    def dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_.astype(jnp.bfloat16),
                       k_.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        mask = lax.broadcasted_iota(jnp.int32, (T, T), 1) <= \
            lax.broadcasted_iota(jnp.int32, (T, T), 0)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16),
                          v_.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    timed(dense, "xla dense fwd", fwd_flops * 2)  # no causal skip

    def dense_grad(q_, k_, v_):
        def f(qq, kk_, vv):
            return jnp.sum(dense(qq, kk_, vv) * 1e-3)
        g = jax.grad(f)(q_, k_, v_)
        return q_ + g

    timed(dense_grad, "xla dense fwd+bwd", (fwd_flops + bwd_flops) * 2)
    return 0


if __name__ == "__main__":
    sys.exit(main())


def in_situ() -> int:
    """Reproduce the in-model attention cost: ring_attention under
    shard_map on a (1,1,1) mesh, with the lse-merge and real cotangents."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_tpu.ops.ring_attention import ring_attention
    B, H, T, D = 32, 16, 1024, 64
    reps = 16
    rtt = _scalar_time(jax.jit(lambda x: jnp.sum(x)),
                       jnp.ones((8,), jnp.float32))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, T, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, T, D), jnp.float32)

    fwd_flops = B * H * 2 * T * T * D
    bwd_flops = fwd_flops * 2.5
    peak = 197e12

    def attn_local(q_, k_, v_):
        return ring_attention(q_, k_, v_, "sp", 1, causal=True,
                              mxu_dtype=jnp.bfloat16, chunk=T,
                              layout="bhtd")

    spec = P(None, None, "sp", None)
    sm = jax.shard_map(attn_local, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec)

    def grad_step(q_, k_, v_):
        def f(qq):
            return jnp.sum(sm(qq, k_, v_) * 1e-3)
        return q_ + jax.grad(f)(q_)

    def chain(q_, k_, v_):
        def body(c, _):
            return grad_step(c, k_, v_).astype(c.dtype), None
        out, _ = lax.scan(body, q_, None, length=reps)
        return jnp.sum(out)

    t = max(_scalar_time(jax.jit(chain), q, k, v) - rtt, 1e-9) / reps
    print(f"{'in-situ ring(sp=1) fwd+bwd(dq)':34s} {t*1e3:7.2f} ms  "
          f"eff={(fwd_flops+bwd_flops)/t/peak:.3f}", file=sys.stderr)

    # and with grads to q, k, v (the model differentiates all three)
    def grad_all(q_, k_, v_):
        def f(qq, kk_, vv):
            return jnp.sum(sm(qq, kk_, vv) * 1e-3)
        gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)
        return q_ + gq + gk + gv

    def chain2(q_, k_, v_):
        def body(c, _):
            return grad_all(c, k_, v_).astype(c.dtype), None
        out, _ = lax.scan(body, q_, None, length=reps)
        return jnp.sum(out)

    t = max(_scalar_time(jax.jit(chain2), q, k, v) - rtt, 1e-9) / reps
    print(f"{'in-situ ring(sp=1) fwd+bwd(all)':34s} {t*1e3:7.2f} ms  "
          f"eff={(fwd_flops+bwd_flops)/t/peak:.3f}", file=sys.stderr)
    return 0


def from_einsum() -> int:
    """Kernel cost when q/k/v are einsum outputs (the model's layout),
    vs plain inputs — detects operand relayout copies around the
    pallas custom call."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.ops.flash_attention import flash_block

    B, H, T, D = 32, 16, 1024, 64
    reps = 16
    rtt = _scalar_time(jax.jit(lambda x: jnp.sum(x)),
                       jnp.ones((8,), jnp.float32))
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (B, T, H * D), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (H * D, H, 3 * D), jnp.float32) * 0.03

    fwd_flops = B * H * 2 * T * T * D
    bwd_flops = fwd_flops * 2.5
    peak = 197e12

    def attn(h_, w_):
        hb = h_.astype(jnp.bfloat16)
        wb = w_.astype(jnp.bfloat16)
        q = jnp.einsum("btd,dhf->bhtf", hb, wb[..., :D],
                       preferred_element_type=jnp.bfloat16)
        k = jnp.einsum("btd,dhf->bhtf", hb, wb[..., D:2 * D],
                       preferred_element_type=jnp.bfloat16)
        v = jnp.einsum("btd,dhf->bhtf", hb, wb[..., 2 * D:],
                       preferred_element_type=jnp.bfloat16)
        o, _ = flash_block(q, k, v, jnp.float32(0.0), jnp.float32(1.0),
                           layout="bhtd")
        return o

    def grad_step(h_, w_):
        def f(hh, ww):
            return jnp.sum(attn(hh, ww) * 1e-3)
        gh, gw = jax.grad(f, argnums=(0, 1))(h_, w_)
        return h_ + gh

    def chain(h_, w_):
        def body(c, _):
            return grad_step(c, w_).astype(c.dtype), None
        out, _ = lax.scan(body, h_, None, length=reps)
        return jnp.sum(out)

    t = max(_scalar_time(jax.jit(chain), h, w) - rtt, 1e-9) / reps
    # projection flops: 3 einsums fwd (2*B*T*HD*D each) x3 for fwd+bwd
    proj = 3 * 3 * 2 * B * T * (H * D) * D
    print(f"{'einsum-fed flash fwd+bwd':34s} {t*1e3:7.2f} ms  "
          f"(attn ideal {(fwd_flops+bwd_flops)/peak*1e3:.1f} + proj ideal "
          f"{proj/peak*1e3:.1f} ms)", file=sys.stderr)
    return 0
