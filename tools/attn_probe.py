"""Quick on-chip probes for the MFU hunt: isolated flash fwd+bwd cost,
remat variants of the full step, and memory analysis. Chained-scan timed
(bench.py methodology)."""

import sys

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
from bench import _scalar_time


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_tpu.models import transformer as tfm

    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    K = 8
    dev = jax.devices()[0]
    print("device:", getattr(dev, "device_kind", dev), file=sys.stderr)
    rtt = _scalar_time(jax.jit(lambda x: jnp.sum(x)),
                       jnp.ones((8,), jnp.float32))
    print(f"rtt {rtt*1e3:.1f} ms", file=sys.stderr)

    B, H, T, D = 32, 16, 1024, 64

    if which in ("all", "flash"):
        from ompi_tpu.ops.flash_attention import flash_block

        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, T, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D),
                              jnp.bfloat16)

        def one(q_, k_, v_):
            o = flash_block(q_, k_, v_, 0.0, 1.0, layout="bhtd")[0]
            return o

        def fwd_chain(q_, k_, v_):
            def body(c, _):
                o = one(c, k_, v_)
                return o.astype(jnp.bfloat16), jnp.float32(0)
            c, _ = lax.scan(body, q_, None, length=K)
            return jnp.sum(c.astype(jnp.float32))

        t = (max(_scalar_time(jax.jit(fwd_chain), q, k, v) - rtt, 0)) / K
        # causal fwd flops: 2 matmuls * T^2/2 * D * 2 per BH
        fl = 2 * 2 * (T * T // 2) * D * B * H
        print(f"flash fwd          {t*1e3:8.2f} ms  "
              f"{fl/t/1e12:6.1f} TF/s", file=sys.stderr)

        def vjp_chain(q_, k_, v_):
            def body(c, _):
                o, pull = jax.vjp(lambda a, b, cc: one(a, b, cc), c, k_, v_)
                dq, dk, dv = pull(o)
                return (c + dq.astype(jnp.bfloat16)), jnp.sum(dk) + jnp.sum(dv)
            c, s = lax.scan(body, q_, None, length=K)
            return jnp.sum(c.astype(jnp.float32)) + jnp.sum(s)

        t2 = (max(_scalar_time(jax.jit(vjp_chain), q, k, v) - rtt, 0)) / K
        fl2 = fl * 3.5  # fwd + recompute-heavy bwd
        print(f"flash fwd+bwd      {t2*1e3:8.2f} ms  "
              f"(~{fl2/t2/1e12:5.1f} TF/s)", file=sys.stderr)

    if which in ("all", "step"):
        for remat, label in ((False, "remat=False"), (True, "remat=True")):
            cfg = tfm.Config(vocab=32768, d_model=1024, n_heads=16,
                             n_layers=8, d_ff=4096, seq_len=1024,
                             remat=remat)
            mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                        ("dp", "sp", "tp"))
            params = tfm.init_params(jax.random.PRNGKey(0), cfg)
            rng = np.random.RandomState(0)
            toks = jnp.asarray(rng.randint(0, cfg.vocab, (B, T), np.int64)
                               .astype(np.int32))
            tgts = jnp.asarray(np.roll(np.asarray(toks), -1, 1))
            step, place = tfm.make_train_step(mesh, cfg)
            p, t_, g_ = place(params, toks, tgts)

            def chain(p_, tk_, tg_):
                def body(c, _):
                    loss, newp = step(c, tk_, tg_)
                    return newp, loss
                newp, losses = lax.scan(body, p_, None, length=K)
                return jnp.sum(losses) + jnp.sum(newp["ln_f"])

            jc = jax.jit(chain)
            low = jc.lower(p, t_, g_).compile()
            mem = low.memory_analysis()
            ts = (max(_scalar_time(jc, p, t_, g_) - rtt, 0)) / K
            n_params = sum(x.size for x in
                           jax.tree_util.tree_leaves(params))
            fl = 6.0 * n_params * B * T \
                + 12.0 * cfg.n_layers * T * cfg.d_model * B * T
            from ompi_tpu.accelerator.tpu import peaks

            mfu = (f"mfu={fl/ts/peaks(dev)[0]:.3f}"
                   if dev.platform == "tpu" else "mfu=n/a (not a TPU)")
            print(f"step {label}:  {ts*1e3:7.1f} ms  {mfu}  "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GB",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
