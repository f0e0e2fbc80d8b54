"""Flagship demo model: a causal-LM transformer parallelized with the
framework's collective vocabulary.

This is the framework's end-to-end proof (the analog of the reference's
examples/ + the OSU/Horovod ladder configs in BASELINE.md): a training step
whose every communication — tensor-parallel activation reductions,
sequence-parallel ring attention, data-parallel gradient allreduce — is an
ompi_tpu collective (ompi_tpu.parallel.axes in-mesh verbs + ops.ring_attention),
laid out Megatron-style over a (dp, sp, tp) mesh:

- tp: QKV/W1 column-parallel, WO/W2 row-parallel with psum of partial
  outputs (attention heads sharded over tp)
- sp: sequence dim sharded; attention runs as ring attention (ppermute
  K/V rotation with flash-style accumulation)
- dp: batch sharded; gradients allreduced (the "Horovod-style 1GB gradient
  allreduce" BASELINE config is exactly this traffic)

All matmuls run in bfloat16 on the MXU with float32 accumulation/params.

A second architecture, ``MoEConfig``, takes the same step on one shard
of sequence and tensor (sp = tp = 1): grouped-query attention with
rotary positions, layers of two kinds (a sliding band of keys with
default frequencies, or full causal with yarn), RMSNorm, a sparse
expert layer that holds some of the router's experts
(ops/moe.py), and an untied output head. Its step also returns the rows
each held expert received in each layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Config:
    vocab: int = 512
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    seq_len: int = 128
    lr: float = 1e-2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# The flagship step on one v5e chip (about 135M params). head_dim=128
# fills the MXU's 128-lane contraction (hd=64 capped the attention
# matmuls at half the array). Batch 36 came from a pre-PR-1 sweep through
# the retired device link (32/36/40/44/48), not measured on the chip:
# temporaries about 10 GB of the 16 GB HBM.
FLAGSHIP = Config(vocab=32768, d_model=1024, n_heads=8, n_layers=8,
                  d_ff=4096, seq_len=1024)
FLAGSHIP_BATCH = 36


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A GQA decoder with sliding and full layers and sparse experts, of
    which this shard holds ``experts_held`` from ``first_expert`` on
    (Mellum2's block at ``benchmark/configs/mellum2_ep8.json``)."""
    vocab: int = 512
    d_model: int = 128
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    layer_types: Tuple[str, ...] = ("sliding", "full")
    window: int = 64
    rope_theta: float = 500000.0
    # yarn on full layers: factor, original max positions, beta_fast,
    # beta_slow, attention factor
    yarn: Tuple[float, int, float, float, float] = (
        16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    n_experts: int = 16       # the router's width
    first_expert: int = 0
    experts_held: int = 4
    top_k: int = 4
    d_expert: int = 32
    lr: float = 1e-2
    eps: float = 1e-6

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


# tokens of each sequence in one chunk of the streamed loss: at batch 1
# the flagship's 128 would make 64 small vocabulary matmuls of a step
_MOE_LOSS_CHUNK = 1024


def _moe_init_params(key, cfg: MoEConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, F = cfg.experts_held, cfg.d_expert
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    blocks = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 8)
        blocks.append({
            "ln1": jnp.ones((D,), jnp.float32),
            "wq": normal(k[0], (D, H, hd), D),
            "wk": normal(k[1], (D, Hkv, hd), D),
            "wv": normal(k[2], (D, Hkv, hd), D),
            "wo": normal(k[3], (H, hd, D), H * hd),
            "ln2": jnp.ones((D,), jnp.float32),
            "router": normal(k[4], (D, cfg.n_experts), D),
            "w_gate": normal(k[5], (E, D, F), D),
            "w_up": normal(k[6], (E, D, F), D),
            "w_down": normal(k[7], (E, F, D), F),
        })
    return {"embed": normal(keys[0], (cfg.vocab, D), D),
            "head": normal(keys[1], (cfg.vocab, D), D),
            "ln_f": jnp.ones((D,), jnp.float32), "blocks": blocks}


_MOE_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router", "w_gate",
               "w_up", "w_down")


def init_params(key, cfg: Config) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    if isinstance(cfg, MoEConfig):
        return _moe_init_params(key, cfg)

    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = lambda d: 1.0 / np.sqrt(d)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                   jnp.float32) * scale(cfg.d_model),
        "pos": jax.random.normal(keys[1], (cfg.seq_len, cfg.d_model),
                                 jnp.float32) * scale(cfg.d_model),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "blocks": [],
    }
    for i in range(cfg.n_layers):
        k1, k2, k3, k4 = jax.random.split(keys[2 + i], 4)
        params["blocks"].append({
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            # [D, H, 3*hd]: sharding the heads dim over tp keeps each
            # shard's q/k/v intact (a flat [D, 3D] column shard would mix
            # q columns with k columns)
            "qkv": jax.random.normal(
                k1, (cfg.d_model, cfg.n_heads, 3 * cfg.head_dim),
                jnp.float32
            ) * scale(cfg.d_model),
            "wo": jax.random.normal(
                k2, (cfg.d_model, cfg.d_model), jnp.float32
            ) * scale(cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "w1": jax.random.normal(
                k3, (cfg.d_model, cfg.d_ff), jnp.float32
            ) * scale(cfg.d_model),
            "w2": jax.random.normal(
                k4, (cfg.d_ff, cfg.d_model), jnp.float32
            ) * scale(cfg.d_ff),
        })
    return params


def param_specs(cfg: Config):
    """Megatron sharding plan as PartitionSpecs (tp axis only; every param
    is replicated over dp and sp)."""
    from jax.sharding import PartitionSpec as P

    if isinstance(cfg, MoEConfig):
        # one tensor shard: every parameter whole on every device
        return {"embed": P(), "head": P(), "ln_f": P(),
                "blocks": [{n: P() for n in _MOE_LEAVES}
                           for _ in range(cfg.n_layers)]}
    block = {
        "ln1": P(), "ln2": P(),
        "qkv": P(None, "tp", None),  # heads sharded (column parallel)
        "wo": P("tp", None),         # row parallel -> psum
        "w1": P(None, "tp"),         # column parallel
        "w2": P("tp", None),         # row parallel -> psum
    }
    return {
        "embed": P(), "pos": P(), "ln_f": P(),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
    }


def _ln(x, g):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return x * g


def _mm(a, w):
    """bf16 MXU matmul with f32 accumulation."""
    import jax.numpy as jnp

    return jnp.einsum("...d,df->...f", a.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)




def features_local(params, tokens, cfg: Config, tp: int = 1, sp: int = 1,
                   in_mesh: bool = False):
    """Forward on local shards up to the final layernorm (pre-logits
    features [B, T, D]). Inside shard_map (``in_mesh=True``): tokens
    [B/dp, S/sp]; tp-sharded weights arrive as local slices; activations
    psum over 'tp' after every row-parallel matmul (emitted even when
    tp == 1 — a size-1 psum is free and lets shard_map prove the loss is
    tp-replicated); attention rotates K/V over 'sp'. With in_mesh=False
    this is the plain single-device forward.
    """
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops.mxu import einsum_bf16
    from ompi_tpu.ops.ring_attention import ring_attention
    from ompi_tpu.parallel import axes

    B, T = tokens.shape
    h_local = cfg.n_heads // tp
    hd = cfg.head_dim

    if in_mesh:
        seq_off = axes.rank("sp") * T
        pos_idx = seq_off + jnp.arange(T)
    else:
        pos_idx = jnp.arange(T)
    x = params["embed"][tokens] + params["pos"][pos_idx][None]

    def block(x, blk):
        with jax.named_scope("attention"):
            h = _ln(x, blk["ln1"])
            w_qkv = blk["qkv"]  # local [D, H/tp, 3*hd]
            # three projections emitted straight into the attention kernel's
            # native [B, H, T, hd] layout: a fused qkv einsum + split costs a
            # strided-slice relayout of 3x128MB per block (measured +8.7ms per
            # layer on v5e); separate slices of the weight are free
            hb = h.astype(jnp.bfloat16)
            wb = w_qkv.astype(jnp.bfloat16)
            # bf16 q/k/v via einsum_bf16: the attention kernel consumes bf16
            # tiles anyway, and keeping the projections (= the kernel's saved
            # residuals) in bf16 halves their HBM footprint — at the flagship
            # shape the f32 version sat on the 15.75GB ceiling and XLA
            # spilled (r4 ablation: attention cost 178ms in-model vs 87ms
            # isolated); the backward transpose dots still accumulate f32
            q = einsum_bf16("btd,dhf->bhtf", hb, wb[..., :hd])
            k = einsum_bf16("btd,dhf->bhtf", hb, wb[..., hd:2 * hd])
            v = einsum_bf16("btd,dhf->bhtf", hb, wb[..., 2 * hd:])
            if in_mesh:
                # full-tile chunk: the flash/recompute backward keeps the
                # dense tile memory-safe; long-seq configs shrink the tile
                # via the chunk arg (lax fallback only)
                att = ring_attention(q, k, v, "sp", sp, causal=True,
                                     mxu_dtype=jnp.bfloat16, chunk=T,
                                     layout="bhtd")
            else:
                from ompi_tpu.ops.ring_attention import reference_attention

                tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
                att = tr(reference_attention(tr(q), tr(k), tr(v), causal=True))
            # row-parallel output projection contracted directly over (h, d):
            # no [B,T,H*hd] relayout of the attention output
            wo = blk["wo"].reshape(h_local, hd, cfg.d_model)
            out = jnp.einsum("bhtf,hfd->btd", att.astype(jnp.bfloat16),
                             wo.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            if in_mesh:
                out = axes.allreduce(out, "tp")  # MPI_Allreduce on ICI
            x = x + out

        with jax.named_scope("mlp"):
            h2 = _ln(x, blk["ln2"])
            # the saved relu residual ([B,T,d_ff], the layer's largest
            # activation) is stored bf16 (half-size) with f32-accumulated
            # backward via einsum_bf16
            ff1 = jnp.maximum(einsum_bf16("btd,df->btf",
                                          h2.astype(jnp.bfloat16),
                                          blk["w1"].astype(jnp.bfloat16)),
                              jnp.bfloat16(0))
            ff = _mm(ff1, blk["w2"])
            if in_mesh:
                ff = axes.allreduce(ff, "tp")
            return x + ff

    for blk in params["blocks"]:
        x = block(x, blk)

    return _ln(x, params["ln_f"])


def _rms(x, g, eps: float):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _gqa_attention(q, k, v, window: int):
    """Causal GQA attention of one whole sequence, banded where
    ``window`` > 0, [B, H, T, hd] -> f32: the flash kernels on a TPU, a
    dense lax path elsewhere."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops.flash_attention import flash_attention
    from ompi_tpu.ops.ring_attention import use_flash_default

    if use_flash_default(q.shape, k.shape, "bhtd"):
        return flash_attention(q, k, v, window)
    B, H, T, hd = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, T, hd).astype(jnp.bfloat16)
    s = jnp.einsum("bgjqd,bgkd->bgjqk", qg, k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    i, j = np.arange(T)[:, None], np.arange(T)[None]
    keep = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bgjqk,bgkd->bgjqd", p.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, T, hd)


def moe_features_local(params, tokens, cfg: MoEConfig):
    """The MoEConfig forward up to the final RMSNorm: (features [B, T,
    D] f32, rows each held expert received in each layer [L, E] int32).
    One sequence shard and one tensor shard: positions count from 0."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import rope
    from ompi_tpu.ops.moe import moe_layer
    from ompi_tpu.ops.mxu import einsum_bf16

    B, T = tokens.shape
    pos = jnp.arange(T)
    tabs = {"sliding": rope.tables(pos, cfg.head_dim, cfg.rope_theta),
            "full": rope.tables(pos, cfg.head_dim, cfg.rope_theta,
                                rope.Yarn(*cfg.yarn))}
    x = params["embed"][tokens]
    rows = []
    for kind, blk in zip(cfg.layer_types, params["blocks"]):
        sliding = kind == "sliding"
        with jax.named_scope("attention.window" if sliding
                             else "attention.full"):
            hb = _rms(x, blk["ln1"], cfg.eps).astype(jnp.bfloat16)
            q, k, v = (einsum_bf16("btd,dhf->bhtf", hb,
                                   blk[w].astype(jnp.bfloat16))
                       for w in ("wq", "wk", "wv"))
            q, k = (rope.apply(a, *tabs[kind]) for a in (q, k))
            att = _gqa_attention(q, k, v, cfg.window if sliding else 0)
            x = x + jnp.einsum("bhtf,hfd->btd", att.astype(jnp.bfloat16),
                               blk["wo"].astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
        h2 = _rms(x, blk["ln2"], cfg.eps).reshape(B * T, -1)
        y, counts = moe_layer(h2, blk["router"], blk["w_gate"], blk["w_up"],
                              blk["w_down"], cfg.top_k, cfg.first_expert)
        x = x + y.reshape(B, T, -1)
        rows.append(counts)
    return _rms(x, params["ln_f"], cfg.eps), jnp.stack(rows)


def forward_local(params, tokens, cfg: Config, tp: int = 1, sp: int = 1,
                  in_mesh: bool = False):
    """Forward to logits [B, T, vocab] (dense — for inference/tests; the
    training loss streams the vocab projection in chunks instead and
    forms its gradient in the same pass, see ops/softmax_xent.py)."""
    from ompi_tpu.ops.softmax_xent import logits_matmul

    if isinstance(cfg, MoEConfig):
        return logits_matmul(moe_features_local(params, tokens, cfg)[0],
                             params["head"])
    x = features_local(params, tokens, cfg, tp=tp, sp=sp, in_mesh=in_mesh)
    return logits_matmul(x, params["embed"])


def forward(params, tokens, cfg: Config):
    """Single-device forward (jittable as-is) — the graft entry fn."""
    return forward_local(params, tokens, cfg, tp=1, sp=1, in_mesh=False)


def _loss_local(params, tokens, targets, cfg: Config, tp: int, sp: int,
                denom: float):
    import jax

    from ompi_tpu.ops.softmax_xent import softmax_xent_sum

    x = features_local(params, tokens, cfg, tp=tp, sp=sp, in_mesh=True)
    with jax.named_scope("loss"):
        return softmax_xent_sum(x, params["embed"], targets, 128,
                                ("dp", "sp")) / denom


def _moe_loss_local(params, tokens, targets, cfg: MoEConfig, denom: float):
    import jax

    from ompi_tpu.ops.softmax_xent import softmax_xent_sum

    x, rows = moe_features_local(params, tokens, cfg)
    with jax.named_scope("loss"):
        loss = softmax_xent_sum(x, params["head"], targets, _MOE_LOSS_CHUNK,
                                ("dp", "sp")) / denom
    return loss, rows


def _make_moe_step(mesh, cfg: MoEConfig, dp: int, sp: int, tp: int):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    if sp != 1 or tp != 1:
        raise ValueError(
            f"MoEConfig trains on one sequence and tensor shard (sp = tp = "
            f"1); the mesh has sp = {sp}, tp = {tp}: banded attention has "
            "no ring path and the experts no tensor split")
    pspecs = param_specs(cfg)

    def step_local(params, tokens, targets):
        B, T = tokens.shape
        denom = float(B * T * dp)
        (loss, rows), grads = jax.value_and_grad(
            _moe_loss_local, has_aux=True)(params, tokens, targets, cfg,
                                           denom)
        new_params = jax.tree.map(
            lambda p, g: (p - cfg.lr * g).astype(p.dtype), params, grads)
        return (lax.psum(loss, ("dp", "sp")),
                lax.psum(rows, ("dp", "sp"))), new_params

    return jax.shard_map(step_local, mesh=mesh,
                         in_specs=(pspecs, P("dp", "sp"), P("dp", "sp")),
                         out_specs=((P(), P()), pspecs))


def make_train_step(mesh, cfg: Config):
    """Build the jitted full training step over a (dp, sp, tp) mesh:
    forward + backward + dp/sp gradient allreduce + SGD update. With a
    ``MoEConfig`` the step returns ((loss, rows [L, E] int32), params)
    and takes only sp = tp = 1."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = int(mesh.shape["dp"])
    sp = int(mesh.shape["sp"])
    tp = int(mesh.shape["tp"])
    pspecs = param_specs(cfg)
    tok_spec = P("dp", "sp")

    def step_local(params, tokens, targets):
        B, T = tokens.shape
        denom = float(B * T * dp * sp)

        def lossfn(p):
            return _loss_local(p, tokens, targets, cfg, tp, sp, denom)

        loss, grads = jax.value_and_grad(lossfn)(params)
        # NOTE on the gradient allreduce (the Horovod-style traffic of
        # BASELINE config #5): params are replicated over (dp, sp), so
        # shard_map's replication-preserving AD *auto-inserts* the psum of
        # their cotangents across dp/sp — the collective is in the compiled
        # program without an explicit call here (an explicit psum would
        # double-count; verified by loss-trajectory tests).
        loss = lax.psum(loss, ("dp", "sp"))
        new_params = jax.tree.map(
            lambda p, g: (p - cfg.lr * g).astype(p.dtype), params, grads)
        return loss, new_params

    if isinstance(cfg, MoEConfig):
        step = _make_moe_step(mesh, cfg, dp, sp, tp)
    else:
        step = jax.shard_map(step_local, mesh=mesh,
                             in_specs=(pspecs, tok_spec, tok_spec),
                             out_specs=(P(), pspecs))
    jitted = jax.jit(step)

    def place(params, tokens, targets):
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs)
        sh = NamedSharding(mesh, tok_spec)
        return params, jax.device_put(tokens, sh), jax.device_put(targets, sh)

    return jitted, place
