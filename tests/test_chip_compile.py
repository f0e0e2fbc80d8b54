"""Compiles of the main path for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology it is
only told about: what it refuses here (a tile it cannot lay out, a
cotangent of the wrong type, a program that does not fit) costs no chip
time. Nothing runs, so these say nothing of results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHAPE = (36, 8, 1024, 128)  # the flagship step's per-layer q/k/v, bhtd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    """A chip compile is written to the persistent cache but cannot be
    read back without a chip: keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def _n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _flash_kinds(compiled):
    """The sorted kinds (fwd, dqkv) of the program's kernels, each found
    as the benchmark finds it: every kernel by the name its pallas_call
    gives the instruction (flash_fwd_ms, flash_bwd_ms), and the forward
    also by its signature (flops.flash_kernel, for flash_attn_ms), read
    from HLO text with operand shapes, as a device trace prints it."""
    from jax._src.lib import xla_client

    from benchmark import flops

    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    kinds = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split(" = ")[0]
            if "flash_fwd" in name:
                kind = flops.flash_kernel(line.strip())
                assert kind is not None and kind[0] == "fwd", line[:200]
                kinds.append("fwd")
            else:
                assert "flash_dqkv" in name, line[:200]
                kinds.append("dqkv")
    return sorted(kinds)


def _qkv(sharding):
    return [jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16, sharding=sharding)
            for _ in range(3)]


def _attend(q, k, v):
    from ompi_tpu.ops.flash_attention import flash_block

    out, _ = flash_block(q, k, v, False, True, layout="bhtd")
    return out


def test_flash_forward_compiles(one_chip):
    compiled = jax.jit(_attend).lower(*_qkv(one_chip)).compile()
    assert _n_kernels(compiled) == 1
    assert _flash_kinds(compiled) == ["fwd"]


def test_flash_backward_compiles(one_chip):
    grad = jax.grad(lambda q, k, v: jnp.sum(_attend(q, k, v)),
                    argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(*_qkv(one_chip)).compile()
    # forward + the one backward kernel
    assert _n_kernels(compiled) == 2
    assert _flash_kinds(compiled) == ["dqkv", "fwd"]


def test_gqa_flash_compiles(one_chip):
    """Mellum2's sliding layer at its cell's shape: 32 query heads over 4
    K/V heads, T = 8192, a band of 1024; forward and backward, each under
    its own name."""
    from ompi_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, 1024)),
                    argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_gqa_win_fwd" in text and "flash_gqa_win_dqkv" in text


def test_expert_layer_compiles(one_chip):
    """The expert layer at the cell's shape: 8,192 tokens of 2,304, a
    64-wide router, top 8, 8 experts held of width 896. Two branches,
    each with the grouped matmuls' forward (as the backward recomputes
    it: the gradient alone needs no first forward), dx and dw, gate-up
    and down each: the compact buffer's 17,408 rows and the worst
    case's 66,560."""
    from ompi_tpu.ops.moe import moe_layer

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def loss(h, r, g, u, d):
        out, rows = moe_layer(h, r, g, u, d, 8, impl="kernel")
        return jnp.sum(out)

    args = (shape(8192, 2304), shape(2304, 64), shape(8, 2304, 896),
            shape(8, 2304, 896), shape(8, 896, 2304))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    for rows in (17408, 66560):
        # a kernel's buffer operand: its first [rows, ...] array
        mine = [ln for ln in kernels if f"[{rows}," in ln]
        names = [ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
                 for ln in mine]
        assert sorted(names) == sorted(["expert_gmm", "expert_gmm_dx",
                                        "expert_gmm_dw"] * 2), (rows, names)
    assert len(kernels) == 12
    assert "conditional(" in text and "[17408,2304]" in text


def _compile_step(topo, devices, batch):
    """The flagship step at full width, two layers, compiled for a
    (dp, sp, tp) mesh of the described chips."""
    from ompi_tpu.models import transformer as tfm

    cfg = dataclasses.replace(tfm.FLAGSHIP, n_layers=2)
    mesh = Mesh(np.array(devices), ("dp", "sp", "tp"))
    step, _ = tfm.make_train_step(mesh, cfg)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        params, tfm.param_specs(cfg))
    toks = jax.ShapeDtypeStruct((batch, cfg.seq_len), jnp.int32,
                                sharding=NamedSharding(mesh, P("dp", "sp")))
    return cfg, step.lower(params, toks, toks).compile()


@pytest.fixture
def flash_on(monkeypatch):
    from ompi_tpu.ops import ring_attention

    # the backend here is the CPU, so the default would pick the lax path
    monkeypatch.setattr(ring_attention, "use_flash_default",
                        lambda *a, **k: True)


def test_train_step_1x2x2_compiles(topo, no_compile_cache, flash_on):
    """The four-chip dp x sp x tp = 1x2x2 step at full width, two layers:
    ring attention's flash backward must give the sp-varying keep flags
    cotangents of their own type."""
    cfg, compiled = _compile_step(
        topo, np.array(topo.devices).reshape(1, 2, 2), 8)
    # per layer, each of the 2 ring steps runs forward + backward
    assert _n_kernels(compiled) == 2 * 2 * cfg.n_layers
    assert _flash_kinds(compiled) == sorted(["fwd", "dqkv"] * 2 *
                                             cfg.n_layers)


def test_train_step_one_chip_compiles(topo, no_compile_cache, flash_on):
    """The one-chip flagship step (batch 36): the loss scan scores each
    chunk once and feeds the same logits to the dx and dW matmuls, so the
    program holds three vocabulary-wide convolutions, not four, and its
    temporaries fit the chip's 16 GB."""
    from jax._src.lib import xla_client

    cfg, compiled = _compile_step(
        topo, np.array(topo.devices[:1]).reshape(1, 1, 1), 36)
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.print_metadata = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    vocab = [ln for ln in text.splitlines()
             if " convolution(" in ln and str(cfg.vocab) in ln]
    assert len(vocab) == 3, vocab
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
