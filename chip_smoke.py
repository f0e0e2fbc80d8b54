"""chip_smoke — the mesh-mode path and the flagship train step, once, on TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one four-chip host

One chip: the device and its published peaks; the accelerator component
(a 64 MiB host->device->host round trip); the mesh-mode collectives
(``mesh_world()`` -> coll selection -> coll/xla executables) at 64 MiB
f32 per rank against numpy; and the flagship train step
(``models.transformer.make_train_step``) at full width and batch for a
few steps, with the Pallas flash kernel in the compiled program.

Four chips: the same verbs on a 4-device mesh, and the dp x sp x tp =
1x2x2 train step against the one-device step on the same params and
batch. Nothing else.

Readings on earlier lines are smoke readings of one run, not benchmark
numbers. Any failed check exits non-zero before the last line; the last
line is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

MIB = 1 << 20
VERB_BYTES = 64 * MIB          # per rank
SEED = 0                       # params and batch
# The 1x2x2 and one-device flagship steps differ by rounding in
# different places (ring-merged vs one-block attention, sharded vs
# whole matmuls): at most 3.4e-5 over three steps' losses and drops
# (chip run, PR 21). One SGD step lowers the loss by about 6e-3, so a
# step that applied half the update, or none, would be off by 3e-3 or
# more. The bound sits between.
LOSS_ATOL = 5e-4


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ device
def phase_device(chips: int):
    import jax

    from ompi_tpu.accelerator.tpu import peaks

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU found: JAX reports platform {devs[0].platform!r}")
    check(len(devs) == chips,
          f"expected {chips} chip(s), JAX reports {len(devs)}")
    flops, bw = peaks(devs[0])  # raises for a kind with no published peak
    say(f"device: {len(devs)} x {devs[0].device_kind} "
        f"(peaks {flops / 1e12:g} TFLOP/s bf16, {bw:g} GB/s HBM)")
    return devs


# ------------------------------------------------------------- accelerator
def phase_accelerator(devs) -> None:
    from ompi_tpu.accelerator import get_module
    from ompi_tpu.accelerator.tpu import peaks

    mod = get_module()
    check(mod.NAME == "tpu", f"accelerator {mod.NAME!r} selected, not tpu")
    host = np.random.default_rng(0).integers(
        0, 2 ** 32, VERB_BYTES // 4, dtype=np.uint32).view(np.float32)
    dev_buf = mod.mem_copy_to_device(host)
    check({d.platform for d in dev_buf.devices()} == {"tpu"},
          "round-trip buffer is not on the TPU")
    back = mod.mem_copy_to_host(dev_buf)
    check(np.array_equal(back.view(np.uint32), host.view(np.uint32)),
          "64 MiB host->device->host round trip is not bit-exact")
    mod.synchronize()
    bw = mod.get_mem_bw(0)
    check(bw == peaks(devs[0])[1], f"get_mem_bw {bw} is not the table entry")
    say(f"accelerator: tpu selected; 64 MiB round trip bit-exact; "
        f"get_mem_bw {bw:g} GB/s")


# ------------------------------------------------------------- collectives
def _on_devices(out, devs, what: str) -> None:
    placed = {s.device for s in out.addressable_shards}
    check(placed == set(devs),
          f"{what}: shards on {sorted(d.id for d in placed)}, "
          f"want all of {sorted(d.id for d in devs)}")
    check({d.platform for d in placed} == {devs[0].platform},
          f"{what}: output not on {devs[0].platform}")


def phase_collectives(devs, nbytes: int = VERB_BYTES) -> None:
    """allreduce, bcast, allgather, alltoall and a Split's allreduce on
    ``mesh_world(devs)``, each against its numpy closed form. The data
    are small integers, so every f32 sum is exact and the check is
    bit-for-bit."""
    from ompi_tpu.mca.var import all_pvars
    from ompi_tpu.parallel import mesh_world

    w = len(devs)
    n = nbytes // 4
    world = mesh_world(devs)
    data = np.stack([(np.arange(n) % 4099).astype(np.float32) + r
                     for r in range(w)])

    def pvar(name):
        return all_pvars()[name].value

    def verb(name, fn, x, want):
        out = fn(x)
        _on_devices(out, devs, name)
        check(np.array_equal(np.asarray(out), want),
              f"{name} does not match numpy")
        out.delete()
        say(f"collectives: {name} {nbytes // MIB} MiB/rank x {w} "
            "matches numpy")

    x = world.shard(data)
    misses, hits = pvar("coll_xla_cache_misses"), pvar("coll_xla_cache_hits")
    total = np.broadcast_to(data.sum(0), data.shape)
    verb("allreduce", world.allreduce, x, total)
    check(pvar("coll_xla_cache_misses") == misses + 1,
          "first allreduce did not count a compile (coll_xla_cache_misses)")
    verb("allreduce again", world.allreduce, x, total)
    check(pvar("coll_xla_cache_hits") == hits + 1,
          "second allreduce did not count a hit (coll_xla_cache_hits)")
    say(f"collectives: coll_xla_cache_misses {misses} -> "
        f"{pvar('coll_xla_cache_misses')}, hits {hits} -> "
        f"{pvar('coll_xla_cache_hits')}")
    root = w - 1
    verb("bcast", lambda a: world.bcast(a, root=root), x,
         np.broadcast_to(data[root], data.shape))
    verb("allgather", world.allgather, x,
         np.broadcast_to(data, (w,) + data.shape))
    colors = [r % 2 for r in range(w)]
    sub = world.Split(colors)
    want = np.stack([data[[q for q in range(w) if colors[q] == colors[r]]]
                     .sum(0) for r in range(w)])
    verb("Split(r % 2) allreduce", sub.allreduce, sub.shard(data), want)
    x.delete()
    del data
    blocks = np.stack([(np.arange(w * (n // w)) % 4099).astype(np.float32)
                       .reshape(w, n // w) + 8192 * r for r in range(w)])
    verb("alltoall", world.alltoall, world.shard(blocks),
         blocks.transpose(1, 0, 2))
    sub.Free()
    world.Free()


# ---------------------------------------------------------------- training
def _batch(cfg, batch: int):
    rng = np.random.RandomState(SEED)
    toks = rng.randint(0, cfg.vocab, size=(batch, cfg.seq_len))
    return toks.astype(np.int32), np.roll(toks, -1, axis=1).astype(np.int32)


def _mesh(devs, shape):
    from jax.sharding import Mesh

    return Mesh(np.array(devs).reshape(shape), ("dp", "sp", "tp"))


def _n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _steps(mesh, cfg, params, toks, tgts, n_steps: int, kernels: int):
    """Place, compile (checking the flash kernel count), run ``n_steps``.
    Returns (losses, timings): step 0 includes loading the program;
    step 2, where there is one, is synced by a scalar readback of the
    loss instead of block_until_ready."""
    import jax

    from ompi_tpu.models import transformer as tfm

    step, place = tfm.make_train_step(mesh, cfg)
    p, t, g = place(params, toks, tgts)
    t0 = time.perf_counter()
    compiled = step.lower(p, t, g).compile()
    compile_s = time.perf_counter() - t0
    got = _n_kernels(compiled)
    check(got == kernels, f"{got} flash kernels in the step, want {kernels}")
    say(f"train {'x'.join(map(str, mesh.devices.shape))}: {got} flash "
        f"kernels in the compiled step; compile {compile_s!r} s")
    losses = []
    times = {"first": [], "dispatch": [], "block": [], "readback": []}
    for i in range(n_steps):
        t0 = time.perf_counter()
        loss, p = step(p, t, g)
        t_dispatch = time.perf_counter() - t0
        if i == 2:
            losses.append(float(loss))
            times["readback"].append(time.perf_counter() - t0)
        else:
            jax.block_until_ready((loss, p))
            t_block = time.perf_counter() - t0
            times["first" if i == 0 else "block"].append(t_block)
            times["dispatch"].append(t_dispatch)
            losses.append(float(loss))
    for leaf in jax.tree_util.tree_leaves((p, t, g)):
        leaf.delete()
    return losses, times


def phase_training(devs, cfg=None, batch=None, n_steps: int = 4) -> None:
    """The flagship step on one device: finite, falling loss, the flash
    kernel in (2 per layer: forward, backward), smoke timings."""
    import jax

    from ompi_tpu.models import transformer as tfm

    cfg = cfg or tfm.FLAGSHIP
    batch = batch or tfm.FLAGSHIP_BATCH
    params = tfm.init_params(jax.random.PRNGKey(SEED), cfg)
    toks, tgts = _batch(cfg, batch)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(f"training: {n_params / 1e6:.1f}M params, batch {batch} x "
        f"{cfg.seq_len}")
    losses, times = _steps(_mesh(devs[:1], (1, 1, 1)), cfg, params, toks,
                           tgts, n_steps, 2 * cfg.n_layers)
    say("training: losses " + ", ".join(repr(v) for v in losses))
    check(all(np.isfinite(losses)), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not fall")
    step_s = float(np.median(times["block"]))
    stats = devs[0].memory_stats() or {}
    say(f"training (smoke reading, one run): step {step_s!r} s by "
        f"block_until_ready; {batch * cfg.seq_len / step_s!r} tokens/s; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    say(f"training (smoke reading): step() returned after "
        f"{times['dispatch']!r} s; block_until_ready after "
        f"{times['first'] + times['block']!r} s; the scalar-readback step "
        f"after {times['readback']!r} s")


def phase_train_4(devs, cfg=None, batch=None, n_steps: int = 3) -> None:
    """The 1x2x2 step against the one-device step: same params, same
    batch; each of ``n_steps`` losses, and each step's drop, within
    LOSS_ATOL."""
    import jax

    from ompi_tpu.models import transformer as tfm

    cfg = cfg or tfm.FLAGSHIP
    batch = batch or tfm.FLAGSHIP_BATCH
    params = tfm.init_params(jax.random.PRNGKey(SEED), cfg)
    toks, tgts = _batch(cfg, batch)
    one, _ = _steps(_mesh(devs[:1], (1, 1, 1)), cfg, params, toks, tgts,
                    n_steps, 2 * cfg.n_layers)
    # sp=2: each layer runs both ring steps through the kernel
    four, _ = _steps(_mesh(devs, (1, 2, 2)), cfg, params, toks, tgts,
                     n_steps, 2 * 2 * cfg.n_layers)
    say("train 1x2x2: losses " + ", ".join(repr(v) for v in four))
    say("train 1x1x1: losses " + ", ".join(repr(v) for v in one))
    drop_one = [a - b for a, b in zip(one, one[1:])]
    drop_four = [a - b for a, b in zip(four, four[1:])]
    say("train 1x2x2: drops per step " + ", ".join(map(repr, drop_four)))
    say("train 1x1x1: drops per step " + ", ".join(map(repr, drop_one)))
    # else the bound could not tell a halved update from a whole one
    check(all(d > 2 * LOSS_ATOL for d in drop_one),
          f"one-device loss drops {drop_one!r} not above 2 x {LOSS_ATOL}")
    gap = max(abs(a - b) for a, b in zip(one + drop_one, four + drop_four))
    check(all(np.isfinite(four)) and gap <= LOSS_ATOL,
          f"1x2x2 losses {four!r} vs one-device {one!r}: beyond "
          f"{LOSS_ATOL}")
    say(f"train 1x2x2 matches the one-device step within {LOSS_ATOL} "
        f"(max |diff| of losses and drops {gap!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)

    from ompi_tpu.utils import compile_cache

    cache = compile_cache.enable()
    devs = phase_device(opts.chips)
    say(f"compile cache: {cache}")
    from ompi_tpu.native import get_lib

    say("native library: "
        + ("loaded" if get_lib() is not None else
           "not loaded (pure-Python fallback)"))
    if opts.chips == 4:
        phase_collectives(devs)
        phase_train_4(devs)
    else:
        phase_accelerator(devs)
        phase_collectives(devs)
        phase_training(devs)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
