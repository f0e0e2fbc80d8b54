"""A run with the timed path broken underneath comes out not correct:
once for each fault a cell can have. The harness's look for a chip is
skipped; everything else of a run goes as on the chip."""

import pytest

import jax.numpy as jnp

from conftest import run_cell


def _break_allreduce(monkeypatch, fault):
    from ompi_tpu.parallel import mesh

    orig = mesh.XlaComm.allreduce

    def broken(self, x, *a, **k):
        n = x.shape[0]
        if fault == "exchange_left_out":       # also: state unchanged
            return x
        if fault == "half_the_ranks":
            keep = (jnp.arange(n) < n // 2).reshape((n,) + (1,) *
                                                    (x.ndim - 1))
            return orig(self, jnp.where(keep, x, 0).astype(x.dtype), *a, **k)
        out = orig(self, x, *a, **k)            # an answer altered
        return out.at[(n - 1,) + (0,) * (x.ndim - 1)].add(1)

    monkeypatch.setattr(mesh.XlaComm, "allreduce", broken)


@pytest.mark.parametrize("fault", ["exchange_left_out", "half_the_ranks",
                                   "answer_altered"])
def test_bench_coll_fault_is_not_correct(tiny_coll, monkeypatch, fault):
    _break_allreduce(monkeypatch, fault)
    out = run_cell(*tiny_coll)
    assert out["correct"] is False
    assert out["checks"]["max_abs_err"]["value"] > 0
    assert out["failed"] > 0


def _break_step(monkeypatch, fault):
    from ompi_tpu.models import transformer as tfm

    orig = tfm.make_train_step

    def make(mesh, cfg):
        step, place = orig(mesh, cfg)

        def broken(params, tokens, targets):
            if fault == "state_unchanged":
                loss, _ = step(params, tokens, targets)
                return loss, params
            if fault == "half_the_batch":
                h = tokens.shape[0] // 2
                return step(params, tokens[:h], targets[:h])
            loss, new = step(params, tokens, targets)  # an answer altered
            return loss, dict(new, ln_f=new["ln_f"].at[0].add(1e-2))

        return broken, place

    monkeypatch.setattr(tfm, "make_train_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "answer_altered"])
def test_bench_train_fault_is_not_correct(tiny_train, monkeypatch, fault):
    _break_step(monkeypatch, fault)
    out = run_cell(*tiny_train)
    assert out["correct"] is False, out["checks"]
