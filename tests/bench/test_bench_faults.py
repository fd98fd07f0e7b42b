"""The correctness check of each cell, driven on the CPU at a tiny size:
a sound run passes and the timed path broken underneath fails the cell's
own limits; the control (the reference in float8 in the program's place)
fails them in training and, in serving, leaves the reference's choice far
more often than the program does.

The harness's look for a chip is skipped: each test calls the cell's
runner with the CPU device.  Faults are planted by patching the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_cells
from bench import harness
from bench.cells import serve_offline, train_lm

SEED = 2 ** 33 + 7          # past 32 bits, as the benchmark's seeds are


@pytest.fixture(scope="module")
def cpu():
    return jax.devices()[:1]


def _train(cpu):
    return train_lm.run(tiny_cells.train_cell(), seed=SEED, seconds=0.3,
                        trace=False, devices=cpu)


def _serve(cpu):
    return serve_offline.run(tiny_cells.serve_cell(), seed=SEED, seconds=1.0,
                             trace=False, devices=cpu)


def test_train_sound_run_is_correct(cpu):
    r = _train(cpu)
    assert r["correct"], r["checks"]
    assert r["window"]["compiles"] == 0


def test_train_state_left_unchanged_is_caught(cpu, monkeypatch):
    from repro.training import trainer
    real = trainer.make_train_step

    def frozen(ctx, run, opt_cfg=None):
        step = real(ctx, run, opt_cfg)

        def f(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return f
    monkeypatch.setattr(trainer, "make_train_step", frozen)
    r = _train(cpu)
    assert not r["correct"]
    assert r["checks"]["change"]["value"] > r["checks"]["change"]["limit"]


def test_train_half_batch_is_caught(cpu, monkeypatch):
    from repro.models import transformer
    real = transformer.loss_fn

    def half(params, batch, ctx, aux_weight=1.0):
        n = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:n] for k, v in batch.items()}, ctx,
                    aux_weight)
    monkeypatch.setattr(transformer, "loss_fn", half)
    assert not _train(cpu)["correct"]


def test_train_control_fails(cpu):
    cell = tiny_cells.train_cell()
    with train_lm.Program(cell, cpu, SEED) as prog:
        _, _, _, rows = train_lm.first_steps(prog, SEED,
                                             cell.traffic["checked_steps"])
        abstract = prog.abstract
    ref = train_lm.reference(cell, abstract, SEED, rows, "f32")
    ctl = train_lm.reference(cell, abstract, SEED, rows, "fp8")
    ok, checks = harness.judge(train_lm.numbers(ctl, ref), cell.limits)
    assert not ok, checks


def test_serve_sound_run_is_correct(cpu):
    r = _serve(cpu)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0


def test_serve_altered_token_is_caught(cpu, monkeypatch):
    from repro.serving import engine

    def worst():
        def sample(logits, temps, key):
            return jnp.argmin(logits.astype(jnp.float32), -1).astype(
                jnp.int32)
        return sample
    monkeypatch.setattr(engine, "_make_sample", worst)
    r = _serve(cpu)
    assert not r["correct"]
    assert r["checks"]["far_share"]["value"] > 0.5


def test_serve_control_fails(cpu):
    cell = tiny_cells.serve_cell()
    with serve_offline.Program(cell, cpu, SEED) as prog:
        eng = prog.engine(SEED)
        vocab = prog.arch.vocab_size
        reqs = serve_offline.requests(cell.traffic, vocab, SEED, 8)
        report = eng.run(reqs, seed=SEED)
        picked = serve_offline.sample(report.streams, cell.traffic["check"],
                                      SEED)
        abstract = prog.abstract
    gaps = serve_offline.reference_gaps(cell, abstract, SEED, picked,
                                        control=True)
    prog = np.concatenate([g[0] for g in gaps])
    ctl = np.concatenate([g[1] for g in gaps])
    # at this size no logit lies half a logit from the best, so the cell's
    # limit cannot be read here; the control still leaves the reference's
    # choice far more often than the program, as it does on the chip
    assert np.mean(ctl > 0) > 3 * np.mean(prog > 0) + 0.05
    assert ctl.max() > 3 * prog.max()
