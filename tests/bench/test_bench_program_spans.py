"""The program's own host spans, recorded by the profiler on the CPU: one
tiny ``ServingEngine.run`` writes ``serve.*`` spans nested in its steps,
one ``serve.decode.wait`` a decode step, and ``serve_host_idle_ms.serve``
reads them; the data pipeline writes ``data.batch`` and ``data.shard``;
``trainer.train`` writes ``train.step``, ``train.log`` and ``train.ckpt``."""

import jax
import numpy as np
import pytest

import tiny_cells
from bench import harness
from bench import trace as trace_lib

SEED = 2 ** 33 + 7
NESTED = ("serve.admit", "serve.admit.wait", "serve.decode",
          "serve.decode.wait", "serve.tokens")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(trace, report) of one run of the tiny serving cell's model: 2 slots,
    one prompt bucket, 3 requests; then one batch of the data pipeline; all
    under the profiler."""
    from bench.cells import serve_offline
    from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
    from repro.serving import engine
    from repro.serving.scheduler import Request
    d = str(tmp_path_factory.mktemp("profile"))
    cfg = engine.ServeConfig(num_slots=2, cache_len=24, prefill_pack=1,
                             prompt_buckets=(16,))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, tokens=rng.integers(0, 64, n).tolist(),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 4), (9, 2), (12, 3)])]
    cell = tiny_cells.serve_cell()
    with serve_offline.Program(cell, jax.devices()[:1], SEED) as prog:
        # host-made weights: nothing compiles but the serving programs
        params = jax.tree_util.tree_map(
            lambda a: (0.02 * rng.standard_normal(a.shape)).astype(a.dtype),
            prog.abstract)
        eng = engine.ServingEngine(params, prog.ctx, cfg)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # as the benchmark traces
        with jax.profiler.trace(d, profiler_options=opts):
            report = eng.run(reqs, seed=SEED)
            data = SyntheticLM(DataConfig(vocab_size=64, seq_len=8,
                                          global_batch=2))
            shard_batch(data.batch(0), prog.mesh)
    return trace_lib.load(d), report


def _spans(tr, name):
    return [(s, e) for n, s, e in tr.host if n == name]


def test_serve_spans_nest_in_steps(traced):
    tr, report = traced
    runs, steps = _spans(tr, "serve.run"), _spans(tr, "serve.step")
    assert len(runs) == 1 and steps
    assert all(runs[0][0] <= s and e <= runs[0][1] for s, e in steps)
    for name in NESTED:
        inner = _spans(tr, name)
        assert inner, name
        assert all(any(a <= s and e <= b for a, b in steps)
                   for s, e in inner), name
    assert len(_spans(tr, "serve.decode.wait")) == report.decode_steps
    assert len(_spans(tr, "serve.admit.wait")) == report.prefill_calls == 3


def test_host_idle_reader_on_recorded_spans(traced):
    # the CPU trace holds no TPU plane: the reader sees a device that never
    # ran, so every step's time outside its waits counts as host time
    tr, report = traced
    dev = trace_lib.Device("/device:TPU:0", [], [])
    info = {"kind": "serve", "decode_steps": report.decode_steps,
            "trace": trace_lib.Trace([dev], tr.host)}
    got = harness.read_per_layer(
        [{"name": "serve_host_idle_ms.serve", "unit": "ms"}], info)
    v = got["serve_host_idle_ms.serve"]["value"]
    steps = trace_lib.length(_spans(tr, "serve.step"))
    assert 0 < v < 1e3 * steps / report.decode_steps


def test_data_pipeline_spans(traced):
    tr, _ = traced
    assert len(_spans(tr, "data.batch")) == 1
    assert len(_spans(tr, "data.shard")) == 1


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Trace of a tiny profiled ``trainer.train`` (one dense layer, as the
    spans do not depend on the model): 3 steps, logged at steps 0 and 2
    (the last), one checkpoint after step 1."""
    import dataclasses

    from repro.configs.base import RunConfig, get_config
    from repro.launch.mesh import make_host_mesh
    from repro.training import trainer
    d = tmp_path_factory.mktemp("train")
    arch = dataclasses.replace(get_config("olmo_1b").reduced(), num_layers=1)
    run = RunConfig(seq_len=16, global_batch=2, total_steps=3,
                    warmup_steps=1)
    mesh = make_host_mesh(data=1, model=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(d / "profile"), profiler_options=opts):
        trainer.train(arch, run, mesh, steps=3, log_every=2,
                      ckpt_path=str(d / "ckpt"), ckpt_every=2,
                      verbose=False)
    return trace_lib.load(str(d / "profile"))


def test_train_spans(trained):
    steps, logs, ckpts = (_spans(trained, f"train.{n}")
                          for n in ("step", "log", "ckpt"))
    assert (len(steps), len(logs), len(ckpts)) == (3, 2, 1)
    # the first log waits on step 0, the save follows step 1, the last log
    # waits on step 2
    assert steps[0][1] <= logs[0][0] and logs[0][1] <= steps[1][0]
    assert steps[1][1] <= ckpts[0][0] and ckpts[0][1] <= steps[2][0]
    assert steps[2][1] <= logs[1][0]
    assert len(_spans(trained, "data.batch")) == 3
