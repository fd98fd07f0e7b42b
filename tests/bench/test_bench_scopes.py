"""The program's named scopes and the readers of spans and scopes: the
scopes that ``bench/scopes.json`` names are in the compiled programs of
both cells (tiny, on the CPU), a scope is found through JAX's backward-pass
wrappers, device time is attributed to scopes through the compiled text,
and ``serve_host_idle_ms.serve`` counts idle time inside step spans and
outside wait spans."""

import jax
import jax.numpy as jnp
import pytest

import tiny_cells
from bench import harness, scopes
from bench import trace as trace_lib

SEED = 2 ** 33 + 7


def _scopes_in(text: str) -> set:
    found = set()
    for op_name in scopes.parse(text)["op_names"].values():
        found.update(scopes.components(op_name))
    return found


@pytest.fixture(scope="module")
def compiled():
    """Optimized texts of the tiny train step (``a2a``) and decode step
    (``gather``), compiled from shapes alone; the decode step with one
    more layer than the tiny cell, so that its layer loop runs twice."""
    from bench.cells import serve_offline, train_lm
    from repro.optim import adamw
    from repro.serving import batching, engine
    cpu = jax.devices()[:1]
    serve = tiny_cells.serve_cell()
    serve.config["program"] = dict(serve.config["program"], num_layers=3)
    serve.config["num_hidden_layers"] = 3
    with train_lm.Program(tiny_cells.train_cell(), cpu, SEED) as prog:
        B, S = prog.run.global_batch, prog.run.seq_len
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
        opt = jax.eval_shape(adamw.init_state, prog.abstract)
        train = prog.step.lower(prog.abstract, opt, batch).compile().as_text()
    with serve_offline.Program(serve, cpu, SEED) as prog:
        cfg = prog.cfg
        eng = engine.ServingEngine(prog.abstract, prog.ctx, cfg)
        cache = jax.eval_shape(lambda: batching.SlotKVCache(
            prog.ctx, cfg.num_slots, cfg.cache_len).cache)
        cur = jax.ShapeDtypeStruct((cfg.num_slots, 1), jnp.int32)
        decode = eng._decode.lower(prog.abstract, cache, cur).compile(
        ).as_text()
    return {"train": train, "decode": decode}


def test_every_named_scope_is_compiled(compiled):
    want = {s for v in scopes.groups().values() for s in v}
    train, decode = (_scopes_in(compiled[k]) for k in ("train", "decode"))
    assert want <= train | decode, want - (train | decode)
    # each path names its routing, expert work and combine; attention in
    # both programs; the shared experts of the serving model in decode
    assert {"moe.route", "moe.dispatch", "moe.experts", "moe.combine",
            "attn", "layer", "layer.params"} <= train
    assert {"moe.route", "moe.experts", "moe.combine", "moe.shared",
            "attn"} <= decode
    assert scopes.parse(compiled["train"])["module"] == "jit_step"


def test_decode_layer_loop_slices_are_layer_params(compiled):
    # the layer loop's slices of the parameter and cache stacks, and its
    # writes of each layer's new cache back, fall under ``layer.params``:
    # outside every sublayer's ``layer`` scope
    want = sorted({s for v in scopes.groups().values() for s in v})
    got = {}
    for name, op_name in scopes.parse(compiled["decode"])["op_names"].items():
        if name.startswith(("dynamic-slice", "dynamic_slice",
                            "dynamic-update-slice", "dynamic_update_slice")):
            sc = scopes.scope_of(op_name, want)
            got[sc] = got.get(sc, 0) + 1
    assert set(got) == {"layer.params"}, got


def test_scope_through_backward_wrappers():
    groups = ["moe.route", "moe.dispatch", "attn"]
    assert scopes.components("jit(step)/transpose(jvp(moe.route))/mul")[1] \
        == "moe.route"
    assert scopes.scope_of("jit(step)/transpose(jvp(moe.route))/mul",
                           groups) == "moe.route"
    assert scopes.scope_of("checkpoint/rematted_computation/attn/exp",
                           groups) == "attn"
    assert scopes.scope_of("jit(step)/jvp(layer)/moe.routed/add",
                           groups) is None
    assert scopes.scope_of("jit(step)/jvp()/mul", groups) is None


TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/transpose(jvp(moe.route))/mul"}
}

ENTRY %main.2 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/attn/dot_general"}
  ROOT %copy.4 = f32[8]{0} copy(%fusion.2), metadata={op_name="jit(step)/copy"}
}
"""


def test_device_time_by_scope():
    # in jit_step: fusion.1 (moe.route through its root) 3 us, fusion.2
    # (attn) 2 us, copy.4 (no scope) 1 us; outside any program text: 4 us
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0e-6, 3e-6),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1)", 3e-6, 5e-6),
           ("%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.2)", 5e-6, 6e-6),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %y)", 10e-6, 14e-6)]
    mods = [("jit_step(7)", 0.0, 6e-6), ("jit_other(3)", 10e-6, 14e-6)]
    dev = trace_lib.Device("/device:TPU:0", ops, mods)
    got = scopes.attribute(dev, [TEXT], {"moe_dispatch": ["moe.route"],
                                         "attention": ["attn"]})
    assert got["scopes_s"]["moe.route"] == pytest.approx(3e-6)
    assert got["groups_s"]["attention"] == pytest.approx(2e-6)
    assert got["unscoped_s"] == pytest.approx(1e-6)
    assert got["unmatched_s"] == pytest.approx(4e-6)
    assert got["groups_share"]["moe_dispatch"] == pytest.approx(30.0)


def test_idle_by_innermost_span():
    # ops [0,2) [5,6) [9,10): idle [2,5) under serve.tokens (inside
    # serve.step), [6,9) under serve.step alone; runtime spans ignored
    ops = [("%a", 0, 2), ("%b", 5, 6), ("%c", 9, 10)]
    host = [("serve.step", 0, 10), ("serve.tokens", 1, 5),
            ("np.asarray(jax.Array)", 2, 4)]
    dev = trace_lib.Device("/device:TPU:0", ops, [])
    assert scopes.idle_by_span(dev, host) == {"serve.tokens": 3,
                                              "serve.step": 3}


def _serve_info(host, ops, decode_steps=2):
    tr = trace_lib.Trace([trace_lib.Device("/device:TPU:0", ops, [])], host)
    return {"kind": "serve", "trace": tr, "decode_steps": decode_steps}


def test_host_idle_counts_idle_in_steps_outside_waits():
    # two steps [0,10) and [10,20) ms; the device runs [2,6) and [12,19);
    # the host waits [6,8) and [19,20): idle in steps [0,2) [6,10) [10,12)
    # [19,20) = 9 ms, less the waits' idle [6,8) [19,20) = 6 ms, in 2 steps
    ms = 1e-3
    host = [("serve.run", -5 * ms, 30 * ms),
            ("serve.step", 0, 10 * ms), ("serve.step", 10 * ms, 20 * ms),
            ("serve.decode.wait", 6 * ms, 8 * ms),
            ("serve.admit.wait", 19 * ms, 20 * ms),
            ("serve.tokens", 8 * ms, 10 * ms)]
    ops = [("%fusion.1", 2 * ms, 6 * ms), ("%fusion.2", 12 * ms, 19 * ms)]
    got = harness.read_per_layer(
        [{"name": "serve_host_idle_ms.serve", "unit": "ms"}],
        _serve_info(host, ops))
    assert got["serve_host_idle_ms.serve"]["value"] == pytest.approx(3.0)


def test_host_idle_reads_nothing_without_step_spans():
    # a program that writes no serve spans, as the parent of these spans
    got = harness.read_per_layer(
        [{"name": "serve_host_idle_ms.serve", "unit": "ms"}],
        _serve_info([("bench.step", 0.0, 1.0)], [("%fusion.1", 0.0, 0.5)]))
    assert got == {}
