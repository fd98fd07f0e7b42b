"""The four-chip training cell cut to a size a CPU test run holds, and the
checks ``test_bench_ep4.py`` makes on it in one process over four forced
host devices (the checks that need no mesh run in the test process).  Run as a script (``XLA_FLAGS`` set before JAX loads), it
prints one JSON line of results per check, ``CASE <name> <json>``."""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

EP4 = "gpt3_moe_full.train_ep4"
SEED = 2 ** 33 + 7          # past 32 bits, as the benchmark's seeds are


def ep4_cell() -> harness.Cell:
    """Every width shrunk, 8 experts (2 a chip), 2 layers; the TA plan of
    this size (64 tokens a chip: capacity 2 x 64 x 2.0 / 8 = 32); the real
    cell's limits."""
    c = harness.cell(EP4)
    cfg = dict(c.config)
    cfg.update({
        "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                    "num_kv_heads": 4, "d_ff": 128, "vocab_size": 512,
                    "moe": {"num_experts": 8, "d_ff_expert": 32}},
        "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
        "intermediate_size": 128, "vocab_size": 512, "num_experts": 8,
        "moe_intermediate_size": 32,
        "ta_plan": dict(cfg["ta_plan"], tokens_per_device=64, capacity=32)})
    tr = dict(c.traffic)
    tr.update({"seq_len": 32, "batch_per_chip": 2, "log_every": 2})
    return harness.Cell(c.workload, cfg, tr, c.limits, c.end_to_end,
                        c.per_layer)


def case(name: str, value) -> None:
    print(f"CASE {name} {json.dumps(value)}", flush=True)


def sound(devices) -> None:
    """A run of the cell on four devices, compared as on the chip."""
    from bench.cells import train_lm_ep
    r = train_lm_ep.run(ep4_cell(), seed=SEED, seconds=0.3, trace=False,
                        devices=devices)
    case("sound", {"correct": r["correct"], "checks": r["checks"],
                   "compiles": r["window"]["compiles"],
                   "frac_self": r["window"].get("frac_self"),
                   "dropped": r["window"].get("dropped")})


def plan_refused(devices) -> None:
    """The harness refuses a program whose plan differs from the file."""
    from bench.cells import train_lm_ep
    cell = ep4_cell()
    out = {}
    for key, val in (("capacity", 40), ("penalties", [1.0, 2.0])):
        cfg = dict(cell.config, ta_plan=dict(cell.config["ta_plan"],
                                             **{key: val}))
        try:
            train_lm_ep.program(dataclasses.replace(cell, config=cfg),
                                devices, SEED)
            out[key] = None
        except ValueError as e:
            out[key] = str(e)
    case("plan_refused", out)


def shares_sum(devices) -> None:
    """One MoE layer of the program on four devices, where no capacity
    binds: each chip's share (the other chips' experts zeroed), summed,
    against the uncut reference's layer (float32 throughout)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import weights
    from bench.reference import moe_lm
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib, transformer

    cell = ep4_cell()
    arch = harness.program_arch(cell.config)
    n = arch.moe.num_experts
    arch = dataclasses.replace(
        arch, dtype="float32", moe=dataclasses.replace(
            arch.moe, capacity_factor=n / arch.moe.top_k))
    mesh = make_host_mesh(data=len(devices), model=1)
    ctx = model_lib.build_ctx(arch, mesh, seq_len=32, global_batch=8,
                              aux_mode="ta", dispatch="a2a")
    abstract = model_lib.abstract_params(jax.random.PRNGKey(0), ctx)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               weights.make(abstract, SEED)["groups"]["sub1"]
                               ["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, arch.d_model),
                          jnp.float32)
    held = n // len(devices)
    own = np.arange(n) // held
    layer = jax.jit(lambda p, x: transformer._moe_block(p, x, ctx,
                                                        decode=False)[0])
    with mesh:
        parts = [layer(dict(p, w_out=p["w_out"] * jnp.asarray(
            own == k, jnp.float32)[:, None, None]), x)
            for k in range(len(devices))]
    total = np.asarray(sum(parts)).reshape(-1, arch.d_model)
    s = moe_lm.spec(cell.config)
    with jax.default_matmul_precision("highest"):
        want, _ = moe_lm.moe(p, x.reshape(-1, arch.d_model), s, "f32", None)
    want = np.asarray(want)
    case("shares_sum", {"err": float(np.abs(total - want).max()),
                        "scale": float(np.abs(want).max())})


def a2a_scopes(devices) -> None:
    """The tiny cell's compiled train step: the ops each all_to_all of the
    step (forward and backward) compiles to, by their ``op_name``, and how
    many of those carry the ``moe.a2a`` scope."""
    import re

    import jax

    from bench.cells import train_lm_ep
    from repro.optim import adamw

    with train_lm_ep.program(ep4_cell(), devices, SEED) as prog:
        opt = jax.eval_shape(adamw.init_state, prog.abstract)
        _, batch = prog.feed(0)
        text = prog.step.lower(prog.abstract, opt, batch).compile().as_text()
    names = re.findall(r'op_name="([^"]*/all_to_all)"', text)
    case("a2a_scopes", {"all_to_all": len(names),
                        "backward": sum("transpose(" in n for n in names),
                        "scoped": sum("/moe.a2a/" in n for n in names)})


def main() -> int:
    import jax
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    for f in (plan_refused, shares_sum, a2a_scopes, sound):
        f(devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
