"""The four-chip expert-parallel training cell, driven on the CPU at a tiny
size (``ep4_cases.py``): in one subprocess over four forced host devices,
a sound run passes its limits, the harness refuses a program whose TA plan
differs from the file, the chips' shares of one MoE layer add up to the
uncut reference layer, and every all-to-all of the step carries the
``moe.a2a`` scope.  In this process, on one device: the per-source
reference with one source is today's reference, and its
exchange-left-out fault fails the cell's limits."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ep4_cases
from bench import harness, weights
from bench.cells import train_lm, train_lm_ep
from bench.reference import moe_lm, moe_lm_ep

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.join(HERE, "ep4_cases.py")],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("CASE "):
            _, name, value = line.split(" ", 2)
            got[name] = json.loads(value)
    return got


def test_ep4_sound_run_is_correct(cases):
    r = cases["sound"]
    assert r["correct"], r["checks"]
    assert r["compiles"] == 0
    # four chips: some traffic stays home, most crosses (a quarter of the
    # experts are a chip's own)
    assert 0.0 < r["frac_self"] < 0.5


def test_ep4_plan_differing_from_file_is_refused(cases):
    r = cases["plan_refused"]
    assert "capacity" in (r["capacity"] or ""), r
    assert "penalties" in (r["penalties"] or ""), r


def test_ep4_shares_sum_to_uncut_layer(cases):
    r = cases["shares_sum"]
    assert r["err"] <= 1e-5 * r["scale"], r


def test_ep4_all_to_alls_carry_the_a2a_scope(cases):
    r = cases["a2a_scopes"]
    assert r["all_to_all"] > 0 and r["backward"] > 0, r
    assert r["scoped"] == r["all_to_all"], r


@pytest.fixture(scope="module")
def one_device():
    """The tiny cell's parameter layout on one device, its first batches
    and the per-source reference (four sources) on them."""
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib
    cell = ep4_cases.ep4_cell()
    arch = harness.program_arch(cell.config)
    t = cell.traffic
    batch = t["batch_per_chip"] * cell.workload["chips"]
    ctx = model_lib.build_ctx(arch, make_host_mesh(data=1, model=1),
                              seq_len=t["seq_len"], global_batch=batch)
    abstract = model_lib.abstract_params(jax.random.PRNGKey(0), ctx)
    data = SyntheticLM(DataConfig(vocab_size=arch.vocab_size,
                                  seq_len=t["seq_len"], global_batch=batch,
                                  seed=ep4_cases.SEED), arch)
    rows = [{k: np.asarray(data.batch(i)[k]) for k in ("tokens", "labels")}
            for i in range(t["checked_steps"])]
    return cell, abstract, rows, train_lm_ep.Reference(cell, abstract)


def test_ep4_exchange_left_out_is_caught(one_device):
    cell, _, rows, ref = one_device
    base = ref.run(ep4_cases.SEED, rows, "f32")
    fault = ref.run(ep4_cases.SEED, rows, "f32", local_only=True)
    ok, checks = harness.judge(train_lm.numbers(fault, base), cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("capacity", [None, 64, 16])
def test_one_source_reference_is_todays(one_device, capacity):
    # loss and gradient of the whole model, drop-free and with capacity
    # binding, equal to the last bit
    cell, abstract, rows, _ = one_device
    s = moe_lm.spec(cell.config)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights.make(abstract, ep4_cases.SEED))
    batch = rows[0]
    one = functools.partial(moe_lm_ep.moe, capacity=capacity, sources=1)
    n = batch["tokens"].size

    def today(p):
        return moe_lm.lm_loss(p, batch, s, "f32", capacity, 1.0, n)[0]

    def per_source(p):
        return moe_lm_ep.lm_loss(p, batch, s, "f32", one, 1.0, n)[0]
    with jax.default_matmul_precision("highest"):
        (la, ga), (lb, gb) = (jax.jit(jax.value_and_grad(f))(params)
                              for f in (today, per_source))
    assert float(la) == float(lb)
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
