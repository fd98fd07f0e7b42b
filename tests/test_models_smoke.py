"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned arch runs one forward/train step on CPU — output shapes + no NaNs.

The FULL configs are exercised only by the dry-run (launch/dryrun.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding
from repro.configs.base import ARCH_IDS, RunConfig, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as model_lib, transformer
from repro.optim import adamw
from repro.training import trainer

SEQ, BATCH = 16, 2


def _setup(arch_id, mesh11, aux="ta"):
    arch = get_config(arch_id).reduced()
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=SEQ, global_batch=BATCH,
                              aux_mode=aux if arch.is_moe else "none")
    rules = model_lib.default_rules(mesh11)
    return arch, ctx, rules


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_forward_shapes_and_finite(arch_id, mesh11, key):
    arch, ctx, rules = _setup(arch_id, mesh11)
    data = SyntheticLM(DataConfig(vocab_size=arch.vocab_size, seq_len=SEQ,
                                  global_batch=BATCH), arch)
    batch = data.batch(0)
    with mesh11, sharding.axis_rules(rules):
        params = model_lib.init_params(key, ctx)
        logits, aux = jax.jit(
            lambda p, b: transformer.forward(p, b, ctx))(params, batch)
    assert logits.shape == (BATCH, SEQ, arch.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_one_train_step(arch_id, mesh11, key):
    arch, ctx, rules = _setup(arch_id, mesh11)
    run = RunConfig(seq_len=SEQ, global_batch=BATCH, total_steps=4,
                    warmup_steps=1,
                    aux_mode="ta" if arch.is_moe else "none")
    data = SyntheticLM(DataConfig(vocab_size=arch.vocab_size, seq_len=SEQ,
                                  global_batch=BATCH), arch)
    with mesh11, sharding.axis_rules(rules):
        params = model_lib.init_params(key, ctx)
        opt = adamw.init_state(params)
        step = jax.jit(trainer.make_train_step(ctx, run))
        p2, o2, metrics = step(params, opt, data.batch(0))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(p2)[0]
    assert not np.allclose(np.asarray(l0, np.float32),
                           np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_decode_step_runs(arch_id, mesh11, key):
    from repro.models import decode as decode_lib
    arch, ctx, rules = _setup(arch_id, mesh11)
    with mesh11, sharding.axis_rules(rules):
        params = model_lib.init_params(key, ctx)
        cache = decode_lib.init_cache(ctx, BATCH, max_len=SEQ)
        tok = jnp.zeros((BATCH, 1), jnp.int32)
        logits, cache2 = jax.jit(
            lambda p, c, t: decode_lib.decode_step(p, c, t, ctx))(
                params, cache, tok)
    assert logits.shape == (BATCH, 1, arch.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_reduced_configs_respect_budgets():
    for arch_id in ARCH_IDS:
        r = get_config(arch_id).reduced()
        assert r.d_model <= 512
        if r.moe:
            assert r.moe.num_experts <= 4
        assert r.num_layers <= 8


def test_full_configs_match_assignment():
    """The exact assigned numbers (system prompt) are encoded."""
    expect = {
        "jamba_v0_1_52b": (32, 4096, 32, 8, 14336, 65536),
        "internlm2_1_8b": (24, 2048, 16, 8, 8192, 92544),
        "internvl2_26b": (48, 6144, 48, 8, 16384, 92553),
        "olmo_1b": (16, 2048, 16, 16, 8192, 50304),
        "whisper_tiny": (4, 384, 6, 6, 1536, 51865),
        "deepseek_v2_lite_16b": (27, 2048, 16, 16, None, 102400),
        "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
        "deepseek_v2_236b": (60, 5120, 128, 128, None, 102400),
        "granite_3_2b": (40, 2048, 32, 8, 8192, 49155),
        "minitron_4b": (32, 3072, 24, 8, 9216, 256000),
    }
    for aid, (L, d, H, kv, ff, V) in expect.items():
        c = get_config(aid)
        assert c.num_layers == L and c.d_model == d
        assert c.num_heads == H and c.num_kv_heads == kv
        assert c.vocab_size == V
        if ff is not None:
            assert c.d_ff == ff
    assert get_config("jamba_v0_1_52b").moe.num_experts == 16
    assert get_config("deepseek_v2_lite_16b").moe.num_experts == 64
    assert get_config("deepseek_v2_lite_16b").moe.top_k == 6
    assert get_config("deepseek_v2_236b").moe.num_experts == 160
    assert get_config("deepseek_v2_lite_16b").mla.kv_lora_rank == 512


@pytest.mark.parametrize("arch_id", [a for a in ARCH_IDS
                                     if get_config(a).is_moe])
def test_moe_layers_follow_moe_period(arch_id, mesh11):
    """Every MoE arch puts its MoE FFN on every ``moe_period``-th layer
    after the dense prefix (gpt3_medium_moe: every other layer), and its
    expert weights keep the model dtype."""
    arch = get_config(arch_id)
    m = arch.moe
    prefix, group, n_groups = transformer.layer_plan(arch)
    ffns = [s.ffn for s in list(prefix) + list(group) * n_groups]
    assert len(ffns) == arch.num_layers
    assert ffns == ["mlp"] * m.first_dense + [
        "moe" if (i + 1) % m.moe_period == 0 else "mlp"
        for i in range(arch.num_layers - m.first_dense)]
    small = dataclasses.replace(arch.reduced(), dtype=arch.dtype)
    ctx = model_lib.build_ctx(small, mesh11, seq_len=SEQ, global_batch=BATCH)
    shapes = jax.eval_shape(lambda k: transformer.init_model(k, ctx),
                            jax.random.PRNGKey(0))
    experts = [leaf for path, leaf in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if jax.tree_util.keystr(path).endswith("['ffn']['w_in']")
               and leaf.ndim == 4]
    assert experts and all(e.dtype == small.jnp_dtype for e in experts)
