"""The two layer layouts of a params/cache tree (``decode.unstack_groups``):
``decode_step`` and ``prefill`` give the same logits and cache when the
groups arrive per layer (a Python loop over each layer's own buffers, what
``ServingEngine`` runs) as when they arrive stacked (``lax.scan``), in every
mixer family and under a dispatch override that unrolls the stacked loop;
and ``unstack_groups`` / ``stack_groups`` round-trip exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding
from repro.configs.base import get_config
from repro.models import decode as decode_lib, model as model_lib

B, S, CACHE_LEN, STEPS = 2, 8, 12, 2

# every case has at least two layer groups, so the loop runs more than once
CASES = {
    "mla_moe": ("deepseek_v2_lite_16b", {"num_layers": 3}, ()),
    "attn": ("olmo_1b", {}, ()),
    "recurrent": ("xlstm_350m", {"num_layers": 4, "slstm_every": 2}, ()),
    "cross": ("whisper_tiny", {}, ()),
    # layer 3 is the second group's MoE layer: off the decode default
    "override": ("gpt3_medium_moe", {"num_layers": 4}, ((3, "einsum"),)),
}


def _close(a, b):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def _same_tree(a, b, check=_close):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        check(x, y)


@pytest.mark.parametrize("case", sorted(CASES))
def test_per_layer_layout_matches_stacked_scan(case, mesh11, key):
    arch_id, changes, override = CASES[case]
    arch = dataclasses.replace(get_config(arch_id).reduced(), **changes)
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=CACHE_LEN,
                              global_batch=B, aux_mode="none")
    ctx = dataclasses.replace(ctx, dispatch_override=override)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, arch.vocab_size, (B, S)),
                                   jnp.int32),
             "lens": jnp.asarray([S, S - 3], jnp.int32)}
    if arch.frontend:
        batch["frontend"] = jnp.asarray(rng.standard_normal(
            (B, arch.frontend_len, arch.d_model)), jnp.float32)
    prefill = jax.jit(lambda p, b: decode_lib.prefill(
        p, b, ctx, cache_len=CACHE_LEN, lens=b["lens"]))
    step = jax.jit(lambda p, c, t: decode_lib.decode_step(p, c, t, ctx))

    with mesh11, sharding.axis_rules(model_lib.default_rules(mesh11)):
        stacked = model_lib.init_params(key, ctx)
        per_layer = decode_lib.unstack_groups(stacked)
        n_groups = len(per_layer["groups"])
        assert n_groups >= 2
        _same_tree(decode_lib.stack_groups(per_layer), stacked,
                   np.testing.assert_array_equal)
        # a tree already in a layout is returned as it is
        assert decode_lib.unstack_groups(per_layer) is per_layer
        assert decode_lib.stack_groups(stacked) is stacked

        out = {}
        for name, params in (("stacked", stacked), ("per_layer", per_layer)):
            logits, cache = prefill(params, batch)
            trail = [logits]
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            for _ in range(STEPS):
                lg, cache = step(params, cache, tok)
                trail.append(lg[:, 0])
                tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)[:, None]
            out[name] = (trail, cache)

    (trail_s, cache_s), (trail_p, cache_p) = out["stacked"], out["per_layer"]
    assert not isinstance(cache_s["groups"], tuple)
    assert isinstance(cache_p["groups"], tuple)
    assert len(cache_p["groups"]) == n_groups
    for a, b in zip(trail_s, trail_p):
        _close(a, b)
    _same_tree(decode_lib.stack_groups(cache_p), cache_s)
    _same_tree(decode_lib.unstack_groups(cache_s), cache_p)


def test_unstack_with_donate_deletes_each_stack(mesh11, key):
    """``unstack_groups(..., donate=True)`` gives the same per-layer arrays
    as the plain split and deletes every stacked group array (and no other
    leaf), so the stacks and their slices are never held side by side."""
    arch, changes, _ = CASES["mla_moe"]
    arch = dataclasses.replace(get_config(arch).reduced(), **changes)
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=CACHE_LEN,
                              global_batch=B, aux_mode="none")
    with mesh11, sharding.axis_rules(model_lib.default_rules(mesh11)):
        stacked = model_lib.init_params(key, ctx)
        copy = jax.tree_util.tree_map(jnp.copy, stacked)
        want = decode_lib.unstack_groups(stacked)
        got = decode_lib.unstack_groups(copy, donate=True)
    assert len(got["groups"]) >= 2
    assert all(a.is_deleted()
               for a in jax.tree_util.tree_leaves(copy["groups"]))
    assert not any(a.is_deleted() for k, v in copy.items() if k != "groups"
                   for a in jax.tree_util.tree_leaves(v))
    _same_tree(got, want, np.testing.assert_array_equal)
    for a, b in zip(jax.tree_util.tree_leaves(got["groups"]),
                    jax.tree_util.tree_leaves(want["groups"])):
        assert a.sharding == b.sharding
