"""Continuous-batching serving layer: scheduler admit/evict/slot-reuse,
prefill packing equivalence, slotted KV-cache ops, and batched-generate
parity with the single-stream driver.

The recompilation assertions use the jit cache size of the engine's own
compiled functions — the no-recompile invariant (fixed pack width,
bucketed prompt pads, fixed slot count) is the whole point of the slot
design, so a second cache entry is a regression, not a detail.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding
from repro.configs.base import get_config
from repro.models import decode as decode_lib, model as model_lib
from repro.serving import batching, engine
from repro.serving.scheduler import Request, Scheduler

ARCH_ID = "gpt3_medium_moe"


def _build(mesh11, key, arch_id=ARCH_ID, seq_len=32, batch=4):
    arch = dataclasses.replace(get_config(arch_id).reduced(), dtype="float32")
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=seq_len,
                              global_batch=batch, aux_mode="none")
    with mesh11, sharding.axis_rules(model_lib.default_rules(mesh11)):
        params = model_lib.init_params(key, ctx)
    return arch, ctx, params


def _prompts(arch, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, arch.vocab_size, size=n).tolist() for n in lens]


# ---------------------------------------------------------------------------
# scheduler (pure python, no jax)
# ---------------------------------------------------------------------------


def test_scheduler_slot_exhaustion():
    sched = Scheduler(num_slots=2)
    for i in range(5):
        sched.submit(Request(uid=i, tokens=[1, 2], max_new_tokens=3))
    admits = sched.take(10, now=0.0)
    assert [s for s, _ in admits] == [0, 1]
    assert sched.num_active == 2 and sched.num_pending == 3
    # pool exhausted: further takes admit nothing
    assert sched.take(10, now=0.0) == []
    sched.on_token(0, 7)
    # freeing one slot admits exactly one more request, into that slot
    sched.complete(0, now=1.0)
    admits = sched.take(10, now=1.0)
    assert [s for s, _ in admits] == [0]
    assert admits[0][1].uid == 2


def test_scheduler_variable_length_completion_and_reuse():
    sched = Scheduler(num_slots=3)
    for i, budget in enumerate([1, 3, 2]):
        sched.submit(Request(uid=i, tokens=[5], max_new_tokens=budget))
    [(s0, _), (s1, _), (s2, _)] = sched.take(3, now=0.0)
    # stream 0 finishes first (budget 1), then 2, then 1
    assert sched.on_token(s0, 11) is True
    sched.complete(s0, now=0.1)
    assert sched.on_token(s1, 12) is False
    assert sched.on_token(s2, 13) is False
    assert sched.on_token(s2, 14) is True
    sched.complete(s2, now=0.2)
    # lowest freed slot (0) is reused first, deterministically
    sched.submit(Request(uid=9, tokens=[5], max_new_tokens=1))
    assert sched.take(1, now=0.3)[0][0] == min(s0, s2) == 0
    assert [st.request.uid for st in sched.finished] == [0, 2]
    assert sched.finished[1].generated == [13, 14]


def test_scheduler_validation():
    sched = Scheduler(num_slots=1)
    with pytest.raises(ValueError):
        sched.submit(Request(uid=0, tokens=[], max_new_tokens=1))
    with pytest.raises(ValueError):
        sched.submit(Request(uid=0, tokens=[1], max_new_tokens=0))
    sched.submit(Request(uid=0, tokens=[1], max_new_tokens=1))
    [(slot, _)] = sched.take(1, now=0.0)
    assert sched.on_token(slot, 3) is True
    with pytest.raises(ValueError):
        sched.on_token(slot, 4)       # stream already complete
    with pytest.raises(ValueError):
        Scheduler(num_slots=0)


def test_pad_pack_and_buckets():
    assert batching.pick_bucket(5, (8, 16)) == 8
    assert batching.pick_bucket(9, (8, 16)) == 16
    with pytest.raises(ValueError):
        batching.pick_bucket(17, (8, 16))
    tokens, lens = batching.pad_pack([[1, 2, 3], [4]], pack=4,
                                     buckets=(8,))
    assert tokens.shape == (4, 8) and lens.shape == (4,)
    assert list(np.asarray(lens)) == [3, 1, 1, 1]   # padded rows: dummy len 1
    assert list(np.asarray(tokens[0, :3])) == [1, 2, 3]
    assert int(tokens[1, 0]) == 4
    with pytest.raises(ValueError):
        batching.pad_pack([[1]] * 5, pack=4, buckets=(8,))


# ---------------------------------------------------------------------------
# slotted KV cache
# ---------------------------------------------------------------------------


def test_slot_cache_insert_evict_reuse_no_recompile(mesh11, key):
    arch, ctx, params = _build(mesh11, key)
    cache_len, pack = 24, 2
    kv = batching.SlotKVCache(ctx, num_slots=4, cache_len=cache_len)
    prefill = jax.jit(engine.make_prefill(ctx, with_cache=True,
                                          cache_len=cache_len))
    prompts = _prompts(arch, [6, 9])
    tokens, lens = batching.pad_pack(prompts, pack, buckets=(16,))
    with mesh11:
        _, pack_cache = prefill(params, {"tokens": tokens, "lens": lens})
        # second pack row carries an out-of-range slot id -> dropped
        kv.insert(pack_cache, jnp.asarray([2, kv.num_slots], jnp.int32))
        assert list(kv.positions()) == [0, 0, 6, 0]
        kv.insert(pack_cache, jnp.asarray([0, 3], jnp.int32))
        assert list(kv.positions()) == [6, 0, 6, 9]
        kv.evict(jnp.asarray([2, 3], jnp.int32))
        assert list(kv.positions()) == [6, 0, 0, 0]
        # re-admitting into the freed slots reuses the same compiled fns
        kv.insert(pack_cache, jnp.asarray([2, 3], jnp.int32))
        assert list(kv.positions()) == [6, 0, 6, 9]
    assert kv._insert._cache_size() == 1
    assert kv._evict._cache_size() == 1


# ---------------------------------------------------------------------------
# prefill packing equivalence
# ---------------------------------------------------------------------------


def test_prefill_packing_equivalence(mesh11, key):
    """A right-padded prompt pack must be indistinguishable from prefilling
    each prompt alone: same last logits, and same decode trajectory from
    the materialized cache (the strongest check that padded rows never
    leak into real rows — decode=True MoE dispatch is drop-free)."""
    arch, ctx, params = _build(mesh11, key)
    cache_len = 24
    lens_py = [4, 9, 6]
    prompts = _prompts(arch, lens_py)
    prefill = jax.jit(engine.make_prefill(ctx, with_cache=True,
                                          cache_len=cache_len))
    step = jax.jit(engine.make_decode_step(ctx))
    with mesh11:
        tokens, lens = batching.pad_pack(prompts, pack=4, buckets=(16,))
        logits_p, cache_p = prefill(params, {"tokens": tokens, "lens": lens})
        traj_p = [np.asarray(logits_p)]
        tok = jnp.argmax(logits_p, axis=-1).astype(jnp.int32)[:, None]
        for _ in range(3):
            lg, cache_p = step(params, cache_p, tok)
            traj_p.append(np.asarray(lg[:, 0]))
            tok = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)[:, None]
        for i, p in enumerate(prompts):
            t1 = jnp.asarray(np.asarray(p, np.int32)[None])
            l1 = jnp.asarray([len(p)], jnp.int32)
            lg1, c1 = prefill(params, {"tokens": t1, "lens": l1})
            err = np.max(np.abs(np.asarray(lg1[0]) - traj_p[0][i]))
            assert err < 2e-4, f"prompt {i}: prefill logits diverge {err}"
            tok1 = jnp.argmax(lg1, axis=-1).astype(jnp.int32)[:, None]
            for k in range(3):
                lg1, c1 = step(params, c1, tok1)
                err = np.max(np.abs(np.asarray(lg1[0, 0]) - traj_p[k + 1][i]))
                assert err < 2e-4, f"prompt {i} step {k}: {err}"
                tok1 = jnp.argmax(lg1[:, 0], axis=-1).astype(jnp.int32)[:, None]
    # one packed entry + one per distinct single-prompt length
    assert prefill._cache_size() == 1 + len(set(lens_py))


def test_prefill_rejects_overlong_prompt(mesh11, key):
    arch, ctx, params = _build(mesh11, key)
    prefill = engine.make_prefill(ctx, with_cache=True, cache_len=8)
    toks = jnp.zeros((1, 12), jnp.int32)
    with mesh11, pytest.raises(ValueError):
        prefill(params, {"tokens": toks})


# ---------------------------------------------------------------------------
# end-to-end serving
# ---------------------------------------------------------------------------


def test_batched_generate_parity_with_single_stream(mesh11, key):
    """Greedy continuous batching must emit exactly the tokens the
    single-stream ``generate`` driver produces for each request."""
    arch, ctx, params = _build(mesh11, key)
    lens_py = [5, 8, 3]
    prompts = _prompts(arch, lens_py, seed=3)
    steps = 5
    cfg = engine.ServeConfig(num_slots=4, cache_len=24, prefill_pack=2,
                             prompt_buckets=(16,))
    with mesh11:
        # the engine consumes stacked params; ``generate`` below reads them
        eng = engine.ServingEngine(decode_lib.unstack_groups(params), ctx,
                                   cfg)
        reqs = [Request(uid=i, tokens=p, max_new_tokens=steps)
                for i, p in enumerate(prompts)]
        report = eng.run(reqs)
        assert report.total_new_tokens == steps * len(prompts)
        assert report.prefill_calls == 2       # 3 requests, pack width 2
        for i, p in enumerate(prompts):
            single = engine.generate(
                params, ctx, jnp.asarray(np.asarray(p, np.int32)[None]),
                steps=steps, cache_len=24)
            want = list(np.asarray(single.tokens[0]))
            assert report.tokens_for(i) == want, f"request {i} diverged"


def test_serving_slot_reuse_never_recompiles(mesh11, key):
    """More requests than slots, mixed lengths within one bucket: every
    admit/evict/re-admit round must hit the same compiled entries."""
    arch, ctx, params = _build(mesh11, key)
    cfg = engine.ServeConfig(num_slots=2, cache_len=24, prefill_pack=2,
                             prompt_buckets=(16,))
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i,
                    tokens=_prompts(arch, [int(rng.integers(2, 12))],
                                    seed=i)[0],
                    max_new_tokens=int(rng.integers(1, 5)))
            for i in range(6)]
    with mesh11:
        eng = engine.ServingEngine(params, ctx, cfg)
        report = eng.run(reqs)
    assert len(report.streams) == 6
    assert report.prefill_calls >= 3          # forced several rounds
    assert eng._prefill._cache_size() == 1
    assert eng._decode._cache_size() == 1
    assert eng._sample._cache_size() <= 2     # pack-width + slot-width rows
    for r in reqs:
        assert len(report.tokens_for(r.uid)) == r.max_new_tokens


def test_streams_stamp_their_first_token(mesh11, key):
    """``t_first_token`` is stamped when the prefill's token reaches the
    host: after admission, no later than completion."""
    arch, ctx, params = _build(mesh11, key)
    cfg = engine.ServeConfig(num_slots=2, cache_len=24, prefill_pack=2,
                             prompt_buckets=(16,))
    reqs = [Request(uid=i, tokens=p, max_new_tokens=n) for i, (p, n) in
            enumerate(zip(_prompts(arch, [4, 7, 3]), [3, 1, 2]))]
    with mesh11:
        report = engine.ServingEngine(params, ctx, cfg).run(reqs)
    assert len(report.streams) == 3
    for s in report.streams:
        assert 0 < s.t_admitted <= s.t_first_token <= s.t_finished


def test_engine_programs_take_per_layer_buffers(mesh11, key):
    """A model with MoE groups: the engine consumes the stacked params (each
    group stack deleted once split), its decode and prefill programs take
    each layer's weights and cache as buffers of their own (no argument has
    the stacks' leading layer-group axis), and decode, insert and evict
    donate the slot cache."""
    arch = dataclasses.replace(get_config(ARCH_ID).reduced(), num_layers=6,
                               dtype="float32")
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=24, global_batch=2,
                              aux_mode="none")
    cfg = engine.ServeConfig(num_slots=2, cache_len=24, prefill_pack=2,
                             prompt_buckets=(16,))
    with mesh11, sharding.axis_rules(model_lib.default_rules(mesh11)):
        params = model_lib.init_params(key, ctx)
        stacks = jax.tree_util.tree_leaves(
            (params["groups"], decode_lib.init_cache(ctx, 2, 24)["groups"]))
        eng = engine.ServingEngine(params, ctx, cfg)
        kv = batching.SlotKVCache(ctx, cfg.num_slots, cfg.cache_len,
                                  per_layer=True)
        tokens, lens = batching.pad_pack([[1, 2, 3]], cfg.prefill_pack,
                                         cfg.prompt_buckets)
        prefill = eng._prefill.lower(eng.params,
                                     {"tokens": tokens, "lens": lens})
        cur = jnp.zeros((cfg.num_slots, 1), jnp.int32)
        decode = eng._decode.lower(eng.params, kv.cache, cur)
        _, pack = eng._prefill(eng.params, {"tokens": tokens, "lens": lens})
        slots = jnp.asarray([0, cfg.num_slots], jnp.int32)
        insert = kv._insert.lower(kv.cache, pack, slots)
        evict = kv._evict.lower(kv.cache, slots)
    assert isinstance(eng.params["groups"], tuple)
    assert all(a.is_deleted()
               for a in jax.tree_util.tree_leaves(params["groups"]))
    assert not params["embed"]["table"].is_deleted()
    assert stacks[0].shape[0] == 3
    stacked = {leaf.shape for leaf in stacks}
    for program in (prefill, decode, insert):
        shapes = [a.shape for a in jax.tree_util.tree_leaves(
            program.args_info)]
        assert not stacked & set(shapes), stacked & set(shapes)
    (p_info, c_info, t_info), _ = decode.args_info
    assert all(a.donated for a in jax.tree_util.tree_leaves(c_info))
    assert not any(a.donated for a in jax.tree_util.tree_leaves(
        (p_info, t_info)))
    assert "tf.aliasing_output" in decode.as_text()
    for program in (insert, evict):
        cache_info = program.args_info[0][0]
        assert all(a.donated for a in jax.tree_util.tree_leaves(cache_info))


def test_engine_keeps_the_scan_without_moe_groups(mesh11, key):
    """A model without MoE groups is served from its stacked params, which
    the engine leaves as they are, and still matches ``generate``."""
    arch, ctx, params = _build(mesh11, key, arch_id="olmo_1b")
    cfg = engine.ServeConfig(num_slots=2, cache_len=24, prefill_pack=2,
                             prompt_buckets=(16,))
    prompts = _prompts(arch, [5, 8], seed=4)
    with mesh11:
        eng = engine.ServingEngine(params, ctx, cfg)
        assert eng.params is params
        report = eng.run([Request(uid=i, tokens=p, max_new_tokens=3)
                          for i, p in enumerate(prompts)])
        for i, p in enumerate(prompts):
            single = engine.generate(
                params, ctx, jnp.asarray(np.asarray(p, np.int32)[None]),
                steps=3, cache_len=24)
            assert report.tokens_for(i) == list(np.asarray(single.tokens[0]))


def test_serving_rejects_budget_overflow(mesh11, key):
    arch, ctx, params = _build(mesh11, key)
    cfg = engine.ServeConfig(num_slots=2, cache_len=16, prefill_pack=2,
                             prompt_buckets=(16,))
    with mesh11:
        eng = engine.ServingEngine(params, ctx, cfg)
        req = Request(uid=0, tokens=_prompts(arch, [10])[0],
                      max_new_tokens=10)     # 10 + 10 > 16
        with pytest.raises(ValueError):
            eng.run([req])


def test_generate_counts_only_generated_tokens(mesh11, key):
    """Regression for the steps_per_sec bug: the reported rate is per
    generated token (prompt positions are prefill work, not decode)."""
    arch, ctx, params = _build(mesh11, key)
    toks = jnp.asarray(np.asarray(_prompts(arch, [10])[0], np.int32)[None])
    with mesh11:
        res = engine.generate(params, ctx, toks, steps=4, cache_len=24)
    assert res.tokens.shape == (1, 4)
    assert res.steps_per_sec > 0
