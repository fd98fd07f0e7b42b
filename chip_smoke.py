#!/usr/bin/env python3
"""Bring-up smoke run of the MoE trainer and server on a TPU.

    python chip_smoke.py                # one chip: kernels, train, serve
    python chip_smoke.py --four-chips   # a 2x2 host: expert-parallel a2a

The model is ``gpt3_medium_moe`` (the TA-MoE paper's own) at every
published width: d_model 1024, 16 heads, d_ff 4096, 64 experts top-2 with
d_ff_expert 2048 and capacity factor 2.0, vocab 50304, bf16.  Only depth
is cut, to whole ``moe_period=2`` periods, so that bf16 params and grads
and the f32 AdamW moments fit one chip's HBM.  Weights are random, made
from ``--seed``; data is the repo's synthetic LM stream.

One chip, in one process:

* ``kernels`` — each MoE kernel of the path (fused local megakernel,
  permute, ragged grouped GEMM, unpermute) at the real widths against its
  jnp reference, on a small token count;
* ``train`` — ``trainer.train`` on a 1x1 mesh with the TA aux loss: the
  losses must be finite and must not rise from the first step to the last;
* ``serve`` — ``ServingEngine.run`` answers requests through continuous
  batching: every request gets all its tokens, every id lies in
  ``[0, vocab)``, nothing is evicted.

``--four-chips`` runs only the expert-parallel path: the same model on a
``data=4`` mesh (16 experts per chip, ``a2a`` dispatch) against the same
first step on a one-device mesh in this process, with the same seed,
params and global batch; no token may be dropped on either, and the two
first-step LM losses must agree within ``LOSS_RTOL``.

Every compiled program must hold ``tpu_custom_call`` ops, so the Pallas
kernels ran compiled, not interpreted and not as their jnp references.
Step and request times printed here are smoke timings, not benchmark
numbers.  The last line of stdout is one JSON object, ``{"ok": true,
"device": {...}}``.  Without a TPU the script exits 1 before any work and
prints no result; any failed check raises, so the exit code is nonzero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Depth cut: two moe_period=2 periods (2 dense + 2 MoE layers).
NUM_LAYERS = 4
#: Train shape: 4 x 1024 tokens per step (the memory analysis of the
#: donated step puts a batch of 8 past the chip's 16 GB).
SEQ_LEN, GLOBAL_BATCH, TRAIN_STEPS = 1024, 4, 5
#: Serve shape: 8 requests of 128-256 prompt tokens, 32 new tokens each.
NUM_REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 32, (128, 256)
#: --four-chips: 4 x 128 tokens per step, and the agreement asked of the
#: two first-step LM losses: one bf16 ulp (2**-8), relative.  The capacity
#: factor is num_experts / top_k, so that every expert can take every token
#: of every source: at random init the router sends a third of the tokens
#: past a capacity factor of 4.0 (measured on a 2x2 v5e host), and only
#: this factor drops none whatever the routing.  It keeps the one-device
#: slot space at 64 x 512 slots, the size the serve phase's prefill
#: already runs.
FOUR_CHIP_SEQ_LEN, LOSS_RTOL = 128, 2.0 ** -8

_compile_seconds = [0.0]


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_seconds[0] += duration


def log(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def custom_calls(compiled) -> int:
    """``tpu_custom_call`` ops (compiled Pallas kernels) in a compiled
    program or its HLO text."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    return text.count("tpu_custom_call")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def base_arch(num_layers: int = NUM_LAYERS):
    from repro.configs.base import get_config
    full = get_config("gpt3_medium_moe")
    arch = dataclasses.replace(full, num_layers=num_layers)
    log(f"reduced: num_layers {full.num_layers} -> {num_layers}")
    m = arch.moe
    log(f"widths: d_model {arch.d_model} heads {arch.num_heads} d_ff "
        f"{arch.d_ff} experts {m.num_experts} top_k {m.top_k} d_ff_expert "
        f"{m.d_ff_expert} capacity_factor {m.capacity_factor} vocab "
        f"{arch.vocab_size} dtype {arch.dtype} moe_period {m.moe_period}")
    return arch


def describe_moe_paths(ctx, decode: bool):
    """One line per MoE layer: its dispatch path and the kernels it runs."""
    from repro.core.dispatch import transport
    from repro.models import transformer
    prefix, group, n_groups = transformer.layer_plan(ctx.arch)
    subs = list(prefix) + list(group) * n_groups
    lines = []
    for idx, sub in enumerate(subs):
        if sub.ffn != "moe":
            continue
        name = ctx.dispatch_for_layer(idx, decode)
        if name == "gather":
            kern = "moe_fused.local_moe over the gathered tokens"
        else:
            stages = transport.plan_stages(ctx.plan, ctx.ep)
            kern = ", ".join(
                f"stage {s.index}: "
                + ("moe_fused.local_moe" if s.num_dests == 1 else
                   f"moe_permute -> a2a over {s.num_dests} -> "
                   f"moe_gemm ragged -> a2a -> moe_unpermute")
                for s in stages)
        lines.append(f"moe layer {idx}: dispatch {name}; {kern}")
    return lines


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(arch, *, tokens: int = 256, experts: int = 16,
                  cap: int = 32, seed: int = 0) -> dict:
    """Each MoE kernel at the arch's widths vs its jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.moe_fused import ops as fused_ops
    from repro.kernels.moe_fused.ref import local_moe_ref
    from repro.kernels.moe_gemm import ops as gemm_ops
    from repro.kernels.moe_gemm.ref import grouped_ffn_ragged_ref
    from repro.kernels.moe_permute import ops as permute_ops
    from repro.kernels.moe_permute import ref as permute_ref

    d, f, dt = arch.d_model, arch.moe.d_ff_expert, arch.jnp_dtype
    act = arch.activation
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, d)), dt)
    wi = jnp.asarray(rng.standard_normal((experts, d, f)) / math.sqrt(d), dt)
    wo = jnp.asarray(rng.standard_normal((experts, f, d)) / math.sqrt(f), dt)
    offs = tuple(range(0, (experts + 1) * cap, cap))
    exps = tuple(range(experts))
    S = offs[-1]
    tok = np.full(S, tokens, np.int32)
    w = np.zeros(S, np.float32)
    valid = rng.integers(0, cap + 1, experts).astype(np.int32)
    for e in range(experts):
        nv = int(valid[e])
        tok[offs[e]:offs[e] + nv] = rng.choice(tokens, nv, replace=False)
        w[offs[e]:offs[e] + nv] = rng.uniform(0.1, 1.0, nv)
    picks = [[] for _ in range(tokens)]
    for s in range(S):
        if tok[s] < tokens:
            picks[tok[s]].append(s)
    K = max(1, max(len(p) for p in picks))
    inv_idx = np.full((tokens, K), S, np.int32)
    inv_w = np.zeros((tokens, K), np.float32)
    for t, slots in enumerate(picks):
        for k, s in enumerate(slots):
            inv_idx[t, k], inv_w[t, k] = s, w[s]
    tok, w, valid = jnp.asarray(tok), jnp.asarray(w), jnp.asarray(valid)
    inv_idx, inv_w = jnp.asarray(inv_idx), jnp.asarray(inv_w)
    buf = permute_ref.permute_ref(x, tok)

    def rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want))
                     / max(np.max(np.abs(want)), 1e-30))

    def fused(x, tok, w, valid, wi, wo):
        return fused_ops.local_moe(x, tok, w, offs, exps, valid, wi, None,
                                   wo, activation=act)

    def fused_ref(x, tok, w, valid, wi, wo):
        return local_moe_ref(x, tok, w, offs, exps, valid, wi, None, wo,
                             activation=act)

    def ragged(buf, valid, wi, wo):
        return gemm_ops.grouped_ffn_ragged(buf, offs, exps, valid, wi, None,
                                           wo, activation=act)

    def ragged_ref(buf, valid, wi, wo):
        return grouped_ffn_ragged_ref(buf, offs, exps, valid, wi, None, wo,
                                      activation=act)

    # name: (kernel entry, its reference, arguments, relative tolerance)
    cases = {
        "moe_fused.local_moe": (fused, fused_ref,
                                (x, tok, w, valid, wi, wo), 2e-2),
        "moe_permute.permute": (permute_ops.permute,
                                permute_ref.permute_ref, (x, tok), 0.0),
        "moe_gemm.ragged": (ragged, ragged_ref, (buf, valid, wi, wo), 2e-2),
        "moe_permute.unpermute": (permute_ops.unpermute,
                                  permute_ref.unpermute_ref,
                                  (buf, inv_idx, inv_w), 1e-5),
    }
    out = {}
    for name, (fn, ref, fargs, tol) in cases.items():
        jitted = jax.jit(fn)
        n_calls = custom_calls(jitted.lower(*fargs).compile())
        err = rel_err(jitted(*fargs), jax.jit(ref)(*fargs))
        log(f"kernel {name}: d {d} f {f} tokens {tokens} experts "
            f"{experts} tpu_custom_call {n_calls} rel_err {err!r} "
            f"(tol {tol})")
        check(err <= tol, f"{name} disagrees with its reference: {err}")
        out[name] = n_calls
    return out


def phase_train(arch, mesh, *, seq_len: int, global_batch: int, steps: int,
                seed: int, capacity_factor: float | None = None) -> dict:
    """trainer.train on ``mesh``; returns the losses, the first step's
    metrics and the compiled step's HLO text."""
    import jax

    from repro import sharding
    from repro.configs.base import RunConfig
    from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
    from repro.models import model as model_lib
    from repro.training import trainer

    if capacity_factor is not None:
        arch = dataclasses.replace(arch, moe=dataclasses.replace(
            arch.moe, capacity_factor=capacity_factor))
    run = RunConfig(seq_len=seq_len, global_batch=global_batch,
                    warmup_steps=1, total_steps=steps, aux_mode="ta",
                    seed=seed)
    c0 = _compile_seconds[0]
    res = trainer.train(arch, run, mesh, steps=steps, log_every=1)
    compile_s = _compile_seconds[0] - c0
    # the step trainer.train jitted, lowered again at the same shapes to
    # read the compiled program (a persistent-cache hit when it is on)
    ctx = model_lib.build_ctx(arch, mesh, seq_len=seq_len,
                              global_batch=global_batch, aux_mode="ta")
    rules = model_lib.default_rules(mesh)
    with mesh, sharding.axis_rules(rules):
        batch = shard_batch(SyntheticLM(DataConfig(
            vocab_size=arch.vocab_size, seq_len=seq_len,
            global_batch=global_batch, seed=seed), arch).batch(0), mesh)
        hlo = jax.jit(trainer.make_train_step(ctx, run),
                      donate_argnums=(0, 1)).lower(
            res.params, res.opt_state, batch).compile().as_text()
    return {"res": res, "ctx": ctx, "hlo": hlo, "compile_s": compile_s}


def train_one_chip(arch, seed: int) -> dict:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib

    mesh = make_host_mesh(data=1, model=1)
    out = phase_train(arch, mesh, seq_len=SEQ_LEN,
                      global_batch=GLOBAL_BATCH, steps=TRAIN_STEPS,
                      seed=seed)
    res, ctx = out["res"], out["ctx"]
    for line in describe_moe_paths(ctx, decode=False):
        log(f"train {line}")
    losses = res.losses
    n_params = model_lib.count_params(res.params)
    secs = res.step_seconds
    steady = statistics.median(secs[1:]) if len(secs) > 1 else float("nan")
    n_calls = custom_calls(out["hlo"])
    log(f"train: params {n_params} tokens/step {SEQ_LEN * GLOBAL_BATCH} "
        f"steps {len(losses)}")
    log(f"train: losses {losses!r}")
    log(f"train: compile_s {out['compile_s']!r} first_step_s {secs[0]!r} "
        f"steady_step_s {steady!r} (smoke timings)")
    log(f"train: step program tpu_custom_call {n_calls}")
    log(f"train: peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    check(len(losses) == TRAIN_STEPS, f"expected {TRAIN_STEPS} losses")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(losses[-1] <= losses[0],
          f"loss rose from {losses[0]} to {losses[-1]}")
    check(n_calls > 0, "no Pallas kernel in the compiled train step")
    return {"losses": losses, "params": n_params}


def serve_one_chip(arch, seed: int) -> dict:
    import jax
    import numpy as np

    from repro import sharding
    from repro.launch.mesh import make_host_mesh
    from repro.models import decode as decode_lib, model as model_lib
    from repro.serving import batching, engine
    from repro.serving.scheduler import Request

    mesh = make_host_mesh(data=1, model=1)
    cfg = engine.ServeConfig(num_slots=NUM_REQUESTS,
                             cache_len=PROMPT_LENS[1] + 64,
                             prefill_pack=2,
                             prompt_buckets=(PROMPT_LENS[1],))
    ctx = model_lib.build_ctx(arch, mesh, seq_len=cfg.cache_len,
                              global_batch=cfg.num_slots, aux_mode="none")
    for line in describe_moe_paths(ctx, decode=True):
        log(f"serve {line}")
    rng = np.random.default_rng(seed + 1)

    def requests(n, uid0=0):
        return [Request(uid=uid0 + i,
                        tokens=rng.integers(0, arch.vocab_size, int(
                            rng.integers(PROMPT_LENS[0],
                                         PROMPT_LENS[1] + 1))).tolist(),
                        max_new_tokens=NEW_TOKENS)
                for i in range(n)]

    rules = model_lib.default_rules(mesh)
    with mesh, sharding.axis_rules(rules):
        # the engine consumes the stacked params: inspect what it serves
        eng = engine.ServingEngine(model_lib.init_params(
            jax.random.PRNGKey(seed), ctx, rules=rules), ctx, cfg)
        c0 = _compile_seconds[0]
        warm = eng.run(requests(2, uid0=1000), seed=seed)
        compile_s = _compile_seconds[0] - c0
        reqs = requests(NUM_REQUESTS)
        report = eng.run(reqs, seed=seed)
        cache = jax.eval_shape(lambda: batching.SlotKVCache(
            ctx, cfg.num_slots, cfg.cache_len,
            per_layer=decode_lib.is_per_layer(eng.params)).cache)
        i32 = np.int32
        prefill_calls = custom_calls(eng._prefill.lower(
            eng.params, {"tokens": jax.ShapeDtypeStruct(
                (cfg.prefill_pack, PROMPT_LENS[1]), i32),
                "lens": jax.ShapeDtypeStruct((cfg.prefill_pack,), i32)}
        ).compile())
        decode_calls = custom_calls(eng._decode.lower(
            eng.params, cache, jax.ShapeDtypeStruct(
                (cfg.num_slots, 1), i32)).compile())
    served = sum(len(s.generated) for s in report.streams)
    log(f"serve: requests {len(reqs)} served {len(report.streams)} tokens "
        f"{served} decode_steps {report.decode_steps} prefill_packs "
        f"{report.prefill_calls} evictions {report.evictions}")
    log(f"serve: warm-up wall_s {warm.wall_time!r} (compile_s "
        f"{compile_s!r}); wall_s {report.wall_time!r} tokens_per_s "
        f"{report.tokens_per_sec!r} (smoke timings)")
    log(f"serve: prefill program tpu_custom_call {prefill_calls}, decode "
        f"program tpu_custom_call {decode_calls}")
    log(f"serve: peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    got = {s.request.uid: s for s in report.streams}
    check(sorted(got) == [r.uid for r in reqs], "a request went unanswered")
    for s in report.streams:
        check(len(s.generated) == NEW_TOKENS and not s.evicted,
              f"request {s.request.uid}: {len(s.generated)} tokens, "
              f"evicted={s.evicted}")
        check(all(0 <= t < arch.vocab_size for t in s.generated),
              f"request {s.request.uid}: token id out of range")
    check(report.evictions == 0, "evictions")
    check(prefill_calls > 0 and decode_calls > 0,
          "no Pallas kernel in the compiled serve programs")
    return {"served": served}


def four_chips(arch, seed: int) -> None:
    """Expert-parallel a2a on a data=4 mesh vs one device, first step."""
    import jax
    import numpy as np

    from repro.compat import make_mesh
    from repro.launch.mesh import make_host_mesh

    check(len(jax.devices()) == 4, f"need 4 devices, have "
          f"{len(jax.devices())}")
    cf = arch.moe.num_experts / arch.moe.top_k
    log(f"four-chips: capacity_factor {arch.moe.capacity_factor} -> {cf} "
        f"(num_experts / top_k: no token can be dropped); tokens/step "
        f"{FOUR_CHIP_SEQ_LEN * GLOBAL_BATCH}")
    kw = dict(seq_len=FOUR_CHIP_SEQ_LEN, global_batch=GLOBAL_BATCH, steps=1,
              seed=seed, capacity_factor=cf)
    out4 = phase_train(arch, make_host_mesh(data=4, model=1), **kw)
    for line in describe_moe_paths(out4["ctx"], decode=False):
        log(f"four-chips {line}")
    m4 = out4["res"].metrics_history[0]
    per_dev = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            out4["res"].params)[0]:
        name = jax.tree_util.keystr(path)
        if "ffn" in name and ("w_in" in name or "w_out" in name) \
                and leaf.ndim == 4:
            for sh in leaf.addressable_shards:
                nb, ne = per_dev.get(sh.device.id, (0, set()))
                per_dev[sh.device.id] = (nb + sh.data.nbytes,
                                         ne | {sh.data.shape[1]})
    for dev, (nb, ne) in sorted(per_dev.items()):
        log(f"four-chips: device {dev} expert-weight bytes {nb} experts "
            f"per layer {sorted(ne)}")
    n_a2a = out4["hlo"].count("all-to-all")
    n_calls = custom_calls(out4["hlo"])
    log(f"four-chips: step program all-to-all {n_a2a} tpu_custom_call "
        f"{n_calls} compile_s {out4['compile_s']!r}")
    e_l = arch.moe.num_experts // 4
    check(len(per_dev) == 4 and all(ne == {e_l} for _, ne in
                                    per_dev.values()),
          f"experts not spread {e_l} per device: {per_dev}")
    check(n_a2a > 0 and n_calls > 0, "no all-to-all / kernel in the step")
    del out4
    one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    out1 = phase_train(arch, one, **kw)
    m1 = out1["res"].metrics_history[0]
    del out1
    for k in ("loss", "nll", "aux", "dropped"):
        log(f"four-chips: first step {k}: 4 chips {m4[k]!r} one device "
            f"{m1[k]!r}")
    check(m4["dropped"] == 0.0 and m1["dropped"] == 0.0, "tokens dropped")
    # the TA aux term is computed against each mesh's own topology, so the
    # two runs are held to the same LM loss (nll)
    diff = abs(m4["nll"] - m1["nll"])
    tol = LOSS_RTOL * abs(m1["nll"])
    log(f"four-chips: |nll diff| {diff!r} tolerance {tol!r} (rtol 2**-8)")
    check(np.isfinite(m4["nll"]) and diff <= tol,
          "first-step losses disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1

    from jax import monitoring

    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    monitoring.register_event_duration_secs_listener(_on_duration)
    log(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}")

    arch = base_arch()
    t0 = time.time()
    if args.four_chips:
        four_chips(arch, args.seed)
    else:
        calls = phase_kernels(arch, seed=args.seed)
        check(all(n > 0 for n in calls.values()),
              f"a kernel compiled without tpu_custom_call: {calls}")
        train_one_chip(arch, args.seed)
        serve_one_chip(arch, args.seed)
    log(f"total wall_s {time.time() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
