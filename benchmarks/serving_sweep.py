"""Serving throughput sweep: continuous batching at 1/4/8 concurrent
streams over the mla and vlm serving configs.

Rows (``serving_<fam>_s<N>``) report microseconds per *generated* token
and aggregate tokens/sec at each concurrency level; they land in
``BENCH_serving.json`` and are gated by ``benchmarks.compare`` against
``results/BENCH_baseline.json``.  ``serving_anchor_*`` rows are fixed
pure-jnp workloads running no repo code — compare's machine-speed
normalization pivots on them, so a serving-path regression cannot
masquerade as "the runner got slower".

The ``serving_mla_seq8`` row is the contrast arm: the same eight requests
served as eight *sequential* single-stream ``generate`` calls (shared
warmed jit entries, so compile time is excluded from both arms).  On the
CPU lane the batched engine must beat it measurably — eight slots advance
per decode step for roughly the cost of one — and this module *raises*
otherwise, which run.py records as a ``serving_FAILED`` row and the
compare gate then rejects.

Measurement: every engine is compiled and warmed with a full run first;
the reported number is the min over measured runs (timeit convention).
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.compat import make_mesh
from repro.configs.base import get_config
from repro.models import model as model_lib, vlm
from repro.serving import batching, engine
from repro.serving.scheduler import Request

ARCHS = (("mla", "deepseek_v2_lite_16b"), ("vlm", "internvl2_26b"))
STREAMS = (1, 4, 8)
CACHE_LEN = 48
BUCKET = 24


def _build(arch_id, mesh):
    arch = dataclasses.replace(get_config(arch_id).reduced(),
                               dtype="float32")
    ctx = model_lib.build_ctx(arch, mesh, seq_len=CACHE_LEN,
                              global_batch=max(STREAMS), aux_mode="none")
    rules = model_lib.default_rules(mesh)
    with mesh, sharding.axis_rules(rules):
        params = model_lib.init_params(jax.random.PRNGKey(0), ctx,
                                       rules=rules)
    return arch, ctx, params


def _requests(arch, n, new_tokens, seed=0):
    """Mixed prompt lengths within one bucket, fixed output budget."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n):
        plen = int(rng.integers(6, BUCKET - 3))
        fe = (vlm.make_patches(rng, 1, arch)[0]
              if arch.frontend == "vision" else None)
        reqs.append(Request(uid=uid,
                            tokens=rng.integers(0, arch.vocab_size,
                                                size=plen).tolist(),
                            max_new_tokens=new_tokens, frontend=fe))
    return reqs


def _serve(eng, reqs, rounds):
    """Warm (compile) once, then min wall-time over measured runs."""
    eng.run(reqs)
    walls, report = [], None
    for _ in range(rounds):
        report = eng.run(reqs)
        walls.append(report.wall_time)
    return min(walls), report


def _sequential(arch, ctx, params, reqs, new_tokens, rounds, mesh):
    """The contrast arm: one warmed single-stream ``generate`` per
    request, prompts right-padded to the shared bucket so all eight calls
    hit one jit entry (exactly the shapes the batched engine prefills)."""
    fns = engine.make_generate_fns(ctx, CACHE_LEN)
    packs = []
    for req in reqs:
        toks, lens = batching.pad_pack([req.tokens], 1, (BUCKET,))
        fe = (req.frontend[None] if req.frontend is not None else None)
        packs.append((toks, lens, fe))

    def one_round():
        t0 = time.perf_counter()
        for toks, lens, fe in packs:
            engine.generate(params, ctx, toks, steps=new_tokens,
                            cache_len=CACHE_LEN, lens=lens, frontend=fe,
                            fns=fns)
        return time.perf_counter() - t0

    with mesh:
        one_round()                      # compile + warm
        return min(one_round() for _ in range(rounds))


def _anchor_rows(rounds):
    """Fixed pure-jnp decode-shaped workloads (no repo code): a batched
    GEMM chain driven from a host loop, mimicking the decode loop's
    call-overhead profile, plus a plain matmul."""
    a = jax.random.normal(jax.random.PRNGKey(3), (8, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (512, 512), jnp.float32)
    step = jax.jit(lambda x, _w=w: jnp.tanh(x @ _w))
    m = jax.random.normal(jax.random.PRNGKey(5), (640, 640), jnp.float32)
    mm = jax.jit(lambda x: (x @ x) @ x)

    def loop():
        x = a
        for _ in range(16):
            x = step(x)
        return x

    jax.block_until_ready(loop())
    jax.block_until_ready(mm(m))
    rows = []
    for name, fn, iters in (("decode_loop", loop, 4), ("matmul",
                                                       lambda: mm(m), 8)):
        samples = []
        for _ in range(max(rounds, 2) * 4):   # anchors set the gate scale
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            samples.append((time.perf_counter() - t0) / iters * 1e6)
        rows.append((f"serving_anchor_{name}", float(min(samples)),
                     f"backend={jax.default_backend()}"))
    return rows


def run(quick: bool = False):
    new_tokens = 4 if quick else 8
    rounds = 1 if quick else 2
    mesh = make_mesh((1, 1), ("data", "model"))
    backend = jax.default_backend()
    rows = []
    walls = {}
    print(f"# serving sweep: streams={STREAMS} new={new_tokens} "
          f"cache={CACHE_LEN} backend={backend} (min of {rounds} runs)")
    for fam, arch_id in ARCHS:
        arch, ctx, params = _build(arch_id, mesh)
        reqs = _requests(arch, max(STREAMS), new_tokens)
        for s in STREAMS:
            cfg = engine.ServeConfig(num_slots=s, cache_len=CACHE_LEN,
                                     prefill_pack=min(s, 4),
                                     prompt_buckets=(BUCKET,))
            with mesh:
                # the engine consumes the params it is given
                eng = engine.ServingEngine(
                    jax.tree_util.tree_map(jnp.copy, params), ctx, cfg)
                wall, report = _serve(eng, reqs[:s], rounds)
            total = report.total_new_tokens
            tps = total / wall
            us = wall / total * 1e6
            walls[(fam, s)] = wall
            rows.append((f"serving_{fam}_s{s}", us,
                         f"streams={s};tok_s={tps:.2f};new={new_tokens};"
                         f"backend={backend}"))
            print(f"  {fam} s={s}: {tps:8.2f} tok/s "
                  f"({us:9.0f} us/token)")
        if fam == "mla":
            seq_wall = _sequential(arch, ctx, params, reqs[:8],
                                   new_tokens, rounds, mesh)
            seq_us = seq_wall / (8 * new_tokens) * 1e6
            rows.append(("serving_mla_seq8", seq_us,
                         f"streams=8;sequential=1;"
                         f"tok_s={8 * new_tokens / seq_wall:.2f};"
                         f"backend={backend}"))
            print(f"  {fam} seq8: {8 * new_tokens / seq_wall:8.2f} tok/s "
                  f"(sequential contrast)")
            batched = walls[("mla", 8)]
            print(f"# batched/sequential 8-stream wall ratio: "
                  f"{batched / seq_wall:.3f}")
            if backend == "cpu" and batched > 0.9 * seq_wall:
                raise RuntimeError(
                    f"8-stream continuous batching not measurably faster "
                    f"than 8 sequential generate calls "
                    f"({batched:.2f}s vs {seq_wall:.2f}s): the slot loop "
                    "is not amortizing decode steps")
    rows.extend(_anchor_rows(rounds))
    return rows
