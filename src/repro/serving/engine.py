"""Serving engine: fused prefill, decode steps, and continuous batching.

Three layers, lowest first:

- ``make_prefill`` / ``make_decode_step`` — jittable single-call entries
  (what the decode_32k / long_500k dry-runs lower).  ``make_prefill`` with
  ``with_cache=True`` runs the fused full-sequence forward *and*
  materializes the decode cache in one pass (``models/decode.prefill``).
- ``generate`` — the single-batch driver: one fused prefill, then one
  decode step per generated token.
- ``ServingEngine`` — slot-based continuous batching (MLPerf-offline
  style): a ``Scheduler`` admits requests from a queue into a fixed pool
  of decode slots, admission packs prefill through the fused path and are
  inserted into a ``SlotKVCache``, and every decode step advances all
  occupied slots at once.  Shapes are static everywhere (fixed pack width,
  fixed bucketed prompt pads, fixed slot count), so admit/evict/re-admit
  cycles never recompile.  See docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.models import decode as decode_lib, model as model_lib
from repro.models import transformer
from repro.serving import batching
from repro.serving.scheduler import Request, Scheduler

#: A host span in the profiler's trace; costs about a microsecond when no
#: profiler runs.
_span = jax.profiler.TraceAnnotation


def _with_overrides(ctx: transformer.ModelCtx, dispatch_override):
    """Serving-side per-layer dispatch override (e.g. force a prefill MoE
    layer onto ``a2a_pipelined``, or a decode layer off the gather path).
    Names resolve through the core.dispatch engine registry; entries merge
    per layer index with the ctx's existing (arch/run-level) overrides,
    serving-side entries winning.  Plans are level-indexed, so overrides
    behave identically on 2-level and N-level meshes — chunk alignment
    rounds every stage capacity of the ctx's ``DispatchPlan``."""
    if dispatch_override is None:
        return ctx
    from repro.core import capacity, dispatch as dispatch_lib
    for _, name in dispatch_override:
        dispatch_lib.get_path(name)
    merged = dict(ctx.dispatch_override)
    merged.update(dict(dispatch_override))
    ctx = dataclasses.replace(ctx,
                              dispatch_override=tuple(sorted(merged.items())))
    # a pipelined override needs a resolved chunk count + chunk-aligned
    # plan; build_ctx does this for overrides it saw, so only fill the gap
    if (ctx.plan is not None and ctx.a2a_num_chunks <= 1
            and any(n == "a2a_pipelined" for _, n in ctx.dispatch_override)):
        nc = model_lib.resolve_num_chunks(ctx.arch, ctx.plan, ctx.ep, 0)
        ctx = dataclasses.replace(
            ctx, a2a_num_chunks=nc,
            plan=capacity.align_to_chunks(ctx.plan, nc))
    return ctx


def _rules_cm(ctx):
    import contextlib
    rules = model_lib.default_rules(ctx.mesh) if ctx.mesh else None
    return sharding.axis_rules(rules) if rules else contextlib.nullcontext()


def make_decode_step(ctx: transformer.ModelCtx, dispatch_override=None):
    ctx = _with_overrides(ctx, dispatch_override)

    def step(params, cache, tokens):
        with _rules_cm(ctx):
            logits, new_cache = decode_lib.decode_step(params, cache,
                                                       tokens, ctx)
        return logits, new_cache
    return step


def make_prefill(ctx: transformer.ModelCtx, dispatch_override=None, *,
                 with_cache: bool = False, cache_len: int | None = None):
    """Fused full-sequence prefill.

    Default (``with_cache=False``): ``prefill(params, batch) ->
    last_logits`` — the logits-only entry the dry-runs lower.

    ``with_cache=True`` (requires ``cache_len``): ``prefill(params, batch)
    -> (last_logits [B, V], cache)`` where ``batch`` is ``{"tokens":
    [B, S], optional "lens" [B], optional "frontend"}``.  The cache is
    materialized from the same forward (K/V for attention, compressed
    latents for MLA; recurrent families scan — see
    ``models/decode.prefill``), with per-request positions set to ``lens``
    so right-padded prompt packs behave exactly like unpadded ones.
    """
    ctx = _with_overrides(ctx, dispatch_override)

    if with_cache:
        if cache_len is None:
            raise ValueError("with_cache=True requires cache_len")

        def prefill_cached(params, batch):
            with _rules_cm(ctx):
                return decode_lib.prefill(params, batch, ctx,
                                          cache_len=cache_len,
                                          lens=batch.get("lens"))
        return prefill_cached

    def prefill(params, batch):
        with _rules_cm(ctx):
            logits, _ = transformer.forward(params, batch, ctx)
        return logits[:, -1]
    return prefill


def _make_sample():
    """Jitted per-row sampler: greedy where temperature <= 0, categorical
    at ``logits / temperature`` elsewhere.  logits [N, V], temps [N]."""
    def sample(logits, temps, key):
        lf = logits.astype(jnp.float32)
        greedy = jnp.argmax(lf, axis=-1)
        scaled = lf / jnp.maximum(temps, 1e-6)[:, None]
        drawn = jax.random.categorical(key, scaled)
        return jnp.where(temps > 0, drawn, greedy).astype(jnp.int32)
    return sample


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray          # [B, steps]
    steps_per_sec: float


def make_generate_fns(ctx: transformer.ModelCtx, cache_len: int):
    """The jitted (prefill, decode_step, sample) triple ``generate`` runs.
    Build once and pass as ``generate(..., fns=...)`` when issuing many
    sequential calls — each bare ``generate`` call otherwise re-jits its
    own closures (fresh function identity, fresh jit cache)."""
    return (jax.jit(make_prefill(ctx, with_cache=True, cache_len=cache_len)),
            jax.jit(make_decode_step(ctx)),
            jax.jit(_make_sample()))


def generate(params, ctx: transformer.ModelCtx, prompt_tokens, *,
             steps: int, cache_len: int, temperature: float = 0.0,
             seed: int = 0, frontend=None, lens=None,
             fns=None) -> GenerationResult:
    """Greedy/temperature generation driver for the serving example.

    The prompt goes through the fused ``make_prefill`` path (one
    full-sequence forward that also materializes the cache); only the
    ``steps`` generated tokens run ``decode_step``.  ``steps_per_sec``
    counts generated tokens only — prompt positions are prefill work, not
    decode steps.  ``lens`` optionally marks per-row true prompt lengths
    when ``prompt_tokens`` is right-padded.
    """
    B, S = prompt_tokens.shape
    prefill_fn, step_fn, sample_fn = (
        fns if fns is not None else make_generate_fns(ctx, cache_len))
    temps = jnp.full((B,), temperature, jnp.float32)
    batch = {"tokens": prompt_tokens,
             "lens": (jnp.asarray(lens, jnp.int32) if lens is not None
                      else jnp.full((B,), S, jnp.int32))}
    if frontend is not None:
        batch["frontend"] = frontend
    key = jax.random.PRNGKey(seed)
    t0 = time.time()
    logits, cache = prefill_fn(params, batch)
    key, sub = jax.random.split(key)
    tok = sample_fn(logits, temps, sub)[:, None]
    out = [tok]
    for _ in range(steps - 1):
        logits, cache = step_fn(params, cache, tok)
        key, sub = jax.random.split(key)
        tok = sample_fn(logits[:, 0], temps, sub)[:, None]
        out.append(tok)
    tokens = jnp.concatenate(out, axis=1)
    tokens.block_until_ready()
    dt = time.time() - t0
    return GenerationResult(tokens=tokens,
                            steps_per_sec=steps / max(dt, 1e-9))


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static shapes of the continuous-batching engine.

    ``num_slots`` decode slots run every step; admission packs are
    ``prefill_pack`` wide with prompts right-padded to the smallest
    ``prompt_buckets`` entry that fits (one jit entry per bucket used).
    Every admitted request must satisfy
    ``prompt_len + max_new_tokens <= cache_len``.
    """
    num_slots: int = 8
    cache_len: int = 128
    prefill_pack: int = 4
    prompt_buckets: tuple = (32,)


@dataclasses.dataclass
class ServingReport:
    streams: list                  # finished Stream records, completion order
    wall_time: float
    total_new_tokens: int
    decode_steps: int
    prefill_calls: int
    evictions: int = 0             # deadline-evicted streams (partial
                                   # outputs stay on `streams`, flagged)

    @property
    def tokens_per_sec(self) -> float:
        return self.total_new_tokens / max(self.wall_time, 1e-9)

    def tokens_for(self, uid: int):
        for s in self.streams:
            if s.request.uid == uid:
                return s.generated
        raise KeyError(uid)


def _moe_groups(arch) -> bool:
    """Whether ``arch``'s layer groups hold an MoE sub-layer.  A scanned
    step copies each such layer's expert stacks out for the Pallas expert
    kernel, which cannot take a slice of a stack as its operand, so the
    engine serves these models per layer; other models keep the scan,
    whose program does not grow with depth."""
    return any(sub.ffn == "moe" for sub in transformer.layer_plan(arch)[1])


class ServingEngine:
    """Slot-based continuous batching over the MoE decode path.

    One engine owns the jitted prefill/decode/sample functions and a
    ``SlotKVCache``; ``run`` drains a list of requests through the
    scheduler.  The loop per iteration: (1) admit pending requests into
    free slots and prefill them as one fused pack, (2) advance every slot
    one decode step, (3) complete streams that hit their budget, freeing
    their slots for the next admission round.

    A model whose layer groups hold an MoE sub-layer is served from one
    buffer per layer (``decode_lib.unstack_groups``): the engine consumes
    stacked ``params``, deleting each stacked group array once its
    per-layer slices exist, so the weights are never held twice.  A caller
    that goes on using ``params`` passes ``unstack_groups(params)``, which
    the engine takes as it is.  The decode program donates the slot cache:
    a cache handed to it is consumed, and the one it returns replaces it.
    """

    def __init__(self, params, ctx: transformer.ModelCtx, cfg: ServeConfig,
                 dispatch_override=None):
        if _moe_groups(ctx.arch):
            params = decode_lib.unstack_groups(params, donate=True)
        self.params = params
        self.ctx = _with_overrides(ctx, dispatch_override)
        self.cfg = cfg
        if max(cfg.prompt_buckets) > cfg.cache_len:
            raise ValueError("prompt bucket exceeds cache_len")
        self._prefill = jax.jit(make_prefill(
            self.ctx, with_cache=True, cache_len=cfg.cache_len))
        self._decode = jax.jit(make_decode_step(self.ctx), donate_argnums=1)
        self._sample = jax.jit(_make_sample())
        # current token per slot, scatter-updated at admission; padded pack
        # rows carry slot id == num_slots and are dropped (OOB scatter)
        self._scatter = jax.jit(
            lambda cur, slots, toks: cur.at[slots, 0].set(toks))

    def _admit(self, sched, kv, cur, temps, key, now):
        cfg = self.cfg
        with _span("serve.admit") as span:
            admits = sched.take(cfg.prefill_pack, now=now)
            if not admits:
                return cur, key, 0
            if _span.is_enabled():      # only while a profiler records
                span.set_metadata(
                    uids=",".join(str(r.uid) for _, r in admits),
                    slots=",".join(str(s) for s, _ in admits))
            for _, req in admits:
                need = req.prompt_len + req.max_new_tokens
                if need > cfg.cache_len:
                    raise ValueError(
                        f"request {req.uid}: prompt+new tokens {need} exceed "
                        f"cache_len {cfg.cache_len}")
            tokens, lens = batching.pad_pack([req.tokens for _, req in admits],
                                             cfg.prefill_pack,
                                             cfg.prompt_buckets)
            batch = {"tokens": tokens, "lens": lens}
            if any(req.frontend is not None for _, req in admits):
                batch["frontend"] = batching.pad_frontend_pack(
                    [req.frontend for _, req in admits], cfg.prefill_pack)
            logits, pack_cache = self._prefill(self.params, batch)
            slots = np.full((cfg.prefill_pack,), cfg.num_slots, np.int32)
            slots[:len(admits)] = [s for s, _ in admits]
            slots = jnp.asarray(slots)
            kv.insert(pack_cache, slots)
            pack_temps = np.zeros((cfg.prefill_pack,), np.float32)
            for i, (s, req) in enumerate(admits):
                temps[s] = req.temperature
                pack_temps[i] = req.temperature
            key, sub = jax.random.split(key)
            first = self._sample(logits, jnp.asarray(pack_temps), sub)
            cur = self._scatter(cur, slots, first)
            with _span("serve.admit.wait"):
                first = np.asarray(first)
            t_first = time.time()
            for i, (s, _) in enumerate(admits):
                sched.stream(s).t_first_token = t_first
                if sched.on_token(s, int(first[i])):
                    sched.complete(s, now=time.time())
            return cur, key, 1

    @functools.partial(jax.profiler.annotate_function, name="serve.run")
    def run(self, requests, *, seed: int = 0) -> ServingReport:
        """Serve ``requests`` to completion; returns per-stream stats.

        The loop writes profiler spans: ``serve.run`` around the call, and
        per iteration ``serve.step`` around ``serve.admit``,
        ``serve.decode`` and ``serve.tokens``, with ``*.wait`` where the
        host blocks on a device result (see docs/serving.md)."""
        cfg = self.cfg
        sched = Scheduler(cfg.num_slots)
        for req in requests:
            sched.submit(req)
        kv = batching.SlotKVCache(
            self.ctx, cfg.num_slots, cfg.cache_len,
            per_layer=decode_lib.is_per_layer(self.params))
        cur = jnp.zeros((cfg.num_slots, 1), jnp.int32)
        temps = np.zeros((cfg.num_slots,), np.float32)
        key = jax.random.PRNGKey(seed)
        decode_steps = prefill_calls = evictions = 0
        t0 = time.time()
        while sched.has_work:
            with _span("serve.step"):
                cur, key, n_pre = self._admit(sched, kv, cur, temps, key,
                                              now=time.time())
                prefill_calls += n_pre
                # deadline sweep: evict overdue streams mid-decode — their
                # KV rows are zeroed and the freed slots return to the pool
                # for the next admission round (one stuck stream can't
                # wedge the engine)
                now = time.time()
                overdue = sched.expired(now)
                if overdue:
                    kv.evict(overdue)
                    for slot in overdue:
                        sched.evict(slot, now=now)
                    evictions += len(overdue)
                if not sched.num_active:
                    continue    # everything admitted finished at 1 token
                with _span("serve.decode"):
                    logits, kv.cache = self._decode(self.params, kv.cache,
                                                    cur)
                    key, sub = jax.random.split(key)
                    nxt = self._sample(logits[:, 0], jnp.asarray(temps), sub)
                    cur = nxt[:, None]
                decode_steps += 1
                with _span("serve.decode.wait"):
                    nxt_host = np.asarray(nxt)
                with _span("serve.tokens"):
                    for slot in sched.active_slots():
                        if sched.on_token(slot, int(nxt_host[slot])):
                            sched.complete(slot, now=time.time())
        wall = time.time() - t0
        total = sum(len(s.generated) for s in sched.finished)
        return ServingReport(streams=sched.finished, wall_time=wall,
                             total_new_tokens=total,
                             decode_steps=decode_steps,
                             prefill_calls=prefill_calls,
                             evictions=evictions)


__all__ = ["GenerationResult", "Request", "ServeConfig", "ServingEngine",
           "ServingReport", "generate", "make_decode_step",
           "make_generate_fns", "make_prefill"]
