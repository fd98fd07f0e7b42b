"""Decode-time forward: one new token against per-layer caches/states.

Cache pytree mirrors the (prefix, groups) layer plan.  The groups come in
one of two layouts (see ``unstack_groups``): stacked, with a leading
n_groups axis, threaded through ``lax.scan`` alongside the stacked params;
or per-layer, a tuple of per-group trees that a Python loop runs, each
layer on its own buffers.  Params and cache come in one layout.
Mixer-family cache kinds:

    attn  -> KV cache [B, L, K, hd]        (L may be sharded: context parallel)
    mla   -> compressed latent cache [B, L, r] + rope keys
    mamba -> (h, conv) recurrent state     (O(1) per step)
    mlstm -> (C, n, m) matrix memory       (O(1) per step)
    slstm -> (c, n, h, m) scalar memory    (O(1) per step)
    cross -> precomputed encoder K/V       (static during decode)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import sharding
from repro.models import layers, mamba as mamba_lib, mla as mla_lib
from repro.models import xlstm as xlstm_lib
from repro.models.transformer import (ModelCtx, SubLayer, _moe_block,
                                      _overrides_hit_groups, layer_plan)


def _init_sub_cache(sub: SubLayer, batch: int, max_len: int, ctx: ModelCtx):
    c = {}
    if sub.mixer == "attn":
        c["mixer"] = layers.init_kv_cache(batch, max_len, ctx.attn_cfg)
    elif sub.mixer == "mla":
        c["mixer"] = mla_lib.init_mla_cache(batch, max_len, ctx.mla_cfg)
    elif sub.mixer == "mamba":
        c["mixer"] = mamba_lib.init_mamba_state(batch, ctx.mamba_cfg)
    elif sub.mixer == "mlstm":
        c["mixer"] = xlstm_lib.init_mlstm_state(batch, ctx.xlstm_cfg)
    elif sub.mixer == "slstm":
        c["mixer"] = xlstm_lib.init_slstm_state(batch, ctx.xlstm_cfg)
    if sub.cross:
        # encoder K/V filled at prefill; zeros here
        a = ctx.attn_cfg
        enc_len = ctx.arch.frontend_len or 1
        c["cross_k"] = jnp.zeros((batch, enc_len, a.num_kv_heads, a.head_dim),
                                 a.dtype)
        c["cross_v"] = jnp.zeros((batch, enc_len, a.num_kv_heads, a.head_dim),
                                 a.dtype)
    return c


def init_cache(ctx: ModelCtx, batch: int, max_len: int):
    prefix, group, n_groups = layer_plan(ctx.arch)
    cache = {}
    for i, sub in enumerate(prefix):
        cache[f"prefix{i}"] = _init_sub_cache(sub, batch, max_len, ctx)

    def one(_):
        return {f"sub{j}": _init_sub_cache(s, batch, max_len, ctx)
                for j, s in enumerate(group)}
    cache["groups"] = jax.vmap(one)(jnp.arange(n_groups))
    return cache


def fill_cross_cache(params, cache, enc_out, ctx: ModelCtx):
    """Project encoder output into every decoder layer's cross K/V cache."""
    prefix, group, n_groups = layer_plan(ctx.arch)
    a = ctx.attn_cfg
    B, F, _ = enc_out.shape

    def kv(p_cross, stacked: bool):
        eq = "bfd,gdk->gbfk" if stacked else "bfd,dk->bfk"
        k = jnp.einsum(eq, enc_out, p_cross["wk"])
        v = jnp.einsum(eq, enc_out, p_cross["wv"])
        shp = ((n_groups, B, F, a.num_kv_heads, a.head_dim) if stacked
               else (B, F, a.num_kv_heads, a.head_dim))
        return k.reshape(shp), v.reshape(shp)

    cache = jax.tree_util.tree_map(lambda x: x, cache)  # shallow copy
    for i, sub in enumerate(prefix):
        if sub.cross:
            k, v = kv(params[f"prefix{i}"]["cross"], stacked=False)
            cache[f"prefix{i}"]["cross_k"] = k.astype(a.dtype)
            cache[f"prefix{i}"]["cross_v"] = v.astype(a.dtype)
    for j, sub in enumerate(group):
        if not sub.cross:
            continue
        if is_per_layer(params):
            for pg, cg in zip(params["groups"], cache["groups"]):
                k, v = kv(pg[f"sub{j}"]["cross"], stacked=False)
                cg[f"sub{j}"]["cross_k"] = k.astype(a.dtype)
                cg[f"sub{j}"]["cross_v"] = v.astype(a.dtype)
        else:
            k, v = kv(params["groups"][f"sub{j}"]["cross"], stacked=True)
            cache["groups"][f"sub{j}"]["cross_k"] = k.astype(a.dtype)
            cache["groups"][f"sub{j}"]["cross_v"] = v.astype(a.dtype)
    return cache


# ---------------------------------------------------------------------------
# layer layouts and slot-indexed cache ops (continuous-batching serving)
# ---------------------------------------------------------------------------
#
# A params or cache tree holds its scanned layer groups under ``groups`` in
# one of two layouts.  Stacked: every leaf carries a leading n_groups axis,
# and ``lax.scan`` runs the groups (training, ``generate``, the dry-runs).
# Per-layer: a tuple of n_groups per-group trees, every leaf its own
# buffer, and a Python loop runs the groups (``ServingEngine`` for models
# with MoE groups): a Pallas call cannot take a slice of a stack as its
# operand, so a scanned step copies each layer's expert weights out of
# their stacks first.  A call takes params and cache in one layout.
# Top-level ``prefix{i}`` cache entries and per-layer group entries carry
# the batch on axis 0, stacked group entries on axis 1.  The helpers below
# are the only place that layout knowledge lives.


def is_per_layer(tree) -> bool:
    return isinstance(tree["groups"], tuple)


def _nbytes(a) -> int:
    return math.prod(a.shape) * jnp.dtype(a.dtype).itemsize


def unstack_groups(tree, *, donate: bool = False):
    """``tree`` in the per-layer layout: its stacked ``groups`` entry as a
    tuple of n_groups per-group trees (a per-layer tree is returned as it
    is).  Leaves may be traced values, arrays (host arrays land on the
    device) or ``jax.ShapeDtypeStruct`` shapes; arrays and shapes on a mesh
    keep their stacked parent's sharding with the group axis dropped.

    With ``donate``, each stacked device array is deleted as soon as its
    slices exist, as a donated argument is: the leaves are split a few at a
    time, at most the largest leaf's bytes at once, so the stacks and their
    slices never sit whole side by side."""
    groups = tree["groups"]
    if isinstance(groups, tuple):
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(groups)
    n = leaves[0].shape[0]

    def split(xs):
        return [tuple(x[g] for g in range(n)) for x in xs]

    if isinstance(leaves[0], jax.core.Tracer):
        parts = split(leaves)
    else:
        # largest first, so each big leaf goes alone and the small ones
        # share a program
        order = sorted(range(len(leaves)), key=lambda i: -_nbytes(leaves[i]))
        cap = _nbytes(leaves[order[0]])
        chunks, size = [], cap
        for i in order:
            if size + _nbytes(leaves[i]) > cap:
                chunks.append([])
                size = 0
            chunks[-1].append(i)
            size += _nbytes(leaves[i])
        parts = [None] * len(leaves)
        for chunk in chunks:
            xs = [leaves[i] for i in chunk]
            out = None              # placed where the inputs are
            if all(isinstance(getattr(x, "sharding", None), NamedSharding)
                   for x in xs):
                out = [(NamedSharding(x.sharding.mesh,
                                      PartitionSpec(*x.sharding.spec[1:])),)
                       * n for x in xs]
            fn = jax.jit(split, out_shardings=out)
            if isinstance(xs[0], jax.ShapeDtypeStruct):
                got = fn.eval_shape(xs)
            else:
                got = fn(xs)
                if donate:
                    jax.block_until_ready(got)
                    for x in xs:
                        if isinstance(x, jax.Array):
                            x.delete()
            for i, g in zip(chunk, got):
                parts[i] = g
    return dict(tree, groups=tuple(
        treedef.unflatten([p[g] for p in parts]) for g in range(n)))


def stack_groups(tree):
    """The inverse of ``unstack_groups``: ``tree`` in the stacked layout."""
    groups = tree["groups"]
    if not isinstance(groups, tuple):
        return tree
    return dict(tree, groups=jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *groups))


def batch_axis(key, entry) -> int:
    """The batch axis of the leaves of a cache's top-level ``entry``."""
    return 1 if key == "groups" and not isinstance(entry, tuple) else 0


def _map_batch_axis(cache, fn, *others):
    """``fn(leaf, *other_leaves, batch_axis)`` across the cache pytree;
    ``others`` are trees of the same structure."""
    return {k: jax.tree_util.tree_map(
                lambda *xs, ax=batch_axis(k, v): fn(*xs, ax),
                v, *(o[k] for o in others))
            for k, v in cache.items()}


def cache_insert_slots(dst, src, slots):
    """Write ``src`` (leading batch P) into ``dst`` (leading batch N, the
    same layer layout) at ``slots`` [P].  Slot ids >= N are dropped (JAX
    scatter out-of-bounds semantics), which is how padded admission packs
    no-op: pad ``slots`` with N and the extra rows never land."""
    def ins(d, s, axis):
        idx = (slice(None),) * axis + (slots,)
        return d.at[idx].set(s.astype(d.dtype))
    return _map_batch_axis(dst, ins, src)


def cache_evict_slots(cache, slots):
    """Zero every cache leaf at ``slots`` (pos included, so the slot reads
    as empty).  Not required before re-insertion — ``cache_insert_slots``
    overwrites a slot completely — but keeps freed slots inert and is the
    eviction half of the serving API."""
    def ev(leaf, axis):
        idx = (slice(None),) * axis + (slots,)
        return leaf.at[idx].set(jnp.zeros((), leaf.dtype))
    return _map_batch_axis(cache, ev)


def _select_batch(mask, new, old):
    """Per-request select between two cache pytrees of one layout: ``mask``
    [B] picks ``new`` where True.  Used by the scan prefill to freeze a
    request's cache once its (right-padded) prompt is exhausted."""
    def sel(n, o, axis):
        shape = [1] * n.ndim
        shape[axis] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n, o)
    return _map_batch_axis(new, sel, old)


@jax.named_scope("layer")
def _decode_sublayer(p, c, x, sub: SubLayer, ctx: ModelCtx, layer_idx=None):
    a = ctx.arch
    h = layers.norm_apply(p["norm1"], x, a.norm)
    if sub.mixer == "attn":
        mix, c["mixer"] = layers.attn_decode(p["mixer"], h, c["mixer"],
                                             ctx.attn_cfg)
    elif sub.mixer == "mla":
        mix, c["mixer"] = mla_lib.mla_decode(p["mixer"], h, c["mixer"],
                                             ctx.mla_cfg)
    elif sub.mixer == "mamba":
        mix, c["mixer"] = mamba_lib.mamba_decode(p["mixer"], h, c["mixer"],
                                                 ctx.mamba_cfg)
    elif sub.mixer == "mlstm":
        mix, c["mixer"] = xlstm_lib.mlstm_decode(p["mixer"], h, c["mixer"],
                                                 ctx.xlstm_cfg)
    elif sub.mixer == "slstm":
        mix, c["mixer"] = xlstm_lib.slstm_decode(p["mixer"], h, c["mixer"],
                                                 ctx.xlstm_cfg)
    x = x + mix
    if sub.cross:
        h = layers.norm_apply(p["norm_cross"], x, a.norm)
        B = x.shape[0]
        cfg = ctx.attn_cfg
        q = (h @ p["cross"]["wq"]).reshape(B, 1, cfg.num_heads, cfg.head_dim)
        out = layers._sdpa(q, c["cross_k"], c["cross_v"], causal=False,
                           sliding_window=0, q_positions=jnp.zeros((1,), int),
                           k_positions=jnp.arange(c["cross_k"].shape[1]))
        x = x + out.reshape(B, 1, -1) @ p["cross"]["wo"]
    if sub.ffn == "mlp":
        h = layers.norm_apply(p["norm2"], x, a.norm)
        x = x + layers.mlp_apply(p["ffn"], h, a.activation)
    elif sub.ffn == "moe":
        h = layers.norm_apply(p["norm2"], x, a.norm)
        y, _ = _moe_block(p["ffn"], h, ctx, decode=True, layer_idx=layer_idx)
        x = x + y
    return x, c


def _group_loop(params, cache, x, ctx: ModelCtx, sublayer):
    """Run the layer groups: ``sublayer(p, c, x, sub, layer_idx)`` returns
    ``(x, c)``.  Per-layer params and cache run a Python loop over each
    group's own buffers, and so do stacked ones under a dispatch override
    that lands inside the groups (each layer then has its own dispatch);
    stacked ones otherwise keep ``lax.scan``.  Returns ``(x, groups)``, the
    new group caches in the layout they came in."""
    prefix, group, n_groups = layer_plan(ctx.arch)
    n_prefix = len(prefix)
    per_layer = is_per_layer(params)
    # ops of the loop itself, outside every sublayer's ``layer`` scope, are
    # ``layer.params``: under ``lax.scan``, the slices of each layer's
    # parameters and cache out of their stacks and the cache written back
    with jax.named_scope("layer.params"):
        if per_layer or _overrides_hit_groups(
                ctx, n_prefix, group, n_groups, decode=True):
            ps = unstack_groups(params)["groups"]
            new = []
            for g, c in enumerate(unstack_groups(cache)["groups"]):
                c = dict(c)
                for j, sub in enumerate(group):
                    x, c[f"sub{j}"] = sublayer(
                        ps[g][f"sub{j}"], dict(c[f"sub{j}"]), x, sub,
                        n_prefix + g * len(group) + j)
                new.append(c)
            groups = tuple(new)
            if not per_layer:
                groups = stack_groups({"groups": groups})["groups"]
            return x, groups

        def body(x, pc):
            p, c = pc
            c = dict(c)
            for j, sub in enumerate(group):
                x, c[f"sub{j}"] = sublayer(
                    p[f"sub{j}"], dict(c[f"sub{j}"]), x, sub, None)
            return x, c

        return jax.lax.scan(body, x, (params["groups"], cache["groups"]))


def decode_step(params, cache, tokens, ctx: ModelCtx):
    """tokens: [B, 1] — returns (logits [B, 1, V], new_cache)."""
    a = ctx.arch
    prefix = layer_plan(a)[0]
    x = layers.embed_apply(params["embed"], tokens)
    if not ctx.decode_replicated:
        x = sharding.constrain(x, "batch", None, None)

    new_cache = {}
    for i, sub in enumerate(prefix):
        x, new_cache[f"prefix{i}"] = _decode_sublayer(
            params[f"prefix{i}"], dict(cache[f"prefix{i}"]), x, sub, ctx,
            layer_idx=i)

    x, new_cache["groups"] = _group_loop(
        params, cache, x, ctx,
        lambda p, c, x, sub, i: _decode_sublayer(p, c, x, sub, ctx,
                                                 layer_idx=i))
    x = layers.norm_apply(params["final_norm"], x, a.norm)
    logits = layers.unembed_apply(params["embed"], x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# fused prefill: full-sequence forward that materializes the decode cache
# ---------------------------------------------------------------------------


def _needs_scan_prefill(arch) -> bool:
    """Recurrent mixers (mamba/xlstm) and cross-attention decoders carry
    per-step state the full-sequence applies do not expose, so those
    families prefill by scanning ``decode_step`` (still one fused XLA call,
    just sequential over time)."""
    prefix, group, _ = layer_plan(arch)
    return any(sub.mixer not in ("attn", "mla") or sub.cross
               for sub in list(prefix) + list(group))


@jax.named_scope("layer")
def _prefill_sublayer(p, c, x, sub: SubLayer, ctx: ModelCtx, lens,
                      layer_idx=None):
    """Full-sequence sublayer forward that also writes the decode cache:
    K/V (attn) or the compressed latent entries (mla) for positions
    [0, S), with ``pos`` set to each request's true prompt length so
    right-padded rows are never attended."""
    a = ctx.arch
    S = x.shape[1]
    h = layers.norm_apply(p["norm1"], x, a.norm)
    if sub.mixer == "attn":
        mix, (k, v) = layers.attn_apply(p["mixer"], h, ctx.attn_cfg)
        c["mixer"] = {
            "k": jnp.asarray(c["mixer"]["k"]).at[:, :S].set(
                k.astype(c["mixer"]["k"].dtype)),
            "v": jnp.asarray(c["mixer"]["v"]).at[:, :S].set(
                v.astype(c["mixer"]["v"].dtype)),
            "pos": lens,
        }
    elif sub.mixer == "mla":
        mix, entry = mla_lib.mla_apply(p["mixer"], h, ctx.mla_cfg)
        c["mixer"] = {
            "c_kv": jnp.asarray(c["mixer"]["c_kv"]).at[:, :S].set(
                entry["c_kv"].astype(c["mixer"]["c_kv"].dtype)),
            "k_rope": jnp.asarray(c["mixer"]["k_rope"]).at[:, :S].set(
                entry["k_rope"].astype(c["mixer"]["k_rope"].dtype)),
            "pos": lens,
        }
    else:  # _needs_scan_prefill routes recurrent mixers away from here
        raise ValueError(f"fused prefill cannot cache mixer {sub.mixer!r}")
    x = x + mix
    if sub.ffn == "mlp":
        h = layers.norm_apply(p["norm2"], x, a.norm)
        x = x + layers.mlp_apply(p["ffn"], h, a.activation)
    elif sub.ffn == "moe":
        # decode=True: the weights-stationary gather path computes every
        # token independently (no capacity drops), so a packed prefill is
        # exactly equivalent to prefilling each request alone — the
        # property the continuous-batching tests pin.
        h = layers.norm_apply(p["norm2"], x, a.norm)
        y, _ = _moe_block(p["ffn"], h, ctx, decode=True, layer_idx=layer_idx)
        x = x + y
    return x, c


def _prefill_by_scan(params, batch, cache, ctx: ModelCtx, lens):
    """Prefill fallback for recurrent/cross families: one ``lax.scan`` of
    ``decode_step`` over the prompt.  Per-request cache updates freeze once
    t >= lens[b], so right padding cannot corrupt recurrent state."""
    tokens = batch["tokens"]
    B, S = tokens.shape

    def body(carry, inp):
        cache, last = carry
        tok, t = inp
        logits, new_cache = decode_step(params, cache, tok[:, None], ctx)
        active = t < lens
        cache = _select_batch(active, new_cache, cache)
        last = jnp.where((t == lens - 1)[:, None], logits[:, 0], last)
        return (cache, last), None

    last0 = jnp.zeros((B, ctx.arch.vocab_size), jnp.float32)
    (cache, last), _ = jax.lax.scan(
        body, (cache, last0), (tokens.T, jnp.arange(S)))
    return last, cache


def prefill(params, batch, ctx: ModelCtx, *, cache_len: int, lens=None):
    """Fused prefill: full-sequence forward over right-padded prompts that
    materializes the decode cache in one pass.

    batch: {"tokens": [B, S], optional "frontend"}; ``lens`` [B] gives each
    request's true prompt length (default S).  Returns
    ``(last_logits [B, V], cache)`` — the logits at each request's final
    prompt position (the distribution of its first generated token) and a
    cache of length ``cache_len`` with ``pos == lens``.

    Attention/MLA families run the parallel forward and write K/V (or the
    compressed latents) directly; recurrent and cross-attention families
    fall back to a scanned ``decode_step`` (see ``_needs_scan_prefill``).
    MoE sublayers go through the decode-default ``gather`` path, which is
    drop-free and per-token independent — a packed prefill therefore
    equals a sequence of single-request prefills.
    """
    a = ctx.arch
    tokens = batch["tokens"]
    B, S = tokens.shape
    if S > cache_len:
        raise ValueError(f"prompt length {S} exceeds cache_len {cache_len}")
    if lens is None:
        lens = jnp.full((B,), S, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    cache = init_cache(ctx, B, cache_len)
    if is_per_layer(params):
        cache = unstack_groups(cache)

    if a.family == "audio" and "frontend" in batch:
        from repro.models.transformer import _run_encoder
        enc_out = _run_encoder(params, batch["frontend"].astype(a.jnp_dtype),
                               ctx)
        cache = fill_cross_cache(params, cache, enc_out, ctx)

    if _needs_scan_prefill(a):
        return _prefill_by_scan(params, batch, cache, ctx, lens)

    prefix = layer_plan(a)[0]
    x = layers.embed_apply(params["embed"], tokens)
    if a.family == "vlm" and "frontend" in batch:
        patches = jax.nn.gelu(batch["frontend"].astype(x.dtype)
                              @ params["proj"]["w1"]) @ params["proj"]["w2"]
        n = patches.shape[1]
        x = jnp.concatenate([patches, x[:, n:]], axis=1)
    if not ctx.decode_replicated:
        x = sharding.constrain(x, "batch", None, None)

    new_cache = {}
    for i, sub in enumerate(prefix):
        x, new_cache[f"prefix{i}"] = _prefill_sublayer(
            params[f"prefix{i}"], dict(cache[f"prefix{i}"]), x, sub, ctx,
            lens, layer_idx=i)

    x, new_cache["groups"] = _group_loop(
        params, cache, x, ctx,
        lambda p, c, x, sub, i: _prefill_sublayer(p, c, x, sub, ctx, lens,
                                                  layer_idx=i))

    x = layers.norm_apply(params["final_norm"], x, a.norm)
    logits = layers.unembed_apply(params["embed"], x)
    last = jnp.take_along_axis(
        logits.astype(jnp.float32),
        jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    return last, new_cache
