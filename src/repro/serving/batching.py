"""Prefill packing and the slotted KV cache for continuous batching.

``pad_pack`` right-pads a pack of prompts to a fixed (pack, bucket) shape
so every admission round hits the same jit cache entry; ``SlotKVCache``
wraps ``decode_lib.init_cache`` with slot-indexed insert/evict so freed
slots are reused without recompilation (slot ids are traced values, the
shapes never change).  Padded pack rows carry slot id ``num_slots`` —
out of bounds, so JAX scatter semantics drop them on insert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode as decode_lib


def pick_bucket(length: int, buckets) -> int:
    """Smallest right-pad bucket that fits ``length``."""
    for b in sorted(buckets):
        if length <= b:
            return int(b)
    raise ValueError(f"prompt length {length} exceeds the largest prefill "
                     f"bucket {max(buckets)}")


def pad_pack(prompts, pack: int, buckets):
    """Right-pad ``prompts`` (list of 1-D int sequences, len <= pack) to a
    fixed ``[pack, bucket]`` token block.

    Returns ``(tokens [pack, L], lens [pack])`` — padded rows get a
    single-token dummy prompt (lens 1) so downstream gathers at
    ``lens - 1`` stay in bounds; their slot ids are out of range so their
    cache rows are never inserted.
    """
    if len(prompts) > pack:
        raise ValueError(f"pack of {len(prompts)} prompts exceeds width "
                         f"{pack}")
    L = pick_bucket(max((len(p) for p in prompts), default=1), buckets)
    tokens = np.zeros((pack, L), np.int32)
    lens = np.ones((pack,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = np.asarray(p, np.int32)
        lens[i] = len(p)
    return jnp.asarray(tokens), jnp.asarray(lens)


def pad_frontend_pack(frontends, pack: int):
    """Stack per-request frontend arrays (e.g. vision patches) into a
    ``[pack, F, d]`` block, zero-filled for padded rows.  All present
    arrays must share one shape (the arch's ``frontend_len``)."""
    shapes = {tuple(np.asarray(f).shape) for f in frontends if f is not None}
    if len(shapes) != 1:
        raise ValueError(f"frontend arrays disagree on shape: {shapes}")
    F, d = shapes.pop()
    out = np.zeros((pack, F, d), np.float32)
    for i, f in enumerate(frontends):
        if f is not None:
            out[i] = np.asarray(f, np.float32)
    return jnp.asarray(out)


class SlotKVCache:
    """A decode cache with ``num_slots`` batch rows managed as slots, its
    layer groups stacked or, with ``per_layer``, one buffer per layer
    (``decode_lib.unstack_groups``): the layout of the params it serves.

    All three operations are jitted once and reused for the engine's
    lifetime — slot ids are data, not shapes — so admit/evict/re-admit
    cycles never recompile.  ``insert`` and ``evict`` donate the cache they
    replace, so each writes its slots in place: hold ``self.cache``, never
    an earlier value of it.
    """

    def __init__(self, ctx, num_slots: int, cache_len: int, *,
                 per_layer: bool = False):
        self.ctx = ctx
        self.num_slots = int(num_slots)
        self.cache_len = int(cache_len)
        layout = decode_lib.unstack_groups if per_layer else (lambda c: c)
        self.cache = jax.jit(lambda: layout(
            decode_lib.init_cache(ctx, self.num_slots, self.cache_len)))()
        if getattr(ctx, "mesh", None) is not None:
            # match the NamedSharding that prefilled pack caches carry, so
            # the very first insert hits the same jit entry as every later
            # one (SingleDeviceSharding vs NamedSharding keys differently)
            from jax.sharding import NamedSharding, PartitionSpec
            repl = NamedSharding(ctx.mesh, PartitionSpec())
            self.cache = jax.device_put(self.cache, repl)
        # wrap in partials so each instance gets a private tracing cache:
        # jax.jit shares its cache across wrappers of the same callable, so
        # another engine's differently-shaped cache would otherwise leak
        # into this instance's cache stats
        self._insert = jax.jit(
            functools.partial(decode_lib.cache_insert_slots), donate_argnums=0)
        self._evict = jax.jit(
            functools.partial(decode_lib.cache_evict_slots), donate_argnums=0)

    def insert(self, src_cache, slot_ids) -> None:
        """Write a prefilled pack cache into ``slot_ids`` (out-of-range ids
        are dropped — the padded-pack convention)."""
        self.cache = self._insert(self.cache, src_cache,
                                  jnp.asarray(slot_ids, jnp.int32))

    def evict(self, slot_ids) -> None:
        """Zero the cache at ``slot_ids`` (pos included)."""
        self.cache = self._evict(self.cache,
                                 jnp.asarray(slot_ids, jnp.int32))

    def positions(self):
        """Per-slot cache positions [num_slots] (0 = empty/evicted); reads
        the first attention/mla sublayer's ``pos`` leaf."""
        for k in sorted(self.cache):
            leaves = [leaf for path, leaf in
                      jax.tree_util.tree_flatten_with_path(self.cache[k])[0]
                      if str(getattr(path[-1], "key", "")) == "pos"]
            if leaves:
                pos = leaves[0]
                return np.asarray(
                    pos[0] if decode_lib.batch_axis(k, self.cache[k]) else pos)
        raise ValueError("cache has no pos leaf (recurrent-only family)")
