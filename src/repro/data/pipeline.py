"""Deterministic synthetic LM data pipeline.

Produces sharded token batches without any external dataset (the container
is offline).  The stream is a reproducible mixture of Zipf-distributed
"vocabulary" draws with short Markov motifs so the LM loss is learnable
(structure exists) but not trivially memorizable.  Supports:

* train batches  {tokens, labels, loss_mask}
* frontend stubs (audio frames / vision patches) keyed off the arch config
* host-sharded iteration: each JAX process materializes only its shard
  (here there is one process; the API mirrors multi-host usage)
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    zipf_a: float = 1.2
    motif_len: int = 8


class SyntheticLM:
    """Deterministic, restartable synthetic token stream."""

    def __init__(self, cfg: DataConfig, arch: ArchConfig | None = None):
        self.cfg = cfg
        self.arch = arch
        rng = np.random.default_rng(cfg.seed)
        v = cfg.vocab_size
        # motif table: each token deterministically suggests a follower, so
        # p(next|cur) has learnable structure
        self._next = rng.integers(0, v, size=(v,), dtype=np.int64)
        ranks = np.arange(1, v + 1, dtype=np.float64)
        p = 1.0 / ranks ** cfg.zipf_a
        self._probs = p / p.sum()

    @functools.partial(jax.profiler.annotate_function, name="data.batch")
    def batch(self, step: int) -> dict:
        """Batch for a given step (stateless — random access by step)."""
        cfg = self.cfg
        rng = np.random.default_rng((cfg.seed, step))
        B, S, v = cfg.global_batch, cfg.seq_len, cfg.vocab_size
        base = rng.choice(v, size=(B, S + 1), p=self._probs)
        # with prob .5 follow the motif instead of fresh draw
        follow = rng.random((B, S)) < 0.5
        toks = base.copy()
        for t in range(1, S + 1):
            toks[:, t] = np.where(follow[:, t - 1],
                                  self._next[toks[:, t - 1]], base[:, t])
        tokens = toks[:, :-1].astype(np.int32)
        labels = toks[:, 1:].astype(np.int32)
        out = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
               "loss_mask": jnp.ones((B, S), jnp.float32)}
        if self.arch is not None and self.arch.frontend:
            if self.arch.frontend == "vision":
                from repro.models import vlm
                out["frontend"] = vlm.make_patches(rng, B, self.arch)
                F = self.arch.frontend_len
                out["loss_mask"] = out["loss_mask"].at[:, :F].set(0.0)
            else:
                from repro.models import whisper
                out["frontend"] = whisper.make_frames(rng, B, self.arch)
        return out

    def __iter__(self):
        step = 0
        while True:
            yield self.batch(step)
            step += 1


@functools.partial(jax.profiler.annotate_function, name="data.shard")
def shard_batch(batch: dict, mesh, batch_axes=("pod", "data")):
    """Place a host batch on the mesh, sharded over the batch axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    spec_b = P(axes if len(axes) > 1 else (axes[0] if axes else None))

    def put(x):
        spec = P(*(spec_b + P(*([None] * (x.ndim - 1)))))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, batch)
