"""MoE gates and auxiliary losses: load-balance (Eq. 1), TA-MoE topology loss
(Eq. 8), and the FasterMoE-style compulsory-ratio baseline.

Everything here is per-shard math designed to run inside ``shard_map`` over
the expert-parallel mesh axes; callers psum/pmean the returned metrics.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GateConfig:
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_mode: str = "lb"          # "lb" (Eq 1) | "ta" (Eq 8) | "hir" | "none"
    aux_weight: float = 1.0       # paper uses 1.0
    # normalized per-level penalties p (level 0=self, 1=intra-pod, 2=inter-pod),
    # produced by core.topology.penalty_weights on the level-constant c_hat.
    penalty_by_level: tuple = (1.0, 1.0, 1.0)
    # hir: additive logit bias toward intra-pod experts (compulsory preference)
    hir_bias: float = 2.0
    router_dtype: jnp.dtype = jnp.float32


def init_gate_params(key, d_model: int, cfg: GateConfig):
    scale = 1.0 / np.sqrt(d_model)
    return {"w": jax.random.normal(key, (d_model, cfg.num_experts),
                                   dtype=jnp.float32) * scale}


def expert_levels_nd(num_experts: int, experts_per_rank: int,
                     axis_sizes, my_coords) -> jnp.ndarray:
    """Topology level of each global expert relative to this rank (N-level).

    ``axis_sizes`` are the EP mesh extents outermost-first and
    ``my_coords`` this rank's matching coordinates.  Expert ``e`` lives on
    EP rank ``e // experts_per_rank`` with outermost-major rank order.
    Returns int array [N]: 0 = my own experts, ``n_axes - i`` when the
    owning rank first differs from mine at axis ``i`` — i.e. 1 = innermost
    neighbours, ``n_axes`` = across the root switch.
    """
    sizes = tuple(int(s) for s in axis_sizes)
    n = len(sizes)
    e = jnp.arange(num_experts)
    rank = e // experts_per_rank
    lvl = jnp.zeros_like(rank)
    stride = 1
    for i in range(n - 1, -1, -1):
        c = (rank // stride) % sizes[i]
        lvl = jnp.maximum(lvl, jnp.where(c != my_coords[i], n - i, 0))
        stride *= sizes[i]
    return lvl


def expert_levels(num_experts: int, experts_per_rank: int, ep_per_pod: int,
                  num_pods: int, my_pod, my_data) -> jnp.ndarray:
    """Deprecated 2-level wrapper over :func:`expert_levels_nd`.

    Returns int array [N]: 0 = my own experts, 1 = same pod, 2 = other pod.
    """
    if num_pods > 1:
        return expert_levels_nd(num_experts, experts_per_rank,
                                (num_pods, ep_per_pod), (my_pod, my_data))
    return expert_levels_nd(num_experts, experts_per_rank,
                            (ep_per_pod,), (my_data,))


def gate_forward(params, x, cfg: GateConfig, levels: jnp.ndarray | None):
    """Compute router probabilities and top-k selection.

    x: [T, d] local tokens. Returns dict with probs [T, N], topk_idx [T, k],
    topk_weight [T, k] (combine weights), logits.
    """
    logits = (x.astype(cfg.router_dtype)
              @ params["w"].astype(cfg.router_dtype))  # [T, N]
    if cfg.aux_mode == "hir" and levels is not None:
        # FasterMoE-style compulsory preference: bias the gate toward
        # low-level (near) experts.  This is the accuracy-damaging hard
        # mechanism TA-MoE replaces with a loss.
        logits = logits + jnp.where(levels <= 1, cfg.hir_bias, 0.0)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_weight, topk_idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.top_k > 1:
        # GShard-style renormalization of the selected experts' weights
        topk_weight = topk_weight / (topk_weight.sum(-1, keepdims=True) + 1e-9)
    return {"logits": logits, "probs": probs,
            "topk_idx": topk_idx, "topk_weight": topk_weight}


def dispatch_fractions(topk_idx, num_experts: int) -> jnp.ndarray:
    """c_e / (k*S): fraction of assignments routed to each expert. [N]"""
    one_hot = jax.nn.one_hot(topk_idx, num_experts,
                             dtype=jnp.float32)  # [T, k, N]
    counts = one_hot.sum(axis=(0, 1))  # [N]
    total = topk_idx.shape[0] * topk_idx.shape[1]
    return counts / total


def frac_by_level(frac, levels, num_stages: int) -> jnp.ndarray:
    """Aggregate per-expert dispatch fractions into per-stage fractions.

    Stage ``s`` serves topology level ``s + 1``; level 0 (self) is folded
    into stage 0, matching the capacity-plan convention.  Returns a fixed
    ``[num_stages]`` vector summing to 1 — the uniform ``frac_by_level``
    metric of the dispatch engine.
    """
    stage = jnp.clip(levels - 1, 0, num_stages - 1)
    onehot = jax.nn.one_hot(stage, num_stages, dtype=jnp.float32)   # [N, S]
    return jnp.einsum("ns,n->s", onehot, frac.astype(jnp.float32))


def frac_self(frac, levels) -> jnp.ndarray:
    """Share of the assignments routed to this rank's own experts (level
    0), which :func:`frac_by_level` folds into stage 0."""
    return jnp.sum(jnp.where(levels == 0, frac.astype(jnp.float32), 0.0))


def aux_loss(gate_out, cfg: GateConfig,
             levels: jnp.ndarray | None = None) -> jnp.ndarray:
    """Auxiliary loss for this shard's tokens.

    lb (Eq. 1):  l_aux  = N * sum_e m_e * f_e
    ta (Eq. 8):  l_topo = N * sum_e p_e * m_e * f_e   with p from the
                 topology plan (normalized to mean 1, so magnitudes match —
                 the paper's N*P factor against its un-normalized p).
    hir:         same as lb (the compulsory mechanism lives in the gate bias
                 and the capacity plan, mirroring FasterMoE).
    """
    if cfg.aux_mode == "none":
        return jnp.asarray(0.0, jnp.float32)
    probs = gate_out["probs"]
    m = probs.mean(axis=0)                                    # m_e  [N]
    f = dispatch_fractions(gate_out["topk_idx"], cfg.num_experts)  # f_e [N]
    if cfg.aux_mode == "ta":
        assert levels is not None, "ta aux loss needs expert levels"
        pen = jnp.asarray(cfg.penalty_by_level, jnp.float32)[levels]  # [N]
        return cfg.num_experts * jnp.sum(pen * m * f)
    return cfg.num_experts * jnp.sum(m * f)


def ta_penalties(ratios: tuple, norm: str = "sum",
                 level_sizes: tuple | None = None) -> tuple:
    """Per-level penalty weights p_l = Norm(1/c_hat_l) (Eq. 8).

    ``ratios`` are the per-level capacity multipliers from
    core.topology.per_level_ratios (level-constant c_hat, up to a common
    factor).  Normalization is the *population* mean over experts — slow
    levels contain many more experts, so we weight by level sizes when
    provided.
    """
    inv = np.array([1.0 / max(r, 1e-9) for r in ratios], dtype=np.float64)

    def _pop_mean(v):
        if level_sizes is not None:
            w = np.asarray(level_sizes, dtype=np.float64)
            return float((v * w).sum() / max(w.sum(), 1.0))
        return float(v.mean())

    p = inv / max(_pop_mean(inv), 1e-12)
    if norm == "softmax":
        # softmax reweighting of the mean-normalized inverse capacities,
        # rescaled back to population mean 1 so the loss magnitude stays
        # comparable with norm="sum".  (The old expression
        # ``e / e.mean() / e.sum() * e.sum()`` cancelled to ``e / e.mean()``
        # — an *unweighted* mean that broke the mean-1 invariant whenever
        # level_sizes were given.)
        e = np.exp(p - p.max())
        p = e / max(_pop_mean(e), 1e-12)
    elif norm != "sum":
        raise ValueError(f"unknown norm {norm!r}; expected 'sum' or 'softmax'")
    return tuple(float(v) for v in p)
