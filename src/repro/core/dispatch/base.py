"""Shared MoE building blocks for every dispatch path.

This module owns the pieces that are *schedule-independent*: the layer /
expert-parallel configuration dataclasses, parameter init + partition
specs, and the grouped expert FFN (plus the DeepSeek-style shared-expert
FFN).  The dispatch stages compose around these:

    routing.py   — gate + per-level token selection (identical for all
                   staged paths; what makes their outputs equivalent)
    transport.py — the collective movement primitives (near/far a2a,
                   gather/psum) with the wire-dtype cast
    schedule.py  — the software-pipeline execution skeleton
    engine.py    — the registry that composes the above into named paths

Everything here runs INSIDE ``shard_map`` over the expert-parallel mesh
axes; see engine.py for the path contract.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gating
from repro.core.dispatch import wire as wire_lib


@dataclasses.dataclass(frozen=True)
class EPSpec:
    """How expert parallelism maps onto the mesh.

    The canonical description is ``hierarchy``: ordered
    ``(axis_name, size)`` pairs, outermost-first, covering every mesh axis
    the experts span (e.g. ``(("pod", 2), ("node", 2), ("data", 4))``).
    When omitted it is derived from the legacy 2-level
    ``num_pods``/``ep_per_pod``/``pod_axis``/``data_axis`` fields, which
    remain the constructor surface for 2-level callers.
    """
    num_pods: int                 # pods over which experts span (1 = no pod span)
    ep_per_pod: int               # "data"-axis size
    pod_axis: str | None       # mesh axis name, None when experts don't span pods
    data_axis: str
    model_axis: str | None     # tensor-parallel axis for d_ff
    hierarchy: tuple = ()         # ((axis_name, size), ...) outermost-first

    def __post_init__(self):
        if not self.hierarchy:
            # legacy multipod semantics: the pod tier only exists when the
            # experts actually span pods (pod_axis set AND num_pods > 1)
            multipod = self.pod_axis is not None and self.num_pods > 1
            h = (((self.pod_axis, self.num_pods),) if multipod else ()) \
                + ((self.data_axis, self.ep_per_pod),)
            object.__setattr__(self, "hierarchy", h)

    @classmethod
    def from_axes(cls, axis_names, axis_sizes, model_axis=None) -> EPSpec:
        """Build an N-level spec; the legacy fields become the 2-level
        summary (outer axes collapsed into ``num_pods``)."""
        names = tuple(axis_names)
        sizes = tuple(int(s) for s in axis_sizes)
        assert len(names) == len(sizes) and names, (names, sizes)
        outer = 1
        for s in sizes[:-1]:
            outer *= s
        return cls(num_pods=outer, ep_per_pod=sizes[-1],
                   pod_axis=names[0] if len(names) > 1 else None,
                   data_axis=names[-1], model_axis=model_axis,
                   hierarchy=tuple(zip(names, sizes)))

    @property
    def axis_names(self) -> tuple:
        """EP mesh-axis names, outermost-first."""
        return tuple(n for n, _ in self.hierarchy)

    @property
    def axis_sizes(self) -> tuple:
        """EP mesh extents, outermost-first."""
        return tuple(s for _, s in self.hierarchy)

    @property
    def num_stages(self) -> int:
        """Dispatch stages = EP mesh axes (stage 0 = innermost)."""
        return len(self.hierarchy)

    @property
    def ep_world(self) -> int:
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n

    def ep_axes(self):
        return self.axis_names


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                     # per-expert intermediate size
    num_experts: int              # routed experts N
    top_k: int
    capacity_factor: float = 1.25
    num_shared_experts: int = 0   # DeepSeek-style always-on experts
    activation: str = "swiglu"    # "swiglu" | "gelu"
    dtype: jnp.dtype = jnp.bfloat16
    use_kernel: bool = False      # Pallas grouped GEMM for expert FFN
    a2a_dtype: str = ""           # deprecated alias for wire_codec: a raw
                                  # dtype name resolves to the cast-only
                                  # codec (DeprecationWarning)
    wire_codec: object = None     # wire.WireCodec | registered name | None:
                                  # what dispatch/combine payloads look
                                  # like on the a2a wire (§Perf.2)

    def __post_init__(self):
        # resolve once at config time: unknown names fail here with the
        # registry listed, not deep inside jnp.dtype at trace time
        object.__setattr__(
            self, "wire_codec",
            wire_lib.resolve(self.wire_codec, self.a2a_dtype, stacklevel=4))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_moe_params(key, cfg: MoEConfig, ep: EPSpec, gate_cfg: gating.GateConfig):
    """Global (unsharded-view) parameter pytree for one MoE layer.

    Expert tensors carry the full N on axis 0; the caller shards axis 0 over
    the EP axes and the d_ff axis over ``model``.
    """
    keys = jax.random.split(key, 8)
    d, f, n = cfg.d_model, cfg.d_ff, cfg.num_experts
    # python floats: a numpy float64 scale would promote bf16 weights to f32
    s1 = float(1.0 / np.sqrt(d))
    s2 = float(1.0 / np.sqrt(f))
    p = {
        "gate": gating.init_gate_params(keys[0], d, gate_cfg),
        "w_in": jax.random.normal(keys[1], (n, d, f), cfg.dtype) * s1,
        "w_out": jax.random.normal(keys[2], (n, f, d), cfg.dtype) * s2,
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = jax.random.normal(keys[3], (n, d, f), cfg.dtype) * s1
    if cfg.num_shared_experts:
        fs = cfg.d_ff * cfg.num_shared_experts
        p["shared_in"] = jax.random.normal(keys[4], (d, fs), cfg.dtype) * s1
        p["shared_out"] = jax.random.normal(keys[5], (fs, d), cfg.dtype) * s2
        if cfg.activation == "swiglu":
            p["shared_gate"] = jax.random.normal(keys[6], (d, fs), cfg.dtype) * s1
    return p


def moe_param_specs(cfg: MoEConfig, ep: EPSpec):
    """PartitionSpec pytree matching init_moe_params."""
    from jax.sharding import PartitionSpec as P
    expert_axes = (ep.ep_axes() if len(ep.ep_axes()) > 1 else ep.data_axis)
    if isinstance(expert_axes, tuple) and len(expert_axes) == 1:
        expert_axes = expert_axes[0]
    m = ep.model_axis
    specs = {
        "gate": {"w": P(None, None)},
        "w_in": P(expert_axes, None, m),
        "w_out": P(expert_axes, m, None),
    }
    if cfg.activation == "swiglu":
        specs["w_gate"] = P(expert_axes, None, m)
    if cfg.num_shared_experts:
        specs["shared_in"] = P(None, m)
        specs["shared_out"] = P(m, None)
        if cfg.activation == "swiglu":
            specs["shared_gate"] = P(None, m)
    return specs


# ---------------------------------------------------------------------------
# expert FFN (grouped)
# ---------------------------------------------------------------------------


def _act(cfg, xin, params):
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, params["w_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", xin, params["w_in"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, params["w_in"]))
    return h


def expert_ffn(params, xin, cfg: MoEConfig, ep: EPSpec, *,
               chunk_granular: bool = False):
    """Grouped expert FFN on [E_local, C, d] -> [E_local, C, d].

    d_ff is sharded over the model axis; the output psum happens here so the
    caller sees full activations.  ``chunk_granular`` routes through the
    row-padding kernel entry sized for pipelined-dispatch chunk slices.
    """
    if cfg.use_kernel:
        from repro.kernels.moe_gemm import ops as moe_gemm_ops
        ffn = (moe_gemm_ops.grouped_ffn_chunk if chunk_granular
               else moe_gemm_ops.grouped_ffn)
        y = ffn(
            xin, params["w_in"],
            params.get("w_gate"), params["w_out"],
            activation=cfg.activation)
    else:
        h = _act(cfg, xin, params)
        y = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    if ep.model_axis is not None:
        y = jax.lax.psum(y, ep.model_axis)
    return y


def expert_ffn_flat(params, x_flat, seg_offsets, cfg: MoEConfig, ep: EPSpec,
                    *, seg_experts=None, rows_valid=None,
                    chunk_granular: bool = False, use_pallas=None,
                    slot_to_token=None, slot_w=None,
                    quantized: bool = False):
    """Segment-offset grouped expert FFN on a flat [R, d] row buffer.

    ``seg_offsets`` is the static offset vector of the contiguous sorted
    spans the moe_permute dispatch delivers; ``seg_experts`` names each
    segment's expert (default: one segment per expert, in order) and
    ``rows_valid`` optionally carries the *runtime* realized-row count per
    segment — the occupancy view of TA-MoE's capacity slack.  Semantics
    match :func:`expert_ffn` on the segment-reshaped view — same model-axis
    psum, same zero-slot convention (callers keep rows past the valid count
    zero-filled; outputs there are zero either way, computed-from-zeros or
    skipped).

    Fused mode: passing ``slot_to_token`` / ``slot_w`` (the flat sort-order
    maps of ``routing.build_indices``) switches the meaning of ``x_flat``
    from the segment-sorted slot buffer to the **raw [T, d] token buffer**
    — dispatch gather, expert FFN, and the gate-weighted combine run as one
    ``moe_fused.local_moe`` call and the return value is the [T, d] float32
    combined output.  The model-axis psum still happens here (the
    down-projection partials commute with the linear combine scatter), so
    callers see full activations either way.

    Quantized compute: ``quantized=True`` (the engine sets it when the
    wire codec opts delivered rows into low-precision compute) routes the
    non-fused call through the AQT-style int8 grouped GEMM — per-segment
    int8 activations x per-expert int8 ``w_in``/``w_gate`` with i32
    accumulation, full-precision backward (straight-through) — regardless
    of the Pallas backend decision.

    Backend routing: with the Pallas kernels active for ``use_pallas``
    (``moe_gemm.ops.use_ragged``) every non-fused call goes through the
    occupancy-aware ragged entry, so FLOPs scale with delivered tokens;
    otherwise equal fully-occupied per-expert spans reshape onto the dense
    einsum / ``cfg.use_kernel`` path exactly as before, and any genuinely
    ragged static layout falls back to the ragged jnp reference.
    """
    from repro.kernels.moe_gemm import ops as moe_gemm_ops
    offs = tuple(int(o) for o in seg_offsets)
    d = x_flat.shape[-1]
    if slot_to_token is not None:
        from repro.kernels.moe_fused import ops as moe_fused_ops
        y = moe_fused_ops.local_moe(
            x_flat, slot_to_token, slot_w, offs, seg_experts, rows_valid,
            params["w_in"], params.get("w_gate"), params["w_out"],
            activation=cfg.activation, use_pallas=use_pallas)
        if ep.model_axis is not None:
            y = jax.lax.psum(y, ep.model_axis)
        return y
    if quantized or moe_gemm_ops.use_ragged(use_pallas) or cfg.use_kernel:
        y = moe_gemm_ops.grouped_ffn_segments(
            x_flat, offs, params["w_in"], params.get("w_gate"),
            params["w_out"], activation=cfg.activation,
            row_align=128 if chunk_granular else 1,
            seg_experts=seg_experts, rows_valid=rows_valid,
            use_pallas=use_pallas, quantized=quantized)
    else:
        # jnp path: collapse the (contiguous, expert-major) segments to
        # per-expert spans — zero-filled slack rows make the dense compute
        # equal to the masked one, so occupancy info is simply dropped here
        if seg_experts is None:
            per_expert = offs
        else:
            assert tuple(seg_experts) == tuple(sorted(seg_experts)), \
                "segments must be expert-major for the jnp path"
            E = params["w_in"].shape[0]
            per_expert = [0] * (E + 1)
            for s, e in enumerate(seg_experts):
                per_expert[e + 1] = offs[s + 1]
            for e in range(E):                 # experts with no segments
                per_expert[e + 1] = max(per_expert[e + 1], per_expert[e])
            per_expert = tuple(per_expert)
        E = len(per_expert) - 1
        widths = {per_expert[e + 1] - per_expert[e] for e in range(E)}
        if len(widths) == 1:
            xg = x_flat.reshape(E, per_expert[1] - per_expert[0], d)
            h = _act(cfg, xg, params)
            y = jnp.einsum("ecf,efd->ecd", h, params["w_out"]).reshape(-1, d)
        else:
            y = moe_gemm_ops.grouped_ffn_ragged(
                x_flat, per_expert, tuple(range(E)), None,
                params["w_in"], params.get("w_gate"), params["w_out"],
                activation=cfg.activation, use_pallas=False)
    if ep.model_axis is not None:
        y = jax.lax.psum(y, ep.model_axis)
    return y


def shared_ffn(params, x, cfg: MoEConfig, ep: EPSpec):
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ params["shared_gate"]) * (x @ params["shared_in"])
    else:
        h = jax.nn.gelu(x @ params["shared_in"])
    y = h @ params["shared_out"]
    if ep.model_axis is not None:
        y = jax.lax.psum(y, ep.model_axis)
    return y
