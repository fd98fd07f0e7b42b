"""DispatchEngine: the registry that composes routing→transport→compute→
combine into named MoE dispatch paths.

Paths are registered by name (the string carried by ``RunConfig.dispatch``
and per-layer ``MoEArch.dispatch_override`` entries) and resolved through
:func:`make_engine`.  Every path returns ``(y, metrics)`` with the uniform
schema :data:`METRIC_KEYS` — missing keys are filled with neutral defaults
by the engine so callers (shard_map out_specs, trainers, benchmarks) never
branch on the path.  ``frac_by_level`` is a fixed-length ``[num_stages]``
vector (one entry per dispatch stage of the EP hierarchy, stage 0 folding
in the self level); ``frac_self`` is the share of assignments that stay on
the rank's own experts, the self level that stage 0 folds in.

Every path names its parts with ``jax.named_scope`` -- ``moe.route``
(gate, top-k, aux loss, dispatch metrics), ``moe.dispatch`` (index
building and the token movement to the experts), ``moe.experts`` (the
expert FFN), ``moe.combine`` (the movement back and the weighted sum) and
``moe.shared`` (the shared experts) -- so a profile attributes each
compiled op to one of them through its ``op_name`` metadata.  Scopes
change metadata only, never an op.

Built-in paths:

    a2a            staged hierarchical all-to-all (train / prefill); the
                   software pipeline at num_chunks=1, i.e. fully serialized
    a2a_pipelined  same routing/capacities, chunked 3-stage comm–compute
                   overlap schedule (num_chunks > 1)
    gather         weights-stationary decode regime: all-gather + psum
    einsum         the GShard/DeepSpeed one-hot [T, N, C] formulation —
                   shard-local (no collectives), kept as the §2 baseline
                   and the equivalence oracle for the selection-based paths

Adding a path: implement ``fn(params, x, eng) -> (y, metrics)`` where
``eng`` is the resolved :class:`DispatchEngine` (cfg/ep/plan/gate_cfg and
schedule knobs), then decorate with ``@register("name")``.  Compose the
stage modules rather than re-implementing them — routing is what makes
cross-path outputs comparable.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core import gating
from repro.core.capacity import DispatchPlan
from repro.core.dispatch import routing, schedule, transport
from repro.core.dispatch.base import (EPSpec, MoEConfig, expert_ffn,
                                      expert_ffn_flat, shared_ffn)
from repro.core.dispatch.routing import _prod
from repro.kernels.moe_fused import ops as moe_fused_ops
from repro.kernels.moe_gemm import ops as moe_gemm_ops
from repro.kernels.moe_permute import ops as permute_ops

#: Uniform metrics schema every path resolves to.  ``frac_by_level`` is a
#: ``[num_stages]`` vector.
METRIC_KEYS = ("aux_loss", "frac_by_level", "dropped", "frac_self")


@dataclasses.dataclass(frozen=True)
class DispatchPath:
    """A registered dispatch implementation."""
    name: str
    fn: Callable
    needs_plan: bool = False


_REGISTRY: dict = {}


def register(name: str, *, needs_plan: bool = False):
    """Decorator registering ``fn(params, x, eng) -> (y, metrics)``."""
    def deco(fn):
        _REGISTRY[name] = DispatchPath(name=name, fn=fn, needs_plan=needs_plan)
        return fn
    return deco


def available() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_path(name: str) -> DispatchPath:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown dispatch {name!r}; "
                         f"registered paths: {available()}") from None


@dataclasses.dataclass(frozen=True)
class DispatchEngine:
    """A dispatch path resolved against one MoE layer's static config.

    Callable on ``(params, x)`` INSIDE shard_map over the EP axes, with
    ``x: [T_local, d]``; returns ``(y, metrics)`` where metrics carries
    exactly :data:`METRIC_KEYS`.
    """

    path: DispatchPath
    cfg: MoEConfig
    ep: EPSpec
    gate_cfg: gating.GateConfig
    plan: DispatchPlan | None = None
    num_chunks: int = 1               # a2a_pipelined schedule depth
    capacity: int | None = None    # einsum buffer capacity (None = cf rule)
    tokens_replicated: bool = False   # gather: tokens already on every rank
    # Token-permutation implementation for the dispatch/combine hot path:
    # None = auto (Pallas kernels on TPU/GPU, the jnp reference elsewhere);
    # True/False force it.  See repro.kernels.moe_permute.ops.
    use_pallas: bool | None = None

    @property
    def name(self) -> str:
        return self.path.name

    @property
    def num_stages(self) -> int:
        """Length of the ``frac_by_level`` metric vector."""
        return self.plan.num_stages if self.plan is not None \
            else self.ep.num_stages

    def __call__(self, params, x):
        y, metrics = self.path.fn(params, x, self)
        S = self.num_stages
        fb = metrics.get("frac_by_level")
        if fb is None:
            # neutral default: everything stays at the innermost stage
            fb = jnp.zeros((S,), jnp.float32).at[0].set(1.0)
        fb = jnp.asarray(fb, jnp.float32)
        out = {"aux_loss": metrics["aux_loss"],
               "frac_by_level": fb,
               "dropped": jnp.asarray(metrics.get("dropped", 0.0),
                                      jnp.float32),
               # neutral default: every expert is this rank's own
               "frac_self": jnp.asarray(metrics.get("frac_self", 1.0),
                                        jnp.float32)}
        return y, out


def make_engine(name: str, *, cfg: MoEConfig, ep: EPSpec,
                gate_cfg: gating.GateConfig,
                plan: DispatchPlan | None = None, num_chunks: int = 1,
                capacity: int | None = None,
                tokens_replicated: bool = False,
                use_pallas: bool | None = None) -> DispatchEngine:
    """Resolve ``name`` against the registry and bind the static config."""
    path = get_path(name)
    if path.needs_plan and plan is None:
        raise ValueError(f"dispatch {name!r} requires a DispatchPlan")
    return DispatchEngine(path=path, cfg=cfg, ep=ep, gate_cfg=gate_cfg,
                          plan=plan, num_chunks=max(1, int(num_chunks)),
                          capacity=capacity,
                          tokens_replicated=tokens_replicated,
                          use_pallas=use_pallas)


def dispatch_moe(name: str, params, x, *, cfg: MoEConfig, ep: EPSpec,
                 gate_cfg: gating.GateConfig, **kwargs):
    """One-shot convenience: resolve + apply in a single call."""
    return make_engine(name, cfg=cfg, ep=ep, gate_cfg=gate_cfg, **kwargs)(
        params, x)


# ---------------------------------------------------------------------------
# staged a2a paths (sync == num_chunks 1, pipelined == num_chunks k)
# ---------------------------------------------------------------------------


def _staged_a2a(params, x, eng: DispatchEngine, num_chunks: int):
    """The one staged implementation behind both ``a2a`` and
    ``a2a_pipelined``: shared routing, the shared sort-based buffer builder
    (``routing.build_indices`` + the moe_permute kernels), chunk-sliced
    stage-list transport, and the software-pipeline schedule (serialized
    when ``num_chunks == 1``).

    Dispatch is one fused permute per chunk — tokens gathered straight into
    the (stage, destination, expert)-sorted capacity buffers — and combine
    is the inverse permutation with the gate-weight multiply fused in
    (``eng.use_pallas`` picks kernel vs reference).  Routing, capacities and
    combine weights are identical across chunk counts, so outputs are
    allclose at matched capacities (the per-token accumulation order over
    chunks may differ in the last ulp).

    Occupancy: when the Pallas GEMM is active (``moe_gemm.ops.use_ragged``)
    the runtime per-(destination, expert) valid-row counts that
    ``routing.build_indices`` derives ride along each chunk's payload
    (``A2ATransport.dispatch_counts`` — a tiny exact all_to_all of the
    count vector), and the expert compute goes through the occupancy-aware
    ragged grouped GEMM: row blocks past a segment's delivered tokens do
    zero MXU work, so FLOPs track Eq. (7)'s *realized* skewed load instead
    of the static worst-case capacity.  Numerically this changes nothing —
    the skipped rows are the permute sentinel's zero-filled slack, whose
    FFN output is zero either way.

    Fused local path: when the kernels are active
    (``moe_fused.ops.use_fused``), stages whose delivery chain is the
    identity — every delivery axis has size 1, i.e. the folded-in self
    level of a unit mesh axis — skip the permute → a2a → GEMM → a2a →
    unpermute round trip entirely.  Their selections are flattened by the
    same ``build_indices`` into a local index set and computed in one
    ``moe_fused.local_moe`` megakernel call (through
    ``expert_ffn_flat(slot_to_token=...)``): no sorted [S, d] capacity
    buffer in HBM, no collectives, gather + grouped GEMM + gate-weighted
    combine in a single pass.  Remote stages keep the permute → a2a chain
    unchanged — a (token, expert) pair occupies at most one slot globally,
    so the local and remote index sets partition the slots and their
    combined outputs simply add.  The local contribution is computed once,
    outside the chunk pipeline (it has no comm to overlap).
    """
    cfg, ep, plan, gate_cfg = eng.cfg, eng.ep, eng.plan, eng.gate_cfg
    T, d = x.shape
    tr = transport.A2ATransport(ep=ep, codec=cfg.wire_codec)
    stages = transport.plan_stages(plan, ep)
    # codecs may opt delivered rows into quantized expert compute — only
    # the remote staged GEMMs; the fused local path never hits the wire
    quant = cfg.wire_codec is not None and cfg.wire_codec.quantize_compute

    with jax.named_scope("moe.route"):
        routed = routing.route(params, x, cfg, ep, plan, gate_cfg,
                               with_bufs=False)
        kept_unpadded = sum(sel.valid.sum() for _, sel in routed.sels)
    num_chunks = max(1, int(num_chunks))
    chunked = num_chunks > 1
    topk_idx = routed.gate_out["topk_idx"]

    # split the active stages: purely local delivery fuses, the rest keep
    # the staged transport.  Per-stage state for the remote group:
    # (transport stage, padded selection, capacity axis, per-chunk
    # capacity, expert-row count per chunk)
    fused_on = moe_fused_ops.use_fused(eng.use_pallas)
    local_work, work = [], []
    for (s, sel), stage in zip(routed.sels, stages):
        if fused_on and stage.num_dests == 1:
            local_work.append((stage, sel))
            continue
        cap_axis = s + 2
        sel = routing.pad_selection(sel, axis=cap_axis, multiple=num_chunks)
        cpc = sel.idx.shape[cap_axis] // num_chunks
        work.append((stage, sel, cap_axis, cpc, stage.num_dests * cpc))

    out_local = None
    if local_work:
        # the fused megakernel path: flatten the local stages' selections
        # with the same shared builder, then one local_moe call — permute,
        # ragged GEMM, and weighted combine in a single kernel, segment
        # occupancy (rows_per_expert) consumed directly (no count exchange:
        # the rows never leave the device)
        E_l = params["w_in"].shape[0]
        with jax.named_scope("moe.dispatch"):
            li = routing.build_indices(
                tuple((stage.index, sel) for stage, sel in local_work),
                topk_idx, T)
        offs, exps = [0], []
        for _stage, sel in local_work:
            width = sel.idx.shape[-1]
            for e in range(E_l):
                offs.append(offs[-1] + width)
                exps.append(e)
        with jax.named_scope("moe.experts"):
            out_local = expert_ffn_flat(
                params, x, tuple(offs), cfg, ep, seg_experts=tuple(exps),
                rows_valid=li.rows_per_expert,
                slot_to_token=li.slot_to_token, slot_w=li.slot_w,
                use_pallas=eng.use_pallas)                      # [T, d] f32

    # the shared buffer builder: chunk j's capacity slice of every remote
    # stage, flattened into one sort-order index set (sync == chunk 0)
    with jax.named_scope("moe.dispatch"):
        indices = [routing.build_indices(
            tuple((stage.index,
                   routing.slice_selection(sel, cap_axis, j * cpc, cpc))
                  for stage, sel, cap_axis, cpc, _ in work),
            topk_idx, T) for j in range(num_chunks)] if work else []

    # occupancy-aware compute: only pay for the count exchange when the
    # ragged Pallas entry will actually consume it
    ragged = moe_gemm_ops.use_ragged(eng.use_pallas)

    @jax.named_scope("moe.dispatch")
    def dispatch(j):
        di = indices[j]
        flat = permute_ops.permute(x, di.slot_to_token,
                                   use_pallas=eng.use_pallas)      # [S_j, d]
        parts, cnts = [], None
        for (stage, *_), (_, off, shape) in zip(work, di.stage_spans()):
            buf = jax.lax.slice_in_dim(flat, off, off + _prod(shape), axis=0)
            parts.append(tr.dispatch(buf.reshape(shape + (d,)), stage))
        if ragged:
            cnts = tuple(
                tr.dispatch_counts(
                    jax.lax.slice_in_dim(di.rows_per_expert, off,
                                         off + _prod(shape),
                                         axis=0).reshape(shape), stage)
                for (stage, *_), (_, off, shape) in zip(work,
                                                        di.expert_spans()))
        xin = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        return xin, cnts

    @jax.named_scope("moe.experts")
    def compute(j, v):
        # contiguous expert spans -> the segment-offset grouped GEMM entry
        xin, cnts = v
        E_l, R, _ = xin.shape
        if cnts is None:
            segs, exps, valid = transport.expert_segments(E_l, R), None, None
        else:
            # one segment per (expert, stage, source): the granularity at
            # which delivered rows are a valid prefix
            segs, exps = transport.stage_segments(
                E_l, tuple((stage.num_dests, cpc)
                           for stage, _, _, cpc, _ in work))
            valid = jnp.concatenate(cnts, axis=1).reshape(-1) \
                if len(cnts) > 1 else cnts[0].reshape(-1)
        y = expert_ffn_flat(params, xin.reshape(E_l * R, d), segs, cfg, ep,
                            seg_experts=exps, rows_valid=valid,
                            chunk_granular=chunked,
                            use_pallas=eng.use_pallas, quantized=quant)
        return y.reshape(E_l, R, d)

    @jax.named_scope("moe.combine")
    def combine(out, j, y_exp):
        if out is None:
            out = jnp.zeros((T, d), y_exp.dtype)
        di = indices[j]
        flats, off = [], 0
        for stage, _, _, _, rows in work:
            back = tr.combine(y_exp[:, off:off + rows], stage)
            off += rows
            flats.append(back.reshape(-1, d))
        y_flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats, 0)
        mixed = permute_ops.unpermute(y_flat, di.inv_idx, di.inv_w,
                                      use_pallas=eng.use_pallas)
        return out + mixed.astype(out.dtype)

    out = schedule.software_pipeline(num_chunks, dispatch, compute, combine,
                                     None) if work else jnp.zeros((T, d),
                                                                  x.dtype)
    if out_local is not None:
        # like shared_ffn: independent of every chunk, added after the
        # pipeline drains
        out = out + out_local.astype(out.dtype)

    if cfg.num_shared_experts:
        # independent of every chunk: another overlap opportunity for the
        # scheduler, issued after the pipeline drains.
        with jax.named_scope("moe.shared"):
            out = out + shared_ffn(params, x, cfg, ep).astype(out.dtype)

    with jax.named_scope("moe.route"):
        frac = gating.dispatch_fractions(routed.gate_out["topk_idx"],
                                         cfg.num_experts)
        metrics = {
            "aux_loss": routed.aux,
            "frac_by_level": gating.frac_by_level(frac, routed.levels,
                                                  plan.num_stages),
            "dropped": 1.0 - jnp.minimum(
                kept_unpadded / (T * gate_cfg.top_k), 1.0),
            "frac_self": gating.frac_self(frac, routed.levels),
        }
    return out.astype(x.dtype), metrics


@register("a2a", needs_plan=True)
def _a2a_path(params, x, eng: DispatchEngine):
    """Sync staged all-to-all: the pipeline schedule at num_chunks=1."""
    return _staged_a2a(params, x, eng, 1)


@register("a2a_pipelined", needs_plan=True)
def _a2a_pipelined_path(params, x, eng: DispatchEngine):
    """Chunked comm–compute-overlap schedule over the same routing."""
    return _staged_a2a(params, x, eng, eng.num_chunks)


# ---------------------------------------------------------------------------
# gather path (decode)
# ---------------------------------------------------------------------------


@register("gather")
def _gather_path(params, x, eng: DispatchEngine):
    """Decode-time MoE: weights stationary, tokens gathered.

    x: [T_local, d].  When ``eng.tokens_replicated`` the same tokens exist
    on every EP rank already (long_500k batch=1) and no gather/slice is
    done.  Bandwidth-optimal for single-token steps (no all-to-all).
    """
    cfg, ep, gate_cfg = eng.cfg, eng.ep, eng.gate_cfg
    E_l = max(1, -(-cfg.num_experts // ep.ep_world))
    tr = transport.GatherTransport(ep=ep,
                                   tokens_replicated=eng.tokens_replicated)
    coords = tuple(jax.lax.axis_index(a) for a in ep.axis_names)
    my_rank = jnp.int32(0)
    for c, s in zip(coords, ep.axis_sizes):
        my_rank = my_rank * s + c

    with jax.named_scope("moe.dispatch"):
        xg = tr.gather(x)
    with jax.named_scope("moe.route"):
        levels = gating.expert_levels_nd(cfg.num_experts, E_l,
                                         ep.axis_sizes, coords)
        # levels=None for the gate itself: the hir bias is rank-relative
        # and every rank gates the *gathered* tokens here, so biasing would
        # make the implied routing rank-dependent.  The aux loss below does
        # use the levels — gather is a first-class training path, so it
        # reports the real balance/topology loss (decode callers ignore
        # metrics anyway).
        gate_out = gating.gate_forward(params["gate"], xg, gate_cfg, None)
        aux = gating.aux_loss(gate_out, gate_cfg, levels)

    Tg, d = xg.shape
    if moe_fused_ops.use_fused(eng.use_pallas):
        # fused decode grid: the dense [E_l, Tg] slot space is never
        # materialized (nor is the [E_l, Tg, d] broadcast buffer) — slot
        # ``e * Tg + t`` maps token ``t`` through expert ``e``, so the
        # megakernel gathers each expert's rows straight from the gathered
        # tokens and scatter-accumulates with the gate weights fused in.
        # An expert picked by *no* gathered token is pure slack: its whole
        # Tg-row segment is a skipped zero-valid segment, exactly the
        # whole-segment skip the ragged GEMM did here before.
        with jax.named_scope("moe.route"):
            wts = routing.gather_weights(gate_out, my_rank, E_l)  # [Tg, E_l]
            valid = jnp.where(jnp.any(wts > 0, axis=0), Tg,
                              0).astype(jnp.int32)
        slot_tok = jnp.tile(jnp.arange(Tg, dtype=jnp.int32), E_l)
        with jax.named_scope("moe.experts"):
            y = expert_ffn_flat(params, xg,
                                transport.expert_segments(E_l, Tg),
                                cfg, ep, seg_experts=tuple(range(E_l)),
                                rows_valid=valid, slot_to_token=slot_tok,
                                slot_w=wts.T.reshape(-1),
                                use_pallas=eng.use_pallas)       # [Tg, d]
    else:
        with jax.named_scope("moe.experts"):
            xin = jnp.broadcast_to(xg, (E_l,) + xg.shape)        # [E_l, Tg, d]
            y = expert_ffn(params, xin, cfg, ep)                 # [E_l, Tg, d]
        # combine through the same weighted inverse-permutation the staged
        # paths use: the dense [E_l, Tg] grid is a degenerate slot buffer
        with jax.named_scope("moe.combine"):
            inv_idx, inv_w = routing.gather_inverse(gate_out, my_rank, E_l,
                                                    Tg)
            y = permute_ops.unpermute(y.reshape(E_l * Tg, -1), inv_idx,
                                      inv_w, use_pallas=eng.use_pallas)
    with jax.named_scope("moe.combine"):
        y = y.astype(x.dtype)
        y = tr.reduce(y)
        y = tr.slice_local(y, my_rank, x.shape[0])

    if cfg.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + shared_ffn(params, x, cfg, ep).astype(y.dtype)

    with jax.named_scope("moe.route"):
        frac = gating.dispatch_fractions(gate_out["topk_idx"],
                                         cfg.num_experts)
        fb = gating.frac_by_level(frac, levels, eng.num_stages)
    metrics = {"aux_loss": aux, "frac_by_level": fb,
               "dropped": 0.0,   # no capacity limit: nothing ever drops
               "frac_self": gating.frac_self(frac, levels)}
    return y.astype(x.dtype), metrics


# ---------------------------------------------------------------------------
# GShard/DeepSpeed-style einsum dispatch (baseline from the paper's §2)
# ---------------------------------------------------------------------------


@register("einsum")
def _einsum_path(params, x, eng: DispatchEngine):
    """The classic einsum formulation: one-hot dispatch/combine tensors of
    shape [T, N, C] route tokens through a zero-padded [N, C, d] buffer.

    This is the DeepSpeed-MoE / GShard baseline the paper describes as
    introducing "redundant zero computation and extra memory consumption"
    (§2) — kept for comparison and as the equivalence oracle for the
    selection-based paths.  Runs shard-local (no collectives): suitable for
    pjit auto-sharding or single-rank tests only.
    """
    cfg, ep, gate_cfg = eng.cfg, eng.ep, eng.gate_cfg
    T, d = x.shape
    N, K = cfg.num_experts, cfg.top_k
    capacity = eng.capacity
    if capacity is None:
        capacity = max(1, int(T * K * cfg.capacity_factor / N))

    with jax.named_scope("moe.route"):
        gate_out = gating.gate_forward(params["gate"], x, gate_cfg, None)
        aux = gating.aux_loss(gate_out, gate_cfg, None)
    topk_idx, topk_w = gate_out["topk_idx"], gate_out["topk_weight"]

    with jax.named_scope("moe.dispatch"):
        # position of each (token, slot) within its expert's capacity buffer
        dispatch = jnp.zeros((T, N, capacity), jnp.float32)
        combine = jnp.zeros((T, N, capacity), jnp.float32)
        counts = jnp.zeros((N,), jnp.int32)
        for s in range(K):
            e = topk_idx[:, s]                                 # [T]
            onehot = jax.nn.one_hot(e, N, dtype=jnp.int32)     # [T, N]
            pos_in_e = (jnp.cumsum(onehot, axis=0) - 1) * onehot   # [T, N]
            pos = jnp.sum(pos_in_e, axis=1) + counts[e]        # [T]
            keep = pos < capacity
            slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
            mask = (onehot.astype(jnp.float32)
                    * keep[:, None].astype(jnp.float32))
            d_s = mask[:, :, None] * slot[:, None, :]          # [T, N, C]
            dispatch = dispatch + d_s
            combine = combine + d_s * topk_w[:, s][:, None, None]
            counts = counts + jnp.sum(onehot * keep[:, None], axis=0)
        xin = jnp.einsum("tnc,td->ncd", dispatch, x.astype(jnp.float32))

    with jax.named_scope("moe.experts"):
        y_exp = expert_ffn(params, xin.astype(x.dtype), cfg, ep)  # [N, C, d]
    with jax.named_scope("moe.combine"):
        y = jnp.einsum("tnc,ncd->td", combine, y_exp.astype(jnp.float32))
    if cfg.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + shared_ffn(params, x, cfg, ep).astype(y.dtype)
    metrics = {"aux_loss": aux,
               "dropped": 1.0 - dispatch.sum() / (T * K)}
    return y.astype(x.dtype), metrics
