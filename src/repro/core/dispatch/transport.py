"""Transport stage: the collective movement primitives of each dispatch path.

A transport object owns *how bytes move between EP ranks* — nothing about
routing or scheduling.  Two families exist:

* :class:`A2ATransport` — equal-split staged ``lax.all_to_all`` driven by a
  list of :class:`Stage` objects derived from the level-indexed
  :class:`~repro.core.capacity.DispatchPlan`.  Stage ``s`` delivers over
  the innermost ``s + 1`` EP mesh axes as a chain of all_to_alls
  (outermost hop first), so a 2-axis mesh reproduces the PR-2 near/far
  pair and an N-axis mesh gets N stages with no new code.  The wire
  encoding (:mod:`repro.core.dispatch.wire` codec: cast, or per-segment
  scaled int8/fp8 quantization) lives here: the payload is encoded once
  before the hop chain, the f32 scale sideband rides the *same* chain the
  per-segment counts use, and decode happens after the final transpose —
  so only wire bytes are low-precision while compute stays in the model
  dtype (unless the codec opts delivered rows into quantized compute).
* :class:`GatherTransport` — the weights-stationary decode regime: tokens
  are (all-)gathered to every EP rank and partial expert outputs are
  psum-combined; no all-to-all at all.

Buffer layout contract with the moe_permute dispatch: the payload arrives
already (stage, destination, expert, slot)-sorted, so each stage's
delivered rows are *contiguous per-expert spans* — :func:`expert_segments`
derives the static segment-offset vector the grouped GEMM entry
(``moe_gemm.ops.grouped_ffn_segments``) consumes, and the all_to_all
chains themselves are unchanged (equal splits of a sorted buffer stay
sorted).

New transports (e.g. a ragged / sparsity-aware exchange) plug in by
implementing the same dispatch/combine surface and get picked up by a path
definition in engine.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.dispatch import wire as wire_lib
from repro.core.dispatch.base import EPSpec


#: Named scope of every all_to_all of the exchange (dispatch, counts,
#: combine), so a profile tells the exchange from the permute around it.
A2A_SCOPE = "moe.a2a"


def _a2a(x, axis_name, *, split_axis, concat_axis):
    with jax.named_scope(A2A_SCOPE):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)


def wire_a2a(x, axis_name, *, split_axis, concat_axis, wire_dtype: str = ""):
    """all_to_all with an optional (deprecated) on-the-wire dtype cast.

    ``wire_dtype=`` resolves to the cast-only codec with a
    DeprecationWarning; scaled codecs need the segment layout only
    :class:`A2ATransport` knows, so quantized wire goes through a
    transport built with ``codec=`` instead of this helper.
    """
    codec = wire_lib.resolve(None, wire_dtype)
    if codec is not None:
        payload, _ = codec.encode(x)
        payload = _a2a(payload, axis_name, split_axis=split_axis,
                       concat_axis=concat_axis)
        return codec.decode(payload, None, x.dtype)
    return _a2a(x, axis_name, split_axis=split_axis, concat_axis=concat_axis)


@dataclasses.dataclass(frozen=True)
class Stage:
    """One level-indexed exchange stage of a dispatch plan.

    ``axis_names``/``axis_sizes`` are the delivery chain, outermost hop
    first: stage ``index`` traverses the innermost ``index + 1`` EP mesh
    axes.  ``cap`` is the per-(source device, expert) token capacity the
    routing stage selects for this level.
    """

    index: int                    # dispatch stage (0 = innermost / "near")
    axis_names: tuple             # delivery chain, outermost hop first
    axis_sizes: tuple
    cap: int

    @property
    def num_dests(self) -> int:
        """Destination ranks addressed by this stage's buffer (incl. the
        lower-stage block that routing masks out)."""
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n


def plan_stages(plan, ep: EPSpec) -> tuple:
    """Active :class:`Stage` list for one plan on one EP spec.

    The plan's ``level_axes`` name the canonical hierarchy; the EP spec is
    authoritative for the mesh axis names actually bound inside shard_map,
    so stages are rebuilt from ``ep.hierarchy`` and validated against the
    plan's stage count.
    """
    names, sizes = ep.axis_names, ep.axis_sizes
    n = len(names)
    assert plan.num_stages == n, (
        f"plan has {plan.num_stages} stages but the EP spec spans {n} mesh "
        f"axes {names}; rebuild the plan for this mesh")
    return tuple(Stage(index=s, axis_names=names[n - s - 1:],
                       axis_sizes=sizes[n - s - 1:], cap=plan.caps[s])
                 for s in range(n) if plan.caps[s] > 0)


def expert_segments(num_experts: int, rows_per_expert: int) -> tuple:
    """Static [E + 1] segment-offset vector of a delivered stage buffer:
    expert ``e`` owns flat rows ``offs[e]:offs[e + 1]`` of the
    [E * rows, d] view — the contract between the sorted a2a payload and
    ``moe_gemm.ops.grouped_ffn_segments``."""
    return tuple(rows_per_expert * e for e in range(num_experts + 1))


def stage_segments(num_experts: int, stage_widths) -> tuple:
    """Fine-grained ``(seg_offsets, seg_experts)`` of a delivered buffer
    concatenated over stages: flat row order is (expert, stage,
    destination, capacity-slot) and ``stage_widths`` is the static
    ``((num_dests, rows_per_dest), ...)`` stage list.  One segment per
    (expert, stage, source destination) — the granularity at which the
    delivered rows are a valid prefix, and therefore the granularity the
    occupancy-aware ragged GEMM masks at."""
    offs, exps = [0], []
    for e in range(num_experts):
        for num_dests, width in stage_widths:
            for _ in range(num_dests):
                offs.append(offs[-1] + width)
                exps.append(e)
    return tuple(offs), tuple(exps)


def _dispatch_perm(buf, stage: Stage):
    """Codec-free dispatch: the pure element permutation.  [*sizes, E_l,
    C, d] -> a2a chain (outermost hop first) -> [E_l, num_dests*C, d]."""
    k = len(stage.axis_names)
    for i in range(k):
        buf = _a2a(buf, stage.axis_names[i], split_axis=i, concat_axis=i)
    E_l, C, d = buf.shape[k:]
    perm = (k,) + tuple(range(k)) + (k + 1, k + 2)
    return buf.transpose(perm).reshape(E_l, stage.num_dests * C, d)


def _combine_perm(y, stage: Stage):
    """Inverse (== transpose) of :func:`_dispatch_perm`: [E_l,
    num_dests*C, d] -> reverse a2a chain -> [*sizes, E_l, C, d]."""
    sizes = stage.axis_sizes
    k = len(sizes)
    E_l, R, d = y.shape
    y = y.reshape((E_l,) + sizes + (R // stage.num_dests, d))
    perm = tuple(range(1, k + 1)) + (0, k + 1, k + 2)
    y = y.transpose(perm)                         # [*sizes, E_l, C, d]
    for i in range(k - 1, -1, -1):
        y = _a2a(y, stage.axis_names[i], split_axis=i, concat_axis=i)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dispatch_scaled(codec, stage: Stage, buf):
    """Scaled-codec dispatch: encode once, move (payload, scale) through
    the same chain, decode after the final transpose.

    Straight-through gradient: ``round`` and the float->int8 cast are
    non-differentiable, so the backward pass is the exact full-precision
    reverse permutation (the forward is a permutation up to rounding) —
    quantized wire on the way out, f32 cotangents on the way back.
    """
    k = len(stage.axis_names)
    payload, scale = codec.encode(buf, block_ndim=2)
    for i in range(k):
        ax = stage.axis_names[i]
        payload = _a2a(payload, ax, split_axis=i, concat_axis=i)
        scale = _a2a(scale, ax, split_axis=i, concat_axis=i)
    E_l, C, d = payload.shape[k:]
    perm = (k,) + tuple(range(k)) + (k + 1, k + 2)
    out = payload.transpose(perm).reshape(E_l, stage.num_dests, C, d)
    s = scale.transpose((k,) + tuple(range(k))).reshape(E_l, stage.num_dests)
    return codec.decode(out, s[:, :, None, None], buf.dtype).reshape(
        E_l, stage.num_dests * C, d)


def _dispatch_scaled_fwd(codec, stage, buf):
    return _dispatch_scaled(codec, stage, buf), None


def _dispatch_scaled_bwd(codec, stage, _res, g):
    # the cotangent already carries the source dtype (decode casts there)
    return (_combine_perm(g, stage),)


_dispatch_scaled.defvjp(_dispatch_scaled_fwd, _dispatch_scaled_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _combine_scaled(codec, stage: Stage, y):
    """Scaled-codec combine: transpose back to the send layout, encode,
    reverse chain, decode at the source.  Same straight-through backward
    as :func:`_dispatch_scaled`."""
    sizes = stage.axis_sizes
    k = len(sizes)
    E_l, R, d = y.shape
    orig = y.dtype
    y = y.reshape((E_l,) + sizes + (R // stage.num_dests, d))
    perm = tuple(range(1, k + 1)) + (0, k + 1, k + 2)
    y = y.transpose(perm)                         # [*sizes, E_l, C, d]
    payload, scale = codec.encode(y, block_ndim=2)
    for i in range(k - 1, -1, -1):
        ax = stage.axis_names[i]
        payload = _a2a(payload, ax, split_axis=i, concat_axis=i)
        scale = _a2a(scale, ax, split_axis=i, concat_axis=i)
    return codec.decode(payload, scale[..., None, None], orig)


def _combine_scaled_fwd(codec, stage, y):
    return _combine_scaled(codec, stage, y), None


def _combine_scaled_bwd(codec, stage, _res, g):
    return (_dispatch_perm(g, stage),)


_combine_scaled.defvjp(_combine_scaled_fwd, _combine_scaled_bwd)


@dataclasses.dataclass(frozen=True)
class A2ATransport:
    """Equal-split staged all-to-all over the EP mesh axes.

    ``codec`` (a :mod:`repro.core.dispatch.wire` codec, a registered codec
    name, or None for a raw wire) owns the payload encoding.  Scaled
    codecs compute one f32 scale per (destination, expert) ``[C, d]``
    block — shaped ``[*sizes, E_l]``, exactly the :meth:`dispatch_counts`
    metadata layout — and the scale sideband rides the identical
    split/concat chain as the payload, landing as ``[E_l, num_dests]`` at
    the receiver.  Scaled transfers differentiate straight-through: the
    backward pass moves full-precision cotangents over the exact reverse
    permutation, so quantized wire stays trainable.  ``wire_dtype`` is
    the deprecated stringly alias and resolves to the byte-identical cast
    codec with a DeprecationWarning.
    """

    ep: EPSpec
    wire_dtype: str = ""          # deprecated: use codec=
    codec: wire_lib.WireCodec | str | None = None

    def __post_init__(self):
        object.__setattr__(
            self, "codec",
            wire_lib.resolve(self.codec, self.wire_dtype, stacklevel=4))

    def dispatch(self, buf, stage: Stage):
        """[*sizes, E_l, C, d] local buffer -> [E_l, prod(sizes)*C, d]
        expert rows, via a chain of all_to_alls (outermost hop first)."""
        if self.codec is None:
            return _dispatch_perm(buf, stage)
        if self.codec.scaled:
            return _dispatch_scaled(self.codec, stage, buf)
        # cast codec: a plain dtype cast around the permutation (autodiff
        # handles the cast, so no straight-through wrapper is needed)
        payload, _ = self.codec.encode(buf, block_ndim=2)
        return self.codec.decode(_dispatch_perm(payload, stage), None,
                                 buf.dtype)

    def dispatch_counts(self, cnt, stage: Stage):
        """[*sizes, E_l] per-(destination, expert) valid-row counts ->
        [E_l, num_dests] per-(expert, source) counts at the receiver.

        Runs the *same* all_to_all chain and transpose as :meth:`dispatch`
        (minus the trailing [C, d] payload dims and the wire-dtype cast —
        counts travel exact), so entry ``[e, g]`` describes exactly the
        ``g``-th capacity chunk of expert ``e``'s delivered rows.  This is
        the tiny metadata exchange that lets the occupancy-aware grouped
        GEMM size its compute by realized tokens."""
        k = len(stage.axis_names)
        for i, ax in enumerate(stage.axis_names):
            cnt = _a2a(cnt, ax, split_axis=i, concat_axis=i)
        perm = (k,) + tuple(range(k))
        return cnt.transpose(perm).reshape(cnt.shape[k], stage.num_dests)

    def combine(self, y, stage: Stage):
        """[E_l, prod(sizes)*C, d] expert outputs -> [*sizes, E_l, C, d]
        back at the source (reverse chain, innermost hop first)."""
        if self.codec is None:
            return _combine_perm(y, stage)
        if self.codec.scaled:
            return _combine_scaled(self.codec, stage, y)
        orig = y.dtype
        payload, _ = self.codec.encode(y, block_ndim=2)
        return self.codec.decode(_combine_perm(payload, stage), None, orig)

    # --- deprecated near/far wrappers (PR-2 compat) ------------------------

    def _stage2(self, index: int) -> Stage:
        names, sizes = self.ep.axis_names, self.ep.axis_sizes
        n = len(names)
        return Stage(index=index, axis_names=names[n - index - 1:],
                     axis_sizes=sizes[n - index - 1:], cap=0)

    def dispatch_near(self, buf):
        """Deprecated: ``dispatch(buf, stage 0)``."""
        return self.dispatch(buf, self._stage2(0))

    def dispatch_far(self, buf):
        """Deprecated: ``dispatch(buf, stage 1)``."""
        return self.dispatch(buf, self._stage2(1))

    def combine_near(self, y):
        """Deprecated: ``combine(y, stage 0)``."""
        return self.combine(y, self._stage2(0))

    def combine_far(self, y):
        """Deprecated: ``combine(y, stage 1)``."""
        return self.combine(y, self._stage2(1))


@dataclasses.dataclass(frozen=True)
class GatherTransport:
    """Weights-stationary transport: gather tokens, psum partial outputs."""

    ep: EPSpec
    tokens_replicated: bool = False   # tokens already on every EP rank

    def gather(self, x):
        """[T_local, d] -> [T_global, d] on every EP rank.

        Gathers innermost axis first so the global order is outermost-major
        rank order — matching the mixed-radix ``my_rank`` numbering."""
        if self.tokens_replicated:
            return x
        for ax in reversed(self.ep.axis_names):
            x = jax.lax.all_gather(x, ax, axis=0, tiled=True)
        return x

    def reduce(self, y):
        """Sum each rank's partial expert outputs across the EP axes."""
        for ax in self.ep.axis_names:
            y = jax.lax.psum(y, ax)
        return y

    def slice_local(self, y, my_rank, T: int):
        """[T_global, d] -> this rank's [T_local, d] slice (no-op when the
        tokens were replicated)."""
        if self.tokens_replicated:
            return y
        return jax.lax.dynamic_slice_in_dim(y, my_rank * T, T, axis=0)
