"""Public fused local-MoE entry with backend + autodiff policy.

:func:`local_moe` is the permute-free hot path for *local* dispatch
traffic: one kernel call takes the raw [T, d] token buffer plus the
flattened sort indices (``DispatchIndices.slot_to_token`` / ``slot_w``
and the static segment layout with its runtime ``rows_per_expert``
occupancy) and returns the [T, d] combined output — no sorted [S, d]
capacity buffer in HBM, no separate permute / unpermute round trips.

Backend selection is the shared ``repro.kernels.backend`` policy (the
same ``kernels_active`` decision moe_permute and moe_gemm resolve
through, so one engine call can never mix fused and unfused layers
across backends).  The kernel-off path and the ``custom_vjp`` backward
both run :func:`ref.local_moe_ref` — plain differentiable jnp — so
training and CPU CI work unchanged, and gate-weight gradients flow
through the fused combine multiply exactly as they do through
``unpermute``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import (float0 as _float0,
                                   interpret_mode as _interpret,
                                   kernels_active as _kernels_active)
from repro.kernels.moe_fused import kernel
from repro.kernels.moe_fused.ref import local_moe_ref
from repro.kernels.moe_gemm import ops as gemm_ops


def use_fused(use_pallas=None) -> bool:
    """Whether the fused megakernel is active for this flag — the shared
    ``kernels_active`` decision, so it can never disagree with
    ``moe_gemm.ops.use_ragged`` / the moe_permute entries."""
    return _kernels_active(use_pallas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_pallas(static, x, slot_to_token, slot_w, rows_valid, w_in,
                  w_gate, w_out):
    seg_offsets, seg_experts, activation, block_c, block_f, interpret = static
    bc, brow, beid, bseg, bloc = gemm_ops.plan_blocks(seg_offsets,
                                                      seg_experts, block_c)
    nvalid = jnp.clip(jnp.take(jnp.asarray(rows_valid, jnp.int32),
                               jnp.asarray(bseg)) - jnp.asarray(bloc),
                      0, bc).astype(jnp.int32)
    return kernel.local_moe_pallas(
        x, slot_to_token, slot_w, jnp.asarray(brow),
        jnp.asarray(beid), nvalid, w_in, w_gate, w_out,
        activation=activation, block_c=bc, block_f=block_f,
        interpret=interpret)


def _fused_fwd(static, x, slot_to_token, slot_w, rows_valid, w_in, w_gate,
               w_out):
    y = _fused_pallas(static, x, slot_to_token, slot_w, rows_valid, w_in,
                      w_gate, w_out)
    return y, (x, slot_to_token, slot_w, rows_valid, w_in, w_gate, w_out)


def _fused_bwd(static, res, g):
    seg_offsets, seg_experts, activation, *_ = static
    x, slot_to_token, slot_w, rows_valid, w_in, w_gate, w_out = res

    def f(x_, sw_, wi_, wg_, wo_):
        return local_moe_ref(
            x_, slot_to_token, sw_, seg_offsets, seg_experts, rows_valid,
            wi_, wg_ if activation == "swiglu" else None, wo_,
            activation=activation)

    _, vjp = jax.vjp(f, x, slot_w, w_in, w_gate, w_out)
    gx, gsw, gwi, gwg, gwo = vjp(g.astype(jnp.float32))
    return (gx, _float0(slot_to_token), gsw, _float0(rows_valid), gwi, gwg,
            gwo)


_fused_pallas.defvjp(_fused_fwd, _fused_bwd)


def local_moe(x, slot_to_token, slot_w, seg_offsets, seg_experts, rows_valid,
              w_in, w_gate, w_out, *, activation: str = "swiglu",
              block_c: int = 128, block_f: int = 256, use_pallas=None):
    """Fused dispatch→GEMM→combine over local traffic.

    x: [T, d] raw tokens; ``slot_to_token`` [S] / ``slot_w`` [S] are the
    flat sort-order maps ``routing.build_indices`` emits (sentinel ``T``
    marks empty slots, whose weight is 0); ``seg_offsets`` (static
    [n + 1]) / ``seg_experts`` (static [n]) describe the contiguous
    capacity segments of slot space and ``rows_valid`` (runtime [n]
    int32, or None = fully occupied) each segment's realized rows —
    identical contracts to ``moe_gemm.ops.grouped_ffn_ragged``.  Returns
    the [T, d] float32 combined output; on the kernel path the sorted
    [S, d] buffer is never materialized.
    """
    offs = tuple(int(o) for o in seg_offsets)
    exps = tuple(int(e) for e in seg_experts)
    S = slot_to_token.shape[0]
    assert len(offs) == len(exps) + 1 and offs[0] == 0 and offs[-1] == S, \
        (offs, len(exps), S)
    swiglu = activation == "swiglu" and w_gate is not None
    if rows_valid is None:
        rows_valid = jnp.asarray(
            [offs[s + 1] - offs[s] for s in range(len(exps))], jnp.int32)
    if not use_fused(use_pallas) or S == 0:
        return local_moe_ref(x, slot_to_token, slot_w, offs, exps,
                             rows_valid, w_in, w_gate if swiglu else None,
                             w_out, activation=activation)
    wg = w_gate if swiglu else w_in   # placeholder, un-grad-ed by gelu
    static = (offs, exps, "swiglu" if swiglu else "gelu",
              int(block_c), int(block_f), _interpret())
    return _fused_pallas(static, x, slot_to_token.astype(jnp.int32),
                         slot_w.astype(jnp.float32), rows_valid, w_in, wg,
                         w_out)
