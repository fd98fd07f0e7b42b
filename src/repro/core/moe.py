"""Deprecated compatibility shim over :mod:`repro.core.dispatch`.

The MoE layer used to live here as four hand-rolled dispatch functions;
it is now the composable ``core/dispatch`` package (routing / transport /
schedule / engine).  This module keeps the old import surface working —
``MoEConfig``, ``EPSpec``, parameter init/specs, the expert FFNs, the
``software_pipeline`` skeleton, and ``moe_apply_*`` wrappers that resolve
through the :class:`~repro.core.dispatch.DispatchEngine` registry.

New code should import from ``repro.core.dispatch`` directly (or go through
``models/transformer._moe_block``, which already does); each ``moe_apply_*``
wrapper emits a ``DeprecationWarning`` on use.  Note one schema change the
wrappers inherit: every path now returns the uniform metrics dict
``("aux_loss", "frac_by_level", "dropped")`` — ``frac_by_level`` is the
level-indexed vector.
"""

from __future__ import annotations

import warnings

from repro.core import dispatch as _dispatch
from repro.core.dispatch import (          # noqa: F401  (re-exports)
    EPSpec,
    MoEConfig,
    expert_ffn,
    init_moe_params,
    moe_param_specs,
    shared_ffn,
    software_pipeline,
)
from repro.core.dispatch.base import _act  # noqa: F401  (legacy private name)
from repro.core.dispatch.routing import (  # noqa: F401  (legacy private names)
    pad_selection as _pad_selection,
    route as _route,
    score_matrix as _score_matrix,
    select as _select,
)
from repro.core.dispatch.transport import wire_a2a as _a2a  # noqa: F401


def _deprecated(wrapper: str, path: str):
    warnings.warn(
        f"repro.core.moe.{wrapper} is deprecated; use "
        f"repro.core.dispatch.dispatch_moe({path!r}, ...) or make_engine "
        f"instead", DeprecationWarning, stacklevel=3)


def moe_apply_a2a(params, x, cfg, ep, plan, gate_cfg):
    """x: [T_local, d] inside shard_map. Returns (y, metrics)."""
    _deprecated("moe_apply_a2a", "a2a")
    return _dispatch.dispatch_moe("a2a", params, x, cfg=cfg, ep=ep,
                                  gate_cfg=gate_cfg, plan=plan)


def moe_apply_a2a_pipelined(params, x, cfg, ep, plan, gate_cfg,
                            num_chunks: int = 2):
    """Chunked, software-pipelined variant of :func:`moe_apply_a2a`."""
    _deprecated("moe_apply_a2a_pipelined", "a2a_pipelined")
    return _dispatch.dispatch_moe("a2a_pipelined", params, x, cfg=cfg, ep=ep,
                                  gate_cfg=gate_cfg, plan=plan,
                                  num_chunks=num_chunks)


def moe_apply_gather(params, x, cfg, ep, gate_cfg,
                     tokens_replicated: bool = False):
    """Decode-time MoE: weights stationary, tokens gathered."""
    _deprecated("moe_apply_gather", "gather")
    return _dispatch.dispatch_moe("gather", params, x, cfg=cfg, ep=ep,
                                  gate_cfg=gate_cfg,
                                  tokens_replicated=tokens_replicated)


def moe_apply_einsum(params, x, cfg, ep, gate_cfg,
                     capacity: int | None = None):
    """GShard/DeepSpeed einsum baseline (paper §2)."""
    _deprecated("moe_apply_einsum", "einsum")
    return _dispatch.dispatch_moe("einsum", params, x, cfg=cfg, ep=ep,
                                  gate_cfg=gate_cfg, capacity=capacity)
