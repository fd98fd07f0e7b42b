"""Operations and bytes the model needs, worked out from its shapes.

A multiply-add is 2 operations.  Model FLOPs count what the model needs:
the routed experts a token picks (top_k of them), the shared experts, the
LM head, and attention scores over the causal context -- not recompute, not
padding, not capacity slack.  Bytes are bf16 weights (2 per element) and
the activations a kernel must read and write.
"""

from __future__ import annotations

from bench.reference.moe_lm import Spec

BF16 = 2


def attn_proj_params(s: Spec) -> int:
    if s.mla:
        r, dn, dr, dv = s.mla
        return (s.d * s.heads * (dn + dr) + s.d * r + s.d * dr
                + r * s.heads * dn + r * s.heads * dv + s.heads * dv * s.d)
    return 4 * s.d * s.heads * s.head_dim


def ffn_mats(s: Spec) -> int:
    return 3 if s.act == "silu" else 2


def dense_ffn_params(s: Spec) -> int:
    return ffn_mats(s) * s.d * s.d_ff


def expert_params(s: Spec) -> int:
    """One routed expert's weights."""
    return ffn_mats(s) * s.d * s.d_ff_expert


def moe_active_params(s: Spec) -> int:
    """Weights one token multiplies in an MoE layer: router, its top_k
    experts and the shared ones."""
    return s.d * s.experts + (s.top_k + s.shared) * expert_params(s)


def n_moe(s: Spec) -> int:
    return sum(s.is_moe(i) for i in range(s.layers))


def matmul_params_per_token(s: Spec, head: bool = True) -> int:
    m = n_moe(s)
    p = (s.layers * attn_proj_params(s) + (s.layers - m) * dense_ffn_params(s)
         + m * moe_active_params(s))
    return p + (s.d * s.vocab if head else 0)


def attn_flops(s: Spec, context: float) -> float:
    """Forward attention-score and value FLOPs of one token that attends to
    ``context`` positions, over all layers (MLA in its expanded form)."""
    if s.mla:
        r, dn, dr, dv = s.mla
        per = 2 * s.heads * context * ((dn + dr) + dv)
    else:
        per = 2 * s.heads * context * 2 * s.head_dim
    return s.layers * per


def decode_attn_flops(s: Spec, context: float) -> float:
    """Forward attention FLOPs of one decoded token against ``context``
    cached positions; MLA decodes in the absorbed form (scores over the
    latent and rope parts, the context in the latent; the absorbed
    up-projections are counted with the weights)."""
    if s.mla:
        r, dn, dr, dv = s.mla
        return s.layers * 2 * s.heads * context * ((r + dr) + r)
    return attn_flops(s, context)


def train_flops_per_token(s: Spec, seq_len: int) -> float:
    """Forward + backward (3x forward) model FLOPs per trained token, the
    causal context averaging (seq_len + 1) / 2."""
    fwd = 2 * matmul_params_per_token(s) + attn_flops(s, (seq_len + 1) / 2)
    return 3 * fwd


def prefill_flops(s: Spec, prompt_len: int) -> float:
    """Forward FLOPs of one prompt: every position through the layers, the
    LM head at the last position only."""
    return (prompt_len * 2 * matmul_params_per_token(s, head=False)
            + prompt_len * attn_flops(s, (prompt_len + 1) / 2)
            + 2 * s.d * s.vocab)


def decode_flops(s: Spec, context: float) -> float:
    """Forward FLOPs of one decoded token at ``context`` positions."""
    return 2 * matmul_params_per_token(s) + decode_attn_flops(s, context)


# ---------------------------------------------------------------------------
# MoE expert work, for the kernels' roofline
# ---------------------------------------------------------------------------


def expert_flops(s: Spec, assignments: float) -> float:
    """Forward FLOPs of ``assignments`` (token, routed expert) pairs."""
    return assignments * 2 * expert_params(s)


def expert_call_bytes(s: Spec, tokens: float) -> float:
    """Bytes one MoE-layer call must move: every routed expert's weights
    (bf16), the tokens in (bf16) and the combined output (f32)."""
    return (s.experts * expert_params(s) * BF16
            + tokens * s.d * BF16 + tokens * s.d * 4)


# ---------------------------------------------------------------------------
# a decode step, for its roofline
# ---------------------------------------------------------------------------


def weight_bytes(s: Spec) -> float:
    """Every weight a decode step reads: all layers with all routed experts
    (64 tokens x top_k picks touch nearly every expert), the router, the
    shared experts, and the tied LM head once.  The embedding lookup reads
    a few rows only and is not counted again."""
    m = n_moe(s)
    layer = (s.layers * attn_proj_params(s) + (s.layers - m) * dense_ffn_params(s)
             + m * (s.d * s.experts + (s.experts + s.shared) * expert_params(s)))
    return (layer + s.d * s.vocab) * BF16


def cache_row_bytes(s: Spec) -> float:
    """Cache bytes of one position over all layers."""
    if s.mla:
        r, dn, dr, dv = s.mla
        return s.layers * (r + dr) * BF16
    return s.layers * 2 * s.heads * s.head_dim * BF16
