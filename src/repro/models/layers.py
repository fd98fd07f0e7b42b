"""Shared transformer building blocks (pure-functional JAX).

Conventions: params are plain dict pytrees; ``init_*`` builds them,
``*_apply`` consumes them.  Activations default to bf16, norms/softmax in
f32.  Sharding is applied by the caller (pjit constraints / param specs) —
these functions are mesh-agnostic except where noted.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _norm_init(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(kind: str, d: int):
    if kind == "nonparam_ln":      # OLMo: LayerNorm without scale/bias
        return {}
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    raise ValueError(kind)


def norm_apply(params, x, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = y * params["scale"]
    else:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: [..., S, H, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1e4
    sliding_window: int = 0      # 0 = full attention
    causal: bool = True
    qkv_bias: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    use_flash_kernel: bool = False
    use_blockwise: bool = False      # flash-style jnp path (dry-run perf)


def init_attn(key, cfg: AttnConfig):
    ks = jax.random.split(key, 4)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": _norm_init(ks[0], (d, H * hd), s).astype(cfg.dtype),
        "wk": _norm_init(ks[1], (d, K * hd), s).astype(cfg.dtype),
        "wv": _norm_init(ks[2], (d, K * hd), s).astype(cfg.dtype),
        "wo": _norm_init(ks[3], (H * hd, d), 1.0 / np.sqrt(H * hd)).astype(cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((K * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((K * hd,), cfg.dtype)
    return p


def _qkv(params, x, cfg: AttnConfig):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (q.reshape(B, S, H, hd), k.reshape(B, S, K, hd),
            v.reshape(B, S, K, hd))


def _sdpa(q, k, v, *, causal, sliding_window, q_positions, k_positions):
    """Reference scaled-dot-product attention with GQA + optional window.

    q: [B, Sq, H, hd]; k/v: [B, Sk, K, hd]. Returns [B, Sq, H, hd].
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.astype(jnp.float32) / np.sqrt(hd)
    qg = qf.reshape(B, Sq, K, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32))
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_positions[:, None] >= k_positions[None, :]
    if sliding_window:
        mask &= (q_positions[:, None] - k_positions[None, :]) < sliding_window
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _blockwise_sdpa(q, k, v, *, causal, sliding_window, block_k: int = 1024):
    """Flash-style online-softmax attention in pure jnp: never materializes
    the [Sq, Sk] score matrix — memory is O(Sq * block_k).  This is the
    HLO-level analogue of kernels/flash_attn for the dry-run (Pallas cannot
    lower on the CPU backend); on TPU the Pallas kernel takes over.
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    hdv = v.shape[-1]                 # may differ from hd (MLA)
    G = H // K
    bk = min(block_k, Sk)
    nk = -(-Sk // bk)
    qf = (q.astype(jnp.float32) / np.sqrt(hd)).reshape(B, Sq, K, G, hd)
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, nk * bk - Sk),
                                         (0, 0), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, nk * bk - Sk),
                                         (0, 0), (0, 0)))
    kb = kp.reshape(B, nk, bk, K, hd).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, bk, K, hdv).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Sq)

    def step(carry, inp):
        o, m, l, j = carry
        kj, vj = inp
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kj)      # [B,K,G,Sq,bk]
        kpos = j * bk + jnp.arange(bk)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if sliding_window:
            mask &= (qpos[:, None] - kpos[None, :]) < sliding_window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new[..., None]),
                      0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        o = o * alpha[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, vj)
        return (o, m_new, l, j + 1), None

    o0 = jnp.zeros((B, K, G, Sq, hdv), jnp.float32)
    m0 = jnp.full((B, K, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    (o, m, l, _), _ = jax.lax.scan(step, (o0, m0, l0, jnp.int32(0)),
                                   (kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    o = (o / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hdv)
    return o.astype(q.dtype)


@jax.named_scope("attn")
def attn_apply(params, x, cfg: AttnConfig, positions=None):
    """Full-sequence (train / prefill) attention. x: [B, S, d]."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    if positions is None:
        positions = jnp.arange(S)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_flash_kernel:
        from repro.kernels.flash_attn import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=cfg.causal,
                                     sliding_window=cfg.sliding_window)
    elif cfg.use_blockwise:
        out = _blockwise_sdpa(q, k, v, causal=cfg.causal,
                              sliding_window=cfg.sliding_window)
    else:
        out = _sdpa(q, k, v, causal=cfg.causal,
                    sliding_window=cfg.sliding_window,
                    q_positions=positions, k_positions=positions)
    return out.reshape(B, S, -1) @ params["wo"], (k, v)


@jax.named_scope("attn")
def attn_decode(params, x, cache, cfg: AttnConfig):
    """Single-token decode vs a KV cache.

    x: [B, 1, d]; cache: {"k": [B, L, K, hd], "v": ..., "pos": [B]}.
    The cache position axis may be sharded (context parallelism) — the
    softmax reductions lower to collectives under pjit automatically.
    """
    B = x.shape[0]
    q, k_new, v_new = _qkv(params, x, cfg)
    pos = cache["pos"]  # [B] current length
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
    L = cache["k"].shape[1]
    idx = pos  # write position
    k = jax.lax.select(
        jnp.ones((), bool),
        jnp.asarray(cache["k"]).at[jnp.arange(B), idx].set(k_new[:, 0]),
        cache["k"])
    v = jnp.asarray(cache["v"]).at[jnp.arange(B), idx].set(v_new[:, 0])
    k_positions = jnp.arange(L)
    valid = k_positions[None, :] <= pos[:, None]          # [B, L]
    if cfg.sliding_window:
        valid &= (pos[:, None] - k_positions[None, :]) < cfg.sliding_window
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    qg = (q.astype(jnp.float32) / np.sqrt(hd)).reshape(B, K, G, hd)
    logits = jnp.einsum("bkgh,blkh->bkgl", qg, k.astype(jnp.float32))
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", w, v.astype(jnp.float32))
    out = out.reshape(B, 1, H * hd).astype(x.dtype)
    new_cache = {"k": k, "v": v, "pos": pos + 1}
    return out @ params["wo"], new_cache


def init_kv_cache(batch: int, max_len: int, cfg: AttnConfig):
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, K, hd), cfg.dtype),
            "v": jnp.zeros((batch, max_len, K, hd), cfg.dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, f: int, activation: str, dtype):
    ks = jax.random.split(key, 3)
    s1, s2 = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {"w_in": _norm_init(ks[0], (d, f), s1).astype(dtype),
         "w_out": _norm_init(ks[1], (f, d), s2).astype(dtype)}
    if activation == "swiglu":
        p["w_gate"] = _norm_init(ks[2], (d, f), s1).astype(dtype)
    return p


def mlp_apply(params, x, activation: str):
    if activation == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_in"])
    else:
        h = jax.nn.gelu(x @ params["w_in"])
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def init_embed(key, vocab: int, d: int, dtype):
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32)
            .astype(dtype) * 0.02}


def embed_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed_apply(params, x):
    # logits in f32 for a stable softmax-xent
    return x.astype(jnp.float32) @ params["table"].T.astype(jnp.float32)
