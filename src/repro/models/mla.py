"""DeepSeek-V2 Multi-head Latent Attention (MLA).

Train/prefill use the expanded form; decode uses the *absorbed* form that
keeps only the compressed latent cache (kv_lora_rank + rope dims per token),
which is the whole point of MLA for long-context serving: the long_500k
cache is 512+64 floats per token instead of 2*K*hd.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    num_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    q_lora_rank: int = 0          # 0 = full-rank q projection
    rope_theta: float = 1e4
    dtype: jnp.dtype = jnp.bfloat16
    use_blockwise: bool = False   # flash-style attention (no S x S scores)

    @property
    def qk_dim(self):
        return self.qk_nope_dim + self.qk_rope_dim


def init_mla(key, cfg: MLAConfig):
    ks = jax.random.split(key, 8)
    d, H = cfg.d_model, cfg.num_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    s = 1.0 / np.sqrt(d)
    p = {
        "w_dkv": layers._norm_init(ks[0], (d, r), s).astype(cfg.dtype),
        "w_uk": layers._norm_init(ks[1], (r, H, dn), 1 / np.sqrt(r)).astype(cfg.dtype),
        "w_uv": layers._norm_init(ks[2], (r, H, dv), 1 / np.sqrt(r)).astype(cfg.dtype),
        "w_kr": layers._norm_init(ks[3], (d, dr), s).astype(cfg.dtype),
        "w_o": layers._norm_init(ks[4], (H * dv, d), 1 / np.sqrt(H * dv)).astype(cfg.dtype),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
    }
    if cfg.q_lora_rank:
        p["w_dq"] = layers._norm_init(ks[5], (d, cfg.q_lora_rank), s).astype(cfg.dtype)
        p["w_uq"] = layers._norm_init(
            ks[6], (cfg.q_lora_rank, H, cfg.qk_dim),
            1 / np.sqrt(cfg.q_lora_rank)).astype(cfg.dtype)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)}
    else:
        p["w_q"] = layers._norm_init(ks[5], (d, H, cfg.qk_dim), s).astype(cfg.dtype)
    return p


def _q_proj(params, x, cfg: MLAConfig):
    if cfg.q_lora_rank:
        cq = layers.norm_apply(params["q_norm"], x @ params["w_dq"], "rmsnorm")
        q = jnp.einsum("bsr,rhd->bshd", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"])
    return q  # [B, S, H, qk_dim]


@jax.named_scope("attn")
def mla_apply(params, x, cfg: MLAConfig, positions=None):
    """Expanded-form MLA for train/prefill.  Returns (out, cache_entry)."""
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    if positions is None:
        positions = jnp.arange(S)

    q = _q_proj(params, x, cfg)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = layers.norm_apply(params["kv_norm"], x @ params["w_dkv"], "rmsnorm")
    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhd->bshd", c_kv, params["w_uv"])
    k_rope = layers.apply_rope((x @ params["w_kr"])[:, :, None, :],
                               positions, cfg.rope_theta)  # [B,S,1,dr]
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, dr))

    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, k_rope_b], -1)
    if cfg.use_blockwise:
        out = layers._blockwise_sdpa(qf, kf, v, causal=True,
                                     sliding_window=0)
        out = out.astype(jnp.float32)
    else:
        scale = 1.0 / np.sqrt(cfg.qk_dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk",
                            qf.astype(jnp.float32) * scale,
                            kf.astype(jnp.float32))
        mask = positions[:, None] >= positions[None, :]
        logits = jnp.where(mask, logits, -1e30)
        w = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    out = out.reshape(B, S, H * dv).astype(x.dtype) @ params["w_o"]
    return out, {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}


def init_mla_cache(batch: int, max_len: int, cfg: MLAConfig):
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), cfg.dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


@jax.named_scope("attn")
def mla_decode(params, x, cache, cfg: MLAConfig):
    """Absorbed-form single-token decode against the compressed cache.

    logits_h(l) = q_abs_h . c_kv(l) + q_rope_h . k_rope(l)
    with q_abs_h = q_nope_h @ w_uk_h  — the k up-projection is absorbed into
    the query, so attention runs in the rank-r latent space.
    """
    B = x.shape[0]
    H, dn, dr = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    pos = cache["pos"]

    q = _q_proj(params, x, cfg)[:, 0]          # [B, H, qk_dim]
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope[:, None].swapaxes(1, 2), pos[:, None],
                               cfg.rope_theta).swapaxes(1, 2)[:, 0]

    c_new = layers.norm_apply(params["kv_norm"],
                              x[:, 0] @ params["w_dkv"], "rmsnorm")
    kr_new = layers.apply_rope((x[:, 0] @ params["w_kr"])[:, None, None, :],
                               pos[:, None], cfg.rope_theta)[:, 0, 0]
    c_kv = jnp.asarray(cache["c_kv"]).at[jnp.arange(B), pos].set(c_new)
    k_rope = jnp.asarray(cache["k_rope"]).at[jnp.arange(B), pos].set(kr_new)

    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope, params["w_uk"])   # [B,H,r]
    scale = 1.0 / np.sqrt(cfg.qk_dim)
    logits = (jnp.einsum("bhr,blr->bhl", q_abs.astype(jnp.float32),
                         c_kv.astype(jnp.float32))
              + jnp.einsum("bhd,bld->bhl", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    L = c_kv.shape[1]
    valid = jnp.arange(L)[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, -1)
    ctx = jnp.einsum("bhl,blr->bhr", w, c_kv.astype(jnp.float32))  # latent ctx
    out = jnp.einsum("bhr,rhd->bhd", ctx, params["w_uv"].astype(jnp.float32))
    out = out.reshape(B, 1, -1).astype(x.dtype) @ params["w_o"]
    return out, {"c_kv": c_kv, "k_rope": k_rope, "pos": pos + 1}
