"""Plain float32 reference of a pre-norm decoder with MoE FFN layers.

It covers the two families the benchmark runs: GPT-3 style blocks (layer
norm, GELU, multi-head attention with RoPE) and DeepSeek-V2 style blocks
(RMS norm, SwiGLU, multi-head latent attention, shared experts).  Widths
come from the configuration file, never from the program; weights are the
benchmark's own, made from the seed (``bench/weights.py``), and are read by
their path in the parameter tree the program is handed.

Every matrix product runs through :func:`mm`, in float32 at the
``highest`` matmul precision, or -- for the control -- on operands
rounded to float8 e4m3 with a per-tensor scale, the two products of its
backward pass too (their incoming gradient rounded the same way), with
float32 accumulation.

The MoE layer follows the routing the configuration states: softmax gate
in float32, top-k, weights renormalised over the k picks, and -- where a
capacity is given -- each expert keeps the ``capacity`` tokens with the
largest combine weight among those that picked it.  With no capacity the
layer is drop-free.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    heads: int
    head_dim: int
    layers: int
    vocab: int
    d_ff: int
    experts: int
    top_k: int
    d_ff_expert: int
    shared: int
    first_dense: int
    moe_every: int
    norm: str                     # layernorm | rmsnorm
    norm_eps: float
    act: str                      # gelu_tanh | silu (SwiGLU)
    rope_theta: float
    renorm_topk: bool
    capacity_factor: float
    mla: tuple = ()               # (kv_lora_rank, qk_nope, qk_rope, v_dim)

    def is_moe(self, i: int) -> bool:
        j = i - self.first_dense
        return j >= 0 and j % self.moe_every == self.moe_every - 1


def spec(cfg: dict) -> Spec:
    """Reads the configuration file's keys (HF names where the model has
    a published config.json)."""
    rs = cfg.get("rope_scaling")
    if rs is not None and not (rs.get("type") == "yarn" and rs["factor"] <= 1):
        # YaRN at factor 1 leaves the frequencies and the softmax scale as
        # they are; any other scaling is not plain RoPE, which is all this
        # reference runs.
        raise NotImplementedError(f"rope_scaling {rs}")
    mla = ()
    if cfg.get("kv_lora_rank"):
        mla = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
               cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    heads = cfg["num_attention_heads"]
    return Spec(
        d=cfg["hidden_size"], heads=heads,
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        d_ff=cfg["intermediate_size"],
        experts=cfg.get("n_routed_experts") or cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_ff_expert=cfg["moe_intermediate_size"],
        shared=cfg.get("n_shared_experts") or 0,
        first_dense=cfg.get("first_k_dense_replace", 0),
        moe_every=cfg.get("moe_layer_freq", 1),
        norm=cfg.get("norm", "rmsnorm"),
        norm_eps=cfg.get("rms_norm_eps", cfg.get("norm_eps", 1e-5)),
        act=cfg["hidden_act"], rope_theta=cfg["rope_theta"],
        renorm_topk=bool(cfg.get("norm_topk_prob", True)),
        capacity_factor=cfg.get("capacity_factor", 0.0), mla=mla)


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------

F8_MAX = 448.0      # largest finite float8_e4m3fn


def fp8(x):
    """Round to float8 e4m3 with a per-tensor absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(eq, a, b):
    return _einsum(eq, fp8(a), fp8(b))


def _mm_fp8_fwd(eq, a, b):
    qa, qb = fp8(a), fp8(b)
    return _einsum(eq, qa, qb), (qa, qb)


def _mm_fp8_bwd(eq, res, g):
    qa, qb = res
    return jax.vjp(functools.partial(_einsum, eq), qa, qb)[1](fp8(g))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(eq, a, b, prec):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        return _mm_fp8(eq, a, b)
    return _einsum(eq, a, b)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def norm(p, x, s: Spec):
    if s.norm == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s.norm_eps)
        return y * p["scale"]
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + s.norm_eps) * p["scale"] + p["bias"]


def rope(x, theta):
    """Rotary embedding on interleaved pairs; x [B, S, H, r]."""
    r = x.shape[-1]
    S = x.shape[1]
    freqs = 1.0 / (theta ** (np.arange(0, r, 2) / r))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _causal_softmax_av(q, k, v, scale, prec):
    """q [B,S,H,e], k [B,S,H,e], v [B,S,H,f] -> [B,S,H,f], one head at a
    time so the score matrix stays [B, S, S]."""
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def head(args):
        qh, kh, vh = args
        logits = mm("bqe,bke->bqk", qh, kh, prec) * scale
        w = jax.nn.softmax(jnp.where(mask, logits, -1e30), -1)
        return mm("bqk,bkf->bqf", w, vh, prec)
    out = jax.lax.map(head, (q.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
                             v.transpose(2, 0, 1, 3)))
    return out.transpose(1, 2, 0, 3)


def attention(p, x, s: Spec, prec):
    B, S, _ = x.shape
    if s.mla:
        r, dn, dr, dv = s.mla
        q = mm("bsd,dhe->bshe", x, p["w_q"], prec)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], s.rope_theta)], -1)
        c = mm("bsd,dr->bsr", x, p["w_dkv"], prec)
        c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + s.norm_eps)
        c = c * p["kv_norm"]["scale"]
        k_nope = mm("bsr,rhd->bshd", c, p["w_uk"], prec)
        v = mm("bsr,rhd->bshd", c, p["w_uv"], prec)
        k_rope = rope(mm("bsd,de->bse", x, p["w_kr"], prec)[:, :, None, :],
                      s.rope_theta)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, s.heads, dr))], -1)
        o = _causal_softmax_av(q, k, v, 1.0 / math.sqrt(dn + dr), prec)
        return mm("bsf,fd->bsd", o.reshape(B, S, -1), p["w_o"], prec)
    H, hd = s.heads, s.head_dim
    q = mm("bsd,df->bsf", x, p["wq"], prec).reshape(B, S, H, hd)
    k = mm("bsd,df->bsf", x, p["wk"], prec).reshape(B, S, H, hd)
    v = mm("bsd,df->bsf", x, p["wv"], prec).reshape(B, S, H, hd)
    q, k = rope(q, s.rope_theta), rope(k, s.rope_theta)
    o = _causal_softmax_av(q, k, v, 1.0 / math.sqrt(hd), prec)
    return mm("bsf,fd->bsd", o.reshape(B, S, -1), p["wo"], prec)


def _act(s: Spec, x, w_in, w_gate, prec, eq):
    if s.act == "silu":
        return jax.nn.silu(mm(eq, x, w_gate, prec)) * mm(eq, x, w_in, prec)
    return jax.nn.gelu(mm(eq, x, w_in, prec), approximate=True)


def mlp(p, x, s: Spec, prec):
    h = _act(s, x, p["w_in"], p.get("w_gate"), prec, "...d,df->...f")
    return mm("...f,fd->...d", h, p["w_out"], prec)


def moe(p, x, s: Spec, prec, capacity=None):
    """x [T, d] -> (y [T, d], aux).  ``capacity`` None = drop-free."""
    T = x.shape[0]
    N, K = s.experts, s.top_k
    logits = mm("td,dn->tn", x, p["gate"]["w"], prec)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, K)
    if K > 1 and s.renorm_topk:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-9)
    counts = jnp.zeros((N,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    aux = N * jnp.sum(probs.mean(0) * counts / (T * K))
    weights = jnp.zeros((T, N), jnp.float32).at[
        jnp.arange(T)[:, None], top_i].set(top_w)           # [T, N]
    has_gate = "w_gate" in p
    if capacity is None or capacity >= T:
        def expert(y, e):
            w_in = p["w_in"][e]
            w_gate = p["w_gate"][e] if has_gate else None
            h = _act(s, x, w_in, w_gate, prec, "td,df->tf")
            ye = mm("tf,fd->td", h, p["w_out"][e], prec)
            return y + ye * weights[:, e][:, None], None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(N))
    else:
        score = jnp.where(weights.T > 0, weights.T, -1.0)   # [N, T]
        w_sel, idx = jax.lax.top_k(score, capacity)         # [N, C]
        keep = jnp.where(w_sel > 0, w_sel, 0.0)
        xin = x[idx]                                        # [N, C, d]
        h = _act(s, xin, p["w_in"], p.get("w_gate"), prec, "ncd,ndf->ncf")
        ye = mm("ncf,nfd->ncd", h, p["w_out"], prec) * keep[..., None]
        y = jnp.zeros_like(x).at[idx.reshape(-1)].add(ye.reshape(-1, s.d))
    if s.shared:
        y = y + mlp({"w_in": p["shared_in"], "w_out": p["shared_out"],
                     "w_gate": p.get("shared_gate")}, x, s, prec)
    return y, aux


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def layer_params(params, s: Spec, i: int):
    """The program's layout: leading dense layers as ``prefix<i>``, the
    rest stacked by period under ``groups/sub<j>``."""
    if i < s.first_dense:
        return params[f"prefix{i}"]
    g, j = divmod(i - s.first_dense, s.moe_every)
    return jax.tree_util.tree_map(lambda a: a[g], params["groups"][f"sub{j}"])


def features(params, tokens, s: Spec, prec, capacity=None, remat=False):
    """tokens [B, S] -> (final-normed features [B, S, d], mean aux).
    Weights are read in their stored dtype and widened where they are
    used, so no float32 copy of the whole tree is held."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    B, S = tokens.shape
    aux = jnp.float32(0.0)

    def block(x, p, i):
        x = x + attention(p["mixer"], norm(p["norm1"], x, s), s, prec)
        h = norm(p["norm2"], x, s)
        if s.is_moe(i):
            y, a = moe(p["ffn"], h.reshape(B * S, s.d), s, prec, capacity)
            return x + y.reshape(B, S, s.d), a
        return x + mlp(p["ffn"], h, s, prec), jnp.float32(0.0)

    for i in range(s.layers):
        f = jax.checkpoint(block, static_argnums=(2,)) if remat else block
        x, a = f(x, layer_params(params, s, i), i)
        aux = aux + a
    # the mean runs over the layers after the leading dense ones
    aux = aux / max(1, s.layers - s.first_dense)
    return norm(params["final_norm"], x, s), aux


def logits(params, feats, prec):
    """Tied LM head: features [..., d] -> float32 logits [..., V]."""
    table = params["embed"]["table"].astype(jnp.float32)
    return mm("...d,vd->...v", feats, table, prec)


def lm_loss(params, batch, s: Spec, prec, capacity, aux_weight,
            row_block: int = 2048):
    """Mean next-token NLL over the batch plus ``aux_weight`` x aux; the LM
    head runs over ``row_block`` rows at a time."""
    feats, aux = features(params, batch["tokens"], s, prec, capacity,
                          remat=True)
    table = params["embed"]["table"].astype(jnp.float32)
    f = feats.reshape(-1, s.d)
    lab = batch["labels"].reshape(-1)
    n = f.shape[0] // row_block

    @jax.checkpoint
    def block_nll(args):
        fb, lb = args
        lg = mm("td,vd->tv", fb, table, prec)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0])
    nll = jax.lax.map(block_nll, (f.reshape(n, row_block, s.d),
                                  lab.reshape(n, row_block))).sum()
    nll = nll / f.shape[0]
    return nll + aux_weight * aux, (nll, aux)


def capacity_for(s: Spec, tokens: int) -> int:
    """GShard capacity: top_k x tokens x capacity factor / experts, up to a
    multiple of 8."""
    c = s.top_k * tokens * s.capacity_factor / s.experts
    return max(8, int(math.ceil(c / 8)) * 8)


# ---------------------------------------------------------------------------
# AdamW as the run states it
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["learning_rate"] * warm * (0.1 + 0.9 * cos)


B1, B2, EPS = 0.9, 0.95, 1e-8


def step_scalars(opt: dict, step: int) -> tuple:
    """(lr, 1 - b1^step, 1 - b2^step) of the 1-based ``step``."""
    return lr_at(opt, step), 1 - B1 ** step, 1 - B2 ** step


def adamw(params, grads, mu, nu, lr, c1, c2, opt: dict, stored):
    """One AdamW step over flat leaf lists (global-norm clip, decay on
    leaves of rank 2 or more).  Params are float32 holding values of their
    ``stored`` dtype, and are rounded to it after the update, as the run
    keeps them.  Returns (params, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    out = [[], [], [], []]
    for p, g, m, v, dt in zip(params, grads, mu, nu, stored):
        g = g * scale
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        delta = (m / c1) / (jnp.sqrt(v / c2) + EPS)
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        for lst, val in zip(out, ((p - lr * delta).astype(dt).astype(
                jnp.float32), m, v, g)):
            lst.append(val)
    return tuple(out)
