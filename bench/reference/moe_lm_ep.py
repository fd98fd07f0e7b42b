"""The plain float32 reference (``moe_lm``) with its MoE layers split by
source chip, for cells that spread the experts over several chips.

In an expert-parallel step each chip routes only its own rows of the
batch (the rows ``shard_batch`` gives it), and its tokens compete for that
chip's own per-expert capacity.  So here the global batch is split into
``sources`` equal parts in row order, and for each source and each expert
the ``capacity`` picks with the largest combine weights are kept.  The aux
loss is ``N sum_e p_level(c, e) m_e f_e`` over each source c's tokens --
level 0 for the experts held on c (``experts / sources`` of them, in
order), level 1 for the rest -- and is averaged over the sources, as the
program's ``pmean`` over the mesh does.  With one source this is
``moe_lm``'s layer, operation for operation.

``local_only`` (a bool, or a traced one, so that one compiled step serves
both) is a fault: each source's tokens reach only the experts on its own
chip, which is what a program that left out the all-to-all would compute.
The router and the aux loss are as in a sound run.

Everything else (attention, norms, the dense FFN, the LM head, AdamW)
comes from ``moe_lm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import moe_lm
from bench.reference.moe_lm import Spec, mm


def own_experts(s: Spec, sources: int):
    """[sources, experts] True where the expert is held on the source's
    chip (experts in order, ``experts / sources`` a chip)."""
    held = s.experts // sources
    return (jnp.arange(s.experts)[None, :] // held
            == jnp.arange(sources)[:, None])


def moe(p, x, s: Spec, prec, capacity=None, *, sources: int = 1,
        penalties=(1.0, 1.0), local_only: bool = False):
    """x [T, d], the sources' rows one after another -> (y [T, d], aux).
    ``capacity`` is per (source, expert); None = drop-free."""
    T, d = x.shape
    N, K = s.experts, s.top_k
    xs = x.reshape(sources, T // sources, d)
    Tc = xs.shape[1]
    logits = mm("ctd,dn->ctn", xs, p["gate"]["w"], prec)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, K)
    if K > 1 and s.renorm_topk:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-9)
    src = jnp.arange(sources)[:, None, None]
    counts = jnp.zeros((sources, N), jnp.float32).at[src, top_i].add(1.0)
    own = own_experts(s, sources)
    pen = jnp.where(own, penalties[0], penalties[1])
    aux = jnp.mean(N * jnp.sum(pen * probs.mean(1) * counts / (Tc * K), -1))
    weights = jnp.zeros((sources, Tc, N), jnp.float32).at[
        src, jnp.arange(Tc)[None, :, None], top_i].set(top_w)  # [C, Tc, N]
    if local_only is not False:     # a bool, or traced at run time
        weights = jnp.where(own[:, None, :] | jnp.logical_not(local_only),
                            weights, 0.0)
    has_gate = "w_gate" in p
    if capacity is None or capacity >= Tc:
        def expert(y, e):
            w_in = p["w_in"][e]
            w_gate = p["w_gate"][e] if has_gate else None
            h = moe_lm._act(s, xs, w_in, w_gate, prec, "ctd,df->ctf")
            ye = mm("ctf,fd->ctd", h, p["w_out"][e], prec)
            return y + ye * weights[:, :, e][..., None], None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(xs), jnp.arange(N))
    else:
        score = jnp.where(weights > 0, weights, -1.0).transpose(0, 2, 1)
        w_sel, idx = jax.lax.top_k(score, capacity)         # [C, N, cap]
        # each expert's kept rows of x, source after source: [N, C x cap]
        idx = (idx + Tc * src).transpose(1, 0, 2).reshape(N, -1)
        keep = jnp.where(w_sel > 0, w_sel, 0.0).transpose(1, 0, 2).reshape(
            N, -1)
        xin = x[idx]                                        # [N, C cap, d]
        h = moe_lm._act(s, xin, p["w_in"], p.get("w_gate"), prec,
                        "ncd,ndf->ncf")
        ye = mm("ncf,nfd->ncd", h, p["w_out"], prec) * keep[..., None]
        y = jnp.zeros_like(x).at[idx.reshape(-1)].add(ye.reshape(-1, d))
    y = y.reshape(T, d)
    if s.shared:
        y = y + moe_lm.mlp({"w_in": p["shared_in"], "w_out": p["shared_out"],
                            "w_gate": p.get("shared_gate")}, x, s, prec)
    return y, aux


def features(params, tokens, s: Spec, prec, moe_fn, remat=False):
    """``moe_lm.features`` with ``moe_fn(p, h, s, prec)`` as its MoE layer
    and a scan over the stacked layer groups (one compiled body however
    deep the model): tokens [B, S] -> (final-normed features [B, S, d],
    mean aux)."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    B, S = tokens.shape

    def block(x, p, is_moe):
        x = x + moe_lm.attention(p["mixer"], moe_lm.norm(p["norm1"], x, s),
                                 s, prec)
        h = moe_lm.norm(p["norm2"], x, s)
        if is_moe:
            y, a = moe_fn(p["ffn"], h.reshape(B * S, s.d), s, prec)
            return x + y.reshape(B, S, s.d), a
        return x + moe_lm.mlp(p["ffn"], h, s, prec), jnp.float32(0.0)
    if remat:
        block = jax.checkpoint(block, static_argnums=(2,))

    def group(carry, pg):
        x, aux = carry
        for j in range(s.moe_every):
            x, a = block(x, pg[f"sub{j}"], s.is_moe(s.first_dense + j))
            aux = aux + a
        return (x, aux), None

    aux = jnp.float32(0.0)
    for i in range(s.first_dense):
        x, _ = block(x, params[f"prefix{i}"], False)
    if s.layers > s.first_dense:
        (x, aux), _ = jax.lax.scan(group, (x, aux), params["groups"])
    aux = aux / max(1, s.layers - s.first_dense)
    return moe_lm.norm(params["final_norm"], x, s), aux


def lm_loss(params, batch, s: Spec, prec, moe_fn, aux_weight,
            row_block: int = 2048):
    """``moe_lm.lm_loss`` with ``moe_fn`` as the MoE layer."""
    feats, aux = features(params, batch["tokens"], s, prec, moe_fn,
                          remat=True)
    table = params["embed"]["table"].astype(jnp.float32)
    f = feats.reshape(-1, s.d)
    lab = batch["labels"].reshape(-1)
    if f.shape[0] % row_block:
        row_block = f.shape[0]
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
