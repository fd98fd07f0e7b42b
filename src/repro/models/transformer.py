"""Composable decoder / encoder-decoder assembly over all mixer families.

A model is a (prefix, repeated group) layer plan; the repeated group is
initialized with ``vmap`` and executed with ``lax.scan`` so the HLO stays
small at 60-layer scale (critical for multi-pod compile times).  Sublayers:

    mixer: attn (GQA, optional sliding window, optional cross) | mla |
           mamba | mlstm | slstm
    ffn  : mlp | moe | None

MoE sublayers enter ``shard_map`` over the expert-parallel axes (see
core/dispatch/); dense compute relies on pjit sharding constraints
(repro.sharding.constrain).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.configs.base import ArchConfig
from repro.core import dispatch as dispatch_lib, gating
from repro.core.capacity import DispatchPlan
from repro.core.dispatch import base as moe_base
from repro.models import layers, mamba as mamba_lib, mla as mla_lib
from repro.models import xlstm as xlstm_lib


@dataclasses.dataclass(frozen=True)
class SubLayer:
    mixer: str                    # attn | mla | mamba | mlstm | slstm
    ffn: str | None            # mlp | moe | None
    cross: bool = False           # add cross-attention (whisper decoder)
    causal: bool = True


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Everything the forward pass needs besides params and data."""
    arch: ArchConfig
    mesh: object | None = None
    ep: moe_base.EPSpec | None = None
    plan: DispatchPlan | None = None          # level-indexed a2a capacities
    gate_cfg: gating.GateConfig | None = None
    use_flash: bool = False
    use_moe_kernel: bool = False
    remat: bool = False
    decode_replicated: bool = False              # long_500k batch=1
    # default MoE dispatch path (any name in the core.dispatch registry:
    # "a2a" | "a2a_pipelined" | "gather" | "einsum")
    dispatch: str = "a2a"
    a2a_num_chunks: int = 1                      # resolved by build_ctx
    # per-layer dispatch override: tuple of (global_layer_idx, path_name)
    # pairs.  Overrides on scanned group layers force the group loop to
    # unroll (the schedule becomes layer-dependent, so the HLO does too).
    dispatch_override: tuple = ()
    # moe_permute token-permutation kernels in the dispatch hot path:
    # None = auto (Pallas on TPU/GPU, jnp reference elsewhere)
    use_pallas: bool | None = None
    # perf flags (see EXPERIMENTS.md §Perf) — default off = paper baseline
    use_blockwise: bool = False                  # flash-style attention HLO
    fused_xent: bool = False                     # vocab-sharded xent
    a2a_dtype: str = ""                          # deprecated: use wire_codec
    wire_codec: object = None                    # a2a wire codec (a
                                                 # core.dispatch.wire codec or
                                                 # registered name) — payload
                                                 # encoding + scale sideband
    mamba_scan_chunk: int = 0                    # chunked selective scan
    xlstm_chunk: int = 0                         # chunkwise mLSTM
    resilience: object | None = None             # ResilienceConfig (guards,
                                                 # recovery policy, chaos) —
                                                 # carried for the guarded
                                                 # step factory; None = the
                                                 # classic unguarded loop

    @property
    def attn_cfg(self):
        a = self.arch
        return layers.AttnConfig(
            d_model=a.d_model, num_heads=a.num_heads,
            num_kv_heads=a.num_kv_heads, head_dim=a.head_dim_,
            rope_theta=a.rope_theta, sliding_window=a.sliding_window,
            qkv_bias=a.qkv_bias, dtype=a.jnp_dtype,
            use_flash_kernel=self.use_flash,
            use_blockwise=self.use_blockwise)

    @property
    def mla_cfg(self):
        a = self.arch
        m = a.mla
        return mla_lib.MLAConfig(
            d_model=a.d_model, num_heads=a.num_heads,
            kv_lora_rank=m.kv_lora_rank, qk_nope_dim=m.qk_nope_dim,
            qk_rope_dim=m.qk_rope_dim, v_dim=m.v_dim,
            q_lora_rank=m.q_lora_rank, rope_theta=a.rope_theta,
            dtype=a.jnp_dtype, use_blockwise=self.use_blockwise)

    @property
    def mamba_cfg(self):
        return mamba_lib.MambaConfig(d_model=self.arch.d_model,
                                     dtype=self.arch.jnp_dtype,
                                     scan_chunk=self.mamba_scan_chunk)

    @property
    def xlstm_cfg(self):
        a = self.arch
        return xlstm_lib.XLSTMConfig(d_model=a.d_model, num_heads=a.num_heads,
                                     slstm_every=a.slstm_every or 8,
                                     dtype=a.jnp_dtype,
                                     chunk_size=self.xlstm_chunk)

    @property
    def moe_cfg(self):
        a = self.arch
        return moe_base.MoEConfig(
            d_model=a.d_model, d_ff=a.moe.d_ff_expert,
            num_experts=a.moe.num_experts, top_k=a.moe.top_k,
            capacity_factor=a.moe.capacity_factor,
            num_shared_experts=a.moe.num_shared_experts,
            activation=a.activation, dtype=a.jnp_dtype,
            use_kernel=self.use_moe_kernel, a2a_dtype=self.a2a_dtype,
            wire_codec=self.wire_codec)

    @property
    def traffic_shares(self) -> tuple:
        """The dispatch metrics the layers average and the loss reports:
        ``frac_by_level``, and ``frac_self`` where the experts span more
        than one device (on one device every expert is the rank's own)."""
        if self.ep is not None and self.ep.ep_world > 1:
            return ("frac_by_level", "frac_self")
        return ("frac_by_level",)

    @property
    def frac_levels(self) -> int:
        """Length of the ``frac_by_level`` metric vector (dispatch stages
        of the EP hierarchy; 1 when the model has no MoE layers)."""
        if self.plan is not None:
            return self.plan.num_stages
        if self.ep is not None:
            return self.ep.num_stages
        return 1

    def dispatch_for_layer(self, layer_idx: int | None,
                           decode: bool = False) -> str:
        """Dispatch path name for one layer: the per-layer override when
        present, else the mode default (decode steps default to the
        weights-stationary gather path)."""
        default = "gather" if decode else self.dispatch
        if layer_idx is None:
            return default
        return dict(self.dispatch_override).get(layer_idx, default)


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------


def layer_plan(arch: ArchConfig):
    """Returns (prefix: [SubLayer], group: [SubLayer], n_groups)."""
    if arch.family == "ssm" and arch.ssm_kind == "xlstm":
        g = arch.slstm_every or 8
        group = [SubLayer("slstm" if j == g - 1 else "mlstm", None)
                 for j in range(g)]
        return [], group, arch.num_layers // g

    if arch.family == "hybrid":           # jamba
        g = arch.attn_every
        group = []
        for j in range(g):
            mixer = "attn" if j == arch.attn_offset else "mamba"
            ffn = "moe" if (arch.moe and j % arch.moe.moe_period
                            == arch.moe.moe_period - 1) else "mlp"
            group.append(SubLayer(mixer, ffn))
        return [], group, arch.num_layers // g

    mixer = "mla" if arch.mla else "attn"
    if arch.is_moe:
        # after the dense prefix, one group per moe_period layers: dense
        # FFNs, then the MoE FFN last (GShard's every-other-layer at 2)
        prefix = [SubLayer(mixer, "mlp")] * arch.moe.first_dense
        period = arch.moe.moe_period
        group = [SubLayer(mixer, "mlp")] * (period - 1) + [SubLayer(mixer,
                                                                  "moe")]
        return prefix, group, (arch.num_layers - arch.moe.first_dense) // period
    # dense / vlm / audio decoder
    cross = arch.family == "audio"
    group = [SubLayer(mixer, "mlp", cross=cross)]
    return [], group, arch.num_layers


def encoder_plan(arch: ArchConfig):
    return [SubLayer("attn", "mlp", causal=False)], arch.enc_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_sublayer(key, sub: SubLayer, ctx: ModelCtx):
    a = ctx.arch
    ks = jax.random.split(key, 6)
    p = {"norm1": layers.init_norm(a.norm, a.d_model)}
    if sub.mixer == "attn":
        p["mixer"] = layers.init_attn(ks[0], ctx.attn_cfg)
    elif sub.mixer == "mla":
        p["mixer"] = mla_lib.init_mla(ks[0], ctx.mla_cfg)
    elif sub.mixer == "mamba":
        p["mixer"] = mamba_lib.init_mamba(ks[0], ctx.mamba_cfg)
    elif sub.mixer == "mlstm":
        p["mixer"] = xlstm_lib.init_mlstm(ks[0], ctx.xlstm_cfg)
    elif sub.mixer == "slstm":
        p["mixer"] = xlstm_lib.init_slstm(ks[0], ctx.xlstm_cfg)
    else:
        raise ValueError(sub.mixer)
    if sub.cross:
        p["norm_cross"] = layers.init_norm(a.norm, a.d_model)
        p["cross"] = layers.init_attn(ks[1], ctx.attn_cfg)
    if sub.ffn == "mlp":
        p["norm2"] = layers.init_norm(a.norm, a.d_model)
        p["ffn"] = layers.init_mlp(ks[2], a.d_model, a.d_ff, a.activation,
                                   a.jnp_dtype)
    elif sub.ffn == "moe":
        p["norm2"] = layers.init_norm(a.norm, a.d_model)
        p["ffn"] = moe_base.init_moe_params(ks[2], ctx.moe_cfg, ctx.ep,
                                           ctx.gate_cfg)
    return p


def _init_group(key, group, ctx: ModelCtx):
    ks = jax.random.split(key, len(group))
    return {f"sub{j}": _init_sublayer(ks[j], sub, ctx)
            for j, sub in enumerate(group)}


def init_model(key, ctx: ModelCtx):
    a = ctx.arch
    prefix, group, n_groups = layer_plan(a)
    keys = jax.random.split(key, 8 + len(prefix))
    params = {"embed": layers.init_embed(keys[0], a.vocab_size, a.d_model,
                                         a.jnp_dtype),
              "final_norm": layers.init_norm(a.norm, a.d_model)}
    for i, sub in enumerate(prefix):
        params[f"prefix{i}"] = _init_sublayer(keys[8 + i], sub, ctx)
    gkeys = jax.random.split(keys[1], n_groups)
    params["groups"] = jax.vmap(lambda k: _init_group(k, group, ctx))(gkeys)
    if a.frontend == "vision":
        # 2-layer projector from the (stub) vision encoder width to d_model
        pk = jax.random.split(keys[2], 2)
        params["proj"] = {
            "w1": layers._norm_init(pk[0], (1024, a.d_model),
                                    1 / np.sqrt(1024)).astype(a.jnp_dtype),
            "w2": layers._norm_init(pk[1], (a.d_model, a.d_model),
                                    1 / np.sqrt(a.d_model)).astype(a.jnp_dtype),
        }
    if a.enc_layers:
        esub, n_enc = encoder_plan(a)
        ekeys = jax.random.split(keys[3], n_enc)
        params["enc_groups"] = jax.vmap(
            lambda k: _init_group(k, esub, ctx))(ekeys)
        params["enc_norm"] = layers.init_norm(a.norm, a.d_model)
    return params


# ---------------------------------------------------------------------------
# MoE via shard_map
# ---------------------------------------------------------------------------


def _tree_specs_default(tree, special: dict):
    from jax.sharding import PartitionSpec as P

    def path_str(path):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)

    def assign(path, leaf):
        return special.get(path_str(path), P())
    return jax.tree_util.tree_map_with_path(assign, tree)


def _moe_block(p, x, ctx: ModelCtx, decode: bool, layer_idx=None):
    """x: [B, S, d] (global view). Returns (y, metrics).

    Resolves the layer's dispatch path through the core.dispatch engine
    registry (per-layer override via ``ctx.dispatch_override``); every path
    returns the same uniform metrics schema, so the out_specs never branch.
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    ep, cfg, gate_cfg = ctx.ep, ctx.moe_cfg, ctx.gate_cfg
    mesh = ctx.mesh
    d = x.shape[-1]
    batch_axes = sharding.hierarchy_axes(mesh) if mesh is not None else ()
    replicated = ctx.decode_replicated
    name = ctx.dispatch_for_layer(layer_idx, decode)
    eng = dispatch_lib.make_engine(
        name, cfg=cfg, ep=ep, gate_cfg=gate_cfg, plan=ctx.plan,
        num_chunks=max(1, ctx.a2a_num_chunks),
        tokens_replicated=replicated and decode,
        use_pallas=ctx.use_pallas)

    def body(p_local, x_local):
        y, metrics = eng(p_local, x_local.reshape(-1, d))
        # average metrics over every mesh axis so outputs are replicated
        for ax in mesh.axis_names:
            metrics = {k: jax.lax.pmean(v, ax) for k, v in metrics.items()}
        return y.reshape(x_local.shape), metrics

    pspecs = moe_base.moe_param_specs(cfg, ep)
    pspecs = _merge_specs(p, pspecs)
    x_spec = (P() if replicated
              else P(batch_axes if len(batch_axes) > 1 else
                     (batch_axes[0] if batch_axes else None), None, None))
    fn = shard_map(body, mesh=mesh, in_specs=(pspecs, x_spec),
                   out_specs=(x_spec, _metric_specs()),
                   check_vma=False)
    return fn(p, x)


def _metric_specs():
    from jax.sharding import PartitionSpec as P
    return {k: P() for k in dispatch_lib.METRIC_KEYS}


def _merge_specs(params, partial_specs):
    """Full spec tree for the MoE params: known names from
    moe_param_specs, default replicated for the rest (gate, norms)."""
    from jax.sharding import PartitionSpec as P

    def assign(path, leaf):
        node = partial_specs
        for k in path:
            key = getattr(k, "key", None)
            if isinstance(node, dict) and key in node:
                node = node[key]
            else:
                return P()
        return node if isinstance(node, P) else P()
    return jax.tree_util.tree_map_with_path(assign, params)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


@jax.named_scope("layer")
def _apply_sublayer(p, x, sub: SubLayer, ctx: ModelCtx, *, enc_out=None,
                    aux0=0.0, frac0=None, drop0=None, layer_idx=None):
    """Returns (x, aux, frac, drop): the residual stream, the accumulated
    aux loss, the accumulated traffic shares (a dict keyed by
    ``ctx.traffic_shares``), and the accumulated dropped-token fraction
    (``frac0`` / ``drop0`` passed through unchanged — possibly None — for
    non-MoE sublayers)."""
    a = ctx.arch
    h = layers.norm_apply(p["norm1"], x, a.norm)
    if sub.mixer == "attn":
        cfg = ctx.attn_cfg
        if not sub.causal:
            cfg = dataclasses.replace(cfg, causal=False)
        mix, _ = layers.attn_apply(p["mixer"], h, cfg)
    elif sub.mixer == "mla":
        mix, _ = mla_lib.mla_apply(p["mixer"], h, ctx.mla_cfg)
    elif sub.mixer == "mamba":
        mix = mamba_lib.mamba_apply(p["mixer"], h, ctx.mamba_cfg)
    elif sub.mixer == "mlstm":
        mix = xlstm_lib.mlstm_apply(p["mixer"], h, ctx.xlstm_cfg)
    elif sub.mixer == "slstm":
        mix, _ = xlstm_lib.slstm_apply(p["mixer"], h, ctx.xlstm_cfg)
    x = x + mix
    x = sharding.constrain(x, "batch", None, None)
    if sub.cross and enc_out is not None:
        h = layers.norm_apply(p["norm_cross"], x, a.norm)
        mix = _cross_attn(p["cross"], h, enc_out, ctx)
        x = x + mix
    aux = jnp.asarray(aux0, jnp.float32)
    frac = frac0
    drop = drop0
    if sub.ffn == "mlp":
        h = layers.norm_apply(p["norm2"], x, a.norm)
        x = x + layers.mlp_apply(p["ffn"], h, a.activation)
    elif sub.ffn == "moe":
        h = layers.norm_apply(p["norm2"], x, a.norm)
        y, metrics = _moe_block(p["ffn"], h, ctx, decode=False,
                                layer_idx=layer_idx)
        x = x + y
        aux = aux + metrics["aux_loss"]
        if frac is not None:
            frac = {k: v + metrics[k] for k, v in frac.items()}
        if drop is not None:
            drop = drop + metrics["dropped"]
    x = sharding.constrain(x, "batch", None, None)
    return x, aux, frac, drop


def _cross_attn(p, x, enc_out, ctx: ModelCtx):
    """Simple full cross-attention (whisper decoder)."""
    cfg = ctx.attn_cfg
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (enc_out @ p["wk"]).reshape(B, enc_out.shape[1], K, hd)
    v = (enc_out @ p["wv"]).reshape(B, enc_out.shape[1], K, hd)
    out = layers._sdpa(q, k, v, causal=False, sliding_window=0,
                       q_positions=jnp.arange(S),
                       k_positions=jnp.arange(enc_out.shape[1]))
    return out.reshape(B, S, -1) @ p["wo"]


def _run_encoder(params, frames, ctx: ModelCtx):
    esub, n_enc = encoder_plan(ctx.arch)

    def body(x, p):
        x, _, _, _ = _apply_sublayer(p["sub0"], x, esub[0], ctx)
        return x, None
    x, _ = jax.lax.scan(body, frames, params["enc_groups"])
    return layers.norm_apply(params["enc_norm"], x, ctx.arch.norm)


def _overrides_hit_groups(ctx: ModelCtx, n_prefix: int, group, n_groups: int,
                          decode: bool = False) -> bool:
    """True when a per-layer dispatch override actually changes a scanned
    group layer's dispatch — only then is the unroll (and its n_groups-fold
    HLO growth) warranted.  Prefix overrides never force an unroll (that
    loop is already Python-level), and neither do out-of-range indices,
    overrides on non-MoE sublayers, or overrides equal to the default
    path."""
    default = ctx.dispatch_for_layer(None, decode)
    n_layers = n_prefix + n_groups * len(group)
    for idx, name in (ctx.dispatch_override or ()):
        if not (n_prefix <= idx < n_layers) or name == default:
            continue
        if group[(idx - n_prefix) % len(group)].ffn == "moe":
            return True
    return False


def forward_features(params, batch, ctx: ModelCtx):
    """Full-sequence forward up to the final norm.

    Returns ``(x, aux, shares, dropped)``: features, the mean aux loss, the
    mean traffic shares over the MoE layers (a dict: the per-level
    dispatch-fraction vector ``frac_by_level`` and, across devices,
    ``frac_self``), and the mean dropped-token fraction (the engine's
    uniform ``dropped`` metric — the step-health watermark reads it).  The
    latter two are None for models without MoE layers.
    """
    a = ctx.arch
    prefix, group, n_groups = layer_plan(a)

    x = layers.embed_apply(params["embed"], batch["tokens"])
    x = sharding.constrain(x, "batch", None, None)

    enc_out = None
    if a.family == "audio":
        enc_out = _run_encoder(params, batch["frontend"].astype(x.dtype), ctx)
    elif a.family == "vlm" and "frontend" in batch:
        patches = jax.nn.gelu(batch["frontend"].astype(x.dtype)
                              @ params["proj"]["w1"]) @ params["proj"]["w2"]
        n = patches.shape[1]
        x = jnp.concatenate([patches, x[:, n:]], axis=1)

    aux = jnp.float32(0.0)
    n_moe = n_groups * sum(1 for s in group if s.ffn == "moe")
    frac = None
    if n_moe:
        frac = {k: jnp.float32(0.0) for k in ctx.traffic_shares}
        frac["frac_by_level"] = jnp.zeros((ctx.frac_levels,), jnp.float32)
    drop = jnp.float32(0.0) if n_moe else None
    for i, sub in enumerate(prefix):
        x, aux, frac, drop = _apply_sublayer(
            params[f"prefix{i}"], x, sub, ctx, enc_out=enc_out, aux0=aux,
            frac0=frac, drop0=drop, layer_idx=i)

    n_prefix = len(prefix)
    # the layer loop slices each layer's parameters out of their stacks;
    # that work, outside every sublayer's ``layer`` scope, is
    # ``layer.params``
    with jax.named_scope("layer.params"):
        if _overrides_hit_groups(ctx, n_prefix, group, n_groups):
            # a per-layer dispatch override lands inside the scanned groups:
            # the schedule is layer-dependent, so unroll the group loop (each
            # group gets its own HLO with its own dispatch path).
            def run_group(carry, pg, base_idx):
                x, aux, frac, drop = carry
                for j, sub in enumerate(group):
                    x, aux, frac, drop = _apply_sublayer(
                        pg[f"sub{j}"], x, sub, ctx, enc_out=enc_out, aux0=aux,
                        frac0=frac, drop0=drop, layer_idx=base_idx + j)
                return x, aux, frac, drop
            if ctx.remat:
                run_group = jax.checkpoint(run_group, static_argnums=(2,),
                                           prevent_cse=False)
            for g in range(n_groups):
                pg = jax.tree_util.tree_map(lambda a, g=g: a[g],
                                            params["groups"])
                x, aux, frac, drop = run_group((x, aux, frac, drop), pg,
                                               n_prefix + g * len(group))
        else:
            def body(carry, p):
                x, aux, frac, drop = carry
                for j, sub in enumerate(group):
                    x, aux, frac, drop = _apply_sublayer(
                        p[f"sub{j}"], x, sub, ctx, enc_out=enc_out, aux0=aux,
                        frac0=frac, drop0=drop)
                return (x, aux, frac, drop), None

            if ctx.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux, frac, drop), _ = jax.lax.scan(body, (x, aux, frac, drop),
                                                   params["groups"])

    x = layers.norm_apply(params["final_norm"], x, a.norm)
    if frac is not None:
        frac = {k: v / max(1, n_moe) for k, v in frac.items()}
    if drop is not None:
        drop = drop / max(1, n_moe)
    return x, aux / max(1, n_groups * len(group)), frac, drop


def forward(params, batch, ctx: ModelCtx):
    """Full-sequence forward (train / prefill). Returns (logits, aux)."""
    x, aux, _, _ = forward_features(params, batch, ctx)
    logits = layers.unembed_apply(params["embed"], x)
    logits = sharding.constrain(logits, "batch", None, "model")
    return logits, aux


def _fused_xent(params, x, labels, ctx: ModelCtx):
    """Vocab-sharded cross entropy without materializing f32 logits or
    gathering the vocabulary axis (perf flag; EXPERIMENTS.md §Perf.1).

    logits stay bf16 and sharded over "model"; the max / sum-exp / label
    reductions over the sharded vocab axis lower to small all-reduces
    instead of a [B,S,V] all-gather; take_along_axis is replaced by an
    iota==label masked sum (elementwise on the sharded operand).
    """
    table = params["embed"]["table"]                  # [V, d]
    logits = x @ table.T.astype(x.dtype)              # bf16 [B,S,V]
    logits = sharding.constrain(logits, "batch", None, "model")
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    V = table.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
              == labels[..., None])
    label_logit = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    return lse - label_logit                          # [B,S]


def loss_fn(params, batch, ctx: ModelCtx, aux_weight: float = 1.0):
    labels = batch["labels"]
    x, aux, frac, drop = forward_features(params, batch, ctx)
    if ctx.fused_xent:
        nll = _fused_xent(params, x, labels, ctx)
    else:
        logits = layers.unembed_apply(params["embed"], x)
        logits = sharding.constrain(logits, "batch", None, "model")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
    nll = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = nll + aux_weight * aux
    metrics = {"nll": nll, "aux": aux, "loss": total}
    if frac is not None:
        # mean per-level dispatch fractions (and, across devices, the self
        # share) over the MoE layers
        metrics.update(frac)
    if drop is not None:
        # mean dropped-assignment fraction over the MoE layers (the
        # engine's uniform `dropped` metric) — feeds the step-health
        # dropped-token watermark
        metrics["dropped"] = drop
    return total, metrics
