"""Training loop: step factory (fwd+bwd+AdamW, optional grad accumulation),
metric aggregation, checkpoint hooks.  The jitted step is the unit the
multi-pod dry-run lowers.

Two step factories: :func:`make_train_step` is the classic unguarded step;
:func:`make_guarded_train_step` adds the resilience runtime's in-step
health check (one fused non-finite tree-reduce over loss + grads) and an
in-jit skip — on a non-finite verdict the params/opt update is suppressed
with a select, so the host never sees a poisoned tree.  With no fault
firing the guarded step is bit-identical to the unguarded one (fault
multipliers of 1.0 are exact; the healthy select branch is bitwise).
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ArchConfig, RunConfig
from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
from repro.models import model as model_lib, transformer
from repro.optim import adamw

#: A host span in the profiler's trace (``train.step``, ``train.log``,
#: ``train.ckpt``); about a microsecond when no profiler runs.
_span = jax.profiler.TraceAnnotation


def make_train_step(ctx: transformer.ModelCtx, run: RunConfig,
                    opt_cfg: adamw.AdamWConfig | None = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    Pure function of its inputs — jit it (optionally with shardings).
    """
    if opt_cfg is None:
        opt_cfg = adamw.AdamWConfig(
            learning_rate=run.learning_rate, warmup_steps=run.warmup_steps,
            total_steps=run.total_steps, weight_decay=run.weight_decay,
            grad_clip=run.grad_clip)

    def loss(params, batch):
        return transformer.loss_fn(params, batch, ctx,
                                   aux_weight=run.aux_weight)

    def step(params, opt_state, batch):
        rules = model_lib.default_rules(ctx.mesh) if ctx.mesh else None
        ctxm = sharding.axis_rules(rules) if rules else _null()
        with ctxm:
            if run.microbatch and run.microbatch < batch["tokens"].shape[0]:
                params_new, opt_state, metrics = _accum_step(
                    params, opt_state, batch, loss, opt_cfg, run.microbatch)
                return params_new, opt_state, metrics
            (l, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)
            params, opt_state, opt_metrics = adamw.apply_updates(
                params, grads, opt_state, opt_cfg)
            metrics = dict(metrics, **opt_metrics)
            return params, opt_state, metrics

    return step


def make_guarded_train_step(ctx: transformer.ModelCtx, run: RunConfig,
                            opt_cfg: adamw.AdamWConfig | None = None):
    """Guarded step: step(params, opt_state, batch, fault) -> (p, o, m).

    ``fault`` is the chaos injection channel — ``{"loss_mult",
    "grad_mult"}`` scalars (traced arguments, so no recompile per step;
    pass 1.0 when nothing fires).  Both ride the *differentiated* total:
    the chain rule delivers them to every grad leaf with zero per-leaf
    work, and reported metrics stay raw.  The loss-spike fault
    (``param_scale``) is applied by the host loop *between* steps on its
    scheduled step only — injected post-update so the global-norm clip
    can't neutralize it, and off the jitted path so the healthy step
    never pays for it.

    The guard itself is free by construction: the non-finite verdict
    reuses the optimizer's global-norm reduce (``sqrt(sum g^2)`` is NaN
    or inf exactly when any grad element is — the same single fused
    tree-reduce ``guards.nonfinite_score`` spells out standalone), and
    the skip *action* costs nothing in-step: the step always returns the
    updated trees plus ``metrics["nonfinite"]``, and on a bad verdict
    the host loop simply keeps its still-live references to the previous
    params/opt instead of assigning the poisoned ones (nothing is
    donated, so the old trees are intact on device).  In-jit ``where``
    selects / ``lax.cond`` branches over the trees were measured at
    8-15% of step time — the whole guard must stay under 5%
    (``benchmarks/dispatch_sweep.py`` gates it), so every tree-sized
    action lives on the host where it is a pointer swap.
    """
    if opt_cfg is None:
        opt_cfg = adamw.AdamWConfig(
            learning_rate=run.learning_rate, warmup_steps=run.warmup_steps,
            total_steps=run.total_steps, weight_decay=run.weight_decay,
            grad_clip=run.grad_clip)
    def loss(params, batch):
        return transformer.loss_fn(params, batch, ctx,
                                   aux_weight=run.aux_weight)

    def step(params, opt_state, batch, fault):
        rules = model_lib.default_rules(ctx.mesh) if ctx.mesh else None
        ctxm = sharding.axis_rules(rules) if rules else _null()
        with ctxm:
            def scaled(p, b):
                total, metrics = loss(p, b)
                # both multipliers via the chain rule: d(c*L)/dp = c*dL/dp,
                # so grads are scaled without a per-leaf pass (1.0 * 1.0
                # is exact, keeping the healthy path bitwise)
                return total * (fault["loss_mult"] * fault["grad_mult"]), \
                    metrics

            if run.microbatch and run.microbatch < batch["tokens"].shape[0]:
                grads, metrics = _accum_grads(params, batch, scaled,
                                              run.microbatch)
            else:
                (_, metrics), grads = jax.value_and_grad(
                    scaled, has_aux=True)(params, batch)
            new_p, new_o, opt_metrics = adamw.apply_updates(
                params, grads, opt_state, opt_cfg)
            # the optimizer's clipping reduce doubles as the health check:
            # sqrt(sum g^2) is non-finite iff any grad element is
            ok = jnp.logical_and(jnp.isfinite(metrics["loss"]),
                                 jnp.isfinite(opt_metrics["grad_norm"]))
            metrics = dict(metrics, **opt_metrics)
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
            return new_p, new_o, metrics

    return step


def _null():
    import contextlib
    return contextlib.nullcontext()


def _accum_grads(params, batch, loss, micro: int):
    """Microbatched grad accumulation: returns (mean grads, mean metrics)."""
    B = batch["tokens"].shape[0]
    n = B // micro
    split = jax.tree_util.tree_map(
        lambda x: x.reshape((n, micro) + x.shape[1:]), batch)

    def body(carry, mb):
        gsum, msum = carry
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
            params, mb)
        gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
        msum = jax.tree_util.tree_map(jnp.add, msum, metrics)
        return (gsum, msum), None

    zeros_g = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    mb0 = jax.tree_util.tree_map(lambda x: x[0], split)
    m_shapes = jax.eval_shape(lambda p, mb: loss(p, mb)[1], params, mb0)
    zeros_m = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), m_shapes)
    (gsum, msum), _ = jax.lax.scan(body, (zeros_g, zeros_m), split)
    grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
    metrics = jax.tree_util.tree_map(lambda m: m / n, msum)
    return grads, metrics


def _accum_step(params, opt_state, batch, loss, opt_cfg, micro: int):
    grads, metrics = _accum_grads(params, batch, loss, micro)
    params, opt_state, opt_metrics = adamw.apply_updates(
        params, grads, opt_state, opt_cfg)
    return params, opt_state, dict(metrics, **opt_metrics)


@dataclasses.dataclass
class TrainResult:
    losses: list
    metrics_history: list
    steps_per_sec: float
    params: object
    opt_state: object
    # resilience accounting (0 on unguarded runs) — the same counters ride
    # every logged metrics_history entry
    skipped_steps: int = 0
    rollbacks: int = 0
    replans: int = 0
    # host wall seconds of each step; a step waits for the device only
    # when it is logged, so with log_every=1 these are device step times
    # (the first one includes the compile)
    step_seconds: list = dataclasses.field(default_factory=list)


def _rolling_path(ckpt_path: str, step: int) -> str:
    base, ext = os.path.splitext(ckpt_path)
    return f"{base}-{step:06d}{ext or '.npz'}"


def _prune_rolling(rolling: list, keep: int) -> None:
    while len(rolling) > keep:
        _, path = rolling.pop(0)
        for p in (path, path + ".meta.json"):
            if os.path.exists(p):
                os.unlink(p)


def _restore_last_good(rolling: list, template):
    """Walk rolling checkpoints newest-first; restore the first one whose
    sha256 manifest verifies (a corrupt newest falls back to the previous
    — the integrity-hash contract of checkpoint/ckpt.py)."""
    for step, path in reversed(rolling):
        if ckpt.verify(path):
            return step, ckpt.restore(path, template)
    raise RuntimeError(
        "rollback requested but no rolling checkpoint passes integrity "
        "verification")


def train(arch: ArchConfig, run: RunConfig, mesh, *, steps: int,
          aux_mode: str | None = None, log_every: int = 10,
          ckpt_path: str | None = None, ckpt_every: int = 0,
          ckpt_keep: int = 3, eval_fn=None,
          data_seed: int | None = None, verbose: bool = True
          ) -> TrainResult:
    """End-to-end training driver (used by examples + benchmarks).

    When ``run.topology`` carries a nested spec, the mesh's hierarchy axes
    must match it — the level-indexed dispatch plan is derived from the
    mesh, so a mismatched spec would silently train under the wrong
    per-level capacities.

    ``ckpt_every > 0`` writes rolling checkpoints (``<base>-<step>.npz``,
    newest ``ckpt_keep`` kept) with sha256 manifests; they are the
    rollback target of the resilience policy.  ``run.resilience`` (a
    ``repro.resilience.ResilienceConfig``) switches the loop onto the
    guarded step: in-jit skip on non-finite grads, rollback on sustained
    loss spike, and the degraded-topology replan at ``replan_every``
    boundaries (plans are static per compilation, so a replan re-jits).

    Under a profiler the loop writes the spans ``train.step`` (the step's
    dispatch), ``train.log`` (the host's wait for a logged step's
    metrics) and ``train.ckpt`` (a checkpoint save), beside the data
    pipeline's ``data.batch`` and ``data.shard``: device idle time under
    one of them is that part of the loop holding the chip back.
    """
    aux_mode = aux_mode or run.aux_mode
    want = run.mesh_axis_sizes()
    if want:
        got = tuple(mesh.shape[a] for a in sharding.hierarchy_axes(mesh))
        if got != want:
            raise ValueError(
                f"RunConfig.topology {run.topology!r} implies hierarchy "
                f"sizes {want} but the mesh has {got}; build the mesh with "
                f"repro.launch.mesh.mesh_from_topology(run.topology)")
    ctx = model_lib.build_ctx(arch, mesh, seq_len=run.seq_len,
                              global_batch=run.global_batch,
                              aux_mode=aux_mode, remat=run.remat,
                              dispatch=run.dispatch,
                              a2a_num_chunks=run.a2a_num_chunks,
                              dispatch_override=run.dispatch_override,
                              use_pallas=run.use_pallas,
                              wire_codec=run.wire_codec,
                              resilience=run.resilience)
    res = run.resilience
    guarded = res is not None
    policy = None
    chaos = None
    if guarded:
        from repro.resilience import chaos as chaos_lib
        from repro.resilience.policy import RecoveryPolicy
        policy = RecoveryPolicy(res)
        chaos = res.chaos
        if res.rollback_on_spike and not (ckpt_path and ckpt_every > 0):
            raise ValueError(
                "ResilienceConfig.rollback_on_spike needs ckpt_path and "
                "ckpt_every > 0 — rolling checkpoints are the rollback "
                "target")
    rules = model_lib.default_rules(mesh)
    key = jax.random.PRNGKey(run.seed)
    with mesh, sharding.axis_rules(rules):
        params = model_lib.init_params(key, ctx, rules=rules)
        opt_state = adamw.init_state(params)

        def make_fn(c):
            if guarded:
                # nothing donated: a skipped step keeps the previous trees
                return jax.jit(make_guarded_train_step(c, run))
            # the unguarded loop drops the old trees at once, so they are
            # donated: params and moments then exist once on the device
            return jax.jit(make_train_step(c, run), donate_argnums=(0, 1))
        step_fn = make_fn(ctx)
        data = SyntheticLM(DataConfig(vocab_size=arch.vocab_size,
                                      seq_len=run.seq_len,
                                      global_batch=run.global_batch,
                                      seed=data_seed if data_seed is not None
                                      else run.seed), arch)
        losses, history, step_seconds = [], [], []
        rolling = []                     # [(step, path)] oldest-first
        t0 = time.time()
        for i in range(steps):
            t_step = time.time()
            # degraded-topology fallback: probe at epoch boundaries only
            # (a plan change means a re-jit, so it must land between jits)
            if (guarded and res.replan_every and i > 0
                    and i % res.replan_every == 0 and ctx.plan is not None):
                slow = policy.observe_links(mesh, ctx.ep.axis_names, i)
                new_ctx = policy.replan(ctx, slow)
                if new_ctx is not None:
                    ctx = new_ctx
                    step_fn = make_fn(ctx)
                    if verbose:
                        print(f"step {i:5d} replan: caps -> "
                              f"{ctx.plan.caps}")
            if chaos is not None:
                chaos_lib.maybe_straggle(chaos, i)
            batch = shard_batch(data.batch(i), mesh)
            if guarded:
                scales = chaos_lib.fault_scales(chaos, i)
                fault = {k: jnp.float32(scales[k])
                         for k in ("loss_mult", "grad_mult")}
                with _span("train.step"):
                    new_p, new_o, metrics = step_fn(params, opt_state,
                                                    batch, fault)
                if scales["param_scale"] != 1.0:
                    # loss-spike fault: wreck the updated params between
                    # steps (host-gated, so the healthy path never traces
                    # or pays for it)
                    ps = jnp.float32(scales["param_scale"])
                    new_p = jax.tree_util.tree_map(
                        lambda p: (p * ps).astype(p.dtype), new_p)
                verdict = {
                    "nonfinite": float(metrics["nonfinite"]),
                    "loss": float(metrics["loss"]),
                    "dropped": (float(metrics["dropped"])
                                if "dropped" in metrics else None)}
                action = policy.classify(i, verdict)
                if action == "rollback":
                    template = {"params": params, "opt": opt_state}
                    at, good = _restore_last_good(rolling, template)
                    params, opt_state = good["params"], good["opt"]
                    policy.on_rollback()
                    if verbose:
                        print(f"step {i:5d} rollback -> checkpoint of "
                              f"step {at}")
                elif action == "skip":
                    # the poisoned trees are simply never assigned — the
                    # previous params/opt are still live on device (nothing
                    # is donated), so the skip is a host pointer swap
                    pass
                else:
                    params, opt_state = new_p, new_o
            else:
                with _span("train.step"):
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
            if i % log_every == 0 or i == steps - 1:
                # scalar metrics become floats; vector metrics (e.g. the
                # level-indexed frac_by_level) become lists: the host waits
                # for the step here
                with _span("train.log"):
                    m = {k: (float(v) if getattr(v, "ndim", 0) == 0
                             else [float(x) for x in v])
                         for k, v in metrics.items()}
                m.update(policy.counters() if policy is not None else
                         {"skipped_steps": 0, "rollbacks": 0, "replans": 0,
                          "drop_alarms": 0})
                losses.append(m["loss"])
                history.append(m)
                if verbose:
                    fb = m.get("frac_by_level")
                    extra = (" frac_by_level=[" +
                             ",".join(f"{x:.2f}" for x in fb) + "]"
                             if fb else "")
                    print(f"step {i:5d} loss {m['loss']:.4f} "
                          f"nll {m['nll']:.4f} aux {m.get('aux', 0):.4f}"
                          f"{extra}")
            if (ckpt_path and ckpt_every > 0 and (i + 1) % ckpt_every == 0
                    and (policy is None or policy.healthy)):
                rp = _rolling_path(ckpt_path, i)
                with _span("train.ckpt"):
                    ckpt.save(rp, {"params": params, "opt": opt_state},
                              step=i)
                rolling.append((i, rp))
                _prune_rolling(rolling, ckpt_keep)
                if chaos is not None and chaos_lib.should_corrupt(chaos, i):
                    chaos_lib.corrupt_checkpoint(rp, chaos.seed)
            step_seconds.append(time.time() - t_step)
        dt = time.time() - t0
        if ckpt_path:
            ckpt.save(ckpt_path, {"params": params, "opt": opt_state},
                      step=steps)
    counters = policy.counters() if policy is not None else {}
    return TrainResult(losses=losses, metrics_history=history,
                       steps_per_sec=steps / max(dt, 1e-9),
                       params=params, opt_state=opt_state,
                       skipped_steps=counters.get("skipped_steps", 0),
                       rollbacks=counters.get("rollbacks", 0),
                       replans=counters.get("replans", 0),
                       step_seconds=step_seconds)
