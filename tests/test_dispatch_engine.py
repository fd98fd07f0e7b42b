"""Unified core/dispatch engine: registry resolution, the uniform metrics
schema, the cross-path equivalence oracle, and per-layer dispatch override.

The oracle: at matched, ample capacities the four registered paths
(``einsum`` — the GShard baseline the paper describes in §2 — plus the
selection-based ``a2a``, ``a2a_pipelined``, and the weights-stationary
``gather``) are different *execution schedules* of the same math, so their
outputs must be allclose.  The multipod mesh case runs as a slow
subprocess (forced host devices)."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import shard_map
from repro.core import dispatch as dispatch_lib
from repro.core import gating
from repro.core.capacity import make_plan
from repro.models import model as model_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, F, N, K, T = 16, 32, 4, 2, 64
PATHS = ("einsum", "a2a", "a2a_pipelined", "gather")


def _setup(key, capacity_factor=8.0, shared=0):
    cfg = dispatch_lib.MoEConfig(d_model=D, d_ff=F, num_experts=N, top_k=K,
                                 capacity_factor=capacity_factor,
                                 num_shared_experts=shared,
                                 dtype=jnp.float32)
    ep = dispatch_lib.EPSpec(num_pods=1, ep_per_pod=1, pod_axis=None,
                             data_axis="data", model_axis="model")
    gate_cfg = gating.GateConfig(num_experts=N, top_k=K, aux_mode="lb")
    params = dispatch_lib.init_moe_params(key, cfg, ep, gate_cfg)
    plan = make_plan(tokens_per_device=T, num_experts=N, top_k=K,
                     capacity_factor=capacity_factor, num_pods=1,
                     ep_per_pod=1, mode="even")
    return cfg, ep, gate_cfg, params, plan


def _apply(name, mesh, params, x, cfg, ep, gate_cfg, **kw):
    from jax.sharding import PartitionSpec as P
    eng = dispatch_lib.make_engine(name, cfg=cfg, ep=ep, gate_cfg=gate_cfg,
                                   **kw)
    body = shard_map(lambda p, xx: eng(p, xx), mesh=mesh,
                     in_specs=(P(), P()), out_specs=(P(), P()),
                     check_vma=False)
    with mesh:
        return body(params, x)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_contains_all_paths():
    assert set(PATHS) <= set(dispatch_lib.available())
    for name in PATHS:
        path = dispatch_lib.get_path(name)
        assert path.name == name
    # the staged paths refuse to resolve without a capacity plan
    assert dispatch_lib.get_path("a2a").needs_plan
    assert dispatch_lib.get_path("a2a_pipelined").needs_plan


def test_unknown_path_raises():
    with pytest.raises(ValueError, match="unknown dispatch"):
        dispatch_lib.get_path("ragged_a2a")
    cfg, ep, gate_cfg, _, plan = _setup(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="requires a DispatchPlan"):
        dispatch_lib.make_engine("a2a", cfg=cfg, ep=ep, gate_cfg=gate_cfg)


def test_build_ctx_rejects_unknown_dispatch(mesh11):
    from repro.configs.base import get_config
    arch = get_config("gpt3_medium_moe").reduced()
    with pytest.raises(ValueError, match="unknown dispatch"):
        model_lib.build_ctx(arch, mesh11, seq_len=32, global_batch=4,
                            dispatch="bogus")
    with pytest.raises(ValueError, match="unknown dispatch"):
        model_lib.build_ctx(arch, mesh11, seq_len=32, global_batch=4,
                            dispatch_override=((1, "bogus"),))


# ---------------------------------------------------------------------------
# uniform metrics schema
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", PATHS)
def test_uniform_metrics_schema(key, mesh11, name):
    cfg, ep, gate_cfg, params, plan = _setup(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
    _, metrics = _apply(name, mesh11, params, x, cfg, ep, gate_cfg,
                        plan=plan, num_chunks=2)
    assert set(metrics) == set(dispatch_lib.METRIC_KEYS)
    for k in dispatch_lib.METRIC_KEYS:
        assert np.isfinite(np.asarray(metrics[k])).all(), k
    # frac_by_level is a fixed-length vector (1 stage on this 1-axis EP
    # spec) summing to 1
    fb = np.asarray(metrics["frac_by_level"])
    assert fb.shape == (1,)
    assert fb.sum() == pytest.approx(1.0, abs=1e-6)
    # ample capacity + single rank: nothing drops, nothing leaves level <= 1
    assert float(metrics["dropped"]) == pytest.approx(0.0, abs=1e-6)
    assert float(fb[0]) == pytest.approx(1.0, abs=1e-6)


# ---------------------------------------------------------------------------
# cross-path equivalence oracle (single-pod)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("a2a", "a2a_pipelined", "gather"))
@pytest.mark.parametrize("shared", (0, 1))
@pytest.mark.parametrize("use_pallas", (False, True))
def test_cross_path_equivalence_vs_einsum_oracle(key, mesh11, name, shared,
                                                 use_pallas):
    """Each selection-based path == the einsum oracle at matched ample
    capacity (einsum capacity=T keeps every token, cf=8 does for a2a),
    with the moe_permute Pallas kernels both off (jnp reference) and
    forced on (Pallas interpreter on CPU)."""
    cfg, ep, gate_cfg, params, plan = _setup(key, shared=shared)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, D), jnp.float32)
    y_oracle, _ = _apply("einsum", mesh11, params, x, cfg, ep, gate_cfg,
                         capacity=T)
    y, _ = _apply(name, mesh11, params, x, cfg, ep, gate_cfg,
                  plan=plan, num_chunks=3, use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_oracle),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("name", ("a2a", "a2a_pipelined", "einsum"))
def test_cross_path_equivalence_decode_shapes(key, mesh11, name):
    """At decode shapes (a handful of tokens) the gather path is the
    reference and every other path must agree."""
    Td = 4
    cfg, ep, gate_cfg, params, plan = _setup(key)
    plan = dataclasses.replace(plan, tokens_per_device=Td)
    x = jax.random.normal(jax.random.PRNGKey(3), (Td, D), jnp.float32)
    y_ref, _ = _apply("gather", mesh11, params, x, cfg, ep, gate_cfg)
    y, _ = _apply(name, mesh11, params, x, cfg, ep, gate_cfg,
                  plan=plan, num_chunks=2, capacity=Td)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# occupancy-aware ragged grouped GEMM through the engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("a2a", "a2a_pipelined", "gather"))
def test_ragged_gemm_entry_on_and_off_agree(key, mesh11, name):
    """With the Pallas GEMM forced on, every selection path routes its
    expert compute through the occupancy-aware ragged entry (runtime
    valid-row counts, block-skip predicate) — outputs must equal both the
    dense jnp path and the einsum oracle.  A tight capacity factor makes
    the capacity buffers genuinely under-filled, so slack blocks really
    are skipped rather than trivially full."""
    from repro.kernels.moe_gemm import ops as gemm_ops
    assert gemm_ops.use_ragged(True), "ragged entry must be viable here"
    cfg, ep, gate_cfg, params, plan = _setup(key, capacity_factor=1.5)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, D), jnp.float32)
    kw = dict(plan=plan) if name != "gather" else {}
    if name == "a2a_pipelined":
        kw["num_chunks"] = 3
    y_off, m_off = _apply(name, mesh11, params, x, cfg, ep, gate_cfg,
                          use_pallas=False, **kw)
    y_on, m_on = _apply(name, mesh11, params, x, cfg, ep, gate_cfg,
                        use_pallas=True, **kw)
    np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(m_on["dropped"]),
                               float(m_off["dropped"]), atol=1e-6)


def test_gather_ragged_skips_unpicked_experts(key, mesh11):
    """Decode regime: at tiny token counts most local experts are picked by
    no token at all — the ragged entry skips their whole segments and the
    output still matches the dense gather compute."""
    Td = 3
    cfg, ep, gate_cfg, params, _ = _setup(key)
    x = jax.random.normal(jax.random.PRNGKey(6), (Td, D), jnp.float32)
    y_dense, _ = _apply("gather", mesh11, params, x, cfg, ep, gate_cfg,
                        use_pallas=False)
    y_ragged, _ = _apply("gather", mesh11, params, x, cfg, ep, gate_cfg,
                         use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_ragged), np.asarray(y_dense),
                               atol=1e-5, rtol=1e-5)


def test_rows_per_expert_counts_delivered_tokens(key, mesh11):
    """DispatchIndices.rows_per_expert must sum to the number of kept
    (token, pick) slots and bound every segment by its plan capacity."""
    from jax.sharding import PartitionSpec as P
    from repro.core.dispatch import routing
    cfg, ep, gate_cfg, params, plan = _setup(key, capacity_factor=1.25)

    def body(p, xx):
        routed = routing.route(p, xx, cfg, ep, plan, gate_cfg,
                               with_bufs=False)
        di = routing.build_indices(routed.sels,
                                   routed.gate_out["topk_idx"], T)
        kept = sum(jnp.sum(sel.valid) for _, sel in routed.sels)
        return di.rows_per_expert, di.slot_w, kept

    x = jax.random.normal(jax.random.PRNGKey(7), (T, D), jnp.float32)
    fn = shard_map(body, mesh=mesh11, in_specs=(P(), P()),
                   out_specs=(P(), P(), P()), check_vma=False)
    with mesh11:
        counts, slot_w, kept = fn(params, x)
    counts = np.asarray(counts)
    assert counts.sum() == int(kept) == int((np.asarray(slot_w) > 0).sum())
    # one segment per (stage, dest, expert); each bounded by its stage cap
    off = 0
    for s in range(plan.num_stages):
        if plan.caps[s] <= 0:
            continue
        n_seg = N  # single-rank mesh: num_dests == 1, E_l == N
        seg = counts[off:off + n_seg]
        assert (seg <= min(plan.caps[s], T)).all()
        off += n_seg
    assert off == counts.shape[0]


# ---------------------------------------------------------------------------
# per-layer dispatch override through the model stack
# ---------------------------------------------------------------------------


def _moe_layer_indices(arch):
    from repro.models.transformer import layer_plan
    prefix, group, n_groups = layer_plan(arch)
    idxs = []
    for g in range(n_groups):
        for j, sub in enumerate(group):
            if sub.ffn == "moe":
                idxs.append(len(prefix) + g * len(group) + j)
    return idxs


def test_per_layer_dispatch_override_train(mesh11):
    """Overriding one MoE layer to the num_chunks=1 pipelined schedule (==
    sync) must reproduce the baseline losses exactly; an ample-capacity
    gather override stays allclose (same math, different transport)."""
    from repro.configs.base import RunConfig, get_config
    from repro.training import trainer
    arch = get_config("gpt3_medium_moe").reduced()
    arch = dataclasses.replace(
        arch, moe=dataclasses.replace(arch.moe, capacity_factor=8.0))
    moe_idxs = _moe_layer_indices(arch)
    assert moe_idxs, "reduced gpt3_medium_moe must keep MoE layers"
    base = dict(seq_len=32, global_batch=4, learning_rate=1e-3,
                total_steps=10, warmup_steps=2, aux_mode="ta")
    r_sync = trainer.train(arch, RunConfig(**base), mesh11, steps=3,
                           log_every=1, verbose=False)
    r_ovr = trainer.train(
        arch, RunConfig(**base, a2a_num_chunks=1,
                        dispatch_override=((moe_idxs[0], "a2a_pipelined"),)),
        mesh11, steps=3, log_every=1, verbose=False)
    np.testing.assert_allclose(r_ovr.losses, r_sync.losses, rtol=1e-6)
    r_gather = trainer.train(
        arch, RunConfig(**base,
                        dispatch_override=((moe_idxs[0], "gather"),)),
        mesh11, steps=3, log_every=1, verbose=False)
    np.testing.assert_allclose(r_gather.losses, r_sync.losses, rtol=1e-4)


def test_noop_overrides_keep_the_group_scan(mesh11):
    """Out-of-range indices, overrides equal to the default path, and
    prefix-only overrides must not force the n_groups-fold unroll."""
    from repro.configs.base import get_config
    from repro.models.transformer import _overrides_hit_groups, layer_plan
    arch = get_config("gpt3_medium_moe").reduced()
    prefix, group, n_groups = layer_plan(arch)
    moe_idx = _moe_layer_indices(arch)[0]
    cases = [
        (((999, "gather"),), False),              # stale / out-of-range idx
        (((moe_idx, "a2a"),), False),             # == default: no-op
        (((moe_idx, "gather"),), True),           # genuine change
    ]
    for ovr, want in cases:
        ctx = model_lib.build_ctx(arch, mesh11, seq_len=32, global_batch=4,
                                  dispatch_override=ovr)
        got = _overrides_hit_groups(ctx, len(prefix), group, n_groups)
        assert got == bool(want), (ovr, got)


def test_build_ctx_merges_arch_and_run_overrides(mesh11):
    from repro.configs.base import get_config
    arch = get_config("gpt3_medium_moe").reduced()
    arch = dataclasses.replace(
        arch, moe=dataclasses.replace(
            arch.moe, dispatch_override=((1, "a2a_pipelined"), (2, "gather"))))
    ctx = model_lib.build_ctx(arch, mesh11, seq_len=32, global_batch=4,
                              dispatch_override=((2, "a2a"),))
    got = dict(ctx.dispatch_override)
    assert got[1] == "a2a_pipelined"      # arch-level survives
    assert got[2] == "a2a"                # run-level wins per layer
    # an a2a_pipelined override alone triggers plan chunk alignment
    assert ctx.a2a_num_chunks >= 1
    assert ctx.plan.cap_near % ctx.a2a_num_chunks == 0


# ---------------------------------------------------------------------------
# multipod mesh case (slow subprocess: forced host devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_ragged_gemm_multipod_counts_align():
    """4-rank EP at a *tight* capacity factor: the delivered-count exchange
    (dispatch_counts) must line the runtime occupancy up with the payload
    chunks — a misalignment would zero real token rows and break the
    ragged-on == ragged-off equality that under-filled buffers expose."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core import dispatch as dl, gating
        from repro.core.capacity import make_plan

        mesh = make_mesh((2, 2), ("pod", "data"))
        D, F, N, K, T = 16, 32, 8, 2, 32
        cfg = dl.MoEConfig(d_model=D, d_ff=F, num_experts=N, top_k=K,
                           capacity_factor=2.0, dtype=jnp.float32)
        ep = dl.EPSpec(num_pods=2, ep_per_pod=2, pod_axis="pod",
                       data_axis="data", model_axis=None)
        gate_cfg = gating.GateConfig(num_experts=N, top_k=K, aux_mode="ta")
        params = dl.init_moe_params(jax.random.PRNGKey(0), cfg, ep, gate_cfg)
        plan = make_plan(tokens_per_device=T, num_experts=N, top_k=K,
                         capacity_factor=2.0, num_pods=2, ep_per_pod=2,
                         mode="ta", round_multiple=1)
        x = jax.random.normal(jax.random.PRNGKey(1), (4 * T, D), jnp.float32)
        pspecs = {"gate": {"w": P()},
                  "w_in": P(("pod", "data"), None, None),
                  "w_gate": P(("pod", "data"), None, None),
                  "w_out": P(("pod", "data"), None, None)}

        def run(name, **kw):
            eng = dl.make_engine(name, cfg=cfg, ep=ep, gate_cfg=gate_cfg,
                                 **kw)
            fn = shard_map(lambda p, xx: eng(p, xx)[0], mesh=mesh,
                           in_specs=(pspecs, P(("pod", "data"), None)),
                           out_specs=P(("pod", "data"), None),
                           check_vma=False)
            with mesh:
                return np.asarray(fn(params, x))

        y_off = run("a2a", plan=plan, use_pallas=False)
        for name, kw in (("a2a", {}), ("a2a_pipelined", {"num_chunks": 2}),
                         ("gather", {})):
            pkw = dict(plan=plan) if name != "gather" else {}
            y_on = run(name, use_pallas=True, **pkw, **kw)
            ref = y_off if name != "gather" \
                else run("gather", use_pallas=False)
            err = float(np.abs(y_on - ref).max())
            print(name, "ERR", err)
            assert err < 1e-4, (name, err)
        print("MULTIRANK-RAGGED-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    assert "MULTIRANK-RAGGED-OK" in r.stdout


@pytest.mark.slow
def test_cross_path_equivalence_multipod():
    """4-rank EP (2 pods x 2) through the engine registry: a2a,
    a2a_pipelined (several chunk counts) and gather must all agree at
    matched ample capacities, with the uniform metrics schema."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core import dispatch as dl, gating
        from repro.core.capacity import make_plan

        mesh = make_mesh((2, 2), ("pod", "data"))
        D, F, N, K, T = 16, 32, 8, 2, 32   # T per rank
        cfg = dl.MoEConfig(d_model=D, d_ff=F, num_experts=N, top_k=K,
                           capacity_factor=8.0, dtype=jnp.float32)
        ep = dl.EPSpec(num_pods=2, ep_per_pod=2, pod_axis="pod",
                       data_axis="data", model_axis=None)
        gate_cfg = gating.GateConfig(num_experts=N, top_k=K, aux_mode="ta")
        params = dl.init_moe_params(jax.random.PRNGKey(0), cfg, ep, gate_cfg)
        plan = make_plan(tokens_per_device=T, num_experts=N, top_k=K,
                         capacity_factor=8.0, num_pods=2, ep_per_pod=2,
                         mode="ta", round_multiple=1)
        assert plan.cap_far > 0
        x = jax.random.normal(jax.random.PRNGKey(1), (4 * T, D), jnp.float32)
        pspecs = {"gate": {"w": P()},
                  "w_in": P(("pod", "data"), None, None),
                  "w_gate": P(("pod", "data"), None, None),
                  "w_out": P(("pod", "data"), None, None)}

        def run(name, **kw):
            eng = dl.make_engine(name, cfg=cfg, ep=ep, gate_cfg=gate_cfg,
                                 **kw)
            fn = shard_map(lambda p, xx: eng(p, xx), mesh=mesh,
                           in_specs=(pspecs, P(("pod", "data"), None)),
                           out_specs=(P(("pod", "data"), None),
                                      {k: P() for k in dl.METRIC_KEYS}),
                           check_vma=False)
            with mesh:
                y, m = fn(params, x)
            m = {k: np.asarray(v) for k, v in m.items()}
            assert set(m) == set(dl.METRIC_KEYS), m
            return np.asarray(y), m

        y_ref, m_ref = run("a2a", plan=plan)
        # both levels exercised
        assert 0.0 < float(m_ref["frac_by_level"][0]) < 1.0
        for k in (1, 2, 3):
            yk, mk = run("a2a_pipelined", plan=plan, num_chunks=k)
            err = float(np.abs(yk - y_ref).max())
            print("CHUNKS", k, "ERR", err)
            assert err < 1e-4, (k, err)
            assert abs(mk["dropped"] - m_ref["dropped"]) < 1e-6
        yg, mg = run("gather")
        err = float(np.abs(yg - y_ref).max())
        print("GATHER ERR", err)
        assert err < 1e-3, err
        print("MULTIPOD-ORACLE-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    assert "MULTIPOD-ORACLE-OK" in r.stdout
