import os
if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
# ^ MUST run before any jax import: the sweeps build 8-device meshes (2x4
# pod x data and 2x2x2 pod x node x data) out of forced host devices.  When
# imported through benchmarks.run the sweep re-launches itself in a
# subprocess instead (jax may already be initialized with one device there).

"""Pipelined-dispatch overlap sweep (comm–compute overlap ablation).

For num_chunks in {1, 2, 4} on an 8-host-device mesh — both the 2-tier
2x4 (pod x data) and the 3-tier 2x2x2 (pod x node x data) hierarchy —
measure the wall-clock of one MoE layer step under ``a2a`` (sync baseline)
and ``a2a_pipelined`` through the dispatch-engine registry, and report the
alpha-beta model's simulated sync / pipelined exchange-step times for the
same level-indexed plan.  Host-device collectives are memcpys, so the
*measured* columns are a schedule-correctness and overhead check, while
the *simulated* columns show the predicted overlap on the target
interconnect (ICI/DCN/DCI ladder in core/topology.py).

Usage:
    PYTHONPATH=src python -m benchmarks.fig_overlap
"""

import subprocess
import sys
import time

CHUNKS = (1, 2, 4)


def _measure(fn, *args):
    jfn = __import__("jax").jit(fn)
    import jax
    out = jax.block_until_ready(jfn(*args))
    t0 = time.time()
    iters = 10
    for _ in range(iters):
        out = jax.block_until_ready(jfn(*args))
    return (time.time() - t0) / iters


def sweep(axis_sizes, T=256, D=64, F=128, N=16, K=2):
    """One overlap sweep on an EP hierarchy of ``axis_sizes`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.core import capacity, comm_model, dispatch as dl, gating
    from repro.core.capacity import default_axis_names

    names = default_axis_names(len(axis_sizes))
    topo_tag = "x".join(str(s) for s in axis_sizes)
    suffix = "" if len(axis_sizes) == 2 else f"@{len(axis_sizes)}tier"
    mesh = make_mesh(axis_sizes, names)
    cfg = dl.MoEConfig(d_model=D, d_ff=F, num_experts=N, top_k=K,
                       capacity_factor=2.0, dtype=jnp.float32)
    ep = dl.EPSpec.from_axes(names, axis_sizes)
    gate_cfg = gating.GateConfig(num_experts=N, top_k=K, aux_mode="ta")
    params = dl.init_moe_params(jax.random.PRNGKey(0), cfg, ep, gate_cfg)
    base_plan = capacity.make_dispatch_plan(
        tokens_per_device=T, num_experts=N, top_k=K, capacity_factor=2.0,
        axis_sizes=axis_sizes, axis_names=names, mode="ta")
    x = jax.random.normal(jax.random.PRNGKey(1), (ep.ep_world * T, D),
                          jnp.float32)
    pspec = dl.moe_param_specs(cfg, ep)
    pspec["gate"] = {"w": P()}

    def wrap(name, plan, num_chunks=1):
        eng = dl.make_engine(name, cfg=cfg, ep=ep, gate_cfg=gate_cfg,
                             plan=plan, num_chunks=num_chunks)
        return shard_map(lambda p, xx: eng(p, xx)[0], mesh=mesh,
                         in_specs=(pspec, P(names, None)),
                         out_specs=P(names, None), check_vma=False)

    rows = []
    caps = "/".join(str(c) for c in base_plan.caps)
    print(f"# overlap sweep: {topo_tag} host mesh ({'x'.join(names)}), "
          f"T/rank={T}, N={N}, top-{K}, caps by level={caps}")
    print(f"{'schedule':18s}{'chunks':>7s}{'meas ms':>9s}{'sim sync ms':>12s}"
          f"{'sim pipe ms':>12s}{'sim speedup':>12s}")

    with mesh:
        t_sync = _measure(wrap("a2a", base_plan), params, x)
    terms = comm_model.moe_overlap_terms(base_plan, d_model=D, d_ff=F,
                                         bytes_per_el=4)
    est1 = comm_model.estimate_overlap(num_chunks=1, **terms)
    print(f"{'a2a (sync)':18s}{'-':>7s}{t_sync*1e3:9.2f}"
          f"{est1.t_sync*1e3:12.4f}{'-':>12s}{'-':>12s}")
    rows.append((f"fig_overlap_sync{suffix}", t_sync * 1e6,
                 f"sim_ms={est1.t_sync*1e3:.4f};topology={topo_tag}"))

    for k in CHUNKS:
        plan = capacity.align_to_chunks(base_plan, k)
        with mesh:
            t = _measure(wrap("a2a_pipelined", plan, k), params, x)
        est = comm_model.estimate_overlap(num_chunks=k, **terms)
        print(f"{'a2a_pipelined':18s}{k:>7d}{t*1e3:9.2f}"
              f"{est.t_sync*1e3:12.4f}{est.t_pipelined*1e3:12.4f}"
              f"{est.speedup:12.2f}")
        rows.append((f"fig_overlap_pipelined_c{k}{suffix}", t * 1e6,
                     f"sim_pipe_ms={est.t_pipelined*1e3:.4f};"
                     f"sim_speedup={est.speedup:.2f};topology={topo_tag}"))
    auto = comm_model.choose_num_chunks(**terms)
    print(f"# comm-model pick (topology constants): num_chunks={auto}")
    rows.append((f"fig_overlap_auto_chunks{suffix}", float(auto),
                 f"model choice;topology={topo_tag}"))

    # quantized wire: rerun the chunk chooser on int8-codec byte counts
    # (1-byte payload + f32 scale sideband) — the codec swap must be
    # visible in the chooser's inputs, and often in its verdict
    qterms = comm_model.moe_overlap_terms(base_plan, d_model=D, d_ff=F,
                                          bytes_per_el=4, codec="int8")
    q_auto = comm_model.choose_num_chunks(**qterms)
    print(f"# comm-model pick (int8 wire codec): num_chunks={q_auto} "
          f"(t_exchange {terms['t_exchange']*1e6:.2f}us -> "
          f"{qterms['t_exchange']*1e6:.2f}us)")
    rows.append((f"fig_overlap_auto_chunks_int8{suffix}", float(q_auto),
                 f"t_exchange_us={qterms['t_exchange']*1e6:.2f};"
                 f"topology={topo_tag}"))

    # measured alpha/beta: micro-benchmark every mesh axis and rerun the
    # chunk chooser on the fitted terms (level-indexed links)
    links = comm_model.measured_ep_links(mesh, ep.axis_names)
    mterms = comm_model.moe_overlap_terms(base_plan, d_model=D, d_ff=F,
                                          bytes_per_el=4, links=links)
    m_auto = comm_model.choose_num_chunks(**mterms)
    for ax in ep.axis_names:
        li = links.get(ax)
        if li is not None:
            print(f"# measured axis {ax!r}: alpha={li.alpha*1e6:.1f}us "
                  f"beta={li.beta*1e9:.3f}ns/B")
    for r in comm_model.stage_overlap_terms(base_plan, d_model=D,
                                            bytes_per_el=4, links=links):
        print(f"# stage {r['stage']}: {r['bytes']/1e3:.1f}kB  "
              f"t_exchange={r['t_exchange']*1e6:.2f}us")
    print(f"# comm-model pick (measured alpha/beta): num_chunks={m_auto}")
    rows.append((f"fig_overlap_auto_chunks_measured{suffix}", float(m_auto),
                 f"alpha_us={mterms['alpha']*1e6:.2f};topology={topo_tag}"))
    return rows


def main():
    import jax
    assert jax.device_count() >= 8, (
        "needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    rows = sweep((2, 4))          # 2-tier: pod x data
    rows += sweep((2, 2, 2))      # 3-tier: pod x node x data
    for name, us, derived in rows:
        print(f"CSV {name},{us:.2f},{derived}")
    return rows


def run():
    """benchmarks.run entry: re-exec in a subprocess so the forced 8-device
    host platform is set before jax initializes.  The child runs on the
    CPU (``JAX_PLATFORMS=cpu``), so its rows are CPU rows, and it never
    contends with the parent for an accelerator the parent may hold."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m", "benchmarks.fig_overlap"],
                       capture_output=True, text=True, timeout=1800, env=env)
    print(r.stdout, end="")
    if r.returncode != 0:
        raise RuntimeError(f"fig_overlap subprocess failed:\n{r.stderr[-2000:]}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("CSV "):
            name, us, derived = line[4:].split(",", 2)
            rows.append((name, float(us), f"{derived};backend=cpu"))
    return rows


if __name__ == "__main__":
    main()
