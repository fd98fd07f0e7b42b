"""HLO collective verifier.

AOT-lowers the MoE step (``jax.jit(...).lower()`` over an abstract mesh —
no devices needed, so this runs on single-CPU CI) for every registered
dispatch path × topology, parses the StableHLO for collective ops, and
asserts the inventory matches what the Eq. (7) ``DispatchPlan`` promises:

* one all_to_all **chain** per active remote stage — stage ``s`` hops
  over its ``s+1`` delivery axes, each hop's ``replica_groups`` exactly
  the device groups of that mesh axis;
* per-hop payloads of ``num_dests × E_l × cap_chunk × d`` elements in
  the **wire dtype** (the resolved ``MoEConfig.wire_codec``), i.e. wire
  bytes scale with the plan's caps — and with the chunk count on the
  pipelined path;
* the valid-count exchange riding the same chain (int32, no wire cast)
  exactly when the occupancy-aware ragged GEMM is active;
* for **scaled** wire codecs (int8 / fp8e4m3), the per-segment f32
  scale sideband riding the same chain — one scale exchange per payload
  exchange, ``num_dests × E_l`` f32 elements each, dispatch and combine;
* **no** unaccounted collective anywhere in the step — stray
  all-gathers / reshards in the hot path are inventory violations, and
  the fused unit-mesh path must lower to **zero** collectives
  (generalizing the old ``test_moe_fused`` jaxpr pin);
* the gather path's per-axis all_gather + psum pairs, and the einsum
  oracle's empty inventory.

The expected inventory is *computed*, not hard-coded: it replicates the
engine's stage split (``plan_stages``, the fused local-stage shortcut,
``use_ragged``) and capacity arithmetic (cap clamp, chunk alignment)
from the same modules the engine uses, so a plan change moves both sides
together while a mapping bug moves only the lowering.
"""

from __future__ import annotations

import ast
import dataclasses
import math
import re

from repro.analysis import Violation

# innermost axis last, matching EPSpec's outermost-first hierarchy order
_AXIS_NAMES = {1: ("data",), 2: ("pod", "data"), 3: ("pod", "node", "data")}

# jnp dtype name -> StableHLO element type
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
              "int32": "i32", "int8": "i8", "float8_e4m3fn": "f8E4M3FN",
              "float8_e5m2": "f8E5M2"}


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One lowering under verification: dispatch path × topology × kernel
    flag (× wire dtype / chunk count)."""

    name: str
    axis_sizes: tuple
    path: str
    use_pallas: bool
    num_chunks: int = 1
    a2a_dtype: str = ""           # deprecated cast-only wire (kept for the
                                  # alias coverage); prefer wire_codec
    wire_codec: str = ""          # registered codec name in dispatch.wire
    tokens: int = 32
    num_experts: int = 16
    d_model: int = 16
    d_ff: int = 32
    top_k: int = 2
    capacity_factor: float = 2.0

    @property
    def axis_names(self) -> tuple:
        return _AXIS_NAMES[len(self.axis_sizes)]


def default_scenarios() -> tuple:
    """All four dispatch paths on the 2-level (2×2) and 3-level (2×2×2)
    meshes, kernels on and off, plus the pipelined chunking, the fused
    unit-mesh zero-collective pin, a cast-wire variant, and the scaled
    (int8 / fp8e4m3) wire-codec variants with their scale sidebands."""
    return (
        Scenario("a2a-2x2-ref", (2, 2), "a2a", False),
        Scenario("a2a-2x2-kernels", (2, 2), "a2a", True),
        Scenario("a2a_pipelined-2x2-kernels", (2, 2), "a2a_pipelined", True,
                 num_chunks=2),
        Scenario("gather-2x2-ref", (2, 2), "gather", False),
        Scenario("gather-2x2-kernels", (2, 2), "gather", True),
        Scenario("einsum-2x2", (2, 2), "einsum", False),
        Scenario("a2a-2x2x2-ref", (2, 2, 2), "a2a", False),
        Scenario("a2a-2x2x2-kernels", (2, 2, 2), "a2a", True),
        Scenario("a2a_pipelined-2x2x2-kernels", (2, 2, 2), "a2a_pipelined",
                 True, num_chunks=2),
        Scenario("gather-2x2x2-ref", (2, 2, 2), "gather", False),
        Scenario("einsum-2x2x2", (2, 2, 2), "einsum", False),
        Scenario("a2a-unit-mesh-fused", (1,), "a2a", True),
        Scenario("a2a-2x2-wire-bf16", (2, 2), "a2a", True,
                 a2a_dtype="bfloat16"),
        Scenario("a2a-2x2-wire-int8", (2, 2), "a2a", True,
                 wire_codec="int8"),
        Scenario("a2a-2x2x2-wire-fp8e4m3", (2, 2, 2), "a2a", True,
                 wire_codec="fp8e4m3"),
    )


@dataclasses.dataclass(frozen=True)
class Collective:
    """A collective op signature.  On *expected* entries, ``None`` fields
    are wildcards; parsed entries carry ``None`` only where the textual
    form omits the information (e.g. region ops' operand type)."""

    kind: str
    dtype: str | None = None
    elements: int | None = None
    groups: tuple | None = None

    def describe(self) -> str:
        parts = [self.kind]
        if self.dtype is not None:
            parts.append(f"dtype={self.dtype}")
        if self.elements is not None:
            parts.append(f"elements={self.elements}")
        if self.groups is not None:
            parts.append(f"groups={list(map(list, self.groups))}")
        return " ".join(parts)


def axis_groups(names, sizes, axis) -> tuple:
    """Replica groups of mesh axis ``axis``: device ids laid out
    row-major over the mesh, grouped by fixing every other axis."""
    import numpy as np

    ids = np.arange(math.prod(sizes)).reshape(sizes)
    k = names.index(axis)
    rows = np.moveaxis(ids, k, -1).reshape(-1, sizes[k])
    return tuple(sorted(tuple(int(x) for x in row) for row in rows))


# ---------------------------------------------------------------------------
# expected inventory (computed from the same modules the engine uses)
# ---------------------------------------------------------------------------


def _scenario_codec(sc: Scenario):
    """Resolve the scenario's wire codec the way MoEConfig does: the
    first-class ``wire_codec`` name wins, the deprecated ``a2a_dtype``
    falls back to a cast-only codec (no warning here — the analysis lane
    exercises the alias deliberately)."""
    from repro.core.dispatch import wire as wire_lib

    if sc.wire_codec:
        return wire_lib.get_codec(sc.wire_codec)
    if sc.a2a_dtype:
        return wire_lib.cast_codec(sc.a2a_dtype)
    return None


def expected_inventory(sc: Scenario) -> list:
    from repro.core import dispatch as dispatch_lib
    from repro.core.capacity import make_dispatch_plan
    from repro.core.dispatch import transport
    from repro.kernels.moe_fused import ops as fused_ops
    from repro.kernels.moe_gemm import ops as gemm_ops

    names = sc.axis_names
    T, d, N = sc.tokens, sc.d_model, sc.num_experts
    ep_world = math.prod(sc.axis_sizes)
    E_l = N // ep_world
    groups_of = {a: axis_groups(names, sc.axis_sizes, a) for a in names}

    if sc.path == "einsum":
        return []

    if sc.path == "gather":
        exp = []
        for a, size in zip(names, sc.axis_sizes):
            if size == 1:
                continue
            exp.append(Collective("all_gather", groups=groups_of[a]))
            exp.append(Collective("all_reduce", groups=groups_of[a]))
        return exp

    # staged a2a paths
    plan = make_dispatch_plan(
        tokens_per_device=T, num_experts=N, top_k=sc.top_k,
        capacity_factor=sc.capacity_factor, axis_sizes=sc.axis_sizes,
        mode="ta")
    ep = dispatch_lib.EPSpec.from_axes(names, sc.axis_sizes, model_axis=None)
    stages = transport.plan_stages(plan, ep)
    fused_on = fused_ops.use_fused(sc.use_pallas)
    ragged = gemm_ops.use_ragged(sc.use_pallas)
    codec = _scenario_codec(sc)
    wire = _HLO_DTYPE[str(codec.wire_dtype) if codec else "float32"]
    scaled = codec is not None and codec.scaled
    nc = max(1, sc.num_chunks)

    exp = []
    for stage in stages:
        if stage.cap <= 0:
            continue
        if fused_on and stage.num_dests == 1:
            continue  # fused local path: zero collectives for this stage
        cap_eff = min(int(stage.cap), T)       # routing.select's clamp
        aligned = -(-cap_eff // nc) * nc       # routing.pad_selection
        cpc = aligned // nc
        payload = stage.num_dests * E_l * cpc * d
        counts = stage.num_dests * E_l
        for ax, size in zip(stage.axis_names, stage.axis_sizes):
            if size == 1:
                continue  # trivial hop: jax lowers it away
            for _ in range(nc):
                # dispatch hop + combine hop, both in the wire dtype
                exp.append(Collective("all_to_all", wire, payload,
                                      groups_of[ax]))
                exp.append(Collective("all_to_all", wire, payload,
                                      groups_of[ax]))
                if scaled:
                    # per-segment f32 scale sideband: one exchange per
                    # payload exchange, shaped like the count tensor
                    exp.append(Collective("all_to_all", "f32", counts,
                                          groups_of[ax]))
                    exp.append(Collective("all_to_all", "f32", counts,
                                          groups_of[ax]))
                if ragged:
                    # valid-count exchange rides the same chain, exact i32
                    exp.append(Collective("all_to_all", "i32", counts,
                                          groups_of[ax]))
    return exp


# ---------------------------------------------------------------------------
# lowering + StableHLO parsing
# ---------------------------------------------------------------------------


def lower_scenario(sc: Scenario) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.compat import shard_map
    from repro.core import dispatch as dispatch_lib, gating
    from repro.core.capacity import make_dispatch_plan
    from repro.core.dispatch.base import moe_param_specs

    names = sc.axis_names
    T, d, N = sc.tokens, sc.d_model, sc.num_experts
    ep_world = math.prod(sc.axis_sizes)
    cfg = dispatch_lib.MoEConfig(d_model=d, d_ff=sc.d_ff, num_experts=N,
                                 top_k=sc.top_k, dtype=jnp.float32,
                                 wire_codec=_scenario_codec(sc))
    ep = dispatch_lib.EPSpec.from_axes(names, sc.axis_sizes, model_axis=None)
    gate_cfg = gating.GateConfig(num_experts=N, top_k=sc.top_k,
                                 aux_mode="lb")
    params = dispatch_lib.init_moe_params(jax.random.PRNGKey(0), cfg, ep,
                                          gate_cfg)
    kwargs = {}
    if sc.path in ("a2a", "a2a_pipelined"):
        kwargs["plan"] = make_dispatch_plan(
            tokens_per_device=T, num_experts=N, top_k=sc.top_k,
            capacity_factor=sc.capacity_factor, axis_sizes=sc.axis_sizes,
            mode="ta")
    if sc.path == "einsum":
        kwargs["capacity"] = T
    eng = dispatch_lib.make_engine(sc.path, cfg=cfg, ep=ep,
                                   gate_cfg=gate_cfg,
                                   num_chunks=sc.num_chunks,
                                   use_pallas=sc.use_pallas, **kwargs)

    mesh = AbstractMesh(tuple(sc.axis_sizes), tuple(names))
    if sc.path == "einsum":
        # the shard-local oracle: everything replicated, no mesh traffic
        pspecs, xspec = jax.tree.map(lambda _: P(), params), P()
    else:
        pspecs, xspec = moe_param_specs(cfg, ep), P(names)
    xg = jnp.zeros((T * ep_world, d), jnp.float32)
    fn = shard_map(lambda p, xx: eng(p, xx), mesh=mesh,
                   in_specs=(pspecs, xspec), out_specs=(xspec, P()),
                   check_vma=False)
    return jax.jit(fn).lower(params, xg).as_text()


_OP_RE = re.compile(r'"stablehlo\.(all_to_all|all_gather|all_reduce'
                    r'|reduce_scatter|collective_permute|collective_broadcast'
                    r')"')
_GROUPS_RE = re.compile(r"replica_groups = dense<(\[\[.*?\]\])>")
_TYPE_RE = re.compile(r"\}>\s*:\s*\(tensor<([^>]*)>")


def parse_collectives(text: str) -> list:
    """Collective signatures from a StableHLO dump.  Ops print one per
    line; region ops (all_reduce) keep their attributes on the first line
    but their type signature after the region, so dtype/elements stay
    ``None`` for them."""
    out = []
    for line in text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        groups = None
        gm = _GROUPS_RE.search(line)
        if gm:
            raw = ast.literal_eval(gm.group(1))
            groups = tuple(sorted(tuple(int(x) for x in g) for g in raw))
        dtype = elements = None
        tm = _TYPE_RE.search(line)
        if tm:
            parts = tm.group(1).split("x")
            dtype = parts[-1]
            elements = math.prod(int(p) for p in parts[:-1])
        out.append(Collective(m.group(1), dtype, elements, groups))
    return out


# ---------------------------------------------------------------------------
# matching
# ---------------------------------------------------------------------------


def _matches(exp: Collective, act: Collective) -> bool:
    if exp.kind != act.kind:
        return False
    return all(getattr(exp, f) is None or getattr(exp, f) == getattr(act, f)
               for f in ("dtype", "elements", "groups"))


def match_inventory(where: str, expected, actual) -> list:
    """Greedy multiset match; every miss in either direction is a
    violation (so stray collectives fail even when all expected ones are
    present)."""
    violations = []
    remaining = list(actual)
    for exp in expected:
        hit = next((a for a in remaining if _matches(exp, a)), None)
        if hit is None:
            violations.append(Violation(
                "hlo", "collective-inventory", where,
                f"missing expected collective: {exp.describe()}"))
        else:
            remaining.remove(hit)
    for act in remaining:
        violations.append(Violation(
            "hlo", "collective-inventory", where,
            f"unexpected collective in the lowering: {act.describe()}"))
    return violations


def verify(sc: Scenario, expected=None) -> list:
    """Lower one scenario and diff its collective inventory against the
    plan-derived expectation (``expected`` overrides it — fixtures use
    this to prove the check fires)."""
    if expected is None:
        expected = expected_inventory(sc)
    actual = parse_collectives(lower_scenario(sc))
    return match_inventory(sc.name, expected, actual)


def run(scenarios=None) -> tuple:
    if scenarios is None:
        scenarios = default_scenarios()
    violations, covered = [], []
    for sc in scenarios:
        covered.append(sc.name)
        violations.extend(verify(sc))
    return violations, covered
