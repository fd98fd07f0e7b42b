"""The benchmark's trace reduction on a synthesised ``.xplane.pb``: busy
union, kernel and program time, exposed-collective share, idle gaps."""

import os

import pytest

import tiny_cells  # noqa: F401  (puts the repo root on sys.path)
from bench import trace as trace_lib

US = 1_000_000  # picoseconds in a microsecond


def _events(spec, meta):
    out = []
    for name, start_us, dur_us in spec:
        mid = meta.setdefault(name, len(meta) + 1)
        out.append(f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
                   f"duration_ps: {dur_us * US} }}")
    return "\n".join(out)


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (lname, spec) in enumerate(lines, start=1):
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                    f"{_events(spec, meta)} }}")
    md = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                   for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body)
            + "\n" + md + "\n}")


# device 0: a loop [0,15) around fusions [0,10) and [10,15), a collective
# [20,30) half hidden by compute [25,27), a Pallas MoE kernel [40,50);
# programs jit_step x2
KERNEL = ('%closed_call.5 = f32[64,1,1024] custom-call(bf16[64,1024,2048] %w), '
          'custom_call_target=\\"tpu_custom_call\\"')
DEV0_OPS = [("%while.9 = (s32[]) while(s32[] %t)", 0, 15),
            ("%fusion.1 = f32[8]", 0, 10), ("%fusion.2 = f32[8]", 10, 5),
            ("%all-to-all.3 = f32[8]", 20, 10), ("%fusion.4 = f32[8]", 25, 2),
            (KERNEL, 40, 10)]
DEV0_MODS = [("jit_step(12)", 0, 30), ("jit_step(12)", 40, 20),
             ("jit_prefill_cached(3)", 70, 5)]
# device 1: busy [0, 20)
DEV1_OPS = [("fusion.1", 0, 20)]
HOST = [("bench.step", 0, 60), ("bench.data", 32, 6)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Ops", DEV0_OPS),
                                    ("XLA Modules", DEV0_MODS)]),
        _plane(2, "/device:TPU:1", [("XLA Ops", DEV1_OPS)]),
        _plane(3, "/host:CPU", [("python", HOST)])])
    d = tmp_path_factory.mktemp("trace")
    sub = d / "plugins" / "profile" / "2026_01_01"
    os.makedirs(sub)
    with open(sub / "host.xplane.pb", "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_lib.load(str(d))


def test_planes_found(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0",
                                                  "/device:TPU:1"]
    assert len(recorded.host) == 2


def test_busy_is_the_union_of_op_intervals(recorded):
    d0, d1 = recorded.devices
    # [0,15) + [20,30) + [40,50) = 35 us; overlap and nesting counted once
    assert trace_lib.busy_s(d0) == pytest.approx(35e-6)
    assert trace_lib.busy_s(d1) == pytest.approx(20e-6)


def test_kernel_and_program_time(recorded):
    d0 = recorded.devices[0]
    assert trace_lib.op_seconds(d0, "tpu_custom_call") == pytest.approx(10e-6)
    assert trace_lib.op_seconds(d0, "fusion") == pytest.approx(17e-6)
    assert trace_lib.module_durations(d0, "^jit_step") == pytest.approx(
        [30e-6, 20e-6])


def test_exposed_collective(recorded):
    # the all-to-all runs [20,30); compute covers [25,27): 8 us exposed
    d0 = recorded.devices[0]
    assert trace_lib.exposed_collective_s(d0) == pytest.approx(8e-6)
    assert trace_lib.exposed_collective_s(recorded.devices[1]) == 0.0


def test_idle_gaps_named_by_host_span(recorded):
    d0 = recorded.devices[0]
    lo, hi = trace_lib.span(recorded)
    assert (lo, hi) == pytest.approx((0.0, 50e-6))
    gaps = trace_lib.idle_gaps(d0, recorded.host, lo, hi)
    # gaps [15,20) and [30,40); the longer one's midpoint 35 lies in
    # bench.data, the shorter's in bench.step only
    assert [g[0] for g in gaps] == ["bench.data", "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([10e-6, 5e-6])


def test_top_ops_are_leaves(recorded):
    top = dict(trace_lib.top_ops(recorded.devices[0]))
    # the loop holds the fusions and the all-to-all holds fusion.4
    assert not any(k.startswith(("%while", "%all-to-all")) for k in top)
    assert top["%fusion.1 = f32[8]"] == pytest.approx(10e-6)
    assert top["%fusion.2 = f32[8]"] == pytest.approx(5e-6)
    assert max(top.values()) == pytest.approx(10e-6)


def test_interval_helpers():
    assert trace_lib.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace_lib.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace_lib.length([(0, 1), (0.5, 2)]) == 2


def test_op_runs_counted(recorded):
    d0 = recorded.devices[0]
    assert trace_lib.op_count(d0, "tpu_custom_call") == 1
    assert trace_lib.op_count(d0, "^%fusion") == 3


def test_train_kernel_roofline_counts_recomputed_runs():
    # one step over two MoE layers whose kernels each run twice (forward,
    # then again in the recomputing backward pass), 2 ms a run: the share
    # is that of one run, not of two calls taking 4 ms each
    from bench import counts, harness
    from bench.reference import moe_lm
    s = moe_lm.spec(harness.cell(tiny_cells.TRAIN).config)
    name = ('%closed_call.1 = f32[8192,1,1024] custom-call(bf16[64,1024,2048]'
            ' %w), custom_call_target="tpu_custom_call"')
    ops = [(name, 0.01 * i, 0.01 * i + 0.002) for i in range(4)]
    tr = trace_lib.Trace([trace_lib.Device("/device:TPU:0", ops, [])], [])
    pk = harness.peaks("TPU v5 lite")
    info = {"kind": "train", "spec": s, "trace": tr, "chips": 1, "peak": pk,
            "tokens": 8192, "steps": 1, "dropped": 0.25}
    got = harness.read_per_layer(
        [{"name": "moe_kernel_roofline.train", "unit": "%"}], info)
    least = max(counts.expert_flops(s, 8192 * 2 * 0.75) / pk["bf16_flops"],
                counts.expert_call_bytes(s, 8192) / pk["hbm_bytes_per_s"])
    assert got["moe_kernel_roofline.train"]["value"] == pytest.approx(
        100 * least / 0.002)
