"""The four-chip cell's trace readers on a recorded ``.xplane.pb``:
``a2a_exposed_share.train`` counts an all-to-all only where no other
operation runs (an enclosing loop hides nothing, the gradient all-reduce is
not the exchange), ``moe_gemm_roofline.train`` reads the Pallas calls on a
chip's 16 held experts and not the one-chip cell's 64, and
``moe_permute_roofline.train`` the row-moving kernels; the cell's
breakdown by op kind counts the same way, and the interval subtraction they
share gives what ``bench/trace.py``'s gives."""

import dataclasses
import os
import random

import pytest

import tiny_cells  # noqa: F401  (puts the repo root on sys.path)
from bench import counts, harness
from bench import trace as trace_lib
from bench.cells import train_lm_ep
from bench.metrics import _ops
from bench.reference import moe_lm

US = 1_000_000  # picoseconds in a microsecond
EP4 = "gpt3_moe_full.train_ep4"
CALL = 'custom_call_target=\\"tpu_custom_call\\"'


def _plane(pid, name, ops):
    meta, events = {}, []
    for op, start_us, dur_us in ops:
        mid = meta.setdefault(op, len(meta) + 1)
        events.append(f"events {{ metadata_id: {mid} offset_ps: "
                      f"{start_us * US} duration_ps: {dur_us * US} }}")
    md = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
                   f"}} }}" for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
            + "\n".join(events) + " }\n" + md + "\n}")


def _kernel(out, operand, n=0):
    return (f"%closed_call.{n} = {out} custom-call({operand} %w), {CALL}")


HELD = _kernel("bf16[32768,1024]", "bf16[16,1024,2048]", 1)
WHOLE = _kernel("f32[12288,1,1024]", "bf16[64,1024,2048]", 2)
PERMUTE = _kernel("f32[32768,1,1024]", "f32[8193,1,1024]", 3)
UNPERMUTE = _kernel("f32[8192,1,1024]", "f32[32769,1,1024]", 4)
# chip 0: a loop [0,100) around a fusion [0,10), an exposed all-to-all
# [10,20), one hidden by a fusion [30,40), an exposed all-reduce [50,60),
# two runs of the held-expert GEMM (1 ms each in all), a run of the
# one-chip kernel, a permute and an unpermute; chip 1: an exposed
# all-to-all [0,5)
DEV0 = [("%while.1 = (s32[]) while(s32[] %t)", 0, 100),
        ("%fusion.1 = f32[8]", 0, 10), ("%all-to-all.2 = bf16[4,16]", 10, 10),
        ("%all-to-all.3 = bf16[4,16]", 30, 10), ("%fusion.4 = f32[8]", 30, 10),
        ("%all-reduce.5 = f32[8]", 50, 10), (HELD, 60, 10), (HELD, 70, 10),
        (WHOLE, 80, 5), (PERMUTE, 85, 4), (UNPERMUTE, 89, 2)]
DEV1 = [("%all-to-all.2 = bf16[4,16]", 0, 5), ("%fusion.7 = f32[8]", 5, 50)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData
    text = "\n".join([_plane(1, "/device:TPU:0", DEV0),
                      _plane(2, "/device:TPU:1", DEV1)])
    d = tmp_path_factory.mktemp("trace")
    sub = d / "plugins" / "profile" / "2026_01_01"
    os.makedirs(sub)
    with open(sub / "host.xplane.pb", "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_lib.load(str(d))


def _info(tr, chips=2, **kw):
    s = moe_lm.spec(harness.cell(EP4).config)
    return {"kind": "train", "spec": s, "trace": tr, "chips": chips,
            "peak": harness.peaks("TPU v5 lite"), "window_s": 100e-6,
            "tokens": 32768, "steps": 1, "dropped": 0.25, "capacity": 512,
            **kw}


def _read(name, info):
    got = harness.read_per_layer([{"name": name, "unit": "%"}], info)
    return got.get(name, {}).get("value")


def test_a2a_exposed_counts_only_what_no_op_covers(recorded):
    # chip 0: [10,20) exposed (the loop around it hides nothing), [30,40)
    # hidden, the all-reduce not counted; chip 1: [0,5) exposed
    assert _read("a2a_exposed_share.train", _info(recorded)) == \
        pytest.approx(100 * (10e-6 + 5e-6) / 2 / 100e-6)


def test_a2a_exposed_silent_without_all_to_all(recorded):
    one = trace_lib.Trace([trace_lib.Device("/device:TPU:0",
                                            [("%fusion.1 = f32[8]", 0, 1)],
                                            [])], [])
    assert _read("a2a_exposed_share.train", _info(one, chips=1)) is None


def test_gemm_reader_matches_the_held_stack_only(recorded):
    # four chips hold 16 experts each; two of them recorded: the held-stack
    # kernel runs once a chip for 10 us, the one-chip kernel is not read
    info = _info(recorded, chips=4)
    held = dataclasses.replace(info["spec"], experts=16)
    kept = 32768 * 2 * 0.75 / 4
    pk = info["peak"]
    least = max(counts.expert_flops(held, kept) / pk["bf16_flops"],
                counts.expert_call_bytes(held, kept) / pk["hbm_bytes_per_s"])
    assert _read("moe_gemm_roofline.train", info) == pytest.approx(
        100 * least / 10e-6)
    assert _read("moe_gemm_roofline.train", _info(recorded, chips=1)) is None


def test_permute_reader_moves_rows_over_bandwidth(recorded):
    # four chips: a chip's 8192 tokens and 64 x 512 slots match the
    # recorded permute (4 us) and unpermute (2 us)
    info = _info(recorded, chips=4)
    T, S = 32768 // 4, 64 * 512
    moved = (2 * S + 3 * T) * info["spec"].d * 4
    assert _read("moe_permute_roofline.train", info) == pytest.approx(
        100 * moved / info["peak"]["hbm_bytes_per_s"] / 6e-6)
    assert _read("moe_permute_roofline.train",
                 _info(recorded, chips=1)) is None


def test_breakdown_by_kind_counts_exposed_collectives(recorded):
    got = train_lm_ep.by_kind(recorded, 2)
    # all-to-all: 20 us on chip 0 (10 of them exposed), 5 exposed on chip 1
    assert got["all-to-all_s"] == pytest.approx((20e-6 + 5e-6) / 2)
    assert got["all-to-all_exposed_s"] == pytest.approx((10e-6 + 5e-6) / 2)
    assert got["all-reduce_exposed_s"] == pytest.approx(10e-6 / 2)
    assert got["pallas_s"] == pytest.approx((20e-6 + 5e-6 + 4e-6 + 2e-6) / 2)


@pytest.mark.parametrize("seed", range(4))
def test_linear_subtract_is_trace_subtract(seed):
    rng = random.Random(seed)

    def intervals(n, width):
        out = []
        for _ in range(n):
            s = rng.randint(0, 200)
            out.append((s, s + rng.randint(0, width)))
        return out
    for _ in range(200):
        a, b = intervals(rng.randint(0, 10), 30), intervals(rng.randint(0, 20),
                                                            20)
        assert _ops.subtract(a, b) == trace_lib.subtract(a, b), (a, b)
