"""Roofline share of the ragged grouped GEMM in expert-parallel training:
least time of its work at the chip's peaks over the device time of the
Pallas calls that take the stack of the experts a chip holds,
``bf16[experts / chips, d, d_ff_expert]`` (``_moe_kernels.pattern``) --
not the one-chip cell's whole stack, whose fused kernel
``moe_kernel_roofline`` reads.  The work of one run is the kept
assignments that reach the chip, tokens x top_k x (1 - dropped) / chips,
tokens those of a step; its least time the larger of their FLOPs over the
bf16 peak and, over HBM bandwidth, the bytes of the held experts' weights
and of those rows (``bench/counts.py`` through a ``Spec`` whose
``experts`` is the held count).  Runs and time are the means over the
chips used; runs are counted in the trace, as ``moe_kernel_roofline.train``
does, so that recompute shows as more runs.  An op matches where its text
holds the data file's ``op`` and ``operand`` (plain substrings, as
``_moe_kernels.pattern`` states them).  None on one chip."""

import dataclasses
import json
import os

from bench import counts
from bench.metrics import _ops


def read(info):
    tr = info.get("trace")
    if (info.get("kind") != "train" or not info.get("steps")
            or info.get("chips", 1) < 2 or tr is None or not tr.devices):
        return None
    chips = info["chips"]
    s = dataclasses.replace(info["spec"], experts=info["spec"].experts // chips)
    with open(os.path.join(os.path.dirname(__file__),
                           "moe_gemm_roofline.train.json")) as f:
        want = json.load(f)
    test = _ops.having(want["op"], want["operand"].format(
        experts=s.experts, d=s.d, d_ff_expert=s.d_ff_expert))
    devs = tr.devices[:chips]
    got = [_ops.runs(d, test) for d in devs]
    runs = sum(n for n, _ in got) / len(devs)
    t = sum(t for _, t in got) / len(devs)
    if not runs or t <= 0:
        return None
    kept = (info["tokens"] / info["steps"] * s.top_k
            * (1.0 - info["dropped"]) / chips)
    pk = info["peak"]
    least = max(counts.expert_flops(s, kept) / pk["bf16_flops"],
                counts.expert_call_bytes(s, kept) / pk["hbm_bytes_per_s"])
    return 100.0 * runs * least / t
