"""Roofline share of the MoE Pallas kernels, shared by the
``moe_kernel_roofline.*`` readers.

Least time of the routed work at the chip's peaks, summed over MoE-layer
calls, each call the larger of its FLOPs over the bf16 peak and its bytes
over HBM bandwidth (``bench/counts.py``), divided by the summed device
time of the kernels the metric's data file names: Pallas calls
(``tpu_custom_call``) that take an expert weight stack
``bf16[experts, d, d_ff_expert]``.  Forward work only: the kernels run the
forward; the backward is not a Pallas kernel.  A reader may count the
kernel's runs in the trace (:func:`executions`) rather than the calls the
model makes, so that a step that runs the kernel again (a recomputing
backward pass) shows as more runs and not as a slower kernel."""

import json
import os
import re

from bench import counts
from bench import trace as trace_lib


def pattern(metric: str, s) -> str:
    """Ops whose HLO text holds both the data file's ``op`` and its
    ``operand`` (filled in from the configuration)."""
    with open(os.path.join(os.path.dirname(__file__), metric + ".json")) as f:
        want = json.load(f)
    operand = want["operand"].format(experts=s.experts, d=s.d,
                                     d_ff_expert=s.d_ff_expert)
    return f"(?=.*{re.escape(want['op'])})(?=.*{re.escape(operand)})"


def executions(info) -> float:
    """Runs of the matched kernels in the trace, the mean over the chips
    used; None without a trace."""
    tr = info.get("trace")
    if tr is None or not tr.devices:
        return None
    devs = tr.devices[:info["chips"]]
    rx = pattern(info["metric"], info["spec"])
    return sum(trace_lib.op_count(d, rx) for d in devs) / len(devs)


def share(info, calls):
    """``calls``: [(number of runs on one chip, assignments per run, rows
    per run)]."""
    tr = info.get("trace")
    if tr is None or not tr.devices:
        return None
    s, pk = info["spec"], info["peak"]
    least = 0.0
    for n, assign, rows in calls:
        f = counts.expert_flops(s, assign) / pk["bf16_flops"]
        b = counts.expert_call_bytes(s, rows) / pk["hbm_bytes_per_s"]
        least += n * max(f, b)
    devs = tr.devices[:info["chips"]]
    rx = pattern(info["metric"], s)
    t = sum(trace_lib.op_seconds(d, rx) for d in devs)
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / len(devs) / t
