"""Roofline share of one decode step: the bytes a step must read -- every
weight (all routed experts, which 64 slots x top_k picks nearly all touch,
the shared experts, attention, the tied LM head) plus the live part of the
latent cache, the mean over the window's decode steps -- over HBM
bandwidth, divided by the mean device time of one decode program (the
program the data file names), in %."""

import json
import os

from bench import counts
from bench import trace as trace_lib


def read(info):
    tr = info.get("trace")
    if info.get("kind") != "serve" or tr is None or not tr.devices:
        return None
    with open(os.path.join(os.path.dirname(__file__),
                           "decode_step_roofline.serve.json")) as f:
        pattern = json.load(f)["program"]
    durs = trace_lib.module_durations(tr.devices[0], pattern)
    if not durs or not info.get("decode_steps"):
        return None
    s = info["spec"]
    rows = info["cache_rows"] / info["decode_steps"]
    need = counts.weight_bytes(s) + rows * counts.cache_row_bytes(s)
    least = need / info["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(durs) / len(durs))
