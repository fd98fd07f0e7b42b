"""The benchmark's two cells cut to a size a CPU test run holds: every
width shrunk, the traffic shortened, the limits those of the real cell."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

TRAIN = "gpt3_moe_l4.train_2k"
SERVE = "dsv2_lite_l5.serve_decode"


def train_cell() -> harness.Cell:
    c = harness.cell(TRAIN)
    cfg = dict(c.config)
    cfg.update({
        "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                    "num_kv_heads": 4, "d_ff": 128, "vocab_size": 512,
                    "moe": {"num_experts": 4, "d_ff_expert": 32}},
        "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
        "intermediate_size": 128, "vocab_size": 512, "num_experts": 4,
        "moe_intermediate_size": 32})
    tr = dict(c.traffic)
    tr.update({"seq_len": 32, "batch_per_chip": 2, "log_every": 2})
    return harness.Cell(c.workload, cfg, tr, c.limits, c.end_to_end,
                        c.per_layer)


def serve_cell() -> harness.Cell:
    c = harness.cell(SERVE)
    cfg = dict(c.config)
    cfg.update({
        "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                    "num_kv_heads": 4, "d_ff": 128, "vocab_size": 512,
                    "moe": {"num_experts": 4, "d_ff_expert": 32, "top_k": 2,
                            "num_shared_experts": 1},
                    "mla": {"kv_lora_rank": 32, "qk_nope_dim": 16,
                            "qk_rope_dim": 8, "v_dim": 16}},
        "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
        "intermediate_size": 128, "vocab_size": 512, "n_routed_experts": 4,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "moe_intermediate_size": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16})
    tr = dict(c.traffic)
    tr.update({"num_slots": 4, "cache_len": 96, "prefill_pack": 1,
               "prompt_buckets": [16, 32, 64],
               "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 64},
               "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32},
               "requests_per_second": 8.0,
               "check": {"min_tokens": 40, "max_requests": 4}})
    return harness.Cell(c.workload, cfg, tr, c.limits, c.end_to_end,
                        c.per_layer)
