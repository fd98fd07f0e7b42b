"""Roofline share of the MoE kernels in serving (see ``_moe_kernels.py``):
each decode step routes the tokens decoded in it over every MoE layer, and
reads every routed expert's weights; each prefill pack routes its prompt
tokens.  The work is the served tokens x top_k (serving drops none); the
rows moved are the slots of a decode step and the padded pack of a
prefill.  The bound is memory in both: a call's 1.1 GB of expert weights
take 1.35 ms, the routed FLOPs of a 1024-token prefill 0.54 ms."""

from bench import counts
from bench.metrics import _moe_kernels as _mk


def read(info):
    if info.get("kind") != "serve" or not info.get("decode_steps"):
        return None
    s = info["spec"]
    m = counts.n_moe(s)
    ds, pc = info["decode_steps"], max(1, info["prefill_calls"])
    bucket = max(info["traffic"]["prompt_buckets"])
    calls = [(ds * m, info["decode_tokens"] * s.top_k / ds,
              info["num_slots"]),
             (pc * m, info["prefill_tokens"] * s.top_k / pc,
              info["prefill_pack"] * bucket)]
    return _mk.share(dict(info, metric="moe_kernel_roofline.serve"), calls)
