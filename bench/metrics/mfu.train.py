"""Model FLOPs utilisation of training: model FLOPs per token (forward and
backward, routed experts at top_k, LM head, causal attention; see
``bench/counts.py``) x tokens trained in the traced window / window
seconds / (chips x the chip's bf16 peak), in %."""

from bench import counts


def read(info):
    if info.get("kind") != "train" or not info.get("tokens"):
        return None
    s = info["spec"]
    flops = counts.train_flops_per_token(s, info["traffic"]["seq_len"])
    rate = flops * info["tokens"] / info["window_s"]
    return 100.0 * rate / (info["chips"] * info["peak"]["bf16_flops"])
