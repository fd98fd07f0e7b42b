"""Model FLOPs utilisation of serving: the forward FLOPs of every prompt
token prefilled and every token decoded in the window (``bench/counts.py``)
/ window seconds / (chips x the chip's bf16 peak), in %."""


def read(info):
    if info.get("kind") != "serve":
        return None
    flops = info["prefill_flops"] + info["decode_flops"]
    if flops <= 0:
        return None
    rate = flops / info["window_s"]
    return 100.0 * rate / (info["chips"] * info["peak"]["bf16_flops"])
