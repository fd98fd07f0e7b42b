"""Roofline share of the token-permutation kernels in expert-parallel
training: the bytes the ``moe_permute`` Pallas calls move, over HBM
bandwidth, divided by their device time.  Both move float32 rows of the
``[N, 1, d]`` view, one row a DMA: ``permute`` takes the chip's tokens
with the zero row (``f32[T + 1, 1, d]``) and reads and writes one row for
each of the S slots of its capacity buffer (S = chips x held experts x
capacity); ``unpermute`` takes the slots with the zero row
(``f32[S + 1, 1, d]``), reads top_k rows a token and writes one.  T is a
chip's tokens a step.  Runs are counted in the trace, the mean over the
chips used.  None without the cell's stated ``capacity`` or on one chip."""

from bench.metrics import _ops

F32 = 4
CALL = 'custom_call_target="tpu_custom_call"'


def read(info):
    tr = info.get("trace")
    if (info.get("kind") != "train" or not info.get("steps")
            or info.get("chips", 1) < 2 or not info.get("capacity")
            or tr is None or not tr.devices):
        return None
    s, chips = info["spec"], info["chips"]
    T = int(info["tokens"] / info["steps"] / chips)
    S = s.experts * info["capacity"]
    row = s.d * F32
    kinds = [(_ops.having(CALL, f"f32[{T + 1},1,{s.d}]"), 2 * S * row),
             (_ops.having(CALL, f"f32[{S + 1},1,{s.d}]"),
              (s.top_k + 1) * T * row)]
    devs = tr.devices[:chips]
    moved = t = 0.0
    for test, per_run in kinds:
        for d in devs:
            n, secs = _ops.runs(d, test)
            moved += per_run * n
            t += secs
    if t <= 0:
        return None
    return 100.0 * moved / info["peak"]["hbm_bytes_per_s"] / t
