"""Roofline share of the MoE kernels in training (see ``_moe_kernels.py``):
every step, every MoE layer routes the step's tokens; the work of one
kernel run is the kept assignments, tokens x top_k x (1 - dropped),
``dropped`` the program's own counter averaged over the logged steps of
the window.  The runs are counted in the trace, not taken as steps x MoE
layers: under today's per-group recompute the kernel runs once a layer
and step (the backward pass is the jnp reference's and needs no rerun of
it), and a change of the recompute policy must not read as a slower
kernel.  The bound is memory at 12288 tokens a run: 537 MB of expert
weights and 75 MB of rows take 0.75 ms at 819 GB/s, the kept FLOPs
1.05 ms x (1 - dropped) at 197 TFLOP/s."""

from bench.metrics import _moe_kernels as _mk


def read(info):
    if info.get("kind") != "train" or not info.get("steps"):
        return None
    info = dict(info, metric="moe_kernel_roofline.train")
    runs = _mk.executions(info)
    if not runs:
        return None
    per_step = info["tokens"] / info["steps"] / info["chips"]
    kept = per_step * info["spec"].top_k * (1.0 - info["dropped"])
    return _mk.share(info, [(runs, kept, per_step)])
