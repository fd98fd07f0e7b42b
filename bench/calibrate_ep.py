#!/usr/bin/env python3
"""Readings that the correctness limits of an expert-parallel training cell
(traffic kind ``train_lm_ep``) are set from.

    python3 bench/calibrate_ep.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9]

As ``bench/calibrate.py`` does for one-chip training cells: for every seed
in ``--seeds`` it runs the program's checked first steps and prints the
compared numbers against the per-source reference; for every seed in
``--control-seeds`` it also puts in the program's place the reference
computed in float8 (the control), the reference with half of each batch
left out (``half_batch``: the first half fed twice, as a program whose
chips got each other's rows would compute, so the mean runs over half the
rows and the reference keeps its shape), and the reference in which each source chip's
tokens reach only the experts on that chip (``exchange_left_out``, what a
program that skipped the all-to-all computes).  A limit lies above the
largest program reading and below the smallest control or fault reading.
The last line is a summary: each number's least and greatest reading by
who produced it.  Needs the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import calibrate, harness  # noqa: E402


def readings(cell, devices, seeds, control_seeds):
    import numpy as np

    from bench.cells import train_lm, train_lm_ep
    from repro.data.pipeline import DataConfig, SyntheticLM

    n = cell.traffic["checked_steps"]
    with train_lm_ep.program(cell, devices, seeds[0]) as prog:
        reference = train_lm_ep.Reference(cell, prog.abstract)
        # both precisions compile on worker threads while the program
        # compiles and runs its first steps
        shape = (prog.run.global_batch, prog.run.seq_len)
        for prec in ("f32", "fp8"):
            reference.prepare(prec, shape)
        for seed in seeds:
            prog.data = SyntheticLM(DataConfig(
                vocab_size=prog.arch.vocab_size, seq_len=prog.run.seq_len,
                global_batch=prog.run.global_batch, seed=seed), prog.arch)
            t = time.perf_counter()
            params, opt, got, rows = train_lm.first_steps(prog, seed, n)
            del params, opt
            gc.collect()
            ref = reference.run(seed, rows, "f32")
            yield {"seed": seed, "who": "program",
                   **calibrate._judged(cell, train_lm.numbers(got, ref)),
                   "losses": got["losses"], "ref_losses": ref["losses"],
                   "worst_leaves": calibrate._worst(prog.abstract, got, ref),
                   "s": time.perf_counter() - t}
            if seed not in control_seeds:
                continue
            # the first half of each batch fed twice: the mean is taken
            # over half the batch, and one compiled step serves
            half = [{k: np.concatenate([v[:len(v) // 2]] * 2)
                     for k, v in r.items()} for r in rows]
            for who, args in (("control", (rows, "fp8")),
                              ("half_batch", (half, "f32")),
                              ("exchange_left_out", (rows, "f32", True))):
                fault = reference.run(seed, *args)
                yield {"seed": seed, "who": who,
                       **calibrate._judged(cell,
                                           train_lm.numbers(fault, ref)),
                       "losses": fault["losses"]}


def summary(rows, limits) -> dict:
    whos = sorted({r["who"] for r in rows})
    out = {"correct": {w: [r["correct"] for r in rows if r["who"] == w]
                       for w in whos}}
    for name in limits:
        out[name] = {}
        for w in whos:
            vals = [r[name] for r in rows if r["who"] == w]
            out[name][w] = [min(vals), max(vals)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.cell(args.workload)
    try:
        devices = harness.require_chips(cell.workload["chips"])
    except harness.NoChip as e:
        print(f"calibrate_ep: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = []
    for r in readings(cell, devices, calibrate._seeds(args.seeds),
                      set(calibrate._seeds(args.control_seeds))):
        print(json.dumps(r), flush=True)
        out.append(r)
    print(json.dumps({"summary": summary(out, cell.limits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
