#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--seconds 10]

For every seed in ``--seeds`` it runs the program's timed path as a
benchmark run does (train: the checked first steps; serve: a window of
``--seconds`` at the cell's load) and prints the compared numbers against
the plain reference.  For every seed in ``--control-seeds`` it also puts
the reference computed in float8 (the control) in the program's place and
prints its numbers; for a training cell, also the reference with half of
each batch left out (the mean taken over the rest), a fault the check has
to catch.  A limit lies above the largest program reading and below the
smallest control or fault reading.  The benchmark's own runs never run the
control.  Needs the chips the cell asks for, like ``bench/run.py``.
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

from bench import harness  # noqa: E402


def _seeds(text: str) -> list:
    return [int(x) for x in text.split(",") if x.strip()]


def train_readings(cell, devices, seeds, control_seeds):
    from bench.cells import train_lm
    from repro.data.pipeline import DataConfig, SyntheticLM
    n = cell.traffic["checked_steps"]
    with train_lm.Program(cell, devices, seeds[0]) as prog:
        for seed in seeds:
            prog.data = SyntheticLM(DataConfig(
                vocab_size=prog.arch.vocab_size, seq_len=prog.run.seq_len,
                global_batch=prog.run.global_batch, seed=seed), prog.arch)
            t = time.perf_counter()
            params, opt, got, rows = train_lm.first_steps(prog, seed, n)
            del params, opt
            gc.collect()
            ref = train_lm.reference(cell, prog.abstract, seed, rows, "f32")
            yield {"seed": seed, "who": "program",
                   **_judged(cell, train_lm.numbers(got, ref)),
                   "losses": got["losses"], "ref_losses": ref["losses"],
                   "worst_leaves": _worst(prog.abstract, got, ref),
                   "s": time.perf_counter() - t}
            if seed in control_seeds:
                ctl = train_lm.reference(cell, prog.abstract, seed, rows,
                                         "fp8")
                yield {"seed": seed, "who": "control",
                       **_judged(cell, train_lm.numbers(ctl, ref)),
                       "losses": ctl["losses"]}
                half = [{k: v[:len(v) // 2] for k, v in r.items()}
                        for r in rows]
                flt = train_lm.reference(cell, prog.abstract, seed, half,
                                         "f32")
                yield {"seed": seed, "who": "half_batch",
                       **_judged(cell, train_lm.numbers(flt, ref)),
                       "losses": flt["losses"]}


def _judged(cell, numbers: dict) -> dict:
    """The numbers with ``correct``, as the cell's limits judge them."""
    return {**numbers, "correct": harness.judge(numbers, cell.limits)[0]}


def _worst(abstract, got, ref, n: int = 3) -> dict:
    """The leaves with the widest grad and change gaps, for the look."""
    import jax
    import numpy as np
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(abstract)[0]]
    out = {}
    for key in ("grad", "change"):
        r, p = ref[key], got[key]
        gap = np.abs(p - r) / np.maximum(r, np.median(r))
        out[key] = [[names[i], float(gap[i]), float(r[i])]
                    for i in np.argsort(-gap)[:n]]
    return out


def _stats(arrs) -> dict:
    import numpy as np
    g = np.concatenate(arrs)
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "p90": float(np.quantile(g, 0.9)), "p99": float(np.quantile(g, 0.99)),
            "share_off": float(np.mean(g > 0)), "n": int(g.size)}


def serve_readings(cell, devices, seeds, control_seeds, seconds):
    import numpy as np

    from bench.cells import serve_offline as so
    t = cell.traffic
    with so.Program(cell, devices, seeds[0]) as prog:
        vocab = prog.arch.vocab_size
        for seed in seeds:
            t0 = time.perf_counter()
            eng = prog.engine(seed)
            eng.run(so.warm_requests(t, vocab), seed=seed)
            reqs = so.requests(t, vocab, seed,
                               max(1, round(t["requests_per_second"] * seconds)))
            report = eng.run(reqs, seed=seed)
            picked = so.sample(report.streams, t["check"], seed)
            del eng, report
            gc.collect()
            ctl = seed in control_seeds
            gaps = so.reference_gaps(cell, prog.abstract, seed, picked,
                                     control=ctl)
            far = so.FAR
            yield {"seed": seed, "who": "program",
                   **_judged(cell, {"far_share": float(np.mean(
                       np.concatenate([g[0] for g in gaps]) > far))}),
                   "gap": max(float(np.max(g[0])) for g in gaps),
                   "stats": _stats([g[0] for g in gaps]),
                   "tokens": sum(len(g) for _, g in picked),
                   "s": time.perf_counter() - t0}
            if ctl:
                yield {"seed": seed, "who": "control",
                       **_judged(cell, {"far_share": float(np.mean(
                           np.concatenate([g[1] for g in gaps]) > far))}),
                       "gap": max(float(np.max(g[1])) for g in gaps),
                       "stats": _stats([g[1] for g in gaps])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.cell(args.workload)
    try:
        devices = harness.require_chips(cell.workload["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch import compile_cache
    compile_cache.enable()
    # every program into the cache, so later runs load even the small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds, ctl = _seeds(args.seeds), set(_seeds(args.control_seeds))
    kind = cell.traffic["kind"]
    rows = (train_readings(cell, devices, seeds, ctl) if kind == "train_lm"
            else serve_readings(cell, devices, seeds, ctl, args.seconds))
    out = []
    for r in rows:
        print(json.dumps(r), flush=True)
        out.append(r)
    summary = {"correct": {}}
    for who in sorted({r["who"] for r in out}):
        summary["correct"][who] = [r["correct"] for r in out
                                   if r["who"] == who]
    for name in cell.limits:
        summary[name] = {}
        for who in sorted({r["who"] for r in out}):
            vals = [r[name] for r in out if r["who"] == who]
            summary[name][who] = [min(vals), max(vals)]
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
