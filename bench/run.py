#!/usr/bin/env python3
"""Runs one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic named in ``BENCHMARK.json``,
makes weights and inputs from ``--seed``, warms up every shape the cell
uses, measures for ``--seconds``, checks what the measured window produced
against the plain reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number beside its limit.  Exits 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402  (starts the set-up clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.cell(args.workload)
    try:
        devices = harness.require_chips(cell.workload["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch import compile_cache
    compile_cache.enable()
    # every program into the cache, so later runs load even the small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.mark("chips")
    mod = harness.cell_module(cell.traffic["kind"])
    result = mod.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
