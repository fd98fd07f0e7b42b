"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and (with ``--json``) writes a
machine-readable ``BENCH_dispatch.json`` with the same rows plus run
metadata, so CI can archive the perf trajectory (step times and
chunk-chooser verdicts per dispatch path / topology).  The JSON is
re-written after *every* suite, so a crash mid-sweep never loses the rows
already measured — the failing suite is recorded as a ``<name>_FAILED``
row carrying the exception, and the harness exits nonzero once every
suite has run.  Usage:

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --only table1 fig4
    PYTHONPATH=src python -m benchmarks.run --quick    # smaller trainings
    PYTHONPATH=src python -m benchmarks.run --only dispatch overlap \
        --json BENCH_dispatch.json
"""

import argparse
import json
import platform
import sys
import time


def _write_json(path, sel, suite_times, quick, rows, complete):
    payload = {
        "schema": "bench_dispatch/v1",
        "suites": sel,
        "suite_seconds": suite_times,
        "quick": bool(quick),
        "complete": bool(complete),   # False while suites are still running
        "platform": platform.platform(),
        "python": platform.python_version(),
        "rows": [{"name": n, "us_per_call": round(us, 2), "derived": d}
                 for n, us, d in rows],
    }
    try:
        import jax
        payload["jax"] = jax.__version__
        payload["device_count"] = jax.device_count()
    except Exception:
        pass
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + metadata as JSON "
                         "(e.g. BENCH_dispatch.json); flushed after every "
                         "suite so partial sweeps survive a crash")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (ablation_dispatch, dispatch_sweep,
                            fig3_convergence, fig4_throughput,
                            fig5_fastermoe, fig6_dispatch, fig_overlap,
                            roofline, serving_sweep, table1_comm)

    suites = {
        "table1": lambda: table1_comm.run(),
        "fig4": lambda: fig4_throughput.run(),
        "fig6": lambda: fig6_dispatch.run(),
        "fig3": lambda: fig3_convergence.run(steps=30 if args.quick else 60, experts=(4,) if args.quick else (4, 8)),
        "fig5": lambda: fig5_fastermoe.run(steps=30 if args.quick else 60),
        "roofline": lambda: roofline.run(),
        "ablation": lambda: ablation_dispatch.run(),
        "overlap": lambda: fig_overlap.run(),
        "dispatch": lambda: dispatch_sweep.run(quick=args.quick),
        "serving": lambda: serving_sweep.run(quick=args.quick),
    }
    sel = args.only or list(suites)
    rows = []
    suite_times = {}
    for i, name in enumerate(sel):
        print(f"\n==== {name} ====", flush=True)
        t0 = time.time()
        try:
            rows.extend(suites[name]())
        except Exception as e:  # keep the harness running
            import traceback
            traceback.print_exc(limit=6)
            rows.append((f"{name}_FAILED", 0.0,
                         f"{type(e).__name__}: {e}"[:200]))
        suite_times[name] = round(time.time() - t0, 1)
        print(f"[{name} done in {suite_times[name]}s]", flush=True)
        if args.json:
            # incremental flush: completed rows survive a later crash
            _write_json(args.json, sel, suite_times, args.quick, rows,
                        complete=(i == len(sel) - 1))

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")

    if args.json:
        print(f"[wrote {args.json}: {len(rows)} rows]")
    failed = [n for n, _, _ in rows if n.endswith("_FAILED")]
    if failed:
        print(f"[{len(failed)} suite(s) failed: {', '.join(failed)}]")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
