"""Shared machinery of the benchmark: what ``BENCHMARK.json`` names, the
chip check, the result line, and the per-layer metric readers.

A cell (one entry of ``workloads``) is a configuration file
(``bench/configs/<config>.json``), a traffic file
(``bench/traffic/<traffic>.json``, whose ``kind`` names the runner module in
``bench/cells/``) and a limits file (``bench/limits/<workload>.json``).
Per-layer metrics are ``bench/metrics/<name>.py``, each with
``read(info) -> float | None``.  Nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class NoChip(RuntimeError):
    pass


def process_age_s() -> float:
    """Seconds since this process started (Linux), 0 where unknown."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


#: perf_counter() reading at which this process started.
T_START = time.perf_counter() - process_age_s()

#: [name, seconds since the process started] at the end of each set-up
#: phase, so a run shows where its set-up time went.
SETUP_MARKS = [["harness", time.perf_counter() - T_START]]


def mark(name: str) -> None:
    SETUP_MARKS.append([name, time.perf_counter() - T_START])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(entries, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}")


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell reads."""
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list           # BENCHMARK.json metric entries of this cell
    per_layer: list


def _applies(metric: dict, wl: str) -> bool:
    return "workloads" not in metric or wl in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    b = benchmark(root)
    wl = find(b["workloads"], name)
    conf = find(b["configs"], wl["config"])
    e2e = [m for m in b["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per = [m for m in b["per_layer"]
           if _applies(m, name) and m["moves"] in moved]
    return Cell(workload=wl, config=load_json(os.path.join(root, conf["file"])),
                traffic=load_json(os.path.join(BENCH, "traffic",
                                               wl["traffic"] + ".json")),
                limits=load_json(os.path.join(BENCH, "limits",
                                              name + ".json"))["limits"],
                end_to_end=e2e, per_layer=per)


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------


def require_chips(n: int):
    """The first ``n`` TPU devices; raises NoChip where JAX finds fewer or
    another platform.  Never falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(str(e)) from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform if devs else 'none'}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_info(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


class CompileCounter:
    """Counts, after ``arm()``, the programs JAX compiles and those it
    loads from the persistent cache (``jax.monitoring``), with the seconds
    each took."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.events, self.hits, self.armed = [], 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on(self, event, duration, **_):
        # a load from the cache is timed under the compile event too
        if self.armed and event == self.COMPILE:
            self.events.append(float(duration))

    def _on_hit(self, event, **_):
        if self.armed and event == self.HIT:
            self.hits += 1

    def arm(self):
        self.events, self.hits, self.armed = [], 0, True

    def disarm(self) -> dict:
        """``compiles`` (not found in the cache), ``cache_loads`` and
        ``compile_s``, the seconds all of them took."""
        self.armed = False
        return {"compiles": len(self.events) - self.hits,
                "cache_loads": self.hits, "compile_s": sum(self.events)}


class HostWatch:
    """Looks for host stalls in a window: a thread that wakes every
    ``tick`` seconds records each wake-up that comes ``stall`` seconds or
    more late (the process was not scheduled, or held the interpreter),
    and ``gc.callbacks`` time the garbage collector's passes."""

    def __init__(self, tick: float = 0.01, stall: float = 0.2):
        self.tick, self.stall = tick, stall
        self.stalls, self.gc_s, self.gc_max_s, self.gc_full = [], 0.0, 0.0, 0
        self._gc_t0 = None

    def _watch(self):
        last = self.t0
        while not self._stop.wait(self.tick):
            now = time.perf_counter()
            if now - last >= self.tick + self.stall:
                self.stalls.append([last - self.t0, now - last - self.tick])
            last = now

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dt = time.perf_counter() - self._gc_t0
            self.gc_s += dt
            self.gc_max_s = max(self.gc_max_s, dt)
            self.gc_full += info.get("generation") == 2
            self._gc_t0 = None

    def __enter__(self):
        import gc
        import threading
        self.t0 = time.perf_counter()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._on_gc)
        self._stop.set()
        self._thread.join()

    def report(self) -> dict:
        return {"host_stalls": self.stalls[:10], "gc_s": self.gc_s,
                "gc_max_s": self.gc_max_s, "gc_full": self.gc_full}


def free_device_memory(devices):
    """On a TPU, drops every live array and compiled program of this
    process, so a reference that runs next has the chip's memory to
    itself.  Elsewhere (a CPU test run, which shares its process) it only
    collects garbage."""
    import gc

    import jax
    gc.collect()
    if devices[0].platform == "tpu":
        for a in jax.live_arrays():
            a.delete()
        jax.clear_caches()
        gc.collect()


# ---------------------------------------------------------------------------
# the program's configuration, checked against the file
# ---------------------------------------------------------------------------


def program_arch(config: dict):
    """The program's ArchConfig for this configuration: ``get_config`` with
    the file's ``program`` overrides (nested ones for ``moe`` / ``mla``)."""
    from repro.configs.base import get_config
    arch = get_config(config["get_config"])
    over = dict(config.get("program", {}))
    for sub in ("moe", "mla"):
        if sub in over:
            over[sub] = dataclasses.replace(getattr(arch, sub), **over[sub])
    return dataclasses.replace(arch, **over)


def check_arch(arch, s) -> None:
    """Refuses to run where the program's configuration and the file's
    sizes disagree."""
    m = arch.moe
    want = {"d_model": s.d, "num_heads": s.heads, "num_layers": s.layers,
            "vocab_size": s.vocab, "d_ff": s.d_ff,
            "num_experts": s.experts, "top_k": s.top_k,
            "d_ff_expert": s.d_ff_expert, "num_shared_experts": s.shared,
            "first_dense": s.first_dense, "moe_period": s.moe_every,
            "activation": "swiglu" if s.act == "silu" else "gelu",
            "norm": s.norm, "dtype": "bfloat16"}
    got = {"d_model": arch.d_model, "num_heads": arch.num_heads,
           "num_layers": arch.num_layers, "vocab_size": arch.vocab_size,
           "d_ff": arch.d_ff, "num_experts": m.num_experts,
           "top_k": m.top_k, "d_ff_expert": m.d_ff_expert,
           "num_shared_experts": m.num_shared_experts,
           "first_dense": m.first_dense, "moe_period": m.moe_period,
           "activation": arch.activation, "norm": arch.norm,
           "dtype": arch.dtype}
    if s.mla:
        a = arch.mla
        want["mla"] = s.mla
        got["mla"] = (a.kv_lora_rank, a.qk_nope_dim, a.qk_rope_dim, a.v_dim)
    else:
        want["head_dim"], got["head_dim"] = s.head_dim, arch.head_dim_
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"program config disagrees with the file: {bad}")


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------


def metric_value(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def read_per_layer(entries, info: dict) -> dict:
    """Runs each metric's reader; a reader that finds nothing is left out."""
    out = {}
    for m in entries:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(info)
        if v is not None and math.isfinite(v):
            out[m["name"]] = metric_value(v, m["unit"])
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit.  A number
    that is missing or not finite fails."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = {"value": (float(v) if v is not None else None),
                        "limit": float(lim)}
    return ok, checks


def emit(result: dict) -> None:
    """Prints each compared number beside its limit as the last lines of
    stderr, then the result as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def cell_module(kind: str):
    return importlib.import_module(f"bench.cells.{kind}")


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def start_trace() -> None:
    import shutil

    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    # no Python-call tracer: it costs the host loop; the host's own spans
    # (TraceAnnotation, the runtime's) stay
    opts.python_tracer_level = 0
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def stop_trace():
    """Stops the profiler and returns the reduced trace; the files go."""
    import shutil

    import jax

    from bench import trace as trace_lib
    jax.profiler.stop_trace()
    t = trace_lib.load(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return t


def trace_device_fields(tr, window_s: float, chips: int) -> dict:
    """``busy_s`` (mean over the chips used) and ``window_s``."""
    from bench import trace as trace_lib
    devs = tr.devices[:chips]
    busy = sum(trace_lib.busy_s(d) for d in devs) / max(1, len(devs))
    return {"busy_s": busy, "window_s": window_s}


def breakdown(tr) -> dict:
    """The first chip's top operations and its longest idle gaps, each
    named by the host span that covers it, between its first and last
    operation."""
    from bench import trace as trace_lib
    d = tr.devices[0]
    lo, hi = trace_lib.span(tr)
    return {"device_ops": trace_lib.top_ops(d),
            "idle_gaps": trace_lib.idle_gaps(d, tr.host, lo, hi)}
