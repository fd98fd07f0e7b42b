"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Reads the file with ``jax.profiler.ProfileData``.  A device is a plane
whose name starts with ``/device:TPU:``; its operations are the events of
the line ``XLA Ops`` and its programs those of ``XLA Modules``.  Host
planes (``/host:...``) carry the spans the host wrote, used to say what the
host was doing while a device sat idle.  Times are seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Collective operations, by HLO op name.
COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all_to_all|all_reduce|"
                        r"all_gather|reduce_scatter|collective_permute")


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # [(name, start_s, end_s)]
    modules: list      # [(name, start_s, end_s)]


@dataclasses.dataclass
class Trace:
    devices: list      # [Device]
    host: list         # [(name, start_s, end_s)] host spans


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def from_profile(pd) -> Trace:
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    mods += _events(line)
            if ops or mods:
                devices.append(Device(plane.name, sorted(ops, key=lambda e: e[1]),
                                      sorted(mods, key=lambda e: e[1])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(find_xplane(trace_dir)))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of the given intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list:
    """Parts of union(a) that union(b) does not cover."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------


def span(trace: Trace) -> tuple:
    """(first, last) instant any device op covers."""
    evs = [e for d in trace.devices for e in d.ops]
    return min(e[1] for e in evs), max(e[2] for e in evs)


def busy_s(dev: Device) -> float:
    """Seconds in which some operation ran on the device."""
    return length((s, e) for _, s, e in dev.ops)


def op_seconds(dev: Device, pattern) -> float:
    """Summed device time of the operations whose name matches."""
    rx = re.compile(pattern)
    return sum(e - s for n, s, e in dev.ops if rx.search(n))


def op_count(dev: Device, pattern) -> int:
    """Number of operations whose name matches: each run of an op is an
    event of its own."""
    rx = re.compile(pattern)
    return sum(1 for n, _, _ in dev.ops if rx.search(n))


def module_durations(dev: Device, pattern) -> list:
    rx = re.compile(pattern)
    return [e - s for n, s, e in dev.modules if rx.search(n)]


def exposed_collective_s(dev: Device) -> float:
    """Seconds in which a collective runs on the device and no other
    operation does."""
    coll = [(s, e) for n, s, e in dev.ops if COLLECTIVE.search(n)]
    comp = [(s, e) for n, s, e in dev.ops if not COLLECTIVE.search(n)]
    return length(subtract(coll, comp))


def leaf_ops(dev: Device) -> list:
    """The operations that hold no other: a ``while`` or ``call`` event
    spans the events of its body on the same line."""
    ops = sorted(dev.ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= e:
            out.append((n, s, e))
    return out


def short_name(hlo: str, width: int = 72) -> str:
    """An HLO op's event name cut to its id and the head of its result
    type: ``%fusion.12 = bf16[4,2048,1024]...``."""
    head, _, rest = hlo.partition(" = ")
    return (head + (" = " + rest[:width] if rest else "")).strip()


def top_ops(dev: Device, n: int = 10) -> list:
    """[[op, seconds]] of the leaf operations that took most device time,
    summed over the window by op."""
    tot = {}
    for name, s, e in leaf_ops(dev):
        key = short_name(name)
        tot[key] = tot.get(key, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: Device, host, lo: float, hi: float, n: int = 10) -> list:
    """[[what the host was doing, seconds]] for the longest idle gaps of the
    device inside [lo, hi]: the innermost host span that covers the gap's
    midpoint, or ``no host span``."""
    gaps = subtract([(lo, hi)], [(s, e) for _, s, e in dev.ops])
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = 0.5 * (s + e)
        cover = [h for h in host if h[1] <= mid <= h[2]]
        label = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                 else "no host span")
        out.append([label, e - s])
    return out
