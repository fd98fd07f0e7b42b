"""Device time by the program's named scopes, for a reader of scope shares:
the seconds, and the share of the chip's busy time, that the ops under each
group of ``bench/scopes.json`` take (``moe_dispatch``: ``moe.route``,
``moe.dispatch`` and ``moe.combine``; ``attention``: ``attn``; ...).

A device op's event in the trace carries the op's HLO text without its
metadata.  Its scope comes from the compiled program's text
(``compiled.as_text()``), where every instruction holds
``metadata={op_name="jit(step)/.../moe.route/..."}``; a fusion holds its
root's, so a fusion counts under its root's scope.  Each op is matched to a
program by the ``XLA Modules`` event that covers it, and to an instruction
of that program's text by its name and result type.  A scope matches a
component of the ``op_name`` path, also where JAX wraps it for the backward
pass (``transpose(jvp(moe.route))``); the innermost listed scope wins.

No benchmark metric reads these yet: the cells do not hand their compiled
programs' texts to the readers (PERF.md, Open questions).
"""

from __future__ import annotations

import bisect
import json
import os
import re

from bench import trace as trace_lib

GROUPS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "scopes.json")

_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([^,\s]+)")
#: Prefixes of the spans the program and the benchmark write.
SPANS = ("serve.", "train.", "data.", "bench.")


def groups() -> dict:
    with open(GROUPS_FILE) as f:
        return json.load(f)


def components(op_name: str) -> list:
    """The path components of an ``op_name``, each with JAX's transform
    wrappers taken off: ``transpose(jvp(moe.route))`` -> ``moe.route``."""
    out = []
    for c in op_name.split("/"):
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        out.append(c)
    return out


def scope_of(op_name: str, scopes) -> str | None:
    """The innermost of ``scopes`` on the ``op_name`` path, or None."""
    hit = None
    for c in components(op_name):
        if c in scopes:
            hit = c
    return hit


def head(text: str) -> str:
    """An instruction's name and result type, which the trace's op events
    and the compiled text print alike (the trace adds operand types)."""
    m = _INSTR.match(text)
    if m is None:
        return text
    rest, depth = m.group(2), 0
    for i, ch in enumerate(rest):
        depth += (ch in "([{") - (ch in ")]}")
        if ch == " " and depth == 0:
            rest = rest[:i]
            break
    return f"{m.group(1)} = {rest}"


def parse(text: str) -> dict:
    """``{"module", "op_names": {instruction: op_name}, "heads": set}`` of a
    compiled program's text.  A fusion or call without metadata of its own
    takes its called computation's root's."""
    names, calls, roots, heads = {}, {}, {}, set()
    module, comp = None, None
    for line in text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        inst = _INSTR.match(line)
        if inst is None:
            hdr = _HEADER.match(line)
            if hdr:
                comp = hdr.group(1)
            continue
        name = inst.group(1)
        heads.add(head(line))
        op = _OP_NAME.search(line)
        if op:
            names[name] = op.group(1)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = name
    for name, comp in calls.items():
        if name not in names and roots.get(comp) in names:
            names[name] = names[roots[comp]]
    return {"module": module, "op_names": names, "heads": heads}


def attribute(dev: trace_lib.Device, texts, group_scopes: dict) -> dict:
    """Seconds of the device's leaf ops by scope and by group, each op
    matched to the program text (of ``texts``) whose ``XLA Modules`` event
    covers it.  ``unmatched_s`` is op time no text accounts for."""
    progs = [parse(t) for t in texts]
    scopes = sorted({s for v in group_scopes.values() for s in v})
    mods = sorted(dev.modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    by_mod = {}
    for op in trace_lib.leaf_ops(dev):
        k = bisect.bisect_right(starts, op[1]) - 1
        key = mods[k][0] if k >= 0 and mods[k][2] >= op[2] else None
        by_mod.setdefault(key, []).append(op)
    per_scope = dict.fromkeys(scopes, 0.0)
    top = {s: {} for s in scopes + ["unscoped"]}
    unscoped = unmatched = 0.0
    for mod, ops in by_mod.items():
        base = mod.split("(")[0] if mod else None
        cands = [p for p in progs if p["module"] == base]
        best = max(cands, default=None, key=lambda p: sum(
            head(o[0]) in p["heads"] for o in ops))
        for name, s, e in ops:
            if best is None or head(name) not in best["heads"]:
                unmatched += e - s
                continue
            op_name = best["op_names"].get(_INSTR.match(name).group(1), "")
            sc = scope_of(op_name, scopes)
            if sc is None:
                unscoped += e - s
                sc = "unscoped"
            else:
                per_scope[sc] += e - s
            key = trace_lib.short_name(name)
            top[sc][key] = top[sc].get(key, 0.0) + (e - s)
    busy = trace_lib.busy_s(dev)
    per_group = {g: sum(per_scope[s] for s in v)
                 for g, v in group_scopes.items()}
    return {
        "busy_s": busy, "scopes_s": per_scope, "groups_s": per_group,
        "groups_share": {g: 100.0 * v / busy if busy else None
                         for g, v in per_group.items()},
        "unscoped_s": unscoped, "unmatched_s": unmatched,
        "top_ops": {s: [[k, v] for k, v in sorted(
            t.items(), key=lambda kv: -kv[1])[:5]] for s, t in top.items()}}


def idle_by_span(dev: trace_lib.Device, host) -> dict:
    """Seconds of the device's idle time between its first and last op, by
    the innermost span of :data:`SPANS` that covers each idle gap's
    midpoint (``no host span`` where no such span does)."""
    spans = [h for h in host if h[0].startswith(SPANS)]
    lo = min(o[1] for o in dev.ops)
    hi = max(o[2] for o in dev.ops)
    out = {}
    for name, gap in trace_lib.idle_gaps(dev, spans, lo, hi, n=None):
        out[name] = out.get(name, 0.0) + gap
    return out
