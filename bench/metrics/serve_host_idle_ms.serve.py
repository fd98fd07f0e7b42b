"""Host time the serving loop costs the chip, per decode step: the first
chip's idle time inside the program's ``serve.step`` spans and outside its
``*.wait`` spans (the host blocked on a device result; the data file names
them), summed over the traced window and divided by its decode steps, in
ms.  Idle time outside every step span (before the first, after the last)
is left out.  None where the trace holds no step span, as a program that
writes none gives."""

import json
import os

from bench import trace as trace_lib


def read(info):
    tr = info.get("trace")
    if info.get("kind") != "serve" or tr is None or not tr.devices:
        return None
    with open(os.path.join(os.path.dirname(__file__),
                           "serve_host_idle_ms.serve.json")) as f:
        spans = json.load(f)
    steps = [(s, e) for n, s, e in tr.host if n == spans["step"]]
    if not steps or not info.get("decode_steps"):
        return None
    waits = [(s, e) for n, s, e in tr.host if n in spans["waits"]]
    busy = [(s, e) for _, s, e in tr.devices[0].ops]
    idle = trace_lib.subtract(trace_lib.subtract(steps, busy), waits)
    return 1e3 * trace_lib.length(idle) / info["decode_steps"]
