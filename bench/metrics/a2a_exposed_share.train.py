"""Share of the traced training window in which an all-to-all runs on the
device and no other operation does: the all-to-all intervals less those of
every other leaf operation (a loop or call that holds an all-to-all hides
nothing; ``_ops.exposed_s``), over the window, the mean over the chips
used, in %.  Only all-to-alls count, so the data-parallel gradient
all-reduce is not read as the exchange.  None where the trace holds no
all-to-all (one chip: the exchange is absent)."""

import re

from bench.metrics import _ops

A2A = re.compile(r"all-to-all|all_to_all")


def is_a2a(name: str) -> bool:
    return A2A.search(name) is not None


def read(info):
    tr = info.get("trace")
    if info.get("kind") != "train" or tr is None or not tr.devices:
        return None
    devs = tr.devices[:info["chips"]]
    if not any(is_a2a(n) for d in devs for n, _, _ in d.ops):
        return None
    exposed = sum(_ops.exposed_s(d, is_a2a) for d in devs) / len(devs)
    return 100.0 * exposed / info["window_s"]
