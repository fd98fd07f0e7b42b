"""Share of the traced training window in which no operation ran on the
device: 1 - (union of the device's op intervals / window), the mean over
the chips used, in %."""

from bench.metrics import _idle


def read(info):
    return _idle.idle_share(info) if info.get("kind") == "train" else None
