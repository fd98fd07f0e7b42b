"""Device idle share of the traced window, shared by the
``device_idle_share.*`` readers."""

from bench import trace as trace_lib


def idle_share(info):
    tr = info.get("trace")
    if tr is None or not tr.devices:
        return None
    devs = tr.devices[:info["chips"]]
    busy = sum(trace_lib.busy_s(d) for d in devs) / len(devs)
    return 100.0 * max(0.0, 1.0 - busy / info["window_s"])
