"""Share of the traced slice in which no operation ran on the device
(%), averaged over the chips used. Moves ``p95_latency_ms``."""
from chipbench import trace as trace_mod


def read(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    busy = sum(trace_mod.busy_s(d, tr.window) for d in tr.devices)
    return 100.0 * (1.0 - busy / (len(tr.devices) * tr.window_s))
