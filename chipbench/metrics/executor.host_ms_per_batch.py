"""Host time per executor call (ms): the mean of the benchmark's span
around ``run_prefill`` over the calls of the traced slice, minus the mean
device time of the program executions in it. Moves ``p95_latency_ms``."""


def read(ctx):
    dev = ctx.device_s_per_call()
    if dev is None or not ctx.calls:
        return None
    span = sum(c.t1 - c.t0 for c in ctx.calls) / len(ctx.calls)
    return (span - dev) * 1e3
