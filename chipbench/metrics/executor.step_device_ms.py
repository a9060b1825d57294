"""Device time of the prefill program per executor call (ms): the mean
duration of the program executions in the traced slice. Moves
``p95_latency_ms``."""


def read(ctx):
    s = ctx.device_s_per_call()
    return None if s is None else s * 1e3
