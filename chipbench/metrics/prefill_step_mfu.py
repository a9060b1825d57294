"""The whole prefill step's share of the chip's bf16 peak (%): useful
FLOPs of the calls in the traced slice (the served subnet on its real
rows, LM head at the last position) over the device time of the program
executions in it. Moves ``p95_latency_ms``."""


def read(ctx):
    t = sum(m.end - m.start for m in ctx.modules) * 1e-9
    if t <= 0 or not ctx.calls:
        return None
    flops = sum(ctx.call_flops(c) for c in ctx.calls)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
