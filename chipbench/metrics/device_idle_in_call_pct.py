"""Share of the traced slice (%) in which a chip is idle while a batch
of the replica on that same chip is in flight (the program's
``router.batch`` span), over chips x slice: the chip waits on its own
replica's host path. At most ``device_idle_pct``. Moves
``p95_latency_ms``."""
from chipbench import program_spans


def read(ctx):
    per = program_spans.idle_under_own_calls(ctx)
    if not per or ctx.trace.window_s <= 0:
        return None
    return 100.0 * sum(inside for _, inside, _ in per) \
        / (len(per) * ctx.trace.window_s)
