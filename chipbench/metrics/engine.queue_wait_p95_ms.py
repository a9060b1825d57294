"""95th percentile (ms) of the program's ``engine.queue_wait`` over the
traced slice: each served query's time from arrival to its batch's
launch. Moves ``p95_latency_ms``."""
import numpy as np

from chipbench import program_spans


def read(ctx):
    ss = program_spans.named(ctx, "engine.queue_wait")
    if not ss:
        return None
    return float(np.percentile([s.t1 - s.t0 for s in ss], 95)) * 1e3
