"""Mean time (ms) of the program's ``executor.fetch`` span over the
traced slice: the device-to-host copy of a call's bucket of logits.
Moves ``p95_latency_ms``."""
from chipbench import program_spans


def read(ctx):
    s = program_spans.mean_s(ctx, "executor.fetch")
    return None if s is None else s * 1e3
