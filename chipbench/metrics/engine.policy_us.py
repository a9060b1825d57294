"""Mean time (us) of the program's ``engine.policy`` span over the
traced slice: the scheduling policy's choice per dispatch. Moves
``p95_latency_ms``."""
from chipbench import program_spans


def read(ctx):
    s = program_spans.mean_s(ctx, "engine.policy")
    return None if s is None else s * 1e6
