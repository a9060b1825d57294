"""Queries the engine dropped as infeasible (their futures resolved to
``(None, 0.0)``), over all attempted, in %. Moves ``slo_attainment``."""


def read(ctx):
    oc = ctx.window.outcomes
    from chipbench.outcomes import DROPPED
    return 100.0 * oc.count(DROPPED) / oc.attempted if oc.attempted else None
