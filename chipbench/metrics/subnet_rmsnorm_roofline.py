"""``subnet_rmsnorm``'s share of its roofline (%), from its events in
the traced slice and the operations and bytes of their operand shapes
(``chipbench/kernels/subnet_rmsnorm.py``). Moves ``p95_latency_ms``."""


def read(ctx):
    return ctx.kernel_share("subnet_rmsnorm")
