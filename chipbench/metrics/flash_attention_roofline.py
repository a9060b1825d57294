"""``flash_attention``'s share of its roofline (%), from its events in
the traced slice and the operations and bytes of their operand shapes
(``chipbench/kernels/flash_attention.py``). Moves ``p95_latency_ms``."""


def read(ctx):
    return ctx.kernel_share("flash_attention")
