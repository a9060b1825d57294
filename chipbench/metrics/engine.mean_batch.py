"""Real rows per launched batch, from the engine's dispatch records
(``DispatchRecord.batch``) over the window. Moves ``goodput_qps``."""


def read(ctx):
    b = [rows for _, rows, _ in ctx.window.dispatches]
    return sum(b) / len(b) if b else None
