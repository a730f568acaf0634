"""Mean device time of one executed prefill program (one chunk), ms,
from the trace."""


def read(ctx):
    return ctx.trace.program_ms(r"jit__prefill") if ctx.trace else None
