"""Mean device time of one executed decode program, ms, from the trace."""


def read(ctx):
    return ctx.trace.program_ms(r"jit__decode") if ctx.trace else None
