"""Mean device time of one executed train-step program, ms, from the
trace, over every chip."""


def read(ctx):
    return ctx.trace.program_ms(r"jit__step") if ctx.trace else None
