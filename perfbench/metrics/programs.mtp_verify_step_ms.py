"""Mean device time of one executed MTP verify program (a width-2 step
of every lane with the MTP module's pass), ms, from the trace
(``jit__verify_mtp``). Where the program runs none, nothing."""


def read(ctx):
    return ctx.trace.program_ms(r"^jit__verify_mtp") if ctx.trace else None
