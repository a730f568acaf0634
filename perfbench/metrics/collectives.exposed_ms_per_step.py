"""Collective time per train step during which no other operation ran on
that chip, ms, from the trace of a cell across chips."""


def read(ctx):
    if not ctx.trace:
        return None
    steps = ctx.trace.program_count(r"jit__step")
    exposed = ctx.trace.exposed_collective_s()
    if not steps or exposed is None:
        return None
    return exposed / steps * 1e3
