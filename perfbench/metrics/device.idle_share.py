"""Share of the traced window in which no operation ran on the device,
%, mean over the chips."""


def read(ctx):
    share = ctx.trace.idle_share() if ctx.trace else None
    return None if share is None else 100.0 * share
