"""Peak of ``BlockAllocator.in_use`` over the window, sampled at every
``on_step``, as a share of the pool's usable blocks, %."""


def read(ctx):
    if "pool_in_use_peak" not in ctx.facts:
        return None
    return 100.0 * ctx.facts["pool_in_use_peak"] / ctx.facts["pool_blocks"]
