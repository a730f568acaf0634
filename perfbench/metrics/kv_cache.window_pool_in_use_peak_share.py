"""Peak of the window plane group's blocks in use over the window,
sampled at every ``on_step``, as a share of that group's usable blocks,
%. The engine sizes the group's pool for every lane's window, chunk and
slack, so under 100 by construction; what is left is where released
blocks that are still indexed park. Where the runner left no such fact,
nothing."""


def read(ctx):
    held = ctx.facts.get("group_blocks_held")
    blocks = ctx.facts.get("window_pool_blocks")
    if not held or not blocks:
        return None
    return 100.0 * max(window for _, window in held) / blocks
