"""Blocks the window plane group held over blocks the full group held,
%, the mean over the window's ``on_step``s of the two allocators'
``in_use`` (a block of a group counted once, whatever its planes): 100
says the window group released nothing (every token of every context
still held on the sliding layers' planes), and a lane whose context has
passed the window pulls it towards ``window / context``. Where the
runner left no such fact (a model with one plane group), nothing."""


def read(ctx):
    held = [(full, window) for full, window
            in ctx.facts.get("group_blocks_held", ()) if full]
    if not held:
        return None
    return 100.0 * sum(w / f for f, w in held) / len(held)
