"""Peak of the state-snapshot slots in use over the window, sampled at
every ``on_step``, as a share of the engine's snapshot slots, %. At 100
every new snapshot evicts the least recently used one. Where the runner
left no such fact (a model without per-sequence state), nothing."""


def read(ctx):
    slots = ctx.facts.get("snapshot_slots")
    if not slots or "snapshot_slots_peak" not in ctx.facts:
        return None
    return 100.0 * ctx.facts["snapshot_slots_peak"] / slots
