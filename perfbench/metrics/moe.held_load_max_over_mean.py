"""The busiest held expert's picks over the mean of all held experts,
over the window: the straggler a grouped matmul pays for (1.0 is an even
load). Where the program has no such counters, nothing."""


def read(ctx):
    first, end = ctx.config.get("held_experts", (0, 0))
    picks = [ctx.counter_delta(
        'hvd_tpu_gen_moe_held_expert_picks_total{expert="%d"}' % e) or 0.0
        for e in range(first, end)]
    if not picks or not sum(picks):
        return None
    return max(picks) / (sum(picks) / len(picks))
