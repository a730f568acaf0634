"""Router picks that fell on an FFN expert this chip holds, a token a MoE
layer, over the window (0.25 under uniform routing: 12 picks x 16 held
of 768 outputs). Where the program has no such counters, nothing."""


def read(ctx):
    tokens = ctx.counter_delta("hvd_tpu_gen_moe_tokens_total")
    held = ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="held"}')
    if not tokens or held is None:
        return None
    return held / tokens
