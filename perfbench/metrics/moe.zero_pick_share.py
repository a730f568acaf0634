"""Share of router picks that fell on a zero-compute identity expert, %,
over the window (33.3 under uniform routing: 256 of 768 outputs): the
part of the expert work that costs no matmul. Where the program has no
such counters, nothing."""


def read(ctx):
    picks = [ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="%s"}' % k)
             for k in ("held", "zero", "absent")]
    if any(p is None for p in picks) or not sum(picks):
        return None
    return 100.0 * picks[1] / sum(picks)
