"""Sequences preempted for want of KV blocks inside the window
(``hvd_tpu_gen_preemptions_total``)."""


def read(ctx):
    if not ctx.counters_after:
        return None
    return ctx.counter_delta("hvd_tpu_gen_preemptions_total") or 0.0
