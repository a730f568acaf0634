"""Share of the window's admitted prompt tokens that the prefix cache
answered, %: ``hvd_tpu_gen_prefix_cache_hit_tokens_total`` (every
source) over hit plus ``hvd_tpu_gen_prefix_cache_miss_tokens_total``.
For a model with per-sequence state a hit reaches as deep as the
deepest block that owns a state snapshot, so a lost snapshot shows here
as a lower share. Where the program has no such counters, nothing."""


def read(ctx):
    hit = [ctx.counter_delta(
        'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="%s"}' % source)
        for source in ("local", "transfer")]
    miss = ctx.counter_delta("hvd_tpu_gen_prefix_cache_miss_tokens_total")
    if hit[0] is None or miss is None:
        return None
    hits = sum(h or 0.0 for h in hit)
    if hits + miss <= 0:
        return None
    return 100.0 * hits / (hits + miss)
