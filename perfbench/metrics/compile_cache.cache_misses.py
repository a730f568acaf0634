"""Programs this process compiled and wrote to the persistent cache
because the cache did not hold them
(``hvd_tpu_compile_cache_misses_total``): 0 in a warm checkout."""


def read(ctx):
    from horovod_tpu import metrics

    return metrics.snapshot().get("hvd_tpu_compile_cache_misses_total")
