"""Seconds this process spent building programs, compiled or read back
from the cache (``hvd_tpu_compile_seconds_total``, the program's own
count). ``correct`` demands that nothing is built inside the window, so
this is set-up's total. Read from the registry, since the training
runners take no snapshots."""


def read(ctx):
    from horovod_tpu import metrics

    return metrics.snapshot().get("hvd_tpu_compile_seconds_total")
