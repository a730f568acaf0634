"""The benchmark of horovod_tpu: cells named in ``BENCHMARK.json``.

Everything that decides a number lives here and not in the program:
traffic generation, the reduction from traces and spans to metrics, the
table of peaks, FLOP and byte counts, the plain references, the
comparison behind ``correct``. From the program the benchmark takes only
the system under test, its counters and its ``on_step`` hook.
"""
