"""Host time a busy scheduler iteration spends delivering, ms: results
mirrored into sequences, tokens put on streams, blocks registered,
sequences retired, ``on_step`` (``phase="deliver"`` of
``hvd_tpu_gen_phase_seconds``) plus the iteration's own remainder
(``phase="iter"``). With the admit and launch metrics it adds up to
``scheduler.host_ms_per_iter``."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.phase_ms_per_iter(ctx, ("deliver", "iter"))
