"""Idle time of the first chip inside the traced window while the
scheduler's loop was parked on its queue with nothing running, waiting
or in flight (its ``gen.park`` loop spans), ms a scheduler iteration:
the idle chip that is the traffic's doing, not the host's."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.parked_ms_per_iter(ctx)
