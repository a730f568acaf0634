"""Idle time of the first chip inside the traced window while the
scheduler was admitting, delivering results or between phases (its
``gen.admit`` and ``gen.deliver`` loop spans and ``gen.iter``'s own
time), ms a scheduler iteration."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.idle_ms_per_iter(ctx, "deliver")
