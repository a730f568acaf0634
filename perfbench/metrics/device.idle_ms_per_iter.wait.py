"""Idle time of the first chip inside the traced window while the
scheduler was blocked on a device result (its ``gen.wait`` loop spans:
the program had ended, the host had not yet been handed the result),
ms a scheduler iteration."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.idle_ms_per_iter(ctx, "wait")
