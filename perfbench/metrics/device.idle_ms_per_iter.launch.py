"""Idle time of the first chip inside the traced window while the
scheduler was preparing or dispatching a program (its
``gen.*.prepare`` / ``gen.*.dispatch`` loop spans), ms a scheduler
iteration. See ``perfbench/harness/hostspans.py``."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.idle_ms_per_iter(ctx, "launch")
