"""Idle time of the first chip inside the traced window's ``gen.wait``
loop spans before the awaited program's event of the device's ``XLA
Modules`` line starts (paired by the spans' ``flight`` number), ms a
scheduler iteration: the chip waited for the launch (the dispatch came
late, the runtime was slow to start the program)."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.wait_ms_per_iter(ctx, "head")
