"""Idle time of the first chip inside the traced window's ``gen.wait``
loop spans after the awaited program's event of the device's ``XLA
Modules`` line has ended (paired by the spans' ``flight`` number), ms a
scheduler iteration: the host had not come back (the transfer, the
thread's wake-up, the interpreter lock)."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.wait_ms_per_iter(ctx, "tail")
