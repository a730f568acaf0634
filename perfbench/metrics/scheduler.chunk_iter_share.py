"""Share of the window's busy scheduler iterations that dispatched a
prefill chunk, %: ``hvd_tpu_gen_iter_seconds{carried="prefill"|"both"}``
over every label. How often prefill rides on the loop."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.share(ctx, gaps.ITER, ("prefill", "both"))
