"""Mean duration of a busy scheduler iteration that dispatched no
prefill chunk, ms (``hvd_tpu_gen_iter_seconds{carried="decode"}`` over
the window)."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.mean_ms(ctx, gaps.ITER, ("decode",))
