"""Mean duration of a busy scheduler iteration that dispatched a prefill
chunk, with or without a decode step, ms
(``hvd_tpu_gen_iter_seconds{carried="prefill"|"both"}`` over the window).
With ``scheduler.chunk_iter_share`` and ``scheduler.iter_ms_decode_only``
it gives the mean pass, and with ``scheduler.batch_occupancy_mean`` the
tokens a second."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.mean_ms(ctx, gaps.ITER, ("prefill", "both"))
