"""Mean gap between two tokens of a sequence with at least one prefill
chunk dispatched between them, ms
(``hvd_tpu_gen_itl_seconds{between="prefill"}`` over the window): what a
prefill-side change can move."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.mean_ms(ctx, gaps.ITL, ("prefill",))
