"""Mean gap between two tokens of a sequence with nothing but decode
steps dispatched between them, ms
(``hvd_tpu_gen_itl_seconds{between="decode"}`` over the window): what a
decode-side change can move."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.mean_ms(ctx, gaps.ITL, ("decode",))
