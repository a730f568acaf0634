"""Share of the window's token gaps between whose two tokens the loop
dispatched a prefill chunk, this sequence's or another's, %
(``hvd_tpu_gen_itl_seconds{between="prefill"}`` over every label). Over
10, ``itl_p90_ms`` is a gap that carried a chunk; under 10, a bare decode
iteration; within a few points of 10 it flips between seeds."""

from perfbench.harness import gaps


def read(ctx):
    return gaps.share(ctx, gaps.ITL, ("prefill",))
