"""Host time a busy scheduler iteration spends copying per-sequence
state, ms: the self time of the loop's ``gen.state.snapshot`` and
``gen.state.restore`` spans
(``hvd_tpu_gen_phase_seconds{phase="state.snapshot"|"state.restore"}``),
summed over the window, over the window's busy iterations. The copies
themselves run on the device in program order and wait for nothing: this
is their dispatch. Where the program has no such spans, nothing."""

from perfbench.harness import hostspans


def read(ctx):
    if not any(k.startswith('hvd_tpu_gen_phase_seconds{phase="state.')
               for k in ctx.counters_after):
        return None
    return hostspans.phase_ms_per_iter(ctx, ("state.snapshot",
                                             "state.restore"))
