"""Host time a busy scheduler iteration spends admitting, ms: queue
drain, cancellations, expiry and admission with its prefix matching
(``hvd_tpu_gen_phase_seconds{phase="admit"}``, the self time of the
loop's ``gen.admit`` spans, summed over the window, over the window's
busy iterations)."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.phase_ms_per_iter(ctx, ("admit",))
