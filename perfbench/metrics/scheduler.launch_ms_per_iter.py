"""Host time a busy scheduler iteration spends launching its programs,
ms: block growth, host arrays, uploads and state rebuilds
(``gen.*.prepare``), then the programs' calls until they return
(``gen.*.dispatch``); the four phases of ``hvd_tpu_gen_phase_seconds``
together, summed over the window, over the window's busy iterations."""

from perfbench.harness import hostspans


def read(ctx):
    return hostspans.phase_ms_per_iter(
        ctx, ("prefill.prepare", "prefill.dispatch", "decode.prepare",
              "decode.dispatch"))
