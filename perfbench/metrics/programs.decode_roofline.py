"""The decode program's share of its roofline, %: the least time the chip
could take to read what one step needs (every weight once in bf16, and
the K and V of the live tokens, ``counts.gpt2_decode_bytes``) at the
chip's HBM bandwidth, over the program's device time. A decode step at
these batch sizes is bound by bytes, not by operations. The live tokens
are the mean, over the window's decode steps, of the contexts of the
lanes that step served."""

import bisect

from perfbench.harness import counts


def read(ctx):
    if not ctx.trace:
        return None
    step_ms = ctx.trace.program_ms(r"jit__decode")
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    contexts = []
    for t, phase, ids in ctx.spans.get("steps", ()):
        if phase != "decode" or not t0 <= t <= t1:
            continue
        contexts.append(sum(
            len(by_seq[i].req.prompt)
            + bisect.bisect_right(by_seq[i].token_times, t)
            for i in ids if i in by_seq))
    if not step_ms or not contexts:
        return None
    need = counts.gpt2_decode_bytes(ctx.config,
                                    sum(contexts) / len(contexts))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3)
