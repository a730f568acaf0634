"""The LongCat-Flash decode program's share of its roofline, %: the least
time the chip could take to read what one step needs
(``counts_longcat.decode_bytes``: the weights outside the experts and
the head once in bfloat16, the held experts that live tokens *touched*,
from the program's own counters, and the 576 cached values of every
live token in each of the 8 planes) at the chip's HBM bandwidth, over
``jit__decode``'s device time. The live tokens are the mean, over the
window's decode steps, of the contexts of the lanes that step served."""

import bisect

from perfbench.harness import counts_longcat


def read(ctx):
    if not ctx.trace:
        return None
    step_ms = ctx.trace.program_ms(r"jit__decode")
    touched = ctx.counter_delta(
        'hvd_tpu_gen_moe_experts_touched_total{phase="decode"}')
    calls = ctx.counter_delta('hvd_tpu_gen_moe_calls_total{phase="decode"}')
    if not step_ms or not calls or touched is None:
        return None
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    contexts = [
        sum(len(by_seq[i].req.prompt)
            + bisect.bisect_right(by_seq[i].token_times, t)
            for i in ids if i in by_seq)
        for t, phase, ids in ctx.spans.get("steps", ())
        if phase == "decode" and t0 <= t <= t1]
    if not contexts:
        return None
    need = counts_longcat.decode_bytes(
        ctx.config, sum(contexts) / len(contexts),
        touched / calls / ctx.config["num_layers"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3)
