"""The Command A+ decode program's share of its roofline, %: the least
time the chip could take to move what one step needs
(``counts_command_a_plus.decode_bytes``: the weights outside the routed
experts once in bfloat16, the held experts a live token picked, a live
token's K and V on the full plane, and on the three window planes those
of the last ``min(context, 4096)``) at the chip's HBM bandwidth, over
``jit__decode``'s device time. The contexts are those of the lanes each
decode step of the window served (prompt plus the tokens out by then),
the touched experts the mean a layer a decode call of
``hvd_tpu_gen_moe_experts_touched_total{phase="decode"}``. Where the
program has no such counter, nothing."""

import bisect

from perfbench.harness import counts_command_a_plus as counts


def read(ctx):
    if not ctx.trace:
        return None
    step_ms = ctx.trace.program_ms(r"jit__decode")
    touched = ctx.counter_delta(
        'hvd_tpu_gen_moe_experts_touched_total{phase="decode"}')
    calls = ctx.counter_delta('hvd_tpu_gen_moe_calls_total{phase="decode"}')
    if not step_ms or touched is None or not calls:
        return None
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    steps = [(t, [i for i in ids if i in by_seq])
             for t, phase, ids in ctx.spans.get("steps", ())
             if phase == "decode" and t0 <= t <= t1]
    if not steps:
        return None
    layers = len(counts.layer_kinds(ctx.config))
    need = [counts.decode_bytes(
        ctx.config,
        [len(by_seq[i].req.prompt)
         + bisect.bisect_right(by_seq[i].token_times, t) for i in ids],
        touched / calls / layers) for t, ids in steps]
    return 100.0 * (sum(need) / len(need)) / ctx.peaks["hbm_bytes_per_s"] \
        / (step_ms / 1e3)
