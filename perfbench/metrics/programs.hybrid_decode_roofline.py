"""The Olmo-Hybrid decode program's share of its roofline, %: the least
time the chip could take to move what one step needs
(``counts_olmo_hybrid.decode_bytes``: the weights once in bfloat16, the
K and V rows of every live token on the four full layers' planes, and
each live lane's recurrent state and convolution window read once and
written once) at the chip's HBM bandwidth, over ``jit__decode``'s device
time. The live tokens and lanes are the means, over the window's decode
steps, of the contexts and the count of the lanes that step served."""

import bisect

from perfbench.harness import counts_olmo_hybrid


def read(ctx):
    if not ctx.trace:
        return None
    step_ms = ctx.trace.program_ms(r"jit__decode")
    if not step_ms:
        return None
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    steps = [(t, [i for i in ids if i in by_seq])
             for t, phase, ids in ctx.spans.get("steps", ())
             if phase == "decode" and t0 <= t <= t1]
    if not steps:
        return None
    contexts = [sum(len(by_seq[i].req.prompt)
                    + bisect.bisect_right(by_seq[i].token_times, t)
                    for i in ids) for t, ids in steps]
    need = counts_olmo_hybrid.decode_bytes(
        ctx.config, sum(contexts) / len(steps),
        sum(len(ids) for _, ids in steps) / len(steps))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3)
