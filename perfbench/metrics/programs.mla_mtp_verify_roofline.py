"""The GLM-4.7-Flash MTP verify program's share of its roofline, %: the
least time the chip could take for what one step needs, the larger of
its bytes over the HBM bandwidth (``counts_glm47_flash.verify_bytes``:
the weights outside the routed experts once, the routed experts a live
token picked, each lane's rows in every plane) and its operations over
the bf16 peak (``verify_flops``: two columns a lane through the main
model, the committed positions through the MTP module), over
``jit__verify_mtp``'s device time. The contexts are those of the lanes
each verify step of the window served (prompt plus the tokens out by
then), the touched experts the mean a MoE layer (the MTP module's among
them) a verify call of
``hvd_tpu_gen_moe_experts_touched_total{phase="verify"}``, the accepted
drafts a lane the window's ``hvd_tpu_gen_spec_accepted_total`` over
``_drafted_total`` (mode ``mtp``). Where the program has no such
counter, nothing."""

import bisect

from perfbench.harness import counts_glm47_flash as counts


def read(ctx):
    if not ctx.trace:
        return None
    step_ms = ctx.trace.program_ms(r"^jit__verify_mtp")
    touched = ctx.counter_delta(
        'hvd_tpu_gen_moe_experts_touched_total{phase="verify"}')
    calls = ctx.counter_delta('hvd_tpu_gen_moe_calls_total{phase="verify"}')
    tokens = ctx.counter_delta("hvd_tpu_gen_moe_tokens_total")
    held = ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="held"}')
    drafted = ctx.counter_delta('hvd_tpu_gen_spec_drafted_total{mode="mtp"}')
    accepted = ctx.counter_delta(
        'hvd_tpu_gen_spec_accepted_total{mode="mtp"}')
    if not step_ms or touched is None or not calls or not tokens \
            or held is None or not drafted or accepted is None:
        return None
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    steps = [(t, [i for i in ids if i in by_seq])
             for t, phase, ids in ctx.spans.get("steps", ())
             if phase == "decode" and t0 <= t <= t1]
    steps = [(t, ids) for t, ids in steps if ids]
    if not steps:
        return None
    c = counts.param_counts(ctx.config)
    moe = c["moe_layers"] + ctx.config["num_nextn_predict_layers"]
    rate = accepted / drafted
    need = []
    for t, ids in steps:
        # the step that emitted up to t: its lanes' context before it
        rows = [len(by_seq[i].req.prompt)
                + bisect.bisect_left(by_seq[i].token_times, t) - 1
                for i in ids]
        need.append(max(
            counts.verify_bytes(ctx.config, rows, touched / calls / moe,
                                rate) / ctx.peaks["hbm_bytes_per_s"],
            counts.verify_flops(ctx.config, rows, held / tokens, rate)
            / ctx.peaks["bf16_flops_per_s"]))
    return 100.0 * (sum(need) / len(need)) / (step_ms / 1e3)
