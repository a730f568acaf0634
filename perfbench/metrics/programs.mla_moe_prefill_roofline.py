"""The LongCat-Flash prefill program's share of its roofline, %: for the
mean chunk of the window, the larger of its operations over the chip's
bf16 peak and its bytes over the HBM bandwidth
(``counts_longcat.prefill_chunk_flops`` / ``prefill_chunk_bytes``: live
tokens through the dense parts and the held experts they picked,
attention over the context each chunk had by the cheaper of the two
formulations), over ``jit__prefill``'s device time. A chunk's context
is read off the scheduler's steps: the k-th chunk of a request starts
at ``k x prefill_chunk`` (the traffic shares no prefix and preempts
nothing; a request that was preempted would be counted from its
start)."""

from perfbench.harness import counts_longcat


def read(ctx):
    if not ctx.trace:
        return None
    chunk_ms = ctx.trace.program_ms(r"jit__prefill")
    tokens = ctx.counter_delta("hvd_tpu_gen_moe_tokens_total")
    held = ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="held"}')
    touched = ctx.counter_delta(
        'hvd_tpu_gen_moe_experts_touched_total{phase="prefill"}')
    calls = ctx.counter_delta('hvd_tpu_gen_moe_calls_total{phase="prefill"}')
    if not chunk_ms or not tokens or not calls or held is None \
            or touched is None:
        return None
    cfg = ctx.config
    width = cfg["engine"]["prefill_chunk"]
    by_seq = {r.seq_id: r for r in ctx.facts.get("records", ())
              if r.seq_id is not None}
    t0, t1 = ctx.window
    seen, seconds = {}, []
    for t, phase, ids in ctx.spans.get("steps", ()):
        if phase != "prefill":
            continue
        for i in ids:
            k = seen[i] = seen.get(i, -1) + 1
            if i in by_seq and t0 <= t <= t1:
                prefix = k * width
                queries = min(width, len(by_seq[i].req.prompt) - prefix)
                if queries <= 0:
                    continue
                flops = counts_longcat.prefill_chunk_flops(
                    cfg, queries, prefix, held / tokens)
                nbytes = counts_longcat.prefill_chunk_bytes(
                    cfg, queries, prefix,
                    touched / calls / cfg["num_layers"])
                seconds.append(max(flops / ctx.peaks["bf16_flops_per_s"],
                                   nbytes / ctx.peaks["hbm_bytes_per_s"]))
    if not seconds:
        return None
    return 100.0 * (sum(seconds) / len(seconds)) / (chunk_ms / 1e3)
