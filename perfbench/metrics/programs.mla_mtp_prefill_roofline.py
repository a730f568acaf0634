"""The GLM-4.7-Flash MTP prefill program's share of its roofline, %: for
the mean chunk of the window, the larger of its operations over the
chip's bf16 peak and its bytes over the HBM bandwidth
(``counts_glm47_flash.prefill_chunk_flops`` / ``prefill_chunk_bytes``:
live tokens through the main model and the MTP module, the routed
experts they picked, attention in every plane, the weights once), over
``jit__prefill``'s device time. A chunk's live tokens and the tokens
before it are the scheduler's own: the arguments of its
``gen.prefill.dispatch`` loop spans inside the window (the runner leaves
them in ``prefill_chunks``). The routed picks a token are the window's
``hvd_tpu_gen_moe_picks_total{kind="held"}`` over its routed tokens.
Where the program or the runner leaves none of these, nothing."""

from perfbench.harness import counts_glm47_flash as counts


def read(ctx):
    if not ctx.trace:
        return None
    chunk_ms = ctx.trace.program_ms(r"jit__prefill")
    chunks = ctx.facts.get("prefill_chunks")
    tokens = ctx.counter_delta("hvd_tpu_gen_moe_tokens_total")
    held = ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="held"}')
    if not chunk_ms or not chunks or not tokens or held is None:
        return None
    seconds = [
        max(counts.prefill_chunk_flops(ctx.config, live, prefix,
                                       held / tokens)
            / ctx.peaks["bf16_flops_per_s"],
            counts.prefill_chunk_bytes(ctx.config, live, prefix)
            / ctx.peaks["hbm_bytes_per_s"])
        for prefix, live in chunks]
    return 100.0 * (sum(seconds) / len(seconds)) / (chunk_ms / 1e3)
