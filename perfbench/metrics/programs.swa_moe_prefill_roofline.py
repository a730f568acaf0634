"""The Command A+ prefill program's share of its roofline, %: for the
mean chunk of the window, the larger of its operations over the chip's
bf16 peak and its bytes over the HBM bandwidth
(``counts_command_a_plus.prefill_chunk_flops`` / ``prefill_chunk_bytes``:
live tokens through the projections, the shared experts, the router and
the held experts they picked; attention over the context the chunk had
on the full plane and over ``min(context, 4096)`` on a window plane),
over ``jit__prefill``'s device time. A chunk's live tokens and the
tokens before it are the scheduler's own: the arguments of its
``gen.prefill.dispatch`` loop spans inside the window (the runner leaves
them in ``prefill_chunks``). The held picks a token are the window's
``hvd_tpu_gen_moe_picks_total{kind="held"}`` over its routed tokens
(uniform routing where the program has no such counter)."""

from perfbench.harness import counts_command_a_plus as counts


def read(ctx):
    if not ctx.trace:
        return None
    chunk_ms = ctx.trace.program_ms(r"jit__prefill")
    chunks = ctx.facts.get("prefill_chunks")
    if not chunk_ms or not chunks:
        return None
    tokens = ctx.counter_delta("hvd_tpu_gen_moe_tokens_total")
    held = ctx.counter_delta('hvd_tpu_gen_moe_picks_total{kind="held"}')
    picks = held / tokens if tokens and held is not None else None
    seconds = [
        max(counts.prefill_chunk_flops(ctx.config, live, prefix, picks)
            / ctx.peaks["bf16_flops_per_s"],
            counts.prefill_chunk_bytes(ctx.config, live, prefix)
            / ctx.peaks["hbm_bytes_per_s"])
        for prefix, live in chunks]
    return 100.0 * (sum(seconds) / len(seconds)) / (chunk_ms / 1e3)
