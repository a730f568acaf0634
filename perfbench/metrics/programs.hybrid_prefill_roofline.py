"""The Olmo-Hybrid prefill program's share of its roofline, %: for the
mean chunk of the window, the larger of its operations over the chip's
bf16 peak and its bytes over the HBM bandwidth
(``counts_olmo_hybrid.prefill_chunk_flops`` / ``prefill_chunk_bytes``:
live tokens through the matmuls, the chunked delta rule's products on
the linear layers, attention over the context the chunk had on the full
layers), over ``jit__prefill``'s device time. A chunk's live tokens and
the tokens before it are the scheduler's own: the arguments of its
``gen.prefill.dispatch`` loop spans inside the window (the runner leaves
them in ``prefill_chunks``), so a chunk that starts at a prefix hit is
counted from where it started."""

from perfbench.harness import counts_olmo_hybrid


def read(ctx):
    if not ctx.trace:
        return None
    chunk_ms = ctx.trace.program_ms(r"jit__prefill")
    chunks = ctx.facts.get("prefill_chunks")
    if not chunk_ms or not chunks:
        return None
    seconds = [
        max(counts_olmo_hybrid.prefill_chunk_flops(ctx.config, live, prefix)
            / ctx.peaks["bf16_flops_per_s"],
            counts_olmo_hybrid.prefill_chunk_bytes(ctx.config, live, prefix)
            / ctx.peaks["hbm_bytes_per_s"])
        for prefix, live in chunks]
    return 100.0 * (sum(seconds) / len(seconds)) / (chunk_ms / 1e3)
