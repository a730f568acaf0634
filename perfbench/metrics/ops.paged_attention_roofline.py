"""The paged-attention kernel's own share of its roofline, %: the bytes
of the blocks it read a decode step (the window's
``hvd_tpu_gen_paged_attn_group_blocks_total{kind="read"}`` by plane
group, times a block's bytes in that group's planes, over the window's
decode dispatches) at the chip's HBM bandwidth, over the device time of
the trace's ``paged_attention*`` operations a ``jit__decode`` program.
The kernel is bound by bytes (32 FLOP a byte at these shapes); the
blocks counted are what its walk copies, whole groups of 128 rows from
the first inside a lane's window. Where the program has no such counter
or the trace no such operation, nothing."""

from perfbench.harness import counts_command_a_plus as counts
from perfbench.harness import tracered


def read(ctx):
    if not ctx.trace or not ctx.trace.planes:
        return None
    steps = ctx.trace.program_count(r"jit__decode")
    phase = 'hvd_tpu_gen_phase_seconds{phase="decode.dispatch"}'
    dispatches = (ctx.counters_after.get(phase) or {}).get("count", 0) \
        - (ctx.counters_before.get(phase) or {}).get("count", 0)
    read_bytes = 0.0
    for group in ("full", "window"):
        blocks = ctx.counter_delta(
            'hvd_tpu_gen_paged_attn_group_blocks_total'
            '{kind="read",group="%s"}' % group)
        if blocks is None:
            return None
        read_bytes += blocks * counts.block_bytes(ctx.config, group)
    kernel_ns = sum(
        e["dur_ns"] for p in ctx.trace.planes
        for e in ctx.trace._line(p, tracered.OPS_LINE)
        if "paged_attention" in e["name"]) / len(ctx.trace.planes)
    if not steps or not dispatches or not kernel_ns:
        return None
    return 100.0 * (read_bytes / dispatches) \
        / ctx.peaks["hbm_bytes_per_s"] / (kernel_ns / steps / 1e9)
