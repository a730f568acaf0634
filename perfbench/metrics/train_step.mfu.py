"""Model FLOP/s utilization, %: the FLOPs the forward and backward passes
of one item need (from shapes, no recomputation counted) times the items
per second per chip this run measured, over the chip's bf16 peak."""


def read(ctx):
    rate = ctx.end_to_end.get("train_items_per_s_per_chip")
    flops = ctx.facts.get("train_flops_per_item")
    if rate is None or flops is None or ctx.rehearse:
        return None         # a CPU has no peak in the table
    return 100.0 * flops * rate / ctx.peaks["bf16_flops_per_s"]
