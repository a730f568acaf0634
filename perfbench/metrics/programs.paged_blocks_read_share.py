"""Share of the block tables that the decode-side programs' attention
read over the window, %: ``hvd_tpu_gen_paged_attn_blocks_total``,
``kind="read"`` over ``kind="table"``. 100 means the paged-attention
kernel did not engage (every table block of every lane is gathered,
whatever the lanes hold); on the kernel it is the live lanes' blocks,
rounded up to the kernel's groups, over lanes x ``max_blocks``. Where
the program has no such counter, nothing."""


def read(ctx):
    read, table = (
        ctx.counter_delta('hvd_tpu_gen_paged_attn_blocks_total{kind="%s"}'
                          % kind) for kind in ("read", "table"))
    if read is None or not table:
        return None
    return 100.0 * read / table
