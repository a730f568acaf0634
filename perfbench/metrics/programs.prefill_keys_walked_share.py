"""Share of a lane's block table that the prefill program's attention
read over the window, %: ``hvd_tpu_gen_prefill_attn_keys_total``,
``kind="walked"`` over ``kind="table"``. 100 means the program does not
walk (it gathers every slot of the table, whatever the sequence holds);
where it walks, it is each chunk's last live position rounded up to the
walk's key block, over ``max_blocks x block_size``. Where the program
has no such counter, nothing."""


def read(ctx):
    walked, table = (
        ctx.counter_delta('hvd_tpu_gen_prefill_attn_keys_total{kind="%s"}'
                          % kind) for kind in ("walked", "table"))
    if walked is None or not table:
        return None
    return 100.0 * walked / table
