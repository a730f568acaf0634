"""Live lanes per decode step: the mean of
``hvd_tpu_gen_batch_occupancy`` over the window."""


def read(ctx):
    return ctx.histogram_mean("hvd_tpu_gen_batch_occupancy")
