"""Host share of a scheduler iteration, ms: the mean of
``hvd_tpu_gen_step_seconds{component="host"}`` over the window."""


def read(ctx):
    mean = ctx.histogram_mean('hvd_tpu_gen_step_seconds{component="host"}')
    return None if mean is None else mean * 1e3
