"""Mean wait of a request from its arrival to the dispatch of its first
prefill chunk, ms, over the requests whose first chunk went out in the
window (``hvd_tpu_gen_queue_wait_seconds``): the wait for a lane, for
blocks and for the prefills ahead."""


def read(ctx):
    mean = ctx.histogram_mean("hvd_tpu_gen_queue_wait_seconds")
    return None if mean is None else mean * 1e3
