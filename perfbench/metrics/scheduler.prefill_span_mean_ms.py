"""Mean time from a request's first prefill chunk to its first token on
the stream, ms, over the requests whose first token came in the window
(``hvd_tpu_gen_prefill_span_seconds``): chunked prefill, one chunk an
iteration beside the decode steps."""


def read(ctx):
    mean = ctx.histogram_mean("hvd_tpu_gen_prefill_span_seconds")
    return None if mean is None else mean * 1e3
