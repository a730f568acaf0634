"""The 90th percentile of the gaps between one request's tokens as the
scheduler times them, ms: ``hvd_tpu_gen_itl_seconds``, every label
pooled, over the window, interpolated inside the bucket the rank falls
in. The client's ``itl_p90_ms`` less this is what the streams, their
reader threads and the interpreter lock add; an ``info`` line holds the
same run's client-side figure beside it."""

from perfbench.harness import gaps


def read(ctx):
    deltas = gaps.label_deltas(ctx, gaps.ITL)
    if deltas is None:
        return None
    took = gaps.pooled(deltas)
    p90 = gaps.quantile(took["buckets"], 90)
    if p90 is None:
        return None
    ctx.info(scheduler_itl={
        "p90_ms": p90 * 1e3, "client_p90_ms": ctx.end_to_end.get("itl_p90_ms"),
        "gaps": {label: d["count"] for label, d in deltas.items()}})
    return p90 * 1e3
