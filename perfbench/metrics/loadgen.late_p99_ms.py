"""How late the load generator sent, against each request's due time:
99th percentile over the open-loop requests of the window, ms. A starved
generator flatters the latency it feeds."""

from perfbench.harness import stats


def read(ctx):
    t0, t1 = ctx.window
    late = [r.sent - r.due for r in ctx.facts.get("records", ())
            if r.kind == "open" and r.sent is not None and t0 <= r.due <= t1]
    return stats.percentile(late, 99) * 1e3 if late else None
