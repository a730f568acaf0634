"""Median time to first token, ms, over the requests whose first token
arrived in the window: from the due time in an open loop (the wait a
stall imposes counts), from the submit in a closed loop."""

from perfbench.harness import stats


def read(ctx):
    t0, t1 = ctx.window
    waits = [r.token_times[0] - (r.due if r.kind == "open" else r.sent)
             for r in ctx.facts.get("records", ())
             if r.kind in ("open", "closed") and r.token_times
             and t0 <= r.token_times[0] <= t1]
    return stats.percentile(waits, 50) * 1e3 if waits else None
