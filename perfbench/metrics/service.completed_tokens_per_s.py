"""Prompt plus generated tokens of the requests that completed after the
first request completion inside the window and up to the last, over the
time between the two. It stands beside ``served_tokens_per_s`` to show
what counting whole requests at their end does to a rate (PERF.md,
section 2): the work in flight at the two edges is not the same."""

from perfbench.harness import stats


def read(ctx):
    done = [(r.done, len(r.req.prompt) + len(r.tokens))
            for r in ctx.facts.get("records", ())
            if r.done is not None and r.error is None]
    return stats.rate_between_completions(done, *ctx.window)
