"""Arithmetic on event lists: percentiles, gaps, rates between events.

Pure Python on plain lists, so the tests can feed hand-made events.
"""

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics
    (numpy's default). Raises on an empty sample: a percentile of nothing
    is not 0."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def token_gaps(token_times: Iterable[Sequence[float]], t0: float,
               t1: float) -> List[float]:
    """Gaps between successive tokens of one request, pooled over every
    request, for the gaps whose later token arrived inside ``[t0, t1]``.
    The first token of a request is not a gap."""
    gaps = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if t0 <= b <= t1:
                gaps.append(b - a)
    return gaps


def rate_between_first_tokens(
        first_tokens: Sequence[Tuple[float, int]],
        token_times: Sequence[float], t0: float,
        t1: float) -> Optional[Tuple[float, float, float]]:
    """Tokens served per second between two completion events of the
    stream. A prompt is served when its first token arrives
    (``first_tokens``: ``(time, prompt_tokens)``); a generated token when
    it arrives (``token_times``, first tokens included). The span runs
    from the first prompt completion inside ``[t0, t1]`` to the last one
    inside it and credits what was completed after the first and up to
    the last. Returns ``(rate, span_start, span_end)``, or None with
    fewer than two prompt completions inside the window: one event bounds
    no span, and that is a failed run, not a rate."""
    inside = sorted((t, n) for t, n in first_tokens if t0 <= t <= t1)
    if len(inside) < 2:
        return None
    ta, tb = inside[0][0], inside[-1][0]
    if tb <= ta:
        return None
    credit = sum(n for _, n in inside[1:])
    credit += sum(1 for t in token_times if ta < t <= tb)
    return credit / (tb - ta), ta, tb


def rate_between_completions(
        completions: Sequence[Tuple[float, int]], t0: float,
        t1: float) -> Optional[float]:
    """Tokens (prompt plus generated) of the requests that completed
    after the first request completion inside ``[t0, t1]`` and up to the
    last one inside it, over the time between those two. None with fewer
    than two completions inside the window."""
    inside = sorted((t, n) for t, n in completions if t0 <= t <= t1)
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return None
    return sum(n for _, n in inside[1:]) / (inside[-1][0] - inside[0][0])


def histogram_mean_delta(before: Optional[dict], after: dict) -> Optional[float]:
    """Mean of the observations a ``metrics.Registry`` histogram took
    between two snapshots (``{"sum": s, "count": n, ...}``)."""
    n0, s0 = (before["count"], before["sum"]) if before else (0, 0.0)
    n = after["count"] - n0
    if n <= 0:
        return None
    return (after["sum"] - s0) / n
