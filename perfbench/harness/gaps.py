"""The gaps the program times itself, read over the window.

Two histograms of the scheduler: a token's gap by what the loop
dispatched between it and the sequence's previous token
(``hvd_tpu_gen_itl_seconds{between}``), and a busy pass by what it
carried (``hvd_tpu_gen_iter_seconds{carried}``). And two kinds of idle
time of the first chip in the traced part, by the loop's own spans on the
clock that ``hostspans`` fits: under ``gen.park`` (the loop blocked with
nothing to do), and inside each ``gen.wait`` before the awaited program's
event of the device's ``XLA Modules`` line starts and after it ends.

A program without the histogram, the span or the span's ``flight``
number (a parent commit) reads as nothing. So does a ring that no longer
holds the traced part's spans: a low number would pass for a fast loop.
"""

import re
from typing import Dict, List, Optional, Sequence

from . import hostspans, tracered

ITL = "hvd_tpu_gen_itl_seconds"
ITER = "hvd_tpu_gen_iter_seconds"
PARKED = "hvd_tpu_gen_parked_seconds_total"
PARK, WAIT = "gen.park", "gen.wait"
DISPATCH = ("gen.prefill.dispatch", "gen.decode.dispatch")
#: the module events that one dispatch span each leaves on the device
PROGRAM = re.compile(r"^jit__(prefill|decode|verify|beam)")
#: a program's event may open this long before its dispatch span does, or
#: end this long after its wait returned, and still be theirs (ns). The
#: profiler lines the device's plane up with the host's to within a
#: millisecond, no better: the chunks of a `longcat-flash` run all open
#: 0.8-0.95 ms before their dispatch, those of a `gpt2-xl` run up to
#: 0.33 ms (my chip runs, PR 35). A run that is off by one dispatch misses
#: by the wait's tail and a host pass (3 ms and more) or by a whole program
_TOL_NS = 1_500_000
#: dispatches that may be in flight when the profiler starts
_DEPTH = 8
#: dispatch spans from this long before the window are read too: the
#: program running when the profiler started was dispatched before it
_LEAD_S = 2.0


# -- histograms ---------------------------------------------------------------

def label_deltas(ctx, family: str) -> Optional[Dict[str, dict]]:
    """By the value of ``family``'s one label, what each of its series
    took over the window: ``count``, ``sum`` and cumulative ``buckets``.
    None where the program has no such family."""
    out = {}
    for series, after in ctx.counters_after.items():
        if not series.startswith(family + "{"):
            continue
        before = ctx.counters_before.get(series) or {
            "count": 0, "sum": 0.0, "buckets": {}}
        out[series.split('"')[1]] = {
            "count": after["count"] - before["count"],
            "sum": after["sum"] - before["sum"],
            "buckets": {le: n - before["buckets"].get(le, 0)
                        for le, n in after["buckets"].items()}}
    return out or None


def quantile(buckets: Dict[str, float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of a histogram from its
    cumulative ``buckets`` (upper bound, as text, to count; ``+Inf``
    closes them), linear inside the bucket the rank falls in, as
    Prometheus' ``histogram_quantile``. A rank in the ``+Inf`` bucket
    reads as the highest bound. None for an empty histogram."""
    bounds = sorted((float(le), n) for le, n in buckets.items()
                    if le != "+Inf")
    total = buckets.get("+Inf", bounds[-1][1] if bounds else 0)
    if total <= 0:
        return None
    rank = q / 100.0 * total
    lower, below = 0.0, 0
    for upper, n in bounds:
        if n >= rank:
            if n == below:
                return upper
            return lower + (upper - lower) * (rank - below) / (n - below)
        lower, below = upper, n
    return bounds[-1][0]


def pooled(deltas: Dict[str, dict], labels: Sequence[str] = ()) -> dict:
    """The series of ``labels`` (all of them by default) as one."""
    take = [d for label, d in deltas.items()
            if not labels or label in labels]
    buckets: Dict[str, float] = {}
    for d in take:
        for le, n in d["buckets"].items():
            buckets[le] = buckets.get(le, 0) + n
    return {"count": sum(d["count"] for d in take),
            "sum": sum(d["sum"] for d in take), "buckets": buckets}


def share(ctx, family: str, labels: Sequence[str]) -> Optional[float]:
    """Observations under ``labels`` over all of ``family``'s, %."""
    deltas = label_deltas(ctx, family)
    if deltas is None:
        return None
    every = pooled(deltas)["count"]
    if every <= 0:
        return None
    return 100.0 * pooled(deltas, labels)["count"] / every


def mean_ms(ctx, family: str, labels: Sequence[str]) -> Optional[float]:
    """Mean of the observations under ``labels``, ms."""
    deltas = label_deltas(ctx, family)
    if deltas is None:
        return None
    took = pooled(deltas, labels)
    if took["count"] <= 0:
        return None
    return took["sum"] / took["count"] * 1e3


# -- the chip's idle time under the new spans -----------------------------------

def ring_state() -> Optional[dict]:
    """``tracing.loop_ring()``, or None where the program has none."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return None
    read = getattr(tracing, "loop_ring", None)
    return None if read is None else read()


def _ring_covers(ctx, table: dict) -> bool:
    """Whether the ring still held the traced part's first spans when it
    was read; says so once in an ``info`` line where it did not."""
    if "loop_ring_covers" not in ctx.facts:
        state = ring_state()
        from_ns = ctx.trace.window[0] - table["clock"]["offset_ns"]
        ok = not (state and state["dropped"]
                  and state["oldest_end_ns"] > from_ns)
        if not ok:
            ctx.info(loop_ring_short={
                **state, "traced_from_ns": from_ns,
                "why": "the ring evicted spans of the traced part before "
                       "the readers ran: its span metrics are left out"})
        ctx.facts["loop_ring_covers"] = ok
    return ctx.facts["loop_ring_covers"]


def _table(ctx) -> Optional[dict]:
    """``hostspans.idle_table``, or None also where the ring has lost
    spans of the traced part."""
    table = hostspans.idle_table(ctx)
    if table is None or not _ring_covers(ctx, table):
        return None
    return table


def parked_ms_per_iter(ctx) -> Optional[float]:
    if PARKED not in ctx.counters_after:
        return None             # the program's loop has no gen.park
    table = _table(ctx)
    if table is None:
        return None
    return table["by_span"].get(PARK, 0) / table["iterations"] / 1e6


def pair_flights(dispatches: List[dict], waits: List[dict],
                 modules: List[dict], offset_ns: int) -> Optional[dict]:
    """The module event of each dispatch, by its ``flight``, as
    ``{"events": {flight: event}, "misfits": n, "early_ns": the most an
    event opens before its dispatch}``. The device runs
    programs in the order they were dispatched, so the events (by start)
    are a run of the dispatches (by flight). Of the runs that end at or
    shortly before the last dispatch made when the first event opened,
    the one with the fewest misfits: an event of another program than its
    dispatch's, one that opens before its dispatch does, or one that ends
    after the ``gen.wait`` for it has returned. None where the best run
    misfits in more than a fiftieth of its events."""
    dispatches = sorted(dispatches, key=lambda s: s["args"]["flight"])
    modules = sorted(modules, key=lambda e: e["start_ns"])
    if not dispatches or not modules:
        return None
    returned = {s["args"]["flight"]: s["end_ns"] + offset_ns for s in waits}

    def run(shift):
        return [(dispatches[shift + j], e) for j, e in enumerate(modules)
                if 0 <= shift + j < len(dispatches)]

    def misfit(d, e):
        back = returned.get(d["args"]["flight"])
        return (PROGRAM.match(e["name"]).group(1)
                != (d["args"].get("program") or "prefill")
                or d["start_ns"] + offset_ns > e["start_ns"] + _TOL_NS
                or (back is not None
                    and e["start_ns"] + e["dur_ns"] > back + _TOL_NS))

    first = modules[0]["start_ns"] + _TOL_NS - offset_ns
    latest = sum(1 for d in dispatches if d["start_ns"] <= first) - 1
    runs = range(latest, max(latest - _DEPTH, -len(modules)), -1)
    if not runs:
        return None
    # the fewest misfits; of two such runs the later
    bad, shift = min((sum(misfit(d, e) for d, e in run(k)), -k)
                     for k in runs)
    if bad > len(modules) // 50:
        return None
    pairs = run(-shift)
    return {"misfits": bad,
            "early_ns": max(0, max(d["start_ns"] + offset_ns - e["start_ns"]
                                   for d, e in pairs)),
            "events": {d["args"]["flight"]: e for d, e in pairs}}


def wait_split(ctx) -> Optional[dict]:
    """The first chip's idle time inside the traced part's ``gen.wait``
    spans, split at the awaited program's module event: ``head_ns``
    before it starts, ``inside_ns`` while it runs, ``tail_ns`` after it
    ends. Worked out once a run and printed as an ``info`` line."""
    if "wait_split" not in ctx.facts:
        ctx.facts["wait_split"] = _wait_split(ctx)
    return ctx.facts["wait_split"]


def _wait_split(ctx) -> Optional[dict]:
    table = _table(ctx)
    if table is None:
        return None
    trace = ctx.trace
    lo, hi = trace.window
    off = table["clock"]["offset_ns"]
    spans = hostspans.loop_spans(ctx.window[0] - _LEAD_S) or []
    waits = [s for s in spans if s["name"] == WAIT and "flight" in s["args"]
             and s["end_ns"] + off > lo and s["start_ns"] + off < hi]
    if not waits:
        return None             # the program numbers no flights
    plane = trace.planes[0]
    paired = pair_flights(
        [s for s in spans if s["name"] in DISPATCH and "flight" in s["args"]],
        [s for s in spans if s["name"] == WAIT and "flight" in s["args"]],
        [e for e in trace._line(plane, tracered.MODULES_LINE)
         if PROGRAM.match(e["name"])], off)
    if paired is None:
        ctx.info(wait_split={"why": "the device's program events fit no "
                                    "run of the dispatch spans"})
        return None
    head, inside, tail, unmatched = [], [], [], 0
    for s in waits:
        w0, w1 = s["start_ns"] + off, s["end_ns"] + off
        e = paired["events"].get(s["args"]["flight"])
        if e is None:
            unmatched += 1      # its program ran before the profiler did
            continue
        m0 = min(max(e["start_ns"], w0), w1)
        m1 = min(max(e["start_ns"] + e["dur_ns"], w0), w1)
        head.append((w0, m0))
        inside.append((m0, m1))
        tail.append((m1, w1))
    gaps = tracered.subtract([trace.window], trace._busy(plane))
    idle = tracered.total(gaps)

    def under(intervals):
        return idle - tracered.total(tracered.subtract(
            gaps, tracered.merge(iv for iv in intervals if iv[1] > iv[0])))

    out = {"head_ns": under(head), "inside_ns": under(inside),
           "tail_ns": under(tail), "waits": len(waits),
           "unmatched": unmatched, "misfits": paired["misfits"],
           "early_ns": paired["early_ns"],
           "iterations": table["iterations"]}
    ctx.info(wait_split={
        **{k[:-3] + "_s": out[k] / 1e9
           for k in ("head_ns", "inside_ns", "tail_ns")},
        "under_gen_wait_s": table["by_span"].get(WAIT, 0) / 1e9,
        "waits": len(waits), "unmatched": unmatched,
        "misfits": paired["misfits"],
        # no program starts before its dispatch: the device's plane lies
        # at least so far off the host's, and head and tail are no finer
        "event_before_dispatch_ms": paired["early_ns"] / 1e6})
    return out


def wait_ms_per_iter(ctx, part: str) -> Optional[float]:
    split = wait_split(ctx)
    if split is None:
        return None
    return split[part + "_ns"] / split["iterations"] / 1e6
