"""The program's own loop spans against the device's trace.

``horovod_tpu.tracing`` keeps the generation scheduler's spans
(``gen.iter`` and its phases) in a ring, stamped with
``time.perf_counter_ns()``. The device's busy intervals in ``ctx.trace``
are on the profiler's clock. Both clocks are on two things the run
already holds: every ``bench.sched.after_<phase>`` mark of the trace
opens a few microseconds after the ``(time.perf_counter(), phase, ids)``
stamp that the same ``on_step`` call appended to ``ctx.spans["steps"]``,
and lasts until the next stamp. The run of mark durations picks out the
stamps; the median of ``mark start - stamp`` is the offset.

With the spans moved onto the profiler's clock, each idle gap of the
first chip goes, by exact intersection, to the innermost span over it.
A program without such spans (a parent commit), or a trace without a
device plane (a rehearsal), reads as nothing.
"""

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from . import stats, tracered

MARK = tracered.HOST_MARK + "sched.after_"
ROOT = "gen.iter"
#: span names by the metric that sums the idle time under them
GROUPS = {
    "launch": ("gen.prefill.prepare", "gen.prefill.dispatch",
               "gen.decode.prepare", "gen.decode.dispatch"),
    "deliver": ("gen.admit", "gen.deliver", ROOT),
    "wait": ("gen.wait",),
}
#: marks whose durations have to agree with the stamps' before the
#: offset is believed, and by how much (ns)
_RUN, _RUN_TOL_NS, _NEAR_NS = 12, 1_000_000, 500_000

Interval = Tuple[int, int]


def loop_spans(since_s: float) -> Optional[List[dict]]:
    """The program's loop spans that ended after ``since_s``, or None
    where the program keeps none."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return None
    read = getattr(tracing, "loop_spans", None)
    return None if read is None else read(since_s)


def clock_offset(marks: Sequence[dict],
                 stamps: Sequence[Tuple[float, str]]) -> Optional[dict]:
    """``offset_ns`` such that ``perf_counter_ns + offset_ns`` is the
    profiler's clock, from the ``after_<phase>`` marks (trace events,
    sorted by start) and the ``(perf_counter seconds, phase)`` stamps.
    Also ``residual_ns`` (distance between the quartiles of the matched
    differences), ``worst_ns`` and ``matched``. None when the marks
    cannot be placed among the stamps."""
    marks = [m for m in marks if m["name"].startswith(MARK)]
    run = marks[:_RUN]
    if len(run) < 2 or len(stamps) <= len(run):
        return None
    t_ns = [int(t * 1e9) for t, _ in stamps]
    phases = [MARK + p for _, p in stamps]
    best, best_err = None, None
    for k in range(len(stamps) - len(run)):
        if any(phases[k + j] != m["name"] for j, m in enumerate(run)):
            continue
        errs = sorted(abs(t_ns[k + j + 1] - t_ns[k + j] - m["dur_ns"])
                      for j, m in enumerate(run))
        err = errs[len(errs) // 2]
        if best_err is None or err < best_err:
            best, best_err = k, err
    if best is None or best_err > _RUN_TOL_NS:
        return None
    first = sorted(m["start_ns"] - t_ns[best + j]
                   for j, m in enumerate(run))
    guess = first[len(first) // 2]
    diffs = []
    for m in marks:
        want = m["start_ns"] - guess
        i = bisect.bisect_left(t_ns, want)
        near = [k for k in (i - 1, i) if 0 <= k < len(t_ns)
                and phases[k] == m["name"]
                and abs(t_ns[k] - want) <= _NEAR_NS]
        if near:
            k = min(near, key=lambda k: abs(t_ns[k] - want))
            diffs.append(m["start_ns"] - t_ns[k])
    if len(diffs) < 2:
        return None
    offset = int(round(stats.percentile(diffs, 50)))
    return {"offset_ns": offset,
            "residual_ns": stats.percentile(diffs, 75)
            - stats.percentile(diffs, 25),
            "worst_ns": max(abs(d - offset) for d in diffs),
            "matched": len(diffs), "marks": len(marks)}


def self_intervals(spans: Sequence[dict],
                   offset_ns: int = 0) -> Dict[str, List[Interval]]:
    """By span name, the intervals in which a span of that name was the
    innermost one open: each span less what its children cover."""
    children: Dict[object, List[Interval]] = {}
    for s in spans:
        if s["parent"] is not None:
            children.setdefault(s["parent"], []).append(
                (s["start_ns"] + offset_ns, s["end_ns"] + offset_ns))
    out: Dict[str, List[Interval]] = {}
    for s in spans:
        own = [(s["start_ns"] + offset_ns, s["end_ns"] + offset_ns)]
        out.setdefault(s["name"], []).extend(tracered.subtract(
            own, tracered.merge(children.get(s["span"], ()))))
    return {name: tracered.merge(ivs) for name, ivs in out.items()}


def attribute(gaps: List[Interval], spans: Sequence[dict],
              offset_ns: int = 0) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` (merged intervals) under each span name,
    by exact intersection with the innermost span, and what lay under
    no span as ``unattributed``."""
    out = {}
    for name, own in self_intervals(spans, offset_ns).items():
        under = tracered.total(gaps) - tracered.total(
            tracered.subtract(gaps, own))
        if under:
            out[name] = under
    out["unattributed"] = tracered.total(gaps) - sum(out.values())
    return out


def idle_table(ctx) -> Optional[dict]:
    """The first chip's idle time inside the traced window by the loop
    span the host was in, worked out once a run and printed as an
    ``info`` line."""
    if "host_spans" in ctx.facts:
        return ctx.facts["host_spans"]
    table = ctx.facts["host_spans"] = _idle_table(ctx)
    if table is not None:
        ctx.info(host_spans={
            "clock": table["clock"], "iterations": table["iterations"],
            "idle_s": table["idle_ns"] / 1e9,
            "idle_s_by_span": {k: v / 1e9 for k, v in sorted(
                table["by_span"].items(), key=lambda kv: -kv[1])}})
    return table


def _idle_table(ctx) -> Optional[dict]:
    trace = ctx.trace
    if trace is None or not trace.planes or ctx.window is None:
        return None
    spans = loop_spans(ctx.window[0])
    if not spans:
        return None
    clock = clock_offset(
        trace.host_marks,
        [(t, phase) for t, phase, _ in ctx.spans.get("steps", ())])
    if clock is None:
        return None
    lo, hi = trace.window
    off = clock["offset_ns"]
    spans = [s for s in spans
             if s["end_ns"] + off > lo and s["start_ns"] + off < hi]
    # the first chip's gaps, as Trace.idle_gaps takes them
    gaps = tracered.subtract([trace.window], trace._busy(trace.planes[0]))
    iterations = sum(1 for s in spans if s["name"] == ROOT
                     and lo <= s["start_ns"] + off < hi)
    if not iterations:
        return None
    return {"clock": clock, "iterations": iterations,
            "idle_ns": tracered.total(gaps),
            "by_span": attribute(gaps, spans, off)}


def idle_ms_per_iter(ctx, group: str) -> Optional[float]:
    table = idle_table(ctx)
    if table is None:
        return None
    under = sum(table["by_span"].get(name, 0) for name in GROUPS[group])
    return under / table["iterations"] / 1e6


# -- the phase histogram, over the window ------------------------------------

def _histogram_delta(ctx, series: str) -> Optional[Tuple[float, int]]:
    after = ctx.counters_after.get(series)
    if after is None:
        return None
    before = ctx.counters_before.get(series) or {"sum": 0.0, "count": 0}
    return after["sum"] - before["sum"], after["count"] - before["count"]


def phase_ms_per_iter(ctx, phases: Sequence[str]) -> Optional[float]:
    """Self time of the loop's ``phases`` summed over the window
    (``hvd_tpu_gen_phase_seconds``), over the window's busy iterations
    (the observations of ``hvd_tpu_gen_step_seconds``), ms."""
    busy = _histogram_delta(
        ctx, 'hvd_tpu_gen_step_seconds{component="host"}')
    if busy is None or busy[1] <= 0 or _histogram_delta(
            ctx, 'hvd_tpu_gen_phase_seconds{phase="iter"}') is None:
        return None
    total = 0.0
    for phase in phases:
        delta = _histogram_delta(
            ctx, f'hvd_tpu_gen_phase_seconds{{phase="{phase}"}}')
        total += delta[0] if delta else 0.0   # a phase that never ran
    return total / busy[1] * 1e3
