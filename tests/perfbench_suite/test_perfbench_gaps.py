"""The readers of the gaps the program times itself
(``perfbench/harness/gaps.py``): a token's gap and a pass by label from
hand-made counters, the chip's idle time under ``gen.park`` and at the
head and tail of each ``gen.wait`` on the chat trace recorded on the v5e
with synthetic spans, nothing from a parent's program or a ring that lost
its spans, and the entries of ``BENCHMARK.json``."""

import numpy as np
import pytest

from perfbench.harness import core, gaps, hostspans, tracered
from perfbench_testlib import ROOT, last_line, run_cell
from test_perfbench_hostspans import OFFSET_NS, _chat, _ctx, _span, _stamps

ITL_READERS = ("scheduler.itl_p90_ms", "scheduler.itl_chunk_gap_share",
               "scheduler.itl_decode_gap_mean_ms",
               "scheduler.itl_chunk_gap_mean_ms")
ITER_READERS = ("scheduler.chunk_iter_share",
                "scheduler.iter_ms_decode_only",
                "scheduler.iter_ms_with_chunk")
SPAN_READERS = ("device.idle_ms_per_iter.parked",
                "device.idle_ms_per_iter.wait_head",
                "device.idle_ms_per_iter.wait_tail")
ITL_CELLS = ["gpt2-xl.chat_steady", "longcat-flash.long_prompt_steady"]
SERVED_CELLS = ["gpt2-xl.doc_backlog", "olmo-hybrid.session_turns",
                "command-a-plus.mixed_lengths"]
#: the accepted benchmark's last per-layer entry (PR 34)
ACCEPTED_LAST = "programs.prefill_keys_walked_share.itl"


def _read(name, ctx, suffix=".itl"):
    return core.load_module(core.reader_path(name + suffix),
                            "reader_" + name.replace(".", "_")).read(ctx)


# -- histograms ---------------------------------------------------------------

BOUNDS = ("0.01", "0.02", "0.04", "0.08", "+Inf")


def _hist(counts, total_s):
    """A histogram's snapshot from per-bucket counts (``BOUNDS``)."""
    cum = np.cumsum(counts)
    return {"buckets": {le: int(n) for le, n in zip(BOUNDS, cum)},
            "sum": float(total_s), "count": int(cum[-1])}


def _counter_ctx():
    """A window in which 80 gaps held decode steps only (all between 10
    and 20 ms, 1.2 s), 20 a prefill chunk (between 20 and 40 ms, 0.6 s)
    and none a preemption; 90 passes decoded (1.08 s), 6 carried a chunk
    and a decode step (0.15 s), 4 a chunk alone (0.05 s). The snapshots
    before hold as much again, to be taken off."""
    window = {
        gaps.ITL + '{between="decode"}': _hist([0, 80, 0, 0, 0], 1.2),
        gaps.ITL + '{between="prefill"}': _hist([0, 0, 20, 0, 0], 0.6),
        gaps.ITL + '{between="preempt"}': _hist([0, 0, 0, 0, 0], 0.0),
        gaps.ITER + '{carried="decode"}': _hist([0, 90, 0, 0, 0], 1.08),
        gaps.ITER + '{carried="both"}': _hist([0, 0, 6, 0, 0], 0.15),
        gaps.ITER + '{carried="prefill"}': _hist([0, 4, 0, 0, 0], 0.05),
    }
    ctx = _ctx(None, [], [])
    ctx.end_to_end = {"itl_p90_ms": 31.0}
    ctx.counters_before = window
    ctx.counters_after = {
        k: {"buckets": {le: 2 * n for le, n in v["buckets"].items()},
            "sum": 2 * v["sum"], "count": 2 * v["count"]}
        for k, v in window.items()}
    return ctx


@pytest.mark.parametrize("reader,suffix,want", [
    # rank 90 of 100: the tenth of the 20 gaps between 20 and 40 ms
    ("scheduler.itl_p90_ms", ".itl", 30.0),
    ("scheduler.itl_chunk_gap_share", ".itl", 20.0),
    ("scheduler.itl_decode_gap_mean_ms", ".itl", 15.0),
    ("scheduler.itl_chunk_gap_mean_ms", ".itl", 30.0),
    ("scheduler.chunk_iter_share", ".itl", 10.0),
    ("scheduler.chunk_iter_share", ".served", 10.0),
    ("scheduler.iter_ms_decode_only", ".served", 12.0),
    ("scheduler.iter_ms_with_chunk", ".served", 20.0),
])
def test_counter_readers_take_the_windows_share_of_each_label(
        reader, suffix, want):
    ctx = _counter_ctx()
    assert _read(reader, ctx, suffix) == pytest.approx(want, rel=1e-9)
    if reader == "scheduler.itl_p90_ms":
        # the same run's client-side figure rides beside it
        (line,) = ctx.lines
        assert line["scheduler_itl"]["client_p90_ms"] == 31.0
        assert line["scheduler_itl"]["gaps"] == {
            "decode": 80, "prefill": 20, "preempt": 0}


def test_quantile_interpolates_inside_the_bucket_the_rank_falls_in():
    cum = {"0.01": 0, "0.02": 50, "0.04": 100, "+Inf": 100}
    assert gaps.quantile(cum, 50) == pytest.approx(0.02)
    assert gaps.quantile(cum, 25) == pytest.approx(0.015)
    assert gaps.quantile(cum, 75) == pytest.approx(0.03)
    # a rank beyond the last bound reads as that bound; nothing as nothing
    assert gaps.quantile({"0.01": 1, "+Inf": 10}, 90) == 0.01
    assert gaps.quantile({"0.01": 0, "+Inf": 0}, 90) is None


def test_a_label_without_observations_has_a_share_and_no_mean():
    ctx = _counter_ctx()
    assert gaps.share(ctx, gaps.ITL, ("preempt",)) == 0.0
    assert gaps.mean_ms(ctx, gaps.ITL, ("preempt",)) is None


# -- spans against the recorded trace ---------------------------------------------

def _idle_in(trace, a, b, grid_ns=50):
    """Idle time of the first chip in ``[a, b)`` inside the window, by
    painting the operations onto a grid: no interval arithmetic."""
    lo, hi = trace.window
    a, b = max(a, lo), min(b, hi)
    n = (hi - lo) // grid_ns
    busy = np.zeros(n, bool)
    for e in trace.events:
        if e["line"] == tracered.OPS_LINE:
            busy[max(0, (e["start_ns"] - lo) // grid_ns):
                 min(n, -(-(e["start_ns"] + e["dur_ns"] - lo) // grid_ns))] \
                = True
    return int((~busy[(a - lo) // grid_ns:(b - lo) // grid_ns]).sum()) \
        * grid_ns


def _programs(trace):
    return sorted((e for e in trace.events
                   if e["line"] == tracered.MODULES_LINE
                   and gaps.PROGRAM.match(e["name"])),
                  key=lambda e: e["start_ns"])


def _flight_spans(trace):
    """Three synthetic passes over the recorded window (decode, tiny
    programs and a prefill chunk, decode) and a park after them: the
    first decode's wait opens 4.4 ms before the program starts and ends
    0.4 ms after it; the chunk's wait opens after the chunk started; the
    last wait ends 0.5 ms after the second decode, and the loop parks."""
    decode1, prefill, decode2 = _programs(trace)
    lo, hi = trace.window
    ms = 1_000_000
    d1_end = decode1["start_ns"] + decode1["dur_ns"]
    p0, p_end = prefill["start_ns"], prefill["start_ns"] + prefill["dur_ns"]
    d2_end = decode2["start_ns"] + decode2["dur_ns"]

    def span(sid, parent, name, start, end, trace_id, **args):
        s = _span(sid, parent, name, start, end, f"gen-iter:{trace_id}")
        s["args"] = args
        return s

    a_end, b_end, c_end = p0 + 200_000, p_end + ms, d2_end + ms // 2
    return [
        span(1, None, "gen.iter", lo - 5 * ms, a_end, 1),
        span(2, 1, "gen.decode.dispatch", lo - 49 * ms // 10,
             lo - 45 * ms // 10, 1, program="decode", lanes=2, flight=7),
        span(3, 1, "gen.wait", lo - 44 * ms // 10, d1_end + 400_000, 1,
             program="decode", flight=7),
        span(4, 1, "gen.deliver", d1_end + 400_000, d1_end + ms, 1),
        span(5, 1, "gen.prefill.prepare", d1_end + ms, p0 - 200_000, 1),
        span(6, 1, "gen.prefill.dispatch", p0 - 200_000, p0 + 100_000, 1,
             chunk=512, flight=8),
        span(7, None, "gen.iter", a_end, b_end, 2),
        span(8, 7, "gen.decode.dispatch", a_end + 100_000, a_end + 400_000,
             2, program="decode", lanes=2, flight=9),
        span(9, 7, "gen.wait", a_end + 500_000, p_end + 600_000, 2,
             program="prefill", flight=8),
        span(10, None, "gen.iter", b_end, c_end, 3),
        span(11, 10, "gen.wait", b_end + 100_000, c_end, 3,
             program="decode", flight=9),
        span(12, None, "gen.park", c_end, hi + 7 * ms, 4),
    ]


def _span_ctx(monkeypatch, spans=None):
    _, trace = _chat()
    stamps, _ = _stamps(trace, lag_ns=0)
    spans = _flight_spans(trace) if spans is None else spans
    monkeypatch.setattr(hostspans, "loop_spans", lambda since: list(spans))
    ctx = _ctx(trace, spans, stamps)
    ctx.counters_after = {gaps.PARKED: 12.0}
    return ctx, trace, spans


def test_span_readers_find_the_known_park_head_and_tail(monkeypatch):
    ctx, trace, spans = _span_ctx(monkeypatch)
    got = {r: _read(r, ctx, ".served") for r in SPAN_READERS}
    table = ctx.facts["host_spans"]
    assert table["iterations"] == 2     # the first pass began before
    decode1, prefill, decode2 = _programs(trace)
    by = {s["span"]: (s["start_ns"] + OFFSET_NS, s["end_ns"] + OFFSET_NS)
          for s in spans}
    d1_end = decode1["start_ns"] + decode1["dur_ns"]
    p_end = prefill["start_ns"] + prefill["dur_ns"]
    d2_end = decode2["start_ns"] + decode2["dur_ns"]
    want_head = _idle_in(trace, by[3][0], decode1["start_ns"])
    want_tail = _idle_in(trace, d1_end, by[3][1]) \
        + _idle_in(trace, p_end, by[9][1]) + _idle_in(trace, d2_end, by[11][1])
    want_park = _idle_in(trace, *by[12])
    # what is idle of the 2.0 ms before the first decode starts (the step
    # before it still runs into the window); 0.4 + 0.5 ms after the
    # decodes and the few us between the chunk and the decode behind it
    assert 1_600_000 < want_head < 2_000_000
    assert 900_000 < want_tail < 920_000 and want_park > 1_400_000
    clock = table["clock"]["offset_ns"] - OFFSET_NS      # the fit's error
    tol = dict(rel=1e-3, abs=3 * abs(clock) + 200)
    assert got["device.idle_ms_per_iter.wait_head"] * 2e6 == \
        pytest.approx(want_head, **tol)
    assert got["device.idle_ms_per_iter.wait_tail"] * 2e6 == \
        pytest.approx(want_tail, **tol)
    assert got["device.idle_ms_per_iter.parked"] * 2e6 == \
        pytest.approx(want_park, **tol)
    # head, tail and what lies inside the programs' own events are the
    # idle time under gen.wait, to the nanosecond
    split = ctx.facts["wait_split"]
    assert split["head_ns"] + split["inside_ns"] + split["tail_ns"] == \
        table["by_span"]["gen.wait"]
    assert (split["waits"], split["unmatched"], split["misfits"],
            split["early_ns"]) == (3, 0, 0, 0)
    # with the park named, nothing of the window lies under no span
    assert table["by_span"]["unattributed"] == 0
    assert [list(line) for line in ctx.lines] == [["host_spans"],
                                                  ["wait_split"]]


def _decode_run(n, step_ns=10_000_000, host_ns=3_000_000, first_flight=40):
    """``n`` decode steps a device runs back to back, each dispatched one
    step ahead (async depth 1): dispatch ``k + 1`` goes out ``host_ns``
    after step ``k - 1`` ended, which is when step ``k`` started; the
    wait for step ``k`` returns 0.4 ms after it ended. The device's clock
    runs up to 0.3 ms ahead of the host's."""
    rng = np.random.RandomState(2)
    modules = [{"name": "jit__decode(1)", "dur_ns": step_ns,
                "start_ns": (5 + k) * step_ns} for k in range(n)]
    dispatches, waits = [], []
    for k, m in enumerate(modules):
        sent = m["start_ns"] - step_ns + host_ns
        skew = int(rng.randint(0, 300_000))
        dispatches.append(_span(100 + k, 1, "gen.decode.dispatch",
                                sent + skew, sent + skew + 300_000))
        waits.append(_span(200 + k, 1, "gen.wait", m["start_ns"] + skew,
                           m["start_ns"] + step_ns + 400_000 + skew))
        for s in (dispatches[-1], waits[-1]):
            s["args"] = {"program": "decode", "flight": first_flight + k}
    return dispatches, waits, modules


def test_flights_pair_with_the_run_of_events_that_misfits_least():
    dispatches, waits, modules = _decode_run(60)
    want = {40 + k: m for k, m in enumerate(modules)}

    def pair(d, w, m):
        got = gaps.pair_flights(d, w, m, OFFSET_NS)
        return got and (got["events"], got["misfits"])

    assert pair(dispatches, waits, modules) == (want, 0)
    # the profiler started later than the ring's memory: the first three
    # programs ran untraced
    assert pair(dispatches, waits, modules[3:]) == (
        {f: m for f, m in want.items() if f >= 43}, 0)
    # the ring's memory starts later than the trace: two events belong
    # to dispatches it no longer holds
    assert pair(dispatches[2:], waits[2:], modules) == (
        {f: m for f, m in want.items() if f >= 42}, 0)
    # one event out of place is a misfit, not another run
    late = [dict(m) for m in modules]
    late[30]["start_ns"] -= 9_000_000
    assert pair(dispatches, waits, late)[1] == 1
    # a run of another program fits nowhere, nor do events without spans
    chunks = [dict(m, name="jit__prefill(2)") for m in modules]
    assert pair(dispatches, waits, chunks) is None
    assert pair([], [], modules) is None


def test_the_waits_tell_a_run_two_dispatches_early_from_the_right_one():
    """A chunk and a decode step every pass, each awaited before the next
    is dispatched (the backlog cell): programs alternate, so a run two
    dispatches early has every event of the right program and none before
    its dispatch; only the waits, which returned before those events
    ended, refuse it."""
    ms = 1_000_000
    dispatches, waits, modules = [], [], []
    for k in range(40):
        t = (10 + 25 * k) * ms
        for j, (program, name) in enumerate((
                ("prefill", "gen.prefill.dispatch"),
                ("decode", "gen.decode.dispatch"))):
            flight = 2 * k + j + 1
            d = _span(flight, 1, name, t + j * ms, t + j * ms + ms // 2)
            d["args"] = {"flight": flight}
            if j:
                d["args"]["program"] = program
            dispatches.append(d)
            modules.append({"name": f"jit__{program}(7)", "dur_ns": 11 * ms,
                            "start_ns": t + (1 + 11 * j) * ms})
        w = _span(1000 + k, 1, "gen.wait", t + 3 * ms, t + 24 * ms)
        w["args"] = {"program": "decode", "flight": 2 * k + 2}
        waits.append(w)
    # the profiler missed the first two programs, and the device's clock
    # puts one event 2 ms before its dispatch: the right run's one
    # misfit, where the run two dispatches early would have none
    modules[20]["start_ns"] -= 3_000_000
    got = gaps.pair_flights(dispatches, waits, modules[2:], OFFSET_NS)
    assert got["misfits"] == 1
    assert got["events"] == {k + 1: m for k, m in enumerate(modules)
                             if k >= 2}
    assert gaps.pair_flights(dispatches, [], modules[2:], OFFSET_NS)[
        "events"] != got["events"]


def test_a_ring_that_lost_the_traced_spans_reads_as_nothing(monkeypatch):
    ctx, trace, spans = _span_ctx(monkeypatch)
    # what tracing.loop_ring() says after evicting past the traced start
    start = trace.window[0] - OFFSET_NS
    monkeypatch.setattr(gaps, "ring_state", lambda: {
        "depth": 8, "held": 8, "dropped": 5, "oldest_end_ns": start + 10})
    for r in SPAN_READERS:
        assert _read(r, ctx, ".itl") is None, r
    assert [list(line) for line in ctx.lines] == [["host_spans"],
                                                  ["loop_ring_short"]]
    assert ctx.lines[1]["loop_ring_short"]["dropped"] == 5
    # evictions that stopped short of the traced part cost nothing
    ctx, _, _ = _span_ctx(monkeypatch)
    monkeypatch.setattr(gaps, "ring_state", lambda: {
        "depth": 8, "held": 8, "dropped": 5, "oldest_end_ns": start - 10})
    assert all(_read(r, ctx, ".itl") is not None for r in SPAN_READERS)


@pytest.mark.parametrize("reader", ITL_READERS + ITER_READERS + SPAN_READERS)
def test_new_readers_read_nothing_from_a_parents_program(reader,
                                                         monkeypatch):
    """The parent commit: no itl or iter histogram, no park counter, and
    spans without ``flight`` or ``gen.park``. No reader raises, none
    reads 0."""
    _, trace = _chat()
    spans = [dict(s, args={k: v for k, v in s["args"].items()
                           if k != "flight"})
             for s in _flight_spans(trace) if s["name"] != "gen.park"]
    ctx, _, _ = _span_ctx(monkeypatch, spans)
    ctx.end_to_end = {}
    ctx.counters_before = {}
    ctx.counters_after = {
        'hvd_tpu_gen_step_seconds{component="host"}':
            {"sum": 1.0, "count": 10, "buckets": {"+Inf": 10}},
        "hvd_tpu_gen_preemptions_total": 0.0}
    suffix = ".itl" if reader in ITL_READERS else ".served"
    assert _read(reader, ctx, suffix) is None


# -- the benchmark's entries ------------------------------------------------------

def test_benchmark_lists_the_new_metrics_after_the_accepted_ones(spec):
    names = [m["name"] for m in spec["per_layer"]]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    accepted = names.index(ACCEPTED_LAST)
    want = [r + ".itl" for r in ITL_READERS] + [
        r + s for r in ITER_READERS + SPAN_READERS
        for s in (".itl", ".served")]
    assert len(want) == 16
    for name in want:
        assert names.count(name) == 1 and names.index(name) > accepted, name
        m = by_name[name]
        assert core.reader_path(name).endswith(
            name.rsplit(".", 1)[0] + ".py")
        itl = name.endswith(".itl")
        assert m["moves"] == ("itl_p90_ms" if itl
                              else "served_tokens_per_s")
        # every busy pass of the backlog cell carries a chunk: it has no
        # decode-only pass to average, and a listed metric must be there
        cells = ITL_CELLS if itl else SERVED_CELLS[
            name == "scheduler.iter_ms_decode_only.served":]
        assert m["workloads"] == cells
        span = name.startswith("device.")
        assert m["layer"] == ("device" if span else "scheduler")
        assert m["source"] == ("program_span" if span
                               else "program_counter")
        assert m["better"] == "lower" and m["unit"] in ("ms", "%")
    # in the order the issue gives them, so a reader of the file finds
    # the quantities of one family together
    assert [n for n in names if n in want] == want


@pytest.mark.parametrize("cell,suffix,readers", [
    ("gpt2-xl.chat_steady", ".itl", ITL_READERS + ITER_READERS),
    ("gpt2-xl.doc_backlog", ".served",
     ("scheduler.chunk_iter_share", "scheduler.iter_ms_with_chunk"))])
def test_rehearsal_reports_the_gap_counters_and_leaves_the_span_metrics_out(
        cell, suffix, readers):
    proc = run_cell(ROOT, "--workload", cell, "--seed", "2147483693",
                    "--seconds", "3", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = set(last_line(proc)["metrics"])
    assert {r + suffix for r in readers} <= got
    # a CPU trace has no TPU plane: nothing to attribute, and no error
    assert not {r + suffix for r in SPAN_READERS} & got
