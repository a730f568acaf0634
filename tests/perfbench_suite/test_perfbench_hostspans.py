"""The program's loop spans against the device's trace: the clock offset
recovered from marks and stamps, idle time attributed to the innermost
span, on the chat trace recorded on the v5e with synthetic stamps and
spans; and the new readers in rehearsals of both serving cells."""

import os
import types

import numpy as np
import pytest

from perfbench.harness import core, hostspans, tracered
from perfbench.harness.tracered import Trace
from perfbench_testlib import ROOT, last_line, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT = os.path.join(DATA, "v5e_gpt2xl_chat_decode_prefill_decode.json.gz")
#: profiler's clock minus time.perf_counter_ns(), for the synthetic side
OFFSET_NS = -987_654_321_012
COUNTER_READERS = ("scheduler.admit_ms_per_iter",
                   "scheduler.launch_ms_per_iter",
                   "scheduler.deliver_ms_per_iter",
                   "scheduler.queue_wait_mean_ms",
                   "scheduler.prefill_span_mean_ms")
SPAN_READERS = ("device.idle_ms_per_iter.launch",
                "device.idle_ms_per_iter.deliver",
                "device.idle_ms_per_iter.wait")


def _chat():
    events = tracered.load_events(CHAT)
    return events, Trace(events)


def _stamps(trace, lag_ns=9_000, decoys=40):
    """The ``(perf_counter seconds, phase)`` stamps that would have left
    the trace's marks: each ``lag_ns`` before its mark opens, one more
    where the last mark closes, and decoys on both sides at the cadence
    of a decode step."""
    marks = [m for m in trace.host_marks
             if m["name"].startswith(hostspans.MARK)]
    real = [(m["start_ns"] - lag_ns - OFFSET_NS,
             m["name"][len(hostspans.MARK):]) for m in marks]
    real.append((marks[-1]["start_ns"] + marks[-1]["dur_ns"] - lag_ns
                 - OFFSET_NS, "decode"))
    rng = np.random.RandomState(1)
    step = 172_000_000
    before = [(real[0][0] - (decoys - k) * step
               + int(rng.randint(-400_000, 400_000)),
               "decode" if k % 3 else "prefill") for k in range(decoys)]
    after = [(real[-1][0] + (k + 1) * step
              + int(rng.randint(-400_000, 400_000)),
              "decode" if k % 3 else "prefill") for k in range(decoys)]
    return [(t / 1e9, p) for t, p in before + real + after], marks


def test_clock_offset_is_recovered_from_marks_and_stamps():
    _, trace = _chat()
    stamps, marks = _stamps(trace)
    clock = hostspans.clock_offset(trace.host_marks, stamps)
    # the marks open 9 us after their stamps: that lag is in the offset
    assert clock["offset_ns"] == pytest.approx(OFFSET_NS + 9_000, abs=200)
    assert clock["matched"] == clock["marks"] == len(marks) == 4
    assert clock["residual_ns"] < 1_000 and clock["worst_ns"] < 1_000


def test_clock_offset_gives_up_where_the_marks_fit_no_stamps():
    _, trace = _chat()
    stamps, _ = _stamps(trace)
    wrong = [(t, "prefill") for t, _ in stamps]            # phases differ
    assert hostspans.clock_offset(trace.host_marks, wrong) is None
    stretched = [(t * 1.01, p) for t, p in stamps]          # durations differ
    assert hostspans.clock_offset(trace.host_marks, stretched) is None
    assert hostspans.clock_offset([], stamps) is None
    assert hostspans.clock_offset(trace.host_marks, stamps[:3]) is None


def _span(sid, parent, name, start, end, trace="gen-iter:1"):
    return {"trace": trace, "span": sid, "parent": parent, "name": name,
            "start_ns": start - OFFSET_NS, "end_ns": end - OFFSET_NS,
            "args": {}}


def _chat_spans(trace):
    """Two synthetic iterations over the recorded window (decode,
    tiny programs and a prefill chunk, decode), on perf_counter's
    clock: boundaries at round offsets from the programs' edges."""
    mods = {(e["name"].split("(")[0], e["start_ns"]): e
            for e in trace.events if e["line"] == tracered.MODULES_LINE}
    decode1, prefill, decode2 = (
        e for (name, _), e in sorted(mods.items(), key=lambda kv: kv[0][1])
        if name in ("jit__decode", "jit__prefill"))
    lo, hi = trace.window
    d1_end = decode1["start_ns"] + decode1["dur_ns"]
    p0 = prefill["start_ns"]
    return [
        # iteration 1: blocked on decode 1, delivers, prepares the chunk
        _span(1, None, "gen.iter", lo - 5_000_000, p0 + 300_000),
        _span(2, 1, "gen.admit", lo - 5_000_000, lo - 4_900_000),
        _span(3, 1, "gen.wait", lo - 4_900_000, d1_end + 150_000),
        _span(4, 1, "gen.deliver", d1_end + 150_000, d1_end + 900_000),
        _span(5, 1, "gen.prefill.prepare", d1_end + 1_000_000,
              p0 - 200_000),
        _span(6, 1, "gen.prefill.dispatch", p0 - 200_000, p0 + 250_000),
        # iteration 2, 50 us later: a wait nested in the delivery
        _span(7, None, "gen.iter", p0 + 350_000, hi + 3_000_000,
              "gen-iter:2"),
        _span(8, 7, "gen.deliver", p0 + 400_000, hi - 1_000_000,
              "gen-iter:2"),
        _span(9, 8, "gen.wait", p0 + 500_000, hi - 1_500_000, "gen-iter:2"),
    ]


def _raster(trace, spans, grid_ns=100):
    """Idle time by innermost span, by painting: the device's operations
    and then the spans, outermost first, onto a grid of ``grid_ns``
    cells. Shares no code with the interval arithmetic."""
    lo, hi = trace.window
    n = (hi - lo) // grid_ns
    busy = np.zeros(n, bool)
    for e in trace.events:
        if e["line"] == tracered.OPS_LINE:
            a = max(0, (e["start_ns"] - lo) // grid_ns)
            b = min(n, -(-(e["start_ns"] + e["dur_ns"] - lo) // grid_ns))
            busy[a:b] = True
    names = sorted({s["name"] for s in spans})
    label = np.full(n, -1, int)
    for s in sorted(spans, key=lambda s: s["end_ns"] - s["start_ns"],
                    reverse=True):
        a = max(0, (s["start_ns"] + OFFSET_NS - lo) // grid_ns)
        b = min(n, max(0, (s["end_ns"] + OFFSET_NS - lo) // grid_ns))
        label[a:b] = names.index(s["name"])
    out = {name: int(((label == i) & ~busy).sum()) * grid_ns
           for i, name in enumerate(names)}
    out["unattributed"] = int(((label == -1) & ~busy).sum()) * grid_ns
    return out, int((~busy).sum()) * grid_ns


def test_idle_time_goes_to_the_innermost_span_by_exact_intersection():
    _, trace = _chat()
    spans = _chat_spans(trace)
    gaps = tracered.subtract([trace.window], trace._busy(trace.planes[0]))
    got = hostspans.attribute(gaps, spans, OFFSET_NS)
    want, idle = _raster(trace, spans)
    assert sum(got.values()) == tracered.total(gaps)
    assert tracered.total(gaps) == pytest.approx(idle, rel=0.02)
    assert set(got) == {k for k, v in want.items() if v} | {"unattributed"}
    for name, ns in want.items():
        assert got.get(name, 0) == pytest.approx(ns, rel=0.02, abs=20_000), \
            name
    # the 50 us between the two iterations lie under no span
    assert got["unattributed"] <= 50_000
    # the gap before the chunk is the scheduler preparing it (PERF.md,
    # section 6: four tiny programs, the device idle about 5 ms)
    assert got["gen.prefill.prepare"] > 5_000_000 > got["gen.deliver"]
    # nested: the inner wait takes what it covers, its parent the rest
    assert got["gen.wait"] > 0 and got["gen.iter"] > 0


def _ctx(trace, spans, stamps):
    ctx = types.SimpleNamespace(
        trace=trace, window=(0.0, 1.0), facts={}, lines=[],
        spans={"steps": [(t, p, ()) for t, p in stamps]})
    ctx.info = lambda **doc: ctx.lines.append(doc)
    return ctx


def test_span_readers_split_the_idle_time_of_the_recorded_window(
        monkeypatch):
    _, trace = _chat()
    stamps, _ = _stamps(trace, lag_ns=0)
    spans = _chat_spans(trace)
    monkeypatch.setattr(hostspans, "loop_spans", lambda since: list(spans))
    ctx = _ctx(trace, spans, stamps)
    got = {r: core.load_module(core.reader_path(r + ".itl"),
                               "reader_" + r.replace(".", "_")).read(ctx)
           for r in SPAN_READERS}
    table = ctx.facts["host_spans"]
    # one gen.iter starts inside the window: per iteration is per 1
    assert table["iterations"] == 1
    idle_ms = (trace.window_s - trace.busy_s()) * 1e3
    assert sum(got.values()) + table["by_span"]["unattributed"] / 1e6 == \
        pytest.approx(idle_ms, rel=1e-6)
    assert got["device.idle_ms_per_iter.launch"] > \
        got["device.idle_ms_per_iter.deliver"] > 0
    # the table is worked out and printed once a run
    assert len(ctx.lines) == 1
    line = ctx.lines[0]["host_spans"]
    assert line["clock"]["residual_ns"] < 1_000
    assert "unattributed" in line["idle_s_by_span"]
    assert line["idle_s"] == pytest.approx(idle_ms / 1e3, rel=1e-6)


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    """What the parent commit gives: no ring, no phase histogram, no
    compile counter. No reader raises."""
    from horovod_tpu import metrics, tracing

    _, trace = _chat()
    stamps, _ = _stamps(trace)
    monkeypatch.delattr(tracing, "loop_spans")
    monkeypatch.setattr(metrics, "snapshot", lambda: {})
    ctx = _ctx(trace, [], stamps)
    ctx.counters_before = {}
    ctx.counters_after = {
        'hvd_tpu_gen_step_seconds{component="host"}':
            {"sum": 1.0, "count": 10}}
    ctx.histogram_mean = lambda series: None
    for r in SPAN_READERS + COUNTER_READERS:
        reader = core.load_module(core.reader_path(r + ".served"),
                                  "reader_" + r.replace(".", "_"))
        assert reader.read(ctx) is None, r
    for r in ("compile_cache.compile_s", "compile_cache.cache_misses"):
        reader = core.load_module(core.reader_path(r),
                                  "reader_" + r.replace(".", "_"))
        assert reader.read(ctx) is None, r
    assert ctx.lines == []


@pytest.mark.parametrize("cell,suffix", [
    ("gpt2-xl.chat_steady", ".itl"), ("gpt2-xl.doc_backlog", ".served")])
def test_rehearsal_reports_the_counters_and_leaves_the_span_metrics_out(
        cell, suffix, spec):
    proc = run_cell(ROOT, "--workload", cell, "--seed", "2147483659",
                    "--seconds", "3", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = set(last_line(proc)["metrics"])
    assert {r + suffix for r in COUNTER_READERS} <= got
    assert {"compile_cache.compile_s", "compile_cache.cache_misses"} <= got
    # a CPU trace has no TPU plane: nothing to attribute, and no error
    assert not {r + suffix for r in SPAN_READERS} & got
    listed = {m["name"] for m in spec["per_layer"]
              if "workloads" not in m or cell in m["workloads"]}
    assert {r + suffix for r in SPAN_READERS + COUNTER_READERS} <= listed


def test_benchmark_lists_the_new_metrics_at_the_end(spec):
    names = [m["name"] for m in spec["per_layer"]]
    new = names[-18:]
    assert new[-2:] == ["compile_cache.compile_s",
                        "compile_cache.cache_misses"]
    assert new[:16] == [r + s for r in COUNTER_READERS + SPAN_READERS
                        for s in (".itl", ".served")]
    for m in spec["per_layer"][-18:]:
        assert os.path.exists(core.reader_path(m["name"]))
        if m["name"].endswith((".itl", ".served")):
            assert m["moves"] == ("itl_p90_ms" if m["name"].endswith(".itl")
                                  else "served_tokens_per_s")
        else:
            assert m["moves"] == "setup_s" and "workloads" not in m
