"""The reduction from a profiler trace to numbers: on hand-made events,
where every answer can be worked out by eye, and on a small trace
recorded on the v5e and kept beside this file."""

import os
import re

import pytest

from perfbench.harness import tracered
from perfbench.harness.tracered import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1000, "dur_ns": dur_us * 1000}


def op(name, start_us, dur_us, plane=DEV0):
    return ev(plane, tracered.OPS_LINE, name, start_us, dur_us)


def module(name, start_us, dur_us, plane=DEV0):
    return ev(plane, tracered.MODULES_LINE, name, start_us, dur_us)


def mark(name, start_us, dur_us):
    return ev("/host:CPU", "python3", tracered.HOST_MARK + name, start_us,
              dur_us)


def test_interval_arithmetic():
    assert tracered.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert tracered.total([(0, 3), (5, 8)]) == 6
    assert tracered.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tracered.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tracered.subtract([(0, 4)], []) == [(0, 4)]
    assert tracered.subtract([(2, 3)], [(0, 10)]) == []
    assert tracered.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == \
        [(3, 5), (8, 10)]


def test_op_names_are_stable_and_legal():
    assert tracered.op_name("%fusion.123") == "fusion"
    assert tracered.op_name("copy.5") == "copy"
    assert tracered.op_name(
        "%copy.12 = bf16[1,576,16,25,64]{4,3,2,1,0:T(8,128)(2,1)} copy(x)") \
        == "copy_bf16_1_576_16_25_64_"
    assert tracered.op_name("all-reduce-start.7") == "all-reduce-start"
    name = tracered.op_name("%very.long = f32[" + "9," * 80 + "9]{0} x()")
    assert len(name) <= 64 and " " not in name and "," not in name


def _two_steps():
    """A window of 1000 us on two chips. Chip 0: two programs of 300 us
    with a 200 us gap between them; chip 1: the same, 10 us later."""
    events = [mark("window", 0, 1000)]
    for plane, shift in ((DEV0, 0), (DEV1, 10)):
        for k, base in enumerate((100, 600)):
            b = base + shift
            events += [
                module(f"jit__step({k})", b, 300, plane),
                op("%fusion.1", b, 100, plane),
                op("%all-reduce.3", b + 100, 80, plane),   # 50 us alone
                op("%convolution.2", b + 150, 150, plane),
            ]
    events += [mark("train.dispatch", 80, 30), mark("train.wait", 400, 190),
               mark("train.wait", 900, 100)]
    return events


def test_busy_idle_and_programs_on_a_hand_made_trace():
    t = Trace(_two_steps())
    assert t.planes == [DEV0, DEV1]
    assert t.window_s == pytest.approx(1000e-6)
    assert t.busy_s() == pytest.approx(600e-6)
    assert t.idle_share() == pytest.approx(0.4)
    assert t.program_ms(r"jit__step") == pytest.approx(0.3)
    assert t.program_count(r"jit__step") == 2
    assert t.program_ms(r"jit__decode") is None


def test_exposed_collective_time_is_what_nothing_else_covers():
    t = Trace(_two_steps())
    # each all-reduce runs 80 us, of which the last 30 overlap the
    # convolution: 50 us exposed, twice a chip
    assert t.exposed_collective_s() == pytest.approx(100e-6)


def test_top_operations_and_idle_gaps_by_what_the_host_did():
    t = Trace(_two_steps())
    ops = dict(t.top_ops(10))
    assert list(ops)[0] == "convolution"
    assert ops["convolution"] == pytest.approx(300e-6)
    assert ops["all-reduce"] == pytest.approx(160e-6)
    gaps = dict(t.idle_gaps(10))
    # chip 0 idles 0-100, 400-600, 900-1000
    assert gaps["bench.train.wait"] == pytest.approx(300e-6)
    assert gaps["bench.train.dispatch"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(400e-6)


def test_a_trace_without_a_device_plane_reads_as_nothing():
    t = Trace([mark("window", 0, 1000)])
    assert t.planes == [] and t.busy_s() == 0.0
    assert t.idle_share() is None and t.top_ops() == [] \
        and t.idle_gaps() == [] and t.exposed_collective_s() is None


def test_events_round_trip(tmp_path):
    path = str(tmp_path / "e.json.gz")
    tracered.save_events(_two_steps(), path)
    assert tracered.load_events(path) == _two_steps()


# -- traces recorded on the TPU v5e (my chip run, PR 24), cut to a few
#    programs with perfbench's own event dump (PERFBENCH_KEEP_EVENTS) ------

def _raster_busy_s(events, window, grid_ns=100):
    """Busy time by painting every operation onto a grid: slow, simple,
    and sharing no code with the reducer's interval arithmetic."""
    import numpy as np

    lo, hi = window
    cells = np.zeros((hi - lo) // grid_ns + 1, bool)
    for e in events:
        if e["line"] == tracered.OPS_LINE:
            a = max(0, (e["start_ns"] - lo) // grid_ns)
            b = min(len(cells), -(-(e["start_ns"] + e["dur_ns"] - lo)
                                  // grid_ns))
            cells[a:b] = True
    return cells.sum() * grid_ns / 1e9


def test_recorded_resnet50_steps():
    events = tracered.load_events(
        os.path.join(DATA, "v5e_resnet50_two_steps.json.gz"))
    t = Trace(events)
    assert t.planes == [DEV0]
    mods = [e for e in events if e["line"] == tracered.MODULES_LINE]
    assert len(mods) == 2 and t.program_count(r"jit__step") == 2
    assert t.program_ms(r"jit__step") == pytest.approx(
        sum(m["dur_ns"] for m in mods) / 2 / 1e6)
    assert 98.0 < t.program_ms(r"jit__step") < 99.0
    assert t.window_s == pytest.approx(0.201092308)
    assert t.busy_s() == pytest.approx(
        _raster_busy_s(events, t.window), rel=2e-3)
    assert 0.0 < t.idle_share() < 0.01
    ops = t.top_ops(10)
    assert len(ops) == 10 and ops[0][1] >= ops[1][1] >= ops[-1][1]
    assert all(re.match(r"^[A-Za-z0-9_.\-]{1,64}$", n) for n, _ in ops)
    assert ops[0][0] == "convert_reduce_fusion__f32_256_"
    gaps = dict(t.idle_gaps())
    assert gaps["bench.train.wait"] == pytest.approx(
        t.window_s - t.busy_s(), rel=0.01)       # the 2 ms margins
    assert t.exposed_collective_s() == 0.0       # one chip: none


def test_recorded_chat_iterations():
    """decode, prefill chunk, decode of gpt2-xl.chat_steady, with the
    tiny programs the scheduler runs between them."""
    events = tracered.load_events(
        os.path.join(DATA, "v5e_gpt2xl_chat_decode_prefill_decode.json.gz"))
    t = Trace(events)
    assert t.program_count(r"jit__decode") == 2
    assert t.program_count(r"jit__prefill") == 1
    assert 170.0 < t.program_ms(r"jit__decode") < 174.0
    assert 73.0 < t.program_ms(r"jit__prefill") < 75.0
    assert t.busy_s() == pytest.approx(
        _raster_busy_s(events, t.window), rel=2e-3)
    assert 0.02 < t.idle_share() < 0.05
    names = [n for n, _ in t.top_ops(10)]
    # the whole-layer pool copy and the whole-table gather (PERF.md §5)
    assert names[0] == "copy_bf16_1_576_16_25_64_"
    assert "fusion_bf16_2048_16_25_64_" in names
    gaps = dict(t.idle_gaps())
    assert set(gaps) <= {"bench.sched.after_decode",
                         "bench.sched.after_prefill", "unannotated"}
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())
    assert gaps["bench.sched.after_decode"] > \
        gaps["bench.sched.after_prefill"]
