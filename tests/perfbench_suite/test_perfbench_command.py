"""The benchmark's command itself, rehearsed at tiny sizes on the CPU."""

import json
import os
import shutil

import pytest

from perfbench_testlib import RESULT_KEYS, ROOT, last_line, run_cell


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_cell_runs_end_to_end_and_prints_the_result_line(cell, spec):
    """Every traffic kind through the same command (open loop, closed
    loop, training on one device and on four): the last line has the
    contract's keys, and the cell's end-to-end metrics."""
    proc = run_cell(ROOT, "--workload", cell, "--seed", "3000000019",
                    "--seconds", "3", "--trace", "0", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = last_line(proc)
    assert RESULT_KEYS <= set(doc) and "breakdown" not in doc
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 0
    want = {m["name"] for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(doc["metrics"]) == want and "setup_s" in want
    assert all(set(m) == {"value", "unit"} for m in doc["metrics"].values())
    # a CPU run proves the path and reports no value
    assert doc["rehearsal"] is True
    assert all(m["value"] is None for m in doc["metrics"].values())
    chips = next(w["chips"] for w in spec["workloads"] if w["name"] == cell)
    assert doc["device"]["platform"] == "cpu"
    assert doc["device"]["count"] == chips


def test_traced_run_reports_per_layer_metrics_and_a_breakdown(spec):
    cell = "gpt2-xl.doc_backlog"
    proc = run_cell(ROOT, "--workload", cell, "--seed", "11",
                    "--seconds", "3", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = last_line(proc)
    assert RESULT_KEYS | {"breakdown"} <= set(doc)
    assert set(doc["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(doc["device"])
    allowed = {m["name"] for m in spec["per_layer"]
               if "workloads" not in m or cell in m["workloads"]}
    got = set(doc["metrics"])
    assert got <= allowed
    # counters, spans and the host clock are there without a device; a
    # reader that finds no device trace returns nothing and is left out
    assert {"scheduler.host_ms_per_iter.served",
            "kv_cache.pool_in_use_peak_share.served",
            "service.ttft_p50_ms.served",
            "compile_cache.compiles_in_window"} <= got
    assert "programs.decode_step_ms.served" not in got


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = run_cell(ROOT, "--workload", "resnet50.synthetic_b256",
                    "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "measures the chip" in proc.stderr


def test_with_fewer_chips_than_the_cell_asks_for_it_fails(tmp_path):
    """One CPU device, a cell that asks for four."""
    from perfbench.harness import core

    spec = core.load_spec()
    work = next(w for w in spec["workloads"] if w["chips"] == 4)
    ctx = core.Context(spec, work, 1, 1.0, 0, True, 0.0)
    ctx.chips = 1000
    with pytest.raises(core.NoAccelerator, match="needs 1000 chips"):
        ctx.claim_devices()


def test_alone_without_the_program_the_command_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no system to measure."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(str(tmp_path), "--workload", "resnet50.synthetic_b256",
                    "--seed", "1", "--seconds", "1", "--trace", "0",
                    "--rehearse")
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
