"""Unit tests for the headline benchmark harness (bench.py).

The rules pinned here: the benchmark needs an accelerator unless ``--cpu``
is given, the parent stays off jax (a chip belongs to one process), every
result line names its device, a failed stage fails the run, and nothing
writes a tracked file. The worker subprocess and the stage ladder are
faked — no accelerator needed.
"""

import json
import os
import subprocess
import sys
import types

import pytest


@pytest.fixture
def bench(monkeypatch):
    import bench as b
    monkeypatch.setattr(b, "_best", None)
    monkeypatch.setattr(b.signal, "signal", lambda *a: None)
    return b


@pytest.fixture
def no_world():
    """worker_main() calls hvd.init(); leave no world behind."""
    import horovod_tpu as hvd
    if hvd.is_initialized():
        hvd.shutdown()
    yield
    hvd.shutdown()


def _result(value=2000.0, **kw):
    fields = dict(
        images_per_sec_per_chip=value, images_per_sec_total=value,
        num_chips=1, batch_per_chip=128, platform="tpu",
        device_kind="TPU v5 lite", mfu=0.28, flops_per_step=3.06e12,
        stem="conv")
    fields.update(kw)
    return types.SimpleNamespace(**fields)


def _fake_worker(monkeypatch, bench, lines, returncode):
    """Replace the worker subprocess: it 'prints' ``lines`` and exits with
    ``returncode``. Returns the list that records the spawned commands."""
    spawned = []

    class FakePopen:
        def __init__(self, cmd, **kw):
            spawned.append(cmd)
            self.stdout = iter(line + "\n" for line in lines)

        def poll(self):
            return returncode

        def wait(self):
            return returncode

        def kill(self):
            pass

    monkeypatch.setattr(bench.subprocess, "Popen", FakePopen)
    return spawned


def _stdout_json(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_worker_without_accelerator_measures_nothing(
        bench, no_world, monkeypatch, capsys):
    """The no-chip rule: on a CPU default backend, without --cpu, the
    worker exits non-zero before building a single stage."""
    import horovod_tpu.benchmark as hb

    def must_not_run(stages):
        raise AssertionError("a stage ran without an accelerator")

    monkeypatch.setattr(hb, "synthetic_resnet50_ladder", must_not_run)
    assert bench.worker_main(cpu=False) == bench.EXIT_NO_ACCELERATOR
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err


def test_parent_passes_on_the_no_accelerator_exit(bench, monkeypatch, capsys):
    _fake_worker(monkeypatch, bench, [], bench.EXIT_NO_ACCELERATOR)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == bench.EXIT_NO_ACCELERATOR
    assert capsys.readouterr().out == ""


def test_parent_stays_off_jax(bench):
    """A parent that had touched jax would hold the chip its worker
    needs. Importing bench and walking main()'s parent path up to the
    spawn must not import jax or the package."""
    code = ("import sys, bench; "
            "bench.subprocess.Popen = lambda *a, **k: sys.exit("
            "int('jax' in sys.modules or 'horovod_tpu' in sys.modules)); "
            "sys.argv = ['bench.py']; bench.main()")
    r = subprocess.run([sys.executable, "-c", code], timeout=60,
                       cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert r.returncode == 0


def test_no_code_path_writes_the_tracked_record(bench):
    with open(bench.__file__) as f:
        assert "BENCH_TPU_LAST" not in f.read()
    assert not hasattr(bench, "_persist_tpu_best")


def test_result_json_carries_mfu(bench):
    out = bench._result_json(_result())
    assert out["mfu"] == 0.28
    assert out["flops_per_step"] == 3.06e12
    assert out["vs_baseline"] == pytest.approx(
        2000.0 / (1656.82 / 16), rel=1e-3)


def test_result_json_names_the_device(bench):
    out = bench._result_json(_result())
    assert (out["platform"], out["device_kind"], out["num_chips"]) == \
        ("tpu", "TPU v5 lite", 1)
    cpu = bench._result_json(_result(12.0, platform="cpu", device_kind="cpu",
                                     mfu=None))
    assert cpu["platform"] == "cpu"
    assert "mfu" not in cpu


def test_failed_stage_makes_the_worker_exit_nonzero(
        bench, no_world, monkeypatch, capsys):
    """One stage completes, the next fails: the completed line is printed
    and the exit code still records the failure."""
    import jax

    import horovod_tpu as hvd
    import horovod_tpu.benchmark as hb
    hvd.init()      # the real world first; then pose as a chip
    monkeypatch.setattr(
        jax, "devices", lambda: [types.SimpleNamespace(platform="tpu")])
    monkeypatch.setattr(
        hb, "synthetic_resnet50_ladder",
        lambda stages: iter([(_result(1694.0), None),
                             (None, RuntimeError("out of memory"))]))
    monkeypatch.setenv("HVD_TPU_BENCH_DEADLINE", "1e12")
    assert bench.worker_main(cpu=False) == 1
    captured = capsys.readouterr()
    (line,) = [json.loads(x) for x in captured.out.splitlines()]
    assert line["value"] == 1694.0
    assert "out of memory" in captured.err


def test_no_completed_stage_is_a_failure(
        bench, no_world, monkeypatch, capsys):
    import horovod_tpu.benchmark as hb
    monkeypatch.setattr(
        hb, "synthetic_resnet50_ladder",
        lambda stages: iter([(None, RuntimeError("out of memory"))]))
    monkeypatch.setenv("HVD_TPU_BENCH_DEADLINE", "1e12")
    assert bench.worker_main(cpu=True) == 1
    assert capsys.readouterr().out == ""


def test_completed_stage_is_printed_and_exits_zero(
        bench, no_world, monkeypatch, capsys):
    import horovod_tpu.benchmark as hb
    monkeypatch.setattr(
        hb, "synthetic_resnet50_ladder",
        lambda stages: iter([(_result(12.0, platform="cpu",
                                      device_kind="cpu", mfu=None), None)]))
    monkeypatch.setenv("HVD_TPU_BENCH_DEADLINE", "1e12")
    assert bench.worker_main(cpu=True) == 0
    (line,) = _stdout_json(capsys)
    assert line["value"] == 12.0 and line["platform"] == "cpu"


def test_parent_reprints_the_best_line_last(bench, monkeypatch, capsys):
    lines = [json.dumps({"value": v, "platform": "tpu"})
             for v in (1694.0, 2405.0, 2372.0)]
    spawned = _fake_worker(monkeypatch, bench, lines, 0)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--batch=256"])
    assert bench.main() == 0
    out = _stdout_json(capsys)
    assert [d["value"] for d in out] == [1694.0, 2405.0, 2372.0, 2405.0]
    assert spawned[0][-2:] == ["--worker", "--batch=256"]


def test_worker_killed_or_empty_is_a_failure(bench, monkeypatch, capsys):
    _fake_worker(monkeypatch, bench, [], -9)      # killed at the deadline
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 1
    _fake_worker(monkeypatch, bench, [], 0)       # exited 0, measured nothing
    assert bench.main() == 1
    assert capsys.readouterr().out == ""


def test_cpu_flag_reaches_the_worker(bench, monkeypatch, capsys):
    spawned = _fake_worker(
        monkeypatch, bench,
        [json.dumps({"value": 0.5, "platform": "cpu"})], 0)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--cpu"])
    assert bench.main() == 0
    assert "--cpu" in spawned[0]
    assert _stdout_json(capsys)[-1]["platform"] == "cpu"
