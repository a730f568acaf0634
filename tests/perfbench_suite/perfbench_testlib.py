"""Helpers of the benchmark's tests: run the benchmark's command."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, *args, timeout=420):
    """The benchmark's command in ``root`` with a clean jax environment
    (the suite's own XLA_FLAGS ask for 8 devices)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    # one compute thread: the sizes are tiny, and the suite's timing-
    # sensitive tests run beside these processes
    env["XLA_FLAGS"] = "--xla_cpu_multi_thread_eigen=false"
    return subprocess.run([sys.executable] + command[1:] + list(args),
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=timeout)


def last_line(proc):
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    assert lines, f"no output; stderr: {proc.stderr[-2000:]}"
    return json.loads(lines[-1])
