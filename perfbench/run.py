#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, weights from the seed, compilation or the compile
cache, warm-up, the ``correct`` check) is counted from the start of the
process to the first instant of the measured window. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. Without a
TPU, or with fewer chips than the cell asks for, the exit code is 3 and
no result is printed. ``--rehearse`` is for the tests: it runs the cell's
``rehearsal`` sizes on the CPU, proves the path, and reports no value.
"""

import time

_IMPORTED_AT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print the cells and exit")
    args = ap.parse_args(argv)

    from perfbench.harness import core

    started_at = _IMPORTED_AT - core.process_age_s()
    spec = core.load_spec(ROOT)
    if args.list:
        for w in spec["workloads"]:
            print(json.dumps(w))
        return 0
    workload = next((w for w in spec["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        sys.stderr.write(f"perfbench: no cell named {args.workload!r} in "
                         f"BENCHMARK.json\n")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        sys.stderr.write("perfbench: the system under test (horovod_tpu/) "
                         "is not in this checkout\n")
        return 3
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={workload['chips']}")
    seconds = spec["run_seconds"] if args.seconds is None else args.seconds
    ctx = core.Context(spec, workload, args.seed, seconds, args.trace,
                       args.rehearse, started_at)
    try:
        ctx.claim_devices()
    except core.NoAccelerator as e:
        sys.stderr.write(f"perfbench: {e}\n")
        return 3
    ctx.setup_compile_cache()
    ctx.mark("devices")
    outcome = ctx.load_runner().run(ctx)
    ctx.info(setup_marks=ctx.setup_marks)
    line = core.result_line(ctx, outcome)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
