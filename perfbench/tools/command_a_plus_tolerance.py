#!/usr/bin/env python3
"""What the limits of ``serve_command_a_plus.compare`` lie between.

    python3 perfbench/tools/command_a_plus_tolerance.py [--seeds N,N]
                                                        [--faults a,b]
                                                        [--rehearse]

For each seed, the runner's own check (weights from the seed, the engine
at the configuration's knobs, the greedy requests up to four windows
deep and the sampled batch, served once) compared with the float32
reference clean and with the reference computing one part wrongly at a
time (``perfbench/reference/command_a_plus.py``, ``FAULTS``: the
comparison is symmetric, so a fault in the reference reads as the same
fault in the program would): the router, every softmax or the norms in
bfloat16 (the nearest precision below the float32 the configuration
states), a window of 4096 - 64, rotary on the full layers, rotary by
contiguous halves in place of interleaved pairs.

One JSON line a reading. A fault that the limits do not catch prints
``"ok": true``: the limits then need another look, not the fault.
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

CELL = "command-a-plus.mixed_lengths"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="3000000019")
    ap.add_argument("--faults", default=None,
                    help="of the reference's FAULTS; default all")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from perfbench.harness import core

    spec = core.load_spec(ROOT)
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = core.Context(spec, workload, seed, 1.0, 0, args.rehearse,
                           time.perf_counter())
        ctx.claim_devices()
        ctx.setup_compile_cache()
        runner = ctx.load_runner()
        server = runner.Server(ctx)
        try:
            print(json.dumps({"reading": {
                "seed": seed, "fault": "clean", "ok": server.checked,
                **server.numbers}}), flush=True)
            faults = args.faults.split(",") if args.faults \
                else list(server.plain.FAULTS)
            for fault in faults:
                ok, numbers = runner.compare(
                    server.served, server.params, server.plain, ctx.config,
                    faults=(fault,))
                print(json.dumps({"reading": {
                    "seed": seed, "fault": fault, "ok": ok, **numbers}}),
                    flush=True)
        finally:
            server.close()
            del server
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
