#!/usr/bin/env python3
"""What the limits of ``serve_glm47_flash.compare`` lie between.

    python3 perfbench/tools/glm47_flash_tolerance.py [--seeds N,N]
                                                     [--faults a,b]
                                                     [--rehearse]

For each seed, the runner's own check (weights from the seed, made to
draft; the engine at the configuration's knobs, ``spec_mode='mtp'``;
the greedy requests of ``check_sample`` with every draft the engine's
programs returned, and the sampled batch, served once) compared with
the float32 reference clean and with the reference computing one part
wrongly at a time (``perfbench/reference/glm47_flash.py``, ``FAULTS``;
the comparison is symmetric, so a fault in the reference reads as the
same fault in the program would):

- ``activations_float8``: the residual stream and what each sublayer
  reads rounded to float8, the nearest precision below the bfloat16 the
  configuration states: ``logprob_rms`` and ``draft_gap_mean`` have to
  catch it;
- ``mtp_float8``: the same inside the MTP module alone:
  ``draft_gap_mean`` has to catch it;
- ``mtp_no_hidden``: an MTP module that leaves the main model's hidden
  state out: ``draft_gap_mean`` has to catch it.

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

CELL = "glm47-flash.reasoning_mtp"


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
                "setup_marks": ctx.setup_marks, **server.numbers}}),
                flush=True)
            faults = args.faults.split(",") if args.faults \
                else list(server.plain.FAULTS)
            for fault in faults:
                t = time.perf_counter()
                ok, numbers = runner.compare(
                    server.served, server.params, server.plain, ctx.config,
                    faults=(fault,))
                print(json.dumps({"reading": {
                    "seed": seed, "fault": fault, "ok": ok,
                    "compare_s": round(time.perf_counter() - t, 1),
                    **numbers}}), flush=True)
        finally:
            server.close()
            del server
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
