#!/usr/bin/env python3
"""What the limits of ``serve_olmo_hybrid.compare`` lie between.

    python3 perfbench/tools/olmo_tolerance.py [--seeds N,N] [--faults a,b]
                                              [--rehearse]

For each seed, the runner's own check (weights from the seed, the engine
at the configuration's knobs, the greedy requests, the two-turn session
and the sampled batch, served and compared with the float32 reference)
clean and with one fault at a time:

* ``state_bf16``: the recurrent state held in bfloat16 (the nearest
  precision below the float32 the configuration states);
* ``no_restore``: a prefix hit attaches its blocks and the state slot is
  restored from the null snapshot (zeros) where the block's own belonged.

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

CELL = "olmo-hybrid.session_turns"


def _skip_restore(engine):
    """The fault is silent: the snapshot still counts as restored, so
    only what the served tokens read against the reference can tell."""
    from horovod_tpu.serving.generation import kv_cache

    batcher = engine.batcher
    restore = batcher._restore_state

    def zeros_instead(slot, snapshot):
        restore(slot, 0)
        if snapshot:
            kv_cache.count_snapshots("restored", 1, 0)

    batcher._restore_state = zeros_instead


FAULTS = {
    "clean": dict(),
    "state_bf16": dict(state_dtype="bfloat16"),
    "no_restore": dict(before_check=_skip_restore),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="3000000019")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from perfbench.harness import core

    spec = core.load_spec(ROOT)
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in args.faults.split(","):
            ctx = core.Context(spec, workload, seed, 1.0, 0, args.rehearse,
                               time.perf_counter())
            ctx.claim_devices()
            ctx.setup_compile_cache()
            runner = ctx.load_runner()
            kw = dict(FAULTS[fault])
            if "state_dtype" in kw:
                import jax.numpy as jnp
                kw["state_dtype"] = jnp.dtype(kw["state_dtype"])
            server = runner.Server(ctx, **kw)
            doc = {"seed": seed, "fault": fault, "ok": server.checked,
                   **server.numbers}
            server.close()
            del server
            gc.collect()
            print(json.dumps({"reading": doc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
