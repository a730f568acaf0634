#!/usr/bin/env python3
"""Find the highest open-loop rate a serving cell sustains: one process,
one engine, one window per rate.

    python3 perfbench/tools/sweep.py --workload gpt2-xl.chat_steady \
        --rates 0.8,1.0,1.2,1.4 --seconds 40 [--life-s 26]

For each rate the lanes are preloaded with ``rate * life`` requests, the
window runs, and what is in flight is ended. A rate is sustained when the
time to first token does not climb from the first half of the window to
the second and the requests in flight stay under the lanes. The cell's
traffic file then carries four fifths of the knee as a number; the
benchmark itself never searches.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    from perfbench.harness import core, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--life-s", type=float, default=26.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    spec = core.load_spec()
    workload = next(w for w in spec["workloads"]
                    if w["name"] == args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = core.Context(spec, workload, args.seed, args.seconds, 0,
                       args.rehearse, time.perf_counter())
    ctx.claim_devices()
    ctx.setup_compile_cache()
    runner = ctx.load_runner()
    server = runner.Server(ctx)
    lanes = ctx.config["engine"]["max_seqs"]
    try:
        for rate in [float(x) for x in args.rates.split(",")]:
            count = max(1, min(lanes, round(rate * args.life_s)))
            tr = core.merged(ctx.traffic, {
                "rate_per_s": rate, "preload": {"count": count}})
            ctx.end_to_end.clear()
            outcome = runner.measure(ctx, server, tr)
            t0, t1 = ctx.window
            mid = (t0 + t1) / 2
            recs = [r for r in ctx.facts["records"] if r.kind == "open"]
            ttft = [(r.due, r.token_times[0] - r.due) for r in recs
                    if r.token_times]
            halves = [[w for d, w in ttft if lo <= d < hi]
                      for lo, hi in ((t0, mid), (mid, t1))]
            unanswered = sum(1 for r in recs if not r.token_times)
            in_flight_end = sum(
                1 for r in ctx.facts["records"]
                if r.sent is not None and r.sent <= t1
                and (r.done is None or r.done > t1))
            occ = ctx.histogram_mean("hvd_tpu_gen_batch_occupancy")
            print(json.dumps({
                "rate_per_s": rate, "preloaded": count,
                "sent": len(recs), "unanswered_at_end": unanswered,
                "in_flight_at_end": in_flight_end,
                "ttft_p50_ms_halves": [
                    stats.percentile(h, 50) * 1e3 if h else None
                    for h in halves],
                "ttft_p90_ms": stats.percentile(
                    [w for _, w in ttft], 90) * 1e3 if ttft else None,
                "itl_p50_ms": None if "itl_p90_ms" not in ctx.end_to_end
                else stats.percentile(stats.token_gaps(
                    [r.token_times for r in ctx.facts["records"]],
                    t0, t1), 50) * 1e3,
                "itl_p90_ms": ctx.end_to_end.get("itl_p90_ms"),
                "occupancy_mean": occ,
                "pool_peak_share": 100.0 * ctx.facts["pool_in_use_peak"]
                / ctx.facts["pool_blocks"],
                "preemptions": ctx.counter_delta(
                    "hvd_tpu_gen_preemptions_total"),
                "outcome": outcome,
                "platform": ctx.devices[0].platform}), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
