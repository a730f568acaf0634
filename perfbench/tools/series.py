#!/usr/bin/env python3
"""Run one cell several times, each run a new process with another seed,
and print each metric's median and spread as the contract measures it:
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 perfbench/tools/series.py --workload <name> --runs 6 --sets 2 \
        [--seconds S] [--trace-runs 1] [--skip-seeds K] [--out <file>.jsonl]

Sets use the same seeds. The parent never touches jax: a chip belongs to
one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
#: large seeds, as the driver's are (beyond 32 signed bits among them)
SEEDS = (2147483659, 1234567891, 987654321, 2147480011, 1500450271,
         3000000019, 104729, 2038074743)


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one(workload, seed, seconds, trace, extra):
    cmd = [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
           "--trace", str(trace)] + extra
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1500)
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    doc = {"rc": proc.returncode, "wall_s": time.time() - t0, "seed": seed,
           "trace": trace}
    try:
        doc["result"] = json.loads(lines[-1])
        doc["info"] = [json.loads(x)["info"] for x in lines[:-1]
                       if x.startswith('{"info"')]
    except (IndexError, ValueError):
        doc["stdout_tail"] = proc.stdout[-2000:]
    if proc.returncode != 0 or "result" not in doc:
        doc["stderr_tail"] = proc.stderr[-3000:]
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-runs", type=int, default=0)
    ap.add_argument("--skip-seeds", type=int, default=0,
                    help="start at this index of SEEDS (to extend a set)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    extra = ["--rehearse"] if args.rehearse else []
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        out = open(args.out, "a")

    def emit(doc):
        doc["workload"] = args.workload
        text = json.dumps(doc)
        print(text[:6000], flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    for i in range(args.trace_runs):
        emit(one(args.workload, SEEDS[i % len(SEEDS)], args.seconds, 1,
                 extra))
    sets = []
    for s in range(args.sets):
        docs = []
        for i in range(args.runs):
            doc = one(args.workload,
                      SEEDS[(i + args.skip_seeds) % len(SEEDS)],
                      args.seconds, 0, extra)
            doc["set"] = s
            emit(doc)
            docs.append(doc)
        sets.append(docs)
    summary = {"summary": args.workload, "metrics": {}}
    names = sorted({k for docs in sets for d in docs
                    for k in d.get("result", {}).get("metrics", {})})
    for name in names:
        per_set = []
        for s, docs in enumerate(sets):
            vals = [d["result"]["metrics"][name]["value"] for d in docs
                    if "result" in d and name in d["result"]["metrics"]
                    and d["result"]["metrics"][name]["value"] is not None]
            if name == "setup_s" and s == 0 and not args.skip_seeds:
                vals = vals[1:]            # the first run compiles
            if len(vals) >= 2:
                per_set.append({"n": len(vals),
                                "median": statistics.median(vals),
                                "spread": spread(vals),
                                "values": vals})
        summary["metrics"][name] = per_set
    emit(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
