#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic training throughput.

Prints JSON lines of the form
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
one per completed measurement stage, cheapest stage first, and always
re-prints the BEST result as the final line — so the last parseable JSON
line is the authoritative number no matter when the process is killed.

Mirrors the reference's synthetic benchmark
(/root/reference/examples/tensorflow2_synthetic_benchmark.py: ResNet-50;
docs/benchmarks.rst:66-85). ``vs_baseline`` is measured against the only
absolute throughput the reference publishes: docs/benchmarks.rst:27-43,
total images/sec 1656.82 on 16 Pascal GPUs => 103.55 img/s/GPU.

The benchmark needs an accelerator: a run whose default backend is the
CPU exits non-zero before measuring anything. ``--cpu`` runs a reduced
ladder on the CPU backend to check the machinery; its lines say
``"platform": "cpu"`` and are not device numbers. No run writes a tracked
file.

Robustness contract (a JSON line must appear well inside the driver's
kill window, whatever that window is):
  1. All heavy work runs in a KILLABLE WORKER SUBPROCESS. SIGALRM cannot
     interrupt a native XLA compile (Python only runs signal handlers
     between bytecodes), so in-process alarms around compilation are
     unreliable — a watchdog that kills a child process is not. The
     parent never imports jax: a chip belongs to one process, and a
     parent that had touched it would leave the worker none.
  2. The worker runs a cheapest-first ladder: stage 0 (batch 32, 1 warmup
     + 2 steps) prints a number seconds after the first compile, then
     escalation emits an improved JSON line after every stage.
     Same-batch stages share one compiled step
     (horovod_tpu.benchmark.synthetic_resnet50_ladder).
  3. The parent streams the worker's stdout, immediately passing on every
     JSON line, tracks the best value, enforces an overall wall-clock
     budget (HVD_TPU_BENCH_BUDGET, default 420 s) by killing the worker,
     and re-prints the best line at exit.
  4. SIGTERM/SIGINT on the parent kills the worker and still prints the
     best-so-far line before exiting.
  5. The exit code is non-zero when no accelerator answered, when any
     stage failed, and when no stage completed.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

REFERENCE_IMG_PER_SEC_PER_CHIP = 1656.82 / 16  # docs/benchmarks.rst:27-43

_T0 = time.time()
BUDGET_S = float(os.environ.get("HVD_TPU_BENCH_BUDGET", "420"))
DEADLINE = _T0 + BUDGET_S
# Stop escalating to a new stage when less than this remains: a fresh
# batch-size compile plus its measurement would not fit.
STAGE_MARGIN_S = float(os.environ.get("HVD_TPU_BENCH_STAGE_MARGIN", "100"))

#: worker exit code for "the default backend is the CPU and --cpu was not
#: given" — nothing was measured
EXIT_NO_ACCELERATOR = 3

_best = None          # best result dict seen so far (parent)
_child = None         # live worker Popen (parent)


def _log(msg):
    sys.stderr.write(f"[bench] {msg}\n")
    sys.stderr.flush()


def _emit(d):
    print(json.dumps(d))
    sys.stdout.flush()


def _emit_best_and_exit(signum=None, frame=None):
    global _child
    if _child is not None and _child.poll() is None:
        try:
            _child.kill()
        except Exception:
            pass
    if _best is not None:
        _emit(_best)
    os._exit(1)


def _result_json(r):
    # every line names the device it was measured on
    out = {
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": round(r.images_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "platform": r.platform,
        "device_kind": r.device_kind,
        "num_chips": r.num_chips,
        "vs_baseline": round(
            r.images_per_sec_per_chip / REFERENCE_IMG_PER_SEC_PER_CHIP, 3),
        "batch_per_chip": r.batch_per_chip,
        "total_images_per_sec": round(r.images_per_sec_total, 2),
        "flops_per_step": r.flops_per_step,
    }
    if r.mfu is not None:
        out["mfu"] = round(r.mfu, 4)
    if r.stem:
        # which ResNet stem produced this line
        out["stem"] = r.stem
    return out


# ---------------------------------------------------------------- worker

def worker_main(cpu: bool, batch_override=None):
    """Runs in the killable subprocess: ladder of stages, one JSON line per
    completed stage (improvements only), cheapest first."""
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    deadline = float(os.environ.get("HVD_TPU_BENCH_DEADLINE", time.time() + 300))

    import horovod_tpu as hvd
    from horovod_tpu.benchmark import synthetic_resnet50_ladder
    hvd.init()      # also places the compile cache
    import jax
    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu:
        _log("no accelerator: the default jax backend is the CPU. Nothing "
             "was measured; pass --cpu to check the machinery on the CPU.")
        return EXIT_NO_ACCELERATOR

    if cpu:
        stages = [
            dict(batch_per_chip=4, num_warmup_batches=1,
                 num_batches_per_iter=1, num_iters=2),
        ]
    elif batch_override:
        stages = [
            # quick line first, then the scanned full measurement
            dict(batch_per_chip=batch_override, num_warmup_batches=1,
                 num_batches_per_iter=2, num_iters=1),
            dict(batch_per_chip=batch_override, num_warmup_batches=5,
                 num_batches_per_iter=10, num_iters=10, scanned=True),
        ]
    else:
        stages = [
            # Stage 0: one compile, 3 steps — first JSON line lands seconds
            # after compilation finishes, whatever the driver's window is.
            dict(batch_per_chip=32, num_warmup_batches=1,
                 num_batches_per_iter=2, num_iters=1),
            # Stage 1: same compiled step, a quick honest measurement.
            dict(batch_per_chip=32, num_warmup_batches=2,
                 num_batches_per_iter=5, num_iters=2),
            # Stages 2-3: the MFU-bearing batch with the SCANNED k-step
            # program (one XLA call per timed iteration — no per-step
            # host dispatch in the measurement), re-printing improved
            # lines. Each costs a fresh compile. r4 measurements on a
            # live v5e: batch 32→1694, 64→1866, 128→2372, 256→2405 img/s
            # (mfu 0.21/0.23/0.28/0.30) — so the ladder jumps straight to
            # batch 256 and spends the next budget slot on the stem A/B
            # at that batch (r5: the A/B is the top open measurement; the
            # slot previously re-measured batch 128, a known-worse
            # point). 512 was probed and rejected: its compile alone
            # exceeds 420 s on v5e (HBM-pressure layout search), so it
            # can never pay for itself within the budget.
            dict(batch_per_chip=256, num_warmup_batches=5,
                 num_batches_per_iter=10, num_iters=10, scanned=True),
            # The math-equivalent space-to-depth stem (models/resnet.py
            # SpaceToDepthStem) at the same batch; best-line semantics
            # keep whichever stem wins.
            dict(batch_per_chip=256, num_warmup_batches=5,
                 num_batches_per_iter=10, num_iters=10, scanned=True,
                 stem="space_to_depth"),
            # Larger budgets only: the secondary batch point.
            dict(batch_per_chip=128, num_warmup_batches=5,
                 num_batches_per_iter=10, num_iters=10, scanned=True),
        ]

    best_v = -1.0
    failed = 0
    it = synthetic_resnet50_ladder(stages)
    prev_ok = False
    for i in range(len(stages)):
        # A stage reusing the previous stage's batch size reuses its
        # compiled step — only a fresh batch size (or a first scanned
        # stage, which compiles the k-step program) pays a compile, so
        # only those need the full margin. A FAILED previous stage drops
        # the rig (benchmark.py ladder semantics), so only a successful
        # same-shape predecessor earns the small margin.
        same_rig = prev_ok and i > 0 and (
            stages[i]["batch_per_chip"] == stages[i - 1]["batch_per_chip"]
            and stages[i].get("scanned") == stages[i - 1].get("scanned")
            and stages[i].get("stem") == stages[i - 1].get("stem"))
        margin = 30.0 if same_rig else STAGE_MARGIN_S
        if i > 0 and time.time() > deadline - margin:
            _log(f"worker: {deadline - time.time():.0f}s left < "
                 f"{margin:.0f}s margin; stopping after stage {i}")
            break
        t0 = time.time()
        try:
            r, err = next(it)
        except StopIteration:
            break
        if err is not None:
            # Per-stage failure (e.g. OOM at a larger batch); the ladder
            # stays alive for the remaining stages, the exit code records it.
            prev_ok = False
            failed += 1
            _log(f"worker stage {i + 1} ({stages[i]}) failed: "
                 f"{type(err).__name__}: {err}"[:1500])
            continue
        prev_ok = True
        _log(f"worker stage {i + 1}: batch={r.batch_per_chip} "
             f"{r.images_per_sec_per_chip:.1f} img/s/chip "
             f"in {time.time() - t0:.0f}s")
        if r.images_per_sec_per_chip > best_v:
            best_v = r.images_per_sec_per_chip
            _emit(_result_json(r))
    return 1 if failed or best_v < 0 else 0


# ---------------------------------------------------------------- parent

def _stream_worker(cmd, env):
    """Spawn the worker, pass on its JSON lines, update _best; kill at the
    deadline. Returns the worker's exit code (negative when killed)."""
    global _child, _best
    _child = subprocess.Popen(
        cmd, env=env, text=True, stdout=subprocess.PIPE,
        stderr=sys.stderr, bufsize=1)
    p = _child

    def _watchdog():
        while p.poll() is None:
            if time.time() > DEADLINE - 10:
                _log("budget exhausted; killing worker")
                try:
                    p.kill()
                except Exception:
                    pass
                return
            time.sleep(1)

    threading.Thread(target=_watchdog, daemon=True).start()

    for line in p.stdout:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        _emit(d)
        if _best is None or d.get("value", 0) > _best.get("value", 0):
            _best = d
    rc = p.wait()
    _child = None
    return rc


def main():
    batch = None
    cpu = False
    worker = False
    for a in sys.argv[1:]:
        if a == "--worker":
            worker = True
        elif a == "--cpu":
            cpu = True
        elif a.startswith("--batch="):
            batch = int(a.split("=", 1)[1])
    if worker:
        return worker_main(cpu, batch)

    signal.signal(signal.SIGTERM, _emit_best_and_exit)
    signal.signal(signal.SIGINT, _emit_best_and_exit)

    env = dict(os.environ)
    env["HVD_TPU_BENCH_DEADLINE"] = str(DEADLINE)
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    if cpu:
        cmd.append("--cpu")
    if batch:
        cmd.append(f"--batch={batch}")
    rc = _stream_worker(cmd, env)
    if _best is not None:
        _emit(_best)  # authoritative final line = best stage
    if rc == 0 and _best is None:
        rc = 1
    # a worker killed by signal reports a negative code
    return rc if rc >= 0 else 1


if __name__ == "__main__":
    sys.exit(main())
