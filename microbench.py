#!/usr/bin/env python
"""Collective-plane microbenchmark driver (VERDICT r3 item 2).

Runs nine sections, each in killable CPU subprocesses, and writes
``MICROBENCH.json``:

1. ``eager_1proc``  — payload sweep of the eager plane with one process:
   pure dispatch + staging overhead (no cross-process communication).
2. ``eager_2proc``  — the same sweep across 2 processes rendezvousing
   through the JAX distributed coordinator (the launcher's env contract):
   bytes/sec of eager allreduce / grouped_allreduce, async dispatch
   latency, and the ratio vs an in-jit reduction of the same pre-staged
   payload.
3. ``scaling``      — compiled-plane DP train step under 1/2/4/8 virtual
   CPU devices (``--xla_force_host_platform_device_count``), reporting
   throughput and efficiency = T(n)/(n*T(1)). Virtual CPU devices share
   host cores, so this validates the measurement machinery rather than
   claiming performance — the real-pod run reuses exactly this path.
4. ``injit``        — the compiled-plane fast path (docs/injit.md) on the
   ResNet-50 161-gradient scenario under 1/2/8 virtual devices: per-leaf
   vs packed vs packed+bf16 vs packed+int8 DistributedOptimizer
   reduction, with analytic wire bytes per variant. Each row carries the
   same-scale eager bucketed time (section 1/2) so the eager-vs-compiled
   gap for the REAL optimizer payload is a single recorded number.
5. ``generation``   — continuous batching vs static full-batch
   generation (docs/inference.md) on a mixed-length prompt workload,
   both modes driving the same compiled paged prefill/decode programs:
   useful tokens/sec and peak KV bytes (allocator high-water vs the
   static max-length reservation). Plus ``generation_sampling``: the
   device-resident loop's on-device sampling modes (greedy vs seeded
   temperature/top-k/top-p) under sync vs ``ASYNC_DEPTH=1`` stepping,
   with tokens/sec and the host/device ms-per-step split from
   ``hvd_tpu_gen_step_seconds``. Plus ``generation_prefix``: automatic
   prefix caching on a shared-64-token-system-prompt workload, cache
   on vs off over the same compiled programs (outputs asserted
   identical), reporting tokens/sec, prefilled tokens, and the cache
   hit/miss/eviction counters. Plus ``generation_spec``: n-gram
   speculative decoding vs plain decode on the single-stream latency
   rig, a repetitive (high-accept) vs random (low-accept) workload
   pair with outputs asserted bit-identical across spec on/off — the
   repetitive-workload speedup and accept rate are the acceptance
   numbers.
6. ``sdc``          — SDC defense-plane overhead (docs/robustness.md)
   on the ResNet-50 161-gradient scenario: a jit'd update plain vs with
   the step guard fused in, plus the cross-replica parameter
   fingerprint fold amortized at ``fingerprint_every=20``; the
   guard-on/off step-time delta is the cost of ``HVD_TPU_SDC_GUARD``
   (target <2% where the guard's reductions fuse into the update pass).
7. ``tracing``      — per-request distributed-tracer overhead
   (docs/timeline.md) on the serving hot path's instrumentation
   sequence (root request span, nested span, retroactive span,
   collective hook), ``HVD_TPU_TRACE_SAMPLE=0`` vs ``=1``: the off
   delta over a bare loop is the zero-overhead-when-disabled
   acceptance number.
8. ``failover``     — request-survivability costs (docs/robustness.md):
   fleet-router hedged-retry tail under a 10%-slow-replica workload
   (p50/p99 hedging off vs on against latency-scripted HTTP stubs —
   the p99 collapse is the acceptance number), and the mid-stream
   failover resume cost at 256 already-emitted tokens (time to the
   resumed first token, automatic prefix cache on vs off, with the
   resumed stream asserted bit-identical under seeded sampling).

9. ``disagg``       — disaggregated prefill/decode serving
   (docs/inference.md) vs colocated, end to end through real HTTP
   fleets on the shared-system-prompt mixed workload: tokens/sec and
   per-request p50/p99 for a 2-colocated-replica fleet vs a
   1-prefill + 1-decode pooled fleet, with outputs asserted
   bit-identical across modes, the pooled KV-transfer bytes/seconds
   recorded, and a fully-warm repeat request asserted to move ZERO
   transfer bytes (the content-addressed dedup acceptance number).

Usage: ``python microbench.py [--quick]``. Workers are internal
(``--worker-eager`` / ``--worker-scaling`` / ``--worker-injit`` /
``--worker-generation`` / ``--worker-sdc`` / ``--worker-tracing`` /
``--worker-failover`` / ``--worker-disagg``).
"""

import json
import os
import subprocess
import sys
import time

MB_TAG = "MB_JSON "
ROOT = os.path.dirname(os.path.abspath(__file__))


def _log(msg):
    sys.stderr.write(f"[microbench] {msg}\n")
    sys.stderr.flush()


def _free_port():
    from horovod_tpu.runner.launch import free_port
    return free_port()


def _cpu_env(extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _collect(out: str):
    rows = []
    for line in out.splitlines():
        if line.startswith(MB_TAG):
            rows.append(json.loads(line[len(MB_TAG):]))
    return rows


def _run_eager(nproc: int, quick: bool, timeout: int):
    port = _free_port()
    procs = []
    for rank in range(nproc):
        env = _cpu_env({
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": str(nproc),
            "HVD_TPU_RANK": str(rank),
        } if nproc > 1 else {})
        cmd = [sys.executable, os.path.abspath(__file__), "--worker-eager"]
        if quick:
            cmd.append("--quick")
        procs.append(subprocess.Popen(cmd, env=env, text=True,
                                      stdout=subprocess.PIPE,
                                      stderr=sys.stderr))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            # degrade the section to null, like _run_scaling — the other
            # sections must still run and MICROBENCH.json must be written
            for q in procs:
                q.kill()
            for q in procs:  # reap: no zombies/open pipes during later runs
                try:
                    q.communicate(timeout=10)
                except Exception:
                    pass
            _log(f"eager {nproc}-proc: timeout after {timeout}s")
            return None
        outs.append(out or "")
    if any(p.returncode != 0 for p in procs):
        _log(f"eager {nproc}-proc worker failed "
             f"(rcs={[p.returncode for p in procs]})")
        return None
    return _collect(outs[0])


def _run_scaling(n: int, quick: bool, timeout: int):
    env = _cpu_env({
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
    })
    cmd = [sys.executable, os.path.abspath(__file__),
           f"--worker-scaling={n}"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=env, text=True, capture_output=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired:
        _log(f"scaling n={n}: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"scaling n={n}: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows[0] if rows else None


# ---------------------------------------------------------------- workers

def worker_eager(quick: bool) -> int:
    import horovod_tpu as hvd
    from horovod_tpu.microbench import (
        DEFAULT_SIZES, bucketed_optimizer_sweep, eager_sweep)

    hvd.init()
    sizes = DEFAULT_SIZES[:4] if quick else DEFAULT_SIZES
    rows = eager_sweep(sizes=sizes, iters=3 if quick else 5)
    rows.append(bucketed_optimizer_sweep(iters=2 if quick else 3))
    if hvd.rank() == 0:
        for r in rows:
            print(MB_TAG + json.dumps(r))
    hvd.shutdown()
    return 0


def worker_scaling(n: int, quick: bool) -> int:
    from horovod_tpu.microbench import scaling_sweep_point
    row = scaling_sweep_point(
        batch_per_device=4 if quick else 8,
        image_size=32,
        num_iters=2 if quick else 3,
        num_batches_per_iter=3 if quick else 5)
    assert row["num_devices"] == n, (row, n)
    print(MB_TAG + json.dumps(row))
    return 0


def worker_injit(n: int, quick: bool) -> int:
    from horovod_tpu.microbench import injit_optimizer_sweep
    row = injit_optimizer_sweep(iters=2 if quick else 4)
    assert row["num_devices"] == n, (row, n)
    print(MB_TAG + json.dumps(row))
    return 0


def worker_generation(quick: bool) -> int:
    from horovod_tpu.microbench import (generation_sweep, prefix_sweep,
                                        sampling_sweep, spec_sweep)
    row = generation_sweep(num_requests=12 if quick else 24)
    print(MB_TAG + json.dumps(row))
    row = sampling_sweep(num_requests=8 if quick else 16)
    print(MB_TAG + json.dumps(row))
    row = prefix_sweep(num_requests=12 if quick else 24)
    print(MB_TAG + json.dumps(row))
    # max_tokens stays at 96 even in quick mode: the accept rate (and
    # with it the headline speedup) needs the cycle to dominate the
    # warmup transient, and a single-stream run is sub-second anyway
    row = spec_sweep(max_tokens=96, repeats=2 if quick else 3)
    print(MB_TAG + json.dumps(row))
    return 0


def _run_generation(quick: bool, timeout: int):
    """Returns [generation_sweep, sampling_sweep, prefix_sweep,
    spec_sweep] rows (or None)."""
    p = None
    cmd = [sys.executable, os.path.abspath(__file__), "--worker-generation"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=_cpu_env(), text=True,
                           capture_output=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        _log("generation: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"generation: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows or None


def worker_sdc(quick: bool) -> int:
    from horovod_tpu.microbench import sdc_guard_sweep
    row = sdc_guard_sweep(steps=20 if quick else 40,
                          rounds=2 if quick else 3)
    print(MB_TAG + json.dumps(row))
    return 0


def _run_sdc(quick: bool, timeout: int):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker-sdc"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=_cpu_env(), text=True,
                           capture_output=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        _log("sdc: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"sdc: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows[0] if rows else None


def worker_tracing(quick: bool) -> int:
    from horovod_tpu.microbench import tracing_overhead_sweep
    row = tracing_overhead_sweep(requests=5000 if quick else 20000,
                                 rounds=2 if quick else 3)
    print(MB_TAG + json.dumps(row))
    return 0


def _run_tracing(quick: bool, timeout: int):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker-tracing"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=_cpu_env(), text=True,
                           capture_output=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        _log("tracing: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"tracing: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows[0] if rows else None


def worker_failover(quick: bool) -> int:
    from horovod_tpu.microbench import hedging_sweep, resume_sweep
    row = hedging_sweep(requests=40 if quick else 80)
    print(MB_TAG + json.dumps(row))
    row = resume_sweep(emitted=96 if quick else 256)
    print(MB_TAG + json.dumps(row))
    return 0


def _run_failover(quick: bool, timeout: int):
    """Returns [hedging_sweep, resume_sweep] rows (or None)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker-failover"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=_cpu_env(), text=True,
                           capture_output=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        _log("failover: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"failover: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows or None


def worker_disagg(quick: bool) -> int:
    from horovod_tpu.microbench import disagg_sweep
    row = disagg_sweep(num_requests=8 if quick else 16,
                       batch_slots=4 if quick else 8)
    print(MB_TAG + json.dumps(row))
    return 0


def _run_disagg(quick: bool, timeout: int):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker-disagg"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=_cpu_env(), text=True,
                           capture_output=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        _log("disagg: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"disagg: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows[0] if rows else None


def _run_injit(n: int, quick: bool, timeout: int):
    env = _cpu_env({
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
    })
    cmd = [sys.executable, os.path.abspath(__file__), f"--worker-injit={n}"]
    if quick:
        cmd.append("--quick")
    try:
        p = subprocess.run(cmd, env=env, text=True, capture_output=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired:
        _log(f"injit n={n}: timeout")
        return None
    sys.stderr.write(p.stderr or "")
    if p.returncode != 0:
        _log(f"injit n={n}: rc={p.returncode}")
        return None
    rows = _collect(p.stdout or "")
    return rows[0] if rows else None


# ----------------------------------------------------------------- parent

def main():
    quick = "--quick" in sys.argv
    for a in sys.argv[1:]:
        if a == "--worker-eager":
            return worker_eager(quick)
        if a.startswith("--worker-scaling="):
            return worker_scaling(int(a.split("=", 1)[1]), quick)
        if a.startswith("--worker-injit="):
            return worker_injit(int(a.split("=", 1)[1]), quick)
        if a == "--worker-generation":
            return worker_generation(quick)
        if a == "--worker-sdc":
            return worker_sdc(quick)
        if a == "--worker-tracing":
            return worker_tracing(quick)
        if a == "--worker-failover":
            return worker_failover(quick)
        if a == "--worker-disagg":
            return worker_disagg(quick)

    t0 = time.time()
    result = {"quick": quick}

    def split_bucketed(rows):
        if not rows:
            return rows, None
        plain = [r for r in rows if "scenario" not in r]
        bk = next((r for r in rows if "scenario" in r), None)
        return plain, bk

    _log("section 1/9: eager sweep, 1 process")
    result["eager_1proc"], result["bucketed_1proc"] = split_bucketed(
        _run_eager(1, quick, timeout=600))

    _log("section 2/9: eager sweep, 2 processes")
    result["eager_2proc"], result["bucketed_2proc"] = split_bucketed(
        _run_eager(2, quick, timeout=900))

    _log("section 3/9: compiled-plane scaling sweep")
    points = []
    for n in (1, 2, 4, 8):
        row = _run_scaling(n, quick, timeout=600)
        if row:
            points.append(row)
            _log(f"  n={n}: {row['images_per_sec_total']:.1f} img/s total")
    base = next((p for p in points if p["num_devices"] == 1), None)
    for p in points:
        if base:
            p["efficiency_vs_1dev"] = round(
                p["images_per_sec_total"]
                / (p["num_devices"] * base["images_per_sec_total"]), 3)
    result["scaling"] = points

    _log("section 4/9: in-jit fast path (ResNet-50 gradient scenario)")
    injit_rows = []
    for n in ((1, 2) if quick else (1, 2, 8)):
        row = _run_injit(n, quick, timeout=900)
        if row:
            # stitch in the same-scale eager bucketed time: n virtual
            # devices in one program vs n processes through the eager
            # dispatcher carry the same collective payload, so the ratio
            # IS the compiled-vs-eager plane gap for the real optimizer
            # scenario (ROADMAP item 2's acceptance number)
            bk = result.get(f"bucketed_{n}proc")
            if bk and bk.get("bucketed_s"):
                row["eager_bucketed_same_scale_s"] = bk["bucketed_s"]
                pk = row["variants"]["packed"]["time_s"]
                row["packed_speedup_vs_eager_bucketed"] = round(
                    bk["bucketed_s"] / pk, 2) if pk > 0 else None
            injit_rows.append(row)
            _log(f"  n={n}: packed "
                 f"{row['variants']['packed']['time_s'] * 1e3:.1f} ms "
                 f"(x{row['packed_speedup_vs_per_leaf']} vs per-leaf)")
    result["injit"] = injit_rows

    _log("section 5/9: continuous vs static batch generation + sampling")
    gen_rows = _run_generation(quick, timeout=1800)
    gen = gen_rows[0] if gen_rows else None
    sampling = gen_rows[1] if gen_rows and len(gen_rows) > 1 else None
    prefix = gen_rows[2] if gen_rows and len(gen_rows) > 2 else None
    spec = gen_rows[3] if gen_rows and len(gen_rows) > 3 else None
    if gen:
        _log(f"  continuous {gen['continuous']['tokens_per_s']} tok/s "
             f"(x{gen['continuous_speedup']} vs static full-batch), "
             f"peak KV {gen['kv_bytes_vs_static_reservation']} of the "
             f"static reservation")
    if sampling:
        ga = sampling["modes"]["greedy_async1"]
        gs = sampling["modes"]["greedy_sync"]
        _log(f"  sampling: greedy async1 {ga['tokens_per_s']} tok/s "
             f"(sync {gs['tokens_per_s']}), host "
             f"{ga['host_ms_per_step']} ms/step vs "
             f"{gs['host_ms_per_step']} sync")
    if prefix:
        _log(f"  prefix cache: {prefix['cache_on']['tokens_per_s']} tok/s "
             f"on vs {prefix['cache_off']['tokens_per_s']} off "
             f"(x{prefix['cache_speedup']}), prefill reduced "
             f"{prefix['prefill_reduction']:.0%}")
    if spec:
        rep = spec["modes"]["repetitive_spec"]
        _log(f"  speculative: {rep['tokens_per_s']} tok/s spec-on "
             f"repetitive (x{spec['spec_speedup_repetitive']} vs plain, "
             f"accept {rep['accept_rate']}), random workload "
             f"x{spec['spec_speedup_random']}, "
             f"bit_identical={spec['bit_identical']}")
    result["generation"] = gen
    result["generation_sampling"] = sampling
    result["generation_prefix"] = prefix
    result["generation_spec"] = spec

    _log("section 6/9: SDC guard + fingerprint overhead")
    sdc = _run_sdc(quick, timeout=600)
    if sdc:
        _log(f"  guard on/off: {sdc['guarded_ms_per_step']} vs "
             f"{sdc['plain_ms_per_step']} ms/step "
             f"({sdc['overhead_pct']}% on {sdc['platform']}, target "
             f"<{sdc['target_pct']}%), fingerprint fold "
             f"{sdc['fingerprint_fold_ms']} ms every "
             f"{sdc['fingerprint_every']} steps")
    result["sdc"] = sdc

    _log("section 7/9: per-request tracing overhead")
    tracing_row = _run_tracing(quick, timeout=300)
    if tracing_row:
        _log(f"  off {tracing_row['off_us_per_req']} us/req over bare "
             f"{tracing_row['bare_us_per_req']} "
             f"(+{tracing_row['off_overhead_us_per_req']} us disabled), "
             f"on {tracing_row['on_us_per_req']} us/req "
             f"(+{tracing_row['on_overhead_us_per_req']} us traced)")
    result["tracing"] = tracing_row

    _log("section 8/9: request survivability (hedging tail + resume cost)")
    fo_rows = _run_failover(quick, timeout=900)
    hedging = fo_rows[0] if fo_rows else None
    resume = fo_rows[1] if fo_rows and len(fo_rows) > 1 else None
    if hedging:
        _log(f"  hedging: p99 {hedging['off']['p99_ms']} ms off -> "
             f"{hedging['on']['p99_ms']} ms on "
             f"(x{hedging['p99_speedup']}, "
             f"{hedging['on']['hedges_launched']} launched / "
             f"{hedging['on']['hedges_won']} won)")
    if resume:
        _log(f"  resume at {resume['emitted_tokens']} tokens: "
             f"{resume['resume_first_token_ms_cache_on']} ms cached vs "
             f"{resume['resume_first_token_ms_cache_off']} ms cold "
             f"(x{resume['cached_resume_speedup']}, bit_identical="
             f"{resume['bit_identical']})")
    result["failover"] = ({"hedging": hedging, "resume": resume}
                          if fo_rows else None)

    _log("section 9/9: disaggregated prefill/decode fleet")
    disagg = _run_disagg(quick, timeout=900)
    if disagg:
        _log(f"  pooled {disagg['pooled']['tokens_per_s']} tok/s "
             f"p99 {disagg['pooled']['p99_ms']} ms vs colocated "
             f"{disagg['colocated']['tokens_per_s']} tok/s "
             f"p99 {disagg['colocated']['p99_ms']} ms, "
             f"{disagg['pooled']['transfer_bytes']} transfer bytes "
             f"(warm repeat "
             f"{disagg['pooled']['warm_repeat_transfer_bytes']}), "
             f"bit_identical={disagg['bit_identical']}")
    result["disagg"] = disagg
    result["wall_s"] = round(time.time() - t0, 1)

    out_path = os.path.join(ROOT, "MICROBENCH.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    _log(f"wrote {out_path} in {result['wall_s']}s")

    # one-line summary for the driver log
    two = result.get("eager_2proc") or []
    big = two[-1] if two else None
    bk2 = result.get("bucketed_2proc") or result.get("bucketed_1proc")
    inj2 = next((r for r in injit_rows if r["num_devices"] == 2),
                injit_rows[0] if injit_rows else None)
    print(json.dumps({
        "metric": "collective_microbench",
        "eager_2proc_peak_bytes_per_s": round(big["eager_bytes_per_s"])
        if big else None,
        "eager_over_injit_at_peak": round(big["eager_over_injit"], 2)
        if big else None,
        "dispatch_latency_us": round(
            min(r["dispatch_latency_s"] for r in two) * 1e6) if two else None,
        "bucketed_speedup": bk2.get("bucketed_speedup") if bk2 else None,
        "scaling_points": len(result["scaling"]),
        "injit_packed_ms": round(
            inj2["variants"]["packed"]["time_s"] * 1e3, 1) if inj2 else None,
        "injit_packed_vs_eager_bucketed": inj2.get(
            "packed_speedup_vs_eager_bucketed") if inj2 else None,
        "gen_continuous_tokens_per_s": gen["continuous"]["tokens_per_s"]
        if gen else None,
        "gen_speedup_vs_static_batch": gen["continuous_speedup"]
        if gen else None,
        "gen_async1_tokens_per_s": sampling["modes"]["greedy_async1"]
        ["tokens_per_s"] if sampling else None,
        "gen_host_ms_per_step_async1": sampling["modes"]["greedy_async1"]
        ["host_ms_per_step"] if sampling else None,
        "gen_prefix_cache_speedup": prefix["cache_speedup"]
        if prefix else None,
        "gen_prefix_prefill_reduction": prefix["prefill_reduction"]
        if prefix else None,
        "gen_spec_speedup_repetitive": spec["spec_speedup_repetitive"]
        if spec else None,
        "gen_spec_accept_rate_repetitive": spec["modes"]
        ["repetitive_spec"]["accept_rate"] if spec else None,
        "gen_spec_speedup_random": spec["spec_speedup_random"]
        if spec else None,
        "gen_spec_bit_identical": spec["bit_identical"] if spec else None,
        "sdc_guard_overhead_pct": sdc["overhead_pct"] if sdc else None,
        "sdc_fingerprint_fold_ms": sdc["fingerprint_fold_ms"]
        if sdc else None,
        "tracing_off_overhead_us_per_req": tracing_row
        ["off_overhead_us_per_req"] if tracing_row else None,
        "tracing_on_overhead_us_per_req": tracing_row
        ["on_overhead_us_per_req"] if tracing_row else None,
        "hedging_p99_speedup": hedging["p99_speedup"] if hedging else None,
        "resume_first_token_ms_cached": resume
        ["resume_first_token_ms_cache_on"] if resume else None,
        "resume_bit_identical": resume["bit_identical"] if resume else None,
        "disagg_pooled_tokens_per_s": disagg["pooled"]["tokens_per_s"]
        if disagg else None,
        "disagg_pooled_p99_ms": disagg["pooled"]["p99_ms"]
        if disagg else None,
        "disagg_warm_transfer_bytes": disagg["pooled"]
        ["warm_repeat_transfer_bytes"] if disagg else None,
        "disagg_bit_identical": disagg["bit_identical"]
        if disagg else None,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
