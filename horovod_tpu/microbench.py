"""Collective-plane microbenchmarks.

The reference treats eager collective dispatch as its hot loop — fused
buffers (fusion_buffer_manager.h:30-55), a 5 ms negotiation cycle, and a
finalizer pool that pipelines back-to-back NCCL launches
(gpu_operations.cc:60-87). Our eager plane replaces all of that with one
jitted XLA reduction per dispatch, staging host values to the device on
the way in. This module measures that design instead of assuming it:

* :func:`eager_sweep` — payload sweep (1 KB → 256 MB) of the eager
  ``allreduce`` / ``grouped_allreduce`` path, reporting bytes/sec, the
  async dispatch latency (time for ``allreduce_async`` to return to the
  caller), and the ratio against an **in-jit** reduction of the very same
  global payload with pre-staged device inputs. The gap between the two
  IS the eager plane's staging + host-dispatch overhead — the quantity
  the reference's fusion buffer exists to amortize.
* :func:`scaling_sweep_point` — compiled-data-plane train step (the same
  DistributedOptimizer path ``bench.py`` measures) over every visible
  device, reporting throughput for one device count. The driver script
  (``microbench.py`` at the repo root) sweeps 1→8 virtual CPU devices and
  computes scaling efficiency — exercising the measurement machinery a
  real pod run needs (virtual CPU devices share host cores, so the CPU
  efficiency trend is a machinery check, not a performance claim).

Results are written to ``MICROBENCH.json`` by the root script and cited
in ``docs/tensor-fusion.md``.
"""

import functools
import time
from typing import List, Optional, Sequence

import numpy as np

# Payload ladder: 1 KB → 256 MB (reference fusion threshold is 64 MB;
# common.h:95). The top sizes are where bandwidth dominates, the bottom
# where per-dispatch overhead dominates.
DEFAULT_SIZES = (1 << 10, 1 << 14, 1 << 17, 1 << 20, 1 << 23, 1 << 26,
                 1 << 28)


def _timeit(fn, iters: int, warmup: int = 1) -> float:
    """Best wall-clock seconds of ``fn()`` over ``iters`` runs (min, the
    ``timeit`` convention: outside interference only ever adds time, so
    the minimum is the least-noisy estimate of the code's cost — medians
    of CPU-backend collective runs flapped 3x between identical
    configurations in round 5)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def eager_sweep(sizes: Sequence[int] = DEFAULT_SIZES, iters: int = 5,
                group: int = 8) -> List[dict]:
    """Sweep eager collectives over payload sizes. Must run inside an
    initialized world (any process count); every rank executes the same
    sequence (SPMD lockstep), results are identical across ranks."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from . import collectives

    w = collectives._world()
    wm = w.world_mesh
    nproc = wm.num_procs
    results = []

    for size in sizes:
        n_el = max(1, size // 4)
        x = np.ones((n_el,), np.float32)
        payload = n_el * 4
        # More rounds at the cheap sizes: the box this runs on shares
        # cores, so per-round load swings dominate small payloads
        rounds = iters if payload > (8 << 20) else max(iters, 12)

        # --- eager allreduce: full round trip, host in → host-visible out.
        def run_allreduce():
            out = hvd.allreduce(x, op=hvd.Sum, name=f"mb_ar_{size}")
            np.asarray(out)  # force the result all the way back

        # --- grouped allreduce: ``group`` tensors fused into one dispatch.
        chunk = max(1, n_el // group)
        xs = [np.ones((chunk,), np.float32) for _ in range(group)]

        def run_grouped():
            outs = hvd.grouped_allreduce(xs, op=hvd.Sum,
                                         name=f"mb_gar_{size}")
            np.asarray(outs[0])

        # --- in-jit reduction of the SAME global payload with inputs
        # already staged on device: the compiled-plane cost floor. The
        # program is identical to the eager plane's (sum over the proc
        # axis); only staging and per-call host work differ.
        stacked = collectives._global_from_local(wm, x)
        if nproc > 1:
            injit = jax.jit(lambda g: jnp.sum(g, axis=0),
                            out_shardings=wm.replicated_sharding())
        else:
            injit = jax.jit(lambda g: jnp.sum(g, axis=0))

        def run_injit():
            injit(stacked).block_until_ready()

        # The timed variants are INTERLEAVED round-robin (a full round of
        # single/grouped/injit/dispatch per iteration) so shared-machine
        # load swings hit every variant alike; each variant's estimate is
        # its best round (_timeit convention). Sequential per-variant
        # timing flapped 3x between identical runs in round 5.
        run_allreduce(), run_grouped(), run_injit()  # warmup/compile
        t_eager = t_grouped = t_injit = float("inf")
        lat = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            run_allreduce()
            t_eager = min(t_eager, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_grouped()
            t_grouped = min(t_grouped, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_injit()
            t_injit = min(t_injit, time.perf_counter() - t0)
            # async dispatch latency: how long the caller thread is
            # blocked per submission (EnqueueTensorAllreduce cost).
            t0 = time.perf_counter()
            h = hvd.allreduce_async(x, op=hvd.Sum, name=f"mb_ard_{size}")
            lat.append(time.perf_counter() - t0)
            hvd.synchronize(h)
        t_dispatch = float(np.median(lat))

        results.append({
            "payload_bytes": payload,
            "nproc": nproc,
            "eager_allreduce_s": t_eager,
            "eager_bytes_per_s": payload / t_eager,
            "dispatch_latency_s": t_dispatch,
            "grouped_allreduce_s": t_grouped,
            "grouped_bytes_per_s": (chunk * 4 * group) / t_grouped,
            "injit_reduce_s": t_injit,
            "eager_over_injit": t_eager / t_injit if t_injit > 0 else None,
        })
    return results


def resnet50_grad_shapes() -> List[tuple]:
    """ResNet-50's 161 parameter shapes (~25.5M params, ~102 MB fp32) —
    the realistic parameter set the fusion-threshold default was designed
    around (reference: HOROVOD_FUSION_THRESHOLD=64MB, common.h:95, tuned
    on exactly this model per docs/benchmarks.rst)."""
    shapes = [(7, 7, 3, 64), (64,), (64,)]
    c_in = 64
    for blocks, cmid, cout in ((3, 64, 256), (4, 128, 512),
                               (6, 256, 1024), (3, 512, 2048)):
        for b in range(blocks):
            shapes += [(1, 1, c_in, cmid), (cmid,), (cmid,),
                       (3, 3, cmid, cmid), (cmid,), (cmid,),
                       (1, 1, cmid, cout), (cout,), (cout,)]
            if b == 0:
                shapes += [(1, 1, c_in, cout), (cout,), (cout,)]
            c_in = cout
    shapes += [(2048, 1000), (1000,)]
    return shapes


def bucketed_optimizer_sweep(iters: int = 5,
                             threshold_mb: int = 64) -> dict:
    """Per-parameter dispatch vs bucketed grouped dispatch over a full
    ResNet-50 gradient set at the default fusion threshold — the
    end-to-end claim behind tensor fusion (reference
    collective_operations.cc:37-81): a backward pass issuing one
    allreduce per parameter pays ~161 dispatch+staging roundtrips;
    bucketing pays ceil(total/threshold) grouped ones."""
    import horovod_tpu as hvd
    from .fusion import plan_buckets

    shapes = resnet50_grad_shapes()
    grads = [np.ones(s, np.float32) for s in shapes]
    total_bytes = sum(g.nbytes for g in grads)
    buckets = plan_buckets([(s, np.float32) for s in shapes],
                           threshold_mb * (1 << 20))

    def run_per_param():
        hs = [hvd.allreduce_async(g, op=hvd.Sum, name=f"mb_pp_{i}")
              for i, g in enumerate(grads)]
        outs = [hvd.synchronize(h) for h in hs]
        np.asarray(outs[-1])

    def run_bucketed():
        hs = [hvd.grouped_allreduce_async(
                  [grads[i] for i in b], op=hvd.Sum, name=f"mb_bk_{j}")
              for j, b in enumerate(buckets)]
        outs = [hvd.synchronize(h) for h in hs]
        np.asarray(outs[-1][-1])

    # interleaved A/B rounds, best-round estimates (see eager_sweep)
    run_per_param(), run_bucketed()
    t_pp = t_bk = float("inf")
    for _ in range(max(iters, 5)):
        t0 = time.perf_counter()
        run_per_param()
        t_pp = min(t_pp, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_bucketed()
        t_bk = min(t_bk, time.perf_counter() - t0)
    return {
        "scenario": "resnet50_bucketed_optimizer",
        "num_grads": len(grads),
        "total_mb": round(total_bytes / (1 << 20), 1),
        "threshold_mb": threshold_mb,
        "num_buckets": len(buckets),
        "per_param_s": t_pp,
        "bucketed_s": t_bk,
        "bucketed_speedup": round(t_pp / t_bk, 2) if t_bk > 0 else None,
    }


def injit_optimizer_sweep(iters: int = 5) -> dict:
    """The compiled-plane fast path on the ResNet-50 161-gradient
    scenario (docs/injit.md): per-leaf vs packed vs packed+bf16 vs
    packed+int8 ``DistributedGradientTransform.update`` under shard_map
    over every visible device, inputs pre-staged (the reduction cost, not
    host transfer). This is the in-jit counterpart of
    :func:`bucketed_optimizer_sweep` — the same gradient set the eager
    bucketed path dispatches in ~161 host roundtrips runs here as a
    handful of fused XLA collectives, which is the ROADMAP item 2 claim
    MICROBENCH.json exists to keep honest.

    ``wire_mb`` is the analytic per-device payload entering the
    collectives (fp32 x4 / bf16 x2 / int8 x1 bytes per element; fp16's
    upcast-psum would put fp32 back on the wire, which is why bf16 is the
    headline half — compression.py).
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from .compression import Compression
    from .fusion import packed_plan
    from .optimizer import _packed_threshold

    # check_vma off: all_gather-based lowerings (broadcast, int8) fail
    # shard_map's static replication inference
    shard_map = functools.partial(jax.shard_map, check_vma=False)
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("dp",))

    shapes = resnet50_grad_shapes()
    names = [f"g{i}" for i in range(len(shapes))]
    params = {k: jnp.zeros(s, jnp.float32) for k, s in zip(names, shapes)}
    rng = np.random.RandomState(0)
    grads_host = {
        k: np.stack([rng.standard_normal(s).astype(np.float32) * (d + 1)
                     for d in range(n)])
        for k, s in zip(names, shapes)}
    shard = NamedSharding(mesh, P("dp"))
    grads = {k: jax.device_put(v, shard) for k, v in grads_host.items()}
    total_bytes = sum(int(np.prod(s, dtype=np.int64)) * 4 for s in shapes)
    threshold = _packed_threshold()
    plan = packed_plan([(1,) + tuple(s) for s in shapes],
                       ["float32"] * len(shapes), threshold)

    def make_variant(packing, compression):
        opt = hvd.DistributedOptimizer(
            optax.identity(), axis_name="dp", packing=packing,
            compression=compression)
        state = opt.init(params)
        stateful = getattr(compression, "stateful", False)
        if stateful:
            def step(g, st):
                return opt.update(g, st, params)
            f = jax.jit(shard_map(
                step, mesh=mesh, in_specs=(P("dp"), P()),
                out_specs=(P("dp"), P())))
            box = {"state": state}

            def run():
                u, box["state"] = f(grads, box["state"])
                jax.block_until_ready(u)
                return u
        else:
            def step(g):
                u, _ = opt.update(g, state, params)
                return u
            f = jax.jit(shard_map(step, mesh=mesh, in_specs=P("dp"),
                                  out_specs=P("dp")))

            def run():
                u = f(grads)
                jax.block_until_ready(u)
                return u
        return run

    elem_bytes = {"per_leaf": 4, "packed": 4, "packed_bf16": 2,
                  "packed_int8": 1}
    variants = {
        "per_leaf": make_variant("per_leaf", Compression.none),
        "packed": make_variant("packed", Compression.none),
        "packed_bf16": make_variant("packed", Compression.bf16),
        "packed_int8": make_variant("packed", Compression.int8),
    }

    # warmup/compile + numerics reference off the first calls
    firsts = {k: run() for k, run in variants.items()}
    ref = firsts["per_leaf"]

    def max_err(u):
        return max(float(jnp.max(jnp.abs(u[k].astype(jnp.float32)
                                         - ref[k].astype(jnp.float32))))
                   for k in names)

    errs = {k: max_err(firsts[k]) for k in variants if k != "per_leaf"}
    # interleaved round-robin, best-round estimates (see eager_sweep)
    best = {k: float("inf") for k in variants}
    for _ in range(max(iters, 3)):
        for k, run in variants.items():
            t0 = time.perf_counter()
            run()
            best[k] = min(best[k], time.perf_counter() - t0)

    out = {
        "scenario": "resnet50_injit_reduce",
        "num_grads": len(shapes),
        "total_mb": round(total_bytes / (1 << 20), 1),
        "num_devices": n,
        "threshold_mb": threshold // (1 << 20),
        "num_buckets": len(plan),
        "variants": {},
    }
    for k in variants:
        row = {
            "time_s": best[k],
            "wire_mb": round(total_bytes * elem_bytes[k] / 4 / (1 << 20), 1),
            "collectives_per_step": len(shapes) if k == "per_leaf"
            else len(plan),
        }
        if k != "per_leaf":
            row["max_abs_err_vs_fp32"] = errs[k]
        out["variants"][k] = row
    pl, pk = best["per_leaf"], best["packed"]
    out["packed_speedup_vs_per_leaf"] = round(pl / pk, 2) if pk > 0 else None
    return out


def scaling_sweep_point(batch_per_device: int = 8, image_size: int = 32,
                        model_name: str = "resnet18",
                        num_iters: int = 3,
                        num_batches_per_iter: int = 5) -> dict:
    """One point of the compiled-plane scaling sweep: DP train step over
    every visible device (the bench.py data plane), returning throughput.
    The root script runs this under 1/2/4/8 virtual CPU devices and
    derives efficiency = T(n) / (n * T(1))."""
    import jax

    from .benchmark import _Rig

    rig = _Rig(batch_per_device, image_size, model_name, "sgd")
    r = rig.run_stage(num_warmup_batches=2,
                      num_batches_per_iter=num_batches_per_iter,
                      num_iters=num_iters)
    return {
        "num_devices": r.num_chips,
        "batch_per_device": r.batch_per_chip,
        "images_per_sec_total": r.images_per_sec_total,
        "images_per_sec_per_device": r.images_per_sec_per_chip,
        "platform": r.platform,
    }


def _gen_workload(num_requests: int, shared_prefix: int = 0):
    """The generation sweeps' shared fixture: the tiny fp32 bench
    transformer plus a deterministic mixed-length workload — a few long
    generations pinned among bursts of short ones (the shape that
    strands static batches), mixed prompt lengths including one past
    the prefill chunk. ``shared_prefix > 0`` prepends that many
    identical system-prompt tokens to every prompt (the
    :func:`prefix_sweep` agentic/chat shape). Returns
    ``(model, params, cfg, prompts, new_lens)``."""
    import jax
    import jax.numpy as jnp

    from .models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=512, num_layers=4, d_model=128,
                            num_heads=4, head_dim=32, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, (shared_prefix,)).tolist()
    new_lens = [(32, 4, 4, 4, 8, 4, 16, 4)[i % 8]
                for i in range(num_requests)]
    prompts = [system + rng.randint(0, cfg.vocab_size,
                                    (4 + (i * 5) % 20,)).tolist()
               for i in range(num_requests)]
    return model, params, cfg, prompts, new_lens


def generation_sweep(num_requests: int = 24, batch_slots: int = 8,
                     block_size: int = 8) -> dict:
    """Continuous batching vs static full-batch generation on a
    mixed-length prompt workload (ROADMAP item 1's acceptance pair).

    Both modes run the same paged forward over the same pool shapes —
    static through the raw-logits ``build_program``, continuous through
    the engine's on-device-sampling programs (whose greedy tokens are
    pinned bit-identical to host argmax) — and every program is warmed
    off-clock, so the measured gap is pure scheduling + memory policy,
    not kernel or compile-time differences:

    * **static** — the classic served-systems baseline: requests form
      batches of ``batch_slots`` in arrival order; each batch prefills,
      reserves KV for its longest possible sequence in *every* slot,
      and decodes until its longest request finishes — finished lanes
      keep burning decode steps, and the next batch cannot start early.
    * **continuous** — the :class:`GenerationEngine` end-to-end:
      iteration-level admission into freed slots, immediate retirement,
      paged allocate-on-growth.

    Reported per mode: wall seconds, useful tokens/sec (prompt tokens
    excluded), decode steps, and peak KV bytes (allocator high-water x
    block bytes for continuous; the reservation high-water for static).
    """
    import threading

    import jax.numpy as jnp

    from .models.transformer import PagedCache
    from .serving.generation import (GenerationEngine, block_bytes,
                                     build_program, make_pools)
    from .serving.generation.scheduler import DECODE_WIDTH
    from . import metrics as _metrics

    model, params, cfg, prompts, new_lens = _gen_workload(num_requests)
    prefill_chunk = 16
    total_new = sum(new_lens)
    per_block = block_bytes(cfg, block_size)
    program = build_program(model)
    max_blocks = -(-cfg.max_seq_len // block_size)

    # -- static full-batch baseline -----------------------------------------
    def run_static():
        peak_blocks = 0
        decode_steps = 0
        outs = {}
        t0 = time.perf_counter()
        for lo in range(0, num_requests, batch_slots):
            group = list(range(lo, min(lo + batch_slots, num_requests)))
            longest = max(len(prompts[i]) + new_lens[i] for i in group)
            per_seq = -(-longest // block_size)
            # static reservation: worst case for EVERY slot in the batch
            peak_blocks = max(peak_blocks, per_seq * len(group))
            # pool sized like the continuous engine's, so both modes
            # share the same compiled program shapes (the reservation
            # accounting above is what static *requires*, not what the
            # shared pool holds)
            k, v = make_pools(cfg, batch_slots * max_blocks + 1,
                              block_size)
            tables = np.zeros((batch_slots, max_blocks), np.int32)
            for j in range(len(group)):
                tables[j, :per_seq] = 1 + j * per_seq + np.arange(per_seq)
            seqs = [list(prompts[i]) for i in group]
            # prefill, one sequence at a time (the chunked program)
            for j, i in enumerate(group):
                done = 0
                while done < len(prompts[i]):
                    chunk = prompts[i][done:done + prefill_chunk]
                    buf = np.zeros((1, prefill_chunk), np.int32)
                    buf[0, :len(chunk)] = chunk
                    cache = PagedCache((k, v), jnp.asarray(tables[j:j + 1]),
                                       jnp.asarray([done], jnp.int32),
                                       jnp.asarray([len(chunk)], jnp.int32))
                    logits, cache = program(params, cache, jnp.asarray(buf))
                    k, v = cache.pools
                    done += len(chunk)
                seqs[j].append(int(np.argmax(
                    np.asarray(logits)[0, len(chunk) - 1])))
            # decode to the BATCH max — finished lanes keep stepping
            batch_max = max(new_lens[i] for i in group)
            for _step in range(batch_max - 1):
                tokens = np.zeros((batch_slots, DECODE_WIDTH), np.int32)
                lengths = np.zeros((batch_slots,), np.int32)
                live = np.zeros((batch_slots,), np.int32)
                for j in range(len(group)):
                    tokens[j, 0] = seqs[j][-1]
                    lengths[j] = len(seqs[j]) - 1
                    live[j] = 1
                cache = PagedCache((k, v), jnp.asarray(tables),
                                   jnp.asarray(lengths), jnp.asarray(live))
                logits, cache = program(params, cache, jnp.asarray(tokens))
                k, v = cache.pools
                decode_steps += 1
                for j in range(len(group)):
                    seqs[j].append(int(np.argmax(np.asarray(logits)[j, 0])))
            for j, i in enumerate(group):
                outs[i] = seqs[j][len(prompts[i]):
                                  len(prompts[i]) + new_lens[i]]
        wall = time.perf_counter() - t0
        return wall, peak_blocks, decode_steps, outs

    # -- continuous batching -------------------------------------------------
    def run_continuous():
        snap0 = _metrics.snapshot()
        engine = GenerationEngine(
            model, params=params, block_size=block_size,
            num_blocks=batch_slots * max_blocks + 1, max_seqs=batch_slots,
            prefill_chunk=prefill_chunk, queue_depth=num_requests,
            deadline_ms=0)
        outs = [None] * num_requests
        t0 = time.perf_counter()

        def client(i):
            outs[i] = engine.generate(prompts[i], max_tokens=new_lens[i],
                                      timeout=600)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(num_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        snap1 = _metrics.snapshot()
        occ0 = snap0.get("hvd_tpu_gen_batch_occupancy",
                         {"count": 0, "sum": 0})
        occ1 = snap1["hvd_tpu_gen_batch_occupancy"]
        steps = int(occ1["count"] - occ0["count"])
        occupancy = (occ1["sum"] - occ0["sum"]) / max(1, steps)
        preempt = snap1.get("hvd_tpu_gen_preemptions_total", 0) \
            - snap0.get("hvd_tpu_gen_preemptions_total", 0)
        peak = engine.allocator.peak_in_use
        leaked = engine.allocator.in_use
        engine.close()
        assert leaked == 0, f"{leaked} KV blocks leaked"
        return wall, peak, steps, occupancy, preempt, outs

    # compile every program before any clock starts: the static baseline
    # uses the raw-logits build_program shapes, the engine the sampled
    # prefill/decode programs — warm both modes off-clock
    run_static()
    run_continuous()
    st_wall, st_peak, st_steps, st_outs = run_static()
    ct_wall, ct_peak, ct_steps, ct_occ, ct_preempt, ct_outs = \
        run_continuous()
    # same greedy tokens from both schedulers, or the comparison is moot
    mismatch = sum(st_outs[i] != ct_outs[i] for i in range(num_requests))
    assert mismatch == 0, f"{mismatch} sequences diverged across modes"

    return {
        "scenario": "mixed_length_generation",
        "num_requests": num_requests,
        "batch_slots": batch_slots,
        "block_size": block_size,
        "num_blocks": batch_slots * max_blocks + 1,
        "model": {"layers": cfg.num_layers, "d_model": cfg.d_model,
                  "heads": cfg.num_heads, "head_dim": cfg.head_dim,
                  "vocab": cfg.vocab_size, "max_seq_len": cfg.max_seq_len},
        "total_prompt_tokens": sum(len(p) for p in prompts),
        "total_new_tokens": total_new,
        "static": {
            "wall_s": round(st_wall, 3),
            "tokens_per_s": round(total_new / st_wall, 1),
            "decode_steps": st_steps,
            "peak_kv_blocks": st_peak,
            "peak_kv_bytes": st_peak * per_block,
        },
        "continuous": {
            "wall_s": round(ct_wall, 3),
            "tokens_per_s": round(total_new / ct_wall, 1),
            "decode_steps": ct_steps,
            "avg_occupancy": round(ct_occ, 2),
            "preemptions": int(ct_preempt),
            "peak_kv_blocks": ct_peak,
            "peak_kv_bytes": ct_peak * per_block,
        },
        "continuous_speedup": round(st_wall / ct_wall, 2),
        "kv_bytes_vs_static_reservation": round(ct_peak / st_peak, 3)
        if st_peak else None,
    }


def sampling_sweep(num_requests: int = 16, batch_slots: int = 8,
                   block_size: int = 8) -> dict:
    """On-device sampling modes under sync vs async stepping (ISSUE 11).

    Same tiny model and mixed-length workload class as
    :func:`generation_sweep`, driven through the
    :class:`GenerationEngine` in four modes: ``greedy`` vs ``sampled``
    (temperature + top-k + top-p, seeded per request), each at
    ``async_depth`` 0 (synchronous) and 1 (double-buffered). Reported
    per mode: wall seconds, useful tokens/sec, and the host/device
    milliseconds per scheduler iteration read from the
    ``hvd_tpu_gen_step_seconds{component}`` histogram deltas — the
    before/after for the ROADMAP's live-TPU host-overhead re-measure.
    Each sampling mode's outputs are asserted identical across depths
    (depth-1 reconciliation must not change a single token).
    """
    import threading

    from .serving.generation import GenerationEngine
    from . import metrics as _metrics

    model, params, cfg, prompts, new_lens = _gen_workload(num_requests)
    total_new = sum(new_lens)
    max_blocks = -(-cfg.max_seq_len // block_size)
    sampled_kw = dict(temperature=0.9, top_k=32, top_p=0.9)

    def run_mode(sampled: bool, async_depth: int):
        snap0 = _metrics.snapshot()
        engine = GenerationEngine(
            model, params=params, block_size=block_size,
            num_blocks=batch_slots * max_blocks + 1, max_seqs=batch_slots,
            prefill_chunk=16, queue_depth=num_requests, deadline_ms=0,
            async_depth=async_depth)
        outs = [None] * num_requests
        t0 = time.perf_counter()

        def client(i):
            kw = dict(sampled_kw, seed=1000 + i) if sampled else {}
            outs[i] = engine.generate(prompts[i], max_tokens=new_lens[i],
                                      timeout=600, **kw)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(num_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        snap1 = _metrics.snapshot()
        leaked = engine.allocator.in_use
        engine.close()
        assert leaked == 0, f"{leaked} KV blocks leaked"
        split = {}
        for comp in ("host", "device"):
            key = f'hvd_tpu_gen_step_seconds{{component="{comp}"}}'
            h0 = snap0.get(key, {"sum": 0.0, "count": 0})
            h1 = snap1.get(key, {"sum": 0.0, "count": 0})
            iters = h1["count"] - h0["count"]
            split[comp] = (h1["sum"] - h0["sum"]) / max(1, iters)
            split["iters"] = int(iters)
        return {
            "wall_s": round(wall, 3),
            "tokens_per_s": round(total_new / wall, 1),
            "scheduler_iters": split["iters"],
            "host_ms_per_step": round(split["host"] * 1e3, 3),
            "device_ms_per_step": round(split["device"] * 1e3, 3),
        }, outs

    modes = {}
    outputs = {}
    # compile both programs (and warm the jit caches) off the clock
    run_mode(sampled=False, async_depth=0)
    run_mode(sampled=True, async_depth=0)
    for name, sampled, depth in (("greedy_sync", False, 0),
                                 ("greedy_async1", False, 1),
                                 ("sampled_sync", True, 0),
                                 ("sampled_async1", True, 1)):
        modes[name], outputs[name] = run_mode(sampled, depth)
    # depth-1 reconciliation must be invisible in the outputs
    assert outputs["greedy_sync"] == outputs["greedy_async1"], \
        "greedy outputs diverged between sync and async stepping"
    assert outputs["sampled_sync"] == outputs["sampled_async1"], \
        "seeded sampled outputs diverged between sync and async stepping"

    return {
        "scenario": "on_device_sampling",
        "num_requests": num_requests,
        "batch_slots": batch_slots,
        "block_size": block_size,
        "num_blocks": batch_slots * max_blocks + 1,
        "total_new_tokens": total_new,
        "sampled_params": sampled_kw,
        "modes": modes,
        "async_speedup_greedy": round(
            modes["greedy_sync"]["wall_s"]
            / modes["greedy_async1"]["wall_s"], 2),
        "async_speedup_sampled": round(
            modes["sampled_sync"]["wall_s"]
            / modes["sampled_async1"]["wall_s"], 2),
    }


def prefix_sweep(num_requests: int = 24, batch_slots: int = 8,
                 block_size: int = 16) -> dict:
    """Automatic prefix caching on a shared-system-prompt workload
    (ISSUE 12's acceptance pair).

    Every request is one 64-token shared system prompt plus a short
    private suffix — the chat/agentic serving shape. Two engine runs
    over the SAME compiled programs (the sampling prefill/decode
    programs are memoized on the model): ``cache_off`` prefills every
    prompt in full; ``cache_on`` serves request 0 alone to warm the
    index, then the concurrent burst attaches the system prompt's
    blocks (``hvd_tpu_gen_prefix_cache_hit_tokens_total``) and prefills
    only its private suffix. Request 0 runs first in BOTH modes so the
    schedules differ only in cache policy. Outputs are asserted
    bit-identical across modes and no KV block may leak; reported per
    mode: wall seconds, useful tokens/sec, prefilled tokens (the
    ``hvd_tpu_gen_tokens_total{phase="prefill"}`` delta), and the
    prefix-cache hit/miss/eviction counters.
    """
    import threading

    from .serving.generation import GenerationEngine
    from . import metrics as _metrics

    system_tokens = 64
    model, params, cfg, prompts, new_lens = _gen_workload(
        num_requests, shared_prefix=system_tokens)
    total_new = sum(new_lens)
    max_blocks = -(-cfg.max_seq_len // block_size)
    num_blocks = batch_slots * max_blocks + 1

    def run(prefix_cache):
        snap0 = _metrics.snapshot()
        engine = GenerationEngine(
            model, params=params, block_size=block_size,
            num_blocks=num_blocks, max_seqs=batch_slots,
            prefill_chunk=16, queue_depth=num_requests, deadline_ms=0,
            prefix_cache=prefix_cache)
        outs = [None] * num_requests
        t0 = time.perf_counter()
        # request 0 runs alone first — with the cache on it warms the
        # index so every burst request below finds the system prompt
        outs[0] = engine.generate(prompts[0], max_tokens=new_lens[0],
                                  timeout=600)

        def client(i):
            outs[i] = engine.generate(prompts[i], max_tokens=new_lens[i],
                                      timeout=600)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(1, num_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        snap1 = _metrics.snapshot()
        leaked = engine.allocator.in_use
        engine.close()
        assert leaked == 0, f"{leaked} KV blocks leaked"

        def delta(key):
            return snap1.get(key, 0) - snap0.get(key, 0)

        return {
            "wall_s": round(wall, 3),
            "tokens_per_s": round(total_new / wall, 1),
            "prefill_tokens": int(delta(
                'hvd_tpu_gen_tokens_total{phase="prefill"}')),
            "hit_tokens": int(delta(
                'hvd_tpu_gen_prefix_cache_hit_tokens_total'
                '{source="local"}')),
            "miss_tokens": int(delta(
                "hvd_tpu_gen_prefix_cache_miss_tokens_total")),
            "evictions": int(delta(
                "hvd_tpu_gen_prefix_cache_evictions_total")),
        }, outs

    # compile + warm both paths off the clock (fresh engine per run, so
    # no cache state crosses runs — only the jit caches are shared)
    run(prefix_cache=False)
    run(prefix_cache=True)
    cold, cold_outs = run(prefix_cache=False)
    warm, warm_outs = run(prefix_cache=True)
    mismatch = sum(cold_outs[i] != warm_outs[i]
                   for i in range(num_requests))
    assert mismatch == 0, f"{mismatch} sequences diverged across modes"

    return {
        "scenario": "shared_prefix_generation",
        "num_requests": num_requests,
        "batch_slots": batch_slots,
        "block_size": block_size,
        "num_blocks": num_blocks,
        "system_prompt_tokens": system_tokens,
        "total_prompt_tokens": sum(len(p) for p in prompts),
        "total_new_tokens": total_new,
        "cache_off": cold,
        "cache_on": warm,
        "cache_speedup": round(cold["wall_s"] / warm["wall_s"], 2),
        "prefill_reduction": round(
            1.0 - warm["prefill_tokens"] / cold["prefill_tokens"], 3),
    }


def spec_sweep(max_tokens: int = 96, spec_tokens: int = 4,
               block_size: int = 16, repeats: int = 3) -> dict:
    """N-gram speculative decoding vs plain decode (docs/inference.md),
    on the single-stream latency rig where speculation earns its keep.

    Speculative decoding is a latency play: one widened verify forward
    emits ``1 + accepted`` tokens, so the win scales with the accept
    rate and shows up where per-step cost, not batch throughput, is the
    bottleneck — the interactive single-sequence stream. The rig is a
    deeper bench transformer (8 x d256: enough compute per step that
    the verify chunk's cost is real, not dispatch noise) decoding one
    sequence at a time, spec off vs on over the same compiled prefill
    program, on two workloads:

    * **repetitive** — greedy decode. The model's continuation settles
      into a cycle, the prompt-lookup drafter replays it, and the
      accept rate climbs toward 1.0 — the structured-output /
      code-generation shape, speculation's best case.
    * **random** — seeded temperature/top-k/top-p sampling. The
      drafter's n-gram guesses almost never match a high-entropy
      sample, so speculation pays the wider forward for nothing — the
      honest worst case, reported rather than hidden.

    Outputs are asserted bit-identical across spec on/off for BOTH
    workloads (the correctness contract: speculation may only change
    speed) and no KV block may leak. Reported per mode: wall seconds
    per generation, tokens/sec, the n-gram accept rate
    (``hvd_tpu_gen_spec_accepted_total / ..._drafted_total``), and the
    verify-transfer ms/step from
    ``hvd_tpu_gen_step_seconds{component="verify"}``. The acceptance
    number is ``spec_speedup_repetitive`` (target >= 1.5x).
    """
    import jax
    import jax.numpy as jnp

    from .models.transformer import Transformer, TransformerConfig
    from .serving.generation import GenerationEngine
    from . import metrics as _metrics

    cfg = TransformerConfig(vocab_size=512, num_layers=8, d_model=256,
                            num_heads=4, head_dim=64, max_seq_len=256,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (8,)).tolist()
    max_blocks = -(-cfg.max_seq_len // block_size)
    sampled_kw = dict(temperature=0.9, top_k=32, top_p=0.9, seed=1234)

    def run(spec_mode, sampled):
        engine = GenerationEngine(
            model, params=params, block_size=block_size,
            num_blocks=2 * max_blocks + 1, max_seqs=1, prefill_chunk=16,
            queue_depth=4, deadline_ms=0, spec_mode=spec_mode,
            spec_tokens=spec_tokens, max_beams=1)
        kw = dict(sampled_kw) if sampled else {}
        engine.generate(prompt, max_tokens=max_tokens, timeout=600, **kw)
        snap0 = _metrics.snapshot()
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = engine.generate(prompt, max_tokens=max_tokens,
                                  timeout=600, **kw)
        wall = (time.perf_counter() - t0) / repeats
        snap1 = _metrics.snapshot()
        leaked = engine.allocator.in_use
        engine.close()
        assert leaked == 0, f"{leaked} KV blocks leaked"

        def delta(key):
            return snap1.get(key, 0) - snap0.get(key, 0)

        drafted = delta("hvd_tpu_gen_spec_drafted_total")
        accepted = delta("hvd_tpu_gen_spec_accepted_total")
        vkey = 'hvd_tpu_gen_step_seconds{component="verify"}'
        v0 = snap0.get(vkey, {"sum": 0.0, "count": 0})
        v1 = snap1.get(vkey, {"sum": 0.0, "count": 0})
        vsteps = v1["count"] - v0["count"]
        row = {
            "wall_s": round(wall, 3),
            "tokens_per_s": round(max_tokens / wall, 1),
        }
        if spec_mode != "off":
            row["drafted"] = int(drafted)
            row["accepted"] = int(accepted)
            row["accept_rate"] = round(accepted / max(1, drafted), 3)
            row["verify_steps"] = int(vsteps)
            row["verify_ms_per_step"] = round(
                (v1["sum"] - v0["sum"]) / max(1, vsteps) * 1e3, 3)
        return row, out

    modes = {}
    outputs = {}
    # compile the decode + verify programs off the clock
    run("off", sampled=False)
    run("ngram", sampled=False)
    for name, spec_mode, sampled in (
            ("repetitive_off", "off", False),
            ("repetitive_spec", "ngram", False),
            ("random_off", "off", True),
            ("random_spec", "ngram", True)):
        modes[name], outputs[name] = run(spec_mode, sampled)
    # speculation may only change speed — never a token or a logprob
    assert outputs["repetitive_off"] == outputs["repetitive_spec"], \
        "greedy outputs diverged between spec off and on"
    assert outputs["random_off"] == outputs["random_spec"], \
        "seeded sampled outputs diverged between spec off and on"

    return {
        "scenario": "speculative_decoding",
        "num_layers": cfg.num_layers,
        "d_model": cfg.d_model,
        "max_tokens": max_tokens,
        "spec_tokens": spec_tokens,
        "block_size": block_size,
        "sampled_params": {k: v for k, v in sampled_kw.items()
                           if k != "seed"},
        "modes": modes,
        "spec_speedup_repetitive": round(
            modes["repetitive_off"]["wall_s"]
            / modes["repetitive_spec"]["wall_s"], 2),
        "spec_speedup_random": round(
            modes["random_off"]["wall_s"]
            / modes["random_spec"]["wall_s"], 2),
        "bit_identical": True,
    }


def sdc_guard_sweep(steps: int = 40, rounds: int = 3,
                    fingerprint_every: int = 20) -> dict:
    """Overhead of the SDC defense plane (docs/robustness.md) on the
    ResNet-50 161-gradient scenario: a jit'd SGD update over the full
    gradient set, plain vs with :func:`sdc.guard_update` fused into the
    same program (the finite/magnitude checks and loss-spike bound ride
    the data the update is already streaming), plus the host-side
    parameter fingerprint fold amortized over ``fingerprint_every``
    steps. The guarded step only applies the update when the verdict is
    clean — exactly the Estimator integration — so the delta is the
    real per-step price of turning ``HVD_TPU_SDC_GUARD`` on."""
    import jax
    import jax.numpy as jnp

    from . import sdc

    shapes = resnet50_grad_shapes()
    rng = np.random.RandomState(0)
    params = [rng.randn(*s).astype(np.float32) * 0.01 for s in shapes]
    grads = [rng.randn(*s).astype(np.float32) * 0.001 for s in shapes]
    total_bytes = sum(p.nbytes for p in params)

    @jax.jit
    def step_plain(params, grads):
        return jax.tree_util.tree_map(
            lambda p, g: p - 0.01 * g, params, grads)

    @jax.jit
    def step_guarded(params, grads, loss, ewma):
        code, ewma = sdc.guard_update(grads, loss, ewma, factor=10.0)
        ok = code == 0
        new = jax.tree_util.tree_map(
            lambda p, g: jnp.where(ok, p - 0.01 * g, p), params, grads)
        return new, code, ewma

    def run_plain():
        ps = params
        for _ in range(steps):
            ps = step_plain(ps, grads)
        jax.block_until_ready(ps[-1])

    def run_guarded():
        ps, ewma = params, jnp.float32(1.0)
        for i in range(steps):
            ps, code, ewma = step_guarded(ps, grads, 1.0, ewma)
            if (i + 1) % fingerprint_every == 0:
                sdc.fold_fingerprint(ps)
        jax.block_until_ready(ps[-1])

    t0 = time.perf_counter()
    fp = sdc.fold_fingerprint(params)
    fingerprint_s = time.perf_counter() - t0
    assert 0 <= fp < 2 ** 32

    # interleaved A/B rounds, best-round estimates (see eager_sweep)
    run_plain(), run_guarded()
    t_plain = t_guard = float("inf")
    for _ in range(max(rounds, 2)):
        t0 = time.perf_counter()
        run_plain()
        t_plain = min(t_plain, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_guarded()
        t_guard = min(t_guard, time.perf_counter() - t0)

    plain_ms = t_plain / steps * 1e3
    guard_ms = t_guard / steps * 1e3
    return {
        "scenario": "resnet50_sdc_guard",
        # the <2% target assumes the guard's reductions fuse into the
        # update's data pass (accelerator XLA); CPU runs the extra
        # pass unfused, so interpret overhead_pct against platform
        "platform": jax.default_backend(),
        "num_grads": len(shapes),
        "total_mb": round(total_bytes / (1 << 20), 1),
        "steps_timed": steps,
        "fingerprint_every": fingerprint_every,
        "plain_ms_per_step": round(plain_ms, 3),
        "guarded_ms_per_step": round(guard_ms, 3),
        "fingerprint_fold_ms": round(fingerprint_s * 1e3, 3),
        "fingerprint_amortized_ms": round(
            fingerprint_s * 1e3 / fingerprint_every, 4),
        "overhead_pct": round((guard_ms - plain_ms) / plain_ms * 100, 2)
        if plain_ms > 0 else None,
        "target_pct": 2.0,
    }


def tracing_overhead_sweep(requests: int = 20000, rounds: int = 3) -> dict:
    """Per-request cost of the distributed tracer (docs/timeline.md) on
    the serving hot path's instrumentation sequence — one root
    ``request_span``, one nested span, one retroactive ``emit_span``,
    and one ``collective`` hook per request (the four call-site shapes
    the router/batcher/scheduler wiring added) — measured with
    ``HVD_TPU_TRACE_SAMPLE=0`` (the shipped default: every call site
    must reduce to the module-global no-op guard) and ``=1`` (every
    request traced into the in-memory ring; no span file). The ``off``
    delta over the bare loop is the acceptance number: tracing disabled
    must be within noise of not instrumenting at all."""
    import os

    from . import tracing

    rids = [f"{i:016x}" for i in range(requests)]
    entry = ("allreduce", "grad_0", (1024,), "float32")

    def run_bare():
        for _ in range(requests):
            t = time.monotonic()
            assert t

    def run_traced():
        for rid in rids:
            with tracing.request_span("server.generate", rid):
                with tracing.span("gen.prefill"):
                    tracing.collective(entry)
                t = time.monotonic()
                tracing.emit_span(tracing.current(), "gen.decode", t, t)

    def set_rate(rate):
        os.environ["HVD_TPU_TRACE_SAMPLE"] = rate
        tracing.reset()

    prior = os.environ.get("HVD_TPU_TRACE_SAMPLE")
    try:
        # interleaved A/B/C rounds, best-round estimates (eager_sweep)
        t_bare = t_off = t_on = float("inf")
        for _ in range(max(rounds, 2) + 1):  # first round doubles as warmup
            t0 = time.perf_counter()
            run_bare()
            t_bare = min(t_bare, time.perf_counter() - t0)
            set_rate("0")
            assert tracing.tracer() is None
            t0 = time.perf_counter()
            run_traced()
            t_off = min(t_off, time.perf_counter() - t0)
            set_rate("1")
            assert tracing.tracer() is not None
            t0 = time.perf_counter()
            run_traced()
            t_on = min(t_on, time.perf_counter() - t0)
    finally:
        if prior is None:
            os.environ.pop("HVD_TPU_TRACE_SAMPLE", None)
        else:
            os.environ["HVD_TPU_TRACE_SAMPLE"] = prior
        tracing.reset()

    bare_us = t_bare / requests * 1e6
    off_us = t_off / requests * 1e6
    on_us = t_on / requests * 1e6
    return {
        "scenario": "request_tracing_overhead",
        "requests_timed": requests,
        "call_sites_per_request": 4,
        "spans_per_request_on": 4,
        "bare_us_per_req": round(bare_us, 4),
        "off_us_per_req": round(off_us, 4),
        "on_us_per_req": round(on_us, 4),
        # what HVD_TPU_TRACE_SAMPLE=0 costs over no instrumentation
        "off_overhead_us_per_req": round(off_us - bare_us, 4),
        # what turning tracing ON costs over leaving it off
        "on_overhead_us_per_req": round(on_us - off_us, 4),
        "on_over_off": round(on_us / off_us, 2) if off_us > 0 else None,
    }


def hedging_sweep(requests: int = 80, slow_every: int = 10,
                  slow_ms: float = 250.0, fast_ms: float = 4.0,
                  hedge_quantile: float = 0.8) -> dict:
    """Tail latency of the fleet router's hedged retries
    (docs/robustness.md request survivability) under a workload where
    1-in-``slow_every`` requests stalls on its replica for ``slow_ms``
    — the canonical straggler shape hedging exists for. The replicas
    are latency-scripted HTTP stubs (no model): the quantity under
    test is the ROUTER's hedge race, not a forward pass. Reports
    p50/p99 with hedging off and on; the p99 ratio is the acceptance
    number — the slow tail collapses to roughly the hedge delay.

    The quantile sits BELOW the slow fraction (0.8 < 0.9): the router
    indexes its sorted latency window at ``int(q * n)``, so with
    exactly 10% slow a 0.9 quantile lands on the first slow sample and
    the hedge delay degenerates to the straggler latency itself. The
    retry budget is pinned wide open for the run — the budget's
    collapse-to-pass-through behaviour is a correctness property
    (tests/test_failover.py), not the tail effect measured here."""
    import json as _json
    import os
    import threading
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from .serving import fleet

    def make_stub():
        class _Stub(BaseHTTPRequestHandler):
            count = 0
            lock = threading.Lock()

            def do_GET(self):  # healthz for circuit probes
                self._answer(b'{"ok": true}')

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                self.rfile.read(length)
                if self.path != "/v1/cancel":
                    with type(self).lock:
                        type(self).count += 1
                        n = type(self).count
                    if n % slow_every == 0:
                        time.sleep(slow_ms / 1e3)
                    else:
                        time.sleep(fast_ms / 1e3)
                self._answer(b'{"outputs": []}')

            def _answer(self, body):
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        srv = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv

    knobs = {"HVD_TPU_FLEET_HEDGE_QUANTILE": None,
             "HVD_TPU_FLEET_RETRY_BUDGET_RATIO": "1.0",
             "HVD_TPU_FLEET_RETRY_BUDGET_BURST": "64"}

    def measure(quantile):
        knobs["HVD_TPU_FLEET_HEDGE_QUANTILE"] = str(quantile)
        prior = {k: os.environ.get(k) for k in knobs}
        os.environ.update(knobs)
        stubs = [make_stub(), make_stub()]
        try:
            router = fleet.FleetRouter(
                {f"r{i}": f"http://127.0.0.1:{s.server_address[1]}"
                 for i, s in enumerate(stubs)},
                port=0, addr="127.0.0.1")
            router.start()
            lat = []
            body = _json.dumps({"inputs": [[0.0]]}).encode()
            for _ in range(requests):
                req = urllib.request.Request(
                    router.url + "/v1/infer", data=body, method="POST",
                    headers={"Content-Type": "application/json"})
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                lat.append((time.perf_counter() - t0) * 1e3)
            router.stop()
            return lat
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            for s in stubs:
                s.shutdown()
                s.server_close()

    from . import metrics as _metrics
    off = measure(0.0)
    before = _metrics.snapshot()
    on = measure(hedge_quantile)
    snap = _metrics.snapshot()

    def pct(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)), 2)

    launched = snap.get('hvd_tpu_fleet_hedges_total{outcome="launched"}',
                        0) - before.get(
        'hvd_tpu_fleet_hedges_total{outcome="launched"}', 0)
    won = snap.get('hvd_tpu_fleet_hedges_total{outcome="won"}',
                   0) - before.get(
        'hvd_tpu_fleet_hedges_total{outcome="won"}', 0)
    return {
        "scenario": "fleet_hedging_tail",
        "requests": requests,
        "slow_every": slow_every,
        "slow_ms": slow_ms,
        "fast_ms": fast_ms,
        "hedge_quantile": hedge_quantile,
        "off": {"p50_ms": pct(off, 50), "p99_ms": pct(off, 99)},
        "on": {"p50_ms": pct(on, 50), "p99_ms": pct(on, 99),
               "hedges_launched": int(launched), "hedges_won": int(won)},
        "p99_speedup": round(pct(off, 99) / max(pct(on, 99), 1e-9), 2),
    }


def resume_sweep(emitted: int = 256, prompt_len: int = 8,
                 block_size: int = 8) -> dict:
    """Cost of a mid-stream failover resume — re-submitting
    ``prompt + emitted`` with the journaled seed and ``sample_offset``
    — at ``emitted`` already-delivered tokens, with the automatic
    prefix cache on vs off (docs/inference.md). With the cache on, the
    original generation's blocks are still resident, so the resume's
    re-prefill is mostly block reuse; off, it recomputes every chunk.
    The time to the resumed FIRST token is what a live client observes
    as the failover gap."""
    import jax
    import jax.numpy as jnp

    from .models.transformer import Transformer, TransformerConfig
    from .serving.generation import GenerationEngine

    cfg = TransformerConfig(vocab_size=512, num_layers=4, d_model=128,
                            num_heads=4, head_dim=32,
                            max_seq_len=prompt_len + emitted + 8,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (prompt_len,)).tolist()
    num_blocks = 2 * ((prompt_len + emitted + 8) // block_size + 2)
    sampling = dict(temperature=0.9, top_k=40, top_p=0.95, seed=7)

    def run(prefix_cache):
        with GenerationEngine(model, params=params,
                              block_size=block_size,
                              num_blocks=num_blocks, max_seqs=2,
                              prefill_chunk=32, deadline_ms=0,
                              prefix_cache=prefix_cache) as eng:
            head = eng.result(
                eng.submit(prompt, max_tokens=emitted, **sampling),
                timeout=1200)
            # the failover moment: re-submit prompt+emitted elsewhere
            t0 = time.perf_counter()
            tail = eng.result(
                eng.submit(prompt + head, max_tokens=1,
                           sample_offset=emitted, **sampling),
                timeout=1200)
            first_token_ms = (time.perf_counter() - t0) * 1e3
            return head, tail, round(first_token_ms, 2)

    head_on, tail_on, ms_on = run(True)
    head_off, tail_off, ms_off = run(False)
    return {
        "scenario": "stream_resume_cost",
        "emitted_tokens": emitted,
        "prompt_len": prompt_len,
        # same seed + sample_offset: both engines must continue the
        # same sampled stream (the bit-identity the failover relies on)
        "bit_identical": bool(head_on == head_off
                              and tail_on == tail_off),
        "resume_first_token_ms_cache_on": ms_on,
        "resume_first_token_ms_cache_off": ms_off,
        "cached_resume_speedup": round(ms_off / max(ms_on, 1e-9), 2),
    }


def disagg_sweep(num_requests: int = 16, batch_slots: int = 8,
                 block_size: int = 16) -> dict:
    """Disaggregated prefill/decode serving vs colocated (ISSUE 19's
    acceptance pair), end to end through real HTTP fleets.

    The same mixed long-prefill/long-decode workload (the
    :func:`prefix_sweep` shared-64-token-system-prompt shape, whose
    long prompts are exactly what stalls colocated decodes) runs twice
    over the same compiled programs:

    * **colocated** — two ``role='colocated'`` replicas behind a plain
      :class:`FleetRouter` (the PR 13 fleet, least-outstanding).
    * **pooled** — one prefill replica + one decode replica behind a
      pooled router: every request prestages on the prefill pool, the
      KV manifest is offered to the decode replica, and only missing
      blocks move (``hvd_tpu_disagg_transfer_bytes_total``).

    Outputs are asserted bit-identical across modes (the disagg
    correctness contract), and a fully-warm repeat request through the
    pooled fleet is asserted to move ZERO transfer bytes — the
    content-addressed dedup acceptance number. Reported per mode: wall
    seconds, useful tokens/sec, and per-request latency p50/p99; the
    pooled row adds transfer bytes/seconds and the
    ``source="transfer"`` prefix-hit tokens."""
    import json as _json
    import threading
    import urllib.request

    from . import metrics as _metrics
    from .serving import InferenceServer
    from .serving import fleet
    from .serving.generation import GenerationEngine

    system_tokens = 64
    model, params, cfg, prompts, new_lens = _gen_workload(
        num_requests, shared_prefix=system_tokens)
    total_new = sum(new_lens)
    max_blocks = -(-cfg.max_seq_len // block_size)
    num_blocks = batch_slots * max_blocks + 1

    def make_replica(role):
        eng = GenerationEngine(
            model, params=params, block_size=block_size,
            num_blocks=num_blocks, max_seqs=batch_slots,
            prefill_chunk=16, queue_depth=num_requests, deadline_ms=0,
            role=role)
        srv = InferenceServer(None, port=0, addr="127.0.0.1",
                              gen_engine=eng)
        srv.start()
        return srv

    def post(url, doc):
        req = urllib.request.Request(
            url, data=_json.dumps(doc).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            return _json.loads(resp.read())

    TB = "hvd_tpu_disagg_transfer_bytes_total"
    TS = "hvd_tpu_disagg_transfer_seconds"
    HIT_T = ('hvd_tpu_gen_prefix_cache_hit_tokens_total'
             '{source="transfer"}')

    def run(pooled):
        if pooled:
            srvs = {"p0": make_replica("prefill"),
                    "d0": make_replica("decode")}
            pools = {"p0": "prefill", "d0": "decode"}
        else:
            srvs = {"r0": make_replica("colocated"),
                    "r1": make_replica("colocated")}
            pools = None
        router = fleet.FleetRouter(
            {rid: f"http://127.0.0.1:{s.port}"
             for rid, s in srvs.items()},
            port=0, addr="127.0.0.1", pools=pools)
        router.start()
        outs = [None] * num_requests
        lat = [0.0] * num_requests
        try:
            snap0 = _metrics.snapshot()
            t0 = time.perf_counter()

            def client(i):
                t1 = time.perf_counter()
                outs[i] = post(router.url + "/v1/generate",
                               {"prompt": prompts[i],
                                "max_tokens": new_lens[i]})["tokens"]
                lat[i] = (time.perf_counter() - t1) * 1e3
            # request 0 runs alone first — in the pooled fleet its cold
            # transfer ships the shared system prompt once, so the
            # burst's offers dedup against it
            client(0)
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(1, num_requests)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            snap1 = _metrics.snapshot()

            # fully-warm repeat: every manifest block of prompt 0 is
            # already indexed on the serving replica — the pooled hop
            # must move ZERO bytes (content-addressed dedup)
            repeat = post(router.url + "/v1/generate",
                          {"prompt": prompts[0],
                           "max_tokens": new_lens[0]})["tokens"]
            snap2 = _metrics.snapshot()
            assert repeat == outs[0], "warm repeat diverged"
            warm_bytes = snap2.get(TB, 0) - snap1.get(TB, 0)
            if pooled:
                assert warm_bytes == 0, \
                    f"warm shared prefix moved {warm_bytes} bytes"
        finally:
            router.stop()
            for s in srvs.values():
                s.close()

        def delta(key):
            return snap1.get(key, 0) - snap0.get(key, 0)

        lat_np = np.asarray(lat)
        row = {
            "wall_s": round(wall, 3),
            "tokens_per_s": round(total_new / wall, 1),
            "p50_ms": round(float(np.percentile(lat_np, 50)), 2),
            "p99_ms": round(float(np.percentile(lat_np, 99)), 2),
        }
        if pooled:
            row["transfer_bytes"] = int(delta(TB))
            row["transfer_seconds"] = round(delta(TS), 4)
            row["transfer_hit_tokens"] = int(delta(HIT_T))
            row["warm_repeat_transfer_bytes"] = int(warm_bytes)
        return row, outs

    # compile + warm both paths off the clock (fresh replicas per run;
    # only the jit caches are shared across runs)
    run(pooled=False)
    run(pooled=True)
    colo, colo_outs = run(pooled=False)
    pool, pool_outs = run(pooled=True)
    mismatch = sum(colo_outs[i] != pool_outs[i]
                   for i in range(num_requests))
    assert mismatch == 0, f"{mismatch} sequences diverged across modes"

    return {
        "scenario": "disagg_prefill_decode",
        "num_requests": num_requests,
        "batch_slots": batch_slots,
        "block_size": block_size,
        "num_blocks": num_blocks,
        "system_prompt_tokens": system_tokens,
        "total_new_tokens": total_new,
        "bit_identical": True,
        "colocated": colo,
        "pooled": pool,
    }
