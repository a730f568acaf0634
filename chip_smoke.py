#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives every visible chip through the entry points a user
calls, at the full width of the models the repo declares, with seeded
random weights:

* **kernel** — the Pallas flash-attention kernel, compiled by Mosaic,
  against the plain-XLA reference: forward and backward at the shape the
  sequence-parallel train step hands it, forward at a long-context shape;
  and the paged-attention decode kernel against the gather path it
  replaces, at GPT-2 XL's decode shape (32 lanes, 25 x 64 heads, blocks
  of 16) over ragged lengths with dead lanes.
* **trainer** — ``hvd.init()`` -> ``hvd.DistributedOptimizer(optax.sgd)``
  -> the donated train step of ``horovod_tpu.benchmark._Rig``: ResNet-50,
  224x224, bf16, batch 256 per chip, batch sharded over a ``dp`` mesh of
  every chip. Loss finite and lower after than before; params and batch
  shards live on every chip.
* **server** — ``Transformer(TransformerConfig())`` (12 layers, d_model
  768, 12x64 heads, vocab 32000, 2048 positions, bf16) in a
  ``GenerationEngine`` at the registered knob defaults behind
  ``InferenceServer`` on an ephemeral port: concurrent mixed-length
  ``POST /v1/generate`` requests, greedy and seeded-sampled, answer 200
  with the asked number of tokens; greedy tokens and their logprobs agree
  with ``jax.jit(model.apply)`` on the same prompt within ``LOGIT_TOL``;
  no KV block leaks; the decode program's lowered text holds the TPU
  custom call (its attention is the paged kernel, not the gather path).
* with four chips or more, also **ring_train** —
  ``make_transformer_train_step(TransformerConfig(), mesh)`` over
  ``MeshConfig(dp=-1, sp=2)``: ring attention on the compiled kernel (the
  lowered module must hold the TPU custom call), loss within ``LOSS_TOL``
  of the same params and batch through default attention — and the
  **pipeline** and **experts** phases of ``__graft_entry__``.

Every phase prints one JSON line stamped with platform, device kind,
device count and jax version; set-up seconds (construction, compilation,
first execution) and steady seconds are reported apart, each ended by a
host readback. The times are informational. The last line of stdout is
``{"ok": true, "device": {...}}`` and the exit code 0 only if every phase
passed. Without a TPU the script exits 1 and prints no result. No phase is
skipped except the multi-chip ones on fewer than four chips, which is
printed as such.

A chip belongs to one process, so nothing here starts a child process;
the launcher (one process per chip) is checked by ``tools/launch_check.py``.
The phase functions take their sizes as arguments: ``tests/test_chip_smoke.py``
runs them tiny on the CPU mesh with the kernel's ``interpret=True``.
"""

import dataclasses
import functools
import json
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

# Tolerances, each with what the v5e showed when it was set (PR 22). A TPU
# promises no bit identity between differently-shaped programs, and bf16
# keeps 8 bits; a wrong implementation is one to two orders further out.
#: greedy check: the served token's reference logit is within this of the
#: reference maximum, and the served logprob within this of the reference's
#: (paged decode vs full forward: gaps 0.0 and 0.015; a wrong token is ~1 off)
LOGIT_TOL = 0.1
#: ring attention vs default attention, mean cross-entropy of one batch
#: (10.517872 vs 10.517877)
LOSS_TOL = 0.02
#: flash kernel vs reference, relative L2 error of outputs and gradients
#: (worst 0.0042, on dq)
KERNEL_TOL = 0.02

#: (B, S, H, D): the sp=2 train step's local attention shape, and one
#: long-context shape (forward only: the reference's scores are 1 GiB)
KERNEL_TRAIN_SHAPE = (2, 1024, 12, 64)
KERNEL_LONG_SHAPE = (1, 8192, 4, 128)
#: the paged decode kernel's case: (lanes, chunk columns, heads, head_dim,
#: block_size, table blocks a lane, pool blocks): the ``gpt2-xl`` serving
#: cells' decode step, two planes of their pool
KERNEL_PAGED_SHAPE = (32, 2, 25, 64, 16, 64, 576)

#: (prompt length, new tokens, sampled?) — at least one prompt spans more
#: than one prefill chunk (64) and the burst outnumbers the 8 decode lanes
REQUESTS = ((5, 16, False), (40, 8, True), (150, 12, False), (17, 24, True),
            (70, 6, False), (9, 16, True), (33, 10, False), (64, 8, False),
            (3, 20, True), (100, 5, False))


def _stamp() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d), "jax": jax.__version__}


def _devices_of(tree) -> set:
    import jax
    return {s.device for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------- phases

def _paged_case(shape, dtype, seed: int = 2):
    """A decode step's attention inputs at ``shape``: seeded pools, a
    table a lane over distinct blocks (null-padded past its length),
    ragged lengths from empty to a full table, every fifth lane dead
    with a stale length."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.serving.generation.kv_cache import _row

    lanes, chunk, heads, head_dim, bs, max_blocks, num_blocks = shape
    rng = np.random.RandomState(seed)
    pool = (2, num_blocks, bs, _row(heads * head_dim))
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(pool), dtype)
                      for _ in range(2))
    q = jnp.asarray(rng.standard_normal((lanes, chunk, heads, head_dim)),
                    dtype)
    top = max_blocks * bs - chunk
    lengths = rng.randint(0, top + 1, (lanes,))
    lengths[:4] = (0, bs - 1, bs, top)[:lanes]
    live = (np.arange(lanes) % 5 != 4).astype(np.int32)
    tables = np.zeros((lanes, max_blocks), np.int32)
    for b in range(lanes):
        held = -(-(int(lengths[b]) + chunk) // bs)
        tables[b, :held] = rng.choice(np.arange(1, num_blocks), held,
                                      replace=False)
    return (q, k_pool, v_pool, 1, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(live)), live


def _paged_gather_path(q, k_pool, v_pool, layer, tables, lengths, live):
    """What the kernel replaces: ``Attention``'s gather path with the
    paged forward's mask (``live`` is not consulted: a dead lane's
    output is never read)."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (_gathered_attention,
                                                _table_mask)

    positions = lengths[:, None] + jnp.arange(q.shape[1])[None, :]
    mask = _table_mask(positions, tables.shape[1] * k_pool.shape[2])
    return _gathered_attention(q, k_pool, v_pool, layer, tables, mask,
                               k_pool.dtype)


def kernel_phase(train_shape=KERNEL_TRAIN_SHAPE, long_shape=KERNEL_LONG_SHAPE,
                 paged_shape=KERNEL_PAGED_SHAPE, dtype=None,
                 interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention, mha_reference
    from horovod_tpu.ops.paged_attention import paged_attention

    dtype = dtype or jnp.bfloat16

    def qkv(shape, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        return [jax.random.normal(k, shape, dtype) for k in keys]

    def loss_and_grads(attn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))

    flash = functools.partial(flash_attention, interpret=interpret)
    t0 = time.perf_counter()
    q, k, v = qkv(train_shape, 0)
    (l_f, g_f), (l_r, g_r) = (loss_and_grads(flash)(q, k, v),
                              loss_and_grads(mha_reference)(q, k, v))
    errs = {"loss": abs(float(l_f) - float(l_r)) / abs(float(l_r))}
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_r):
        errs[name] = _rel_err(a, b)
    ql, kl, vl = qkv(long_shape, 1)
    fwd = jax.jit(flash)
    errs["long_fwd"] = _rel_err(
        fwd(ql, kl, vl), jax.jit(mha_reference)(ql, kl, vl))
    paged_args, live = _paged_case(paged_shape, dtype)
    paged = functools.partial(paged_attention, interpret=interpret)
    gathered = jax.jit(_paged_gather_path)
    got, want = paged(*paged_args), gathered(*paged_args)
    errs["paged"] = max(_rel_err(got[b], want[b])
                        for b in range(len(live)) if live[b])
    if np.any(np.asarray(got, np.float32)[live == 0]):
        raise AssertionError("paged kernel: a dead lane's output is not 0")
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    jax.block_until_ready(fwd(ql, kl, vl))
    steady_s = time.perf_counter() - t1
    paged_s = {}
    for name, fn in (("kernel", paged), ("gather_path", gathered)):
        t2 = time.perf_counter()
        jax.block_until_ready([fn(*paged_args) for _ in range(8)])
        paged_s[name] = round((time.perf_counter() - t2) / 8, 6)
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(
            f"a kernel disagrees with its reference beyond "
            f"{KERNEL_TOL}: {bad}")
    return {"setup_s": setup_s, "steady_s": steady_s, "interpret": interpret,
            "rel_err": {n: round(e, 5) for n, e in errs.items()},
            "tolerance": KERNEL_TOL, "paged_call_s": paged_s}


def trainer_phase(model_name: str = "resnet50", image_size: int = 224,
                  batch_per_chip: int = 256, steps: int = 8) -> dict:
    import jax
    import numpy as np

    from horovod_tpu.benchmark import _Rig

    everywhere = set(jax.devices())
    t0 = time.perf_counter()
    rig = _Rig(batch_per_chip, image_size, model_name, "sgd")
    for name, tree in (("params", rig.params), ("images", rig.images),
                       ("labels", rig.labels)):
        if _devices_of(tree) != everywhere:
            raise AssertionError(
                f"{name} live on {sorted(map(str, _devices_of(tree)))}, "
                f"not on every chip")
    p, bs, s = rig.params, rig.batch_stats, rig.opt_state
    p, bs, s, loss = rig.train_step(p, bs, s, rig.images, rig.labels)
    losses = [float(loss)]
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    pending = []
    for _ in range(steps):
        p, bs, s, loss = rig.train_step(p, bs, s, rig.images, rig.labels)
        pending.append(loss)
    losses += [float(x) for x in pending]       # host readback ends the clock
    steady_s = time.perf_counter() - t1
    if not np.all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not come down: {losses}")
    return {"setup_s": setup_s, "steady_s": steady_s, "steps": steps,
            "model": model_name, "batch_per_chip": batch_per_chip,
            "image_size": image_size, "loss_first": losses[0],
            "loss_last": losses[-1],
            "steady_images_per_sec_per_chip":
                steps * batch_per_chip / steady_s,
            "param_and_batch_devices": len(everywhere)}


def _post(url: str, doc: dict, timeout: float = 900.0):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")}


def _decode_program_text(engine) -> str:
    """The engine's decode program lowered at the shapes its scheduler
    calls it with (abstract arguments: nothing runs, nothing is
    donated)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serving.generation.kv_cache import (DecodeState,
                                                         SampleParams)

    b = engine.batcher
    lanes = lambda dtype, *rest: jax.ShapeDtypeStruct(  # noqa: E731
        (b.max_seqs, *rest), dtype)
    i32, f32 = lanes(jnp.int32), lanes(jnp.float32)
    state = DecodeState(
        tokens=i32, lengths=i32, live=i32, remaining=i32, eos=i32,
        sample=SampleParams(temperature=f32, top_k=i32, top_p=f32,
                            key=lanes(jnp.uint32, 2), emitted=i32))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine.params)
    pools = tuple(jax.ShapeDtypeStruct(shape, dtype)
                  for shape, dtype in b._pool_shapes)
    return b._decode_prog.lower(
        params, pools, lanes(jnp.int32, b.max_blocks), state).as_text()


def server_phase(cfg=None, requests=REQUESTS, **engine_kwargs) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.serving import GenerationEngine, InferenceServer

    cfg = cfg or TransformerConfig()
    model = Transformer(cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
               for n, _, _ in requests]

    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    engine = GenerationEngine(model, params=params, **engine_kwargs)
    server = InferenceServer(None, port=0, addr="127.0.0.1",
                             gen_engine=engine)
    try:
        url = f"http://127.0.0.1:{server.start()}/v1/generate"
        placement = {
            "params_devices": sorted(map(str, _devices_of(engine.params))),
            "kv_pool_devices": sorted(map(str, _devices_of(
                engine.batcher._pools)))}
        # warm-up: one request long enough for a second prefill chunk
        # compiles the prefill and decode programs; its per-token deadline
        # is lifted because a token that waits on a compile is not starved
        warm = rng.randint(0, cfg.vocab_size,
                           (max(map(len, prompts)),)).tolist()
        code, doc = _post(url, {"prompt": warm, "max_tokens": 2,
                                "deadline_ms": 900_000})
        if code != 200:
            raise AssertionError(f"warm-up request answered {code}: {doc}")
        # a TPU's decode step attends through the paged kernel, every
        # other backend through the gather path: never an interpreter
        compiled_kernel = "tpu_custom_call" in _decode_program_text(engine)
        if compiled_kernel != (jax.default_backend() == "tpu"):
            raise AssertionError(
                f"the decode program's lowered text "
                f"{'holds' if compiled_kernel else 'holds no'} "
                f"tpu_custom_call on backend {jax.default_backend()!r}")
        setup_s = time.perf_counter() - t0

        answers = [None] * len(requests)

        def client(i):
            n, new, sampled = requests[i]
            doc = {"prompt": prompts[i], "max_tokens": new}
            if sampled:
                doc.update(temperature=0.8, top_k=50, seed=1000 + i)
            answers[i] = _post(url, doc)

        t1 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        steady_s = time.perf_counter() - t1

        for (n, new, _), (code, doc) in zip(requests, answers):
            if code != 200 or len(doc.get("tokens", ())) != new:
                raise AssertionError(
                    f"request (prompt {n}, max_tokens {new}) answered "
                    f"{code}: {str(doc)[:300]}")
        if engine.allocator.in_use != 0:
            raise AssertionError(
                f"{engine.allocator.in_use} KV blocks still held after "
                f"every request finished")

        # greedy answers against the full forward, teacher-forced: one
        # padded length, so one compile
        greedy = [i for i, (_, _, sampled) in enumerate(requests)
                  if not sampled]
        width = max(len(prompts[i]) + requests[i][1] for i in greedy)
        forward = jax.jit(model.apply)
        worst_gap = worst_lp = 0.0
        exact = total = 0
        for i in greedy:
            tokens, logprobs = answers[i][1]["tokens"], \
                answers[i][1]["logprobs"]
            seq = prompts[i] + tokens
            row = np.zeros((1, width), np.int32)
            row[0, :len(seq)] = seq
            logits = np.asarray(forward(engine.params, jnp.asarray(row)))[0]
            for j, (tok, lp) in enumerate(zip(tokens, logprobs)):
                ref = logits[len(prompts[i]) - 1 + j]
                ref_lp = ref - (np.log(np.sum(np.exp(ref - ref.max())))
                                + ref.max())
                worst_gap = max(worst_gap, float(ref.max() - ref[tok]))
                worst_lp = max(worst_lp, abs(float(ref_lp[tok]) - lp))
                exact += int(np.argmax(ref) == tok)
                total += 1
        if worst_gap > LOGIT_TOL or worst_lp > LOGIT_TOL:
            raise AssertionError(
                f"greedy decode left the reference forward: logit gap "
                f"{worst_gap:.4f}, logprob gap {worst_lp:.4f}, tolerance "
                f"{LOGIT_TOL}")
    finally:
        server.close()
    new_tokens = sum(new for _, new, _ in requests)
    return {"setup_s": setup_s, "steady_s": steady_s,
            "requests": len(requests), "new_tokens": new_tokens,
            "steady_tokens_per_sec": new_tokens / steady_s,
            "greedy_tokens_checked": total, "greedy_exact_argmax": exact,
            "worst_logit_gap": round(worst_gap, 5),
            "worst_logprob_gap": round(worst_lp, 5), "tolerance": LOGIT_TOL,
            "decode_compiled_kernel": compiled_kernel, **placement}


def ring_train_phase(devices, cfg=None, steps: int = 2,
                     interpret: bool = False) -> dict:
    import jax
    import numpy as np
    import optax

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import MeshConfig, make_training_mesh
    from horovod_tpu.parallel.train import make_transformer_train_step

    cfg = cfg or TransformerConfig()
    t0 = time.perf_counter()
    mesh = make_training_mesh(MeshConfig(dp=-1, sp=2), devices)
    bundle = make_transformer_train_step(cfg, mesh, attention_kind="ring",
                                         interpret=interpret)
    batch = 2 * mesh.shape["dp"] * mesh.shape["fsdp"]
    rng = np.random.RandomState(0)
    tokens, targets = (
        jax.device_put(rng.randint(0, cfg.vocab_size,
                                   (batch, cfg.max_seq_len)).astype(np.int32),
                       bundle.batch_sharding) for _ in range(2))
    lowered = bundle.step.lower(bundle.params, bundle.opt_state, tokens,
                                targets)
    compiled_kernel = "tpu_custom_call" in lowered.as_text()
    if not interpret and not compiled_kernel:
        raise AssertionError(
            "the lowered train step holds no tpu_custom_call: ring "
            "attention did not take the compiled Pallas kernel")
    step = lowered.compile()

    # the same params and batch through default attention (before the
    # step donates the params)
    reference = Transformer(dataclasses.replace(cfg, attention_fn=None))
    ref_loss = float(jax.jit(
        lambda p, t, y: optax.softmax_cross_entropy_with_integer_labels(
            reference.apply({"params": p}, t), y).mean())(
                bundle.params, tokens, targets))
    params, opt_state, loss = step(bundle.params, bundle.opt_state, tokens,
                                   targets)
    first = float(loss)
    setup_s = time.perf_counter() - t0
    if not abs(first - ref_loss) <= LOSS_TOL:
        raise AssertionError(
            f"ring-attention loss {first:.5f} vs default-attention loss "
            f"{ref_loss:.5f}: beyond {LOSS_TOL}")
    t1 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    last = float(loss)
    steady_s = time.perf_counter() - t1
    if not np.isfinite(last) or not last < first:
        raise AssertionError(f"loss did not come down: {first} -> {last}")
    return {"setup_s": setup_s, "steady_s": steady_s, "steps": steps,
            "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
            "compiled_kernel": compiled_kernel, "interpret": interpret,
            "loss_ring": first, "loss_default_attention": ref_loss,
            "tolerance": LOSS_TOL, "loss_last": last,
            "steady_tokens_per_sec": steps * batch * cfg.max_seq_len
            / steady_s}


def _timed(fn):
    """pipeline/expert phases compile and run once: all of it is set-up."""
    def phase(devices):
        t0 = time.perf_counter()
        fn(devices)
        return {"setup_s": time.perf_counter() - t0, "steady_s": 0.0}
    return phase


# ------------------------------------------------------------------ main

def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU — jax's default backend is "
            f"{devices[0].platform!r} ({devices[0].device_kind}). This "
            f"script proves the system on the chip and does not run "
            f"anywhere else.\n")
        return 1

    from __graft_entry__ import expert_phase, pipeline_phase
    from horovod_tpu.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    print(json.dumps({"phase": "start", **_stamp(),
                      "compile_cache": cache_dir}), flush=True)

    phases = [("kernel", kernel_phase), ("trainer", trainer_phase),
              ("server", server_phase)]
    multichip = [("ring_train", ring_train_phase),
                 ("pipeline", _timed(pipeline_phase)),
                 ("experts", _timed(expert_phase))]
    if len(devices) >= 4:
        phases += [(name, lambda fn=fn: fn(devices)) for name, fn in multichip]
    failed = []
    for name, fn in phases:
        try:
            result = {"ok": True, **fn()}
        except Exception as e:  # noqa: BLE001 — every phase gets its turn
            traceback.print_exc()
            failed.append(name)
            result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        for key in ("setup_s", "steady_s"):
            if key in result:
                result[key] = round(result[key], 3)
        print(json.dumps({"phase": name, **_stamp(), **result}), flush=True)
    if len(devices) < 4:
        for name, _ in multichip:
            print(json.dumps({"phase": name, **_stamp(), "skipped":
                              f"device_count {len(devices)} < 4"}),
                  flush=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
