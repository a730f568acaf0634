#!/usr/bin/env python3
"""What the limits of the ``longcat-flash`` cell's ``correct`` lie
between: one process; for each seed one engine, the check's greedy
requests served once (``serve_longcat.serve_check``), then the runner's
own comparison (``serve_longcat.compare``) against the plain reference
run clean and with one fault at a time.

    python3 perfbench/tools/longcat_tolerance.py [--seeds N,N] [--rehearse]

Prints one JSON line a reading: the fault's name (``clean`` for none),
``correct`` as the runner would have reported it, and the check's three
numbers beside their limits. ``clean`` has to read ``correct: true`` on
every seed and every fault ``false``. The faults are made in the
*reference* (a fault on either side shows as the same disagreement):
the router in bfloat16, the attention softmax in bfloat16 (the two
places where the configuration states float32), the activations in
float8 (the nearest precision below the bfloat16 it states for them), a
rotary position off by one, a stale cache block, the last held expert
dropped, the shortcut missing. Last, for the first seed, the faults that
no served token shows, made in the *program*: the router's matmul, the
router's softmax and choice, and the attention softmax in bfloat16,
which ``serve_longcat.lowered_precisions`` has to name.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "longcat-flash.long_prompt_steady"


def _faults(ref, cfg):
    """name -> (patches of the reference module, settings) ."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16

    def route_bf16(u, router, bias, k, scale):
        s = jax.nn.softmax((u.astype(bf16) @ router.astype(bf16)), axis=-1)
        s = s.astype(jnp.float32)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        chosen = jnp.zeros(s.shape, jnp.bool_).at[
            jnp.arange(s.shape[0])[:, None], idx].set(True)
        return jnp.where(chosen, s * scale, 0.0)

    def heads_softmax_bf16(q_nope, q_rope, c, kr, w_uk, w_uv):
        with jax.default_matmul_precision(ref.PRECISION):
            k_nope = jnp.einsum("btr,rhd->bthd", c, w_uk.astype(jnp.float32))
            v = jnp.einsum("btr,rhd->bthd", c, w_uv.astype(jnp.float32))
            scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                      + jnp.einsum("bshd,btd->bhst", q_rope, kr))
            scores = scores / jnp.sqrt(jnp.float32(
                q_nope.shape[-1] + q_rope.shape[-1]))
            s = scores.shape[-1]
            causal = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf).astype(bf16), axis=-1)
            return jnp.einsum("bhst,bthd->bshd", probs.astype(jnp.float32),
                              v)

    def rotate_queries_one_late(x, theta):
        if x.ndim == 3:             # the shared key: as it should be
            return clean_rotate(x, theta)
        late = clean_rotate(jnp.pad(x, ((0, 0), (1, 0), (0, 0), (0, 0))),
                            theta)
        return late[:, 1:]

    def attention_stale_block(p, x, st):
        # what a stale cache block reads: positions 64..127 hold the
        # activations of positions 0..63
        if x.shape[1] >= 128:
            x = x.at[:, 64:128].set(x[:, 0:64])
        return clean_attention(p, x, st)

    def layer_without_shortcut(p, h, st):
        eps = st["rms_norm_eps"]
        h = h + ref.attention(p["attn_0"],
                              ref._rms(h, p["input_layernorm_0"], eps), st)
        u = ref._rms(h, p["post_attention_layernorm_0"], eps)
        f = p["mlp_0"]
        h = h + ref._swiglu(u, f["gate_proj"], f["up_proj"], f["down_proj"])
        h = h + ref.attention(p["attn_1"],
                              ref._rms(h, p["input_layernorm_1"], eps), st)
        f = p["mlp_1"]
        return h + ref._swiglu(
            ref._rms(h, p["post_attention_layernorm_1"], eps),
            f["gate_proj"], f["up_proj"], f["down_proj"])

    def rms_then_float8(x, w, eps):
        # what a sublayer reads, rounded to 3 bits of mantissa
        return clean_rms(x, w, eps).astype(jnp.float8_e4m3fn).astype(
            jnp.float32)

    clean_rotate, clean_attention, clean_rms = (ref._rotate, ref.attention,
                                                ref._rms)
    first, end = cfg["held_experts"]
    return {
        "router_bf16": ({"_route": route_bf16}, cfg),
        "softmax_bf16": ({"_heads": heads_softmax_bf16}, cfg),
        "activations_float8": ({"_rms": rms_then_float8}, cfg),
        "rotary_position_off_by_one": (
            {"_rotate": rotate_queries_one_late}, cfg),
        "stale_block": ({"attention": attention_stale_block}, cfg),
        # the last held expert's picks counted as absent
        "dropped_expert": ({}, dict(cfg, held_experts=[first, end - 1])),
        "missing_shortcut": ({"layer": layer_without_shortcut}, cfg),
    }


def _program_faults():
    """name -> (module, attribute, replacement): the served model's own
    code computing below float32 where the configuration states it."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import longcat_flash as lf

    bf16 = jnp.bfloat16
    clean_dot = jnp.dot

    def dot_bf16(a, b, **kw):
        if a.dtype == jnp.float32 and b.dtype == jnp.float32:  # the router's
            return clean_dot(a.astype(bf16), b.astype(bf16))
        return clean_dot(a, b, **kw)

    def route_bf16(logits, bias, k, scale):
        s = jax.nn.softmax(logits.astype(bf16), axis=-1)
        _, idx = jax.lax.top_k(s + bias.astype(bf16), k)
        return idx.astype(jnp.int32), \
            jnp.take_along_axis(s, idx, axis=-1).astype(jnp.float32) * scale

    def softmax_bf16(scores, mask):
        return jax.nn.softmax(
            jnp.where(mask, scores, -1e30).astype(bf16), axis=-1)

    return {"program_router_matmul_bf16": (jnp, "dot", dot_bf16),
            "program_router_softmax_bf16": (lf, "route_topk", route_bf16),
            "program_attention_softmax_bf16": (lf, "_masked_softmax",
                                               softmax_bf16)}


def main() -> int:
    from perfbench.harness import core

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="2147483659")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    spec = core.load_spec()
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for seed in [int(x) for x in args.seeds.split(",")]:
        ctx = core.Context(spec, workload, seed, 1.0, 0, args.rehearse,
                           time.perf_counter())
        ctx.claim_devices()
        ctx.setup_compile_cache()
        from horovod_tpu.models import LongcatFlash
        from horovod_tpu.serving import GenerationEngine

        runner, ref, cfg = ctx.load_runner(), ctx.load_reference(), ctx.config
        eng = cfg["engine"]
        model = LongcatFlash(runner.model_config(cfg))
        params = runner.make_weights(model, ctx.seed)
        engine = GenerationEngine(
            model, params=params, max_seqs=eng["max_seqs"],
            block_size=eng["block_size"], num_blocks=eng["num_blocks"],
            prefill_chunk=eng["prefill_chunk"])
        try:
            served = runner.serve_check(ctx, engine)
        finally:
            engine.close()
        faults = dict(clean=({}, cfg), **_faults(ref, cfg))
        for name, (patches, settings) in faults.items():
            saved = {k: getattr(ref, k) for k in patches}
            for k, v in patches.items():
                setattr(ref, k, v)
            try:
                ok, numbers = runner.compare(served, params["params"], ref,
                                             settings)
            finally:
                for k, v in saved.items():
                    setattr(ref, k, v)
            print(json.dumps(dict(
                reading=name, seed=ctx.seed, correct=ok,
                platform=ctx.devices[0].platform, **numbers)), flush=True)
        if seed == int(args.seeds.split(",")[0]):
            clean_ok, _ = runner.compare(served, params["params"], ref, cfg)
            found = {"clean": runner.lowered_precisions(model, params, eng)}
            for name, (module, attr, fault) in _program_faults().items():
                saved = getattr(module, attr)
                setattr(module, attr, fault)
                try:
                    found[name] = runner.lowered_precisions(model, params,
                                                            eng)
                finally:
                    setattr(module, attr, saved)
            for name, lowered in found.items():
                print(json.dumps(dict(
                    reading="precisions_" + name, seed=ctx.seed,
                    correct=clean_ok and not lowered,
                    below_float32=lowered[:4], places=len(lowered))),
                    flush=True)
        del params, engine, served
    return 0


if __name__ == "__main__":
    sys.exit(main())
