"""``LongcatFlash`` served by ``GenerationEngine``: one chip's share of a
32-chip deployment, under open-loop traffic.

Set-up: the model and its bfloat16 weights (router float32) on the
device from the seed in one jitted call; the engine at the
configuration's knobs; one warm-up request that compiles or loads the
prefill and decode programs; the ``correct`` check; then the lanes are
filled and the window runs. The window and the client are
``serve_transformer``'s own (``measure``, ``_Client``), loaded as this
runner's copy of that module. The check is this runner's: three numbers
against the float32 reference, each held to a limit (:func:`compare`),
and the dtypes where the configuration states float32
(:func:`lowered_precisions`).
"""

import os
import re
import time

import numpy as np

from perfbench.harness import core

#: The limits of :func:`compare`, each between two readings on the v5e
#: (PERF.md, section 6, PR 27; ``perfbench/tools/longcat_tolerance.py``):
#: what bfloat16 weights and activations through the paged latent cache
#: read against the float32 reference over this PR's seeds, and what the
#: reference reads with one fault at a time.
#:
#: A served greedy token's reference logit under the reference's best
#: (the reference's logits have a spread of 1.57 and its best two lie
#: 0.25 apart in the median): clean up to 0.126 over this PR's seeds; a
#: rotary position off by one 3.06, a stale cache block 4.08, a missing
#: shortcut 1.15, float8 activations 1.20 at the least. Only this number
#: sees the stale block: it alone reads contexts of over a hundred blocks.
LOGIT_TOL = 0.35
#: Root mean square of served less reference log-probability over 2048
#: served tokens: clean 0.0598-0.0621 over this PR's seeds; the attention
#: softmax in bfloat16 0.0705-0.0715, float8 activations (the nearest
#: precision below the bfloat16 the configuration states) 0.69-0.71, a
#: missing shortcut 0.50, a rotary position off by one 1.58. A router in
#: bfloat16 reads 0.0598-0.0615, as clean: :func:`lowered_precisions`.
LOGPROB_RMS_TOL = 0.066
#: Largest difference of a held expert's picks, counters against the
#: reference's router, over the mean: clean 0.025-0.079; one held expert
#: dropped 0.94-1.17.
PICKS_TOL = 0.3

#: Seconds of a traced run's profile after the window's first arrival
#: (:func:`traced_seconds`): a chunk takes 0.15 s and rides on every
#: iteration until the prompt is in, so this holds a dozen of them.
TRACED_AFTER_ARRIVAL_S = 3.0

SERVE = core.load_module(
    os.path.join(core.BENCH_DIR, "runners", "serve_transformer.py"),
    "perfbench_runner_serve_transformer_for_longcat")


def model_config(cfg: dict):
    """``LongcatFlashConfig`` of the configuration file (the source's
    key names; ``n_routed_experts`` there counts the experts held)."""
    import jax.numpy as jnp

    from horovod_tpu.models import LongcatFlashConfig

    first, end = cfg["held_experts"]
    if end - first != cfg["n_routed_experts"] or \
            cfg["n_routed_experts_published"] + cfg["zero_expert_num"] \
            != cfg["router_width"]:
        raise ValueError("held_experts, n_routed_experts and router_width "
                         "of the configuration disagree")
    return LongcatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_routed_experts=cfg["n_routed_experts_published"],
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        held_experts=(first, end),
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def make_weights(model, seed: int):
    """The model's weights on the device, from the seed, in one jitted
    call, in the dtypes the model holds them in."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(core.seed_key(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    return params


def lowered_precisions(model, params, eng: dict) -> list:
    """Where the served forward computes below the float32 that the
    configuration states for the router and for every softmax, read
    from the traced paged forward of the model the engine serves, at a
    prefill chunk's shape (expanded attention) and a decode step's
    (absorbed): the router's matmul, every ``exp`` and every ``top_k``
    take float32. Empty when all do. A router in bfloat16 moves a served
    log-probability by a thirtieth of what the bfloat16 activations'
    own rounding moves it (PERF.md, section 6, PR 27), so no number
    computed from served tokens can hold it; its dtype can."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import PagedCache
    from horovod_tpu.serving.generation import kv_cache as kvc

    cfg = model.cfg
    router_width = cfg.n_routed_experts + cfg.zero_expert_num
    pools = jax.eval_shape(
        lambda: kvc.make_pools(cfg, eng["num_blocks"], eng["block_size"]))
    tables = cfg.max_seq_len // eng["block_size"]
    found = []

    def walk(jaxpr, where):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            dtypes = [v.aval.dtype for v in eqn.invars
                      if hasattr(v.aval, "dtype")]
            if (name in ("exp", "top_k")
                    or (name == "dot_general"
                        and eqn.outvars[0].aval.shape[-1:] == (router_width,))
                    ) and any(d != jnp.float32 for d in dtypes):
                found.append(f"{where}: {name} of "
                             f"{[str(d) for d in dtypes]} "
                             f"{eqn.outvars[0].aval.shape}")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, where)

    for where, (lanes, width) in (("prefill", (1, eng["prefill_chunk"])),
                                  ("decode", (eng["max_seqs"], 2))):
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        cache = PagedCache(pools, i32(lanes, tables), i32(lanes), i32(lanes))
        walk(jax.make_jaxpr(
            lambda p, t, c: model.apply(p, t, cache=c,
                                        mutable=["moe_stats"]))(
                params, i32(lanes, width), cache).jaxpr, where)
    return found


def _greedy(engine, requests):
    """``[(prompt, n)]`` served greedily, all in flight together:
    ``[(prompt, tokens, logprobs)]``."""
    seqs = [engine.submit(p, max_tokens=n, deadline_ms=900_000.0)
            for p, n in requests]
    return [(p, engine.result(seq, timeout=900.0), list(seq.logprobs))
            for (p, n), seq in zip(requests, seqs)]


def _moe_counters():
    from horovod_tpu import metrics as hvd_metrics

    return {k: v for k, v in hvd_metrics.snapshot().items()
            if k.startswith("hvd_tpu_gen_moe_")}


def serve_check(ctx, engine):
    """What :func:`compare` holds against the reference, served through
    the engine outside the window: the greedy requests of
    ``check_sample`` (inside a chunk, across a chunk boundary, several
    chunks and over a hundred cache blocks), then the batch of
    ``check_logprobs`` (equal lengths, all lanes of it decoding
    together) with the routing counters read before and after it.
    ``None`` where a request returned fewer tokens than asked."""
    cfg = ctx.config
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    draw = lambda n: rng.integers(0, cfg["vocab_size"], n).tolist()  # noqa: E731
    sample = _greedy(engine, [(draw(p), n) for p, n in cfg["check_sample"]])
    b = cfg["check_logprobs"]
    requests = [(draw(b["prompt_tokens"]), b["new_tokens"])
                for _ in range(b["requests"])]
    before = _moe_counters()
    batch = _greedy(engine, requests)
    after = _moe_counters()
    asked = [n for _, n in cfg["check_sample"]] \
        + [b["new_tokens"]] * b["requests"]
    if [len(toks) for _, toks, _ in sample + batch] != asked:
        return None
    return {"sample": sample, "batch": batch,
            "counters": {k: v - before.get(k, 0.0) for k, v in after.items()}}


def compare(served, params, plain, settings):
    """The served path against ``plain`` (the float32 reference module)
    under ``settings`` (the configuration). Returns ``(ok, numbers)``;
    ``ok`` where each number is within its limit:

    - ``worst_logit_gap`` (``LOGIT_TOL``): over the ``check_sample``
      requests, teacher-forced, how far a served token's reference logit
      lies under the reference's best at its position, at most;
    - ``logprob_rms`` (``LOGPROB_RMS_TOL``): over the batch's served
      tokens, the root mean square of served log-probability less the
      reference's log-probability of the same token: a mean over a
      thousand positions, which rounding sets a level for and any lower
      precision raises;
    - ``picks_off`` (``PICKS_TOL``): over the batch's positions, the
      largest difference between a held expert's picks by the program's
      routing counters and by the reference's router, as a share of the
      mean picks of a held expert; the live tokens counted have to be
      the reference's exactly."""
    import jax
    import jax.numpy as jnp

    # a request at a time, all at one width: the reference's float32
    # activations and scores of the three at once, beside 12.4 GB of
    # weights and pool, came within 0.13 GB of the chip's memory
    width = max(len(p) + len(toks) for p, toks, _ in served["sample"])
    worst = 0.0
    for p, toks, _ in served["sample"]:
        row = np.zeros((1, width), np.int32)
        row[0, :len(p) + len(toks)] = p + toks
        logits = np.asarray(plain.forward(params, jnp.asarray(row),
                                          settings))[0]
        worst = max([worst] + [float(logits[len(p) - 1 + j].max()
                                     - logits[len(p) - 1 + j, tok])
                               for j, tok in enumerate(toks)])

    # the batch in one pass, without each request's last token, which
    # the engine never fed back: logits at every served position, and
    # the router's picks at every position the programs counted
    batch = served["batch"]
    first = len(batch[0][0]) - 1
    rows = np.asarray([p + toks[:-1] for p, toks, _ in batch], np.int32)
    tally = []
    logp = np.asarray(jax.nn.log_softmax(
        plain.forward(params, jnp.asarray(rows), settings, tally)[:, first:],
        axis=-1))
    toks = np.asarray([t for _, t, _ in batch])
    ref_logp = np.take_along_axis(logp, toks[..., None], axis=-1)[..., 0]
    off = np.asarray([lp for _, _, lp in batch]) - ref_logp
    rms = float(np.sqrt(np.mean(np.square(off))))

    # by expert id, over every expert either side says is held
    lo, hi = settings["held_experts"]
    picked = sum((np.asarray(t) for t in tally),
                 np.zeros(settings["router_width"], np.int64))
    ref_picks = {e: float(picked[e]) for e in range(lo, hi)}
    count = served["counters"]
    got_picks = {int(re.search(r'expert="(\d+)"', k).group(1)): v
                 for k, v in count.items()
                 if k.startswith("hvd_tpu_gen_moe_held_expert_picks_total")}
    worst_pick = max(abs(got_picks.get(e, 0.0) - ref_picks.get(e, 0.0))
                     for e in set(got_picks) | set(ref_picks))
    picks_off = worst_pick / max(np.mean(list(ref_picks.values())), 1.0)
    tokens_agree = count.get("hvd_tpu_gen_moe_tokens_total") \
        == float(rows.size * len(tally))
    numbers = {"worst_logit_gap": worst, "logit_tolerance": LOGIT_TOL,
               "logprob_rms": rms, "logprob_rms_tolerance": LOGPROB_RMS_TOL,
               "picks_off": float(picks_off), "picks_tolerance": PICKS_TOL,
               "moe_tokens_agree": bool(tokens_agree),
               "served_positions": int(toks.size)}
    ok = worst <= LOGIT_TOL and rms <= LOGPROB_RMS_TOL \
        and picks_off <= PICKS_TOL and tokens_agree
    return bool(ok), numbers


class Server(SERVE.Server):
    """The engine of one run: weights from the seed, both programs warm,
    and the ``correct`` check made. ``serve_transformer.Server``'s
    ``_on_step`` and ``close`` serve as they are."""

    def __init__(self, ctx: core.Context):
        from horovod_tpu.models import LongcatFlash
        from horovod_tpu.serving import GenerationEngine

        cfg = ctx.config
        eng = cfg["engine"]
        self.ctx, self.vocab = ctx, cfg["vocab_size"]
        self.prefill_chunk = eng["prefill_chunk"]
        model = LongcatFlash(model_config(cfg))
        plain = ctx.load_reference()

        t_warm = time.perf_counter()
        params = make_weights(model, ctx.seed)
        ctx.mark("weights")
        self.steps = ctx.spans.setdefault("steps", [])  # (time, phase, ids)
        self.in_use_peak = 0
        self.mark_steps = False
        self._open_mark = None
        self.engine = GenerationEngine(
            model, params=params, max_seqs=eng["max_seqs"],
            block_size=eng["block_size"], num_blocks=eng["num_blocks"],
            prefill_chunk=eng["prefill_chunk"], on_step=self._on_step)
        try:
            # warm-up: two prefill chunks and a few decode steps compile
            # or load both programs; the deadline is lifted, since a
            # token that waits on a compile is not starved
            rng = np.random.default_rng(
                np.random.SeedSequence([ctx.seed, 8]))
            warm = rng.integers(0, self.vocab,
                                eng["prefill_chunk"] + 1).tolist()
            s = ctx.traffic.get("sampling") or {}
            self.engine.result(self.engine.submit(
                warm, max_tokens=3, deadline_ms=1_800_000.0,
                temperature=s.get("temperature"), top_p=s.get("top_p"),
                seed=1), timeout=1800.0)
            ctx.facts["warmup_s"] = time.perf_counter() - t_warm
            ctx.mark("warm")
            served = serve_check(ctx, self.engine)
            self.checked, numbers = (False, {"short_request": True}) \
                if served is None \
                else compare(served, params["params"], plain, cfg)
            lowered = lowered_precisions(model, params, eng)
            self.checked = self.checked and not lowered
        except BaseException:
            self.engine.close()
            raise
        ctx.mark("checked")
        ctx.info(check="greedy requests against the float32 reference",
                 ok=self.checked, below_float32=lowered, **numbers)


def traced_seconds(ctx: core.Context, tr: dict, vocab: int) -> float:
    """Seconds from the start of the window that a ``--trace 1`` run has
    the profiler on: the harness's ``core.TRACE_SECONDS``, or as long as
    this seed's first arrival takes to be sent and to prefill for
    :data:`TRACED_AFTER_ARRIVAL_S`. The window opens with every
    preloaded lane past its prefill, and at a quarter of a request a
    second its 13 arrivals leave the first six seconds empty in a fifth
    of the seeds (the first falls after 8.3 s in a tenth, after 15.8 s
    in a hundredth): such a trace holds no ``jit__prefill``, and the two
    metrics that read it have nothing to read. The generator gives the
    arrivals from the seed, so the span is known before the window."""
    arrivals = SERVE.traffic.open_loop(tr, vocab, ctx.seed, ctx.seconds)
    if not arrivals:
        return core.TRACE_SECONDS
    return max(core.TRACE_SECONDS,
               arrivals[0].due_s + TRACED_AFTER_ARRIVAL_S)


def measure(ctx: core.Context, server: Server, tr: dict) -> dict:
    """``serve_transformer.measure``, its profiler on for
    :func:`traced_seconds`: that function reads the span off
    ``core.TRACE_SECONDS`` when the window opens."""
    harness_s = core.TRACE_SECONDS
    if ctx.tracing:
        core.TRACE_SECONDS = traced_seconds(ctx, tr, server.vocab)
        ctx.info(traced_s=min(core.TRACE_SECONDS, ctx.seconds))
    try:
        return SERVE.measure(ctx, server, tr)
    finally:
        core.TRACE_SECONDS = harness_s


def run(ctx: core.Context) -> dict:
    server = Server(ctx)
    try:
        return measure(ctx, server, ctx.traffic)
    finally:
        server.close()
