"""``Glm47Flash`` served by ``GenerationEngine`` with ``spec_mode='mtp'``:
one stage (the embedding, the dense layer, four MoE layers with all 64
experts, the head and the MTP module) of an 8-stage pipeline, under a
closed loop of short questions and long greedy answers, prefix cache on.
Every decode step is an MTP verify step: two columns a lane, the draft
from the MTP module on the device.

Set-up: the model and its bfloat16 weights on the device from the seed
in one jitted call, made to draft (:func:`make_weights`); the engine at
the configuration's knobs; one warm-up request that compiles or loads
the prefill and verify programs; the ``correct`` check; then the callers
run until the traffic's ``start_after``-th completion and the window
opens. The window, the client, the number-taking and the traffic's
table walk are ``serve_deepseek_v32``'s (``measure``, ``_LongDocs``),
loaded as this runner's copy of that module.

The check (:func:`serve_check`, :func:`compare`): the greedy requests
of ``check_sample`` (as many as the engine has lanes, replies that
cross several blocks, one prompt near the table's end), all in flight
together, then a sampled batch. Every served token is teacher-forced
through the float32 reference's main model, and every draft that the
engine's own prefill and verify programs returned for the greedy
requests is held against the reference's MTP module at the position it
was drafted at.
"""

import os
import time

import numpy as np

from perfbench.harness import core

#: The limits of :func:`compare`, each between two readings on the v5e
#: (PERF.md, section 6, has every reading with its seed;
#: ``perfbench/tools/glm47_flash_tolerance.py`` takes them). The residual
#: stream carries the current token at RMS ``draft_gain`` (24), so what
#: the layers add moves a logit by a little and the stream's own
#: rounding is what a lower precision shows.
#:
#: A served greedy token's reference logit under the reference's best,
#: over the ``check_sample`` requests' 6144 served tokens: clean
#: 0.0206-0.0210 over two seeds (near-ties the bfloat16 program breaks
#: the other way), activations in float8 0.167.
LOGIT_TOL = 0.05
#: Root mean square of served less reference log-probability over the
#: sampled batch's 256 served tokens: clean 0.0050-0.0058 over eight
#: seeds, activations in float8, the nearest precision below the
#: bfloat16 stated, 0.0415-0.0450.
LOGPROB_RMS_TOL = 0.02
#: How far a draft's logit in the reference's MTP module lies under the
#: module's best at the position it was drafted at, on average over
#: every draft the engine's programs returned for the ``check_sample``
#: requests (about 3400): clean 1.5e-5-3.9e-5 over two seeds (one draft
#: in a hundred a near-tie broken the other way); activations in
#: float8 3.9e-3, the MTP module's alone in float8 3.9e-3, the module
#: without the main model's hidden state 3.2e-3.
DRAFT_GAP_MEAN_TOL = 0.001
#: The reference's rows are padded to a multiple of this (it is causal:
#: a position does not see the padding), so few shapes compile.
ROW_MULTIPLE = 256

DSV = core.load_module(
    os.path.join(core.BENCH_DIR, "runners", "serve_deepseek_v32.py"),
    "perfbench_runner_serve_deepseek_v32_for_glm47_flash")
SERVE = DSV.SERVE


def model_config(cfg: dict, **overrides):
    """``Glm47FlashConfig`` of the configuration file (the source's key
    names)."""
    import jax.numpy as jnp

    from horovod_tpu.models import Glm47FlashConfig

    if cfg.get("rope_scaling") is not None:
        raise ValueError("GLM-4.7-Flash's rotary is unscaled")
    first, end = cfg["held_experts"]
    if end - first != cfg["n_routed_experts"]:
        raise ValueError("held_experts and n_routed_experts of the "
                         "configuration disagree")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "n_group", "topk_group", "n_shared_experts",
            "num_nextn_predict_layers", "max_position_embeddings")
    sizes = {k: int(cfg[k]) for k in keys}
    sizes.update(
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), held_experts=(first, end),
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    sizes.update(overrides)
    return Glm47FlashConfig(**sizes)


def make_weights(model, seed: int, gain: float):
    """The model's weights on the device, from the seed, in one jitted
    call, in the dtypes the model holds them in, made to draft (the
    configuration's ``assumed``): seeded normal(0.02), then the
    embedding table times ``gain / 0.02`` and ``gain`` times the
    identity added to the MTP module's ``eh_proj`` where it reads the
    next token's embedding."""
    import jax
    import jax.numpy as jnp

    def build(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))
        p = dict(params["params"])
        emb = p["embed_tokens"]
        p["embed_tokens"] = (emb.astype(jnp.float32)
                             * (gain / 0.02)).astype(emb.dtype)
        mtp = dict(p["mtp"])
        eh = mtp["eh_proj"]
        d = eh.shape[1]
        mtp["eh_proj"] = eh.at[:d].add(
            (gain * jnp.eye(d, dtype=jnp.float32)).astype(eh.dtype))
        p["mtp"] = mtp
        return {"params": p}

    params = jax.jit(build)(core.seed_key(seed))
    jax.block_until_ready(params)
    return params


# -- the check ----------------------------------------------------------------

def _record_drafts(engine, drafts: dict):
    """An ``on_step`` hook that also records, for every sequence that
    just took a step and decodes on, the draft the engine's own programs
    returned (the scheduler's mirror of the lane's ``DecodeState.draft``)
    beside the position the MTP module drafted it at: the sequence's last
    cached position. ``drafts`` maps a sequence id to its
    ``[(position, draft)]``."""
    hook = engine.batcher.on_step

    def on_step(phase, ids):
        if hook is not None:
            hook(phase, ids)
        running = {s.id: s for s in engine.batcher._running}
        for i in ids:
            s = running.get(i)
            if s is not None and s.state == "decode":
                drafts.setdefault(i, []).append((s.cache_len - 1, s.draft))

    return hook, on_step


def serve_check(ctx, engine, model, params):
    """What :func:`compare` holds against the reference, served through
    the engine outside the window: the greedy requests of
    ``check_sample``, all in flight together, with every draft the
    prefill and verify programs return recorded at its position; then
    the batch of ``check_logprobs``, sampled at its ``temperature``
    under seeds drawn from the run's. ``None`` where a request returned
    fewer tokens than asked."""
    cfg = ctx.config
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    draw = lambda n: rng.integers(0, cfg["vocab_size"], n).tolist()  # noqa: E731
    requests = [(draw(p), n) for p, n in cfg["check_sample"]]
    drafts = {}
    hook, engine.batcher.on_step = _record_drafts(engine, drafts)
    try:
        seqs = [engine.submit(p, max_tokens=n, deadline_ms=1_800_000.0)
                for p, n in requests]
        sample = [(p, engine.result(seq, timeout=1800.0),
                   list(seq.logprobs))
                  for (p, _), seq in zip(requests, seqs)]
    finally:
        engine.batcher.on_step = hook
    b = cfg["check_logprobs"]
    batch = DSV._serve(engine, [(draw(b["prompt_tokens"]), b["new_tokens"])
                                for _ in range(b["requests"])],
                       temperature=b["temperature"],
                       seeds=rng.integers(1, 2 ** 31 - 1,
                                          b["requests"]).tolist())
    asked = [n for _, n in cfg["check_sample"]] \
        + [b["new_tokens"]] * b["requests"]
    if [len(toks) for _, toks, _ in sample + batch] != asked:
        return None
    return {"sample": sample, "batch": batch,
            "drafts": [drafts.get(seq.id, []) for seq in seqs]}


def _row(tokens, multiple: int):
    """``tokens`` as a ``(1, S)`` row padded to a multiple of
    ``multiple``: the reference is causal, so what it gives at a
    position does not see the padding, and few shapes compile."""
    import jax.numpy as jnp

    n = -(-len(tokens) // multiple) * multiple
    return jnp.asarray([list(tokens) + [0] * (n - len(tokens))], jnp.int32)


def compare(served, params, plain, settings, faults=()):
    """The served path against ``plain`` (the float32 reference module,
    computing ``faults`` wrongly: the tolerance tool's) under
    ``settings`` (the configuration). Returns ``(ok, numbers)``; ``ok``
    where each number is within its limit:

    - ``worst_logit_gap`` (``LOGIT_TOL``): over the ``check_sample``
      requests, teacher-forced, how far a served token's reference logit
      lies under the reference's best at its position, at most;
    - ``logprob_rms`` (``LOGPROB_RMS_TOL``): over the batch's served
      (sampled) tokens, the root mean square of served log-probability
      less the reference's log-probability of the same token;
    - ``draft_gap_mean`` (``DRAFT_GAP_MEAN_TOL``): over every draft the
      engine's prefill and verify programs returned for the
      ``check_sample`` requests, how far the drafted token's logit in
      the reference's MTP module, at the position it was drafted at,
      lies under the module's best there, on average
      (``draft_worst_gap``, the largest, and ``draft_agreement``, the
      share of drafts that are the reference's best, have no limit)."""
    import jax

    gaps, draft_gaps, agree = [], [], []
    for (p, toks, _), drafts in zip(served["sample"], served["drafts"]):
        row = _row(p + toks, ROW_MULTIPLE)
        hidden = plain.main(params, row, settings, faults)
        at = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        logits = np.asarray(plain.head(params, hidden, at)[0])
        picked = np.arange(len(toks)), np.asarray(toks)
        gaps.append(float(np.max(logits.max(axis=-1) - logits[picked])))
        if drafts:
            # one shape a reply length: the positions padded with the last
            k = len(drafts)
            where = np.asarray([q for q, _ in drafts])
            drafted = np.asarray([d for _, d in drafts])
            where = np.concatenate([where, np.full(max(0, len(toks) - k),
                                                   where[-1])])
            ref = np.asarray(plain.mtp(params, row, hidden, settings,
                                       at=where, faults=faults)[0])[:k]
            draft_gaps.append(ref.max(axis=-1)
                              - ref[np.arange(k), drafted])
            agree.append(ref.argmax(axis=-1) == drafted)
        del hidden

    def batch_off(p, toks, logprobs):
        # without the last token, which the engine never fed back
        row = np.asarray([p + toks[:-1]], np.int32)
        at = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        logits = plain.forward(params, row, settings, at=at,
                               faults=faults)[0]
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        return np.asarray(logprobs) - logp[np.arange(len(toks)), toks]

    off = [batch_off(*r) for r in served["batch"]]
    cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0)  # noqa: E731
    # a request with no recorded draft fails the check: its drafts
    # were never seen
    unseen = sum(1 for d in served["drafts"] if not d)
    draft_gaps = cat(draft_gaps) if not unseen else np.full(1, np.inf)
    numbers = {
        "worst_logit_gap": max(gaps),
        "logit_tolerance": LOGIT_TOL,
        "logprob_rms": float(np.sqrt(np.mean(np.square(cat(off))))),
        "logprob_rms_tolerance": LOGPROB_RMS_TOL,
        "draft_gap_mean": float(np.mean(draft_gaps)),
        "draft_gap_mean_tolerance": DRAFT_GAP_MEAN_TOL,
        "draft_worst_gap": float(np.max(draft_gaps)),
        "draft_agreement": float(np.mean(cat(agree))),
        "drafts": int(sum(len(d) for d in served["drafts"])),
        "requests_without_drafts": unseen,
        "served_positions": int(sum(len(t) for _, t, _ in
                                    served["sample"] + served["batch"]))}
    ok = numbers["worst_logit_gap"] <= LOGIT_TOL \
        and numbers["logprob_rms"] <= LOGPROB_RMS_TOL \
        and numbers["draft_gap_mean"] <= DRAFT_GAP_MEAN_TOL
    return bool(ok), numbers


def build_engine(model, params, eng: dict, on_step=None):
    """The engine at the configuration's knobs."""
    from horovod_tpu.serving import GenerationEngine

    return GenerationEngine(
        model, params=params, max_seqs=eng["max_seqs"],
        block_size=eng["block_size"], num_blocks=eng["num_blocks"],
        prefill_chunk=eng["prefill_chunk"], spec_mode=eng["spec_mode"],
        spec_tokens=eng["spec_tokens"], on_step=on_step)


class Server(SERVE.Server):
    """The engine of one run: weights from the seed, the programs warm,
    and the ``correct`` check made. ``serve_transformer.Server``'s
    ``_on_step`` and ``close`` serve as they are."""

    def __init__(self, ctx: core.Context):
        from horovod_tpu.models import Glm47Flash

        cfg = ctx.config
        eng = cfg["engine"]
        self.ctx, self.vocab = ctx, cfg["vocab_size"]
        self.prefill_chunk = eng["prefill_chunk"]
        model = Glm47Flash(model_config(cfg))
        plain = ctx.load_reference()

        t_warm = time.perf_counter()
        params = make_weights(model, ctx.seed, float(cfg["draft_gain"]))
        ctx.mark("weights")
        self.params, self.plain = params["params"], plain
        self.steps = ctx.spans.setdefault("steps", [])  # (time, phase, ids)
        self.in_use_peak = 0
        self.mark_steps = False
        self._open_mark = None
        self.engine = build_engine(model, params, eng, self._on_step)
        try:
            # warm-up: two prefill chunks and a few verify steps compile
            # or load both programs; the deadline is lifted, since a
            # token that waits on a compile is not starved
            rng = np.random.default_rng(
                np.random.SeedSequence([ctx.seed, 8]))
            warm = rng.integers(0, self.vocab,
                                eng["prefill_chunk"] + 1).tolist()
            self.engine.result(self.engine.submit(
                warm, max_tokens=4, deadline_ms=1_800_000.0),
                timeout=1800.0)
            ctx.facts["warmup_s"] = time.perf_counter() - t_warm
            ctx.mark("warm")
            self.served = serve_check(ctx, self.engine, model, params)
            ctx.mark("served_check")
            self.checked, self.numbers = \
                (False, {"short_request": True}) if self.served is None \
                else compare(self.served, self.params, plain, cfg)
            self.numbers["in_use_after_check"] = self.engine.allocator.in_use
            self.checked = self.checked \
                and self.engine.allocator.in_use == 0
        except BaseException:
            self.engine.close()
            raise
        ctx.mark("checked")
        ctx.info(check="greedy and sampled tokens of the MTP verify path "
                       "and every draft of its programs against the "
                       "float32 reference", ok=self.checked, **self.numbers)


def measure(ctx: core.Context, server: Server, tr: dict) -> dict:
    """``serve_deepseek_v32.measure``: the closed loop over one fixed
    table of lengths, the prefill chunks of the window for the
    roofline's reader, and the blocks, which must all be free."""
    return DSV.measure(ctx, server, tr)


def run(ctx: core.Context) -> dict:
    server = Server(ctx)
    try:
        return measure(ctx, server, ctx.traffic)
    finally:
        server.close()
