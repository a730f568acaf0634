"""``OlmoHybrid`` served by ``GenerationEngine``: one pipeline stage (16
of 32 layers, with the embedding and the head) of a two-chip deployment,
under closed-loop multi-turn sessions, prefix cache on.

Set-up: the model and its bfloat16 weights on the device from the seed
in one jitted call; the engine at the configuration's knobs; one warm-up
request that compiles or loads the prefill and decode programs and both
directions of the state copy; the ``correct`` check; then the callers
run until the traffic's ``start_after``-th completion and the window
opens. The window, the client and the number-taking are
``serve_transformer``'s own (``measure``, ``_Client``), loaded as this
runner's copy of that module and handed this runner's session generator
in place of the harness's list of requests.

The sessions (:func:`sessions`): a caller runs sessions back to back; a
session is ``turns`` requests, turn ``k + 1``'s prompt being turn
``k``'s plus new tokens, so that a turn finds the previous one's
blocks and, as deep as a prefill-chunk boundary left one, a snapshot of
its recurrent state. The lengths are a fixed stratified table that the
callers walk cyclically, the same in every seed (:func:`session_table`
says why the seed does not deal them); token ids are fresh for every
session, drawn from the seed.

The check (:func:`serve_check`, :func:`compare`): greedy requests
inside a chunk, across one chunk boundary and across two; a two-turn
session whose second turn must hit exactly ``expect_hit_tokens`` of the
prefix cache and restore one snapshot; a batch of short prompts that
each decode a few hundred sampled tokens. Every served token is
teacher-forced through the float32 reference.
"""

import os
import time

import numpy as np

from perfbench.harness import core, traffic

#: The limits of :func:`compare`, each between two readings on the v5e
#: (PERF.md, section 6, PR 31, has every reading with its seed;
#: ``perfbench/tools/olmo_tolerance.py`` takes them).
#:
#: A served greedy token's reference logit under the reference's best,
#: over the ``check_sample`` requests and both turns of the session (the
#: reference's logits have a spread near 1.2 and its best two lie about
#: 0.2 apart, so rounding swaps near-ties): bfloat16 weights and
#: activations with a float32 state read 0.0-0.16 over this PR's seeds;
#: a wrong position or a stale block is a whole spread off. The skipped
#: restore reads 0.35-1.34 here, too near for this number to hold it:
#: the session's own number does.
LOGIT_TOL = 0.35
#: Root mean square of served less reference log-probability over the
#: batch's 4096 served tokens (16 requests of 32 + 256, sampled at
#: temperature 1): clean 0.0242-0.0268 over fifteen seeds; the recurrent
#: state held in bfloat16 (the nearest precision below the float32 the
#: configuration states) 0.0390-0.0437 over eight, 1.5 to 1.7 times its
#: own seed's clean reading. A state in bfloat16 is rounded at
#: every decode step, so what it adds grows with the steps decoded
#: (nothing at 16, a fifth at 64, three fifths at 256), and the tokens
#: are sampled because a greedy run of a seeded random model falls into
#: a loop of a dozen tokens, where the activations' own rounding does
#: not decay: over 64 greedy tokens clean read 0.0194-0.0259 and the
#: fault 0.0248-0.0340, and the driver's seed 60060893 was refused clean.
LOGPROB_RMS_TOL = 0.033
#: The same root mean square over the session's second turn alone, the
#: six tokens served after the prefix hit: what a state that was not
#: restored moves: clean 0.024-0.065 over thirty-five runs, the restore
#: skipped 0.63, 0.70 and 0.73.
SESSION_LOGPROB_RMS_TOL = 0.2

SERVE = core.load_module(
    os.path.join(core.BENCH_DIR, "runners", "serve_transformer.py"),
    "perfbench_runner_serve_transformer_for_olmo_hybrid")


def model_config(cfg: dict, **overrides):
    """``OlmoHybridConfig`` of the configuration file (the source's key
    names; ``layer_types`` is the published list, of which the model
    runs the first ``num_hidden_layers``)."""
    import jax.numpy as jnp

    from horovod_tpu.models import OlmoHybridConfig

    layers = cfg["num_hidden_layers"]
    sizes = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"][:layers]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        table_positions=cfg["engine"]["table_positions"],
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        state_dtype=jnp.dtype(cfg["state_dtype"]))
    sizes.update(overrides)
    return OlmoHybridConfig(**sizes)


def make_weights(model, seed: int):
    """The model's weights on the device, from the seed, in one jitted
    call, in the dtypes the model holds them in."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(core.seed_key(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    return params


# -- the traffic --------------------------------------------------------------

def session_table(tr: dict) -> list:
    """The ``sessions`` sessions of a run as ``(first prompt, [added
    tokens a later turn], [reply tokens a turn])``: the
    equal-probability quantiles of the three distributions, dealt once
    (``tr["dealing_seed"]``) and stratified: first prompts in rounds of
    ``clients`` that each span the range of lengths, and every session
    one added length and one reply length from each stratum. Caller
    ``c`` walks entries ``c, c + clients, ...`` cyclically.

    **The table is the same for every ``--seed``.** A turn is credited
    its whole prompt, which grows eightfold through a session, and a
    51 s window holds two sessions a caller, so which late turns fall
    inside it moves the rate: dealt anew by each seed the cell spread
    2.06 % over six seeds on the v5e (half the bound is 1.5 %), and a
    model of the scheduler says any re-dealing of sessions to callers,
    or of replies within them, moves it 1.5-3 %, a fixed table 0.05 %
    (PERF.md, section 6, PR 31). The seed gives the token ids (and the
    weights), which change what is computed and not how long it takes."""
    n, turns, clients = tr["sessions"], tr["turns"], tr["clients"]
    rng = traffic._rng(tr["dealing_seed"], 11)
    first = traffic._deal(
        traffic.quantile_lengths(tr["first_prompt_tokens"], n), rng, clients)
    added = traffic._deal(
        traffic.quantile_lengths(tr["added_tokens"], n * (turns - 1)), rng,
        turns - 1)
    replies = traffic._deal(
        traffic.quantile_lengths(tr["output_tokens"], n * turns), rng, turns)
    return [(int(first[i]),
             [int(a) for a in added[i * (turns - 1):(i + 1) * (turns - 1)]],
             [int(r) for r in replies[i * turns:(i + 1) * turns]])
            for i in range(n)]


def sessions(tr: dict, vocab: int, seed: int, caller: int):
    """Caller ``caller``'s requests, without end: session after session
    from its entries of :func:`session_table`, the first cut to ``1 +
    (caller mod turns)`` turns. A session's token ids are drawn at once
    from ``(seed, caller, session number)``; turn ``k`` sends the first
    ``first + added[0] + ... + added[k - 1]`` of them. Caller ``c``
    holds its very first request back ``c x start_stagger_ms``: the
    callers' threads start together, and the order in which their first
    requests reach the queue (a race otherwise) sets each caller's phase
    for the whole run, worth 0.6 % of spread in the rate by the model."""
    table = session_table(tr)[caller::tr["clients"]]
    number = 0
    while True:
        first, added, replies = table[number % len(table)]
        turns = tr["turns"] if number else 1 + caller % tr["turns"]
        ids = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 12, caller, number])).integers(
                0, vocab, first + sum(added)).tolist()
        length = first
        if number == 0:
            time.sleep(caller * tr.get("start_stagger_ms", 0) / 1e3)
        for k in range(turns):
            req = traffic.Request(
                prompt=ids[:length], max_tokens=replies[k],
                sampling=tr.get("sampling"),
                deadline_ms=tr.get("deadline_ms"))
            req.caller, req.session, req.turn = caller, number, k
            yield req
            if k < len(added):
                length += added[k]
        number += 1


class _SessionTraffic:
    """What ``serve_transformer.measure`` asks its ``traffic`` module
    for in a closed loop: one iterable of requests a client."""

    @staticmethod
    def closed_loop(tr, vocab, seed):
        return [sessions(tr, vocab, seed, c) for c in range(tr["clients"])]


# -- the check ----------------------------------------------------------------

def _serve(engine, requests, temperature=None, seeds=None):
    """``[(prompt, n)]`` served all in flight together, greedily or
    sampled at ``temperature`` under ``seeds`` (one a request):
    ``[(prompt, tokens, logprobs)]``. A sampled token's log-probability
    is the engine's under the unscaled distribution."""
    seeds = seeds or [None] * len(requests)
    seqs = [engine.submit(p, max_tokens=n, deadline_ms=900_000.0,
                          temperature=temperature, seed=seed)
            for (p, n), seed in zip(requests, seeds)]
    return [(p, engine.result(seq, timeout=900.0), list(seq.logprobs))
            for (p, n), seq in zip(requests, seqs)]


def _state_counters():
    from horovod_tpu import metrics as hvd_metrics

    snap = hvd_metrics.snapshot()
    return {"hit_tokens": sum(v for k, v in snap.items() if k.startswith(
                "hvd_tpu_gen_prefix_cache_hit_tokens_total")),
            "restored": snap.get(
                'hvd_tpu_gen_state_snapshots_total{event="restored"}', 0.0)}


def serve_check(ctx, engine):
    """What :func:`compare` holds against the reference, served through
    the engine outside the window: the greedy requests of
    ``check_sample``; the two turns of ``check_session``, the second
    sent when the first has returned, with the prefix-cache hit tokens
    and the snapshots restored counted around it; then the batch of
    ``check_logprobs``, sampled at its ``temperature`` under seeds
    drawn from the run's. ``None`` where a request returned fewer
    tokens than asked."""
    cfg = ctx.config
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    draw = lambda n: rng.integers(0, cfg["vocab_size"], n).tolist()  # noqa: E731
    sample = _serve(engine, [(draw(p), n) for p, n in cfg["check_sample"]])
    ses = cfg["check_session"]
    ids = draw(ses["first_prompt"] + ses["added"])
    turn_1 = _serve(engine, [(ids[:ses["first_prompt"]],
                              ses["new_tokens"])])
    before = _state_counters()
    turn_2 = _serve(engine, [(ids, ses["new_tokens"])])
    after = _state_counters()
    b = cfg["check_logprobs"]
    batch = _serve(engine, [(draw(b["prompt_tokens"]), b["new_tokens"])
                            for _ in range(b["requests"])],
                   temperature=b["temperature"],
                   seeds=rng.integers(1, 2 ** 31 - 1, b["requests"]).tolist())
    asked = [n for _, n in cfg["check_sample"]] + [ses["new_tokens"]] * 2 \
        + [b["new_tokens"]] * b["requests"]
    served = sample + turn_1 + turn_2 + batch
    if [len(toks) for _, toks, _ in served] != asked:
        return None
    # the session's second turn is the sample's last request
    return {"sample": sample + turn_1 + turn_2, "batch": batch,
            "session": {k: after[k] - before[k] for k in after}}


def compare(served, params, plain, settings):
    """The served path against ``plain`` (the float32 reference module)
    under ``settings`` (the configuration). Returns ``(ok, numbers)``;
    ``ok`` where each number is within its limit:

    - ``worst_logit_gap`` (``LOGIT_TOL``): over the ``check_sample``
      requests and both turns of the session, teacher-forced, how far a
      served token's reference logit lies under the reference's best at
      its position, at most;
    - ``logprob_rms`` (``LOGPROB_RMS_TOL``): over the batch's served
      (sampled) tokens, the root mean square of served log-probability
      less the reference's log-probability of the same token;
    - ``session_logprob_rms`` (``SESSION_LOGPROB_RMS_TOL``): the same
      over the tokens of the session's second turn, served from the
      restored state; ``sample_logprob_rms`` is the same over all of the
      sample's and the session's few tokens and has no limit;
    - ``session_hit_tokens`` and ``session_restored``: the second turn
      of the session attached exactly ``expect_hit_tokens`` of the
      prefix cache and restored ``expect_restored`` snapshot."""
    import jax
    import jax.numpy as jnp

    # a request at a time, all at one width and at its served positions
    # only: one compilation, and no (tokens x vocabulary) logits
    width = max(len(p) + len(toks) for p, toks, _ in served["sample"])
    worst, long_off = 0.0, []
    for p, toks, logprobs in served["sample"]:
        row = np.zeros((1, width), np.int32)
        row[0, :len(p) + len(toks)] = p + toks
        at = jnp.arange(len(p) - 1, len(p) - 1 + len(toks))
        logits = plain.forward(params, jnp.asarray(row), settings, at=at)[0]
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        logits = np.asarray(logits)
        worst = max([worst] + [float(logits[j].max() - logits[j, tok])
                               for j, tok in enumerate(toks)])
        long_off.append(np.asarray(logprobs)
                        - logp[np.arange(len(toks)), np.asarray(toks)])

    # the batch a request at a time, without its last token, which the
    # engine never fed back
    off = []
    for p, toks, logprobs in served["batch"]:
        row = jnp.asarray([p + toks[:-1]], jnp.int32)
        at = jnp.arange(len(p) - 1, len(p) - 1 + len(toks))
        logp = np.asarray(jax.nn.log_softmax(
            plain.forward(params, row, settings, at=at)[0], axis=-1))
        off.append(np.asarray(logprobs)
                   - logp[np.arange(len(toks)), np.asarray(toks)])
    rms = float(np.sqrt(np.mean(np.square(np.concatenate(off)))))

    ses, got = settings["check_session"], served["session"]
    numbers = {"worst_logit_gap": worst, "logit_tolerance": LOGIT_TOL,
               "logprob_rms": rms, "logprob_rms_tolerance": LOGPROB_RMS_TOL,
               "sample_logprob_rms": float(np.sqrt(np.mean(np.square(
                   np.concatenate(long_off))))),
               "session_logprob_rms": float(np.sqrt(np.mean(np.square(
                   long_off[-1])))),
               "session_logprob_rms_tolerance": SESSION_LOGPROB_RMS_TOL,
               "session_hit_tokens": got["hit_tokens"],
               "session_restored": got["restored"],
               "served_positions": int(sum(len(o) for o in off))}
    ok = worst <= LOGIT_TOL and rms <= LOGPROB_RMS_TOL \
        and numbers["session_logprob_rms"] <= SESSION_LOGPROB_RMS_TOL \
        and got["hit_tokens"] == ses["expect_hit_tokens"] \
        and got["restored"] == ses["expect_restored"]
    return bool(ok), numbers


class Server(SERVE.Server):
    """The engine of one run: weights from the seed, the programs warm,
    and the ``correct`` check made. ``serve_transformer.Server``'s
    ``close`` serves as it is; ``_on_step`` also samples the snapshot
    slots in use."""

    def __init__(self, ctx: core.Context, before_check=None,
                 **model_overrides):
        from horovod_tpu.models import OlmoHybrid
        from horovod_tpu.serving import GenerationEngine

        cfg = ctx.config
        eng = cfg["engine"]
        self.ctx, self.vocab = ctx, cfg["vocab_size"]
        self.prefill_chunk = eng["prefill_chunk"]
        model = OlmoHybrid(model_config(cfg, **model_overrides))
        plain = ctx.load_reference()

        t_warm = time.perf_counter()
        params = make_weights(model, ctx.seed)
        ctx.mark("weights")
        self.steps = ctx.spans.setdefault("steps", [])  # (time, phase, ids)
        self.snapshots_held = []        # (time, snapshot slots in use)
        self.in_use_peak = 0
        self.mark_steps = False
        self._open_mark = None
        self.engine = GenerationEngine(
            model, params=params, max_seqs=eng["max_seqs"],
            block_size=eng["block_size"], num_blocks=eng["num_blocks"],
            prefill_chunk=eng["prefill_chunk"],
            state_snapshots=eng["state_snapshots"], on_step=self._on_step)
        try:
            # warm-up: two prefill chunks (the first ends on a block
            # boundary and leaves a snapshot), a restore at admission and
            # a few decode steps compile or load every program; the
            # deadline is lifted, since a token that waits on a compile
            # is not starved
            rng = np.random.default_rng(
                np.random.SeedSequence([ctx.seed, 8]))
            warm = rng.integers(0, self.vocab,
                                eng["prefill_chunk"] + 1).tolist()
            self.engine.result(self.engine.submit(
                warm, max_tokens=3, deadline_ms=1_800_000.0),
                timeout=1800.0)
            ctx.facts["warmup_s"] = time.perf_counter() - t_warm
            ctx.mark("warm")
            if before_check is not None:    # the tolerance tool's faults
                before_check(self.engine)
            served = serve_check(ctx, self.engine)
            self.checked, self.numbers = (False, {"short_request": True}) \
                if served is None \
                else compare(served, params["params"], plain, cfg)
        except BaseException:
            self.engine.close()
            raise
        ctx.mark("checked")
        ctx.info(check="greedy requests, a two-turn session and a sampled "
                       "batch against the float32 reference",
                 ok=self.checked, **self.numbers)

    def _on_step(self, phase, ids):
        self.snapshots_held.append(
            (time.perf_counter(),
             self.engine.allocator.snapshot_slots_in_use))
        super()._on_step(phase, ids)


def measure(ctx: core.Context, server: Server, tr: dict) -> dict:
    """``serve_transformer.measure`` over this runner's sessions: its
    closed loop asks the traffic module for a list of requests a client
    and is given the callers' generators. Afterwards: the turns each
    caller sent, the prefill chunks of the window for the roofline's
    reader, and the state slots, which must all be free."""
    from perfbench.harness import hostspans

    harness_traffic = SERVE.traffic
    SERVE.traffic = _SessionTraffic
    try:
        outcome = SERVE.measure(ctx, server, tr)
    finally:
        SERVE.traffic = harness_traffic
    alloc = server.engine.allocator
    sent = {}
    for r in ctx.facts.get("records", ()):
        sent[r.req.caller] = sent.get(r.req.caller, 0) + 1
    t0, t1 = ctx.window
    spans = hostspans.loop_spans(t0) or []
    ctx.facts["prefill_chunks"] = [
        (s["args"]["prefilled"], s["args"]["chunk"]) for s in spans
        if s["name"] == "gen.prefill.dispatch"
        and t0 * 1e9 <= s["end_ns"] <= t1 * 1e9]
    ctx.facts["snapshot_slots"] = alloc.snapshot_slots
    ctx.facts["snapshot_slots_peak"] = max(
        [n for t, n in server.snapshots_held if t0 <= t <= t1], default=0)
    ctx.facts["lanes"] = server.engine.batcher.max_seqs
    orphaned = alloc.snapshots_orphaned()
    ctx.info(turns_sent_by_caller=[sent.get(c, 0)
                                   for c in range(tr["clients"])],
             state_slots_held=alloc.state_slots_in_use,
             snapshot_slots_in_use=alloc.snapshot_slots_in_use,
             snapshot_slots_orphaned=orphaned)
    outcome["correct"] = bool(outcome["correct"]
                              and alloc.state_slots_in_use == 0
                              and orphaned == 0)
    return outcome


def run(ctx: core.Context) -> dict:
    server = Server(ctx)
    try:
        return measure(ctx, server, ctx.traffic)
    finally:
        server.close()
