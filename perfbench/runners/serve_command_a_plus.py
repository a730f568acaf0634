"""``CommandAPlus`` served by ``GenerationEngine``: one chip's share (16
of 128 routed experts, an eighth of the vocabulary, one period of four
layers) of an 8-chip-a-layer deployment, under a closed loop of short
and long requests in one queue, prefix cache on.

Set-up: the model and its bfloat16 weights on the device from the seed
in one jitted call; the engine at the configuration's knobs (the window
group's pool is the engine's own arithmetic); one warm-up request that
compiles or loads the prefill and decode programs; the ``correct``
check; then the callers run until the traffic's ``start_after``-th
completion and the window opens. The window, the client and the
number-taking are ``serve_transformer``'s own (``measure``,
``_Client``), loaded as this runner's copy of that module and handed
this runner's generators in place of the harness's lists of requests.

The traffic (:func:`length_table`, :func:`caller_requests`): one fixed
stratified table of ``requests`` lengths, a long prompt at every
``long_every``-th place, the same for every ``--seed``; whichever caller
sends next takes the table's next entry, so the order of the work is
the table's whatever the callers' phases; token ids are fresh for every
request, drawn from the seed.

The check (:func:`serve_check`, :func:`compare`): greedy requests
inside a chunk, across a chunk, across the first release of a window
block, four windows deep and one whose release boundary falls inside
the reply; then a batch of short prompts that each decode sampled
tokens. Every served token is teacher-forced through the float32
reference; both plane groups must read ``in_use == 0`` afterwards, and
the traced forward must take the router, the norms and every softmax in
float32 (:func:`lowered_precisions`).
"""

import os
import threading
import time

import numpy as np

from perfbench.harness import core, traffic

#: The limits of :func:`compare`, each between two readings on the v5e
#: (PERF.md, section 6, PR 33, has every reading with its seed;
#: ``perfbench/tools/command_a_plus_tolerance.py`` takes them).
#:
#: A served greedy token's reference logit under the reference's best,
#: over the ``check_sample`` requests.
LOGIT_TOL = 0.2
#: Root mean square of served less reference log-probability over the
#: sampled batch's served tokens (short contexts: what precision moves).
LOGPROB_RMS_TOL = 0.027
#: Median of the absolute difference of served and reference
#: log-probability over the tokens of the greedy sample's requests whose
#: prompt is longer than a window and a block (one and four windows
#: deep: served through released blocks): what a wrong window moves. A median, because a router pick that
#: falls the other way under bfloat16 activations moves a single token's
#: log-probability by a tenth, clean, and twelve tokens' root mean square
#: with it; a wrong window moves every token.
LONG_LOGPROB_MEDIAN_TOL = 0.02

SERVE = core.load_module(
    os.path.join(core.BENCH_DIR, "runners", "serve_transformer.py"),
    "perfbench_runner_serve_transformer_for_command_a_plus")


def model_config(cfg: dict, **overrides):
    """``CommandAPlusConfig`` of the configuration file (the source's key
    names; ``num_experts`` there counts the experts held, the router's
    width is the published count; ``layer_types`` is the published list,
    of which the model runs the first ``num_hidden_layers``)."""
    import jax.numpy as jnp

    from horovod_tpu.models import CommandAPlusConfig

    first, end = cfg["held_experts"]
    if end - first != cfg["num_experts"]:
        raise ValueError("held_experts and num_experts of the "
                         "configuration disagree")
    sizes = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        layer_norm_eps=cfg["layer_norm_eps"],
        logit_scale=float(cfg["logit_scale"]),
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=(first, end),
        table_positions=cfg["engine"]["table_positions"],
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    sizes.update(overrides)
    return CommandAPlusConfig(**sizes)


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

def _quantiles(dist: dict, n: int) -> list:
    """``traffic.quantile_lengths``, and log-uniform lengths beside it."""
    if dist["dist"] != "loguniform":
        return traffic.quantile_lengths(dist, n)
    lo, hi = np.log(dist["min"]), np.log(dist["max"])
    return [int(round(float(np.exp(lo + (i + 0.5) / n * (hi - lo)))))
            for i in range(n)]


def length_table(tr: dict) -> list:
    """The ``requests`` requests of the table as ``(prompt tokens, reply
    tokens)``: a long prompt at every ``long_every``-th place, short
    ones between; the lengths are the equal-probability quantiles of
    their distributions, dealt once (``tr["dealing_seed"]``) in rounds
    that each span a distribution's range. **The same for every
    ``--seed``:** a request is credited its whole prompt, so which long
    prompts complete inside a 51 s window moves the rate, and a table
    dealt anew by each seed spread a cell of this shape 2.06 % where a
    fixed one spread 0.63 % (PERF.md, section 6, PR 31)."""
    n, every = tr["requests"], tr["long_every"]
    n_long = len(range(0, n, every))
    rng = traffic._rng(tr["dealing_seed"], 11)
    long_ = traffic._deal(_quantiles(tr["long_prompt_tokens"], n_long),
                          rng, 4)
    short = traffic._deal(
        _quantiles(tr["short_prompt_tokens"], n - n_long), rng, 8)
    replies = traffic._deal(_quantiles(tr["output_tokens"], n), rng, 8)
    longs, shorts = iter(long_), iter(short)
    return [(int(next(longs) if i % every == 0 else next(shorts)),
             int(replies[i])) for i in range(n)]


class _Cursor:
    """The table's next entry, to whichever caller asks next."""

    def __init__(self, table):
        self.table, self.at, self.lock = table, 0, threading.Lock()

    def take(self):
        with self.lock:
            entry = self.table[self.at % len(self.table)]
            self.at += 1
        return entry


def caller_requests(tr: dict, vocab: int, seed: int, caller: int,
                    cursor: _Cursor):
    """Caller ``caller``'s requests, without end: when it is about to
    send, the table's next entry, with token ids drawn from ``(seed,
    caller, its own ordinal)``. Caller ``c`` holds its first request
    back ``c x start_stagger_ms``, so the callers' first requests reach
    the queue in a fixed order and not in a race."""
    ordinal = 0
    while True:
        if ordinal == 0:
            time.sleep(caller * tr.get("start_stagger_ms", 0) / 1e3)
        prompt, reply = cursor.take()
        ids = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 13, caller, ordinal])).integers(0, vocab, prompt)
        req = traffic.Request(prompt=ids.tolist(), max_tokens=reply,
                              sampling=tr.get("sampling"),
                              deadline_ms=tr.get("deadline_ms"))
        req.caller, req.ordinal = caller, ordinal
        yield req
        ordinal += 1


class _MixedTraffic:
    """What ``serve_transformer.measure`` asks its ``traffic`` module
    for in a closed loop: one iterable of requests a client."""

    @staticmethod
    def closed_loop(tr, vocab, seed):
        cursor = _Cursor(length_table(tr))
        return [caller_requests(tr, vocab, seed, c, cursor)
                for c in range(tr["clients"])]


# -- the check ----------------------------------------------------------------

def lowered_precisions(model, params, eng: dict) -> list:
    """Where the served forward computes below the float32 that the
    configuration states for the router, the norms and every softmax,
    read from the traced paged forward of the model the engine serves,
    at a prefill chunk's shape and a decode step's: the router's matmul
    (the one against a ``hidden x router outputs`` weight) and its
    ``logistic``, every ``exp``, ``rsqrt`` and ``top_k`` take float32.
    Empty when all do."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import PagedCache
    from horovod_tpu.serving.generation import kv_cache as kvc

    cfg = model.cfg
    router = (cfg.hidden_size, cfg.num_experts)
    groups = cfg.cache_spec().plane_groups()
    pools = jax.eval_shape(lambda: kvc.make_pools(
        cfg, (eng["num_blocks"],) * len(groups), eng["block_size"]))
    width = cfg.max_seq_len // eng["block_size"]
    found = []

    def walk(jaxpr, where):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            avals = [v.aval for v in eqn.invars if hasattr(v.aval, "dtype")]
            # (a logistic of another width is a gated MLP's silu, which
            # the configuration states in the activations' bfloat16)
            if (name in ("exp", "rsqrt", "top_k")
                    or (name == "logistic"
                        and avals[0].shape[-1:] == router[1:])
                    or (name == "dot_general" and len(avals) == 2
                        and avals[1].shape == router)) \
                    and any(a.dtype != jnp.float32 for a in avals):
                found.append(f"{where}: {name} of "
                             f"{[str(a.dtype) for a in avals]} "
                             f"{eqn.outvars[0].aval.shape}")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, where)

    for where, (lanes, columns) in (("prefill", (1, eng["prefill_chunk"])),
                                    ("decode", (eng["max_seqs"], 2))):
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        tables = tuple(i32(lanes, width) for _ in groups)
        cache = PagedCache(pools, tables if len(groups) > 1 else tables[0],
                           i32(lanes), i32(lanes))
        walk(jax.make_jaxpr(
            lambda p, t, c: model.apply(p, t, cache=c,
                                        mutable=["moe_stats"]))(
                params, i32(lanes, columns), cache).jaxpr, where)
    return found


def _serve(engine, requests, temperature=None, seeds=None):
    """``[(prompt, n)]`` served all in flight together, greedily or
    sampled at ``temperature`` under ``seeds`` (one a request):
    ``[(prompt, tokens, logprobs)]``."""
    seeds = seeds or [None] * len(requests)
    seqs = [engine.submit(p, max_tokens=n, deadline_ms=900_000.0,
                          temperature=temperature, seed=seed)
            for (p, n), seed in zip(requests, seeds)]
    return [(p, engine.result(seq, timeout=900.0), list(seq.logprobs))
            for (p, n), seq in zip(requests, seqs)]


def serve_check(ctx, engine):
    """What :func:`compare` holds against the reference, served through
    the engine outside the window: the greedy requests of
    ``check_sample``, all in flight together (prefill by chunks, then
    decode through both plane groups, the window group releasing as it
    goes), then the batch of ``check_logprobs``, sampled at its
    ``temperature`` under seeds drawn from the run's. ``None`` where a
    request returned fewer tokens than asked."""
    cfg = ctx.config
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    draw = lambda n: rng.integers(0, cfg["vocab_size"], n).tolist()  # noqa: E731
    sample = _serve(engine, [(draw(p), n) for p, n in cfg["check_sample"]])
    b = cfg["check_logprobs"]
    batch = _serve(engine, [(draw(b["prompt_tokens"]), b["new_tokens"])
                            for _ in range(b["requests"])],
                   temperature=b["temperature"],
                   seeds=rng.integers(1, 2 ** 31 - 1, b["requests"]).tolist())
    asked = [n for _, n in cfg["check_sample"]] \
        + [b["new_tokens"]] * b["requests"]
    if [len(toks) for _, toks, _ in sample + batch] != asked:
        return None
    return {"sample": sample, "batch": batch}


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
    - ``long_logprob_median`` (``LONG_LOGPROB_MEDIAN_TOL``): the median
      absolute difference of the two log-probabilities over the tokens
      of the sample's requests whose prompt is longer than a window and
      a block, so that every one of them was served through released
      blocks (``long_offsets`` lists the differences);
      ``long_logprob_rms`` and ``sample_logprob_rms`` are root mean
      squares over those and over all of the sample's tokens and have no
      limit, and ``by_request`` gives a request's prompt length, worst
      gap, root mean square and largest difference."""
    import jax
    import jax.numpy as jnp

    def off_reference(p, toks, logprobs):
        # without the last token, which the engine never fed back
        row = jnp.asarray([p + toks[:-1]], jnp.int32)
        at = jnp.arange(len(p) - 1, len(p) - 1 + len(toks))
        logits = plain.forward(params, row, settings, at=at,
                               faults=faults)[0]
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        logits = np.asarray(logits)
        picked = np.arange(len(toks)), np.asarray(toks)
        return (float(np.max(logits.max(axis=-1) - logits[picked])),
                np.asarray(logprobs) - logp[picked])

    rms = lambda parts: float(np.sqrt(np.mean(np.square(  # noqa: E731
        np.concatenate(parts)))))
    sample = [off_reference(*r) for r in served["sample"]]
    batch = [off_reference(*r) for r in served["batch"]]
    deep = settings["sliding_window"] + settings["engine"]["block_size"]
    long_off = np.concatenate(
        [off for (p, _, _), (_, off) in zip(served["sample"], sample)
         if len(p) > deep] or [np.zeros(1)])
    numbers = {
        "worst_logit_gap": max(gap for gap, _ in sample),
        "logit_tolerance": LOGIT_TOL,
        "logprob_rms": rms([off for _, off in batch]),
        "logprob_rms_tolerance": LOGPROB_RMS_TOL,
        "long_logprob_median": float(np.median(np.abs(long_off))),
        "long_logprob_median_tolerance": LONG_LOGPROB_MEDIAN_TOL,
        "long_logprob_rms": rms([long_off]),
        "long_offsets": [round(float(x), 4) for x in long_off],
        "sample_logprob_rms": rms([off for _, off in sample]),
        "by_request": [[len(p), round(gap, 4), round(rms([off]), 4),
                        round(float(np.max(np.abs(off))), 4)]
                       for (p, _, _), (gap, off)
                       in zip(served["sample"], sample)],
        "served_positions": int(sum(len(off) for _, off in sample + batch))}
    ok = numbers["worst_logit_gap"] <= LOGIT_TOL \
        and numbers["logprob_rms"] <= LOGPROB_RMS_TOL \
        and numbers["long_logprob_median"] <= LONG_LOGPROB_MEDIAN_TOL
    return bool(ok), numbers


class Server(SERVE.Server):
    """The engine of one run: weights from the seed, the programs warm,
    and the ``correct`` check made. ``serve_transformer.Server``'s
    ``close`` serves as it is; ``_on_step`` also samples the window
    group's blocks in use beside the full group's."""

    def __init__(self, ctx: core.Context):
        from horovod_tpu.models import CommandAPlus
        from horovod_tpu.serving import GenerationEngine

        cfg = ctx.config
        eng = cfg["engine"]
        self.ctx, self.vocab = ctx, cfg["vocab_size"]
        self.prefill_chunk = eng["prefill_chunk"]
        model = CommandAPlus(model_config(cfg))
        plain = ctx.load_reference()

        t_warm = time.perf_counter()
        params = make_weights(model, ctx.seed)
        ctx.mark("weights")
        self.params, self.plain = params["params"], plain
        self.steps = ctx.spans.setdefault("steps", [])  # (time, phase, ids)
        self.held = []                  # (time, full in use, window in use)
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
            self.engine.result(self.engine.submit(
                warm, max_tokens=3, deadline_ms=1_800_000.0),
                timeout=1800.0)
            ctx.facts["warmup_s"] = time.perf_counter() - t_warm
            ctx.mark("warm")
            self.served = serve_check(ctx, self.engine)
            self.checked, self.numbers = \
                (False, {"short_request": True}) if self.served is None \
                else compare(self.served, self.params, plain, cfg)
            lowered = lowered_precisions(model, params, eng)
            alloc = self.engine.allocator
            self.numbers["in_use_after_check"] = [
                alloc.in_use, alloc.window.in_use]
            self.checked = self.checked and not lowered \
                and alloc.in_use == 0 and alloc.window.in_use == 0
        except BaseException:
            self.engine.close()
            raise
        ctx.mark("checked")
        ctx.info(check="greedy requests up to four windows deep and a "
                       "sampled batch against the float32 reference",
                 ok=self.checked, below_float32=lowered, **self.numbers)

    def _on_step(self, phase, ids):
        alloc = self.engine.allocator
        self.held.append((time.perf_counter(), alloc.in_use,
                          alloc.window.in_use))
        super()._on_step(phase, ids)


def measure(ctx: core.Context, server: Server, tr: dict) -> dict:
    """``serve_transformer.measure`` over this runner's generators: its
    closed loop asks the traffic module for a list of requests a client
    and is given the callers' generators. Afterwards: the prefill chunks
    of the window for the roofline's reader, what both plane groups held
    at every ``on_step`` of the window, and both groups' blocks, which
    must all be free."""
    from perfbench.harness import hostspans

    harness_traffic = SERVE.traffic
    SERVE.traffic = _MixedTraffic
    try:
        outcome = SERVE.measure(ctx, server, tr)
    finally:
        SERVE.traffic = harness_traffic
    alloc = server.engine.allocator
    t0, t1 = ctx.window
    spans = hostspans.loop_spans(t0) or []
    ctx.facts["prefill_chunks"] = [
        (s["args"]["prefilled"], s["args"]["chunk"]) for s in spans
        if s["name"] == "gen.prefill.dispatch"
        and t0 * 1e9 <= s["end_ns"] <= t1 * 1e9]
    held = [(full, window) for t, full, window in server.held
            if t0 <= t <= t1]
    ctx.facts["group_blocks_held"] = held
    ctx.facts["window_pool_blocks"] = alloc.window.capacity
    ctx.facts["lanes"] = server.engine.batcher.max_seqs
    ended = [r for r in ctx.facts.get("records", ())
             if r.done is not None and t0 <= r.done <= t1]
    ctx.info(requests_completed_in_window=len(ended),
             long_completed_in_window=sum(
                 1 for r in ended
                 if len(r.req.prompt) >= tr["long_prompt_tokens"]["min"]),
             blocks_in_use_after=[alloc.in_use, alloc.window.in_use],
             window_blocks_held_peak=max((w for _, w in held), default=0),
             full_blocks_held_peak=max((f for f, _ in held), default=0))
    outcome["correct"] = bool(outcome["correct"] and alloc.in_use == 0
                              and alloc.window.in_use == 0)
    return outcome


def run(ctx: core.Context) -> dict:
    server = Server(ctx)
    try:
        return measure(ctx, server, ctx.traffic)
    finally:
        server.close()
