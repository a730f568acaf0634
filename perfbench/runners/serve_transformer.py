"""A GPT-2-shaped ``Transformer`` served by ``GenerationEngine``, under
open-loop or closed-loop traffic.

Set-up: weights on the device from the seed in one jitted call; the
engine at the configuration's knobs; one warm-up request that compiles
(or loads) the prefill and decode programs; the ``correct`` check on a
few greedy requests against the plain reference; then the lanes are
filled (open loop: the traffic's preload; closed loop: the clients run
until the second request has completed) and the window starts.

The client's clock is ``time.perf_counter`` in this process: a request's
tokens are stamped by the thread that reads its stream.
"""

import itertools
import threading
import time

import numpy as np

from perfbench.harness import core, models, stats, traffic

#: a served greedy token's reference logit may lie this far under the
#: reference's best logit at that position. The served path computes in
#: bf16 through the paged cache, the reference in float32 at ``highest``;
#: with seeded random weights the logits have a spread near 0.8 and the
#: best two lie about 0.2 apart, so rounding may swap near-ties but a
#: wrong position, a stale cache block or a missing layer is a whole
#: spread off. Measured on the v5e: see PERF.md, section 2.
LOGIT_TOL = 0.1
#: (prompt tokens, new tokens) of the greedy sample: within one chunk,
#: across a chunk boundary, and a few chunks
CHECK_SAMPLE = ((17, 6), (70, 6), (130, 6))


class _Record:
    __slots__ = ("req", "kind", "due", "sent", "token_times", "done",
                 "error", "tokens", "seq_id", "request_id")
    _ids = itertools.count()

    def __init__(self, req, kind, due=None):
        self.req, self.kind, self.due = req, kind, due
        self.sent = None
        self.token_times = []
        self.tokens = []
        self.done = None
        self.error = None
        self.seq_id = None
        self.request_id = f"bench-{next(self._ids)}"

    @property
    def ok(self):
        return self.error is None and len(self.tokens) == self.req.max_tokens


class _Client:
    """Submits requests and stamps the tokens of their streams."""

    def __init__(self, engine):
        self.engine = engine
        self.records = []
        self.by_seq = {}
        self.lock = threading.Lock()
        self.threads = []
        self.completions = 0
        self.on_complete = None

    def send(self, rec: _Record, inline: bool = False):
        r = rec.req
        s = r.sampling or {}
        with self.lock:
            self.records.append(rec)
        rec.sent = time.perf_counter()
        try:
            seq = self.engine.submit(
                r.prompt, max_tokens=r.max_tokens,
                deadline_ms=r.deadline_ms,
                temperature=s.get("temperature"), top_p=s.get("top_p"),
                top_k=s.get("top_k"), seed=r.seed,
                request_id=rec.request_id)
        except Exception as e:  # noqa: BLE001 — a refusal is a failure
            rec.error, rec.done = e, time.perf_counter()
            return
        rec.seq_id = seq.id
        with self.lock:
            self.by_seq[seq.id] = rec
        if inline:
            self._consume(rec, seq)
        else:
            t = threading.Thread(target=self._consume, args=(rec, seq),
                                 daemon=True)
            self.threads.append(t)
            t.start()

    def _consume(self, rec, seq):
        try:
            for tok in self.engine.batcher.stream(seq, timeout=600.0):
                rec.token_times.append(time.perf_counter())
                rec.tokens.append(tok)
        except Exception as e:  # noqa: BLE001
            rec.error = e
        rec.done = time.perf_counter()
        with self.lock:
            self.completions += 1
            n = self.completions
        if self.on_complete is not None:
            self.on_complete(n, rec)


def _check(ctx, engine, params, reference, vocab):
    """Greedy requests through the engine against the reference's full
    forward, teacher-forced. Returns (ok, worst gap)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    sample = [(rng.integers(0, vocab, p).tolist(), n)
              for p, n in ctx.facts["check_sample"]]
    seqs = [engine.submit(p, max_tokens=n, deadline_ms=900_000.0)
            for p, n in sample]
    width = max(len(p) + n for p, n in sample)
    rows = np.zeros((len(sample), width), np.int32)
    served = []
    for i, ((p, n), seq) in enumerate(zip(sample, seqs)):
        toks = engine.result(seq, timeout=900.0)
        if len(toks) != n:
            return False, float("inf")
        served.append(toks)
        rows[i, :len(p) + n] = p + toks
    logits = np.asarray(reference.forward(params["params"],
                                          jnp.asarray(rows)))
    worst = 0.0
    for i, ((p, n), toks) in enumerate(zip(sample, served)):
        for j, tok in enumerate(toks):
            ref = logits[i, len(p) - 1 + j]
            worst = max(worst, float(ref.max() - ref[tok]))
    return worst <= LOGIT_TOL, worst


class Server:
    """The engine of one run: weights from the seed, both programs warm,
    and the ``correct`` check made."""

    def __init__(self, ctx: core.Context):
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models import Transformer
        from horovod_tpu.serving import GenerationEngine

        cfg = ctx.config
        eng = cfg["engine"]
        self.ctx, self.vocab = ctx, cfg["vocab_size"]
        self.prefill_chunk = eng["prefill_chunk"]
        model = Transformer(models.transformer_config(cfg))
        reference = ctx.load_reference()
        ctx.facts["check_sample"] = cfg.get("check_sample", CHECK_SAMPLE)

        t_warm = time.perf_counter()
        params = nn.meta.unbox(jax.jit(model.init)(
            core.seed_key(ctx.seed), jnp.zeros((1, 8), jnp.int32)))
        jax.block_until_ready(params)
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
            self.checked, worst = _check(ctx, self.engine, params,
                                         reference, self.vocab)
        except BaseException:
            self.engine.close()
            raise
        ctx.mark("checked")
        ctx.info(check="greedy tokens against the float32 reference",
                 worst_logit_gap=worst, tolerance=LOGIT_TOL,
                 ok=self.checked)

    def _on_step(self, phase, ids):
        self.steps.append((time.perf_counter(), phase, tuple(ids)))
        self.in_use_peak = max(self.in_use_peak,
                               self.engine.allocator.in_use)
        # one host span from this hook call to the next: what the
        # scheduler did after this phase, on the profiler's clock. Opened
        # and closed on the scheduler's own thread.
        if self._open_mark is not None:
            self._open_mark.__exit__(None, None, None)
            self._open_mark = None
        if self.mark_steps:
            self._open_mark = self.ctx.annotate("sched.after_" + phase)
            self._open_mark.__enter__()

    def close(self):
        self.engine.close()


def measure(ctx: core.Context, server: Server, tr: dict) -> dict:
    """Fill the lanes, measure one window of ``tr``, end what is still in
    flight, and leave the numbers in ``ctx``."""
    from horovod_tpu import metrics as hvd_metrics

    engine, vocab = server.engine, server.vocab
    client = _Client(engine)
    stop = threading.Event()
    if tr["kind"] == "open_loop":
        requests = traffic.open_loop(tr, vocab, ctx.seed, ctx.seconds)
        pre = [_Record(r, "preload") for r in traffic.preload(
            tr, vocab, ctx.seed, server.prefill_chunk)]
        for rec in pre:
            client.send(rec)
        while any(not r.token_times and r.done is None for r in pre):
            time.sleep(0.005)            # every lane has its first token
    elif tr["kind"] == "closed_loop":
        started = threading.Event()
        client.on_complete = \
            lambda n, rec: started.set() if n >= tr["start_after"] else None
        lists = traffic.closed_loop(tr, vocab, ctx.seed)

        def caller(mine):
            for r in itertools.cycle(mine):
                if stop.is_set():
                    return
                client.send(_Record(r, "closed"), inline=True)

        callers = [threading.Thread(target=caller, args=(m,), daemon=True)
                   for m in lists]
        for t in callers:
            t.start()
        client.threads.extend(callers)
        started.wait()
    else:
        raise ValueError(f"{tr['kind']!r} is not serving traffic")

    # -------------------------------------------------------- the window
    def profile():
        with ctx.traced():
            server.mark_steps = True
            stop.wait(min(core.TRACE_SECONDS, ctx.seconds))
            server.mark_steps = False

    profiler = threading.Thread(target=profile, daemon=True) \
        if ctx.tracing else None
    ctx.counters_before = hvd_metrics.snapshot()
    server.in_use_peak = engine.allocator.in_use
    t0 = time.perf_counter()
    if profiler is not None:
        profiler.start()
    if tr["kind"] == "open_loop":
        for r in requests:
            delay = t0 + r.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            client.send(_Record(r, "open", due=t0 + r.due_s))
    delay = t0 + ctx.seconds - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    t1 = time.perf_counter()
    ctx.counters_after = hvd_metrics.snapshot()
    ctx.window = (t0, t1)
    ctx.facts["pool_in_use_peak"] = server.in_use_peak
    ctx.facts["pool_blocks"] = engine.allocator.capacity
    stop.set()
    if profiler is not None:
        profiler.join()
    # what is still in flight is the harness's to end, not the window's
    # (a caller may have sent one more request as the window closed)
    give_up = time.perf_counter() + 30.0
    while time.perf_counter() < give_up:
        open_ = [r for r in list(client.records) if r.done is None]
        if not open_ and not any(t.is_alive() for t in client.threads):
            break
        for rec in open_:
            engine.cancel(rec.request_id)
        time.sleep(0.05)
    leaked = engine.allocator.in_use

    # ------------------------------------------------------- the numbers
    records = list(client.records)
    ctx.facts["records"] = records
    ended = [r for r in records if r.done is not None and t0 <= r.done <= t1]
    bad = [r for r in ended if not r.ok]
    ctx.end_to_end["setup_s"] = ctx.setup_marks["window"] = \
        t0 - ctx.started_at
    gaps = stats.token_gaps([r.token_times for r in records], t0, t1)
    all_tokens = [t for r in records for t in r.token_times]
    firsts = [(r.token_times[0], len(r.req.prompt)) for r in records
              if r.token_times]
    attempted, failed = len(ended), len(bad)
    if gaps:
        ctx.end_to_end["itl_p90_ms"] = stats.percentile(gaps, 90) * 1e3
    rate = stats.rate_between_first_tokens(firsts, all_tokens, t0, t1)
    if rate is not None:
        ctx.end_to_end["served_tokens_per_s"] = rate[0]
        if tr["kind"] == "closed_loop":
            span = [r for r in ended if rate[1] < r.done <= rate[2]]
            attempted, failed = len(span), sum(1 for r in span if not r.ok)
    elif tr["kind"] == "closed_loop":
        # one prompt completion bounds no span: failed, not a rate
        attempted = failed = max(1, len(ended))
    ctx.info(window_s=t1 - t0, itl_gap_samples=len(gaps),
             requests_sent=len(records), requests_ended_in_window=len(ended),
             failed_in_window=len(bad), prompt_completions_in_window=sum(
                 1 for t, _ in firsts if t0 <= t <= t1),
             kv_blocks_leaked=leaked,
             compiles_in_window=ctx.compiles_in_window(),
             first_errors=[repr(r.error)[:200] for r in bad[:3]])
    return {"correct": bool(server.checked and not bad and leaked == 0
                            and attempted > 0 and failed == 0
                            and ctx.compiles_in_window() == 0),
            "attempted": attempted, "failed": failed}


def run(ctx: core.Context) -> dict:
    server = Server(ctx)
    try:
        return measure(ctx, server, ctx.traffic)
    finally:
        server.close()
