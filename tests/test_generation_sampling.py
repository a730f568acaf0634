"""Device-resident decode loop suite (ISSUE 11): on-device sampling,
seeded determinism (incl. across preemption-recompute), async
double-buffered stepping, and the decode-program transfer contract.

Runs in the seeded ``serving-gen`` CI suite alongside
tests/test_generation.py (ci/gen_pipeline.py owns both exclusively).
Everything is in-process on the CPU mesh with the same tiny fp32
transformer; programs are shared across tests through the builders'
memoization.
"""

import json
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import serving
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving.generation import (BlockAllocator, DecodeState,
                                            GenerationEngine, SampleParams,
                                            build_decode_program,
                                            build_program, make_pools)
from horovod_tpu.serving.generation.scheduler import DECODE_WIDTH

SEED = 1234

CFG = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=64,
                        dtype=jnp.float32)

#: a sampled (non-greedy) parameter set used across the determinism
#: tests — restrictive enough to exercise top-k AND top-p masking
SAMPLED = dict(temperature=0.9, top_k=12, top_p=0.85)


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    F.configure("", seed=0)


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    ref = jax.jit(model.apply)
    return model, params, ref


def _greedy_reference(ref, params, prompt, n):
    """Token-by-token greedy decode through the jitted full forward —
    the oracle every scheduled generation must reproduce exactly."""
    seq = list(prompt)
    for _ in range(n):
        logits = np.asarray(ref(params, jnp.asarray([seq], jnp.int32)))
        seq.append(int(np.argmax(logits[0, -1])))
    return seq[len(prompt):]


def _engine(model, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 33)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("deadline_ms", 0)
    return GenerationEngine(model, params=params, **kw)


def _prompt(rng, n):
    return rng.randint(0, CFG.vocab_size, (n,)).tolist()


def _delta(before, key):
    return M.snapshot().get(key, 0) - before.get(key, 0)


def _run_batch(model, params, jobs, **engine_kw):
    """Submit every job (kwargs for engine.submit), then collect
    (tokens, logprobs) per job in order."""
    with _engine(model, params, **engine_kw) as eng:
        seqs = [eng.submit(**j) for j in jobs]
        outs = [(eng.result(s, timeout=240), list(s.logprobs))
                for s in seqs]
        assert eng.allocator.in_use == 0
    return outs


# ---------------------------------------------------------------------------
# the transfer contract: the decode program ships tokens, not logits
# ---------------------------------------------------------------------------

class TestDecodeProgramSurface:
    def test_decode_outputs_are_token_vectors_not_logits(self, model_params):
        """ISSUE 11 acceptance: the per-step device->host transfer is
        (B,) token ids + logprobs — no output leaf carries the vocab
        axis (the pools go back device-side, never through np.asarray
        on the hot path)."""
        model, params, _ = model_params
        B, num_blocks, block_size = 4, 9, 4
        prog = build_decode_program(model, DECODE_WIDTH)
        k, v = make_pools(CFG, num_blocks, block_size)
        tables = jnp.zeros((B, 16), jnp.int32).at[:, 0].set(
            jnp.arange(1, B + 1, dtype=jnp.int32))
        state = DecodeState(
            tokens=jnp.full((B,), 3, jnp.int32),
            lengths=jnp.ones((B,), jnp.int32),
            live=jnp.ones((B,), jnp.int32),
            remaining=jnp.full((B,), 5, jnp.int32),
            eos=jnp.full((B,), -1, jnp.int32),
            sample=SampleParams(
                temperature=jnp.zeros((B,), jnp.float32),
                top_k=jnp.zeros((B,), jnp.int32),
                top_p=jnp.ones((B,), jnp.float32),
                key=jnp.zeros((B, 2), jnp.uint32),
                emitted=jnp.zeros((B,), jnp.int32)))
        (k, v), new_state, tok, logp = prog(params, (k, v), tables, state)
        assert tok.shape == (B,) and tok.dtype == jnp.int32
        assert logp.shape == (B,) and logp.dtype == jnp.float32
        # no vocab axis anywhere in the host-consumed outputs
        for leaf in jax.tree_util.tree_leaves((new_state, tok, logp)):
            assert CFG.vocab_size not in leaf.shape, leaf.shape
        # the state advanced in place: inputs fed back, lengths ticked
        ns = new_state
        assert np.array_equal(np.asarray(ns.tokens), np.asarray(tok))
        assert np.asarray(ns.lengths).tolist() == [2] * B
        assert np.asarray(ns.sample.emitted).tolist() == [1] * B

    def test_lane_retires_itself_on_device(self, model_params):
        """A lane whose remaining hits 0 (or that emits EOS) drops its
        own live flag inside the program — the speculative next step
        needs no host round-trip to neutralize it."""
        model, params, _ = model_params
        B = 2
        prog = build_decode_program(model, DECODE_WIDTH)
        k, v = make_pools(CFG, 9, 4)
        tables = jnp.zeros((B, 16), jnp.int32).at[:, 0].set(
            jnp.asarray([1, 2], jnp.int32))
        state = DecodeState(
            tokens=jnp.asarray([3, 5], jnp.int32),
            lengths=jnp.ones((B,), jnp.int32),
            live=jnp.ones((B,), jnp.int32),
            remaining=jnp.asarray([1, 8], jnp.int32),   # lane 0: last token
            eos=jnp.full((B,), -1, jnp.int32),
            sample=SampleParams(
                temperature=jnp.zeros((B,), jnp.float32),
                top_k=jnp.zeros((B,), jnp.int32),
                top_p=jnp.ones((B,), jnp.float32),
                key=jnp.zeros((B, 2), jnp.uint32),
                emitted=jnp.zeros((B,), jnp.int32)))
        (_k, _v), ns, _tok, _logp = prog(params, (k, v), tables, state)
        assert np.asarray(ns.live).tolist() == [0, 1]
        # snapshot host-side before the state is donated into step 2
        lengths1 = np.asarray(ns.lengths).tolist()
        tokens1 = np.asarray(ns.tokens).tolist()
        # a dead lane is frozen by the next step: no emission, no tick
        (_k, _v), ns2, tok2, _ = prog(params, (_k, _v), tables, ns)
        assert np.asarray(ns2.lengths).tolist()[0] == lengths1[0]
        assert int(np.asarray(tok2)[0]) == tokens1[0]


# ---------------------------------------------------------------------------
# greedy bit-parity: on-device argmax == host argmax over raw logits
# ---------------------------------------------------------------------------

class TestGreedyParity:
    def test_on_device_greedy_matches_host_argmax(self, model_params):
        """The PR 9 loop argmax'd raw logits on the host; the sampling
        programs must reproduce it bit-for-bit (greedy is temperature
        0, and the logits_at projection is pinned bit-identical)."""
        model, params, ref = model_params
        rng = np.random.RandomState(40)
        prompts = [_prompt(rng, n) for n in (3, 9, 5, 12)]
        jobs = [dict(prompt=p, max_tokens=8) for p in prompts]
        outs = _run_batch(model, params, jobs)
        for p, (tokens, logprobs) in zip(prompts, outs):
            assert tokens == _greedy_reference(ref, params, p, 8)
            assert len(logprobs) == len(tokens)
            assert all(lp <= 0.0 for lp in logprobs)

    def test_greedy_logprob_matches_raw_program_log_softmax(
            self, model_params):
        """logprobs come from the unmodified distribution: cross-check
        one step against the raw-logits reference program."""
        model, params, _ = model_params
        rng = np.random.RandomState(41)
        prompt = _prompt(rng, 6)
        outs = _run_batch(model, params, [dict(prompt=prompt, max_tokens=1)])
        (tokens, logprobs), = outs
        raw = build_program(model)
        alloc = BlockAllocator(33, 4)
        k, v = make_pools(CFG, 33, 4)
        blocks = alloc.allocate(alloc.blocks_for(len(prompt)))
        row = np.zeros((1, alloc.blocks_for(CFG.max_seq_len)), np.int32)
        row[0, :len(blocks)] = blocks
        padded = np.zeros((1, 8), np.int32)
        padded[0, :len(prompt)] = prompt
        from horovod_tpu.models.transformer import PagedCache
        cache = PagedCache((k, v), jnp.asarray(row),
                           jnp.zeros((1,), jnp.int32),
                           jnp.asarray([len(prompt)], jnp.int32))
        logits, _cache = raw(params, cache, jnp.asarray(padded))
        ref_row = np.asarray(logits)[0, len(prompt) - 1]
        ref_lp = ref_row - np.log(np.sum(np.exp(ref_row - ref_row.max()))) \
            - ref_row.max()
        assert tokens[0] == int(np.argmax(ref_row))
        assert logprobs[0] == pytest.approx(float(ref_lp[tokens[0]]),
                                            abs=1e-5)


# ---------------------------------------------------------------------------
# seeded sampling: deterministic continuations, also across recompute
# ---------------------------------------------------------------------------

class TestSeededSampling:
    def test_same_seed_same_continuation_across_runs(self, model_params):
        model, params, _ = model_params
        rng = np.random.RandomState(42)
        prompts = [_prompt(rng, n) for n in (4, 7, 5)]
        jobs = [dict(prompt=p, max_tokens=12, seed=777 + i, **SAMPLED)
                for i, p in enumerate(prompts)]
        first = _run_batch(model, params, jobs)
        second = _run_batch(model, params, jobs)
        assert first == second
        # and the draws are genuinely non-greedy somewhere: a different
        # seed must be allowed to diverge (24 draws over a 12-token
        # nucleus — a collision across all of them is ~impossible)
        reseeded = _run_batch(
            model, params,
            [dict(j, seed=j["seed"] + 5000) for j in jobs])
        assert [t for t, _ in reseeded] != [t for t, _ in first]

    def test_unseeded_sampled_requests_still_complete(self, model_params):
        """No seed: the scheduler derives a per-request key (sequence
        id), so sampling works and tokens stay in the vocab."""
        model, params, _ = model_params
        rng = np.random.RandomState(43)
        outs = _run_batch(
            model, params,
            [dict(prompt=_prompt(rng, 5), max_tokens=10, **SAMPLED)])
        (tokens, logprobs), = outs
        assert len(tokens) == 10 and len(logprobs) == 10
        assert all(0 <= t < CFG.vocab_size for t in tokens)

    def test_preemption_recompute_replays_identical_continuation(
            self, model_params):
        """The pinned ISSUE 11 property: a seeded sampled sequence
        preempted mid-decode (blocks freed, prompt + generated tokens
        re-prefilled) continues with the IDENTICAL tokens it would have
        produced unpreempted — every emission's PRNG key is a pure
        function of (request seed, emitted ordinal)."""
        model, params, _ = model_params
        rng = np.random.RandomState(44)
        before = M.snapshot()
        p1, p2 = _prompt(rng, 6), _prompt(rng, 6)
        jobs = [dict(prompt=p1, max_tokens=20, seed=101, **SAMPLED),
                dict(prompt=p2, max_tokens=20, seed=202, **SAMPLED)]
        # 2 x (6 + 20) = 26 tokens each need 7 blocks; a 9-block pool
        # cannot hold both -> at least one preemption-recompute
        squeezed = _run_batch(model, params, jobs, num_blocks=10)
        assert _delta(before, "hvd_tpu_gen_preemptions_total") >= 1
        roomy = _run_batch(model, params, jobs)     # 32 blocks: no preempt
        assert squeezed == roomy


# ---------------------------------------------------------------------------
# async double-buffered stepping: same outputs, measured overlap
# ---------------------------------------------------------------------------

class TestAsyncStepping:
    def _mixed_jobs(self, rng):
        lens = (12, 3, 7, 1, 9, 5)
        jobs = [dict(prompt=_prompt(rng, 3 + (i % 4)), max_tokens=n)
                for i, n in enumerate(lens)]
        # half greedy, half seeded-sampled: both paths must agree
        for i in (1, 3, 5):
            jobs[i].update(seed=900 + i, **SAMPLED)
        return jobs

    def test_depth1_equals_sync_on_mixed_length_workload(self,
                                                         model_params):
        """ASYNC_DEPTH=1 speculates one decode step ahead; retirement
        reconciliation must leave outputs exactly equal to the
        synchronous loop, token for token and logprob for logprob."""
        model, params, _ = model_params
        jobs = self._mixed_jobs(np.random.RandomState(45))
        sync = _run_batch(model, params, jobs, async_depth=0)
        async1 = _run_batch(model, params, jobs, async_depth=1)
        assert sync == async1

    def test_depth1_equals_sync_under_preemption(self, model_params):
        """Speculation + block exhaustion: the pipeline drains before
        any preemption decision, so the squeezed-pool outputs still
        match synchronous ones."""
        model, params, _ = model_params
        rng = np.random.RandomState(46)
        p1, p2 = _prompt(rng, 6), _prompt(rng, 6)
        jobs = [dict(prompt=p1, max_tokens=20),
                dict(prompt=p2, max_tokens=20, seed=7, **SAMPLED)]
        sync = _run_batch(model, params, jobs, num_blocks=10, async_depth=0)
        async1 = _run_batch(model, params, jobs, num_blocks=10,
                            async_depth=1)
        assert sync == async1

    def test_step_seconds_metric_splits_host_and_device(self, model_params):
        """hvd_tpu_gen_step_seconds{component=host|device} records every
        scheduler iteration's wall split — the observable for the
        async-overlap before/after."""
        model, params, _ = model_params
        rng = np.random.RandomState(47)
        before = M.snapshot()
        _run_batch(model, params,
                   [dict(prompt=_prompt(rng, 4), max_tokens=6)],
                   async_depth=1)
        snap = M.snapshot()
        for comp in ("host", "device"):
            key = f'hvd_tpu_gen_step_seconds{{component="{comp}"}}'
            assert snap[key]["count"] > before.get(key, {"count": 0})["count"]

    def test_decode_drill_same_blast_radius_at_depth1(self, model_params):
        """The seeded serving.decode drill under ASYNC_DEPTH=1: an
        error at the decode-step enqueue fails exactly that step's
        batch; the in-flight speculative step's tokens are delivered,
        a waiting sequence serves clean, and every block returns."""
        model, params, ref = model_params
        rng = np.random.RandomState(48)
        before = M.snapshot()
        F.configure("serving.decode:error:once", seed=SEED)
        pa, pb = _prompt(rng, 4), _prompt(rng, 4)
        with _engine(model, params, max_seqs=1, async_depth=1) as eng:
            a = eng.submit(pa, max_tokens=6)    # in the failing step
            b = eng.submit(pb, max_tokens=6)    # waiting: must survive
            with pytest.raises(F.InjectedFault, match="serving.decode"):
                eng.result(a, timeout=120)
            out_b = eng.result(b, timeout=120)
            assert eng.allocator.in_use == 0
        assert out_b == _greedy_reference(ref, params, pb, 6)
        assert _delta(before, 'hvd_tpu_faults_injected_total'
                              '{site="serving.decode",kind="error"}') == 1


# ---------------------------------------------------------------------------
# admission + wire surface for the sampling parameters
# ---------------------------------------------------------------------------

def _post_gen(port, doc, timeout=120):
    req = Request(f"http://127.0.0.1:{port}/v1/generate",
                  data=json.dumps(doc).encode(), method="POST",
                  headers={"Content-Type": "application/json"})
    try:
        with urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class TestSamplingAdmission:
    def test_invalid_sampling_params_rejected_at_submit(self, model_params):
        model, params, _ = model_params
        with _engine(model, params) as eng:
            with pytest.raises(ValueError, match="temperature"):
                eng.submit([1], max_tokens=2, temperature=-0.5)
            with pytest.raises(ValueError, match="temperature"):
                eng.submit([1], max_tokens=2, temperature=float("nan"))
            with pytest.raises(ValueError, match="top_k"):
                eng.submit([1], max_tokens=2, top_k=-3)
            with pytest.raises(ValueError, match="top_p"):
                eng.submit([1], max_tokens=2, top_p=0.0)
            with pytest.raises(ValueError, match="top_p"):
                eng.submit([1], max_tokens=2, top_p=1.5)

    def test_http_sampling_params_and_logprobs(self, model_params):
        """POST /v1/generate: sampling controls ride the request, the
        response carries index-aligned logprobs, invalid values 400."""
        model, params, _ = model_params
        rng = np.random.RandomState(49)
        prompt = _prompt(rng, 5)
        gen = _engine(model, params)
        with serving.InferenceServer(engine=None, gen_engine=gen,
                                     port=0, addr="127.0.0.1") as srv:
            doc = {"prompt": prompt, "max_tokens": 6, "seed": 11,
                   **SAMPLED}
            code, out1 = _post_gen(srv.port, doc)
            assert code == 200
            assert len(out1["logprobs"]) == len(out1["tokens"]) == 6
            assert all(lp <= 0.0 for lp in out1["logprobs"])
            code, out2 = _post_gen(srv.port, doc)   # same seed: replayed
            assert code == 200 and out2["tokens"] == out1["tokens"]
            assert _post_gen(srv.port, {"prompt": prompt,
                                        "temperature": -1})[0] == 400
            assert _post_gen(srv.port, {"prompt": prompt,
                                        "top_p": 0})[0] == 400
            assert _post_gen(srv.port, {"prompt": prompt,
                                        "top_k": "x"})[0] == 400
        gen.close()


# ---------------------------------------------------------------------------
# SDC blast radius: a poisoned logprob fails ONE sequence, not the batch
# ---------------------------------------------------------------------------

class TestSdcBlastRadius:
    def test_nan_logprob_drill_fails_exactly_one_sequence(
            self, model_params):
        """Seeded ``serving.logprob`` nan drill (docs/robustness.md, SDC
        section): the poisoned lane's sequence errors with a message
        naming the corruption; every batchmate finishes greedy-exact;
        all blocks return to the pool."""
        model, params, ref = model_params
        rng = np.random.RandomState(50)
        before = M.snapshot()
        F.configure("serving.logprob:nan:once", seed=SEED)
        prompts = [_prompt(rng, 4) for _ in range(3)]
        results = []
        with _engine(model, params, max_seqs=4) as eng:
            seqs = [eng.submit(p, max_tokens=6) for p in prompts]
            for s in seqs:
                try:
                    results.append(("ok", eng.result(s, timeout=240)))
                except RuntimeError as e:
                    assert "silent data corruption" in str(e)
                    results.append(("err", None))
            assert eng.allocator.in_use == 0
        assert sum(1 for st, _ in results if st == "err") == 1
        for i, (st, out) in enumerate(results):
            if st == "ok":
                assert out == _greedy_reference(ref, params, prompts[i], 6)
        assert _delta(before, 'hvd_tpu_faults_injected_total'
                              '{site="serving.logprob",kind="nan"}') == 1

    def test_nan_logprob_drill_is_one_500_on_the_wire(self, model_params):
        """The same drill through the HTTP front end: the poisoned
        request is a 500 naming the corruption; the next request on the
        same engine is a clean 200 — corruption never outlives the
        sequence it hit."""
        model, params, _ = model_params
        rng = np.random.RandomState(51)
        prompt = _prompt(rng, 4)
        F.configure("serving.logprob:nan:once", seed=SEED)
        gen = _engine(model, params)
        with serving.InferenceServer(engine=None, gen_engine=gen,
                                     port=0, addr="127.0.0.1") as srv:
            code, out = _post_gen(srv.port, {"prompt": prompt,
                                             "max_tokens": 4})
            assert code == 500
            assert "silent data corruption" in out["error"]
            code, out = _post_gen(srv.port, {"prompt": prompt,
                                             "max_tokens": 4})
            assert code == 200 and len(out["tokens"]) == 4
        gen.close()
