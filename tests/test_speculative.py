"""Speculative-decoding + beam-search suite (ISSUE 20).

Runs as its own seeded CI suite (``serving-spec`` in ci/gen_pipeline.py,
owns this file exclusively). The load-bearing pins:

* spec decode is BIT-IDENTICAL to plain decode — tokens AND logprobs —
  for greedy and for seeded temperature/top-k/top-p sampling, so the
  proposer can only ever change throughput, never output;
* PR 17's ``sample_offset`` failover resume composes with multi-token
  spec emission: a stream resumed onto a spec-enabled OR spec-disabled
  replica stays bit-identical;
* the ``serving.verify`` fault site fails exactly the verify step's
  batch ("serving.verify:error:once" drill), and the cache survives;
* beam width 1 is bit-identical to greedy; wider beams match a
  host-side full-forward oracle; blocks never leak across forks.
"""

import json
import time
from urllib.request import Request, urlopen

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import serving
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving import fleet
from horovod_tpu.serving.generation import GenerationEngine, NGramProposer
from horovod_tpu.serving.generation.spec import Proposer, make_proposer

SEED = 1234

CFG = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=96,
                        dtype=jnp.float32)

#: restrictive enough to exercise top-k AND top-p masking — the hard
#: case for verify-step sampling bit-identity
SAMPLED = dict(temperature=0.9, top_k=12, top_p=0.85)

PROMPT = [3, 11, 42, 7, 19, 5, 11, 42, 7]


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    F.configure("", seed=0)


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 49)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("deadline_ms", 0)
    return GenerationEngine(model, params=params, **kw)


def _spec_engine(model, params, **kw):
    kw.setdefault("spec_mode", "ngram")
    kw.setdefault("spec_tokens", 4)
    return _engine(model, params, **kw)


def _result(eng, **submit_kw):
    s = eng.submit(**submit_kw)
    return eng.result(s, timeout=240), list(s.logprobs)


def _delta(before, key):
    return M.snapshot().get(key, 0) - before.get(key, 0)


# ---------------------------------------------------------------------------
# the n-gram proposer (pure host logic)
# ---------------------------------------------------------------------------

class TestNGramProposer:
    def test_repetition_is_predicted(self):
        p = NGramProposer()
        # ... 7 8 9 [5 6] ... [5 6] -> predicts 7 8 9
        ctx = [1, 2, 5, 6, 7, 8, 9, 4, 5, 6]
        assert p.propose(ctx, 3) == [7, 8, 9]

    def test_longest_ngram_wins(self):
        p = NGramProposer(max_ngram=3)
        # trigram [1 2 3] recurs (-> 7); the bigram [2 3] also recurs
        # later (-> 9) but the longer match is the better predictor
        ctx = [1, 2, 3, 7, 2, 3, 9, 1, 2, 3]
        assert p.propose(ctx, 1) == [7]

    def test_most_recent_occurrence_wins(self):
        p = NGramProposer(max_ngram=1)
        ctx = [5, 1, 5, 2, 5]
        # unigram 5 occurred at 0 (-> 1) and 2 (-> 2): recency wins
        assert p.propose(ctx, 1) == [2]

    def test_no_match_is_empty(self):
        assert NGramProposer().propose([1, 2, 3, 4], 4) == []
        assert NGramProposer().propose([], 4) == []
        assert NGramProposer().propose([7], 4) == []

    def test_cap_bounds_the_draft(self):
        p = NGramProposer()
        ctx = [1, 2, 3, 4, 5, 6, 1, 2]
        assert p.propose(ctx, 2) == [3, 4]
        assert p.propose(ctx, 0) == []

    def test_make_proposer_dispatch(self):
        assert make_proposer("off") is None
        assert make_proposer("") is None
        assert isinstance(make_proposer("ngram"), NGramProposer)
        # the device drafts for mtp: a proposer that never proposes
        assert type(make_proposer("mtp")) is Proposer
        with pytest.raises(ValueError):
            make_proposer("draft")          # needs a draft_model
        with pytest.raises(ValueError):
            make_proposer("banana")


# ---------------------------------------------------------------------------
# spec decode == plain decode, bit for bit
# ---------------------------------------------------------------------------

class TestSpecBitIdentity:
    @pytest.mark.parametrize("sampling", [{}, dict(seed=7, **SAMPLED)],
                             ids=["greedy", "sampled"])
    def test_spec_output_identical_tokens_and_logprobs(self, model_params,
                                                      sampling):
        """The tentpole pin: with the n-gram proposer drafting, every
        emitted token AND logprob equals the plain decoder's — the
        verify program recomputes the deterministic fold_in(key,
        ordinal) draw at each position, so acceptance is exact."""
        model, params = model_params
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=32, **sampling)
        with _spec_engine(model, params) as eng:
            spec = _result(eng, prompt=PROMPT, max_tokens=32, **sampling)
            assert eng.allocator.in_use == 0
        assert spec[0] == plain[0]
        assert spec[1] == plain[1]          # logprobs, exact

    @pytest.mark.parametrize("spec_tokens", [1, 3, 8])
    def test_identity_holds_across_draft_widths(self, model_params,
                                                spec_tokens):
        model, params = model_params
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=24,
                            seed=11, **SAMPLED)
        with _spec_engine(model, params, spec_tokens=spec_tokens) as eng:
            spec = _result(eng, prompt=PROMPT, max_tokens=24,
                           seed=11, **SAMPLED)
        assert spec == plain

    def test_eos_inside_verify_window_stops_exactly(self, model_params):
        """EOS retirement must not depend on where in the verified
        chunk the EOS lands: pick the 3rd greedy token as the EOS id
        and re-run — both loops must emit the same (EOS-terminated)
        sequence."""
        model, params = model_params
        with _engine(model, params) as eng:
            base = _result(eng, prompt=PROMPT, max_tokens=24)[0]
        eos = base[2]
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=24, eos_id=eos)
        with _spec_engine(model, params) as eng:
            spec = _result(eng, prompt=PROMPT, max_tokens=24, eos_id=eos)
            assert eng.allocator.in_use == 0
        assert spec == plain
        assert spec[0][-1] == eos

    def test_concurrent_mixed_batch_identical(self, model_params):
        """Several lanes verifying concurrently — different prompts,
        greedy and sampled mixed — each must match its solo plain run."""
        model, params = model_params
        rng = np.random.RandomState(SEED)
        jobs = [dict(prompt=rng.randint(0, CFG.vocab_size, (5,)).tolist()
                     + PROMPT[:4], max_tokens=16 + 4 * i,
                     **({} if i % 2 else dict(seed=i, **SAMPLED)))
                for i in range(4)]
        with _engine(model, params) as eng:
            plain = [_result(eng, **j) for j in jobs]
        with _spec_engine(model, params) as eng:
            seqs = [eng.submit(**j) for j in jobs]
            spec = [(eng.result(s, timeout=240), list(s.logprobs))
                    for s in seqs]
            assert eng.allocator.in_use == 0
        assert spec == plain

    def test_spec_metrics_account_drafts_and_accepts(self, model_params):
        """drafted/accepted counters + the accept-length histogram and
        the verify component of hvd_tpu_gen_step_seconds all move; on a
        self-repeating greedy workload some drafts must be accepted."""
        model, params = model_params
        before = M.snapshot()
        with _spec_engine(model, params) as eng:
            _result(eng, prompt=PROMPT, max_tokens=48)
        drafted = _delta(before, 'hvd_tpu_gen_spec_drafted_total'
                                 '{mode="ngram"}')
        accepted = _delta(before, 'hvd_tpu_gen_spec_accepted_total'
                                  '{mode="ngram"}')
        assert drafted > 0
        assert 0 < accepted <= drafted
        hist = M.snapshot().get(
            'hvd_tpu_gen_spec_accept_length{mode="ngram"}')
        assert hist is not None and hist["count"] > 0
        key = 'hvd_tpu_gen_step_seconds{component="verify"}'
        assert M.snapshot()[key]["count"] > before.get(
            key, {"count": 0})["count"]


    def test_each_proposer_counts_under_its_own_mode(self, model_params):
        """The n-gram proposer's drafts and the draft model's go to the
        speculative counters under their own ``mode``, and neither moves
        the other's series or the MTP one."""
        model, params = model_params
        small = Transformer(TransformerConfig(
            vocab_size=64, num_layers=1, d_model=16, num_heads=2,
            head_dim=8, max_seq_len=64, dtype=jnp.float32))
        small_params = small.init(jax.random.PRNGKey(3),
                                  jnp.zeros((1, 4), jnp.int32))
        series = ("hvd_tpu_gen_spec_drafted_total",
                  "hvd_tpu_gen_spec_accepted_total")
        for mode, kw in (("ngram", {}),
                         ("draft", dict(draft_model=small,
                                        draft_params=small_params))):
            before = M.snapshot()
            with _spec_engine(model, params, spec_mode=mode, **kw) as eng:
                _result(eng, prompt=PROMPT, max_tokens=24)
            moved = {m: {k: _delta(before, f'{k}{{mode="{m}"}}')
                         for k in series}
                     for m in ("ngram", "draft", "mtp")}
            assert moved[mode]["hvd_tpu_gen_spec_drafted_total"] > 0
            hist = f'hvd_tpu_gen_spec_accept_length{{mode="{mode}"}}'
            assert M.snapshot()[hist]["count"] > before.get(
                hist, {"count": 0})["count"]
            for other in {"ngram", "draft", "mtp"} - {mode}:
                assert not any(moved[other].values()), (mode, other)


# ---------------------------------------------------------------------------
# failover: sample_offset resume composes with spec emission
# ---------------------------------------------------------------------------

class TestSpecFailover:
    @pytest.mark.parametrize("sampling", [{}, dict(seed=7, **SAMPLED)],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("resume_spec", [True, False],
                             ids=["onto-spec", "onto-plain"])
    def test_mid_stream_failover_during_spec_decode(self, model_params,
                                                    sampling, resume_spec):
        """The failover-during-spec-decode drill: a stream that died
        mid-generation on a spec replica is resumed — via the PR 17
        journal contract ``prompt + emitted`` with ``sample_offset=
        len(emitted)`` — onto a spec-enabled or spec-disabled replica.
        Either way the spliced stream equals the uninterrupted one."""
        model, params = model_params
        n, k = 24, 9
        with _engine(model, params) as eng:
            full = _result(eng, prompt=PROMPT, max_tokens=n, **sampling)[0]
        with _spec_engine(model, params) as eng:
            head = _result(eng, prompt=PROMPT, max_tokens=k, **sampling)[0]
        assert head == full[:k]
        maker = _spec_engine if resume_spec else _engine
        with maker(model, params) as eng:
            tail = _result(eng, prompt=PROMPT + head, max_tokens=n - k,
                           sample_offset=k, **sampling)[0]
        assert head + tail == full

    def test_verify_fault_fails_batch_and_recovers(self, model_params):
        """The ``serving.verify`` drill: an injected verify-step error
        ("serving.verify:error:once") fails exactly the in-flight
        batch; the pool drains clean and the next request is served
        bit-identically (no cache corruption)."""
        model, params = model_params
        with _engine(model, params) as eng:
            want = _result(eng, prompt=PROMPT, max_tokens=16)
        with _spec_engine(model, params) as eng:
            F.configure("serving.verify:error:once", seed=SEED)
            s = eng.submit(PROMPT, max_tokens=16)
            with pytest.raises(RuntimeError, match="serving.verify"):
                eng.result(s, timeout=240)
            F.configure("", seed=0)
            assert eng.allocator.in_use == 0
            assert _result(eng, prompt=PROMPT, max_tokens=16) == want


# ---------------------------------------------------------------------------
# beam search
# ---------------------------------------------------------------------------

def _beam_oracle(model, params, prompt, max_tokens, width, eos_id=None):
    """Host-side reference beam search over the jitted FULL forward —
    the oracle the paged beam program must reproduce (the existing
    suites pin decode-forward == full-forward bit-identity, so exact
    equality is the right assertion). Mirrors the scheduler's rules:
    candidates best-first with ties toward the older hypothesis and
    higher-ranked token, EOS/max_tokens candidates finish, the search
    prunes when no survivor can overtake the best finished score."""
    ref = jax.jit(model.apply)
    active = [{"tokens": [], "logprobs": [], "score": 0.0}]
    finished = []
    while active:
        cands = []
        for i, h in enumerate(active):
            seq = list(prompt) + h["tokens"]
            logits = np.asarray(
                ref(params, jnp.asarray([seq], jnp.int32)))[0, -1]
            lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
            order = np.argsort(-lp, kind="stable")
            for rank, t in enumerate(order[:max(width, 1)]):
                cands.append((h["score"] + float(lp[t]), i, rank, int(t),
                              float(lp[t])))
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        sel = []
        for score, i, _rank, t, lp_t in cands:
            if len(sel) >= width:
                break
            h = active[i]
            if (eos_id is not None and t == eos_id) \
                    or len(h["tokens"]) + 1 >= max_tokens:
                if len(finished) < width:
                    finished.append({"tokens": h["tokens"] + [t],
                                     "logprobs": h["logprobs"] + [lp_t],
                                     "score": score})
                continue
            sel.append((i, t, lp_t, score))
        active = [{"tokens": active[i]["tokens"] + [t],
                   "logprobs": active[i]["logprobs"] + [lp],
                   "score": score} for i, t, lp, score in sel]
        if finished and (len(finished) >= width or not active
                         or max(f["score"] for f in finished)
                         >= max(h["score"] for h in active)):
            break
    pool = finished if finished else active
    win = max(pool, key=lambda h: h["score"])
    return win["tokens"], win["logprobs"]


class TestBeamSearch:
    def test_width_one_is_bit_identical_to_greedy(self, model_params):
        """Acceptance pin: ``num_beams=1`` through the beam-capable
        engine and plain greedy decode are the same stream, tokens and
        logprobs."""
        model, params = model_params
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=24)
        with _engine(model, params, max_beams=3) as eng:
            beam = _result(eng, prompt=PROMPT, max_tokens=24, num_beams=1)
            assert eng.allocator.in_use == 0
        assert beam == plain

    @pytest.mark.parametrize("width", [2, 3])
    def test_beam_matches_host_oracle(self, model_params, width):
        model, params = model_params
        with _engine(model, params, max_beams=3) as eng:
            got = _result(eng, prompt=PROMPT, max_tokens=10,
                          num_beams=width)
            assert eng.allocator.in_use == 0
        want = _beam_oracle(model, params, PROMPT, 10, width)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], abs=1e-6)

    def test_beam_with_eos_matches_oracle(self, model_params):
        model, params = model_params
        with _engine(model, params) as eng:
            eos = _result(eng, prompt=PROMPT, max_tokens=12)[0][3]
        with _engine(model, params, max_beams=2) as eng:
            got = _result(eng, prompt=PROMPT, max_tokens=12,
                          num_beams=2, eos_id=eos)
            assert eng.allocator.in_use == 0
        want = _beam_oracle(model, params, PROMPT, 12, 2, eos_id=eos)
        assert got[0] == want[0]

    def test_single_token_prompt_branches_first_position(self,
                                                         model_params):
        """The held-back last prompt token makes even a 1-token prompt
        beam-search its FIRST generated position (empty prefill)."""
        model, params = model_params
        with _engine(model, params, max_beams=2) as eng:
            got = _result(eng, prompt=[7], max_tokens=6, num_beams=2)
            assert eng.allocator.in_use == 0
        want = _beam_oracle(model, params, [7], 6, 2)
        assert got[0] == want[0]

    def test_beam_and_plain_lanes_coexist(self, model_params):
        """A beam request runs synchronously beside batched plain
        lanes without disturbing their output."""
        model, params = model_params
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=16, seed=3,
                            **SAMPLED)
        with _engine(model, params, max_beams=2) as eng:
            s1 = eng.submit(PROMPT, max_tokens=16, seed=3, **SAMPLED)
            s2 = eng.submit(PROMPT, max_tokens=10, num_beams=2)
            got1 = (eng.result(s1, timeout=240), list(s1.logprobs))
            got2 = eng.result(s2, timeout=240)
            assert eng.allocator.in_use == 0
        assert got1 == plain
        assert got2 == _beam_oracle(model, params, PROMPT, 10, 2)[0]

    def test_beam_validation(self, model_params):
        model, params = model_params
        with _engine(model, params, max_beams=2) as eng:
            with pytest.raises(ValueError, match="num_beams"):
                eng.submit(PROMPT, max_tokens=4, num_beams=0)
            with pytest.raises(ValueError, match="beam cap"):
                eng.submit(PROMPT, max_tokens=4, num_beams=5)
            with pytest.raises(ValueError, match="greedy"):
                eng.submit(PROMPT, max_tokens=4, num_beams=2,
                           temperature=0.5)
        with _engine(model, params, max_beams=1) as eng:
            with pytest.raises(ValueError, match="disabled"):
                eng.submit(PROMPT, max_tokens=4, num_beams=2)

    def test_spec_and_beam_compose_on_one_engine(self, model_params):
        """An engine with both features routes beam requests through
        the beam loop and plain requests through the spec loop — each
        bit-identical to its reference."""
        model, params = model_params
        with _engine(model, params) as eng:
            plain = _result(eng, prompt=PROMPT, max_tokens=20)
        with _spec_engine(model, params, max_beams=2) as eng:
            assert eng.spec_mode == "ngram"
            assert eng.max_beams == 2
            spec = _result(eng, prompt=PROMPT, max_tokens=20)
            beam = _result(eng, prompt=PROMPT, max_tokens=8, num_beams=2)
            assert eng.allocator.in_use == 0
        assert spec == plain
        assert beam[0] == _beam_oracle(model, params, PROMPT, 8, 2)[0]


# ---------------------------------------------------------------------------
# health surfaces: /healthz + /fleet/health capability reporting
# ---------------------------------------------------------------------------

def _get(url):
    with urlopen(Request(url), timeout=10) as r:
        return json.loads(r.read())


class TestHealthSurfaces:
    def test_healthz_reports_spec_and_beam_enablement(self, model_params):
        model, params = model_params
        eng = _spec_engine(model, params, spec_tokens=5, max_beams=3)
        srv = serving.InferenceServer(None, port=0, addr="127.0.0.1",
                                      gen_engine=eng)
        srv.start()
        try:
            doc = _get(f"http://127.0.0.1:{srv.port}/healthz")
            assert doc["spec_mode"] == "ngram"
            assert doc["spec_tokens"] == 5
            assert doc["max_beams"] == 3
        finally:
            srv.close()
        eng2 = _engine(model, params, max_beams=1)
        srv2 = serving.InferenceServer(None, port=0, addr="127.0.0.1",
                                       gen_engine=eng2)
        srv2.start()
        try:
            doc = _get(f"http://127.0.0.1:{srv2.port}/healthz")
            assert doc["spec_mode"] == "off"
            assert doc["max_beams"] == 1
        finally:
            srv2.close()

    def test_fleet_health_republishes_beat_capabilities(self):
        """A replica's heartbeat carries its capability document; the
        router stores it and /fleet/health republishes it per replica,
        so a decode pool can be asserted homogeneous before prestage."""
        caps = {"spec_mode": "ngram", "spec_tokens": 4, "max_beams": 2}
        router = fleet.FleetRouter({"r0": "http://127.0.0.1:9"},
                                   port=0, addr="127.0.0.1",
                                   heartbeat_timeout=5.0,
                                   heartbeat_interval=0.1)
        router.start()
        hb = fleet.ReplicaHeartbeat(router.url, "r0", interval=0.1,
                                    capabilities=caps)
        try:
            assert hb.beat_once()
            deadline = time.monotonic() + 5
            got = None
            while time.monotonic() < deadline:
                got = _get(router.url + "/fleet/health")[
                    "replicas"]["r0"]["capabilities"]
                if got is not None:
                    break
                time.sleep(0.05)
            assert got == caps
            # a plain liveness beat must not clobber the advertisement
            fleet.ReplicaHeartbeat(router.url, "r0").beat_once()
            assert _get(router.url + "/fleet/health")[
                "replicas"]["r0"]["capabilities"] == caps
        finally:
            hb.stop()
            router.stop()
