"""Continuous-batching generation suite (ISSUE 9): paged KV cache,
decode/full-forward parity, iteration-level scheduling, preemption,
and the seeded generation chaos drills.

Run as its own seeded CI suite (``serving-gen`` in ci/gen_pipeline.py,
owns this file exclusively). Everything is in-process on the CPU mesh
with a tiny fp32 transformer; the compiled prefill/decode programs are
shared across tests through ``build_program``'s memoization.
"""

import json
import threading
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import serving
from horovod_tpu.models.transformer import (PagedCache, Transformer,
                                            TransformerConfig)
from horovod_tpu.serving.batcher import (DeadlineExceededError,
                                         QueueFullError)
from horovod_tpu.serving.generation import (BlockAllocator,
                                            BlocksExhaustedError,
                                            GenerationEngine,
                                            build_program, make_pools)

SEED = 1234

CFG = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=64,
                        dtype=jnp.float32)


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


# ---------------------------------------------------------------------------
# block allocator: strict accounting
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_allocate_free_accounting_and_peak(self):
        a = BlockAllocator(num_blocks=9, block_size=4)
        assert a.capacity == 8 and a.free_blocks == 8 and a.in_use == 0
        got = a.allocate(5)
        assert len(got) == 5 and a.in_use == 5 and a.peak_in_use == 5
        a.free(got[:2])
        assert a.in_use == 3 and a.peak_in_use == 5
        a.free(got[2:])
        assert a.in_use == 0
        assert M.snapshot()["hvd_tpu_gen_kv_blocks_in_use"] == 0

    def test_null_block_never_handed_out(self):
        a = BlockAllocator(num_blocks=5, block_size=4)
        got = a.allocate(4)            # the whole usable pool
        assert 0 not in got
        assert sorted(got) == [1, 2, 3, 4]

    def test_exhaustion_is_all_or_nothing(self):
        a = BlockAllocator(num_blocks=5, block_size=4)
        a.allocate(3)
        with pytest.raises(BlocksExhaustedError):
            a.allocate(2)              # only 1 free: no partial grant
        assert a.free_blocks == 1      # nothing leaked by the failure

    def test_double_free_and_foreign_ids_raise(self):
        a = BlockAllocator(num_blocks=5, block_size=4)
        got = a.allocate(2)
        a.free(got)
        with pytest.raises(ValueError, match="double free"):
            a.free([got[0]])
        with pytest.raises(ValueError, match="invalid"):
            a.free([0])                # the null block is untouchable
        with pytest.raises(ValueError, match="invalid"):
            a.free([99])

    def test_blocks_for(self):
        a = BlockAllocator(num_blocks=5, block_size=4)
        assert [a.blocks_for(n) for n in (0, 1, 4, 5, 8, 9)] \
            == [1, 1, 1, 2, 2, 3]


# ---------------------------------------------------------------------------
# decode / full-forward parity: the paged path is the same math
# ---------------------------------------------------------------------------

#: how far a paged logit may lie from the full forward's, in float32
#: spacings of the largest logit (``np.spacing``). The paged programs sum
#: a row's scores over the gathered table, the full forward over the
#: sequence: the same terms in another order, so float32 rounds the
#: last bit otherwise. Measured on XLA-CPU (jax 0.9.0, eight seeds):
#: at most 8.9e-8 where the largest logit is 0.58 and its spacing
#: 6.0e-8, i.e. 1.5 spacings (6.7e-8 on this test's seed); 8 leaves a
#: margin of five. The faults the pin is for are four to seven orders
#: of magnitude away (``test_paged_parity_catches``).
PARITY_ULPS = 8


def _assert_logits_match(got, full):
    atol = PARITY_ULPS * float(np.spacing(np.abs(full).max()))
    np.testing.assert_allclose(got, full, rtol=0, atol=atol)


def _paged_against_full(model_params, fault=None):
    """Chunked prefill of 12 tokens, then four decode steps, through
    the raw-logits paged program, each compared with the jitted full
    forward over the same prefix. ``fault`` breaks decode step 13:
    ``stale_block`` points the table's second entry at the third block
    (positions 4-7 read 8-11's keys and values), ``position`` tells the
    program the sequence is one token shorter than it is."""
    model, params, ref = model_params
    rng = np.random.RandomState(7)
    toks = np.asarray(_prompt(rng, 16), np.int32)[None, :]
    program = build_program(model)
    k, v = make_pools(CFG, num_blocks=17, block_size=4)
    table = np.zeros((1, 16), np.int32)
    table[0, :4] = [1, 2, 3, 4]

    # prefill 12 prompt tokens in chunks of 8 (the tail chunk padded)
    full = np.asarray(ref(params, jnp.asarray(toks[:, :12])))
    got = []
    lengths = 0
    for chunk in (toks[0, :8], toks[0, 8:12]):
        buf = np.zeros((1, 8), np.int32)
        buf[0, :len(chunk)] = chunk
        cache = PagedCache((k, v), jnp.asarray(table),
                           jnp.asarray([lengths], jnp.int32),
                           jnp.asarray([len(chunk)], jnp.int32))
        logits, cache = program(params, cache, jnp.asarray(buf))
        k, v = cache.pools
        got.append(np.asarray(logits)[:, :len(chunk)])
        lengths += len(chunk)
    _assert_logits_match(np.concatenate(got, axis=1), full)

    # decode tokens 12..15 one at a time (the DECODE_WIDTH=2 chunk)
    from horovod_tpu.serving.generation.scheduler import DECODE_WIDTH
    for i in range(12, 16):
        buf = np.zeros((1, DECODE_WIDTH), np.int32)
        buf[0, 0] = toks[0, i]
        step_table, position = table, i
        if i == 13 and fault == "stale_block":
            step_table = table.copy()
            step_table[0, 1] = 3
        if i == 13 and fault == "position":
            position = i - 1
        cache = PagedCache((k, v), jnp.asarray(step_table),
                           jnp.asarray([position], jnp.int32),
                           jnp.asarray([1], jnp.int32))
        logits, cache = program(params, cache, jnp.asarray(buf))
        k, v = cache.pools
        full_i = np.asarray(ref(params, jnp.asarray(toks[:, :i + 1])))
        _assert_logits_match(np.asarray(logits)[0, 0], full_i[0, -1])


class TestPagedParity:
    def test_chunked_prefill_and_decode_match_full_forward(
            self, model_params):
        """Logits from chunked prefill and from every single-token
        decode step equal the full-sequence forward's for the same
        prefix to float32 rounding (``PARITY_ULPS`` spacings of the
        largest logit). XLA-CPU keeps no bit identity across program
        shapes on this jax: 511 of the 1024 logits compared differ, by
        at most 6.7e-8. What the pin still catches is anything that
        changes which terms are summed: a wrong position, a stale or
        mis-tabled block, a dead query column or a pad token leaking
        into the cache or the scores. Each moves a logit by a share of
        the logits' spread (0.12 here), not by a rounding."""
        _paged_against_full(model_params)

    @pytest.mark.parametrize("fault", ["stale_block", "position"])
    def test_paged_parity_catches(self, model_params, fault):
        """The tolerance is no blanket: one wrong block-table entry
        (measured 0.028 off, 5e4 times the tolerance) or a position off
        by one (0.43 off) at a single decode step fails the same
        comparison."""
        with pytest.raises(AssertionError, match="Not equal to tolerance"):
            _paged_against_full(model_params, fault=fault)

    def test_scheduled_generation_matches_reference_greedy(
            self, model_params):
        model, params, ref = model_params
        rng = np.random.RandomState(3)
        prompt = _prompt(rng, 11)      # > prefill_chunk: exercises chunking
        with _engine(model, params) as eng:
            out = eng.generate(prompt, max_tokens=12, timeout=120)
        assert out == _greedy_reference(ref, params, prompt, 12)

    def test_eos_retires_immediately(self, model_params):
        model, params, ref = model_params
        rng = np.random.RandomState(4)
        prompt = _prompt(rng, 5)
        first = _greedy_reference(ref, params, prompt, 1)[0]
        with _engine(model, params) as eng:
            out = eng.generate(prompt, max_tokens=10, eos_id=first,
                               timeout=120)
        assert out == [first]          # stopped at EOS, not max_tokens


# ---------------------------------------------------------------------------
# iteration-level scheduling
# ---------------------------------------------------------------------------

class TestContinuousBatching:
    def test_mixed_lengths_share_steps_and_retire_immediately(
            self, model_params):
        """Four mixed-length sequences run concurrently (occupancy
        histogram proves shared decode steps), all match the greedy
        oracle, and every KV block is back when the last retires."""
        model, params, ref = model_params
        rng = np.random.RandomState(5)
        before = M.snapshot()
        prompts = [_prompt(rng, 3 + i) for i in range(4)]
        lens = [3, 6, 9, 12]
        with _engine(model, params) as eng:
            reqs = [eng.submit(p, max_tokens=n)
                    for p, n in zip(prompts, lens)]
            outs = [eng.result(r, timeout=120) for r in reqs]
            assert eng.allocator.in_use == 0    # freed at retirement
        for p, n, out in zip(prompts, lens, outs):
            assert out == _greedy_reference(ref, params, p, n)
        occ = M.snapshot()["hvd_tpu_gen_batch_occupancy"]
        prev = before.get("hvd_tpu_gen_batch_occupancy",
                          {"count": 0, "sum": 0})
        steps = occ["count"] - prev["count"]
        seq_steps = occ["sum"] - prev["sum"]
        assert seq_steps == sum(lens) - 4   # first token comes from prefill
        assert steps < seq_steps            # some steps decoded >1 sequence
        assert _delta(before,
                      'hvd_tpu_gen_tokens_total{phase="decode"}') \
            == sum(lens)
        assert _delta(before,
                      'hvd_tpu_gen_tokens_total{phase="prefill"}') \
            == sum(len(p) for p in prompts)

    def test_midflight_admission_joins_within_one_decode_step(
            self, model_params):
        """A sequence submitted while another is decoding joins the
        running batch on the very next decode step after its prefill —
        the Orca property static batching lacks."""
        model, params, ref = model_params
        rng = np.random.RandomState(6)
        log = []
        eng = _engine(model, params, on_step=lambda phase, ids:
                      log.append((phase, list(ids))))
        try:
            a = eng.submit(_prompt(rng, 4), max_tokens=30)
            # wait until A is demonstrably mid-decode
            stream = eng.batcher.stream(a, timeout=60)
            for _ in range(3):
                next(stream)
            b = eng.submit(_prompt(rng, 4), max_tokens=4)
            out_b = eng.result(b, timeout=120)
            out_a = eng.result(a, timeout=120)
        finally:
            eng.close()
        assert len(out_a) == 30 and len(out_b) == 4
        # find B's final prefill in the step log; the next decode step
        # must already include B — and A must still be running in it
        b_prefills = [i for i, (ph, ids) in enumerate(log)
                      if ph == "prefill" and ids == [b.id]]
        after = next((ph, ids) for (ph, ids) in log[b_prefills[-1] + 1:]
                     if ph == "decode")
        assert b.id in after[1] and a.id in after[1], log

    def test_slot_freed_by_retirement_is_refilled(self, model_params):
        """More sequences than batch slots: the waiting line drains as
        slots free, everyone completes correctly."""
        model, params, ref = model_params
        rng = np.random.RandomState(8)
        prompts = [_prompt(rng, 4) for _ in range(5)]
        with _engine(model, params, max_seqs=2) as eng:
            reqs = [eng.submit(p, max_tokens=5) for p in prompts]
            outs = [eng.result(r, timeout=120) for r in reqs]
        for p, out in zip(prompts, outs):
            assert out == _greedy_reference(ref, params, p, 5)

    def test_stream_yields_tokens_incrementally(self, model_params):
        model, params, ref = model_params
        rng = np.random.RandomState(9)
        prompt = _prompt(rng, 4)
        with _engine(model, params) as eng:
            got = list(eng.stream(prompt, max_tokens=6, timeout=60))
        assert got == _greedy_reference(ref, params, prompt, 6)

    def test_preemption_requeues_and_completes(self, model_params):
        """Block exhaustion preempts the youngest sequence and requeues
        it instead of wedging: both sequences complete with exactly the
        unpreempted greedy outputs, hvd_tpu_gen_preemptions_total is
        the evidence, and the allocator ends balanced."""
        model, params, ref = model_params
        rng = np.random.RandomState(10)
        before = M.snapshot()
        # 2 sequences x (6 prompt + 20 generated) = 26 tokens each need
        # 7 blocks; a 9-block pool cannot hold both -> preempt
        p1, p2 = _prompt(rng, 6), _prompt(rng, 6)
        with _engine(model, params, num_blocks=10) as eng:
            r1 = eng.submit(p1, max_tokens=20)
            r2 = eng.submit(p2, max_tokens=20)
            o1 = eng.result(r1, timeout=240)
            o2 = eng.result(r2, timeout=240)
            assert eng.allocator.in_use == 0
        assert _delta(before, "hvd_tpu_gen_preemptions_total") >= 1
        assert o1 == _greedy_reference(ref, params, p1, 20)
        assert o2 == _greedy_reference(ref, params, p2, 20)

    def test_admission_validation(self, model_params):
        model, params, _ = model_params
        with _engine(model, params) as eng:
            with pytest.raises(ValueError, match="at least one token"):
                eng.submit([], max_tokens=4)
            with pytest.raises(ValueError, match="max_tokens"):
                eng.submit([1], max_tokens=0)
            with pytest.raises(ValueError, match="max_seq_len"):
                eng.submit([1] * 60, max_tokens=10)
            with pytest.raises(ValueError, match="vocab"):
                eng.submit([CFG.vocab_size + 3], max_tokens=4)
        # a request bigger than the whole pool is rejected up front
        # (could never be served; admission must not accept-and-wedge)
        with _engine(model, params, num_blocks=5) as eng:
            with pytest.raises(ValueError, match="whole pool"):
                eng.submit([1] * 20, max_tokens=10)

    def test_queue_full_rejects_fast(self, model_params):
        model, params, _ = model_params
        rng = np.random.RandomState(11)
        F.configure("serving.prefill:delay=0.5", seed=SEED)
        with _engine(model, params, queue_depth=1, max_seqs=1) as eng:
            first = eng.submit(_prompt(rng, 4), max_tokens=2)
            deadline = time.monotonic() + 10
            rejected = 0
            while time.monotonic() < deadline and rejected == 0:
                try:
                    eng.submit(_prompt(rng, 4), max_tokens=2)
                except QueueFullError:
                    rejected += 1
            assert rejected == 1
            F.configure("", seed=0)
            eng.result(first, timeout=120)

    def test_per_token_deadline_sheds_waiting_sequence(self, model_params):
        """The 429 shape, extended per token: a sequence parked behind a
        slow prefill past its deadline fails with the serving plane's
        DeadlineExceededError; a negative budget is shed at submit."""
        model, params, _ = model_params
        rng = np.random.RandomState(12)
        F.configure("serving.prefill:delay=0.4", seed=SEED)
        with _engine(model, params, max_seqs=1) as eng:
            slow = eng.submit(_prompt(rng, 4), max_tokens=2)
            late = eng.submit(_prompt(rng, 4), max_tokens=2,
                              deadline_ms=100)
            with pytest.raises(DeadlineExceededError):
                eng.result(late, timeout=60)
            F.configure("", seed=0)
            assert len(eng.result(slow, timeout=120)) == 2
            with pytest.raises(DeadlineExceededError, match="negative"):
                eng.submit(_prompt(rng, 4), deadline_ms=-5)

    def test_deadline_sheds_admitted_sequence_mid_prefill(
            self, model_params):
        """The contract covers *admitted* sequences too: a multi-chunk
        prefill that outlives the per-token budget is shed (429 shape)
        instead of holding its slot to completion."""
        model, params, _ = model_params
        rng = np.random.RandomState(20)
        F.configure("serving.prefill:delay=0.4", seed=SEED)
        with _engine(model, params, max_seqs=1) as eng:
            # 20-token prompt = 3 chunks of 8: expires after chunk 1
            seq = eng.submit(_prompt(rng, 20), max_tokens=2,
                             deadline_ms=150)
            with pytest.raises(DeadlineExceededError):
                eng.result(seq, timeout=60)
            F.configure("", seed=0)
            assert eng.allocator.in_use == 0    # shed freed its blocks

    def test_stream_timeout_raises_timeout_error(self, model_params):
        """A stalled next-token wait surfaces as TimeoutError (the
        result() contract), never a raw queue.Empty."""
        model, params, _ = model_params
        rng = np.random.RandomState(21)
        F.configure("serving.prefill:delay=0.5", seed=SEED)
        with _engine(model, params) as eng:
            it = eng.stream(_prompt(rng, 4), max_tokens=2, timeout=0.05)
            with pytest.raises(TimeoutError):
                next(it)

    def test_stop_fails_inflight_and_returns_blocks(self, model_params):
        model, params, _ = model_params
        rng = np.random.RandomState(13)
        eng = _engine(model, params)
        req = eng.submit(_prompt(rng, 4), max_tokens=40)
        eng.close()
        with pytest.raises(RuntimeError, match="stopped"):
            # a long generation interrupted by close() must fail its
            # waiter, not hang it
            eng.result(req, timeout=10)
        assert eng.allocator.in_use == 0


# ---------------------------------------------------------------------------
# seeded chaos drills: blast radius of each generation fault site
# ---------------------------------------------------------------------------

class TestGenerationChaos:
    def test_decode_error_once_fails_only_the_affected_sequences(
            self, model_params):
        """The ISSUE drill: a mid-decode error:once fails exactly the
        sequences in that decode step's batch; a waiting sequence is
        served clean immediately after, and every block returns."""
        model, params, ref = model_params
        rng = np.random.RandomState(14)
        before = M.snapshot()
        F.configure("serving.decode:error:once", seed=SEED)
        pa, pb = _prompt(rng, 4), _prompt(rng, 4)
        with _engine(model, params, max_seqs=1) as eng:
            a = eng.submit(pa, max_tokens=6)    # in the failing step
            b = eng.submit(pb, max_tokens=6)    # waiting: must survive
            with pytest.raises(F.InjectedFault, match="serving.decode"):
                eng.result(a, timeout=120)
            out_b = eng.result(b, timeout=120)
            assert eng.allocator.in_use == 0
        assert out_b == _greedy_reference(ref, params, pb, 6)
        assert _delta(before, 'hvd_tpu_faults_injected_total'
                              '{site="serving.decode",kind="error"}') == 1

    def test_prefill_error_once_fails_one_sequence(self, model_params):
        model, params, ref = model_params
        rng = np.random.RandomState(15)
        F.configure("serving.prefill:error:once", seed=SEED)
        pa, pb = _prompt(rng, 4), _prompt(rng, 4)
        with _engine(model, params, max_seqs=1) as eng:
            a = eng.submit(pa, max_tokens=4)
            b = eng.submit(pb, max_tokens=4)
            with pytest.raises(F.InjectedFault, match="serving.prefill"):
                eng.result(a, timeout=120)
            assert eng.result(b, timeout=120) \
                == _greedy_reference(ref, params, pb, 4)
            assert eng.allocator.in_use == 0

    def test_evict_error_fails_evicted_sequence_not_grower(
            self, model_params):
        """serving.evict:error — the eviction itself fails: the evicted
        (younger) sequence errors instead of requeueing, while the
        grower that triggered the eviction completes untouched."""
        model, params, ref = model_params
        rng = np.random.RandomState(16)
        F.configure("serving.evict:error:once", seed=SEED)
        p1, p2 = _prompt(rng, 6), _prompt(rng, 6)
        with _engine(model, params, num_blocks=10) as eng:
            r1 = eng.submit(p1, max_tokens=20)
            r2 = eng.submit(p2, max_tokens=20)
            o1 = eng.result(r1, timeout=240)
            with pytest.raises(F.InjectedFault, match="serving.evict"):
                eng.result(r2, timeout=240)
            assert eng.allocator.in_use == 0
        assert o1 == _greedy_reference(ref, params, p1, 20)

    def test_seeded_decode_fault_pattern_is_reproducible(self):
        pats = []
        for _ in range(3):
            F.configure("serving.decode:error:rate=0.4", seed=SEED)
            fp = F.FaultPoint("serving.decode")
            pat = []
            for _ in range(40):
                try:
                    fp.fire()
                    pat.append(0)
                except F.InjectedFault:
                    pat.append(1)
            pats.append(pat)
        assert pats[0] == pats[1] == pats[2]
        assert 4 < sum(pats[0]) < 32


# ---------------------------------------------------------------------------
# engine lifecycle: checkpoint restore + hot-reload reuse
# ---------------------------------------------------------------------------

class TestGenerationEngineLifecycle:
    def test_params_xor_checkpoint_dir(self, model_params):
        model, params, _ = model_params
        with pytest.raises(ValueError):
            GenerationEngine(model)
        with pytest.raises(ValueError):
            GenerationEngine(model, checkpoint_dir="/x", params=params)

    def test_checkpoint_restore_and_hot_reload(self, model_params,
                                               tmp_path):
        """The PR 5 lifecycle carries over: restore the latest committed
        step, serve, reload a newer one with the shared hot-swap
        machinery (metrics included)."""
        from horovod_tpu import checkpointing
        model, params, ref = model_params
        rng = np.random.RandomState(17)
        checkpointing.save(str(tmp_path), 1, params)
        before = M.snapshot()
        prompt = _prompt(rng, 4)
        eng = GenerationEngine(model, checkpoint_dir=str(tmp_path),
                               block_size=4, num_blocks=33, max_seqs=4,
                               prefill_chunk=8, deadline_ms=0,
                               reload_poll_seconds=0)
        try:
            assert eng.step == 1
            assert eng.generate(prompt, max_tokens=3, timeout=120) \
                == _greedy_reference(ref, params, prompt, 3)
            assert eng.reload() is False          # nothing newer
            checkpointing.save(str(tmp_path), 5, params)
            assert eng.reload() is True
            assert eng.step == 5
            # still serving, under the reloaded checkpoint
            assert eng.generate(prompt, max_tokens=3, timeout=120) \
                == _greedy_reference(ref, params, prompt, 3)
        finally:
            eng.close()
        assert _delta(
            before,
            'hvd_tpu_serving_hot_swaps_total{plane="generation"}') == 1
        assert M.snapshot()[
            'hvd_tpu_serving_checkpoint_step{plane="generation"}'] == 5


# ---------------------------------------------------------------------------
# e2e: the /v1/generate route on the serving front-end
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


class TestGenerateHTTP:
    def test_generate_route_healthz_and_infer_coexist(self, model_params):
        """Both engines behind one front-end: /v1/generate serves
        tokens, /v1/infer still serves rows, /healthz reports."""
        model, params, ref = model_params
        rng = np.random.RandomState(18)
        prompt = _prompt(rng, 5)
        inf = serving.InferenceEngine(
            lambda p, x: x @ p["w"], params={"w": np.eye(3, dtype=np.float32)},
            max_batch=4, batch_timeout_ms=5.0, deadline_ms=0,
            reload_poll_seconds=0, warmup=False)
        gen = _engine(model, params)
        srv = serving.InferenceServer(inf, port=0, addr="127.0.0.1",
                                      gen_engine=gen)
        srv.start()
        try:
            code, doc = _post_gen(srv.port,
                                  {"prompt": prompt, "max_tokens": 5})
            assert code == 200
            assert doc["tokens"] == _greedy_reference(ref, params, prompt, 5)
            assert doc["step"] == -1
            code, doc = _post_gen(srv.port, {"prompt": prompt,
                                             "max_tokens": 2,
                                             "eos_id": doc["tokens"][0]})
            assert code == 200 and len(doc["tokens"]) == 1
            req = Request(f"http://127.0.0.1:{srv.port}/v1/infer",
                          data=json.dumps(
                              {"inputs": [[1.0, 2.0, 3.0]]}).encode(),
                          method="POST")
            with urlopen(req, timeout=30) as resp:
                inf_doc = json.loads(resp.read())
            assert inf_doc["outputs"] == [[1.0, 2.0, 3.0]]
            with urlopen(f"http://127.0.0.1:{srv.port}/healthz",
                         timeout=10) as resp:
                health = json.loads(resp.read())
            assert health["status"] == "serving"
        finally:
            srv.close()

    def test_gen_only_server_404s_infer(self, model_params):
        model, params, _ = model_params
        gen = _engine(model, params)
        with serving.InferenceServer(engine=None, gen_engine=gen,
                                     port=0, addr="127.0.0.1") as srv:
            code, _doc = _post_gen(srv.port, {"prompt": [1],
                                              "max_tokens": 1})
            assert code == 200
            req = Request(f"http://127.0.0.1:{srv.port}/v1/infer",
                          data=b'{"inputs": [[1.0]]}', method="POST")
            with pytest.raises(HTTPError) as e:
                urlopen(req, timeout=10)
            assert e.value.code == 404

    def test_bad_requests_400(self, model_params):
        model, params, _ = model_params
        before = M.snapshot()
        gen = _engine(model, params)
        with serving.InferenceServer(engine=None, gen_engine=gen,
                                     port=0, addr="127.0.0.1") as srv:
            assert _post_gen(srv.port, {"max_tokens": 3})[0] == 400
            assert _post_gen(srv.port, {"prompt": "nope"})[0] == 400
            # could-never-fit is the client's 400, not a wedge
            assert _post_gen(srv.port, {"prompt": [1] * 60,
                                        "max_tokens": 30})[0] == 400
        assert _delta(before,
                      'hvd_tpu_serving_requests_total{code="400"}') == 3

    def test_deadline_and_queue_semantics_extend_per_token(
            self, model_params):
        """The PR 5 wire contract on the generation route: 429 when the
        per-token deadline expires, 503 when the bounded queue is full,
        while at least one request is served 200."""
        model, params, _ = model_params
        rng = np.random.RandomState(19)
        before = M.snapshot()
        F.configure("serving.prefill:delay=0.4", seed=SEED)
        gen = _engine(model, params, max_seqs=1, queue_depth=1)
        codes = []
        with serving.InferenceServer(engine=None, gen_engine=gen,
                                     port=0, addr="127.0.0.1") as srv:
            lock = threading.Lock()

            def client(deadline_ms):
                code, _ = _post_gen(srv.port,
                                    {"prompt": _prompt(rng, 4),
                                     "max_tokens": 2,
                                     "deadline_ms": deadline_ms})
                with lock:
                    codes.append(code)

            threads = [threading.Thread(target=client, args=(ddl,))
                       for ddl in (0, 150, 150, 150, 150, 150)]
            for t in threads:
                t.start()
                time.sleep(0.02)    # deterministic arrival order-ish
            for t in threads:
                t.join(timeout=120)
        assert codes and all(c in (200, 429, 503) for c in codes), codes
        assert 200 in codes
        assert 429 in codes or 503 in codes
        total = sum(
            _delta(before, f'hvd_tpu_serving_requests_total{{code="{c}"}}')
            for c in (200, 429, 503))
        assert total == len(codes)
