"""GLM-4.7-Flash and its multi-token prediction (MTP) module on the
generation plane: the block and the MTP module against the plain float32
reference, with and without the cache; the engine through the paged
cache with ``spec_mode='mtp'`` and without; MTP verify bit-identical to
plain decode, greedy and sampled, with drafts accepted and rejected;
the rollback of every plane, the MTP module's included; a draft at a
lane's last budgeted position never written; the verify program read
through the paged kernel where it lies; the engine refusing ``mtp`` for
a model without an MTP module; the counters of each proposer under its
own ``mode``. Tiny float32 sizes; the published widths run on the chip
(``perfbench/runners/serve_glm47_flash.py``)."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.models import (DeepseekV32, DeepseekV32Config, Glm47Flash,
                                Glm47FlashConfig, PagedCache)
from horovod_tpu.ops import paged_attention as pa
from horovod_tpu.serving import GenerationEngine
from horovod_tpu.serving.generation import kv_cache as kvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from perfbench.harness import core  # noqa: E402
from perfbench.reference import glm47_flash as ref  # noqa: E402

RUNNER = core.load_module(
    os.path.join(ROOT, "perfbench", "runners", "serve_glm47_flash.py"),
    "test_glm47_flash_runner")

BLOCK, VOCAB = 8, 97
CFG = Glm47FlashConfig(
    vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=8,
    held_experts=(0, 8), max_position_embeddings=256,
    dtype=jnp.float32, param_dtype=jnp.float32)
SETTINGS = dict(
    rope_theta=1e6, qk_nope_head_dim=16, qk_rope_head_dim=8,
    rms_norm_eps=1e-5, held_experts=[0, 8], num_experts_per_tok=4,
    n_group=1, topk_group=1, routed_scaling_factor=1.8,
    first_k_dense_replace=1)
MODEL = Glm47Flash(CFG)
TOL = 2e-5
#: the runner's construction at a gain where most drafts hold
DRAFTING_GAIN = 2.0


@pytest.fixture(scope="module", autouse=True)
def small_reference_blocks():
    was = ref.QUERY_BLOCK
    ref.QUERY_BLOCK = 16
    yield
    ref.QUERY_BLOCK = was


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def drafting():
    """Weights that draft: the runner's own construction."""
    return RUNNER.make_weights(MODEL, 7, DRAFTING_GAIN)


def _tokens(n, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0, VOCAB)


def _engine(params, **kw):
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("num_blocks", 96)
    kw.setdefault("max_seqs", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("deadline_ms", 0)
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(MODEL, params=params, **kw)


def _serve(params, jobs, **kw):
    """``[(tokens, logprobs)]`` of ``jobs`` (submit keyword arguments),
    all in flight together."""
    with _engine(params, **kw) as eng:
        seqs = [eng.submit(**j) for j in jobs]
        out = [(eng.result(s, timeout=300), list(s.logprobs)) for s in seqs]
        assert eng.allocator.in_use == 0
    return out


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _spec_delta(before, mode):
    after = hvd_metrics.snapshot()
    return {k: after.get(f'{k}{{mode="{mode}"}}', 0.0)
            - before.get(f'{k}{{mode="{mode}"}}', 0.0)
            for k in ("hvd_tpu_gen_spec_drafted_total",
                      "hvd_tpu_gen_spec_accepted_total")}


# -- the block and the MTP module against the reference -----------------------

def test_cache_declares_an_mtp_plane_last():
    spec = CFG.cache_spec()
    assert spec.planes == CFG.num_hidden_layers + 1
    assert spec.rows == (("latent", CFG.kv_lora_rank
                          + CFG.qk_rope_head_dim),)
    assert not spec.groups and not spec.state
    (pool,) = kvc.make_pools(CFG, 4, BLOCK)
    assert pool.shape == (4, 4, BLOCK, 128)


def test_main_and_mtp_logits_match_the_reference(params):
    tokens = _tokens(40, 1)
    logits, hidden = MODEL.apply(params, tokens, with_hidden=True)
    mtp = MODEL.apply(params, tokens[:, 1:], hidden=hidden[:, :-1],
                      method=Glm47Flash.mtp_forward)
    p = params["params"]
    want_h = ref.main(p, tokens, SETTINGS)
    assert _rel(logits, ref.head(p, want_h)) <= TOL
    assert _rel(mtp, ref.mtp(p, tokens, want_h, SETTINGS)) <= TOL


@pytest.mark.parametrize("chunk", [16, 2])
def test_paged_mtp_logits_match_the_reference(params, chunk):
    """The main model and the MTP module through the paged cache a chunk
    at a time (16 columns: the prefill chunk's walked form; 2: the
    verify step's absorbed one), the MTP tokens shifted by one with the
    next chunk's first token at each chunk's last column: every
    position's main logits and MTP logits against the reference."""
    S = 37
    tokens = np.asarray(_tokens(S + 1, 2))[0]
    pools = kvc.make_pools(CFG, 8, BLOCK)
    table = jnp.arange(1, 1 + CFG.max_seq_len // BLOCK, dtype=jnp.int32)

    @jax.jit
    def step(p, t, shifted, cache):
        logits, cache, h = MODEL.apply(p, t, cache=cache, with_hidden=True)
        mtp, cache = MODEL.apply(p, shifted, cache=cache, hidden=h,
                                 method=Glm47Flash.mtp_forward)
        return logits[0], mtp[0], cache

    mains, mtps, at = [], [], 0
    while at < S:
        live = min(chunk, S - at)
        t = np.zeros((1, chunk), np.int32)
        shifted = np.zeros((1, chunk), np.int32)
        t[0, :live] = tokens[at:at + live]
        shifted[0, :live] = tokens[at + 1:at + live + 1]
        logits, mtp, cache = step(params, jnp.asarray(t),
                                  jnp.asarray(shifted), PagedCache(
                                      pools, table[None], jnp.array([at]),
                                      jnp.array([live])))
        pools = cache.pools
        mains.append(logits[:live])
        mtps.append(mtp[:live])
        at += live
    p = params["params"]
    row = jnp.asarray(tokens[None])
    want_h = ref.main(p, row, SETTINGS)
    assert _rel(jnp.concatenate(mains), ref.head(p, want_h)[0, :S]) <= TOL
    assert _rel(jnp.concatenate(mtps),
                ref.mtp(p, row, want_h, SETTINGS)[0, :S]) <= TOL


@pytest.mark.parametrize("mode", ["off", "mtp"])
def test_engine_prefill_then_decode_match_the_reference(params, drafting,
                                                        mode):
    """Greedy requests across chunk boundaries through the engine, on
    seeded weights and on drafting ones: each served token is the
    reference's best at its position, with its log-probability."""
    for weights in (params, drafting):
        p = weights["params"]
        prompts = [np.asarray(_tokens(n, n)).tolist()[0] for n in (5, 23, 40)]
        out = _serve(weights, [dict(prompt=q, max_tokens=9)
                               for q in prompts], spec_mode=mode)
        for q, (toks, logps) in zip(prompts, out):
            row = jnp.asarray([q + toks[:-1]], jnp.int32)
            logits = np.asarray(ref.forward(p, row, SETTINGS)[0])[len(q) - 1:]
            lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            picked = np.arange(len(toks)), np.asarray(toks)
            assert np.all(logits.max(-1) - logits[picked] <= 1e-4)
            np.testing.assert_allclose(logps, lp[picked], atol=1e-4)


# -- MTP speculative decoding -------------------------------------------------

@pytest.mark.parametrize("sampling", [{}, dict(temperature=0.9, top_p=0.9,
                                               seed=11)],
                         ids=["greedy", "sampled"])
def test_mtp_is_bit_identical_to_plain_decode(params, drafting, sampling):
    """Tokens and logprobs equal with ``spec_mode='mtp'`` and ``off``, on
    weights whose drafts mostly miss and on weights whose drafts mostly
    hold; both kinds of step happen."""
    jobs = [dict(prompt=np.asarray(_tokens(n, 30 + n)).tolist()[0],
                 max_tokens=m, **sampling)
            for n, m in ((6, 14), (19, 11), (33, 17))]
    accepted = {}
    for name, weights in (("seeded", params), ("drafting", drafting)):
        plain = _serve(weights, jobs, spec_mode="off")
        before = hvd_metrics.snapshot()
        assert _serve(weights, jobs, spec_mode="mtp") == plain
        moved = _spec_delta(before, "mtp")
        assert moved["hvd_tpu_gen_spec_drafted_total"] > 0
        accepted[name] = moved["hvd_tpu_gen_spec_accepted_total"] \
            / moved["hvd_tpu_gen_spec_drafted_total"]
    if not sampling:
        assert accepted["drafting"] > 0.5 > accepted["seeded"]


def _watch(**kw):
    """An engine at ``kw`` whose ``on_step`` records, for each sequence
    that took a step and decodes on: ``("draft", prompt, position,
    draft)``, the draft the engine's programs returned and the last
    cached position it was made at; at the end of a prefill
    ``("plane", prompt, rows)``, the MTP plane's rows over the prompt;
    and ``("flights", n)``, the verify steps in flight at a delivery."""
    log, holder = [], []

    def on_step(phase, ids):
        eng = holder[0]
        b = eng.batcher
        if phase == "decode":
            log.append(("flights", len(b._inflight)))
        for s in b._running:
            if s.id not in ids or s.state != "decode":
                continue
            key = tuple(s.prompt)
            if phase == "prefill":
                plane = np.asarray(b._pools[0][-1])
                log.append(("plane", key, np.stack(
                    [plane[s.blocks[q // BLOCK], q % BLOCK]
                     for q in range(len(s.prompt))])))
            log.append(("draft", key, s.cache_len - 1, s.draft))

    eng = _engine(kw.pop("params"), on_step=on_step, **kw)
    holder.append(eng)
    return eng, log


def test_a_prefix_hit_drafts_as_a_cold_run(drafting):
    """A prompt that shares three blocks with an earlier one is served
    from the prefix cache with the MTP plane's rows over it and every
    draft equal to the same prompt served cold. The MTP row at a
    position reads the token after it, so the hit stops one block short
    (16 hit tokens, not 24) and that block is computed with this
    prompt's own next token."""
    shared = np.asarray(_tokens(3 * BLOCK, 40)).tolist()[0]
    first = shared + [(t + 1) % VOCAB for t in range(5, 15)]
    second = shared + list(range(20, 30))
    key = tuple(second)

    def served(prompts):
        eng, log = _watch(params=drafting, spec_mode="mtp",
                          prefix_cache=True, max_seqs=1)
        with eng:
            before = hvd_metrics.snapshot()
            for p in prompts:
                eng.result(eng.submit(prompt=p, max_tokens=12), timeout=300)
            after = hvd_metrics.snapshot()
        hit = 'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="local"}'
        rows = [r for kind, k, *r in (x for x in log if x[0] != "flights")
                if kind == "plane" and k == key]
        drafts = [r for kind, k, *r in (x for x in log if x[0] != "flights")
                  if kind == "draft" and k == key]
        return (after.get(hit, 0.0) - before.get(hit, 0.0), rows[0][0],
                drafts)

    hit_tokens, hit_rows, hit_drafts = served([first, second])
    cold_tokens, cold_rows, cold_drafts = served([second])
    assert (hit_tokens, cold_tokens) == (2 * BLOCK, 0)
    np.testing.assert_allclose(hit_rows, cold_rows, rtol=1e-5, atol=1e-6)
    assert hit_drafts == cold_drafts and len(hit_drafts) > 3


@pytest.mark.parametrize("depth", [0, 1])
def test_the_verify_step_is_kept_in_flight(drafting, depth):
    """With ``async_depth`` 1 the next MTP verify step is dispatched
    before the last one's tokens are read (one step in flight at every
    delivery once the lanes decode), with 0 none is; the tokens are the
    same either way."""
    jobs = [np.asarray(_tokens(n, 50 + n)).tolist()[0] for n in (7, 26)]
    out = []
    for d in (depth, 1 - depth):
        eng, log = _watch(params=drafting, spec_mode="mtp", async_depth=d)
        with eng:
            seqs = [eng.submit(prompt=p, max_tokens=15) for p in jobs]
            out.append([eng.result(s, timeout=300) for s in seqs])
        if d == depth:
            flights = [n for kind, n in (x[:2] for x in log)
                       if kind == "flights"]
    assert out[0] == out[1]
    if depth:
        assert max(flights) == 1
        assert sum(flights) > len(flights) // 2
    else:
        assert set(flights) == {0}


def _mtp_state(lanes, tokens, lengths, remaining, draft):
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return kvc.DecodeState(
        tokens=as_i32(tokens), lengths=as_i32(lengths),
        live=jnp.ones((lanes,), jnp.int32), remaining=as_i32(remaining),
        eos=jnp.full((lanes,), -1, jnp.int32),
        sample=kvc.SampleParams(
            temperature=jnp.zeros((lanes,), jnp.float32),
            top_k=jnp.zeros((lanes,), jnp.int32),
            top_p=jnp.ones((lanes,), jnp.float32),
            key=jnp.zeros((lanes, 2), jnp.uint32),
            emitted=jnp.zeros((lanes,), jnp.int32)),
        draft=as_i32(draft))


def _prefilled(params, prompt):
    """A pool holding ``prompt`` through the MTP prefill program (one
    lane, blocks 1.. of its table), seeded with noise everywhere else,
    and the first token and draft it sampled."""
    rng = np.random.RandomState(5)
    (pool,) = kvc.make_pools(CFG, 16, BLOCK)
    pool = jnp.asarray(rng.standard_normal(pool.shape), pool.dtype)
    table = jnp.arange(1, 1 + CFG.max_seq_len // BLOCK, dtype=jnp.int32)
    tok, _, cache, draft, _ = kvc.build_prefill_program(MODEL, mtp=True)(
        params, PagedCache((pool,), table[None], jnp.array([0]),
                           jnp.array([len(prompt)])),
        jnp.asarray([prompt + [0] * (32 - len(prompt))], jnp.int32),
        kvc.SampleParams(jnp.zeros((1,), jnp.float32),
                         jnp.zeros((1,), jnp.int32),
                         jnp.ones((1,), jnp.float32),
                         jnp.zeros((1, 2), jnp.uint32),
                         jnp.zeros((1,), jnp.int32)),
        jnp.array([-1], jnp.int32))
    return cache.pools, table[None], int(tok[0]), int(draft[0])


def _slots(pools, position):
    """Every plane's row at ``position`` of the lane (block 1 on)."""
    return np.asarray(pools[0][:, 1 + position // BLOCK, position % BLOCK])


def test_a_rejected_draft_leaves_every_plane_as_if_never_drafted(params):
    """A verify step whose draft misses writes, in every plane (the MTP
    module's included), exactly what the same step writes with no draft
    at all (a lane at its last budgeted token): the rejected row is
    rolled back, the committed one written. The main planes agree with
    the plain decode step's too."""
    prompt = np.asarray(_tokens(21, 4)).tolist()[0]
    pools, table, tok, _ = _prefilled(params, prompt)
    verify = kvc.build_verify_program(MODEL, 1, mtp=True)
    L = len(prompt)
    missed = verify(params, pools, table,
                    _mtp_state(1, [tok], [L], [9], [(tok + 1) % VOCAB]))
    pools, table, tok, _ = _prefilled(params, prompt)
    assert int(missed[4][0]) == 1 and int(missed[2][0, 0]) != (tok + 1) % VOCAB
    undrafted = verify(params, pools, table,
                       _mtp_state(1, [tok], [L], [1], [0]))
    assert int(undrafted[4][0]) == 1
    # past the null block, where dead columns and committed restores go
    np.testing.assert_array_equal(np.asarray(missed[0][0])[:, 1:],
                                  np.asarray(undrafted[0][0])[:, 1:])
    pools, table, tok, _ = _prefilled(params, prompt)
    before = _slots(pools, L + 1)
    np.testing.assert_array_equal(_slots(missed[0], L + 1), before)
    assert not np.array_equal(_slots(missed[0], L), _slots(pools, L))
    plain = kvc.build_decode_program(MODEL, 2)(
        params, pools, table, _mtp_state(1, [tok], [L], [9], [0]))
    np.testing.assert_array_equal(_slots(plain[0], L)[:-1],
                                  _slots(missed[0], L)[:-1])


def test_a_draft_at_the_last_budgeted_position_is_never_written(params):
    """With one token left to emit, the lane's draft (even a right one)
    is not scored: its slot keeps what it held in every plane, and the
    step emits one token. With two left, the same right draft is
    accepted and its row committed."""
    prompt = np.asarray(_tokens(13, 6)).tolist()[0]
    L = len(prompt)
    pools, table, tok, _ = _prefilled(params, prompt)
    verify = kvc.build_verify_program(MODEL, 1, mtp=True)
    plain = kvc.build_decode_program(MODEL, 2)(
        params, pools, table, _mtp_state(1, [tok], [L], [9], [0]))
    right = int(plain[2][0])                 # the token after tok
    pools, table, tok, _ = _prefilled(params, prompt)
    before = _slots(pools, L + 1)
    last = verify(params, pools, table, _mtp_state(1, [tok], [L], [1],
                                                   [right]))
    assert int(last[4][0]) == 1
    np.testing.assert_array_equal(_slots(last[0], L + 1), before)
    pools, table, tok, _ = _prefilled(params, prompt)
    two = verify(params, pools, table, _mtp_state(1, [tok], [L], [2],
                                                  [right]))
    assert int(two[4][0]) == 2
    assert not np.array_equal(_slots(two[0], L + 1), before)


def test_the_verify_step_reads_the_pool_where_it_lies(params, monkeypatch):
    """The MTP verify program traced as on the chip (the path rule asks
    for the default backend; the kernel interpreted): both of its passes,
    the main model's and the MTP module's, attend through the paged
    kernel, and no lane's whole table is gathered: no array of the
    table's rows exists in its lowered text."""
    lanes, max_blocks = 3, CFG.max_seq_len // BLOCK
    (pool,) = kvc.make_pools(CFG, 64, BLOCK)
    assert pa.shapes_fit(CFG.paged_query_rows(2), BLOCK, pool.shape[3],
                         pool.dtype) and not CFG.expands(2)
    interpreted = pa.paged_attention
    calls = []

    def counted(*a, **kw):
        calls.append(a[3])
        return interpreted(*a, **kw, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "paged_attention", counted)
    model = Glm47Flash(dataclasses.replace(CFG, rms_norm_eps=1.1e-5))
    text = kvc.build_verify_program(model, 1, mtp=True).lower(
        params, (pool,), jnp.zeros((lanes, max_blocks), jnp.int32),
        _mtp_state(lanes, [0] * lanes, [0] * lanes, [5] * lanes,
                   [0] * lanes)).as_text()
    assert len(calls) == CFG.num_hidden_layers + 1
    table_rows = f"{lanes}x{max_blocks * BLOCK}x{pool.shape[3]}"
    table_blocks = f"{lanes}x{max_blocks}x{BLOCK}x{pool.shape[3]}"
    assert not re.search(rf"tensor<({table_rows}|{table_blocks})x", text)


def test_mtp_is_refused_for_a_model_without_an_mtp_module():
    cfg = DeepseekV32Config(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, n_group=4, topk_group=2, index_n_heads=4,
        index_head_dim=16, index_topk=16, max_position_embeddings=256,
        rope_scaling=None, held_experts=(0, 8), dtype=jnp.float32,
        param_dtype=jnp.float32)
    assert kvc.mtp_depth(cfg) == 0
    with pytest.raises(ValueError, match="multi-token prediction"):
        GenerationEngine(DeepseekV32(cfg), params={}, spec_mode="mtp",
                         block_size=BLOCK, num_blocks=16)
    with pytest.raises(ValueError, match="multi-token prediction"):
        kvc.build_verify_program(DeepseekV32(cfg), 1, mtp=True)
    with pytest.raises(ValueError, match="drafts one token a module"):
        kvc.build_verify_program(MODEL, 2, mtp=True)


def test_the_verify_step_counts_its_routing_under_its_own_phase(params):
    """An MTP verify step's routing counts, the drafted column and the
    MTP module's pass included, go to phase='verify'; decode counts
    nothing in that engine."""
    before = hvd_metrics.snapshot()
    _serve(params, [dict(prompt=np.asarray(_tokens(9, 9)).tolist()[0],
                         max_tokens=6)], spec_mode="mtp")
    after = hvd_metrics.snapshot()

    def moved(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    calls = moved('hvd_tpu_gen_moe_calls_total{phase="verify"}')
    # one call a verify step read back (the pipeline's last one ran with
    # no live lane: it was in flight when the sixth token came back)
    key = 'hvd_tpu_gen_step_seconds{component="verify"}'
    steps = after[key]["count"] - before.get(key, {"count": 0})["count"]
    assert calls == steps >= 3  # six tokens: the prefill's, five steps'
    assert moved('hvd_tpu_gen_moe_calls_total{phase="decode"}') == 0
    assert moved('hvd_tpu_gen_moe_experts_touched_total{phase="verify"}') \
        > 0
