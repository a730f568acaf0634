"""Olmo-Hybrid on the serving path (ISSUE 31): a per-sequence recurrent
state beside the paged KV pool. Small sizes, seeded float32 weights, the
CPU.

(a) the gated delta rule: chunked form = one-token form = the
    recurrence as written; (b) the model against its plain reference
    (``perfbench/reference/olmo_hybrid.py``); (c) paged chunks and decode
    steps = the full forward, logits; pads and dead lanes leave a state
    bit-identical; (d) through ``GenerationEngine``: chunked prefill and
    decode, preempt-and-recompute, a second turn from a snapshot, a hit
    refused where no snapshot exists, every slot free after a cancel;
    (e) the allocator's state and snapshot slots; (f) what cannot carry
    a state refuses, naming it; (g) the state pools are aliased input to
    output in the compiled programs.
"""

import dataclasses
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import metrics as M
from horovod_tpu.models import (LongcatFlashConfig, OlmoHybrid,
                                OlmoHybridConfig, TransformerConfig)
from horovod_tpu.models.transformer import PagedCache
from horovod_tpu.ops import gated_delta
from horovod_tpu.serving import GenerationEngine
from horovod_tpu.serving.generation import kv_cache as kvc
from horovod_tpu.serving.generation.kv_cache import (BlockAllocator,
                                                     PerSequenceStateError,
                                                     chain_hash)
from horovod_tpu.serving.generation.scheduler import RequestCancelledError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference",
        os.path.join(ROOT, "perfbench", "reference", "olmo_hybrid.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

#: one period: three linear layers and a full one; 4 heads
CFG = OlmoHybridConfig(
    vocab_size=211, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    layer_types=("linear_attention",) * 3 + ("full_attention",),
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16, table_positions=128,
    dtype=jnp.float32, param_dtype=jnp.float32)
SETTINGS = dict(rms_norm_eps=1e-6, layer_types=list(CFG.layer_types),
                num_hidden_layers=4, num_attention_heads=4,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=16, linear_allow_neg_eigval=True)
#: float32 against float32 at ``highest``: the order of the sums and the
#: chunked form's triangular solve differ (measured 3e-6 on logits of
#: spread 0.16)
TOL = 5e-5
BLOCK, CHUNK, LANES = 8, 16, 4


@pytest.fixture(scope="module")
def params():
    return OlmoHybrid(CFG).init(jax.random.PRNGKey(1),
                                jnp.zeros((1, 4), jnp.int32))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size,
                                                n).tolist()


def _counter(name):
    return sum(v for k, v in M.snapshot().items() if k.startswith(name))


# -- (a) the gated delta rule -------------------------------------------------

def _recurrence(S, q, k, v, g, beta):
    """The recurrence as ISSUE 31 writes it, float64, ``S`` a
    ``dv x dk`` matrix a head."""
    B, T, H, _ = q.shape
    S, out = S.copy(), np.zeros(v.shape)
    for t in range(T):
        for b in range(B):
            for h in range(H):
                Sp = np.exp(g[b, t, h]) * S[b, h]
                r = beta[b, t, h] * (v[b, t, h] - Sp @ k[b, t, h])
                S[b, h] = Sp + np.outer(r, k[b, t, h])
                out[b, t, h] = S[b, h] @ q[b, t, h]
    return out, S


def _delta_inputs(T, seed=0):
    rng = np.random.default_rng(seed)
    B, H, dk, dv = 2, 3, 8, 12
    q = rng.normal(size=(B, T, H, dk))
    k = rng.normal(size=(B, T, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k[:, 5:12] = k[:, 5:6]                  # a run of one repeated key
    v = rng.normal(size=(B, T, H, dv))
    g = -np.exp(rng.normal(size=(B, T, H)))
    g[:, :4] = -40.0                         # alpha near 0
    g[:, 12:20] = -1e-6                      # alpha near 1
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(B, T, H))))
    beta[:, 5:12] = 1.999                    # beta near 2, on the run
    S0 = rng.normal(size=(B, H, dv, dk))
    return S0, q, k, v, g, beta


@pytest.mark.parametrize("T,chunk", [(64, 16), (37, 16), (37, 64), (23, 5),
                                     (130, 64), (2, 2), (9, 8)])
def test_chunked_form_equals_the_recurrence(T, chunk):
    S0, *xs = _delta_inputs(T)
    want_o, want_S = _recurrence(S0, *xs)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    o, S = gated_delta.gated_delta_chunked(
        f32(np.swapaxes(S0, -1, -2)), *map(f32, xs), chunk=chunk)
    assert o.dtype == S.dtype == jnp.float32
    assert np.abs(np.asarray(o) - want_o).max() < 5e-4
    assert np.abs(np.swapaxes(np.asarray(S), -1, -2) - want_S).max() < 5e-5


def test_one_token_form_equals_the_recurrence_and_the_chunked_form():
    S0, *xs = _delta_inputs(37)
    want_o, want_S = _recurrence(S0, *xs)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    S0t = f32(np.swapaxes(S0, -1, -2))
    o, S = gated_delta.gated_delta_recurrent(S0t, *map(f32, xs))
    assert np.abs(np.asarray(o) - want_o).max() < 5e-5
    assert np.abs(np.swapaxes(np.asarray(S), -1, -2) - want_S).max() < 5e-6
    o_c, S_c = gated_delta.gated_delta_chunked(S0t, *map(f32, xs), chunk=16)
    assert np.abs(np.asarray(o) - np.asarray(o_c)).max() < 5e-4
    assert np.abs(np.asarray(S) - np.asarray(S_c)).max() < 5e-5


@pytest.mark.parametrize("form", ["chunked", "recurrent"])
def test_a_token_of_no_decay_and_no_step_leaves_the_state_bit_identical(form):
    S0, q, k, v, g, beta = _delta_inputs(20)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    S0t = f32(np.swapaxes(S0, -1, -2))
    rule = gated_delta.gated_delta_chunked if form == "chunked" \
        else gated_delta.gated_delta_recurrent
    _, S = rule(S0t, f32(q), f32(k), f32(v), f32(0 * g), f32(0 * beta))
    assert np.array_equal(np.asarray(S), np.asarray(S0t))


# -- (b) the model against the reference --------------------------------------

@pytest.mark.parametrize("length", [1, 7, 40, 100])
def test_full_forward_matches_reference(params, length):
    toks = jnp.asarray([_tokens(length, length)])
    got = OlmoHybrid(CFG).apply(params, toks)
    want = ref.forward(params["params"], toks, SETTINGS)
    assert got.shape == want.shape == (1, length, CFG.vocab_size)
    assert float(jnp.abs(got - want).max()) < TOL


def test_reference_takes_positions(params):
    toks = jnp.asarray([_tokens(3, 30)])
    every = ref.forward(params["params"], toks, SETTINGS)
    some = ref.forward(params["params"], toks, SETTINGS,
                       at=jnp.asarray([4, 29]))
    assert np.allclose(np.asarray(every)[0, [4, 29]], np.asarray(some)[0],
                       atol=1e-6)


def test_layer_types_is_configuration():
    full_only = dataclasses.replace(
        CFG, layer_types=("full_attention",) * 4)
    assert full_only.cache_spec().state[0][1] == 0
    assert full_only.cache_spec().planes == 4
    assert CFG.planes_of("linear_attention") == (0, 1, 2, -1)
    assert CFG.planes_of("full_attention") == (-1, -1, -1, 0)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("full_attention",) * 3)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("window",) * 4)


def test_the_cache_declaration_names_rows_and_state():
    spec = CFG.cache_spec()
    assert spec.planes == 1 and spec.rows == (("k", 64), ("v", 64))
    assert [(n, p, s) for n, p, s, _ in spec.state] == [
        ("delta_state", 3, (4, 8, 16)), ("conv_window", 3, (3, 128))]
    assert spec.state[0][3] == jnp.float32
    assert kvc.state_bytes(CFG) == 3 * (4 * 8 * 16 * 4 + 3 * 128 * 4)
    # the published widths: 27.4 MB a sequence, 61 440 B of K/V a token
    real = OlmoHybridConfig(num_hidden_layers=16,
                            layer_types=OlmoHybridConfig().layer_types[:16])
    assert kvc.state_bytes(real) == 12 * (2211840 + 69120)
    assert kvc.block_bytes(real, 64) == 64 * 61440
    # the other served blocks declare none, and get the pools they had
    for cfg, pools in ((TransformerConfig(), 2), (LongcatFlashConfig(), 1)):
        assert cfg.cache_spec().state == ()
        shapes = jax.eval_shape(lambda c=cfg: kvc.make_pools(c, 4, 16))
        assert len(shapes) == pools


# -- (c) the paged path, programs called directly -----------------------------

def _prefill_chunks(prog, params, pools, table, slot, toks, start=0):
    """``toks`` through the raw program in chunks of CHUNK from position
    ``start``; returns (logits of the live columns, pools)."""
    out = []
    for at in range(0, len(toks), CHUNK):
        live = min(CHUNK, len(toks) - at)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :live] = toks[at:at + live]
        cache = PagedCache(pools, jnp.asarray(table),
                           jnp.asarray([start + at], jnp.int32),
                           jnp.asarray([live], jnp.int32),
                           jnp.asarray([slot], jnp.int32))
        logits, cache = prog(params, cache, jnp.asarray(chunk))
        pools = cache.pools
        out.append(np.asarray(logits)[0, :live])
    return np.concatenate(out), pools


def test_paged_chunks_and_decode_steps_equal_the_full_forward(params):
    model = OlmoHybrid(CFG)
    toks = _tokens(5, 50)
    full = np.asarray(model.apply(params, jnp.asarray([toks])))[0]
    pools = kvc.make_pools(CFG, 32, BLOCK, state_slots=LANES)
    assert [p.shape for p in pools] == [
        (1, 32, BLOCK, 128), (1, 32, BLOCK, 128),
        (3, LANES, 4, 8, 16), (3, LANES, 3, 128)]
    prog = kvc.build_program(model)
    table = np.zeros((1, 16), np.int32)
    table[0, :8] = np.arange(1, 9)
    slot = 2
    got, pools = _prefill_chunks(prog, params, pools, table, slot, toks[:40])
    tables = np.zeros((LANES, 16), np.int32)
    tables[slot] = table[0]
    before = [np.asarray(p) for p in pools[2:]]
    steps = []
    for pos in range(40, 50):
        chunk = np.zeros((LANES, 2), np.int32)
        chunk[slot, 0] = toks[pos]
        lengths = np.zeros((LANES,), np.int32)
        lengths[slot] = pos
        live = np.zeros((LANES,), np.int32)
        live[slot] = 1
        cache = PagedCache(pools, jnp.asarray(tables), jnp.asarray(lengths),
                           jnp.asarray(live))      # lanes are the slots
        logits, cache = prog(params, cache, jnp.asarray(chunk))
        pools = cache.pools
        steps.append(np.asarray(logits)[slot, :1])
    got = np.concatenate([got] + steps)
    assert np.abs(got - full).max() < TOL
    # ten decode steps, a pad column each, three dead lanes: their
    # states are bit-identical, the live lane's moved
    dead = [i for i in range(LANES) if i != slot]
    for was, now in zip(before, (np.asarray(p) for p in pools[2:])):
        assert np.array_equal(was[:, dead], now[:, dead])
        assert not np.array_equal(was[:, slot], now[:, slot])


def test_pad_columns_of_a_live_lane_do_not_touch_its_state(params):
    """A chunk of 5 live tokens and 11 pads leaves the state that the
    same 5 tokens leave as a chunk's whole live part of another width."""
    model = OlmoHybrid(CFG)
    prog = kvc.build_program(model)
    toks = _tokens(6, 21)
    table = np.zeros((1, 16), np.int32)
    table[0, :4] = np.arange(1, 5)

    def state_after(split):
        pools = kvc.make_pools(CFG, 8, BLOCK, state_slots=1)
        at = 0
        for n in split:
            _, pools = _prefill_chunks(prog, params, pools, table, 0,
                                       toks[at:at + n], start=at)
            at += n
        return [np.asarray(p) for p in pools[2:]]

    one = state_after([16, 5])
    for other in ([5, 16], [21 - 7, 7]):
        for a, b in zip(one, state_after(other)):
            assert np.abs(a - b).max() < 1e-5
    # the first layer's window holds the last three inputs exactly,
    # whatever the split (a deeper layer's inputs differ by rounding)
    assert np.array_equal(one[1][0], state_after([9, 12])[1][0])


def test_named_scopes_of_both_kinds_of_layer(params):
    model = OlmoHybrid(CFG)
    pools = kvc.make_pools(CFG, 8, BLOCK, state_slots=1)
    cache = PagedCache(pools, jnp.zeros((1, 16), jnp.int32),
                       jnp.zeros((1,), jnp.int32),
                       jnp.full((1,), CHUNK, jnp.int32),
                       jnp.zeros((1,), jnp.int32))
    text = kvc.build_program(model).lower(
        params, cache, jnp.zeros((1, CHUNK), jnp.int32)).as_text(
            debug_info=True)
    for scope in ("proj", "conv", "delta_rule", "gate_norm", "out_proj"):
        assert f"layer_0/linear_attn/{scope}" in text, scope
    for scope in ("qkv_proj", "kv_write", "kv_gather", "attention",
                  "out_proj"):
        assert f"layer_3/attn/{scope}" in text, scope
    assert "layer_3/mlp" in text and "head" in text


# -- (d) through the engine ---------------------------------------------------

def _engine(params, **kw):
    kw = dict(dict(max_seqs=LANES, block_size=BLOCK, num_blocks=64,
                   prefill_chunk=CHUNK, state_snapshots=6), **kw)
    return GenerationEngine(OlmoHybrid(CFG), params=params, **kw)


def _reference_logprobs(params, prompt, toks):
    row = jnp.asarray([prompt + toks[:-1]])
    logp = np.asarray(jax.nn.log_softmax(ref.forward(
        params["params"], row, SETTINGS)[0, len(prompt) - 1:], axis=-1))
    return logp, logp[np.arange(len(toks)), toks]


@pytest.mark.parametrize("prompt_len", [5, 16, 37, 70])
def test_chunked_prefill_and_decode_through_the_engine(params, prompt_len):
    """Inside a chunk, a whole chunk, across two boundaries, across
    four: every served token is the reference's best and its
    log-probability the reference's (logits, not tokens)."""
    prompt = _tokens(prompt_len, prompt_len)
    with _engine(params) as eng:
        seq = eng.submit(prompt, max_tokens=6)
        toks = eng.result(seq, timeout=120)
    logp, served = _reference_logprobs(params, prompt, toks)
    assert toks == logp.argmax(axis=-1).tolist()
    assert np.abs(np.asarray(seq.logprobs) - served).max() < TOL


def test_lanes_decode_together_each_on_its_slot(params):
    prompts = [_tokens(10 + i, n) for i, n in enumerate((5, 20, 33, 50, 9))]
    with _engine(params) as eng:
        seqs = [eng.submit(p, max_tokens=6) for p in prompts]
        outs = [eng.result(s, timeout=120) for s in seqs]
        assert eng.allocator.state_slots_in_use == 0
    for p, s, toks in zip(prompts, seqs, outs):
        _, served = _reference_logprobs(params, p, toks)
        assert np.abs(np.asarray(s.logprobs) - served).max() < TOL


def test_a_preempted_sequence_resumes_to_the_same_logits(params):
    """A pool too small for both sequences: the younger is preempted,
    its slot released, and its recompute (a zero state, or a snapshot,
    then a re-prefill of prompt and history) continues to the
    log-probabilities of the run that was never preempted."""
    prompts = [_tokens(21, 30), _tokens(22, 30)]
    with _engine(params) as eng:
        calm = [eng.submit(p, max_tokens=20) for p in prompts]
        want = [(eng.result(s, timeout=120), list(s.logprobs)) for s in calm]
    before = _counter("hvd_tpu_gen_preemptions_total")
    with _engine(params, num_blocks=12) as eng:    # 11 blocks: 88 tokens
        tight = [eng.submit(p, max_tokens=20) for p in prompts]
        got = [(eng.result(s, timeout=120), list(s.logprobs)) for s in tight]
        assert eng.allocator.state_slots_in_use == 0
        assert eng.allocator.in_use == 0
    assert _counter("hvd_tpu_gen_preemptions_total") > before
    for (toks, lp), (toks_w, lp_w) in zip(got, want):
        assert toks == toks_w
        assert np.abs(np.asarray(lp) - np.asarray(lp_w)).max() < TOL


def _hits():
    return _counter("hvd_tpu_gen_prefix_cache_hit_tokens_total")


def _snapshots(event):
    return M.snapshot().get(
        'hvd_tpu_gen_state_snapshots_total{event="%s"}' % event, 0.0)


def test_a_second_turn_from_a_snapshot_equals_the_prefix_cache_off(params):
    first = _tokens(31, 37)
    second = first + _tokens(32, 20)
    with _engine(params, prefix_cache=False) as eng:
        eng.generate(first, max_tokens=4)
        cold = eng.submit(second, max_tokens=6)
        want = eng.result(cold, timeout=120)
        assert eng.allocator.snapshot_slots_in_use == 0
    with _engine(params) as eng:
        taken = _snapshots("taken")
        eng.generate(first, max_tokens=4)
        # chunks of 16 end on block boundaries at 16 and 32: a snapshot each
        assert _snapshots("taken") - taken == 2
        hits, restored = _hits(), _snapshots("restored")
        warm = eng.submit(second, max_tokens=6)
        got = eng.result(warm, timeout=120)
        # the first turn indexed its blocks up to 40 tokens; the hit stops
        # at 32, the deepest block that owns a snapshot
        assert _hits() - hits == 32
        assert _snapshots("restored") - restored == 1
    assert got == want
    assert np.abs(np.asarray(warm.logprobs)
                  - np.asarray(cold.logprobs)).max() < TOL


def test_a_hit_is_refused_where_no_snapshot_exists(params):
    """Evict the snapshots, keep the blocks: the match falls back to the
    next shallower snapshot, then to nothing, and the answer stays."""
    first = _tokens(41, 37)
    second = first + _tokens(42, 20)
    with _engine(params) as eng:
        alloc = eng.allocator
        eng.generate(first, max_tokens=4)
        hashes = eng.kv_manifest(second)
        want = eng.generate(second, max_tokens=6)        # a hit of 32
        # snapshots now follow tokens 16, 32 and (from that run) 48
        blocks = [alloc._index[h] for h in hashes]       # still indexed
        assert alloc.match_probe(hashes)[0] == 6
        assert alloc.drop_snapshot(blocks[5]) and \
            alloc.drop_snapshot(blocks[3])
        assert not alloc.drop_snapshot(blocks[4])        # owned none
        assert alloc.match_probe(hashes)[0] == 2         # 16 tokens
        hits = _hits()
        assert eng.generate(second, max_tokens=6) == want
        assert _hits() - hits == 16
        # that run left snapshots at 32 and 48 again: drop them all
        for b in [alloc._index[h] for h in hashes]:
            alloc.drop_snapshot(b)
        assert alloc.snapshot_slots_in_use == 0
        assert alloc.match_probe(hashes) == (0, 0)
        assert alloc.cached_blocks >= 5                  # the blocks stayed
        hits = _hits()
        assert eng.generate(second, max_tokens=6) == want
        assert _hits() - hits == 0


def test_every_slot_is_free_after_a_cancel(params):
    with _engine(params) as eng:
        seqs = [eng.submit(_tokens(50 + i, 40), max_tokens=60,
                           request_id=f"r{i}") for i in range(3)]
        while not any(s.generated for s in seqs):
            pass
        for i in range(3):
            eng.cancel(f"r{i}")
        for s in seqs:
            with pytest.raises(RequestCancelledError):
                eng.result(s, timeout=120)
        alloc = eng.allocator
        assert alloc.in_use == 0 and alloc.state_slots_in_use == 0
        assert alloc.snapshots_orphaned() == 0
        assert M.snapshot()["hvd_tpu_gen_state_slots_in_use"] == 0
        # and the engine serves on, from a fresh slot
        prompt = _tokens(60, 20)
        toks = eng.generate(prompt, max_tokens=4)
        assert toks == _reference_logprobs(params, prompt, toks)[0].argmax(
            axis=-1).tolist()


def test_state_held_in_bfloat16_is_a_configuration_and_reads_worse(params):
    """``state_dtype`` is what the benchmark's tolerance tool lowers: the
    pool's dtype follows it, and the served log-probabilities move."""
    low = dataclasses.replace(CFG, state_dtype=jnp.bfloat16)
    assert kvc.make_pools(low, 4, BLOCK, state_slots=2)[2].dtype \
        == jnp.bfloat16
    prompt = _tokens(70, 60)
    with GenerationEngine(OlmoHybrid(low), params=params, max_seqs=LANES,
                          block_size=BLOCK, num_blocks=64,
                          prefill_chunk=CHUNK, state_snapshots=4) as eng:
        seq = eng.submit(prompt, max_tokens=6)
        toks = eng.result(seq, timeout=120)
    _, served = _reference_logprobs(params, prompt, toks)
    assert np.abs(np.asarray(seq.logprobs) - served).max() > 10 * TOL


# -- (e) the allocator's slots ------------------------------------------------

def _indexed_chain(alloc, n):
    blocks, hashes, parent = alloc.allocate(n), [], None
    for j, b in enumerate(blocks):
        parent = chain_hash(parent, [j])
        alloc.register(b, parent)
        hashes.append(parent)
    return blocks, hashes


def test_a_match_ends_at_the_deepest_snapshot():
    alloc = BlockAllocator(16, 4, prefix_cache=True, state_slots=2,
                           snapshot_slots=3, state_bytes=100)
    blocks, hashes = _indexed_chain(alloc, 5)
    assert alloc.match_probe(hashes) == (0, 0)          # no snapshot yet
    assert alloc.claim_snapshot(blocks[1]) in (1, 2, 3)
    assert alloc.claim_snapshot(blocks[1]) is None      # owns one already
    slot = alloc.claim_snapshot(blocks[3])
    assert alloc.match_probe(hashes) == (4, 0)
    got = alloc.match(hashes)
    assert got == blocks[:4] and alloc.snapshot_of(got[-1]) == slot
    alloc.free(got)
    assert alloc.snapshot_of(blocks[4]) == 0            # the null snapshot
    # a model without state is matched as far as the index goes
    plain = BlockAllocator(16, 4, prefix_cache=True)
    _, hashes = _indexed_chain(plain, 5)
    assert plain.match_probe(hashes) == (5, 0)
    assert plain.claim_snapshot(1) is None and plain.snapshot_slots == 0


def test_snapshots_go_least_recently_used_first_and_with_their_block():
    alloc = BlockAllocator(8, 4, prefix_cache=True, state_slots=2,
                           snapshot_slots=2, state_bytes=100)
    blocks, hashes = _indexed_chain(alloc, 4)
    evicted = _snapshots("evicted")
    first = alloc.claim_snapshot(blocks[0])
    alloc.claim_snapshot(blocks[1])
    assert alloc.snapshot_of(blocks[0]) == first        # 0 is now recent
    third = alloc.claim_snapshot(blocks[2])             # evicts block 1's
    assert _snapshots("evicted") - evicted == 1
    assert alloc.snapshot_of(blocks[1]) == 0
    assert {alloc.snapshot_of(blocks[0]), third} == {1, 2}
    assert alloc.snapshot_peak == 2 and alloc.snapshots_orphaned() == 0
    # park the chain, then allocate it away: the tail goes first, and
    # block 2's snapshot with it
    alloc.free(blocks)
    alloc.allocate(5)                                   # 3 free + 2 evicted
    assert alloc.snapshot_slots_in_use == 1
    assert alloc.match_probe(hashes) == (1, 1)
    alloc.reset_cache()
    assert alloc.snapshot_slots_in_use == 0
    assert alloc.snapshots_orphaned() == 0


def test_state_slots_are_taken_and_given_back():
    alloc = BlockAllocator(8, 4, state_slots=2, snapshot_slots=1)
    a, b = alloc.take_state_slot(), alloc.take_state_slot()
    assert {a, b} == {0, 1} and alloc.state_slots_in_use == 2
    with pytest.raises(RuntimeError, match="state slot"):
        alloc.take_state_slot()
    alloc.release_state_slot(a)
    with pytest.raises(ValueError, match="not held"):
        alloc.release_state_slot(a)
    assert alloc.take_state_slot() == a


# -- (f) what cannot carry a state refuses ------------------------------------

def test_verify_beam_and_disagg_refuse_naming_the_state(params):
    model = OlmoHybrid(CFG)
    named = "per-sequence recurrent state"
    with pytest.raises(PerSequenceStateError, match=named):
        kvc.build_verify_program(model, 3)
    with pytest.raises(PerSequenceStateError, match=named):
        kvc.build_beam_program(model, 2)
    with pytest.raises(PerSequenceStateError, match=named):
        GenerationEngine(model, params=params, spec_mode="ngram")
    # the registered defaults (spec off, 4 beams) construct: no beam
    # program is built, and a beam request is refused at submit
    with GenerationEngine(model, params=params, max_seqs=2, block_size=BLOCK,
                          num_blocks=16, prefill_chunk=CHUNK) as eng:
        assert eng.max_beams == 1 and eng.spec_mode == "off"
        assert eng.allocator.snapshot_slots == 48
        with pytest.raises(PerSequenceStateError, match=named):
            eng.submit([1, 2, 3], max_tokens=2, num_beams=2)
        with pytest.raises(PerSequenceStateError, match=named):
            eng.kv_export(eng.kv_manifest(list(range(20))))
        with pytest.raises(PerSequenceStateError, match=named):
            eng.kv_import(["a"], ["a"], None)
        assert eng.generate([1, 2, 3], max_tokens=2)
    assert issubclass(PerSequenceStateError, ValueError)    # HTTP 400


# -- (g) in place -------------------------------------------------------------

@pytest.mark.parametrize("name", ["prefill", "decode", "copy"])
def test_state_pools_are_aliased_input_to_output(params, name):
    model = OlmoHybrid(CFG)
    pools = kvc.make_pools(CFG, 16, BLOCK, state_slots=LANES)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    sample = lambda b: kvc.SampleParams(  # noqa: E731
        jnp.zeros((b,)), i32(b), jnp.ones((b,)),
        jnp.zeros((b, 2), jnp.uint32), i32(b))
    if name == "prefill":
        lowered = kvc.build_prefill_program(model).lower(
            params, PagedCache(pools, i32(1, 16), i32(1), i32(1), i32(1)),
            i32(1, CHUNK), sample(1))
    elif name == "decode":
        state = kvc.DecodeState(i32(LANES), i32(LANES), i32(LANES),
                                i32(LANES), i32(LANES), sample(LANES))
        lowered = kvc.build_decode_program(model, 2).lower(
            params, pools, i32(LANES, 16), state)
    else:
        snaps = kvc.make_state_pools(CFG, 5)
        lowered = kvc.build_state_copy_program().lower(
            snaps, pools[2:], 1, 2)
        pools = snaps
    hlo = lowered.compile().as_text()
    header = hlo.splitlines()[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, \w+=", header)
    aliased = set(map(int, re.findall(r"\((\d+), \{", alias.group(1))))
    entry = hlo[hlo.index("\nENTRY "):]
    for pool in pools[-2:]:
        dims = ",".join(map(str, pool.shape))
        mine = set(map(int, re.findall(
            r"\[%s\]\S* parameter\((\d+)\)" % re.escape(dims), entry)))
        assert mine and mine <= aliased, (name, pool.shape, mine, aliased)
