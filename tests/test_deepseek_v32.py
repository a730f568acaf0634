"""DeepSeek-V3.2 on the generation plane (ISSUE 38): the block against the
plain float32 reference, with and without the cache; the engine through
the paged cache (chunk boundaries, a prefix hit that reuses both rows,
preemption and recompute); the selection biting, and the sparse path
equal to the dense one where the choice is the whole prefix; the
segmented top-k; the group-limited router against a NumPy loop; YaRN
against its closed form; the shares of a layer against the uncut layer;
what the scheduler counts; beam, verify and the disagg wire. Tiny
float32 sizes with ``index_topk`` 16 and contexts of 40-130; the
published widths run on the chip
(``perfbench/runners/serve_deepseek_v32.py``)."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.models import DeepseekV32, DeepseekV32Config, PagedCache
from horovod_tpu.models import deepseek_v32 as dsv
from horovod_tpu.models import longcat_flash as lf
from horovod_tpu.models.blocks import YaRN, rope_frequencies
from horovod_tpu.parallel.moe import (held_experts_mlp,
                                      route_group_limited_topk)
from horovod_tpu.serving import GenerationEngine
from horovod_tpu.serving.disagg import pack_blocks, unpack_blocks
from horovod_tpu.serving.generation import kv_cache as kvc

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench.reference import deepseek_v32 as ref  # noqa: E402

TOPK, BLOCK, VOCAB = 16, 8, 97
YARN = YaRN(factor=4, original_max_position_embeddings=64)
CFG = DeepseekV32Config(
    vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    index_n_heads=4, index_head_dim=16, index_topk=TOPK,
    max_position_embeddings=256, rope_scaling=YARN, held_experts=(0, 4),
    dtype=jnp.float32, param_dtype=jnp.float32)
SETTINGS = dict(
    rope_theta=10000, rope_scaling=dict(
        factor=4, original_max_position_embeddings=64, beta_fast=32,
        beta_slow=1, mscale=1, mscale_all_dim=1),
    qk_nope_head_dim=16, qk_rope_head_dim=8, rms_norm_eps=1e-6,
    index_topk=TOPK, held_experts=[0, 4], num_experts_per_tok=4, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, first_k_dense_replace=1)
TOL = 2e-5


@pytest.fixture(scope="module", autouse=True)
def small_reference_blocks():
    was = ref.QUERY_BLOCK
    ref.QUERY_BLOCK = 16
    yield
    ref.QUERY_BLOCK = was


@pytest.fixture(scope="module")
def params():
    return jax.jit(DeepseekV32(CFG).init)(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 8), jnp.int32))


def _tokens(n, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0, VOCAB)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("num_blocks", 80)
    kw.setdefault("max_seqs", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("deadline_ms", 0)
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(DeepseekV32(cfg), params=params, **kw)


def _paged(params, tokens, chunk, cfg=CFG, num_blocks=40, tables=None,
           chosen=None):
    """The paged forward over ``tokens`` ``(1, S)`` a chunk at a time:
    every position's logits. ``tables`` ``(1, max blocks)``: the lane's
    table (blocks 1, 2, ... by default). A list given as ``chosen``
    receives each chunk's sown positions ``(layers, live, k)``."""
    model = DeepseekV32(cfg)
    mutable = ["moe_stats"] + ([] if chosen is None else ["dsa_chosen"])
    apply = jax.jit(lambda p, t, c: model.apply(p, t, cache=c,
                                                mutable=mutable))
    pools = kvc.make_pools(cfg, num_blocks, BLOCK)
    if tables is None:
        tables = jnp.arange(1, 1 + cfg.max_seq_len // BLOCK,
                            dtype=jnp.int32)[None]
    outs, at, S = [], 0, tokens.shape[1]
    while at < S:
        live = min(chunk, S - at)
        t = jnp.zeros((1, chunk), jnp.int32).at[0, :live].set(
            tokens[0, at:at + live])
        (logits, cache), sown = apply(params, t, PagedCache(
            pools, tables, jnp.array([at]), jnp.array([live])))
        pools = cache.pools
        outs.append(logits[0, :live])
        if chosen is not None:
            chosen.append(np.stack([np.asarray(
                sown["dsa_chosen"][f"layer_{i}"]["attn"]["indexer"][
                    "positions"][0])[0, :live]
                for i in range(cfg.num_hidden_layers)]))
        at += live
    return jnp.concatenate(outs)


def _reference_gap(params, prompt, toks, logprobs, settings=SETTINGS):
    """(worst reference-logit gap, worst log-probability difference) of
    served greedy ``toks`` after ``prompt``."""
    row = jnp.asarray([prompt + toks[:-1]], jnp.int32)
    logits = np.asarray(ref.forward(params["params"], row, settings)[0])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    logp = np.asarray(jax.nn.log_softmax(logits[at], axis=-1))
    return (max(float(logits[a].max() - logits[a, t])
                for a, t in zip(at, toks)),
            float(np.max(np.abs(np.asarray(logprobs)
                                - logp[np.arange(len(toks)), toks]))))


# -- the block against the reference ------------------------------------------

def test_cache_declares_a_latent_row_and_an_index_row():
    spec = CFG.cache_spec()
    assert spec.rows == (("latent", 40), ("index_key", 16))
    assert spec.planes == 3 and not spec.groups and not spec.state
    latent, index = kvc.make_pools(CFG, 9, BLOCK)
    assert latent.shape == (3, 9, BLOCK, 128)
    assert index.shape == (3, 9, BLOCK, 128)
    published = DeepseekV32Config(num_hidden_layers=5)
    assert [kvc._row(w) for _, w in published.cache_spec().rows] == [640, 128]
    assert kvc.block_bytes(published, 64) == 491520


@pytest.mark.parametrize("length", [40, 130])
def test_full_forward_matches_reference(params, length):
    tokens = _tokens(length, length)
    got = jax.jit(DeepseekV32(CFG).apply)(params, tokens)
    np.testing.assert_allclose(
        got, ref.forward(params["params"], tokens, SETTINGS), rtol=0,
        atol=TOL)


@pytest.mark.parametrize("chunk,shuffled", [
    (8, False), (16, False), (48, False), (48, True)],
    ids=["8", "16", "48", "48-shuffled"])
def test_paged_chunks_match_full_forward(params, monkeypatch, chunk,
                                         shuffled):
    """Chunks that cross the 16th position inside, on and past a chunk's
    edge: the selection on the paged path is the full forward's. Over a
    table of shuffled blocks and with the running top-k merging segments
    of 16, the positions the indexer sows are the full forward's top-k
    of every layer too."""
    tokens = _tokens(70, 3)
    model = DeepseekV32(CFG)
    full, state = jax.jit(lambda p, t: model.apply(
        p, t, capture_intermediates=lambda m, _: isinstance(m, dsv.Indexer)))(
            params, tokens)
    if not shuffled:
        np.testing.assert_allclose(_paged(params, tokens, chunk), full[0],
                                   rtol=0, atol=TOL)
        return
    monkeypatch.setattr(dsv, "TOP_K_SEGMENT", 16)
    rng = np.random.default_rng(5)
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[
        :CFG.max_seq_len // BLOCK][None], jnp.int32)
    chosen = []
    np.testing.assert_allclose(
        _paged(params, tokens, chunk, tables=tables, chosen=chosen), full[0],
        rtol=0, atol=TOL)
    chosen = np.concatenate(chosen, axis=1)           # (layers, S, k)
    inter = state["intermediates"]
    for i, layer in enumerate(chosen):
        keep = np.asarray(inter[f"layer_{i}"]["attn"]["indexer"][
            "__call__"][0])[0, 0]                     # (S, S)
        for s, picked in enumerate(layer):
            assert set(picked[picked >= 0]) == set(
                np.flatnonzero(keep[s, :s + 1])), (i, s)


def test_a_departure_from_the_equations_is_caught(params):
    """Each of the reference's faults moves the logits of a 70-token
    sequence well past the tolerance: dense attention, an indexer
    without its ReLU or its weights, float8 activations, and on the
    sparse branch alone a row read a position early or in float8."""
    tokens = _tokens(70, 5)
    clean = ref.forward(params["params"], tokens, SETTINGS)
    for fault in ("dense", "no_relu", "no_index_weights",
                  "activations_float8", "rows_shifted", "sparse_rows_float8"):
        off = ref.forward(params["params"], tokens, SETTINGS,
                          faults=(fault,))
        assert float(jnp.max(jnp.abs(off - clean))) > 100 * TOL, fault


def test_sparse_path_equals_the_dense_one_where_the_choice_is_whole(
        params, monkeypatch):
    """With ``index_topk`` at least the context the paged forward takes
    latent attention's own forms; made to select anyway, it reads every
    row of the prefix and gives the same logits."""
    tokens = _tokens(60, 6)
    wide = dataclasses.replace(CFG, index_topk=64)
    dense = _paged(params, tokens, 16, wide)
    monkeypatch.setattr(lf, "_whole_prefix",
                        lambda positions, held, k: jnp.array(False))
    sparse = _paged(params, tokens, 16, wide)
    np.testing.assert_allclose(sparse, dense, rtol=0, atol=TOL)
    np.testing.assert_allclose(
        dense, jax.jit(DeepseekV32(wide).apply)(params, tokens)[0], rtol=0,
        atol=TOL)


# -- the engine ---------------------------------------------------------------

def test_engine_serves_through_the_paged_cache(params):
    """Prompts inside a chunk, across chunks and past the 16th position,
    with replies that decode past it: greedy tokens and log-probabilities
    are the reference's, and every block is free afterwards."""
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, VOCAB, n).tolist(), m)
            for n, m in [(12, 10), (40, 6), (75, 8), (122, 8)]]
    with _engine(params, max_seqs=4, num_blocks=90) as eng:
        seqs = [eng.submit(p, max_tokens=m) for p, m in reqs]
        for (p, m), seq in zip(reqs, seqs):
            toks = eng.result(seq, timeout=240)
            gap, off = _reference_gap(params, p, toks, seq.logprobs)
            assert len(toks) == m and gap == 0.0 and off < 1e-4
        assert eng.allocator.in_use == 0


def test_selection_bites(params):
    """The same request served with the selection off (``index_topk``
    past the context: dense latent attention) is off the reference by
    more than the served path's tolerance."""
    prompt = np.asarray(_tokens(80, 9)).tolist()[0]
    off = dataclasses.replace(CFG, index_topk=4096)
    with _engine(params, cfg=off) as eng:
        seq = eng.submit(prompt, max_tokens=6)
        toks = eng.result(seq, timeout=240)
        _, worst = _reference_gap(params, prompt, toks, seq.logprobs)
    assert worst > 100 * 1e-4


def test_prefix_hit_reuses_both_rows(params):
    """A second request that shares 48 tokens with a first one is served
    from the cached blocks of both rows and is still the reference's."""
    rng = np.random.default_rng(4)
    shared = rng.integers(0, VOCAB, 48).tolist()
    first = shared + rng.integers(0, VOCAB, 9).tolist()
    second = shared + rng.integers(0, VOCAB, 21).tolist()
    hits = "hvd_tpu_gen_prefix_cache_hit_tokens_total{source=\"local\"}"
    with _engine(params, prefix_cache=True) as eng:
        eng.generate(first, max_tokens=4)
        before = hvd_metrics.snapshot().get(hits, 0.0)
        seq = eng.submit(second, max_tokens=6)
        toks = eng.result(seq, timeout=240)
        assert hvd_metrics.snapshot()[hits] - before >= 40
        gap, off = _reference_gap(params, second, toks, seq.logprobs)
        assert gap == 0.0 and off < 1e-4


def test_preemption_recomputes_both_rows(params):
    pre = "hvd_tpu_gen_preemptions_total"
    before = hvd_metrics.snapshot().get(pre, 0.0)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, VOCAB, 40).tolist(), 24) for _ in range(3)]
    with _engine(params, num_blocks=20) as eng:
        seqs = [eng.submit(p, max_tokens=m) for p, m in reqs]
        for (p, m), seq in zip(reqs, seqs):
            toks = eng.result(seq, timeout=240)
            gap, off = _reference_gap(params, p, toks, seq.logprobs)
            assert len(toks) == m and gap == 0.0 and off < 1e-4
        assert hvd_metrics.snapshot()[pre] > before
        assert eng.allocator.in_use == 0


def test_scheduler_counts_the_rows_attention_reads(params):
    """A 40-token prompt in chunks of 16 and three decode steps: the
    first chunk's queries see at most 16 rows (no selection), the rest
    select 16 of their prefix; the counter is the model's arithmetic."""
    names = {(k, ph): f'hvd_tpu_gen_sparse_attn_rows_total{{kind="{k}",'
             f'phase="{ph}"}}' for k in ("context", "scored", "selected")
             for ph in ("prefill", "decode")}
    before = hvd_metrics.snapshot()
    # synchronous: no decode step is dispatched past the last token
    with _engine(params, async_depth=0) as eng:
        eng.generate(np.asarray(_tokens(40, 11)).tolist()[0], max_tokens=4)
    after = hvd_metrics.snapshot()
    got = {k: after.get(n, 0.0) - before.get(n, 0.0)
           for k, n in names.items()}
    want = {}
    for phase, calls in (("prefill", [(0, 16), (16, 16), (32, 8)]),
                         ("decode", [(40, 1), (41, 1), (42, 1)])):
        for n, live in calls:
            for k, v in CFG.attention_reads([n], [live]).items():
                want[(k, phase)] = want.get((k, phase), 0) + v
    assert got == {k: float(v) for k, v in want.items()}
    assert want[("selected", "decode")] == 3 * TOPK
    assert CFG.attention_reads([0], [16]) == {
        "context": 136, "scored": 0, "selected": 136}


# -- the parts ----------------------------------------------------------------

@pytest.mark.parametrize("end,first,last", [
    (None, 99, 99), (37, 20, 36), (48, 17, 47), (100, 30, 99), (16, 0, 14)],
    ids=["None", "37", "48", "100", "short"])
def test_segmented_top_k_is_lax_top_k(monkeypatch, end, first, last):
    """By segments of 16 over 100 slots, two lanes of five queries whose
    positions lie in ``first..last`` (the walk's ``_NEG_INF`` past each,
    ``end`` inside a segment, on its edge, at the row's end, or none;
    ``short``: prefixes under ``k``), ties inside and across segments and
    both signed zeros: the values and positions of one ``top_k``, in its
    order; each position's row the one its lane's table maps it to
    (tables of shuffled, scattered blocks); valid exactly where the
    position is in the query's prefix."""
    monkeypatch.setattr(dsv, "TOP_K_SEGMENT", 16)
    T, k, bs, nb, plane = 100, 16, 4, 64, 2
    rng = np.random.default_rng(end or 0)
    positions = rng.integers(first, last + 1, (2, 5))
    positions[0, 0] = last
    tables = rng.permutation(np.arange(1, nb))[:2 * (T // bs)].reshape(2, -1)
    scores = jnp.round(jax.random.normal(jax.random.PRNGKey(2), (2, 5, T),
                                         jnp.float32), 1)
    # top_k ranks 0.0 above -0.0, whichever comes first
    scores = scores.at[..., 0].set(-0.0).at[..., 1].set(0.0)
    scores = jnp.where(jnp.arange(T) <= positions[..., None], scores,
                       dsv._NEG_INF)
    top, rows, idx = dsv.exact_top_k(
        scores, k, (dsv.slot_rows(jnp.asarray(tables), plane, nb, bs, T)[
            :, None], jnp.arange(T)), None if end is None else jnp.int32(end))
    want_top, want_idx = jax.lax.top_k(scores, k)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_array_equal(top, want_top)
    blocks = np.take_along_axis(tables[:, None, :], np.asarray(idx) // bs, 2)
    np.testing.assert_array_equal(
        rows, (plane * nb + blocks) * bs + np.asarray(idx) % bs)
    np.testing.assert_array_equal(top > dsv._NEG_INF,
                                  idx <= positions[..., None])


def _route_by_loop(logits, bias, k, n_group, topk_group, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    out_idx, out_w = [], []
    for row, b in zip(s, np.broadcast_to(bias, s.shape)):
        biased = row + b
        size = len(row) // n_group
        group = [sorted(biased[g * size:(g + 1) * size])[-2:]
                 for g in range(n_group)]
        order = sorted(range(n_group), key=lambda g: (-sum(group[g]), g))
        keep = set(order[:topk_group])
        cand = [(-biased[e], e) for e in range(len(row))
                if e // size in keep]
        idx = [e for _, e in sorted(cand)[:k]]
        w = row[idx] / row[idx].sum() * scale
        out_idx.append(idx)
        out_w.append(w)
    return np.asarray(out_idx), np.asarray(out_w)


def test_group_limited_router_against_a_loop():
    """A nonzero bias, a group whose best single score loses to two
    middling ones (it is masked), and two groups tied on their sum (the
    lower id is kept)."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(6, 16)).astype(np.float32)
    bias = (0.05 * rng.normal(size=16)).astype(np.float32)
    # row 0: group 0 holds the single best output, groups 1 and 2 the
    # better pairs: group 0 is masked
    logits[0] = -4.0
    logits[0, 0] = 6.0
    logits[0, 4:6] = 2.5
    logits[0, 8:10] = 2.0
    # row 1: groups 2 and 3 tied on their sum, without a bias
    logits[1] = -4.0
    logits[1, 8:10] = logits[1, 12:14] = 1.0
    logits[1, 0:2] = 3.0
    idx, w = route_group_limited_topk(jnp.asarray(logits), jnp.asarray(bias),
                                      3, 4, 2, 2.5)
    want_idx, want_w = _route_by_loop(logits, bias, 3, 4, 2, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    assert not set(np.asarray(idx)[0].tolist()) & {0, 1, 2, 3}
    tie_idx, _ = route_group_limited_topk(jnp.asarray(logits[1:2]),
                                          jnp.zeros(16), 4, 4, 2, 1.0)
    # groups 0 and 2 kept (2 beats the tied 3 by its lower id)
    assert set(np.asarray(tie_idx)[0].tolist()) == {0, 1, 8, 9}
    # the weights come from the unbiased scores, times the scale
    assert np.allclose(np.asarray(w).sum(axis=-1), 2.5)


def test_yarn_frequencies_and_the_softmax_scale():
    """The published rope_scaling against its closed form: correction
    dims 10 and 23 of the 32 pairs, and sigma = 192^-1/2 (0.1 ln 40 +
    1)^2."""
    d, theta = 64, 10000.0
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    f = theta ** (-2.0 * i / d)
    smooth = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = f / 40 * (1 - smooth) + f * smooth
    np.testing.assert_allclose(rope_frequencies(d, theta, YaRN()), want,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_frequencies(d, dict(
        rope_theta=theta, rope_scaling=dict(
            factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1))), want, rtol=1e-6)
    sigma = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert DeepseekV32Config().softmax_scale == pytest.approx(sigma,
                                                              rel=1e-12)
    assert sigma == pytest.approx(192 ** -0.5 * 1.8739, rel=1e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 routed experts over 4 chips, 2 each: the four shares' held parts
    plus the shared expert counted once are the uncut reference layer's
    routed and shared parts."""
    full = dataclasses.replace(CFG, n_routed_experts=8, n_group=4,
                               topk_group=2, held_experts=(0, 8))
    p = DeepseekV32(full).init(jax.random.PRNGKey(7), jnp.zeros(
        (1, 4), jnp.int32))["params"]["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(8), (40, CFG.hidden_size))
    idx, w = route_group_limited_topk(
        jnp.dot(u, p["router"], precision="highest"),
        p["e_score_correction_bias"], 4, 4, 2, 2.5)
    total, picks = 0.0, 0
    for first in (0, 2, 4, 6):
        held, _, stats = held_experts_mlp(
            u, idx, w, p["experts_gate"][first:first + 2],
            p["experts_up"][first:first + 2],
            p["experts_down"][first:first + 2], (first, first + 2), 8)
        total = total + held
        picks += int(stats[1])
    assert picks == 40 * 4
    s = p["shared_experts"]
    shared = ref._swiglu(u, s["gate_proj"], s["up_proj"], s["down_proj"])
    uncut = dict(SETTINGS, held_experts=(0, 8))
    np.testing.assert_allclose(total + shared, ref.experts(p, u, uncut),
                               rtol=0, atol=TOL)


# -- beam, verify and the disagg wire -----------------------------------------

@pytest.mark.parametrize("engine_kw,submit_kw", [
    (dict(spec_mode="ngram", spec_tokens=2), {}),
    (dict(max_beams=2), dict(num_beams=1)),
], ids=["verify", "beam_width_1"])
def test_verify_and_beam_carry_both_rows(params, engine_kw, submit_kw):
    """Past the 16th position, so that every step selects: the verify
    step (its rollback restores both rows) and a width-1 beam reproduce
    plain decode."""
    prompt = ([7, 8, 9] * 12)[:34]      # repetitive: the n-gram drafts
    with _engine(params) as eng:
        plain = eng.generate(prompt, max_tokens=8)
    with _engine(params, **engine_kw) as eng:
        seq = eng.submit(prompt, max_tokens=8, **submit_kw)
        assert eng.result(seq, timeout=240) == plain
        assert eng.allocator.in_use == 0


def test_beam_search_runs_the_model(params):
    with _engine(params, max_beams=2) as eng:
        seq = eng.submit(np.asarray(_tokens(30, 12)).tolist()[0],
                         max_tokens=4, num_beams=2)
        assert len(eng.result(seq, timeout=240)) == 4
        assert eng.allocator.in_use == 0


def test_both_rows_cross_the_disagg_wire(params):
    """Export a prompt's blocks (two pools), pack, unpack, import into a
    second engine: it serves the same tokens from the imported rows."""
    prompt = np.asarray(_tokens(41, 14)).tolist()[0]
    with _engine(params, prefix_cache=True) as a, \
            _engine(params, prefix_cache=True) as b:
        base = a.generate(prompt, max_tokens=4)
        hashes = a.kv_manifest(prompt)
        served, rows = a.kv_export(hashes)
        assert served == hashes and len(rows) == 2
        assert rows[1].shape == (3, len(hashes), BLOCK, 128)
        got, back, _ = unpack_blocks(pack_blocks(served, rows, "native"))
        assert got == served
        assert b.kv_import(hashes, served, back) == (0, len(hashes))
        assert b.generate(prompt, max_tokens=4) == base
