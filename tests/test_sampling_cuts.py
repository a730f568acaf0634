"""The sampling epilogue's two restrictions (ISSUE 32): ``sample_tokens``
finds the top-k and top-p thresholds by a bitwise search that counts
and sums, where it used to sort the vocabulary twice.

Two oracles. A plain float64 numpy reference (sort, cumulative sum)
holds the *kept sets*: top-k exactly, top-p up to the one token whose
prefix mass lands within float32 rounding of ``top_p``. The function as
it stood before the search, kept here verbatim (``_sorted_reference``),
holds the *drawn tokens*: the per-seed draw is what it was. CPU only,
no engine except for the counter's test at the end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import metrics as M
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving.generation import GenerationEngine, SampleParams
from horovod_tpu.serving.generation import kv_cache
from horovod_tpu.serving.generation.kv_cache import (sample_cut,
                                                     sample_tokens)

VOCABS = (64, 16384, 50257)
KINDS = ("peaked", "flat", "tied")
TOP_PS = (1.0, 0.9, 0.5, 1e-3)
#: a prefix mass this close to ``top_p`` in float64 may fall on either
#: side in float32: the token there is not held to the reference
MASS_TOL = 1e-5


def _logits(vocab, kind, rows, seed):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal((rows, vocab))
    if kind == "peaked":
        x = 4.0 * x
    elif kind == "flat":            # what weights drawn from a seed give
        x = 0.05 * x
    else:                           # a handful of distinct values
        x = np.round(1.5 * x)
    return x.astype(np.float32)


def _battery(vocab, kind, seed=0):
    """One batch: every ``top_k`` x ``top_p`` on a sampling lane at two
    temperatures, and three greedy lanes carrying restrictions that must
    not matter."""
    lanes = [(t, k, p) for t in (0.7, 1.3) for k in (0, 1, 12, vocab)
             for p in TOP_PS]
    lanes += [(0.0, 0, 1.0), (0.0, 12, 0.5), (-1.0, 1, 1e-3)]
    temp, top_k, top_p = (np.asarray(c) for c in zip(*lanes))
    rows = len(lanes)
    key = np.asarray(jax.random.key_data(
        jax.random.split(jax.random.PRNGKey(seed + 7), rows)), np.uint32)
    sample = SampleParams(
        temperature=jnp.asarray(temp, jnp.float32),
        top_k=jnp.asarray(top_k, jnp.int32),
        top_p=jnp.asarray(top_p, jnp.float32),
        key=jnp.asarray(key),
        emitted=jnp.asarray(np.arange(rows) % 5, jnp.int32))
    return _logits(vocab, kind, rows, seed), sample


def _rows(sample, idx):
    idx = np.asarray(idx)
    return jax.tree_util.tree_map(lambda a: a[idx], sample)


def _scaled(logits, sample):
    temp = np.asarray(sample.temperature)
    return logits / np.where(temp <= 0.0, np.float32(1.0), temp)[:, None]


def _reference_top_k(scaled, top_k):
    """Kept set of the top-k restriction, float64: at or above the
    k-th largest score."""
    vocab = scaled.shape[-1]
    srt = np.sort(scaled.astype(np.float64), axis=-1)[:, ::-1]
    k_eff = np.clip(np.where(top_k <= 0, vocab, top_k), 1, vocab)
    kth = np.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    return scaled >= kth


def _reference_top_p(scaled, kept_k, top_p):
    """``(kept, boundary)`` of the top-p restriction over the top-k
    survivors, float64: a token stays while the mass strictly above it
    is under ``top_p`` (the sorted prefix rule, whole ties); ``boundary``
    marks the tokens whose mass above lies within ``MASS_TOL`` of it."""
    x = np.where(kept_k, scaled.astype(np.float64), -np.inf)
    p = np.exp(x - x.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    order = np.argsort(-p, axis=-1, kind="stable")
    psort = np.take_along_axis(p, order, axis=-1)
    above_sorted = np.cumsum(psort, axis=-1) - psort
    # ties share the mass above the first of them
    first = np.concatenate([np.ones_like(psort[:, :1], bool),
                            psort[:, 1:] != psort[:, :-1]], axis=-1)
    above_sorted = np.maximum.accumulate(
        np.where(first, above_sorted, 0.0), axis=-1)
    above = np.empty_like(p)
    np.put_along_axis(above, order, above_sorted, axis=-1)
    off = top_p[:, None] >= 1.0
    kept = kept_k & (off | (above < top_p[:, None])
                     | (p == p.max(axis=-1, keepdims=True)))
    boundary = ~off & (np.abs(above - top_p[:, None]) <= MASS_TOL)
    return kept, boundary


def _sorted_reference(logits, sample: SampleParams):
    """``_sample_tokens`` as it stood before ISSUE 32, verbatim: the
    oracle for the drawn tokens."""
    vocab = logits.shape[-1]
    greedy = sample.temperature <= 0.0
    argmax_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _draw(_):
        scaled = logits / jnp.where(greedy, 1.0,
                                    sample.temperature)[:, None]
        # top-k: threshold at the k-th highest score (k <= 0 keeps all)
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]
        k_eff = jnp.clip(jnp.where(sample.top_k <= 0, vocab,
                                   sample.top_k), 1, vocab)
        kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
        limited = jnp.where(scaled < kth, -jnp.inf, scaled)
        # top-p: smallest prefix of the sorted survivors holding >= p
        # mass; the exclusive cumsum always keeps the top token
        probs = jax.nn.softmax(limited, axis=-1)
        psort = jnp.sort(probs, axis=-1)[:, ::-1]
        csum = jnp.cumsum(psort, axis=-1)
        keep = jnp.sum((csum - psort) < sample.top_p[:, None], axis=-1)
        thresh = jnp.take_along_axis(
            psort, (jnp.maximum(keep, 1) - 1)[:, None], axis=-1)
        limited = jnp.where(
            (sample.top_p < 1.0)[:, None] & (probs < thresh),
            -jnp.inf, limited)
        keys = jax.vmap(jax.random.fold_in)(sample.key, sample.emitted)
        drawn = jax.vmap(jax.random.categorical)(keys, limited)
        return drawn.astype(jnp.int32)

    # all-greedy batches skip the two vocab sorts + categorical draw at
    # runtime; sampled lanes run the identical ops either way, so the
    # per-seed draw is unchanged by the branch
    drawn = jax.lax.cond(jnp.any(~greedy), _draw,
                         lambda _: argmax_tok, operand=None)
    token = jnp.where(greedy, argmax_tok, drawn)
    logprob = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), token[:, None], axis=-1)[:, 0]
    return token, logprob


_new = jax.jit(sample_tokens)
_old = jax.jit(_sorted_reference)
_restrict = jax.jit(kv_cache._restrict)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("vocab", VOCABS)
class TestKeptSets:
    def test_top_k_keeps_exactly_the_references_set(self, vocab, kind):
        logits, sample = _battery(vocab, kind)
        scaled = _scaled(logits, sample)
        limited = np.asarray(
            _restrict(jnp.asarray(scaled), sample, True, False))
        want = _reference_top_k(scaled, np.asarray(sample.top_k))
        np.testing.assert_array_equal(np.isfinite(limited), want)
        # what survives is untouched, bit for bit
        np.testing.assert_array_equal(limited[want], scaled[want])

    def test_top_p_keeps_the_references_set_off_the_boundary(self, vocab,
                                                             kind):
        logits, sample = _battery(vocab, kind)
        scaled = _scaled(logits, sample)
        kept = np.isfinite(np.asarray(
            _restrict(jnp.asarray(scaled), sample, True, True)))
        kept_k = _reference_top_k(scaled, np.asarray(sample.top_k))
        want, boundary = _reference_top_p(
            scaled, kept_k, np.asarray(sample.top_p, np.float64))
        differ = (kept != want) & ~boundary
        assert not differ.any(), (
            f"rows {sorted(set(np.nonzero(differ)[0]))} keep another set "
            f"than the float64 reference, off the boundary")
        # the boundary is a token or one tie, never a band
        assert ((kept != want).sum(axis=-1) <= boundary.sum(axis=-1)).all()
        top = scaled.argmax(axis=-1)
        assert kept[np.arange(len(top)), top].all()

    def test_draws_are_the_sorting_functions(self, vocab, kind):
        logits, sample = _battery(vocab, kind)
        tok, logp = _new(jnp.asarray(logits), sample)
        want_tok, want_logp = _old(jnp.asarray(logits), sample)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(want_tok))
        np.testing.assert_array_equal(np.asarray(logp),
                                      np.asarray(want_logp))
        greedy = np.asarray(sample.temperature) <= 0.0
        np.testing.assert_array_equal(np.asarray(tok)[greedy],
                                      logits.argmax(axis=-1)[greedy])

    def test_a_batch_of_one_draws_what_its_lane_drew(self, vocab, kind):
        """The prefill program's shape: one row, whose branches are
        chosen from that row alone."""
        logits, sample = _battery(vocab, kind)
        tok, _ = _new(jnp.asarray(logits), sample)
        for lane in (0, 1, 4, 10, 33):  # none, top_p, top_k, both, greedy
            one, _ = _new(jnp.asarray(logits[lane:lane + 1]),
                          _rows(sample, [lane]))
            assert int(one[0]) == int(tok[lane]), lane


@pytest.mark.parametrize("vocab", VOCABS)
def test_the_branch_taken_does_not_change_a_lanes_draw(vocab):
    """An all-greedy batch, a batch that samples with no restriction and
    a batch that runs both searches give a lane they share one token."""
    logits, sample = _battery(vocab, "flat", seed=3)
    temp, top_k, top_p = (np.asarray(a) for a in (
        sample.temperature, sample.top_k, sample.top_p))
    greedy = [i for i in range(len(temp)) if temp[i] <= 0.0]
    free = [i for i in range(len(temp))
            if temp[i] > 0 and top_k[i] == 0 and top_p[i] >= 1.0]
    cut = [i for i in range(len(temp))
           if temp[i] > 0 and 0 < top_k[i] < vocab and top_p[i] < 1.0]
    got = {}
    for lanes, label in ((greedy, "greedy"), (greedy + free, "none"),
                         (greedy + free + cut, "both")):
        assert sample_cut(temp[lanes], top_k[lanes], top_p[lanes]) == label
        tok, _ = _new(jnp.asarray(logits[lanes]), _rows(sample, lanes))
        for lane, t in zip(lanes, np.asarray(tok)):
            assert got.setdefault(lane, int(t)) == int(t), (lane, label)
    assert len(got) == len(greedy + free + cut)


def test_float64_parameters_draw_what_float32_ones_do():
    """The suite runs with x64 on, and a caller may hand over float64
    vectors: the scores are searched and drawn in float32 all the same."""
    logits, sample = _battery(64, "peaked")
    wide = SampleParams(sample.temperature.astype(jnp.float64), sample.top_k,
                        sample.top_p.astype(jnp.float64), sample.key,
                        sample.emitted)
    assert wide.temperature.dtype == jnp.float64
    np.testing.assert_array_equal(
        np.asarray(_new(jnp.asarray(logits), wide)[0]),
        np.asarray(_new(jnp.asarray(logits), sample)[0]))


@pytest.mark.parametrize("top_p", (0.0, -1.0))
def test_a_top_p_of_nothing_keeps_the_top_token(top_p):
    logits, sample = _battery(64, "peaked")
    sample = SampleParams(sample.temperature, sample.top_k,
                          jnp.full_like(sample.top_p, top_p), sample.key,
                          sample.emitted)
    scaled = _scaled(logits, sample)
    kept = np.isfinite(np.asarray(
        _restrict(jnp.asarray(scaled), sample, False, True)))
    np.testing.assert_array_equal(
        kept, scaled == scaled.max(axis=-1, keepdims=True))


@pytest.mark.parametrize("temp,top_k,top_p,cut", [
    ([0.0, -1.0], [5, 0], [0.5, 1.0], "greedy"),
    ([0.0, 1.0], [5, 0], [0.5, 1.0], "none"),
    ([0.7, 0.0], [5, 0], [1.0, 0.5], "top_k"),
    ([0.7, 0.0], [0, 9], [0.9, 1.0], "top_p"),
    ([0.7, 0.9], [0, 9], [0.9, 1.0], "both"),
])
def test_sample_cut_reads_the_sampling_lanes_only(temp, top_k, top_p, cut):
    assert sample_cut(np.asarray(temp, np.float32),
                      np.asarray(top_k, np.int32),
                      np.asarray(top_p, np.float32)) == cut


# ---------------------------------------------------------------------------
# the counter agrees with the branch the program took
# ---------------------------------------------------------------------------

CFG = TransformerConfig(vocab_size=64, num_layers=1, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=64,
                        dtype=jnp.float32)


def _steps():
    snap = M.snapshot()
    return {cut: snap.get('hvd_tpu_gen_sample_steps_total{cut="%s"}' % cut,
                          0)
            for cut in ("greedy",) + kv_cache.SAMPLE_CUTS}


@pytest.fixture(scope="module")
def probed_engine():
    """An engine on a model of its own, so that its programs are traced
    here, with a host callback inside each search: the callback runs
    only when the program takes that branch."""
    took = []
    searches = {"top_k": kv_cache._kth_largest,
                "top_p": kv_cache._nucleus_threshold}
    patch = pytest.MonkeyPatch()
    for name, fn in searches.items():
        def probed(*args, _name=name, _fn=fn):
            jax.debug.callback(lambda: took.append(_name))
            return _fn(*args)
        patch.setattr(kv_cache, fn.__name__, probed)
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    try:
        with GenerationEngine(model, params=params, block_size=4,
                              num_blocks=33, max_seqs=4, prefill_chunk=8,
                              deadline_ms=0) as eng:
            yield eng, took
    finally:
        patch.undo()


@pytest.mark.parametrize("cut,requests", [
    ("greedy", [dict(), dict()]),
    ("none", [dict(temperature=1.0), dict()]),
    ("top_k", [dict(temperature=0.8, top_k=5), dict(top_p=0.5)]),
    ("top_p", [dict(temperature=0.8, top_p=0.9), dict(top_k=3)]),
    ("both", [dict(temperature=0.8, top_k=5),
              dict(temperature=0.8, top_p=0.9)]),
])
def test_counter_names_the_branch_the_program_took(probed_engine, cut,
                                                   requests):
    """One batch of each kind through the engine: every dispatch of the
    batch counts under one ``cut`` (a greedy request's own prefill under
    ``greedy``), and the searches that ran are that cut's."""
    eng, took = probed_engine
    rng = np.random.RandomState(len(cut))
    before = _steps()
    del took[:]
    # one prefill chunk each, then decode steps that hold both lanes
    seqs = [eng.submit(rng.randint(0, CFG.vocab_size, (6,)).tolist(),
                       max_tokens=5, seed=11 + i, **kw)
            for i, kw in enumerate(requests)]
    for s in seqs:
        assert len(eng.result(s, timeout=240)) == 5
    jax.effects_barrier()
    after = _steps()
    moved = {c: after[c] - before[c] for c in after if after[c] != before[c]}
    alone = {sample_cut([kw.get("temperature", 0.0)], [kw.get("top_k", 0)],
                        [kw.get("top_p", 1.0)]) for kw in requests}
    assert set(moved) <= alone | {cut}, moved
    assert moved.get(cut, 0) >= 4, moved        # the shared decode steps
    want = {"greedy": set(), "none": set(), "top_k": {"top_k"},
            "top_p": {"top_p"}, "both": {"top_k", "top_p"}}
    assert set(took) == set().union(*(want[c] for c in moved)), (took, moved)
    ran = sum(n * len(want[c]) for c, n in moved.items())
    assert len(took) == ran, (len(took), moved)
