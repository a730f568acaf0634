"""LongCat-Flash on the serving path (ISSUE 27): the model against its
plain reference (``perfbench/reference/longcat_flash.py``, float32,
``highest``), small sizes, seeded weights, the CPU.

(a) full forward against the reference, and departures caught;
(b) chunked prefill then decode through ``GenerationEngine``;
(c) absorbed against expanded latent attention on the same cache, the
    expanded form's walk of the lane's blocks (ISSUE 34) against the
    whole table gathered, and the absorbed form through the paged
    kernel (ISSUE 37, interpreted) against its gather path;
(d) the shares of a layer's experts add up to the uncut layer;
(e) dropless under a router that sends most tokens to one expert;
(f) the routing counters; (g) the latent pool over the disagg wire;
(h) the verify and beam programs run the model.
((i), the latent pool's layout on a described TPU, is in
``tests/test_paged_inplace.py`` beside the GPT-2 block's.)
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import metrics as M
from horovod_tpu.models import LongcatFlash, LongcatFlashConfig
from horovod_tpu.models import longcat_flash as lf
from horovod_tpu.models.longcat_flash import HEAD_GROUP, LatentAttention
from horovod_tpu.models.transformer import PagedCache
from horovod_tpu.ops import paged_attention as pa
from horovod_tpu.parallel.moe import (STATS_FIELDS, TILE, held_experts_mlp,
                                      route_topk)
from horovod_tpu.serving import GenerationEngine
from horovod_tpu.serving.disagg import pack_blocks, unpack_blocks
from horovod_tpu.serving.generation import kv_cache as kvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "longcat_flash_reference",
        os.path.join(ROOT, "perfbench", "reference", "longcat_flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

#: two double layers, 4 heads, 8 routed + 4 zero experts, top 3; this
#: chip holds experts 2..5, so picks fall on held, zero and absent ones
CFG = LongcatFlashConfig(
    vocab_size=97, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, n_routed_experts=8, zero_expert_num=4, moe_topk=3,
    max_position_embeddings=64, held_experts=(2, 6), dtype=jnp.float32,
    param_dtype=jnp.float32)
#: the same with heads of 4: latent attention takes the expanded form
#: from r (dn + dv) / (2 r - dn - dv) = 4.6 queries a chunk, so that an
#: engine's prefill chunk (8) expands and its decode step (2) absorbs,
#: as 512 and 2 do at the published widths; ``CFG`` expands from 33
SPLIT = dataclasses.replace(CFG, qk_nope_head_dim=4, v_head_dim=4)
SETTINGS = dict(rms_norm_eps=1e-5, rope_theta=1e7, mla_scale_q_lora=True,
                mla_scale_kv_lora=True, moe_topk=3,
                routed_scaling_factor=6.0, n_routed_experts_published=8,
                held_experts=(2, 6))
#: float32 against float32 at ``highest``: only the order of the sums
#: differs (measured 2e-7 on logits of spread 0.16); every departure
#: below moves a logit by 1e-3 and more
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return LongcatFlash(CFG).init(jax.random.PRNGKey(1),
                                  jnp.zeros((1, 4), jnp.int32))


def _tokens(seed, *shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              CFG.vocab_size)


# -- (a) the full forward -------------------------------------------------------

@pytest.mark.parametrize("length", [40, 23], ids=["expanded", "absorbed"])
def test_full_forward_matches_reference(params, length):
    toks = _tokens(2, 2, length)
    assert CFG.expands(length) == (length == 40)
    got = LongcatFlash(CFG).apply(params, toks)
    want = ref.forward(params["params"], toks, SETTINGS)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def test_expanded_attention_by_groups_of_heads():
    """32 heads are two groups of ``HEAD_GROUP``; 4 heads (every other
    test) are one group taken whole."""
    cfg = dataclasses.replace(CFG, num_attention_heads=2 * HEAD_GROUP,
                              qk_nope_head_dim=4, v_head_dim=4,
                              num_layers=1)
    toks = _tokens(3, 1, 9)
    assert cfg.expands(9)
    model = LongcatFlash(cfg)
    p = model.init(jax.random.PRNGKey(2), toks)
    np.testing.assert_allclose(
        model.apply(p, toks), ref.forward(p["params"], toks, SETTINGS),
        rtol=0, atol=TOL)


def _shortcut_one_sublayer_early(p, h, st):
    eps = st["rms_norm_eps"]
    h = h + ref.attention(p["attn_0"],
                          ref._rms(h, p["input_layernorm_0"], eps), st)
    u = ref._rms(h, p["post_attention_layernorm_0"], eps)
    f = p["mlp_0"]
    h = h + ref._swiglu(u, f["gate_proj"], f["up_proj"], f["down_proj"]) \
        + ref.experts(p["moe"], u, st)            # here, not at the end
    h = h + ref.attention(p["attn_1"],
                          ref._rms(h, p["input_layernorm_1"], eps), st)
    f = p["mlp_1"]
    return h + ref._swiglu(ref._rms(h, p["post_attention_layernorm_1"], eps),
                           f["gate_proj"], f["up_proj"], f["down_proj"])


def _route_renormalised(u, router, bias, k, scale):
    w = ref_route(u, router, bias, k, scale)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale


ref_route = ref._route


@pytest.mark.parametrize("departure", ["eps", "no_kv_scale", "no_q_scale",
                                       "shortcut_early", "renormalised",
                                       "rotary_half_split"])
def test_a_departure_from_the_description_is_caught(params, monkeypatch,
                                                    departure):
    """The comparison is tight enough to tell these apart: each is run
    on the reference's side and must move some logit far past ``TOL``."""
    st = dict(SETTINGS)
    if departure == "eps":
        st["rms_norm_eps"] = 1e-6
    elif departure == "no_kv_scale":
        st["mla_scale_kv_lora"] = False
    elif departure == "no_q_scale":
        st["mla_scale_q_lora"] = False
    elif departure == "shortcut_early":
        monkeypatch.setattr(ref, "layer", _shortcut_one_sublayer_early)
    elif departure == "renormalised":
        monkeypatch.setattr(ref, "_route", _route_renormalised)
    elif departure == "rotary_half_split":
        def half_split(x, theta):       # the non-interleaved rotary
            d = x.shape[-1]
            perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
            return ref_rotate(x[..., np.argsort(perm)], theta)[..., perm]
        ref_rotate = ref._rotate
        monkeypatch.setattr(ref, "_rotate", half_split)
    toks = _tokens(2, 2, 23)
    got = LongcatFlash(CFG).apply(params, toks)
    wrong = ref.forward(params["params"], toks, st)
    assert float(jnp.abs(got - wrong).max()) > 20 * TOL, departure


# -- (b) through the engine ---------------------------------------------------

def _engine(params, cfg=CFG, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 40)
    kw.setdefault("max_seqs", 3)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("deadline_ms", 0)
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(LongcatFlash(cfg), params=params, **kw)


def _teacher_forced(params, prompt, toks):
    """Reference log-probabilities at the positions that produced
    ``toks`` after ``prompt``."""
    seq = jnp.asarray([list(prompt) + list(toks)], jnp.int32)
    logits = ref.forward(params["params"], seq, SETTINGS)[0]
    return np.asarray(jax.nn.log_softmax(
        logits[len(prompt) - 1:len(prompt) - 1 + len(toks)], axis=-1))


@pytest.fixture(scope="module")
def split_params():
    return LongcatFlash(SPLIT).init(jax.random.PRNGKey(1),
                                    jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("split", [True, False],
                         ids=["absorbed_decode", "absorbed_everywhere"])
def test_chunked_prefill_and_decode_through_the_engine(
        params, split_params, split):
    """Prompts inside a chunk, across chunk and block boundaries, and a
    dead lane beside them; the served logprob of every token is the
    reference's, and the token its argmax. The first case is the
    shipped split: a prefill chunk (8) expands, a decode step (2)
    absorbs; at the published widths the count of multiplications puts
    the line at 171 queries."""
    cfg, params = (SPLIT, split_params) if split else (CFG, params)
    assert cfg.expands(8) == split and not cfg.expands(2)
    published = LongcatFlashConfig()
    assert published.expands(171) and not published.expands(170)
    prompts = [np.asarray(_tokens(s, n)).tolist()
               for s, n in ((3, 5), (4, 19))]
    with _engine(params, cfg) as eng:
        seqs = [eng.submit(p, max_tokens=6) for p in prompts]
        outs = [(eng.result(s, timeout=240), list(s.logprobs))
                for s in seqs]
        assert eng.allocator.in_use == 0
    for prompt, (toks, logprobs) in zip(prompts, outs):
        want = _teacher_forced(params, prompt, toks)
        assert toks == want.argmax(-1).tolist()
        np.testing.assert_allclose(
            logprobs, want[np.arange(len(toks)), toks], rtol=0, atol=1e-4)


# -- (c) the two forms on the same cache --------------------------------------

def test_absorbed_equals_expanded_on_the_same_cache(split_params):
    """Eleven tokens of one sequence through the paged cache as chunks
    of 8 (6 and 5 live: the expanded form) and as chunks of 4 (4, 2, 4
    and 1 live: the absorbed form): the same logits at every live
    position, over block boundaries and pad tokens, and the same rows
    left in the pool."""
    bs, tables = 4, [[3, 1, 2, 5, 0, 0]]
    seq = np.asarray(_tokens(5, 1, 11))
    program = kvc.build_program(LongcatFlash(SPLIT))

    def run(width, lives):
        assert SPLIT.expands(width) == (width == 8)
        (pool,), length, outs = kvc.make_pools(SPLIT, 7, bs), 0, []
        for live in lives:
            toks = np.zeros((1, width), np.int32)
            toks[0, :live] = seq[0, length:length + live]
            logits, cache = program(
                split_params,
                PagedCache((pool,), jnp.asarray(tables, jnp.int32),
                           jnp.asarray([length], jnp.int32),
                           jnp.asarray([live], jnp.int32)),
                jnp.asarray(toks))
            (pool,) = cache.pools
            outs.append(np.asarray(logits)[0, :live])
            length += live
        # block 0 is the null block, where pad tokens write
        return np.concatenate(outs), np.asarray(pool)[:, 1:]

    expanded, absorbed = run(8, (6, 5)), run(4, (4, 2, 4, 1))
    np.testing.assert_allclose(absorbed[0], expanded[0], rtol=0, atol=TOL)
    # the rows a token leaves do not depend on the form that reads them
    np.testing.assert_allclose(absorbed[1], expanded[1], rtol=0, atol=TOL)
    want = ref.forward(split_params["params"], jnp.asarray(seq), SETTINGS)
    np.testing.assert_allclose(absorbed[0], np.asarray(want)[0], rtol=0,
                               atol=TOL)


# -- (c) the walk of the paged expanded form -----------------------------------

#: one double layer of ``SPLIT``; blocks of 4 slots, key blocks of 8 (two
#: blocks), a chunk of 8, a table of 10 blocks: 40 slots, 5 key blocks
WALK = dataclasses.replace(SPLIT, num_layers=1, max_position_embeddings=40)
WALK_BS, WALK_KEYS, WALK_CHUNK, WALK_MAX_BLOCKS = 4, 8, 8, 10
WALK_TABLE = WALK_MAX_BLOCKS * WALK_BS
#: a lane's blocks in no order (0 is the null block); a block of large
#: rows, which a walked key block may hold past the sequence; a block of
#: NaN, which no walk may touch
WALK_BLOCKS, JUNK, POISON = [7, 3, 12, 5, 9, 1, 14, 2, 11, 6], 21, 22


@pytest.fixture
def key_blocks_of_8(monkeypatch):
    monkeypatch.setattr(lf, "KEY_BLOCK", WALK_KEYS)
    assert WALK.expands(WALK_CHUNK) and not WALK.expands(2)


@pytest.fixture(scope="module")
def walk_params():
    return LongcatFlash(WALK).init(jax.random.PRNGKey(1),
                                   jnp.zeros((1, 4), jnp.int32))


def _seeded_pool():
    """Every block holds seeded rows, as a pool in use does."""
    (pool,) = kvc.make_pools(WALK, 24, WALK_BS)
    rows = np.random.RandomState(0).standard_normal(pool.shape)
    rows[:, JUNK] *= 50.0
    rows[:, POISON] = np.nan
    return jnp.asarray(rows, pool.dtype)


def _whole_table(self, q_nope, q_rope, pool, plane, tables, positions, live,
                 w_uk, w_uv, scale):
    """What the walk replaces: every slot of the table gathered, the
    dense form under ``_masked_softmax`` over a table-wide mask."""
    rows = pool[plane, tables].reshape(positions.shape[0], -1, pool.shape[3])
    mask = (jnp.arange(rows.shape[1])[None, None, None, :]
            <= positions[:, None, :, None])
    return self._expanded(q_nope, q_rope, rows, mask, w_uk, w_uv, scale)


def _paged_chunk(params, pool, tables, lengths, live, tokens):
    """One paged chunk through the whole model, traced anew."""
    def run(pool, tokens):
        cache = PagedCache((pool,), jnp.asarray(tables, jnp.int32),
                           jnp.asarray(lengths, jnp.int32),
                           jnp.asarray(live, jnp.int32))
        (logits, cache), _ = LongcatFlash(WALK).apply(
            params, tokens, cache=cache, mutable=["moe_stats"])
        return logits, cache.pools[0]

    logits, pool = jax.jit(run)(pool, jnp.asarray(tokens, jnp.int32))
    return np.asarray(logits), np.asarray(pool)


@pytest.mark.parametrize("prefix,live", [
    (0, 8),                         # nothing before the chunk
    (5, 6),                         # ends inside a key block
    (24, 8),                        # several key blocks before it
    (WALK_TABLE - WALK_CHUNK, 8),   # the last chunk the table holds
    (16, 3),                        # pad columns
    (13, 0),                        # a dead lane
], ids=["prefix_0", "ends_inside", "deep", "table_end", "pad", "dead"])
def test_walk_matches_the_whole_table_form(walk_params, key_blocks_of_8,
                                           monkeypatch, prefix, live):
    """A chunk's logits at its live columns and the rows it leaves: the
    walk against the gathered table on the same pool. The walk's table
    names ``JUNK`` where its last key block reaches past the sequence
    (read and masked) and ``POISON`` from there on (never read: one NaN
    row would show in every output, which is what proves the walk
    stops); the oracle's table is clean, since a gather multiplies every
    slot in."""
    held = -(-(prefix + live) // WALK_BS) if live else 0
    walked = WALK.prefill_keys_walked(WALK_CHUNK, prefix, live, WALK_BS,
                                      WALK_MAX_BLOCKS) // WALK_BS
    pool = _seeded_pool()
    tokens = np.random.RandomState(2).randint(0, WALK.vocab_size,
                                              (1, WALK_CHUNK))
    walk_table = [WALK_BLOCKS[:held] + [JUNK] * (walked - held)
                  + [POISON] * (WALK_MAX_BLOCKS - walked)]
    got, got_pool = _paged_chunk(walk_params, pool, walk_table, [prefix],
                                 [live], tokens)
    monkeypatch.setattr(LatentAttention, "_walked", _whole_table)
    clean_table = [WALK_BLOCKS[:held] + [0] * (WALK_MAX_BLOCKS - held)]
    want, want_pool = _paged_chunk(walk_params, pool, clean_table, [prefix],
                                   [live], tokens)
    assert np.isfinite(got).all()       # pad columns and a dead lane too
    np.testing.assert_allclose(got[0, :live], want[0, :live], rtol=0,
                               atol=TOL)
    # live rows went where the table says, the rest to the null block
    changed = {int(b) for b in np.unique(np.nonzero(~np.isclose(
        got_pool, np.asarray(pool), equal_nan=True))[1])}
    assert changed <= set(WALK_BLOCKS[prefix // WALK_BS:held]) | {0}
    assert (0 in changed) == (live < WALK_CHUNK)
    np.testing.assert_allclose(got_pool[:, 1:JUNK], want_pool[:, 1:JUNK],
                               rtol=0, atol=TOL)


def _key_blocks_read(lengths, live):
    """The key blocks one walk reads, found by poisoning one at a time:
    a NaN row reaches every output through ``P x V`` even where its slot
    is masked. Every lane has ten blocks of its own."""
    rng = np.random.RandomState(3)
    lanes, H = len(lengths), WALK.num_attention_heads
    f32 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    q_nope, q_rope = (f32(lanes, WALK_CHUNK, H, WALK.qk_nope_head_dim),
                      f32(lanes, WALK_CHUNK, H, WALK.qk_rope_head_dim))
    w_uk, w_uv = (f32(WALK.kv_lora_rank, H, WALK.qk_nope_head_dim),
                  f32(WALK.kv_lora_rank, H, WALK.v_head_dim))
    tables = 1 + np.arange(lanes * WALK_MAX_BLOCKS).reshape(lanes, -1)
    positions = jnp.asarray(lengths)[:, None] + jnp.arange(WALK_CHUNK)[None]
    per, read = WALK_KEYS // WALK_BS, set()
    for kb in range(WALK_MAX_BLOCKS // per):
        rows = np.random.RandomState(4).standard_normal(
            kvc.make_pools(WALK, 1 + tables.size, WALK_BS)[0].shape)
        rows[:, tables[:, kb * per:(kb + 1) * per].ravel()] = np.nan
        out = LatentAttention(WALK)._walked(
            q_nope, q_rope, jnp.asarray(rows, jnp.float32), 1,
            jnp.asarray(tables), positions, jnp.asarray(live), w_uk, w_uv,
            0.3)
        if not np.isfinite(np.asarray(out)).all():
            read.add(kb)
    return read


@pytest.mark.parametrize("lengths,live", [
    ([0], [1]), ([0], [8]), ([7], [1]), ([7], [2]), ([20], [4]), ([20], [5]),
    ([32], [8]), ([11], [0]), ([3, 26, 9], [8, 5, 0])])
def test_trip_count_follows_lengths_plus_live(key_blocks_of_8, lengths, live):
    """The walk reads key blocks 0 .. ceil((length + live) / keys) - 1 of
    the deepest live lane and no other, and the configuration's host
    arithmetic (what the scheduler's counter adds a chunk) says the
    same of every lane."""
    walked = [WALK.prefill_keys_walked(WALK_CHUNK, n, c, WALK_BS,
                                       WALK_MAX_BLOCKS)
              for n, c in zip(lengths, live)]
    assert walked == [-(-(n + c) // WALK_KEYS) * WALK_KEYS if c else 0
                      for n, c in zip(lengths, live)]
    read = _key_blocks_read(lengths, live)
    assert read == set(range(max(walked) // WALK_KEYS))


def test_keys_walked_at_the_cells_sizes():
    # blocks of 64, key blocks of 512, a table of 264 blocks, a chunk of
    # 512: the chunk's last live position rounded up to the key block
    walked = lambda n, c, chunk=512: LongcatFlashConfig(  # noqa: E731
        ).prefill_keys_walked(chunk, n, c, 64, 264)
    assert walked(0, 512) == 512 and walked(0, 1) == 512
    assert walked(512, 1) == 1024 and walked(4096, 300) == 4608
    assert walked(16384, 512) == 16896
    assert walked(4096, 0) == 0                     # a dead lane
    # a chunk too narrow to expand gathers the table
    assert walked(4096, 2, chunk=2) == 16896
    # a table that is not whole key blocks: clipped to its slots
    assert WALK.prefill_keys_walked(8, 60, 8, 4, 9) == 36


def _keys_counter():
    return {k: M.snapshot().get(
        'hvd_tpu_gen_prefill_attn_keys_total{kind="%s"}' % k, 0)
        for k in ("walked", "table")}


def test_engine_counts_the_keys_its_prefill_walks(walk_params,
                                                  key_blocks_of_8):
    """A prompt of 19 tokens is chunks of 8, 8 and 3 live columns at
    prefixes 0, 8 and 16: the counter adds each chunk's last live
    position rounded up to the key block, 8 + 16 + 24 slots of a 40-slot
    table a chunk."""
    prompt = np.asarray(_tokens(5, 19)).tolist()
    before = _keys_counter()
    with _engine(walk_params, WALK, num_blocks=24, max_seqs=2) as eng:
        toks = eng.result(eng.submit(prompt, max_tokens=3), timeout=240)
        assert eng.allocator.in_use == 0
    after = _keys_counter()
    assert after["table"] - before["table"] == 3 * WALK_TABLE
    assert after["walked"] - before["walked"] == 8 + 16 + 24
    logits = LongcatFlash(WALK).apply(
        walk_params, jnp.asarray([prompt + toks], jnp.int32))
    assert toks == np.asarray(logits)[0, 18:21].argmax(-1).tolist()


def test_a_model_without_the_walk_counts_the_whole_table():
    program = kvc.build_prefill_program(LongcatFlash(WALK))
    assert kvc.prefill_keys_walked(program, 512, 4096, 300, 64, 264) == 4608
    assert kvc.prefill_keys_walked(lambda *a: None, 512, 4096, 300, 64,
                                   264) == 16896


# -- (c) the absorbed form through the paged kernel ----------------------------

#: one attention at widths whose row fits the kernel: 96 + 16 values pad
#: to a 128-wide row; 4 heads x 2 columns are 8 query rows. ``CELL`` has
#: the served row (512 + 64 in 640) and the served 64 heads: 128 query
#: rows over blocks of 64, the shape the benchmark's decode step brings
KERNEL = dataclasses.replace(
    CFG, num_layers=1, kv_lora_rank=96, qk_rope_head_dim=16,
    dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
KERNEL_F32 = dataclasses.replace(KERNEL, dtype=jnp.float32,
                                 param_dtype=jnp.float32)
CELL = dataclasses.replace(
    KERNEL, num_attention_heads=64, kv_lora_rank=512, qk_rope_head_dim=64,
    qk_nope_head_dim=128, v_head_dim=128)
G = pa.GROUP_TOKENS
#: name -> (configuration, block size, table blocks, lengths, live)
ABSORBED = {
    "ragged": (KERNEL, 16, 24, [0, 37, 300, 5], [2, 2, 2, 2]),
    "dead_lane_between_live": (KERNEL, 16, 24, [40, 200, 70], [2, 0, 2]),
    "one_short_of_a_group": (KERNEL, 16, 24, [G - 3, 2 * G - 3], [2, 2]),
    "on_a_groups_edge": (KERNEL, 16, 24, [G - 2, 2 * G - 2], [2, 2]),
    "one_past_a_groups_edge": (KERNEL, 16, 24, [G - 1, 2 * G - 1], [2, 2]),
    "table_end": (KERNEL, 16, 20, [20 * 16 - 2, 9], [2, 2]),
    "first_column_only": (KERNEL, 16, 24, [63, 130, 15], [1, 1, 2]),
    "float32_blocks_of_8": (KERNEL_F32, 8, 40, [0, 7, 130, 250], [2, 1, 0, 2]),
    "served_row_640_blocks_of_64": (CELL, 64, 6, [3, 200, 382, 126],
                                    [2, 0, 2, 1]),
}


@pytest.mark.parametrize("name", sorted(ABSORBED))
def test_absorbed_through_the_kernel_matches_the_gather_path(monkeypatch,
                                                             name):
    """One ``LatentAttention`` over a seeded pool, traced as on the
    chip (the path rule asks for the default backend) with the kernel
    interpreted, against the same call on this backend's gather path:
    the live columns of the live lanes, and the rows the step leaves in
    the pool. The kernel's tables name ``POISON`` (a block of NaN)
    everywhere a lane's walk does not reach: past a live lane's last
    group, and in all of a dead lane's table, whose output is zeros;
    the oracle's tables are clean there, since a gather multiplies
    every slot in."""
    cfg, bs, max_blocks, lengths, live = ABSORBED[name]
    lanes, rng = len(lengths), np.random.RandomState(4)
    (pool,) = kvc.make_pools(cfg, lanes * max_blocks + 2, bs)
    assert pa.shapes_fit(cfg.paged_query_rows(2), bs, pool.shape[3],
                         pool.dtype) and not cfg.expands(2)
    poison = pool.shape[1] - 1
    rows = rng.standard_normal(pool.shape)
    rows[:, poison] = np.nan
    pool = jnp.asarray(rows, pool.dtype)
    own = rng.permutation(np.arange(1, poison)).reshape(lanes, max_blocks)
    clean, reached = np.zeros_like(own), np.full_like(own, poison)
    for b, (n, alive) in enumerate(zip(lengths, live)):
        held = -(-(n + 2) // bs)
        clean[b, :held] = own[b, :held]
        if alive:
            walked = pa.blocks_read([n], 2, bs, max_blocks)
            reached[b, :walked] = clean[b, :walked]
    attn = LatentAttention(cfg)
    x = jnp.asarray(rng.standard_normal((lanes, 2, cfg.hidden_size)),
                    cfg.dtype)
    positions = jnp.asarray(lengths)[:, None] + jnp.arange(2)[None, :]
    params = attn.init(jax.random.PRNGKey(3), x, positions, None,
                       (pool, 0, jnp.asarray(clean), jnp.asarray(live)))

    def step(tables):
        return jax.jit(lambda pool: attn.apply(
            params, x, positions, None,
            (pool, 1, jnp.asarray(tables, jnp.int32),
             jnp.asarray(live, jnp.int32))))(pool)

    want, want_pool = step(clean)
    interpreted = pa.paged_attention
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        m.setattr(pa, "paged_attention", lambda *a, **kw: interpreted(
            *a, **kw, interpret=True))
        got, got_pool = step(reached)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # a dead lane: zeros from the kernel, through a projection with no bias
    assert not got[np.asarray(live) == 0].any()
    tol = 2e-5 if cfg.dtype == jnp.float32 else 0.02
    for b, alive in enumerate(live):
        err = np.linalg.norm(got[b, :alive] - want[b, :alive]) \
            / max(np.linalg.norm(want[b, :alive]), 1e-30)
        assert err <= tol, (name, b, err)
    # the step's own rows went where the table says on both paths
    np.testing.assert_array_equal(
        np.asarray(got_pool, np.float32)[:, 1:poison],
        np.asarray(want_pool, np.float32)[:, 1:poison])


#: name -> (backend, block size, pool dtype, kernel?)
COUNTED = {
    "cpu_fitting_pool": ("cpu", 16, jnp.bfloat16, False),
    "tpu_fitting_pool": ("tpu", 16, jnp.bfloat16, True),
    "tpu_float32_blocks_of_4": ("tpu", 4, jnp.float32, False),
}


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_scheduler_counts_what_the_absorbed_decode_reads(monkeypatch, name):
    """``hvd_tpu_gen_paged_attn_blocks_total``: the decode program
    carries the model's ``paged_query_rows`` (all heads x 2 columns: one
    key-value head), and where the path rule holds for the engine's
    pool the scheduler adds what the kernel's walk copies
    (``blocks_read``) to ``kind="read"``; on the gather path, the
    table."""
    backend, bs, dtype, kernel = COUNTED[name]
    cfg = dataclasses.replace(KERNEL, dtype=dtype, param_dtype=dtype,
                              max_position_embeddings=512)
    assert cfg.paged_query_rows(2) == 8
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model = LongcatFlash(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))
    series = 'hvd_tpu_gen_paged_attn_blocks_total{kind="%s"}'
    lengths = [5, 300, 130]
    with _engine(params, cfg, block_size=bs, num_blocks=48,
                 max_seqs=4) as eng:
        batcher = eng.batcher
        assert batcher._decode_prog.query_rows == 8
        assert ("decode" in batcher._reads_live) is kernel
        before = M.snapshot()
        batcher._count_attention_blocks("decode", lengths, 2)
        after = M.snapshot()
    read, table = (after[series % k] - before.get(series % k, 0.0)
                   for k in ("read", "table"))
    assert table == 4 * (512 // bs)
    assert read == (pa.blocks_read(lengths, 2, bs, 512 // bs) if kernel
                    else table)
    if kernel:
        assert read == (1 + 3 + 2) * (G // bs) < table


# -- (d), (e) the expert layer ------------------------------------------------

def _moe_inputs(params, seed, tokens=40):
    p = params["params"]["layer_0"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(seed), (tokens,
                                                     CFG.hidden_size))
    return p, u


def _share(p, u, held, bias=None, router=None, valid=None):
    """One chip's part: the router over all 12 outputs, the experts in
    ``held`` (sliced out of a tree that holds 2..5)."""
    router = p["router"] if router is None else router
    bias = p["e_score_correction_bias"] if bias is None else bias
    idx, w = route_topk(jnp.dot(u, router, precision="highest"), bias,
                        CFG.moe_topk, CFG.routed_scaling_factor)
    return held_experts_mlp(u, idx, w, *held_weights(p, held), held,
                            CFG.n_routed_experts, valid=valid)


def held_weights(p, held):
    lo, hi = held[0] - CFG.held_experts[0], held[1] - CFG.held_experts[0]
    return (p["experts_gate"][lo:hi], p["experts_up"][lo:hi],
            p["experts_down"][lo:hi])


def test_the_shares_add_up_to_the_uncut_layer():
    """8 routed experts over 4 chips, 2 each: the held parts of the four
    shares, plus the identity part (which every chip computes alike)
    counted once, are the uncut reference's ``M(u)``."""
    full = dataclasses.replace(CFG, held_experts=(0, 8))
    p = LongcatFlash(full).init(jax.random.PRNGKey(7), jnp.zeros(
        (1, 4), jnp.int32))["params"]["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(8), (40, CFG.hidden_size))
    idx, w = route_topk(jnp.dot(u, p["router"], precision="highest"),
                        p["e_score_correction_bias"], CFG.moe_topk,
                        CFG.routed_scaling_factor)
    total, picks = 0.0, 0
    for first in (0, 2, 4, 6):
        held, zero, stats = held_experts_mlp(
            u, idx, w, p["experts_gate"][first:first + 2],
            p["experts_up"][first:first + 2],
            p["experts_down"][first:first + 2], (first, first + 2),
            CFG.n_routed_experts)
        total = total + held
        picks += int(stats[1])
        # on a share, picks of the other three shares' experts are absent
        assert int(stats[1] + stats[2] + stats[3]) == 40 * CFG.moe_topk
    total = total + zero
    assert picks + int(stats[2]) == 40 * CFG.moe_topk
    uncut = dict(SETTINGS, held_experts=(0, 8))
    np.testing.assert_allclose(total, ref.experts(p, u, uncut), rtol=0,
                               atol=TOL)
    # and one share alone is the reference given that share
    share = dict(SETTINGS, held_experts=(2, 4))
    one = {k: (v[2:4] if k.startswith("experts_") else v)
           for k, v in p.items()}
    np.testing.assert_allclose(
        held_experts_mlp(u, idx, w, one["experts_gate"], one["experts_up"],
                         one["experts_down"], (2, 4),
                         CFG.n_routed_experts)[0] + zero,
        ref.experts(one, u, share), rtol=0, atol=TOL)


@pytest.mark.parametrize("tokens", [40, 300])
def test_dropless_when_one_held_expert_takes_most_tokens(params, tokens):
    """A correction bias that sends every token to expert 3 (held): 40
    rows on one expert in one step of the grouped matmul (a tile is no
    wider than the tokens), 300 rows in three steps of ``TILE``, none
    dropped."""
    assert TILE == 128
    p, u = _moe_inputs(params, 9, tokens)
    bias = p["e_score_correction_bias"].at[3].set(10.0)
    held, zero, stats = _share(p, u, (2, 6), bias=bias)
    stats = dict(zip(STATS_FIELDS + (2, 3, 4, 5), np.asarray(stats)))
    assert stats[3] == tokens and stats["tokens"] == tokens
    assert stats["held"] >= tokens and stats["touched"] >= 1
    biased = dict(p, e_score_correction_bias=bias)
    np.testing.assert_allclose(held + zero,
                               ref.experts(biased, u, SETTINGS),
                               rtol=0, atol=TOL)


def test_pad_tokens_are_routed_nowhere(params):
    p, u = _moe_inputs(params, 10)
    valid = jnp.arange(40) < 23
    held, zero, stats = _share(p, u, (2, 6), valid=valid)
    assert int(stats[0]) == 23
    assert int(stats[1] + stats[2] + stats[3]) == 23 * CFG.moe_topk
    assert not np.asarray(held)[23:].any() and not np.asarray(zero)[23:].any()
    want = ref.experts(p, u, SETTINGS)
    np.testing.assert_allclose((held + zero)[:23], want[:23], rtol=0,
                               atol=TOL)


def test_held_experts_is_an_argument_not_the_weights_shape(params):
    p, u = _moe_inputs(params, 11)
    with pytest.raises(ValueError, match="names 3 experts"):
        held_experts_mlp(u, *route_topk(u @ p["router"],
                                        p["e_score_correction_bias"], 3, 6.0),
                         *held_weights(p, (2, 6)), (2, 5),
                         CFG.n_routed_experts)


# -- (f) the counters ----------------------------------------------------------

def _moe_counters():
    snap = M.snapshot()
    return {k: v for k, v in snap.items() if k.startswith("hvd_tpu_gen_moe")}


def test_routing_counters_count_live_tokens_only(params):
    """Two requests beside a dead lane, prompts that leave pad tokens in
    their last chunk: tokens = layers x (prompt + decode steps), picks =
    top-k x tokens, by kind and by held expert."""
    before = _moe_counters()
    prompts = [np.asarray(_tokens(s, n)).tolist()
               for s, n in ((12, 5), (13, 19))]
    new = 6
    with _engine(params) as eng:
        for s in [eng.submit(p, max_tokens=new) for p in prompts]:
            eng.result(s, timeout=240)
    after = _moe_counters()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    tokens = delta["hvd_tpu_gen_moe_tokens_total"]
    assert tokens == CFG.num_layers * sum(len(p) + new - 1 for p in prompts)
    kinds = {k: delta['hvd_tpu_gen_moe_picks_total{kind="%s"}' % k]
             for k in ("held", "zero", "absent")}
    assert sum(kinds.values()) == CFG.moe_topk * tokens
    assert all(v > 0 for v in kinds.values()), kinds
    by_expert = [delta.get(
        'hvd_tpu_gen_moe_held_expert_picks_total{expert="%d"}' % e, 0)
        for e in range(*CFG.held_experts)]
    assert sum(by_expert) == kinds["held"]
    assert not any('expert="%d"' % e in k for k in delta for e in (0, 1, 6))
    # 1 + 3 prefill chunks; decode steps are shared by the two lanes
    assert delta['hvd_tpu_gen_moe_calls_total{phase="prefill"}'] == 4
    calls = 4 + delta['hvd_tpu_gen_moe_calls_total{phase="decode"}']
    touched = sum(delta['hvd_tpu_gen_moe_experts_touched_total{phase="%s"}'
                        % ph] for ph in ("prefill", "decode"))
    assert 0 < touched <= calls * CFG.num_layers * 4


# -- (g) the latent pool over the wire ------------------------------------------

def test_latent_pool_through_gather_wire_and_scatter_bit_for_bit():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    spec = cfg.cache_spec()
    assert (spec.planes, spec.rows) == (4, (("latent", 40),))
    (pool,) = kvc.make_pools(cfg, 9, 4)
    assert pool.shape == (4, 9, 4, 128) and pool.dtype == jnp.bfloat16
    assert kvc.block_bytes(cfg, 4) == 4 * 4 * 128 * 2
    pool = jax.random.normal(jax.random.PRNGKey(14), pool.shape,
                             jnp.float32).astype(jnp.bfloat16)
    rows = kvc.gather_blocks((pool,), [5, 2, 7])
    hashes, back, nbytes = unpack_blocks(
        pack_blocks(["a", "b", "c"], rows, "native"))
    assert hashes == ["a", "b", "c"] and nbytes == rows[0].nbytes
    (dest,) = kvc.scatter_blocks(kvc.make_pools(cfg, 9, 4), [1, 3, 4], back)
    np.testing.assert_array_equal(
        np.asarray(dest[:, [1, 3, 4]]).view(np.uint16),
        np.asarray(pool[:, [5, 2, 7]]).view(np.uint16))
    assert not np.asarray(dest[:, [0, 2, 5, 6, 7, 8]]).any()
    with pytest.raises(ValueError, match="2 transferred rows"):
        kvc.scatter_blocks((dest,), [1], (rows[0], rows[0]))


def test_engine_exports_and_imports_the_latent_blocks(params):
    prompt = np.asarray(_tokens(15, 14)).tolist()
    with _engine(params, prefix_cache=True) as a, \
            _engine(params, prefix_cache=True) as b:
        base = a.generate(prompt, max_tokens=4)
        hashes = a.kv_manifest(prompt)
        served, rows = a.kv_export(hashes)
        assert served == hashes and len(rows) == 1
        assert rows[0].shape == (4, len(hashes), 4, 128)
        assert b.kv_import(hashes, served, rows) == (0, len(hashes))
        assert b.generate(prompt, max_tokens=4) == base


# -- (h) the verify and beam programs run the model ---------------------------

@pytest.mark.parametrize("engine_kw,submit_kw", [
    (dict(spec_mode="ngram", spec_tokens=2), {}),
    (dict(max_beams=2), dict(num_beams=1)),
], ids=["verify", "beam_width_1"])
def test_verify_and_beam_programs_reproduce_plain_decode(params, engine_kw,
                                                         submit_kw):
    prompt = ([7, 8, 9] * 5)[:13]       # repetitive: the n-gram drafts
    with _engine(params) as eng:
        plain = eng.generate(prompt, max_tokens=6)
    with _engine(params, **engine_kw) as eng:
        seq = eng.submit(prompt, max_tokens=6, **submit_kw)
        assert eng.result(seq, timeout=240) == plain
        assert eng.allocator.in_use == 0


def test_beam_search_runs_the_model(params):
    prompt = np.asarray(_tokens(16, 9)).tolist()
    with _engine(params, max_beams=2) as eng:
        seq = eng.submit(prompt, max_tokens=4, num_beams=2)
        assert len(eng.result(seq, timeout=240)) == 4
        assert eng.allocator.in_use == 0
