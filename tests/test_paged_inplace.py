"""The paged forward writes the KV pool in place (ISSUE 26).

Three pins, all on compiled programs:

* **structure** — each of the five programs of ``kv_cache.py`` compiles
  (XLA-CPU, toy widths) to a module whose K and V pools alias input to
  output and in which no ``copy``, slice or ``dynamic-update-slice``
  produces an array of the pool's or of one layer slab's shape: the
  forward keeps one live version of each pool;
* **in place means only in place** — a prefill chunk, a decode step and
  a verify step with rejected drafts, run on pools filled with a seeded
  pattern, change exactly the rows they were told to write (plus the
  null block, where pad tokens and dead lanes go), and what they write
  and return equals a slab-at-a-time oracle kept in this file;
* **the device's layout** — the same programs at GPT-2 XL's widths,
  compiled for a described TPU v5e (no chip needed), hold no pool- or
  slab-shaped copy either; and the decode, verify and beam programs
  there attend through the paged-attention kernel (ISSUE 28): a
  ``tpu_custom_call`` a layer, no gathered ``(32, 1024, 1664)`` table
  and no ``(32, 1024, 25, 64)`` re-tiling of one. On the CPU any row-major pool passes the
  first pin; a TPU tiles the two minor axes ``(8, 128)``, stores a
  ``(..., heads, head_dim)`` pool blocks-minor to avoid padding
  ``(25, 64)``, and then relayouts every slab it scatters into or
  gathers from; it does the same to a ``(..., 1600)`` pool whenever
  the number of blocks is a multiple of 128 — which is why a pool row
  is ``heads * head_dim`` padded to a multiple of 128.
"""

import re

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (MlpBlock, PagedCache,
                                            Transformer, TransformerConfig,
                                            _default_attention)
from horovod_tpu.serving.generation import kv_cache as kvc
from horovod_tpu.serving.generation.scheduler import DECODE_WIDTH

CFG = TransformerConfig(vocab_size=64, num_layers=3, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=64,
                        dtype=jnp.float32)
NUM_BLOCKS, BLOCK_SIZE, MAX_BLOCKS = 9, 4, 5
SPEC = 3


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _i32(*shape):
    return jnp.zeros(shape, jnp.int32)


def _greedy(b):
    return kvc.SampleParams(
        temperature=jnp.zeros((b,), jnp.float32), top_k=_i32(b),
        top_p=jnp.ones((b,), jnp.float32),
        key=jnp.zeros((b, 2), jnp.uint32), emitted=_i32(b))


def _state(tokens, lengths, live, remaining):
    b = len(tokens)
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return kvc.DecodeState(
        tokens=as_i32(tokens), lengths=as_i32(lengths), live=as_i32(live),
        remaining=as_i32(remaining), eos=jnp.full((b,), -1, jnp.int32),
        sample=_greedy(b))


def _programs(model, k, v, lanes=2, chunk=8, max_blocks=MAX_BLOCKS, beam=2):
    """name -> (program, example arguments): the five builders at the
    shapes the scheduler calls them with."""
    tables = _i32(lanes, max_blocks)
    state = _state([0] * lanes, [0] * lanes, [0] * lanes, [0] * lanes)
    return {
        "raw": (kvc.build_program(model), (
            PagedCache((k, v), tables, _i32(lanes), _i32(lanes)),
            _i32(lanes, DECODE_WIDTH))),
        "prefill": (kvc.build_prefill_program(model), (
            PagedCache((k, v), _i32(1, max_blocks), _i32(1), _i32(1)),
            _i32(1, chunk), _greedy(1))),
        "decode": (kvc.build_decode_program(model, DECODE_WIDTH),
                   ((k, v), tables, state)),
        "verify": (kvc.build_verify_program(model, SPEC),
                   ((k, v), tables, state, _i32(lanes, SPEC), _i32(lanes))),
        "beam": (kvc.build_beam_program(model, beam, DECODE_WIDTH),
                 ((k, v), tables, _i32(lanes), _i32(lanes), _i32(lanes))),
    }


# -- structure ---------------------------------------------------------------

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = \(?(?P<dtype>\w+)"
    r"\[(?P<dims>[\d,]*)\]\S* (?P<op>[\w\-]+)\(")
_COPYING = ("copy", "slice")   # also dynamic-slice, dynamic-update-slice


def _pool_structure(hlo, pool_shape):
    """``(aliased_pool_params, pool_params, offenders)`` of a compiled
    module's text: the entry parameters of the pool's shape, those of
    them that alias an output, and every instruction that yields a
    pool- or slab-shaped array by copying, slicing or updating a slice
    (by its opcode or, for a fusion, by the name XLA gave it)."""
    dims = ",".join(map(str, pool_shape))
    slab = ",".join(map(str, pool_shape[1:]))
    header = hlo.splitlines()[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, \w+=", header)
    aliased = set(map(int, re.findall(r"\((\d+), \{", alias.group(1)))) \
        if alias else set()
    entry = hlo[hlo.index("\nENTRY "):]
    pool_params = set(map(int, re.findall(
        r"\[%s\]\S* parameter\((\d+)\)" % re.escape(dims), entry)))
    offenders = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m["dims"] not in (dims, slab, "1," + slab):
            continue
        if any(w in m["op"] or (m["op"] == "fusion" and w in m["name"])
               for w in _COPYING):
            offenders.append((m["name"], m["op"], m["dims"]))
    return aliased & pool_params, pool_params, offenders


@pytest.mark.parametrize("name", ["raw", "prefill", "decode", "verify",
                                  "beam"])
def test_compiled_program_keeps_one_live_pool(model_params, name):
    model, params = model_params
    k, v = kvc.make_pools(CFG, NUM_BLOCKS, BLOCK_SIZE)
    program, args = _programs(model, k, v)[name]
    hlo = program.lower(params, *args).compile().as_text()
    aliased, pool_params, offenders = _pool_structure(hlo, k.shape)
    assert len(pool_params) == 2, pool_params
    assert aliased == pool_params, (
        f"{name}: K/V pools are parameters {sorted(pool_params)}, "
        f"aliased to outputs: {sorted(aliased)}")
    assert not offenders, (
        f"{name}: pool- or slab-shaped copies in the compiled program: "
        f"{offenders[:6]}")


# -- in place means only in place ---------------------------------------------

def _oracle(params, tokens, k, v, tables, lengths, live):
    """The paged forward one layer slab at a time, as it stood before
    the pool was threaded through: slice layer ``i``'s slab out, scatter
    into it, gather from it, put it back. Returns
    ``(logits (B, C, V), k, v)``."""
    p = nn.meta.unbox(params)["params"]
    H, D = CFG.num_heads, CFG.head_dim
    B, C = tokens.shape
    bs = k.shape[2]
    ln = nn.LayerNorm(dtype=CFG.dtype, param_dtype=jnp.float32)
    positions = lengths[:, None] + jnp.arange(C)[None, :]
    x = p["embedding"][tokens] + p["pos_embedding"][
        jnp.clip(positions, 0, CFG.max_seq_len - 1)]
    mask = (jnp.arange(tables.shape[1] * bs)[None, None, None, :]
            <= positions[:, None, :, None])
    blocks = jnp.take_along_axis(tables, positions // bs, axis=1)
    blocks = jnp.where(jnp.arange(C)[None, :] < live[:, None], blocks, 0)
    offsets = positions % bs

    def row(t):     # a token's H*D values, zero-padded to the pool's row
        return jnp.pad(t.reshape(B, C, H * D),
                       ((0, 0), (0, 0), (0, k.shape[3] - H * D)))

    for i in range(CFG.num_layers):
        lp = p[f"layer_{i}"]
        h = ln.apply({"params": lp["ln1"]}, x)
        a = lp["attn"]
        q = jnp.einsum("bse,ehd->bshd", h, a["wq"])
        k_new = jnp.einsum("bse,ehd->bshd", h, a["wk"])
        v_new = jnp.einsum("bse,ehd->bshd", h, a["wv"])
        k_slab = k[i].at[blocks, offsets].set(row(k_new))
        v_slab = v[i].at[blocks, offsets].set(row(v_new))
        out = _default_attention(
            q, k_slab[tables][..., :H * D].reshape(B, -1, H, D),
            v_slab[tables][..., :H * D].reshape(B, -1, H, D), mask, CFG.dtype)
        k = k.at[i].set(k_slab)
        v = v.at[i].set(v_slab)
        x = x + jnp.einsum("bshd,hde->bse", out, a["wo"])
        x = x + MlpBlock(CFG).apply({"params": lp["mlp"]},
                                    ln.apply({"params": lp["ln2"]}, x))
    x = ln.apply({"params": p["ln_f"]}, x)
    return jnp.einsum("bse,ve->bsv", x, p["embedding"]), k, v


def _seeded_pools(seed):
    rng = np.random.RandomState(seed)
    shape = kvc.make_pools(CFG, NUM_BLOCKS, BLOCK_SIZE)[0].shape
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def _told_to_write(tables, lengths, counts):
    """(num_blocks, block_size) mask of the slots a step writes for
    real: lane ``b``'s ``counts[b]`` tokens from position
    ``lengths[b]`` on, through its block table."""
    mask = np.zeros((NUM_BLOCKS, BLOCK_SIZE), bool)
    for b, n in enumerate(counts):
        for pos in range(lengths[b], lengths[b] + n):
            mask[tables[b][pos // BLOCK_SIZE], pos % BLOCK_SIZE] = True
    assert not mask[0].any()
    return mask


def _check_pools(before, after, expected, written):
    """``after`` differs from ``before`` in every ``written`` slot, equals
    ``expected`` there bit for bit, and outside them and the null block
    is ``before`` untouched, in every layer."""
    for b4, got, want in zip(before, after, expected):
        got = np.asarray(got)
        changed = (got != b4).any(axis=-1)            # (L, N, bs)
        free = ~written
        free[0] = False                               # the null block
        assert not changed[:, free].any(), np.argwhere(changed[:, free])
        assert changed[:, written].all()
        np.testing.assert_array_equal(got[:, written],
                                      np.asarray(want)[:, written])


#: four lanes' tables over disjoint blocks, and the batch the decode and
#: verify steps run on: lanes 0, 1, 3 live at different depths, lane 2
#: dead, each lane's next input token
TABLES = np.array([[1, 2, 3, 0, 0], [4, 5, 0, 0, 0],
                   [6, 7, 0, 0, 0], [8, 0, 0, 0, 0]], np.int32)
LENGTHS, LIVE, FIRST = [9, 2, 5, 3], [1, 1, 0, 1], [7, 21, 33, 40]


def _run_prefill(model, params, k, v):
    # 5 live tokens of an 8-wide chunk from position 6: two blocks
    # written in part, three pad tokens
    tables, lengths, live = TABLES[:1], [6], [5]
    tokens = np.arange(3, 11, dtype=np.int32)[None, :]
    cache = PagedCache((jnp.asarray(k), jnp.asarray(v)), jnp.asarray(tables),
                       jnp.asarray(lengths, jnp.int32),
                       jnp.asarray(live, jnp.int32))
    token, logprob, cache = kvc.build_prefill_program(model)(
        params, cache, jnp.asarray(tokens), _greedy(1))
    logits, want_k, want_v = jax.jit(_oracle)(
        params, jnp.asarray(tokens), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(live))
    at = np.asarray(logits)[:, live[0] - 1]
    return (cache.pools, (want_k, want_v),
            _told_to_write(tables, lengths, live),
            (np.asarray(token), at.argmax(-1)),
            (np.asarray(logprob), np.asarray(
                jax.nn.log_softmax(at, axis=-1)).max(-1)))


def _run_decode(model, params, k, v):
    # the second column of every lane is a pad token
    lengths, live = LENGTHS, LIVE
    state = _state(FIRST, lengths, live, [5, 5, 5, 5])
    (new_k, new_v), _, token, logprob = kvc.build_decode_program(
        model, DECODE_WIDTH)(params, (jnp.asarray(k), jnp.asarray(v)),
                             jnp.asarray(TABLES), state)
    tokens = np.zeros((4, DECODE_WIDTH), np.int32)
    tokens[:, 0] = FIRST
    logits, want_k, want_v = jax.jit(_oracle)(
        params, jnp.asarray(tokens), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(TABLES), jnp.asarray(lengths), jnp.asarray(live))
    at = np.asarray(logits)[:, 0]
    alive = np.asarray(live, bool)
    return ((new_k, new_v), (want_k, want_v),
            _told_to_write(TABLES, lengths, live),
            (np.asarray(token)[alive], at.argmax(-1)[alive]),
            (np.asarray(logprob)[alive], np.asarray(
                jax.nn.log_softmax(at, axis=-1)).max(-1)[alive]))


def _run_verify(model, params, k, v):
    # three drafts a lane; lane 1's first draft is the model's own next
    # token (found with the oracle), every other draft is wrong: lane 0
    # commits 1 position and has 3 rolled back, lane 1 commits 2, lane 2
    # is dead, lane 3 has no draft and degrades to a plain decode step
    lengths, live, first = LENGTHS, LIVE, FIRST
    tokens = np.zeros((4, SPEC + 1), np.int32)
    tokens[:, 0] = first
    oracle = jax.jit(_oracle)
    args = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(TABLES),
            jnp.asarray(lengths))
    logits, _, _ = oracle(params, jnp.asarray(tokens), *args,
                          jnp.asarray(live))
    nxt = np.asarray(logits)[:, 0].argmax(-1)
    draft = np.zeros((4, SPEC), np.int32)
    draft[0] = [(nxt[0] + 1) % CFG.vocab_size, 1, 2]
    draft[1] = [nxt[1], 63, 62]
    draft_len = np.array([3, 3, 3, 0], np.int32)
    tokens[:, 1:] = draft
    width = np.where(np.asarray(live) > 0, 1 + draft_len, 0)
    logits, want_k, want_v = oracle(params, jnp.asarray(tokens), *args,
                                    jnp.asarray(width))
    pred = np.asarray(logits).argmax(-1)                # (B, S+1)
    n_emit = np.zeros(4, np.int32)
    for b in range(4):
        if live[b]:
            hit = 0
            while hit < draft_len[b] and pred[b, hit] == draft[b, hit]:
                hit += 1
            n_emit[b] = hit + 1
    assert n_emit[0] == 1 and n_emit[1] >= 2 and n_emit[3] == 1, n_emit
    state = _state(first, lengths, live, [9, 9, 9, 9])
    (new_k, new_v), _, got_pred, _, got_emit = kvc.build_verify_program(
        model, SPEC)(params, (jnp.asarray(k), jnp.asarray(v)),
                     jnp.asarray(TABLES), state, jnp.asarray(draft),
                     jnp.asarray(draft_len))
    np.testing.assert_array_equal(np.asarray(got_emit), n_emit)
    alive = np.asarray(live, bool)
    return ((new_k, new_v), (want_k, want_v),
            _told_to_write(TABLES, lengths, n_emit),
            (np.asarray(got_pred)[alive, 0], pred[alive, 0]), None)


@pytest.mark.parametrize("step", ["prefill", "decode", "verify"])
def test_step_writes_only_what_it_was_told_to(model_params, step):
    model, params = model_params
    k, v = _seeded_pools(seed=26)
    run = {"prefill": _run_prefill, "decode": _run_decode,
           "verify": _run_verify}[step]
    after, expected, written, tokens, logprobs = run(model, params, k, v)
    _check_pools((k, v), after, expected, written)
    np.testing.assert_array_equal(*tokens)
    if logprobs is not None:
        np.testing.assert_allclose(*logprobs, rtol=1e-6, atol=1e-6)


# -- the device's layout ------------------------------------------------------

@pytest.fixture(scope="module")
def one_v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one; keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


#: arrays the gather path makes of the tables at these widths: the
#: gathered K or V of every lane, flat and per block, and its re-tiling
#: for the attention einsums
_TABLE_SHAPED = ("32,1024,25,64", "32,1024,1664", "32,64,16,1664",
                 "2048,16,1664")


@pytest.mark.parametrize("name", ["prefill", "decode", "verify", "beam"])
def test_v5e_program_has_no_pool_shaped_copy(one_v5e_chip, no_compile_cache,
                                             monkeypatch, name):
    """GPT-2 XL's widths and the benchmark's lanes, chunk and tables:
    what the TPU compiler makes of the pool's layout. Two layers keep
    the compile short; they hold the 48 layers' 576 blocks each, since
    a pool small enough for the chip's fast memory is prefetched there
    whole, which is no copy the real program makes.

    The programs are traced as on the chip (the path rule asks for the
    default backend, which is a TPU there): a few query columns go
    through the kernel, which leaves the pools in HBM and gathers no
    table; the prefill chunk's 1600 query rows keep the gather path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(vocab_size=50257, num_layers=2, d_model=1600,
                            num_heads=25, head_dim=64, max_seq_len=1024,
                            dtype=jnp.bfloat16)
    model = Transformer(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_v5e_chip), tree)

    with jax.enable_x64(False):     # the chip runs without x64
        params = on_chip(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), _i32(1, 8))))
        k, v = on_chip(jax.eval_shape(
            lambda: kvc.make_pools(cfg, 576 * 48 // 2, 16)))
        program, args = _programs(model, k, v, lanes=32, chunk=64,
                                  max_blocks=64)[name]
        hlo = program.lower(params, *on_chip(args)).compile().as_text()
    aliased, pool_params, offenders = _pool_structure(hlo, k.shape)
    assert aliased == pool_params and len(pool_params) == 2
    assert not offenders, offenders[:6]
    kernels = len(re.findall(r'custom_call_target="tpu_custom_call"', hlo))
    if name == "prefill":
        assert kernels == 0
        return
    assert kernels == cfg.num_layers, kernels
    made = [shape for shape in _TABLE_SHAPED if f"[{shape}]" in hlo]
    assert not made, made


@pytest.mark.parametrize("name", ["prefill", "decode"])
def test_v5e_latent_pool_is_row_major_and_uncopied(one_v5e_chip,
                                                   no_compile_cache,
                                                   monkeypatch, name):
    """The latent row of LongCat-Flash (ISSUE 27) at its published
    widths and the benchmark's lanes, chunk, block and table: 576 values
    pad to a 640-wide row, a multiple of 128, so the device stores the
    ``(planes, blocks, 64, 640)`` pool row-major, aliases it input to
    output and copies neither a plane nor the pool. One double layer
    (two planes, two attentions, sixteen held experts) keeps the compile
    short; the pool keeps the four layers' size a plane.

    The prefill chunk's two attentions walk the lane's blocks (ISSUE
    34): they read the pool the program has just written where it lies,
    a key block at a time, so the program still aliases the pool and
    copies none of it, no lane's table is gathered, and no table-wide
    scores or mask exist. The decode step's absorbed form (ISSUE 37),
    traced as on the chip, attends through the paged kernel, one pool
    for keys and values: one kernel an attention, no table gathered, no
    table-wide float32 scores, and the 0.7 GB of temporaries they were
    are gone."""
    from horovod_tpu.models import LongcatFlash, LongcatFlashConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LongcatFlashConfig(vocab_size=16384, num_layers=1,
                             max_position_embeddings=16896,
                             held_experts=(0, 16))
    model = LongcatFlash(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_v5e_chip), tree)

    with jax.enable_x64(False):     # the chip runs without x64
        params = on_chip(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), _i32(1, 8))))
        pools = on_chip(jax.eval_shape(
            lambda: kvc.make_pools(cfg, 3072, 64)))
        (pool,) = pools
        assert pool.shape == (2, 3072, 64, 640)
        tables, lanes = _i32(32, 16896 // 64), 32
        program, args = {
            "prefill": (kvc.build_prefill_program(model), (
                PagedCache(pools, _i32(1, 16896 // 64), _i32(1), _i32(1)),
                _i32(1, 512), _greedy(1))),
            "decode": (kvc.build_decode_program(model, DECODE_WIDTH), (
                pools, tables, _state([0] * lanes, [0] * lanes,
                                      [0] * lanes, [0] * lanes))),
        }[name]
        compiled = program.lower(params, *on_chip(args)).compile()
    hlo = compiled.as_text()
    aliased, pool_params, offenders = _pool_structure(hlo, pool.shape)
    assert aliased == pool_params and len(pool_params) == 1
    assert not offenders, offenders[:6]
    layouts = set(re.findall(
        r"bf16\[2,3072,64,640\]\{([\d,]*)", hlo.splitlines()[0]))
    assert layouts == {"3,2,1,0"}, layouts
    # both programs fit beside each other's arguments with room to spare
    mem = compiled.memory_analysis()
    kernels = len(re.findall(r'custom_call_target="tpu_custom_call"', hlo))
    assert kernels == (2 if name == "decode" else 0), kernels
    if name == "decode":
        # (lanes, table slots, row) gathered, and (lanes, heads,
        # columns, table slots) scored
        made = [shape for shape in ("32,16896,640,1", "32,264,64,640",
                                    "32,64,2,16896")
                if f"[{shape}]" in hlo or f",{shape}]" in hlo]
        assert not made, made
        assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes
    if name == "prefill":
        # the lane's gathered table, its scores by groups of 16 heads
        # and its mask are gone, and with them most of the temporaries
        made = [shape for shape in ("1,16896,640", "512,16896",
                                    "16,512,16896")
                if f"[{shape}]" in hlo or f",{shape}]" in hlo]
        assert not made, made
        assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("name", ["prefill", "decode"])
def test_v5e_plane_groups_alias_both_pools_and_take_the_grouped_kernel(
        one_v5e_chip, no_compile_cache, monkeypatch, name):
    """Command A+ (ISSUE 33) at its published widths and the benchmark's
    lanes, chunk, block and table, one sliding and one full layer: the
    two plane groups' four pools alias input to output and none is
    copied; the decode program attends through the paged kernel on both
    planes (its grouped layout: 16 query heads a key-value head, and on
    the sliding plane the window), gathering no table; the prefill
    chunk's 8192 query rows a key-value head walk the keys by blocks in
    XLA, and its temporaries stay far under a gathered table's 8.9 GB of
    float32 scores."""
    from horovod_tpu.models import CommandAPlus, CommandAPlusConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = CommandAPlusConfig(
        vocab_size=32768, num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        held_experts=(0, 2), table_positions=33792)
    model = CommandAPlus(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_v5e_chip), tree)

    lanes, width = 16, 33792 // 64
    with jax.enable_x64(False):     # the chip runs without x64
        params = on_chip(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), _i32(1, 8))))
        pools = on_chip(jax.eval_shape(
            lambda: kvc.make_pools(cfg, (8192, 1185), 64)))
        assert [p.shape for p in pools] == [(1, 8192, 64, 1024)] * 2 \
            + [(1, 1185, 64, 1024)] * 2
        program, args = {
            "prefill": (kvc.build_prefill_program(model), (
                PagedCache(pools, (_i32(1, width), _i32(1, width)),
                           _i32(1), _i32(1)), _i32(1, 512), _greedy(1))),
            "decode": (kvc.build_decode_program(model, DECODE_WIDTH), (
                pools, (_i32(lanes, width), _i32(lanes, width)),
                _state([0] * lanes, [0] * lanes, [0] * lanes,
                       [0] * lanes))),
        }[name]
        compiled = program.lower(params, *on_chip(args)).compile()
    hlo = compiled.as_text()
    for pool in (pools[0], pools[2]):
        aliased, pool_params, offenders = _pool_structure(hlo, pool.shape)
        assert aliased == pool_params and len(pool_params) == 2
        assert not offenders, offenders[:6]
    kernels = len(re.findall(r'custom_call_target="tpu_custom_call"', hlo))
    assert kernels == (2 if name == "decode" else 0), kernels
    # no lane's table gathered whole: (lanes, 33792 slots, 1024)
    assert "[16,33792,1024]" not in hlo and "[1,33792,1024]" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
