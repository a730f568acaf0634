"""The paged-attention decode kernel against the gather path (ISSUE 28).

``horovod_tpu/ops/paged_attention.py`` runs here under the Pallas
interpreter (``interpret=True``, passed explicitly: nothing selects it
by itself); the oracle is what it replaces on a TPU and what every other
backend still runs, ``Attention``'s gather path
(``_gathered_attention``: the whole table gathered, then
``_default_attention``), under the mask the paged forward builds.

**Tolerance.** Relative L2 error of a live lane's output, as
``chip_smoke.KERNEL_TOL`` (0.02) for the flash kernel. The two paths
round differently by construction: the oracle rounds the scores to the
cache dtype before the softmax, normalises the probabilities, rounds
them, and multiplies; the kernel keeps the scores in float32, rounds the
*unnormalised* probabilities for ``P x V`` and divides the float32
accumulator at the end. In bfloat16 (8 bits) that is a few parts in a
thousand (0.002-0.005 measured here); a wrong block, an off-by-one
length or a stale row is a whole output off (relative error near 1). In
float32 the same comparison holds to 1e-5.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (PagedCache, Transformer,
                                            TransformerConfig,
                                            _gathered_attention, _table_mask)
from horovod_tpu.ops import paged_attention as pa
from horovod_tpu.serving.generation import kv_cache as kvc

KERNEL_TOL = 0.02       # chip_smoke.KERNEL_TOL
F32_TOL = 1e-5
BS = 16                 # a bfloat16 tile's rows; float32 cases use 8
MAX_BLOCKS = 10         # not a whole number of groups of 8: the last clips
FULL = "full"           # a length that fills the table with the chunk


def _oracle(q, k_pool, v_pool, layer, tables, lengths):
    positions = lengths[:, None] + jnp.arange(q.shape[1])[None, :]
    mask = _table_mask(positions, tables.shape[1] * k_pool.shape[2])
    return _gathered_attention(q, k_pool, v_pool, layer, tables, mask,
                               k_pool.dtype)


def _run(lengths, live=None, *, chunk=2, heads=3, head_dim=16,
         dtype=jnp.bfloat16, bs=BS, tables=None, num_blocks=64, seed=0):
    """Kernel and oracle on one seeded case; returns their outputs as
    float32 ``(B, C, H, D)`` arrays and the live mask."""
    rng = np.random.RandomState(seed)
    lengths = [MAX_BLOCKS * bs - chunk if n == FULL else n for n in lengths]
    lanes = len(lengths)
    live = np.ones(lanes, np.int32) if live is None else np.asarray(live)
    pool = (3, num_blocks, bs, kvc._row(heads * head_dim))
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(pool), dtype)
                      for _ in range(2))
    q = jnp.asarray(rng.standard_normal((lanes, chunk, heads, head_dim)),
                    dtype)
    if tables is None:
        # every lane a full table of its own blocks, in no order
        tables = rng.permutation(np.arange(1, num_blocks))[
            :lanes * MAX_BLOCKS].reshape(lanes, MAX_BLOCKS)
    args = (q, k_pool, v_pool, 1, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))
    got = pa.paged_attention(*args, jnp.asarray(live), interpret=True)
    assert got.shape == q.shape and got.dtype == k_pool.dtype
    want = jax.jit(_oracle)(*args)
    return (np.asarray(got, np.float32), np.asarray(want, np.float32),
            live.astype(bool))


def _null_padded(lengths, chunk, bs, seed=1):
    """Tables as the scheduler uploads them: the blocks a lane's rows
    need, then the null block 0."""
    rng = np.random.RandomState(seed)
    tables = np.zeros((len(lengths), MAX_BLOCKS), np.int32)
    free = list(rng.permutation(np.arange(1, 48)))
    for b, n in enumerate(lengths):
        held = -(-(n + chunk) // bs)
        tables[b, :held] = [free.pop() for _ in range(held)]
    return tables


def _shared_prefix():
    """Two lanes whose first two blocks are the same pool blocks (a
    prefix-cache hit), then blocks of their own."""
    tables = np.zeros((2, MAX_BLOCKS), np.int32)
    tables[0, :4] = [7, 9, 11, 13]
    tables[1, :5] = [7, 9, 21, 23, 25]
    return tables


CASES = {
    # ragged lengths, one boundary a case (lane 1 is a bystander)
    "length_0": dict(lengths=[0, 37]),
    "length_1": dict(lengths=[1, 37]),
    "length_bs_minus_1": dict(lengths=[BS - 1, 37]),
    "length_bs": dict(lengths=[BS, 37]),
    "length_bs_plus_1": dict(lengths=[BS + 1, 37]),
    "length_full_table": dict(lengths=[FULL, 37]),
    "group_boundary": dict(lengths=[pa.GROUP_TOKENS - 2, pa.GROUP_TOKENS - 1,
                                    pa.GROUP_TOKENS]),
    # dead lanes first, between and last; their lengths are stale
    "dead_lanes_stale_lengths": dict(lengths=[90, 5, 130, 60, FULL],
                                     live=[0, 1, 0, 1, 0]),
    "all_lanes_dead": dict(lengths=[40, 3], live=[0, 0]),
    "chunk_1": dict(lengths=[0, 33, 140], chunk=1),
    "chunk_verify_4": dict(lengths=[2, 33, 140], chunk=4),
    "gpt2_xl_row_1600_in_1664": dict(lengths=[21, 150], heads=25,
                                     head_dim=64),
    "default_row_768": dict(lengths=[21, 150], heads=12, head_dim=64),
    "tables_null_padded": dict(lengths=[0, 15, 31, 100],
                               tables=_null_padded([0, 15, 31, 100], 2, BS)),
    "shared_prefix_blocks": dict(lengths=[50, 70], tables=_shared_prefix()),
    "float32_pool": dict(lengths=[0, 7, 8, 9, 70], dtype=jnp.float32, bs=8,
                         live=[1, 1, 1, 0, 1]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_gather_path(name):
    case = dict(CASES[name])
    got, want, live = _run(case.pop("lengths"), case.pop("live", None),
                           **case)
    tol = F32_TOL if case.get("dtype") == jnp.float32 else KERNEL_TOL
    assert np.isfinite(got).all()
    # a dead lane reads nothing and hands on zeros, never a NaN that a
    # write to the null block could spread
    assert not got[~live].any()
    for b in np.flatnonzero(live):
        err = np.linalg.norm(got[b] - want[b]) / np.linalg.norm(want[b])
        assert err <= tol, (name, b, err)


def test_a_lane_reads_only_its_rows():
    """Poison (NaN) every pool slot the lanes' rows do not reach: blocks
    past a lane's last, every block of a dead lane, every block no table
    names. The gather path would carry it into every output
    (``0 x NaN``); the kernel never copies it. Slots of a live lane's
    last group past its rows are copied and masked, so they stay
    finite here, as pool rows always are."""
    bs, chunk = BS, 2
    lengths, live = [20, 200, 3], [1, 0, 1]
    rng = np.random.RandomState(3)
    pool = (2, 48, bs, 128)
    k_pool, v_pool = (rng.standard_normal(pool).astype(np.float32)
                      for _ in range(2))
    tables = rng.permutation(np.arange(1, 48))[:30].reshape(3, MAX_BLOCKS)
    group = pa.GROUP_TOKENS // bs
    reached = {0}
    for b, n in enumerate(lengths):
        if live[b]:
            groups = -(-(n + chunk) // pa.GROUP_TOKENS)
            reached |= set(tables[b, :groups * group])
    for blk in set(range(48)) - reached:
        k_pool[:, blk] = v_pool[:, blk] = np.nan
    q = jnp.asarray(rng.standard_normal((3, chunk, 2, 64)), jnp.bfloat16)
    out = pa.paged_attention(
        q, jnp.asarray(k_pool, jnp.bfloat16), jnp.asarray(v_pool,
                                                          jnp.bfloat16),
        0, jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(live, jnp.int32), interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def _plain(q, k_pool, v_pool, layer, tables, lengths, kv_heads, scale):
    """Attention over the gathered tables in float32, a key-value head
    shared by ``H / kv_heads`` query heads, scores times ``scale``."""
    B, C, H, D = q.shape
    G = kv_heads or H
    k, v = (pool[layer][tables].reshape(B, -1, pool.shape[3])[..., :G * D]
            .reshape(B, -1, G, D).astype(jnp.float32)
            for pool in (k_pool, v_pool))
    qg = q.astype(jnp.float32).reshape(B, C, G, H // G, D)
    s = jnp.einsum("bcgrd,btgd->bgrct", qg, k) * scale
    seen = (jnp.arange(k.shape[1])[None, None, :]
            <= (lengths[:, None] + jnp.arange(C)[None, :])[:, :, None])
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), axis=-1)
    return jnp.einsum("bgrct,btgd->bcgrd", p, v).reshape(B, C, H, D)


#: heads, head_dim, kv_heads, one pool for keys and values, scale, dtype
ARGUMENTS = {
    # the absorbed form of latent attention: every head's folded query
    # against a token's whole row, which is its value too
    "one_pool_one_shared_head": (4, 128, 1, True, 48 ** -0.5, jnp.bfloat16),
    "one_pool_one_shared_head_float32": (4, 128, 1, True, 48 ** -0.5,
                                         jnp.float32),
    "one_pool_default_scale": (4, 128, 2, True, None, jnp.bfloat16),
    "one_pool_block_diagonal": (2, 64, None, True, None, jnp.bfloat16),
    "scale_grouped": (4, 128, 2, False, 0.05, jnp.bfloat16),
    "scale_block_diagonal": (2, 64, None, False, 0.2, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(ARGUMENTS))
def test_one_pool_and_a_given_scale(name):
    """``v_pool=None`` (a group copied once, keys and values the same
    rows) and ``scale`` in both layouts: against plain attention over
    the gathered tables, and, one pool, bit for bit what the kernel
    makes of the same pool handed in twice. A dead lane between live
    ones, lanes one short of, on and past a group's edge."""
    heads, head_dim, kv_heads, one_pool, scale, dtype = ARGUMENTS[name]
    rng = np.random.RandomState(5)
    bs = 8 if dtype == jnp.float32 else BS
    max_blocks = 5 * pa.GROUP_TOKENS // (2 * bs)      # 2.5 groups
    lengths = jnp.asarray([pa.GROUP_TOKENS - 3, 90, pa.GROUP_TOKENS - 2,
                           pa.GROUP_TOKENS - 1, max_blocks * bs - 2, 0],
                          jnp.int32)
    live = jnp.asarray([1, 0, 1, 1, 1, 1], jnp.int32)
    row = (kv_heads or heads) * head_dim
    pool = (2, 6 * max_blocks + 1, bs, row)
    # rows of unit scale over the whole width, as cached rows are
    k_pool = jnp.asarray(rng.standard_normal(pool) * row ** -0.5 * 8, dtype)
    v_pool = None if one_pool else jnp.asarray(rng.standard_normal(pool),
                                               dtype)
    q = jnp.asarray(rng.standard_normal((6, 2, heads, head_dim)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, pool[1])).reshape(
        6, max_blocks), jnp.int32)
    got = pa.paged_attention(q, k_pool, v_pool, 1, tables, lengths, live,
                             kv_heads=kv_heads, scale=scale, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype
    values = k_pool if one_pool else v_pool
    want = _plain(q, k_pool, values, 1, tables, lengths, kv_heads,
                  head_dim ** -0.5 if scale is None else scale)
    got32, alive = np.asarray(got, np.float32), np.asarray(live, bool)
    assert not got32[~alive].any()
    flat = lambda a: np.asarray(a)[alive].reshape(alive.sum(), -1)  # noqa: E731
    err = np.linalg.norm(flat(got32) - flat(want), axis=1) \
        / np.linalg.norm(flat(want), axis=1)
    tol = F32_TOL if dtype == jnp.float32 else KERNEL_TOL
    assert (err <= tol).all(), err
    if one_pool:
        twice = pa.paged_attention(q, k_pool, k_pool, 1, tables, lengths,
                                   live, kv_heads=kv_heads, scale=scale,
                                   interpret=True)
        np.testing.assert_array_equal(got32, np.asarray(twice, np.float32))


def test_one_pool_copies_half_the_blocks():
    """The traced kernel with ``v_pool=None`` holds one pool operand and
    one group buffer, and starts one copy a block where two pools start
    two: the bytes of the step are the rows', once."""
    q = jnp.zeros((2, 2, 4, 128), jnp.bfloat16)
    pool = jnp.zeros((1, 9, 16, 128), jnp.bfloat16)
    args = (0, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32))

    def starts(v_pool):
        text = str(jax.make_jaxpr(lambda q, k: pa.paged_attention(
            q, k, v_pool, *args, kv_heads=1, interpret=True))(q, pool))
        return text.count("dma_start")

    assert starts(pool) == 2 * starts(None) > 0


def test_blocks_read_is_the_kernels_walk():
    # 16-token blocks, groups of 8: rows round up to 128
    assert pa.blocks_read([0], 2, 16, 64) == 8
    assert pa.blocks_read([126], 2, 16, 64) == 8
    assert pa.blocks_read([127], 2, 16, 64) == 16
    assert pa.blocks_read([1022], 2, 16, 64) == 64
    assert pa.blocks_read([1023], 2, 16, 64) == 64      # clipped to the table
    assert pa.blocks_read([150, 300, 5], 2, 16, 64) == 16 + 24 + 8
    assert pa.blocks_read([], 2, 16, 64) == 0
    assert pa.blocks_read([10], 2, 64, 264) == 2        # 64-token blocks


# -- the path rule ------------------------------------------------------------

#: (backend, query rows, block_size, row, dtype) -> kernel?
RULE = {
    "tpu_decode_gpt2_xl": ("tpu", 2 * 25, 16, 1664, jnp.bfloat16, True),
    "tpu_beam_default_model": ("tpu", 2 * 12, 16, 768, jnp.bfloat16, True),
    "tpu_verify_4_drafts": ("tpu", 5 * 25, 16, 1664, jnp.bfloat16, True),
    "tpu_verify_5_drafts": ("tpu", 6 * 25, 16, 1664, jnp.bfloat16, False),
    "tpu_prefill_chunk_64": ("tpu", 64 * 25, 16, 1664, jnp.bfloat16, False),
    "tpu_float32_blocks_of_8": ("tpu", 4, 8, 128, jnp.float32, True),
    "tpu_float32_blocks_of_4": ("tpu", 4, 4, 128, jnp.float32, False),
    "tpu_bfloat16_blocks_of_8": ("tpu", 4, 8, 128, jnp.bfloat16, False),
    "tpu_row_not_lane_aligned": ("tpu", 50, 16, 1600, jnp.bfloat16, False),
    "tpu_blocks_of_48": ("tpu", 50, 48, 1664, jnp.bfloat16, False),
    "cpu_decode_gpt2_xl": ("cpu", 2 * 25, 16, 1664, jnp.bfloat16, False),
    "gpu_decode_gpt2_xl": ("gpu", 2 * 25, 16, 1664, jnp.bfloat16, False),
}


@pytest.mark.parametrize("name", sorted(RULE))
def test_path_rule(monkeypatch, name):
    backend, rows, bs, row, dtype, kernel = RULE[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pa.kernel_applies(rows, bs, row, dtype) is kernel


BF16 = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                         num_heads=2, head_dim=16, max_seq_len=64,
                         dtype=jnp.bfloat16)


def _decode_args(cfg, lanes=2, bs=16, num_blocks=9):
    model = Transformer(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), "i4")))
    pools = jax.eval_shape(lambda: kvc.make_pools(cfg, num_blocks, bs))
    i32 = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((lanes,), jnp.float32)
    state = kvc.DecodeState(
        tokens=i32, lengths=i32, live=i32, remaining=i32, eos=i32,
        sample=kvc.SampleParams(
            temperature=f32, top_k=i32, top_p=f32, emitted=i32,
            key=jax.ShapeDtypeStruct((lanes, 2), jnp.uint32)))
    tables = jax.ShapeDtypeStruct((lanes, cfg.max_seq_len // bs), jnp.int32)
    return model, params, pools, tables, state


def test_off_a_tpu_the_decode_program_takes_the_gather_path():
    """Shapes the kernel would take, on this backend: the program holds
    no kernel call of any kind (no Mosaic call, no interpreter), and the
    scheduler's rule says it reads whole tables."""
    model, params, pools, tables, state = _decode_args(BF16)
    assert pa.shapes_fit(BF16.paged_query_rows(2), 16, pools[0].shape[3],
                         pools[0].dtype)
    program = kvc.build_decode_program(model, 2)
    text = program.lower(params, pools, tables, state).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text.lower()
    assert program.query_rows == 4
    assert not kvc.reads_live_blocks(program, pools)


def test_on_a_tpu_unfit_shapes_fall_to_the_gather_path(monkeypatch):
    """With a TPU for default backend, a prefill chunk (96 columns x 2
    heads: 192 query rows) and a pool of 4-token blocks still lower to
    plain XLA; the
    decode program over a fitting pool traces the kernel, which only
    Mosaic can compile: it is never handed to the interpreter."""
    import dataclasses
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a configuration of its own: a program traced for another test,
    # under this backend, would be read back from jit's cache
    cfg = dataclasses.replace(BF16, vocab_size=72)
    model, params, pools, tables, state = _decode_args(cfg)
    cache = PagedCache(pools, jax.ShapeDtypeStruct((1, 4), jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32))
    prefill = jax.jit(lambda p, c, t: model.apply(p, t, cache=c))
    text = prefill.lower(params, cache, jax.ShapeDtypeStruct(
        (1, 96), jnp.int32)).as_text()
    assert "tpu_custom_call" not in text
    small = _decode_args(cfg, bs=4)
    program = kvc.build_decode_program(small[0], 2)
    assert "tpu_custom_call" not in program.lower(*small[1:]).as_text()
    assert not kvc.reads_live_blocks(program, small[2])
    # fitting shapes: the kernel is in the trace, not interpreted
    program = kvc.build_decode_program(model, 2)
    assert kvc.reads_live_blocks(program, pools)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: program(*a))(params, pools, tables, state))
    assert "pallas_call" in jaxpr and "interpret=False" in jaxpr, jaxpr[-3000:]


def test_the_kernel_without_interpret_raises_off_a_tpu():
    q = jnp.zeros((1, 2, 2, 16), jnp.bfloat16)
    pool = jnp.zeros((1, 4, 16, 128), jnp.bfloat16)
    with pytest.raises(Exception):
        jax.block_until_ready(pa.paged_attention(
            q, pool, pool, 0, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)))


def test_unfit_shapes_are_refused_by_the_kernel_itself():
    q = jnp.zeros((1, 2, 2, 16), jnp.float32)
    pool = jnp.zeros((1, 4, 4, 128), jnp.float32)
    with pytest.raises(ValueError, match="do not fit"):
        pa.paged_attention(q, pool, pool, 0, jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros((1,), jnp.int32),
                           jnp.ones((1,), jnp.int32), interpret=True)


# -- the scheduler's counter --------------------------------------------------

def test_scheduler_counts_blocks_read_against_the_table():
    """``hvd_tpu_gen_paged_attn_blocks_total``: on this backend the
    decode program gathers whole tables, so read == table; told (as the
    path rule tells it on a TPU) that the program reads live blocks, the
    scheduler counts each live lane's rows rounded up to a group."""
    from horovod_tpu import metrics
    from horovod_tpu.serving import GenerationEngine

    cfg = TransformerConfig(vocab_size=64, num_layers=1, d_model=32,
                            num_heads=2, head_dim=16, max_seq_len=256,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    series = 'hvd_tpu_gen_paged_attn_blocks_total{kind="%s"}'

    def deltas(on_kernel):
        engine = GenerationEngine(model, params=params, block_size=16,
                                  num_blocks=40, max_seqs=4,
                                  prefill_chunk=16, async_depth=0)
        try:
            assert engine.batcher._reads_live == set()
            if on_kernel:
                engine.batcher._reads_live = {"decode"}
            before = metrics.snapshot()
            tokens = engine.generate([1 + i % 60 for i in range(130)],
                                     max_tokens=5)
            after = metrics.snapshot()
        finally:
            engine.close()
        assert len(tokens) == 5
        return [after[series % k] - before.get(series % k, 0.0)
                for k in ("read", "table")]

    read, table = deltas(on_kernel=False)
    # the first token comes from the prefill; four decode steps follow
    assert table == 4 * 4 * 16 and read == table
    read, table = deltas(on_kernel=True)
    # one live lane of 130..133 tokens + 2 columns: two groups of 8
    assert table == 4 * 4 * 16 and read == 4 * 16
