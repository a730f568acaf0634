"""Command A+ on the generation plane (ISSUE 33): the block against the
plain float32 reference through both plane groups, the shares of a layer
against the uncut layer, the sigmoid router on a hand-worked case, the
paged kernel's grouped layout and first row against the gather path, and
the allocator and scheduler cases of a window group: release timing
under chunked prefill, a released block matched from the cached list, a
hit cut by a missing window block, preemption, both groups empty at the
end, and what keeps one block list a sequence refusing the model by
name. Tiny float32 sizes throughout; the published widths run on the
chip (``perfbench/runners/serve_command_a_plus.py``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.models import (CommandAPlus, CommandAPlusConfig, PagedCache,
                                PlaneGroup, TransformerConfig)
from horovod_tpu.models import command_a_plus as cap
from horovod_tpu.ops import paged_attention as pa
from horovod_tpu.parallel import moe
from horovod_tpu.serving import GenerationEngine
from horovod_tpu.serving.generation import kv_cache as kvc

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench.reference import command_a_plus as ref  # noqa: E402

WINDOW, BLOCK = 16, 4
SIZES = dict(vocab_size=211, hidden_size=64, intermediate_size=48,
             num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=2, head_dim=16, sliding_window=WINDOW,
             num_experts=16, num_experts_per_tok=4, num_shared_experts=2,
             held_experts=(0, 16), table_positions=128, dtype=jnp.float32,
             param_dtype=jnp.float32)
SETTINGS = dict(layer_norm_eps=1e-5, sliding_window=WINDOW,
                num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                rope_theta=50000.0, num_experts_per_tok=4,
                num_shared_experts=2, num_experts=16, num_hidden_layers=4,
                layer_types=list(CommandAPlusConfig().layer_types),
                logit_scale=1)


@pytest.fixture(scope="module", autouse=True)
def small_reference_blocks():
    was = ref.QUERY_BLOCK, ref.ROW_BLOCK
    ref.QUERY_BLOCK, ref.ROW_BLOCK = 32, 48
    yield
    ref.QUERY_BLOCK, ref.ROW_BLOCK = was


@pytest.fixture(scope="module")
def served():
    model = CommandAPlus(CommandAPlusConfig(**SIZES))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _engine(model, params, **kw):
    kw = {"max_seqs": 4, "block_size": BLOCK, "num_blocks": 64,
          "prefill_chunk": 8, **kw}
    return GenerationEngine(model, params=params, **kw)


def _reference_gap(params, prompt, toks, logprobs):
    """(worst reference-logit gap, worst log-probability difference) of
    served greedy ``toks`` after ``prompt``."""
    row = jnp.asarray([prompt + toks[:-1]], jnp.int32)
    logits = np.asarray(ref.forward(params["params"], row, SETTINGS)[0])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    logp = np.asarray(jax.nn.log_softmax(logits[at], axis=-1))
    return (max(float(logits[a].max() - logits[a, t])
                for a, t in zip(at, toks)),
            float(np.max(np.abs(np.asarray(logprobs)
                                - logp[np.arange(len(toks)), toks]))))


# -- the declaration ----------------------------------------------------------

def test_cache_spec_declares_two_plane_groups():
    cfg = CommandAPlusConfig()
    spec = cfg.cache_spec()
    assert spec.groups == (PlaneGroup("full", 8),
                           PlaneGroup("window", 24, 4096))
    assert spec.planes == 32 and spec.rows == (("k", 1024), ("v", 1024))
    # layers 0-2 sliding, 3 full, 4-6 sliding ...: a kind's planes in order
    assert [cfg.plane_of(i) for i in (0, 2, 3, 4, 7)] == \
        [(1, 0), (1, 2), (0, 0), (1, 3), (0, 1)]
    assert cfg.paged_query_rows(2) == 32
    one = TransformerConfig().cache_spec()
    assert one.groups == () and one.plane_groups() == \
        (PlaneGroup("full", one.planes),)


def test_make_pools_and_block_bytes_by_group():
    cfg = CommandAPlusConfig(**SIZES)
    pools = jax.eval_shape(lambda: kvc.make_pools(cfg, (9, 5), BLOCK))
    assert [p.shape for p in pools] == [(1, 9, 4, 128)] * 2 \
        + [(3, 5, 4, 128)] * 2
    per_plane = BLOCK * 2 * 128 * 4
    assert kvc.block_bytes(cfg, BLOCK, group=0) == per_plane
    assert kvc.block_bytes(cfg, BLOCK, group=1) == 3 * per_plane
    assert kvc.block_bytes(cfg, BLOCK) == 4 * per_plane
    with pytest.raises(ValueError, match="2 plane groups"):
        kvc.make_pools(cfg, 9, BLOCK)


# -- the block against the reference ------------------------------------------

def test_full_forward_equals_reference(served):
    model, params = served
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 211, (1, 70)),
                       jnp.int32)
    got = model.apply(params, toks)
    want = ref.forward(params["params"], toks, SETTINGS)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def test_paged_prefill_then_decode_equals_reference(served):
    """Chunked prefill and decode steps through hand-made tables of both
    groups, the window group's released entries zeroed as the scheduler
    leaves them: the logits at every position are the reference's."""
    model, params = served
    T = 70
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 211, (1, T)),
                       jnp.int32)
    want = ref.forward(params["params"], toks, SETTINGS)
    spec = model.cfg.cache_spec()
    pools = tuple(jnp.zeros((g.planes, 40, BLOCK, 128), jnp.float32)
                  for g in spec.groups for _ in range(2))
    full = np.arange(1, 33, dtype=np.int32)[None, :]
    apply = jax.jit(lambda chunk, cache: model.apply(params, chunk,
                                                     cache=cache))
    got = []
    for start, width, live in [(a, 16, 16) for a in range(0, 64, 16)] \
            + [(a, 2, 1) for a in range(64, T)]:
        window = full.copy()
        window[0, :max(0, start - WINDOW + 1) // BLOCK] = 0    # released
        chunk = jnp.zeros((1, width), jnp.int32).at[0, :live].set(
            toks[0, start:start + live])
        cache = PagedCache(pools, (jnp.asarray(full), jnp.asarray(window)),
                           jnp.asarray([start], jnp.int32),
                           jnp.asarray([live], jnp.int32))
        logits, cache = apply(chunk, cache)
        pools = cache.pools
        got.append(logits[:, :live])
    got = jnp.concatenate(got, axis=1)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_faults_move_the_logits(served, fault):
    """Each fault of the tolerance tool changes what the reference
    computes (how far, and past which limit, is the chip's to say)."""
    model, params = served
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 211, (1, 50)),
                       jnp.int32)
    clean = ref.forward(params["params"], toks, SETTINGS)
    wrong = ref.forward(params["params"], toks, SETTINGS, faults=(fault,))
    assert float(jnp.max(jnp.abs(wrong - clean))) > 1e-4


def test_engine_serves_through_both_groups(served):
    """Prompts inside a chunk, across chunks whose first column passes a
    release, and replies that cross two more releases: greedy tokens and
    their log-probabilities are the reference's, blocks are released on
    the way and both groups end empty."""
    model, params = served
    held = []
    eng = _engine(model, params, on_step=lambda phase, ids: held.append(
        (eng.allocator.in_use, eng.allocator.window.in_use)))
    released = hvd_metrics.snapshot().get(
        "hvd_tpu_gen_window_blocks_released_total", 0.0)
    try:
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, 211, n).tolist(), m)
                for n, m in [(5, 4), (37, 12), (60, 20), (13, 30)]]
        seqs = [eng.submit(p, max_tokens=m) for p, m in reqs]
        for (p, m), seq in zip(reqs, seqs):
            toks = eng.result(seq, timeout=120)
            gap, off = _reference_gap(params, p, toks, seq.logprobs)
            assert len(toks) == m and gap == 0.0 and off < 1e-5
        alloc = eng.allocator
        assert alloc.in_use == 0 and alloc.window.in_use == 0
        # 60 + 20 tokens: 20 blocks on the full plane, 6 at most on a
        # window plane (a window, a chunk and the block being filled)
        assert alloc.peak_in_use > alloc.window.peak_in_use
        assert max(w for _, w in held) <= 4 * (WINDOW // BLOCK + 2 + 2)
        snap = hvd_metrics.snapshot()
        assert snap["hvd_tpu_gen_window_blocks_released_total"] > released
        assert snap['hvd_tpu_gen_kv_group_blocks_in_use{group="window"}'] == 0
        assert snap['hvd_tpu_gen_kv_group_blocks_in_use{group="full"}'] == 0
        assert snap["hvd_tpu_gen_kv_blocks_in_use"] == 0
        assert snap['hvd_tpu_gen_phase_seconds{phase="window.release"}'][
            "count"] > 0
        assert snap['hvd_tpu_gen_moe_picks_total{kind="zero"}'] == 0
    finally:
        eng.close()


def test_attention_blocks_are_counted_by_group(served):
    model, params = served
    series = 'hvd_tpu_gen_paged_attn_group_blocks_total{kind="%s",group="%s"}'
    total = 'hvd_tpu_gen_paged_attn_blocks_total{kind="%s"}'
    before = hvd_metrics.snapshot()
    eng = _engine(model, params)
    try:
        eng.result(eng.submit(list(range(1, 8)), max_tokens=5), timeout=60)
    finally:
        eng.close()
    after = hvd_metrics.snapshot()
    delta = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    by_group = {(k, g): delta(series % (k, g))
                for k in ("read", "table") for g in ("full", "window")}
    # off a TPU the gather path reads every table: read == table
    assert by_group["table", "full"] == by_group["table", "window"] > 0
    assert by_group["read", "full"] == by_group["table", "full"]
    assert delta(total % "table") == 2 * by_group["table", "full"]
    assert delta(total % "read") == sum(
        by_group["read", g] for g in ("full", "window"))


# -- the shares of a layer ----------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(served):
    """A layer of the deployment: eight chips hold two of the sixteen
    experts each; attention, router and shared experts are whole on each.
    The shares' expert parts, with what every chip computes alike
    counted once, add up to the uncut reference's layer."""
    model, params = served
    p = params["params"]["layer_1"]           # a sliding layer
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    positions = jnp.arange(24)[None, :]
    valid = jnp.ones((1, 24), jnp.bool_)
    want = ref.layer(p, h[0], SETTINGS, "sliding_attention", (0, 16))

    def share(first):
        cfg = CommandAPlusConfig(**{**SIZES,
                                    "held_experts": (first, first + 2)})
        moe_p = dict(p["moe"])
        for name in ("experts_gate", "experts_up", "experts_down"):
            moe_p[name] = p["moe"][name][first:first + 2]
        out = cap.ParallelLayer(cfg, True).apply(
            {"params": {**p, "moe": moe_p}}, h, positions, valid)
        return out - h                        # a + shared + routed share

    cfg = CommandAPlusConfig(**SIZES)
    x = cap.layer_norm(h, p["input_layernorm"], 1e-5)
    shared = cap.GatedMlp(64, 2 * 48, jnp.float32).apply(
        {"params": p["moe"]["shared"]}, x) / 2
    attn = cap.Attention(cfg, True).apply({"params": p["attn"]}, x,
                                          positions)
    parts = [share(first) for first in range(0, 16, 2)]
    # every share carries the attention and the shared mean: count once
    total = h + sum(parts) - 7 * (attn + shared)
    assert float(jnp.max(jnp.abs(total[0] - want))) < 5e-6
    # and a share alone is not the layer: the others' experts are missing
    assert float(jnp.max(jnp.abs((h + parts[0])[0] - want))) > 1e-4


# -- the router ---------------------------------------------------------------

def test_sigmoid_router_on_a_hand_worked_case():
    """Two tokens over five outputs, top 2: the largest sigmoids win and
    their weights are the scores over the pair's sum."""
    logits = jnp.asarray([[0.0, np.log(3.0), -np.log(3.0), np.log(3.0) + 1e-3,
                           -5.0],
                          [np.log(9.0), 0.0, 0.0, -1.0, np.log(4.0)]],
                         jnp.float32)
    idx, w = moe.route_sigmoid_topk(logits, 2)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    assert idx.tolist() == [[3, 1], [0, 4]]
    # sigmoid(ln 3) = 3/4 twice (nearly): halves
    np.testing.assert_allclose(np.asarray(w[0]), [0.5, 0.5], atol=2e-4)
    # sigmoid(ln 9) = 0.9, sigmoid(ln 4) = 0.8: 9/17 and 8/17
    np.testing.assert_allclose(np.asarray(w[1]), [9 / 17, 8 / 17], atol=1e-6)
    ridx, rw = ref.route(logits, jnp.eye(5, dtype=jnp.float32), 2)
    assert ridx.tolist() == idx.tolist()
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w), atol=1e-6)


# -- the kernel ---------------------------------------------------------------

def _gather_path(q, k_pool, v_pool, plane, tables, lengths, live, G, window):
    B, C, H, D = q.shape
    k = k_pool[plane][tables][..., :G * D].reshape(B, -1, G, D)
    v = v_pool[plane][tables][..., :G * D].reshape(B, -1, G, D)
    s = jnp.einsum("bcgrd,btgd->bgrct",
                   q.astype(jnp.float32).reshape(B, C, G, H // G, D),
                   k.astype(jnp.float32)) / np.sqrt(D)
    t = jnp.arange(k.shape[1])[None, None, :]
    at = (lengths[:, None] + jnp.arange(C)[None, :])[:, :, None]
    seen = t <= at
    if window:
        seen &= t > at - window
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), axis=-1)
    out = jnp.einsum("bgrct,btgd->bcgrd", p, v.astype(jnp.float32))
    return jnp.where((live > 0)[:, None, None, None],
                     out.reshape(B, C, H, D), 0.0)


@pytest.mark.parametrize("window", [None, 128, 160, 384])
@pytest.mark.parametrize("columns", [2, 3])
def test_kernel_grouped_layout_and_first_row(window, columns):
    """Interpreted: 8 query heads over 2 key-value heads of 128, lanes
    inside one group, three and five groups deep and dead; with a window
    the entries before it are the null block, as released blocks are."""
    rng = np.random.default_rng(7)
    B, G, rep, D, bs, nb, width = 4, 2, 4, 128, 16, 64, 40
    k_pool = jnp.asarray(rng.normal(size=(3, nb, bs, G * D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(3, nb, bs, G * D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, columns, G * rep, D)), jnp.bfloat16)
    tables = rng.integers(1, nb, (B, width)).astype(np.int32)
    lengths = jnp.asarray([5, 300, 0, 600], jnp.int32)
    live = jnp.asarray([1, 1, 0, 1], jnp.int32)
    want = _gather_path(q, k_pool, v_pool, 1, jnp.asarray(tables), lengths,
                        live, G, window)
    released = tables.copy()
    if window:
        for b in range(B):
            released[b, :max(0, int(lengths[b]) - window + 1) // bs] = 0
    got = pa.paged_attention(q, k_pool, v_pool, 1, jnp.asarray(released),
                             lengths, live, kv_heads=G, window=window,
                             interpret=True)
    assert got.shape == q.shape and got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02
    assert not np.asarray(got[2]).any()               # the dead lane


def test_kernel_one_head_a_key_value_head_keeps_its_layout_with_a_window():
    """The block-diagonal layout (no ``kv_heads``) takes a window too."""
    rng = np.random.default_rng(8)
    B, H, D, bs, nb, width, window = 2, 2, 64, 16, 32, 24, 128
    k_pool = jnp.asarray(rng.normal(size=(2, nb, bs, 128)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(2, nb, bs, 128)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, 2, H, D)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(1, nb, (B, width)), jnp.int32)
    lengths = jnp.asarray([40, 333], jnp.int32)
    live = jnp.ones((B,), jnp.int32)
    want = _gather_path(q, k_pool, v_pool, 0, tables, lengths, live, H,
                        window)
    got = pa.paged_attention(q, k_pool, v_pool, 0, tables, lengths, live,
                             window=window, interpret=True)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02


def test_blocks_read_follows_the_window():
    # 16-token blocks: a group is 8 blocks of 128 rows
    assert pa.blocks_read([5, 300, 600], 2, 16, 40) == (1 + 3 + 5) * 8
    # window 128: the walk starts at the group of position length - 127
    assert pa.blocks_read([5, 300, 600], 2, 16, 40, window=128) \
        == (1 + 2 + 2) * 8
    assert pa.first_group(600, 128) == 3 and pa.first_group(100, 128) == 0
    assert pa.first_group(600, None) == 0
    with pytest.raises(ValueError, match="do not fit"):
        pa.paged_attention(
            jnp.zeros((1, 2, 8, 64), jnp.bfloat16),
            jnp.zeros((1, 4, 16, 128), jnp.bfloat16),
            jnp.zeros((1, 4, 16, 128), jnp.bfloat16), 0,
            jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32), kv_heads=2, interpret=True)


def test_blocked_attention_equals_dense(served):
    """The XLA walk by key blocks against the gather of every slot, for a
    chunk and for decode lanes at different depths, with and without a
    window."""
    rng = np.random.default_rng(9)
    B, C, G, rep, D, bs, nb, width = 3, 4, 2, 4, 16, 4, 40, 32
    k_pool = jnp.asarray(rng.normal(size=(2, nb, bs, 128)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(2, nb, bs, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, C, G * rep, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, (B, width)), jnp.int32)
    lengths = jnp.asarray([3, 77, 40], jnp.int32)
    live = jnp.asarray([4, 2, 0], jnp.int32)
    positions = lengths[:, None] + jnp.arange(C)[None, :]
    was = cap.KEY_BLOCK
    cap.KEY_BLOCK = 16
    try:
        for window in (None, 16):
            want = _gather_path(q, k_pool, v_pool, 1, tables, lengths, live,
                                G, window)
            got = cap.blocked_attention(q, k_pool, v_pool, 1, tables,
                                        positions, live, window, G)
            # pad columns of a live lane (past ``live``) are not compared
            for b, n in enumerate([4, 2, 4]):
                assert float(jnp.max(jnp.abs(got[b, :n] - want[b, :n]))) \
                    < 1e-5, (window, b)
    finally:
        cap.KEY_BLOCK = was


# -- the allocator ------------------------------------------------------------

def _pair(span=4, full=32, window=12):
    w = kvc.BlockAllocator(window, BLOCK, prefix_cache=True, group="window")
    return kvc.BlockAllocator(full, BLOCK, prefix_cache=True, window=w,
                              window_span=span), w


def _chain(n):
    out, parent = [], None
    for j in range(n):
        parent = kvc.chain_hash(parent, [j] * BLOCK)
        out.append(parent)
    return out


def test_match_is_cut_to_what_the_window_group_covers():
    alloc, win = _pair(span=4)
    hashes = _chain(10)
    full = alloc.allocate(10)
    held = win.allocate(10)
    for j in range(10):
        alloc.register(full[j], hashes[j])
        win.register(held[j], hashes[j])
    # every window block still indexed: the whole chain
    assert alloc.match_probe(hashes)[0] == 10
    assert win.covered_depth(hashes, 4) == 10
    # a running sequence released its first six window blocks: they park
    # in the cached list, indexed, and the hit is as deep as before
    win.release(held[:6])
    assert win.cached_blocks == 6 and alloc.match_probe(hashes)[0] == 10
    # evict the released blocks (allocate past the free list)
    win.allocate(win.free_blocks + 6)
    assert win.cached_blocks == 0
    # blocks 6-9 cover the window before boundary 10; no boundary below
    # 10 has its four blocks (5 needs 1-4), and a short prefix needs all
    assert win.covered_depth(hashes, 4) == 10
    assert win.covered_depth(hashes[:9], 4) == 0
    assert alloc.match_probe(hashes[:9]) == (0, 0)
    got = alloc.match(hashes)
    assert got == full
    tail = win.match_tail(hashes, 4)
    assert tail == [0] * 6 + held[6:]
    assert all(win.refcount(b) == 2 for b in held[6:])


def test_a_missing_window_block_cuts_the_hit():
    alloc, win = _pair(span=2, window=8)
    hashes = _chain(6)
    full = alloc.allocate(6)
    held = win.allocate(6)
    for j in range(6):
        alloc.register(full[j], hashes[j])
        if j != 4:
            win.register(held[j], hashes[j])      # block 4 never indexed
    # boundary 6 needs 4-5, boundary 5 needs 3-4: the deepest covered is 4
    assert win.covered_depth(hashes, 2) == 4
    assert alloc.match_probe(hashes)[0] == 4
    assert alloc.match(hashes) == full[:4]
    assert win.match_tail(hashes[:4], 2) == [0, 0] + held[2:4]
    with pytest.raises(ValueError, match="window group"):
        win.match_tail(hashes[:5], 2)


def test_window_allocator_must_match_the_first_groups_blocks():
    w = kvc.BlockAllocator(8, BLOCK * 2)
    with pytest.raises(ValueError, match="window group"):
        kvc.BlockAllocator(8, BLOCK, window=w, window_span=2)
    with pytest.raises(ValueError, match="per-sequence state"):
        kvc.BlockAllocator(8, BLOCK, state_slots=2, snapshot_slots=2,
                           window=kvc.BlockAllocator(8, BLOCK),
                           window_span=2)


# -- the scheduler ------------------------------------------------------------

def test_release_timing_under_chunked_prefill(served):
    """A window block goes exactly when its last position is a window
    behind the position about to be written: before a chunk whose first
    column sits at ``p`` the sequence holds no block that ends at or
    before ``p - window`` and every block after."""
    model, params = served
    seen = []
    eng = _engine(model, params, max_seqs=2)
    batcher = eng.batcher
    run_prefill = batcher._run_prefill

    def spy(cache, tokens, sample):
        (s,) = [x for x in batcher._running if x.state == "prefill"]
        seen.append((s.prefilled, list(s.wblocks), s.wreleased))
        return run_prefill(cache, tokens, sample)

    batcher._run_prefill = spy
    try:
        prompt = np.random.default_rng(4).integers(0, 211, 61).tolist()
        eng.result(eng.submit(prompt, max_tokens=2), timeout=60)
    finally:
        eng.close()
    assert [p for p, _, _ in seen] == list(range(0, 61, 8))
    for start, wblocks, released in seen:
        # block j ends at 4 j + 3: released iff 4 j + 3 <= start - 16
        want = max(0, (start - WINDOW + 1) // BLOCK)
        assert released == want, (start, released)
        assert all(b == 0 for b in wblocks[:want])
        assert all(b > 0 for b in wblocks[want:])
        assert len(wblocks) == -(-min(start + 8, 61) // BLOCK)


def test_released_blocks_are_matched_from_the_cached_list(served):
    """A prompt resent with more tokens attaches the full chain's blocks
    and the window group's last window of them, which the first request
    released or left on retiring; the continuation is the reference's."""
    model, params = served
    eng = _engine(model, params)
    try:
        rng = np.random.default_rng(5)
        first = rng.integers(0, 211, 60).tolist()
        eng.result(eng.submit(first, max_tokens=6), timeout=60)
        assert eng.allocator.window.cached_blocks > 0
        hit = 'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="local"}'
        before = hvd_metrics.snapshot().get(hit, 0.0)
        again = first + rng.integers(0, 211, 9).tolist()
        seq = eng.submit(again, max_tokens=5)
        toks = eng.result(seq, timeout=60)
        assert hvd_metrics.snapshot()[hit] - before == 60
        gap, off = _reference_gap(params, again, toks, seq.logprobs)
        assert gap == 0.0 and off < 1e-5
        assert eng.allocator.in_use == 0 and eng.allocator.window.in_use == 0
    finally:
        eng.close()


def test_hit_is_cut_when_the_window_group_lost_a_block(served):
    """Drop the window group's index (its blocks evicted) while the full
    group still holds the chain: nothing can be continued from, the
    prompt is prefilled whole, and the tokens are still the reference's."""
    model, params = served
    eng = _engine(model, params)
    try:
        rng = np.random.default_rng(6)
        first = rng.integers(0, 211, 40).tolist()
        eng.result(eng.submit(first, max_tokens=3), timeout=60)
        eng.allocator.window.reset_cache()
        hit = 'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="local"}'
        before = hvd_metrics.snapshot().get(hit, 0.0)
        again = first + [7, 8, 9]
        seq = eng.submit(again, max_tokens=4)
        toks = eng.result(seq, timeout=60)
        assert hvd_metrics.snapshot().get(hit, 0.0) == before
        gap, _ = _reference_gap(params, again, toks, seq.logprobs)
        assert gap == 0.0
    finally:
        eng.close()


def test_preemption_frees_both_groups_and_recomputes(served):
    """A full pool too small for three long replies: the youngest is
    preempted, gives back both groups' blocks, and its recomputed
    continuation is still the reference's."""
    model, params = served
    eng = _engine(model, params, num_blocks=30, max_seqs=3)
    pre = "hvd_tpu_gen_preemptions_total"
    before = hvd_metrics.snapshot().get(pre, 0.0)
    try:
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(0, 211, 24).tolist(), 30) for _ in range(3)]
        seqs = [eng.submit(p, max_tokens=m) for p, m in reqs]
        for (p, m), seq in zip(reqs, seqs):
            toks = eng.result(seq, timeout=120)
            gap, off = _reference_gap(params, p, toks, seq.logprobs)
            assert len(toks) == m and gap == 0.0 and off < 1e-5
        assert hvd_metrics.snapshot()[pre] > before
        assert eng.allocator.in_use == 0 and eng.allocator.window.in_use == 0
    finally:
        eng.close()


def test_cancel_leaves_both_groups_empty(served):
    model, params = served
    eng = _engine(model, params)
    try:
        prompt = np.random.default_rng(8).integers(0, 211, 50).tolist()
        seq = eng.submit(prompt, max_tokens=60, request_id="going")
        next(eng.batcher.stream(seq, timeout=60))
        eng.cancel("going")
        with pytest.raises(Exception, match="cancelled"):
            eng.result(seq, timeout=60)
        assert eng.allocator.in_use == 0 and eng.allocator.window.in_use == 0
    finally:
        eng.close()


def test_one_block_list_paths_refuse_plane_groups_by_name(served):
    model, params = served
    with pytest.raises(kvc.PlaneGroupsError, match="speculative"):
        GenerationEngine(model, params=params, spec_mode="ngram",
                         block_size=BLOCK, num_blocks=16)
    with pytest.raises(kvc.PlaneGroupsError, match="verify"):
        kvc.build_verify_program(model, 3)
    with pytest.raises(kvc.PlaneGroupsError, match="beam"):
        kvc.build_beam_program(model, 2)
    eng = _engine(model, params)
    try:
        assert eng.max_beams == 1
        with pytest.raises(kvc.PlaneGroupsError, match="beam"):
            eng.submit([1, 2, 3], max_tokens=2, num_beams=2)
        with pytest.raises(kvc.PlaneGroupsError, match="disagg"):
            eng.kv_export(["x"])
        with pytest.raises(kvc.PlaneGroupsError, match="disagg"):
            eng.kv_import(["x"], ["x"], None)
    finally:
        eng.close()
    # a window that is not whole blocks is refused before anything runs
    with pytest.raises(ValueError, match="whole blocks"):
        GenerationEngine(model, params=params, block_size=3, num_blocks=16)
