"""The GPT-2 block is served from weights held in the dtype its matmuls
take.

:meth:`Transformer.serving_params` casts the attention and MLP weights to
``cfg.dtype`` once, where the engine's :class:`ParamsLifecycle` takes the
params and at every hot reload; the paged forward gathers embedding rows
before casting them. Four pins:

* **the same bits** — the paged forward given the held params returns the
  logits and pools it returns given the float32 masters, bit for bit, and
  an engine's greedy and seeded sampled tokens do not move; the full
  forward (the training branch) is unchanged;
* **intake and reload** — what the lifecycle holds after construction and
  after a reload from a float32 checkpoint, with no program compiled by
  the reload, and ``hvd_tpu_serving_params_bytes`` reading it; a model
  that declares no serving form keeps the very leaves it was given;
* **the form** — what is cast and what is not, and the sharding kept;
* **the program text** — the paged programs given the held params convert
  no whole weight and no embedding table from float32.
"""

import re

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import checkpointing
from horovod_tpu import metrics as M
from horovod_tpu.models.transformer import (PagedCache, Transformer,
                                            TransformerConfig)
from horovod_tpu.serving.generation import GenerationEngine
from horovod_tpu.serving.generation import kv_cache as kvc
from horovod_tpu.serving.generation.scheduler import DECODE_WIDTH

#: bfloat16 matmuls over float32 masters, as gpt2-xl runs; the vocabulary
#: and the position table differ in length so each table has its own shape
CFG = TransformerConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=64,
                        dtype=jnp.bfloat16)
NUM_BLOCKS, BLOCK_SIZE, MAX_BLOCKS, CHUNK = 17, 4, 6, 8
WEIGHTS = {"wq", "wk", "wv", "wo", "wi"}
SAMPLED = dict(temperature=0.9, top_k=12, top_p=0.85)


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 4), jnp.int32)))
    return model, params


def _named_leaves(tree):
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _is_weight(name):
    return re.search(r"\['(\w+)'\]$", name).group(1) in WEIGHTS


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


# -- the form ------------------------------------------------------------------

def test_the_form_casts_the_matmul_weights_and_keeps_the_rest(model_params):
    model, params = model_params
    held = model.serving_params(params)
    pairs = list(zip(_named_leaves(params), jax.tree_util.tree_leaves(held)))
    assert sum(_is_weight(name) for (name, _), _ in pairs) \
        == 6 * CFG.num_layers
    for (name, master), leaf in pairs:
        if _is_weight(name):
            assert leaf.dtype == jnp.bfloat16, name
            assert (_bits(leaf) == _bits(master.astype(jnp.bfloat16))).all()
        else:   # LayerNorms, embedding, pos_embedding: the same objects
            assert leaf is master, name
    # boxed params (model.init as it comes) take the same form
    boxed = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    assert jax.tree_util.tree_structure(model.serving_params(boxed)) \
        == jax.tree_util.tree_structure(boxed)
    # a float32 model holds what it is given
    f32 = Transformer(TransformerConfig(vocab_size=61, num_layers=2,
                                        d_model=32, num_heads=2,
                                        head_dim=16, dtype=jnp.float32))
    assert f32.serving_params(params) is params
    # held params come back as they are
    again = model.serving_params(held)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(again),
                                       jax.tree_util.tree_leaves(held)))


def test_the_form_keeps_each_weights_sharding(model_params):
    model, params = model_params
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("a", "b"))

    def place(x):
        spec = P(None, "b") if x.ndim >= 2 and x.shape[1] % 4 == 0 else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    placed = jax.tree_util.tree_map(place, params)
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(model.serving_params(placed))):
        assert b.sharding == a.sharding


# -- the same bits -------------------------------------------------------------

def _paged_run(model, params, prompt, steps):
    """A prefill chunk and ``steps`` decode steps through the raw paged
    forward: (logits of every call, the pools at the end)."""
    program = kvc.build_program(model)
    pools = kvc.make_pools(CFG, NUM_BLOCKS, BLOCK_SIZE)
    # the whole cache is donated: a fresh table a call
    table = lambda: jnp.asarray([[3, 5, 1, 7, 0, 0]], jnp.int32)  # noqa: E731
    tokens = np.zeros((1, CHUNK), np.int32)
    tokens[0, :len(prompt)] = prompt
    logits, cache = program(
        params, PagedCache(pools, table(), jnp.asarray([0], jnp.int32),
                           jnp.asarray([len(prompt)], jnp.int32)),
        jnp.asarray(tokens))
    out = [logits]
    length, token = len(prompt), int(np.argmax(logits[0, len(prompt) - 1]))
    for _ in range(steps):
        step = np.zeros((1, DECODE_WIDTH), np.int32)
        step[0, 0] = token
        logits, cache = program(
            params, PagedCache(cache.pools, table(),
                               jnp.asarray([length], jnp.int32),
                               jnp.asarray([1], jnp.int32)),
            jnp.asarray(step))
        out.append(logits)
        length, token = length + 1, int(np.argmax(logits[0, 0]))
    return out, cache.pools


def test_paged_logits_and_pools_are_bitwise_those_of_the_masters(
        model_params):
    model, params = model_params
    prompt = np.random.default_rng(3).integers(0, CFG.vocab_size, 7)
    want, want_pools = _paged_run(model, params, prompt, 4)
    got, got_pools = _paged_run(model, model.serving_params(params),
                                prompt, 4)
    for a, b in zip(want + list(want_pools), got + list(got_pools)):
        assert a.dtype == b.dtype
        assert (_bits(a) == _bits(b)).all()


@pytest.mark.parametrize("sampling", [{}, SAMPLED], ids=["greedy", "seeded"])
def test_engine_tokens_do_not_move(model_params, monkeypatch, sampling):
    """The same requests through an engine that holds the serving form
    and through one that serves the float32 masters, as an engine did
    before the form existed."""
    model, params = model_params
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (5, 13, 20)]

    def serve():
        with GenerationEngine(model, params=params, block_size=BLOCK_SIZE,
                              num_blocks=33, max_seqs=4,
                              prefill_chunk=CHUNK, deadline_ms=0) as eng:
            seqs = [eng.submit(p, max_tokens=6, seed=40 + i, **sampling)
                    for i, p in enumerate(prompts)]
            return [(eng.result(s, timeout=120), list(s.logprobs))
                    for s in seqs]

    held = serve()
    monkeypatch.delattr(Transformer, "serving_params")
    assert held == serve()


def test_the_full_forward_is_unchanged(model_params):
    """The training branch casts as it did; given the held params (the
    draft proposer's and the smoke check's reference) it returns the
    masters' logits bit for bit."""
    model, params = model_params
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG.vocab_size, (2, 12)), jnp.int32)
    want = model.apply(params, tokens)
    got = model.apply(model.serving_params(params), tokens)
    assert (_bits(want) == _bits(got)).all()


# -- intake and reload ---------------------------------------------------------

def _held_dtypes(tree):
    return {name: str(leaf.dtype) for name, leaf in _named_leaves(tree)}


def _expected_dtypes(params):
    return {name: "bfloat16" if _is_weight(name) else "float32"
            for name, _ in _named_leaves(params)}


def _bytes_by_dtype(tree):
    held = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        held[str(leaf.dtype)] = held.get(str(leaf.dtype), 0) + leaf.nbytes
    return held


def _gauge(plane, dtype):
    return M.snapshot()[
        f'hvd_tpu_serving_params_bytes{{plane="{plane}",dtype="{dtype}"}}']


def test_intake_and_reload_hold_the_form_and_compile_nothing(
        model_params, tmp_path):
    model, params = model_params
    checkpointing.save(str(tmp_path), 1, params)
    prompt = list(range(3, 14))
    with GenerationEngine(model, checkpoint_dir=str(tmp_path),
                          block_size=BLOCK_SIZE, num_blocks=33, max_seqs=4,
                          prefill_chunk=CHUNK, deadline_ms=0,
                          reload_poll_seconds=0) as eng:
        assert _held_dtypes(eng.params) == _expected_dtypes(params)
        first = eng.generate(prompt, max_tokens=4, timeout=120)
        held = _bytes_by_dtype(eng.params)
        assert held == {"bfloat16": 2 * 12 * CFG.d_model ** 2 * 2,
                        "float32": held["float32"]}
        for dtype, nbytes in held.items():
            assert _gauge("generation", dtype) == nbytes

        # a newer float32 step with other values
        checkpointing.save(str(tmp_path), 2, jax.tree_util.tree_map(
            lambda a: a * 1.5, params))
        before = M.snapshot()["hvd_tpu_compile_total"]
        assert eng.reload() is True
        second = eng.generate(prompt, max_tokens=4, timeout=120)
        assert M.snapshot()["hvd_tpu_compile_total"] == before
        assert _held_dtypes(eng.params) == _expected_dtypes(params)
        for dtype, nbytes in _bytes_by_dtype(eng.params).items():
            assert _gauge("generation", dtype) == nbytes
        # the swap took: the step's values are served
        assert np.asarray(eng.params["params"]["layer_0"]["attn"]["wq"]) \
            .astype(np.float32).tolist() == np.asarray(
                (params["params"]["layer_0"]["attn"]["wq"] * 1.5)
                .astype(jnp.bfloat16)).astype(np.float32).tolist()
    assert len(first) == len(second) == 4


def _longcat():
    from horovod_tpu.models.longcat_flash import (LongcatFlash,
                                                  LongcatFlashConfig)
    return LongcatFlash(LongcatFlashConfig(
        vocab_size=97, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_routed_experts=8, zero_expert_num=4,
        moe_topk=3, max_position_embeddings=64, held_experts=(2, 6)))


def _olmo():
    from horovod_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
    return OlmoHybrid(OlmoHybridConfig(
        vocab_size=211, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        table_positions=128))


@pytest.mark.parametrize("make", [_longcat, _olmo],
                         ids=["longcat_flash", "olmo_hybrid"])
def test_a_model_without_a_form_holds_the_leaves_it_was_given(make):
    model = make()
    assert getattr(model, "serving_params", None) is None
    params = jax.jit(model.init)(jax.random.PRNGKey(1),
                                 jnp.zeros((1, 4), jnp.int32))
    eng = GenerationEngine(model, params=params, block_size=8,
                           num_blocks=32, max_seqs=2, prefill_chunk=16,
                           deadline_ms=0, state_snapshots=2)
    try:
        assert eng.params is params
        for dtype, nbytes in _bytes_by_dtype(params).items():
            assert _gauge("generation", dtype) == nbytes
    finally:
        eng.close()


# -- the program text ------------------------------------------------------------

#: a convert from float32 to bfloat16 in StableHLO, and its operand's shape
_CONVERT = re.compile(r"stablehlo\.convert\s+%[\w#]+\s*:\s*"
                      r"\(tensor<([\dx]+)xf32>\)\s*->\s*tensor<[\dx]+xbf16>")


def _whole_converts(text, params):
    """The operand shapes of the float32 -> bfloat16 converts in
    ``text`` that take a whole weight or a whole embedding table."""
    whole = {"x".join(map(str, leaf.shape)) for name, leaf in
             _named_leaves(params)
             if _is_weight(name) or "embedding" in name}
    return sorted(shape for shape in _CONVERT.findall(text) if shape in whole)


def _lowered(model, params, phase):
    pools = kvc.make_pools(CFG, NUM_BLOCKS, BLOCK_SIZE)
    lanes = 4
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    sample = kvc.SampleParams(
        temperature=jnp.zeros((lanes,), jnp.float32), top_k=i32(lanes),
        top_p=jnp.ones((lanes,), jnp.float32),
        key=jnp.zeros((lanes, 2), jnp.uint32), emitted=i32(lanes))
    if phase == "decode":
        state = kvc.DecodeState(
            tokens=i32(lanes), lengths=i32(lanes), live=i32(lanes),
            remaining=i32(lanes), eos=i32(lanes), sample=sample)
        return kvc.build_decode_program(model, DECODE_WIDTH).lower(
            params, pools, i32(lanes, MAX_BLOCKS), state).as_text()
    one = jax.tree_util.tree_map(lambda a: a[:1], sample)
    return kvc.build_prefill_program(model).lower(
        params, PagedCache(pools, i32(1, MAX_BLOCKS), i32(1), i32(1)),
        i32(1, CHUNK), one).as_text()


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_the_paged_programs_convert_no_whole_weight(model_params, phase):
    model, params = model_params
    assert _whole_converts(_lowered(model, model.serving_params(params),
                                    phase), params) == []
    # given the masters, the same check finds the weights' converts (and
    # still no table's: the rows are gathered before they are cast)
    found = _whole_converts(_lowered(model, params, phase), params)
    d = CFG.d_model
    assert set(found) == {f"{d}x{CFG.num_heads}x{CFG.head_dim}",
                          f"{CFG.num_heads}x{CFG.head_dim}x{d}",
                          f"{d}x{4 * d}", f"{4 * d}x{d}"}


def test_the_draft_proposer_holds_its_models_form(model_params):
    """The draft model's lifecycle applies its form too, under a plane of
    its own, and drafts what the masters draft."""
    from horovod_tpu.serving.generation.spec import DraftModelProposer
    model, params = model_params
    proposer = DraftModelProposer(model, params=params)
    try:
        assert _held_dtypes(proposer.params) == _expected_dtypes(params)
        for dtype, nbytes in _bytes_by_dtype(proposer.params).items():
            assert _gauge("draft", dtype) == nbytes
        context = [5, 9, 2, 33, 17, 4]
        want = []
        for _ in range(3):
            logits = model.apply(params, jnp.asarray([context + want]))
            want.append(int(np.argmax(logits[0, -1])))
        assert proposer.propose(context, 3) == want
    finally:
        proposer.close()
