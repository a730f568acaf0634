"""The benchmark's own files of the ``longcat-flash`` configuration: the
configuration file against the catalog's published values, the counts
against the model's parameter tree, the per-layer readers against what
a program with and without the counters leaves, the traffic file against
the lengths ISSUE 27 names, and the runner's weights."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

from perfbench.harness import core, counts_longcat
from perfbench_testlib import ROOT

CELL = "longcat-flash.long_prompt_steady"
#: ``config`` of the catalog row (``architectures.jsonl`` beside the
#: ``model-configs`` guide), as published
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench/configs/longcat-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return core.load_module(
        os.path.join(ROOT, "perfbench/runners/serve_longcat.py"),
        "perfbench_runner_serve_longcat_under_test")


def _rehearsal(config):
    return core.merged(config, config["rehearsal"])


def test_configuration_holds_the_published_values(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == "longcat-flash")
    assert set(entry["reduced"]) == {"num_layers", "n_routed_experts",
                                     "vocab_size",
                                     "max_position_embeddings"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"], config["max_position_embeddings"]) \
        == (4, 16, 16384, 16896)
    # the guide's floors: four layers, 8 experts, an eighth of the rows
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["router_width"] == 768 and config["held_experts"] == [0, 16]
    assert "32 chips" in config["deployment"]
    assert (config["param_dtype"], config["router_dtype"],
            config["activation_dtype"], config["logits_dtype"]) \
        == ("bfloat16", "float32", "bfloat16", "float32")
    assert len(config["assumed"]) >= 8
    assert config["check_sample"] == [[17, 6], [600, 6], [2100, 6]]


def test_engine_arithmetic_of_the_configuration(config, runner):
    from horovod_tpu.serving.generation import kv_cache as kvc

    eng = config["engine"]
    cfg = runner.model_config(config)
    assert (eng["max_seqs"], eng["prefill_chunk"]) == (32, 512)
    assert kvc.block_bytes(cfg, eng["block_size"]) * eng["num_blocks"] \
        == eng["kv_pool_bytes"] >= 1.5e9
    # 16896 positions are whole blocks and hold the longest request
    assert config["max_position_embeddings"] % eng["block_size"] == 0
    assert config["max_position_embeddings"] >= 16384 + 512
    assert eng["prefill_chunk"] % eng["block_size"] == 0
    assert cfg.expands(eng["prefill_chunk"]) and not cfg.expands(2)
    from horovod_tpu.models import LongcatFlash
    shapes = jax.eval_shape(lambda: LongcatFlash(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == eng["weight_bytes"]
    counted = counts_longcat.param_counts(config)
    assert abs(counted["resident"] - sum(a.size for a in leaves)) < 1000
    assert 5.1e9 < counted["resident"] < 5.2e9


def test_counts_follow_the_shapes(config):
    c = counts_longcat.param_counts(config)
    # ISSUE 27's arithmetic: 90.6 M an attention, 226.5 M a dense FFN,
    # 37.7 M an expert, 4.7 M the router
    assert round(c["attention"] / 1e6, 1) == 90.6
    assert round(c["dense_ffn"] / 1e6, 1) == 226.5
    assert round(c["expert"] / 1e6, 2) == 37.75
    assert round(c["router"] / 1e6, 1) == 4.7
    assert counts_longcat.cache_bytes_per_token(config) == 9216
    # attention takes the cheaper form: absorbed for a decode step,
    # expanded for a chunk, and the line is at 171 queries
    h, r, dn, dr, dv = 64, 512, 128, 64, 128
    for q, prefix in ((1, 8192), (512, 8192)):
        pairs = q * prefix + q * (q + 1) / 2
        absorbed = 2 * h * (pairs * (2 * r + dr) + q * r * (dn + dv))
        expanded = 2 * h * (pairs * (dn + dr + dv)
                            + (prefix + q) * r * (dn + dv))
        assert counts_longcat.attention_flops(config, q, prefix) \
            == min(absorbed, expanded)
        assert (absorbed < expanded) == (q == 1)
    # 0.6 TFLOP an attention for a 512-token chunk over a 16 k prefix
    assert 0.55e12 < counts_longcat.attention_flops(config, 512, 16384) \
        < 0.7e12
    floor = counts_longcat.decode_bytes(config, 0, 0)
    assert 5.2e9 < floor < 5.5e9          # the weights outside the experts
    assert counts_longcat.decode_bytes(config, 1000, 2) - floor \
        == 1000 * 9216 + 4 * 2 * c["expert"] * 2
    assert counts_longcat.prefill_chunk_flops(config, 512, 0, 0.25) \
        > 2 * 512 * c["per_token"]


def _reader(name):
    return core.load_module(core.reader_path(name),
                            "reader_under_test_" + re.sub(r"\W", "_", name))


def _ctx(config, before=None, after=None, trace=None, **facts):
    ctx = types.SimpleNamespace(
        config=config, counters_before=before or {},
        counters_after=after or {}, trace=trace, facts=facts, spans={},
        window=(0.0, 10.0),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    ctx.counter_delta = lambda s: core.Context.counter_delta(ctx, s)
    return ctx


NEW_METRICS = ("programs.mla_moe_decode_roofline.itl",
               "programs.mla_moe_prefill_roofline.itl",
               "moe.held_picks_per_token.itl", "moe.zero_pick_share.itl",
               "moe.held_load_max_over_mean.itl")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_no_counters(
        config, spec, name):
    """On the parent commit the traced run of the cell finds no
    ``hvd_tpu_gen_moe_*`` series: the reader returns nothing and does
    not raise, with a trace and without."""
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "itl_p90_ms" and entry["workloads"] == [CELL]
    read = _reader(name).read
    assert read(_ctx(config)) is None
    trace = types.SimpleNamespace(program_ms=lambda pattern: 50.0)
    assert read(_ctx(config, trace=trace, records=[])) is None


def test_the_three_moe_readers_read_the_counters(config):
    before = {"hvd_tpu_gen_moe_tokens_total": 100.0}
    after = {"hvd_tpu_gen_moe_tokens_total": 4100.0,
             'hvd_tpu_gen_moe_picks_total{kind="held"}': 1000.0,
             'hvd_tpu_gen_moe_picks_total{kind="zero"}': 16000.0,
             'hvd_tpu_gen_moe_picks_total{kind="absent"}': 31000.0}
    after.update({
        'hvd_tpu_gen_moe_held_expert_picks_total{expert="%d"}' % e: 50.0
        for e in range(16)})
    after['hvd_tpu_gen_moe_held_expert_picks_total{expert="3"}'] = 250.0
    ctx = _ctx(config, before, after)
    assert _reader("moe.held_picks_per_token.itl").read(ctx) == 0.25
    assert _reader("moe.zero_pick_share.itl").read(ctx) \
        == pytest.approx(100 * 16000 / 48000)
    assert _reader("moe.held_load_max_over_mean.itl").read(ctx) \
        == pytest.approx(250 / (1000 / 16))


def test_the_roofline_readers_divide_need_by_device_time(config):
    """One request of 1300 prompt tokens: chunks at prefixes 0, 512 and
    1024 (the last with 276 live tokens), then decode steps over its
    context; the touched experts come from the counters."""
    rec = types.SimpleNamespace(
        seq_id=7, req=types.SimpleNamespace(prompt=[0] * 1300),
        token_times=[4.0, 5.0, 6.0])
    after = {"hvd_tpu_gen_moe_tokens_total": 4 * 1302.0,
             'hvd_tpu_gen_moe_picks_total{kind="held"}': 1302.0,
             'hvd_tpu_gen_moe_experts_touched_total{phase="prefill"}': 3 * 4 * 16.0,
             'hvd_tpu_gen_moe_experts_touched_total{phase="decode"}': 2 * 4 * 1.0,
             'hvd_tpu_gen_moe_calls_total{phase="prefill"}': 3.0,
             'hvd_tpu_gen_moe_calls_total{phase="decode"}': 2.0}
    trace = types.SimpleNamespace(program_ms=lambda pattern: 50.0)
    ctx = _ctx(config, {}, after, trace=trace, records=[rec])
    ctx.spans["steps"] = [(1.0, "prefill", (7,)), (2.0, "prefill", (7,)),
                          (3.0, "prefill", (7,)), (4.5, "decode", (7,)),
                          (5.5, "decode", (7,))]
    need = counts_longcat.decode_bytes(config, (1301 + 1302) / 2, 1.0)
    assert _reader("programs.mla_moe_decode_roofline.itl").read(ctx) \
        == pytest.approx(100 * need / 819e9 / 0.050)
    seconds = [max(counts_longcat.prefill_chunk_flops(config, q, p, 0.25)
                   / 197e12,
                   counts_longcat.prefill_chunk_bytes(config, q, p, 16.0)
                   / 819e9)
               for q, p in ((512, 0), (512, 512), (276, 1024))]
    got = _reader("programs.mla_moe_prefill_roofline.itl").read(ctx)
    assert got == pytest.approx(100 * sum(seconds) / 3 / 0.050)
    assert 0 < got < 100


def test_traffic_file_holds_the_lengths_the_issue_names():
    with open(os.path.join(ROOT,
                           "perfbench/traffic/long_prompt_steady.json")) as f:
        tr = json.load(f)
    assert tr["kind"] == "open_loop" and tr["eos"] is None
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                   "sigma": 0.8, "min": 512, "max": 16384}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 192,
                                   "sigma": 0.5, "min": 32, "max": 512}
    assert tr["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    pre = tr["preload"]
    assert (pre["prompt_tokens"], pre["retire_from"], pre["retire_to"]) \
        == (2048, 8, 200) and 1 <= pre["count"] <= 32
    # the rate is a multiple of 0.05
    assert abs(tr["rate_per_s"] / 0.05 - round(tr["rate_per_s"] / 0.05)) \
        < 1e-9


def _traffic():
    with open(os.path.join(ROOT,
                           "perfbench/traffic/long_prompt_steady.json")) as f:
        return json.load(f)


# the first seed is the one whose traced run the driver refused: its
# first arrival is due 7.9 s into the window, past the harness's 6 s
@pytest.mark.parametrize("seed, late", [(698089704, True),
                                        (3162277661, False)])
def test_the_profile_reaches_the_first_arrivals_prefill(runner, seed, late):
    from perfbench.harness import traffic

    tr = _traffic()
    ctx = types.SimpleNamespace(seed=seed, seconds=51.0)
    first = traffic.open_loop(tr, 16384, seed, 51.0)[0].due_s
    assert (first > core.TRACE_SECONDS) == late
    got = runner.traced_seconds(ctx, tr, 16384)
    assert got == max(core.TRACE_SECONDS,
                      first + runner.TRACED_AFTER_ARRIVAL_S)
    assert (got > first + 1.0) and (late or got < 2 * core.TRACE_SECONDS)
    # no arrival at all: the harness's span
    ctx.seconds = 1.0
    assert runner.traced_seconds(ctx, tr, 16384) == core.TRACE_SECONDS


@pytest.mark.parametrize("tracing", [False, True])
def test_measure_lends_the_harness_its_span_and_takes_it_back(
        runner, monkeypatch, tracing):
    seen = []
    monkeypatch.setattr(
        runner.SERVE, "measure",
        lambda ctx, server, tr: seen.append(core.TRACE_SECONDS) or {})
    before = core.TRACE_SECONDS
    ctx = types.SimpleNamespace(seed=698089704, seconds=51.0,
                                tracing=tracing, info=lambda **doc: None)
    runner.measure(ctx, types.SimpleNamespace(vocab=16384), _traffic())
    assert (seen[0] > before) == tracing
    assert core.TRACE_SECONDS == before


def test_reference_is_float32_highest_and_free_of_the_model_module():
    with open(os.path.join(ROOT,
                           "perfbench/reference/longcat_flash.py")) as f:
        source = f.read()
    assert 'PRECISION = "highest"' in source
    assert not re.search(r"^\s*(from|import)\s+horovod_tpu", source, re.M)
    assert "bfloat16" not in source.split('"""', 2)[2]


def test_weights_are_held_in_bfloat16_and_no_call_casts_one(config, runner):
    """The served dtypes at the rehearsal's sizes: every weight bfloat16,
    the router and its bias float32, and in the traced forward (a chunk
    through the paged cache) no ``convert_element_type`` takes a
    parameter as its operand."""
    from horovod_tpu.models import LongcatFlash
    from horovod_tpu.models.transformer import PagedCache
    from horovod_tpu.serving.generation import kv_cache as kvc

    small = dict(_rehearsal(config), param_dtype="bfloat16",
                 activation_dtype="bfloat16")
    cfg = runner.model_config(small)
    model = LongcatFlash(cfg)
    params = runner.make_weights(model, 4000000007)
    assert set(params) == {"params"}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        want = jnp.float32 if "router" in name or "correction_bias" in name \
            else jnp.bfloat16
        assert leaf.dtype == want, (name, leaf.dtype)
    pools = kvc.make_pools(cfg, 9, 8)
    cache = PagedCache(pools, jnp.zeros((1, 16), jnp.int32),
                       jnp.zeros((1,), jnp.int32), jnp.asarray([5]))
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: model.apply(p, t, cache=c, mutable=["moe_stats"]))(
            params, jnp.zeros((1, 16), jnp.int32), cache)
    weights = set(jaxpr.jaxpr.invars[:len(flat)])

    def casts(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "convert_element_type" \
                    and eqn.invars[0] in weights:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from casts(sub)

    assert not list(casts(jaxpr.jaxpr))


def test_runner_refuses_a_file_whose_expert_counts_disagree(config, runner):
    with pytest.raises(ValueError, match="disagree"):
        runner.model_config(dict(config, held_experts=[0, 15]))


# -- the check's three numbers ------------------------------------------------

class _PlainStub:
    """A reference that answers ``compare`` from a table: seeded logits
    by shape, and a tally of two layers in which every held expert of
    ``settings`` was picked 50 times."""

    @staticmethod
    def forward(params, tokens, settings, tally=None):
        import numpy as np

        if tally is not None:
            lo, hi = settings["held_experts"]
            picks = np.zeros(settings["router_width"], np.int64)
            picks[lo:hi] = 25
            tally.extend([picks, picks])
        rng = np.random.default_rng(tokens.shape[1])
        return jnp.asarray(rng.normal(size=tokens.shape + (32,)),
                           jnp.float32)


def _served(runner, logprob_noise=0.0, picks=50.0, tokens=None, swap=False):
    """What ``serve_check`` would return had the engine served the
    stub's own argmax tokens, with its log-probabilities off by
    ``logprob_noise`` at every position."""
    import numpy as np

    settings = {"held_experts": [0, 4], "router_width": 12}
    sample = []
    for p_len, n in ((3, 2), (9, 2)):
        row = np.zeros((1, 11), np.int32)
        logits = np.asarray(_PlainStub.forward(None, row, settings))[0]
        toks = [int(logits[p_len - 1 + j].argmax()) for j in range(n)]
        if swap:                # the runner-up: a gap under the best
            toks[0] = int(np.argsort(logits[p_len - 1])[-2])
        sample.append(([0] * p_len, toks, [0.0] * n))
    rows = np.zeros((3, 4 + 5 - 1), np.int32)
    logp = np.asarray(jax.nn.log_softmax(
        _PlainStub.forward(None, rows, settings), axis=-1))[:, 3:]
    toks = logp.argmax(-1)
    batch = [([0] * 4, toks[i].tolist(),
              (logp[i, np.arange(5), toks[i]] + logprob_noise).tolist())
             for i in range(3)]
    counters = {
        'hvd_tpu_gen_moe_held_expert_picks_total{expert="%d"}' % e: picks
        for e in range(4)}
    counters["hvd_tpu_gen_moe_tokens_total"] = float(
        rows.size * 2 if tokens is None else tokens)
    return {"sample": sample, "batch": batch, "counters": counters}, settings


@pytest.mark.parametrize("fault, number", [
    ({}, None),
    ({"swap": True}, "worst_logit_gap"),
    ({"logprob_noise": 1.0}, "logprob_rms"),
    ({"picks": 5.0}, "picks_off"),
    ({"tokens": 7}, "moe_tokens_agree")])
def test_correct_is_held_by_each_of_the_checks_numbers(runner, monkeypatch,
                                                       fault, number):
    """Each limit refuses alone: a served token under the reference's
    best, log-probabilities off in the mean, a held expert's picks far
    from the reference's router, live tokens miscounted."""
    monkeypatch.setattr(runner, "LOGIT_TOL", 0.01)
    served, settings = _served(runner, **fault)
    ok, numbers = runner.compare(served, None, _PlainStub, settings)
    assert ok == (number is None), numbers
    limits = {"worst_logit_gap": 0.01,
              "logprob_rms": runner.LOGPROB_RMS_TOL,
              "picks_off": runner.PICKS_TOL}
    for name, limit in limits.items():
        assert (numbers[name] > limit) == (name == number), (name, numbers)
    assert numbers["moe_tokens_agree"] == (number != "moe_tokens_agree")
    assert numbers["served_positions"] == 15


@pytest.mark.parametrize("fault", [None, "program_router_matmul_bf16",
                                   "program_router_softmax_bf16",
                                   "program_attention_softmax_bf16"])
def test_a_router_or_softmax_below_float32_is_named(config, runner,
                                                    monkeypatch, fault):
    """At the rehearsal's sizes in the served dtypes: the model as it
    is computes its router and every softmax in float32, and each of the
    tolerance tool's program faults is named in both programs."""
    tool = core.load_module(
        os.path.join(ROOT, "perfbench/tools/longcat_tolerance.py"),
        "longcat_tolerance_under_test")
    from horovod_tpu.models import LongcatFlash

    small = dict(_rehearsal(config), param_dtype="bfloat16",
                 activation_dtype="bfloat16")
    model = LongcatFlash(runner.model_config(small))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    if fault:
        monkeypatch.setattr(*tool._program_faults()[fault])
    found = runner.lowered_precisions(model, params, small["engine"])
    assert bool(found) == bool(fault), found
    if fault:
        assert {f.split(":")[0] for f in found} == {"prefill", "decode"}
