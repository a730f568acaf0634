"""The benchmark's own files of the ``command-a-plus`` configuration: the
configuration file against the catalog's published values, the counts on
hand-worked sizes and against the model's parameter tree, the per-layer
readers against what a program with and without the counters leaves, the
table of lengths against the traffic ISSUE 33 names, the runner's
comparison, and the traced rehearsal of the cell."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import core
from perfbench.harness import counts_command_a_plus as counts
from perfbench_testlib import RESULT_KEYS, ROOT, last_line, run_cell

CELL = "command-a-plus.mixed_lengths"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
#: ``config`` of the catalog row (``architectures.jsonl`` beside the
#: ``model-configs`` guide), as published
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
NEW_METRICS = ("programs.swa_moe_decode_roofline.served",
               "programs.swa_moe_prefill_roofline.served",
               "ops.paged_attention_roofline.served",
               "kv_cache.window_held_over_full.served",
               "kv_cache.window_pool_in_use_peak_share.served",
               "moe.held_picks_per_token.served",
               "moe.held_load_max_over_mean.served")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT,
                           "perfbench/configs/command-a-plus.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic_file():
    with open(os.path.join(ROOT,
                           "perfbench/traffic/mixed_lengths.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return core.load_module(
        os.path.join(ROOT, "perfbench/runners/serve_command_a_plus.py"),
        "perfbench_runner_serve_command_a_plus_under_test")


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


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_published_values(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == "command-a-plus")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/"
        "config.json")
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # the cut: one whole period, 16 of 128 experts, an eighth of the table
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 32768)
    assert config["layer_types"][:4] == PERIOD
    assert config["held_experts"] == [0, 16]
    assert "8 chips share each layer" in config["deployment"]
    assert "8 pipeline stages" in config["deployment"]
    assert (config["param_dtype"], config["activation_dtype"],
            config["router_dtype"], config["norm_dtype"],
            config["softmax_dtype"]) == ("bfloat16", "bfloat16", "float32",
                                         "float32", "float32")
    assumed = " ".join(config["assumed"])
    for said in ("intermediate_size", "added to the routed sum",
                 "counts the token itself", "no selection bias",
                 "nothing scales the embedding", "interleaved pairs",
                 "normal(0.02)"):
        assert said in assumed, said
    assert config["check_sample"] == [[17, 6], [600, 6], [5000, 6],
                                      [20000, 6], [4090, 16]]
    assert (config["runner"], config["reference"]) \
        == ("serve_command_a_plus", "command_a_plus")


def test_engine_arithmetic_of_the_configuration(config, runner):
    from horovod_tpu.models import CommandAPlus
    from horovod_tpu.serving.generation import kv_cache as kvc

    eng = config["engine"]
    cfg = runner.model_config(config)
    assert (eng["max_seqs"], eng["block_size"], eng["num_blocks"],
            eng["prefill_chunk"], eng["table_positions"]) \
        == (16, 64, 8192, 512, 33792)
    assert cfg.max_seq_len == 33792 and cfg.max_position_embeddings == 200000
    assert cfg.num_experts == 128 and cfg.held_experts == (0, 16)
    # ISSUE 33's arithmetic: 4 KB a token on the full plane, 12 KB on the
    # three window planes; 2.15 GB and 0.93 GB of pools
    assert kvc.block_bytes(cfg, 64, group=0) == 64 * 4096
    assert kvc.block_bytes(cfg, 64, group=1) == 64 * 3 * 4096
    assert kvc.block_bytes(cfg, 64, group=0) * 8192 \
        == eng["kv_pool_bytes"]["full"] == 2147483648
    lane = 4096 // 64 + 512 // 64 + 2
    assert kvc.block_bytes(cfg, 64, group=1) * (16 * lane + 1) \
        == eng["kv_pool_bytes"]["window"] == 931921920
    assert 32768 + 384 <= eng["table_positions"]
    shapes = jax.eval_shape(lambda: CommandAPlus(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    leaves = jax.tree_util.tree_leaves(shapes)
    counted = counts.param_counts(config)
    assert counted["resident"] == sum(a.size for a in leaves)
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == eng["weight_bytes"]
    # the router and the norms' weights are float32, all else bfloat16
    assert sum(a.size for a in leaves if a.dtype == jnp.float32) \
        == 4 * (4096 * 128 + 4096) + 4096


def test_counts_follow_the_shapes(config):
    c = counts.param_counts(config)
    # ISSUE 33: attention 142.6 M, shared 201.3 M, router 0.5 M, sixteen
    # experts of 50.33 M, 1149.7 M a layer, 4.733 G on the chip
    assert round(c["attention"] / 1e6, 1) == 142.6
    assert round(c["shared"] / 1e6, 1) == 201.3
    assert c["router"] == 4096 * 128
    assert round(c["expert"] / 1e6, 2) == 50.33
    assert round(c["layer"] / 1e6, 1) == 1149.8          # + the norm
    assert round(c["resident"] / 1e9, 3) == 4.733
    assert counts.planes(config) == {"full": 1, "window": 3}
    assert counts.kv_bytes_per_token_plane(config) == 4096
    assert counts.block_bytes(config, "full") == 262144
    assert counts.block_bytes(config, "window") == 786432
    assert counts.uniform_held_picks(config) == 1.0
    # a decode step over one lane 20 000 deep and one 1000 deep with ten
    # experts touched a layer
    floor = counts.decode_bytes(config, [], 0)
    assert floor == c["outside_experts"] * 2
    assert counts.decode_bytes(config, [20000, 1000], 10) - floor \
        == 4 * 10 * c["expert"] * 2 + 4096 * 21000 \
        + 3 * 4096 * (4096 + 1000)


def test_counts_on_a_hand_worked_size():
    """One sliding and one full layer at widths small enough to count by
    hand: hidden 8, 4 query heads over 2 key-value heads of 2, experts
    of width 4 (2 held of 8, top 2), 1 shared, a window of 3."""
    tiny = {"hidden_size": 8, "intermediate_size": 4, "vocab_size": 10,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 2, "sliding_window": 3,
            "layer_types": ["sliding_attention", "full_attention"],
            "num_experts": 2, "num_experts_per_tok": 2,
            "num_shared_experts": 1, "published": {"num_experts": 8},
            "engine": {"block_size": 4}}
    c = counts.param_counts(tiny)
    assert c["attention"] == 8 * 8 + 2 * 8 * 4 + 8 * 8
    assert c["shared"] == 3 * 8 * 4 and c["expert"] == 3 * 8 * 4
    assert c["router"] == 8 * 8
    assert c["layer"] == c["attention"] + c["shared"] + c["router"] \
        + 2 * c["expert"] + 8
    assert c["resident"] == 2 * c["layer"] + 80 + 8
    assert c["outside_experts"] == c["resident"] - 2 * 2 * c["expert"]
    assert counts.kv_bytes_per_token_plane(tiny) == 2 * 4 * 2
    assert counts.block_bytes(tiny, "window") == 4 * 16
    assert counts.uniform_held_picks(tiny) == 0.5
    # 3 queries after 5 cached tokens: 6 + 7 + 8 pairs, 3 + 3 + 3 in a
    # window of 3; after none: 1 + 2 + 3 and 1 + 2 + 3
    assert counts.attention_pairs(3, 5) == 21
    assert counts.attention_pairs(3, 5, 3) == 9
    assert counts.attention_pairs(3, 0, 3) == 6
    assert counts.attention_flops(tiny, 3, 5) == 2 * 2 * 21 * 8
    per_token = 2 * (c["attention"] + c["shared"] + c["router"]
                     + 0.5 * c["expert"])
    assert counts.prefill_chunk_flops(tiny, 3, 5) \
        == 2 * 3 * per_token + 2 * 2 * (21 + 9) * 8 + 2 * 80
    assert counts.prefill_chunk_flops(tiny, 3, 5, held_picks=2.0) \
        - counts.prefill_chunk_flops(tiny, 3, 5) \
        == 2 * 3 * 2 * 1.5 * c["expert"]
    assert counts.prefill_chunk_bytes(tiny, 3, 5) \
        == c["resident"] * 2 + 16 * 8 + 16 * min(8, 3 + 3)
    # two lanes 2 and 7 deep, one expert touched a layer
    assert counts.decode_bytes(tiny, [2, 7], 1.0) \
        == c["outside_experts"] * 2 + 2 * c["expert"] * 2 \
        + 16 * 9 + 16 * (2 + 3)


def test_a_chunk_at_the_published_widths_is_bound_by_compute(config):
    """ISSUE 33: a 512-token chunk 20 000 tokens deep costs 4096 keys on
    three planes and the whole context on one; its operations take
    longer than its bytes, and the old block-diagonal layout would have
    left the kernel's bytes' bound where the grouped one is at 32."""
    flops = counts.prefill_chunk_flops(config, 512, 20000)
    bytes_ = counts.prefill_chunk_bytes(config, 512, 20000)
    assert flops / 197e12 > bytes_ / 819e9
    assert 0.012 < flops / 197e12 < 0.04
    rows_old, rows_new, row_bytes = 2 * 128, 2 * 16, 2 * 1024 * 2
    assert 2 * 2 * rows_old * 1024 / row_bytes == 256    # FLOP a byte
    assert 2 * 2 * rows_new * 8 * 128 / row_bytes == 32


# -- the readers --------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_nothing(
        config, spec, name):
    """On the parent commit a traced run finds no group counters, no
    routing counts and none of this runner's facts: the reader returns
    nothing and does not raise, with a trace and without."""
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "served_tokens_per_s"
    assert entry["workloads"] == [CELL]
    read = _reader(name).read
    assert read(_ctx(config)) is None
    trace = types.SimpleNamespace(
        program_ms=lambda pattern: None, program_count=lambda pattern: 0,
        planes=[], _line=lambda plane, line: [])
    assert read(_ctx(config, trace=trace, records=[])) is None


def test_the_new_entries_are_appended_and_the_cell_is_listed(spec):
    # after everything the benchmark had, in one run, in this order (a
    # later PR appends after them: nothing here pins the lists' ends)
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 7] == list(NEW_METRICS)
    assert at > names.index("state_cache.copy_ms_per_iter.served")
    configs = [c["name"] for c in spec["configs"]]
    assert configs.index("command-a-plus") > configs.index("olmo-hybrid")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert [w["name"] for w in spec["workloads"]].index(CELL) == 6
    assert cell == {"name": CELL, "config": "command-a-plus",
                    "traffic": "mixed_lengths", "chips": 1,
                    "why": cell["why"]}
    assert "16 callers" in cell["why"] and "8-32k" in cell["why"]
    assert len(cell["why"]) <= 200
    served = next(m for m in spec["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert served["workloads"][:3] == [
        "gpt2-xl.doc_backlog", "olmo-hybrid.session_turns", CELL]
    assert served["bound"] == 0.03
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"programs.decode_step_ms.served",
            "programs.prefill_chunk_ms.served",
            "programs.paged_blocks_read_share.served",
            "kv_cache.pool_in_use_peak_share.served",
            "kv_cache.preemptions.served", "device.idle_share.served",
            "scheduler.host_ms_per_iter.served"} <= listed
    assert not any(name.endswith(".itl") for name in listed)
    assert not any(name.startswith(("state_cache.", "programs.hybrid_"))
                   for name in listed)
    # a quarter of the cells, rounded down, may ask for four chips
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


def test_the_cache_readers_read_the_runners_facts(config):
    ctx = _ctx(config, group_blocks_held=[(100, 50), (200, 40), (0, 0)],
               window_pool_blocks=1184)
    assert _reader("kv_cache.window_held_over_full.served").read(ctx) \
        == pytest.approx(100 * (0.5 + 0.2) / 2)
    assert _reader("kv_cache.window_pool_in_use_peak_share.served").read(
        ctx) == pytest.approx(100 * 50 / 1184)
    before = {"hvd_tpu_gen_moe_tokens_total": 100.0,
              'hvd_tpu_gen_moe_picks_total{kind="held"}': 50.0}
    after = {"hvd_tpu_gen_moe_tokens_total": 1100.0,
             'hvd_tpu_gen_moe_picks_total{kind="held"}': 1050.0,
             'hvd_tpu_gen_moe_held_expert_picks_total{expert="3"}': 30.0,
             'hvd_tpu_gen_moe_held_expert_picks_total{expert="9"}': 10.0}
    ctx = _ctx(config, before, after)
    assert _reader("moe.held_picks_per_token.served").read(ctx) == 1.0
    assert _reader("moe.held_load_max_over_mean.served").read(ctx) \
        == pytest.approx(30 / (40 / 16))


def test_the_roofline_readers_divide_need_by_device_time(config):
    """Two lanes decoding 1301 and 20 002 tokens deep with ten experts
    touched a layer, three chunks, and a kernel that read 1000 blocks of
    each group over 50 dispatches."""
    records = [types.SimpleNamespace(
        seq_id=i, req=types.SimpleNamespace(prompt=[0] * n),
        token_times=[4.0]) for i, n in ((7, 1300), (8, 20001))]
    ops = [{"name": "%paged_attention.3 = bf16[16,8,32,128]", "dur_ns": 250e3},
           {"name": "%fusion.1 = bf16[32,4096]", "dur_ns": 900e3}] * 8
    trace = types.SimpleNamespace(
        program_ms=lambda pattern: 12.0, program_count=lambda pattern: 2,
        planes=["/device:TPU:0"], _line=lambda plane, line: ops)
    hist = lambda n: {"sum": 1.0, "count": n}  # noqa: E731
    group = 'hvd_tpu_gen_paged_attn_group_blocks_total{kind="read",group="%s"}'
    before = {'hvd_tpu_gen_phase_seconds{phase="decode.dispatch"}': hist(10)}
    after = {'hvd_tpu_gen_phase_seconds{phase="decode.dispatch"}': hist(60),
             'hvd_tpu_gen_moe_experts_touched_total{phase="decode"}': 2000.0,
             'hvd_tpu_gen_moe_calls_total{phase="decode"}': 50.0,
             "hvd_tpu_gen_moe_tokens_total": 1000.0,
             'hvd_tpu_gen_moe_picks_total{kind="held"}': 1500.0,
             group % "full": 1000.0, group % "window": 1000.0}
    ctx = _ctx(config, before, after, trace=trace, records=records,
               prefill_chunks=[(0, 512), (20480, 512), (1536, 100)])
    ctx.spans["steps"] = [(3.0, "prefill", (7,)), (4.5, "decode", (7, 8)),
                          (5.5, "decode", (7, 8)), (12.0, "decode", (7,))]
    need = counts.decode_bytes(config, [1301, 20002], 2000 / 50 / 4)
    got = _reader("programs.swa_moe_decode_roofline.served").read(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.012)
    assert 0 < got < 100
    seconds = [max(counts.prefill_chunk_flops(config, q, p, 1.5) / 197e12,
                   counts.prefill_chunk_bytes(config, q, p) / 819e9)
               for p, q in ((0, 512), (20480, 512), (1536, 100))]
    got = _reader("programs.swa_moe_prefill_roofline.served").read(ctx)
    assert got == pytest.approx(100 * sum(seconds) / 3 / 0.012)
    # 1000 blocks of 256 KB and 1000 of 768 KB over 50 dispatches, against
    # 8 kernel calls of 250 us over 2 decode programs
    got = _reader("ops.paged_attention_roofline.served").read(ctx)
    assert got == pytest.approx(
        100 * (1000 * 262144 + 1000 * 786432) / 50 / 819e9 / 1e-3)
    assert 0 < got < 100


# -- the traffic --------------------------------------------------------------

def test_traffic_file_holds_what_the_issue_names(traffic_file):
    tr = traffic_file
    assert (tr["kind"], tr["clients"], tr["requests"], tr["long_every"],
            tr["start_after"], tr["start_stagger_ms"]) \
        == ("closed_loop", 16, 96, 3, 12, 100)
    assert tr["long_prompt_tokens"] == {"dist": "loguniform", "min": 8192,
                                        "max": 32768}
    assert tr["short_prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256,
        "max": 4096}
    assert tr["output_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert tr["deadline_ms"] == 120000 and tr["sampling"] is None \
        and tr["eos"] is None


def test_the_lengths_are_one_fixed_stratified_table(runner, traffic_file):
    tr = traffic_file
    table = runner.length_table(tr)
    assert table == runner.length_table(dict(tr))        # no seed in it
    assert table != runner.length_table(dict(tr, dealing_seed=34))
    assert len(table) == 96
    long_ = [p for i, (p, _) in enumerate(table) if i % 3 == 0]
    short = [p for i, (p, _) in enumerate(table) if i % 3]
    assert len(long_) == 32 and 8192 <= min(long_) and max(long_) <= 32768
    assert len(short) == 64 and 256 <= min(short) and max(short) <= 4096
    # log-uniform: as many long prompts under 16 384 as over
    assert sum(p < 16384 for p in long_) == 16
    assert 900 < sorted(short)[32] < 1150                # the median
    assert all(128 <= r <= 384 for _, r in table)
    # every round of four long prompts spans the range
    for k in range(0, 32, 4):
        assert min(long_[k:k + 4]) < 12000 and max(long_[k:k + 4]) > 23000
    # 85 % of the credited tokens are a long prompt's (ISSUE 33)
    credited = sum(p + r for p, r in table)
    assert 0.82 < sum(long_) / credited < 0.88
    # the sixteen longest requests together fit the full group's pool
    worst = sorted((p + r for p, r in table), reverse=True)[:16]
    assert sum(-(-n // 64) for n in worst) < 8191
    assert max(worst) <= 33792


def test_callers_walk_the_table_in_arrival_order(runner, traffic_file):
    tr = dict(traffic_file, start_stagger_ms=0)
    table = runner.length_table(tr)
    walks = runner._MixedTraffic.closed_loop(tr, 32768, 5)
    assert len(walks) == 16
    # whichever caller asks next gets the table's next entry
    order = [3, 0, 0, 7, 15, 3, 1]
    reqs = [next(walks[c]) for c in order]
    assert [(len(r.prompt), r.max_tokens) for r in reqs] == table[:7]
    assert [r.caller for r in reqs] == order
    assert [r.ordinal for r in reqs] == [0, 0, 1, 0, 0, 1, 0]
    assert all(r.sampling is None and r.deadline_ms == 120000
               and 0 <= min(r.prompt) and max(r.prompt) < 32768
               for r in reqs)
    # ids are fresh for every request and follow the seed
    assert reqs[1].prompt[:64] != reqs[2].prompt[:64]
    again = next(runner._MixedTraffic.closed_loop(tr, 32768, 5)[3])
    other = next(runner._MixedTraffic.closed_loop(tr, 32768, 6)[3])
    assert again.prompt == reqs[0].prompt
    assert other.prompt[:64] != again.prompt[:64] \
        and len(other.prompt) == len(again.prompt)
    # the table is walked cyclically
    cursor = runner._Cursor(table)
    assert [cursor.take() for _ in range(97)][-1] == table[0]


# -- the comparison and the reference -----------------------------------------

def test_reference_is_float32_highest_and_free_of_the_program():
    with open(os.path.join(ROOT,
                           "perfbench/reference/command_a_plus.py")) as f:
        source = f.read()
    assert 'PRECISION = "highest"' in source
    assert not re.search(r"^\s*(from|import)\s+horovod_tpu", source, re.M)
    body = source.split('"""', 2)[2]
    assert "pallas" not in body and "block_tables" not in body
    # bfloat16 appears only where a fault asks for it
    for line in body.splitlines():
        if "bfloat16" in line:
            assert "bf16" in body[max(0, body.index(line) - 200):
                                  body.index(line)], line


@pytest.mark.parametrize("number,value", [
    ("worst", 10.0), ("rms", 10.0), ("long_median", 10.0)])
def test_correct_is_held_by_each_of_the_checks_numbers(runner, config,
                                                       number, value):
    """``compare`` on served tokens that are the reference's own best,
    then with one number off."""
    vocab = 50
    settings = dict(config, sliding_window=20,
                    engine=dict(config["engine"], block_size=8))

    class Plain:
        @staticmethod
        def forward(params, tokens, settings, at=None, faults=()):
            # the best token at a position is (position + 1) mod vocab
            at = np.asarray(at)
            logits = np.zeros((1, len(at), vocab), np.float32)
            logits[0, np.arange(len(at)), (at + 1) % vocab] = 5.0
            if number == "worst":
                logits[0, :, 7] = 5.0 + value
            return jnp.asarray(logits)

    best = lambda p, n: [(len(p) + j) % vocab for j in range(n)]  # noqa: E731
    logp = float(jax.nn.log_softmax(
        jnp.asarray([5.0] + [0.0] * (vocab - 1)))[0])
    request = lambda n_p, n, off=0.0: (  # noqa: E731
        [0] * n_p, best([0] * n_p, n), [logp + off] * n)
    served = {"sample": [request(17, 6), request(28, 6),
                         request(36, 6, value if number == "long_median"
                                 else 0.0)],
              "batch": [request(9, 7, value if number == "rms" else 0.0)
                        for _ in range(3)]}
    ok, numbers = runner.compare(served, {}, Plain, settings)
    assert not ok
    assert numbers["served_positions"] == 18 + 21
    clean, _ = runner.compare(
        {"sample": [request(17, 6), request(36, 6)],
         "batch": [request(9, 7)]}, {}, type("P", (), {
             "forward": staticmethod(
                 lambda params, tokens, settings, at=None, faults=():
                 Plain.forward(params, tokens, settings, at)
                 if number != "worst" else jnp.asarray(
                     np.eye(vocab, dtype=np.float32)[
                         (np.asarray(at) + 1) % vocab][None] * 5.0))}),
        settings)
    assert clean
    if number == "long_median":
        # only the request deeper than a window and a block counts
        assert numbers["long_logprob_median"] == pytest.approx(value)
        assert numbers["long_offsets"] == [value] * 6
        assert numbers["logprob_rms"] < 1e-6
        assert numbers["by_request"][2][:1] == [36]
    if number == "rms":
        assert numbers["long_logprob_median"] < 1e-6


def test_lowered_precisions_hold_the_router_the_norms_and_the_softmax(
        runner, config):
    """The audit of the traced forward: clean at the rehearsal's sizes,
    and a router whose matmul takes bfloat16 is named."""
    from horovod_tpu.models import CommandAPlus
    from horovod_tpu.models import command_a_plus as cap

    small = core.merged(config, config["rehearsal"])
    model = CommandAPlus(runner.model_config(
        small, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert runner.lowered_precisions(model, params, small["engine"]) == []
    was = cap.route_sigmoid_topk

    def in_bfloat16(logits, k):
        picked, idx = jax.lax.top_k(
            jax.nn.sigmoid(logits.astype(jnp.bfloat16)), k)
        return idx.astype(jnp.int32), (
            picked / jnp.sum(picked, axis=-1, keepdims=True)
        ).astype(jnp.float32)

    cap.route_sigmoid_topk = in_bfloat16
    try:
        found = runner.lowered_precisions(model, params, small["engine"])
    finally:
        cap.route_sigmoid_topk = was
    assert found and all("top_k" in f or "logistic" in f for f in found)


# -- the cell -----------------------------------------------------------------

def test_the_cell_rehearses_with_its_new_metrics(spec):
    """The whole command on the CPU at the rehearsal's sizes, traced:
    the check passes, both plane groups end empty, window blocks were
    held and released, and the line carries the new counters' metrics
    (device metrics need a device trace and stay out)."""
    proc = run_cell(ROOT, "--workload", CELL, "--rehearse", "--seconds",
                    "3", "--seed", "3000000019", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert RESULT_KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for name in ("kv_cache.window_held_over_full.served",
                 "kv_cache.window_pool_in_use_peak_share.served",
                 "moe.held_picks_per_token.served",
                 "moe.held_load_max_over_mean.served",
                 "programs.paged_blocks_read_share.served",
                 "kv_cache.pool_in_use_peak_share.served"):
        assert name in line["metrics"], name
    infos = [json.loads(x)["info"] for x in proc.stdout.splitlines()
             if x.startswith('{"info"')]
    check = next(i for i in infos if "check" in i)
    assert check["ok"] is True and check["below_float32"] == []
    assert check["in_use_after_check"] == [0, 0]
    after = next(i for i in infos if "blocks_in_use_after" in i)
    assert after["blocks_in_use_after"] == [0, 0]
    assert 0 < after["window_blocks_held_peak"] \
        < after["full_blocks_held_peak"]
    assert after["long_completed_in_window"] > 0
