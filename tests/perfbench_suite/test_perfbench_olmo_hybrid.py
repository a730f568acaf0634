"""The benchmark's own files of the ``olmo-hybrid`` configuration: the
configuration file against the catalog's published values, the counts
on hand-worked sizes and against the model's parameter tree, the
per-layer readers against what a program with and without the counters
leaves, the session generator against the traffic ISSUE 31 names, the
runner's comparison, and the traced rehearsal of the cell."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import core, counts_olmo_hybrid
from perfbench_testlib import RESULT_KEYS, ROOT, last_line, run_cell

CELL = "olmo-hybrid.session_turns"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
#: ``config`` of the catalog row (``architectures.jsonl`` beside the
#: ``model-configs`` guide), as published
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
NEW_METRICS = ("programs.hybrid_decode_roofline.served",
               "programs.hybrid_prefill_roofline.served",
               "state_cache.prefix_hit_token_share.served",
               "state_cache.snapshot_slots_peak_share.served",
               "state_cache.copy_ms_per_iter.served")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench/configs/olmo-hybrid.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic_file():
    with open(os.path.join(ROOT, "perfbench/traffic/session_turns.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return core.load_module(
        os.path.join(ROOT, "perfbench/runners/serve_olmo_hybrid.py"),
        "perfbench_runner_serve_olmo_hybrid_under_test")


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
    entry = next(c for c in spec["configs"] if c["name"] == "olmo-hybrid")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # 32 -> 16 layers: four whole periods, 12 linear and 4 full
    assert config["num_hidden_layers"] == 16
    assert config["layer_types"][:16] == PERIOD * 4
    assert "two pipeline stages of 16 layers" in config["deployment"]
    assert "embedding and the head" in config["deployment"]
    assert (config["param_dtype"], config["activation_dtype"],
            config["state_dtype"], config["conv_window_dtype"],
            config["kv_dtype"]) == ("bfloat16", "bfloat16", "float32",
                                    "bfloat16", "bfloat16")
    assert len(config["assumed"]) >= 8
    assert config["check_sample"] == [[17, 6], [600, 6], [1500, 6]]
    assert config["check_session"] == {
        "first_prompt": 1100, "added": 300, "new_tokens": 6,
        "expect_hit_tokens": 1024, "expect_restored": 1}
    assert (config["runner"], config["reference"]) \
        == ("serve_olmo_hybrid", "olmo_hybrid")


def test_engine_arithmetic_of_the_configuration(config, runner):
    from horovod_tpu.models import OlmoHybrid
    from horovod_tpu.serving.generation import kv_cache as kvc

    eng = config["engine"]
    cfg = runner.model_config(config)
    assert (eng["max_seqs"], eng["block_size"], eng["num_blocks"],
            eng["prefill_chunk"], eng["table_positions"],
            eng["state_snapshots"]) == (16, 64, 768, 512, 7168, 48)
    assert cfg.max_seq_len == 7168 and cfg.max_position_embeddings == 65536
    # ISSUE 31's arithmetic
    assert kvc.block_bytes(cfg, 64) * 768 == 3019898880     # 3.02 GB
    assert kvc.state_bytes(cfg) == 27371520                 # 27.4 MB
    assert 768 * 64 == 49152
    assert eng["prefill_chunk"] % eng["block_size"] == 0
    # the longest context of the traffic fits the table
    assert 2048 + 7 * 640 + 192 == 6720 <= eng["table_positions"]
    shapes = jax.eval_shape(lambda: OlmoHybrid(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    leaves = jax.tree_util.tree_leaves(shapes)
    counted = counts_olmo_hybrid.param_counts(config)
    assert counted["resident"] == sum(a.size for a in leaves)
    assert 8.19e9 < sum(a.size * a.dtype.itemsize for a in leaves) < 8.21e9
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert sum(a.size for a in leaves if a.dtype == jnp.float32) \
        == 12 * 2 * 30                                      # A_log, dt_bias


def test_counts_follow_the_shapes(config):
    c = counts_olmo_hybrid.param_counts(config)
    # ISSUE 31: 88.5 M a linear mixer, 59.0 M a full one, 126.8 M an MLP,
    # 770.7 M of embedding and head, 4.098 G on the chip
    assert round(c["linear_mixer"] / 1e6, 1) == 88.8     # + a, b, conv
    assert round((3840 * (2880 * 2 + 5760 * 2) + 5760 * 3840) / 1e6, 1) \
        == 88.5
    assert round(c["full_mixer"] / 1e6, 1) == 59.0
    assert round(c["mlp"] / 1e6, 1) == 126.8
    assert round(2 * c["embedding"] / 1e6, 1) == 770.7
    assert round(c["resident"] / 1e9, 2) == 4.10
    assert (c["linear_layers"], c["full_layers"]) == (12, 4)
    assert counts_olmo_hybrid.kv_bytes_per_token(config) == 61440
    assert counts_olmo_hybrid.state_bytes_per_sequence(config) \
        == 12 * (30 * 192 * 96 * 4 + 3 * 11520 * 2)
    # a decode step: the weights without the embedding table, the K/V of
    # the live tokens, each live lane's state in and out
    floor = counts_olmo_hybrid.decode_bytes(config, 0, 0)
    assert floor == (c["resident"] - c["embedding"]) * 2
    assert counts_olmo_hybrid.decode_bytes(config, 30000, 12) - floor \
        == 30000 * 61440 + 2 * 12 * 27371520


def test_counts_on_a_hand_worked_size():
    """One linear and one full layer at widths small enough to count by
    hand: hidden 8, 2 heads, dk 2, dv 4, MLP 16, vocabulary 10."""
    tiny = {"hidden_size": 8, "intermediate_size": 16, "vocab_size": 10,
            "num_hidden_layers": 2,
            "layer_types": ["linear_attention", "full_attention"],
            "linear_num_value_heads": 2, "linear_key_head_dim": 2,
            "linear_value_head_dim": 4, "linear_conv_kernel_dim": 4}
    c = counts_olmo_hybrid.param_counts(tiny)
    channels = 2 * (2 + 2 + 4)                              # 16
    linear_matmul = 8 * channels + 8 * 4 + 8 * 8 + 8 * 8   # qkv, ab, g, o
    assert c["linear_mixer"] == linear_matmul + 4 * channels + 2 * 2 + 4
    assert c["full_mixer"] == 4 * 64 + 2 * 8
    assert c["mlp"] == 3 * 8 * 16
    assert c["per_token"] == linear_matmul + 4 * 64 + 2 * c["mlp"]
    assert c["resident"] == c["linear_mixer"] + c["full_mixer"] \
        + 2 * (c["mlp"] + 16) + 2 * 80 + 8
    assert counts_olmo_hybrid.kv_bytes_per_token(tiny) == 1 * 2 * 8 * 2
    assert counts_olmo_hybrid.state_bytes_per_sequence(tiny) \
        == 2 * 2 * 4 * 4 + 3 * channels * 2
    # one sub-chunk of 64 tokens, per head: K K^T and Q K^T (2 x 64 x 64
    # x 2 multiply-adds), the solve (64 x 64 / 2 x 6), W S, Q S and
    # K^T R (3 x 64 x 2 x 4), (Q K^T) R (64 x 64 x 4)
    per_head = 2 * (2 * 64 * 64 * 2 + 64 * 64 * 6 / 2 + 3 * 64 * 2 * 4
                    + 64 * 64 * 4)
    assert counts_olmo_hybrid.delta_rule_flops(tiny, 64) == 2 * per_head
    assert counts_olmo_hybrid.delta_rule_flops(tiny, 128) == 4 * per_head
    # 3 queries after 5 cached tokens see 5 + 6 pairs
    assert counts_olmo_hybrid.attention_flops(tiny, 3, 5) \
        == 2 * 2 * (15 + 6) * 8
    assert counts_olmo_hybrid.prefill_chunk_flops(tiny, 64, 0) \
        == 2 * 64 * c["per_token"] + 2 * per_head \
        + counts_olmo_hybrid.attention_flops(tiny, 64, 0) + 2 * 80
    assert counts_olmo_hybrid.prefill_chunk_bytes(tiny, 64, 10) \
        == counts_olmo_hybrid.decode_bytes(tiny, 74, 1)


def test_the_delta_rules_share_of_a_chunk_at_the_published_widths(config):
    """ISSUE 31: the chunked products are a hundredth of a chunk's
    matmuls, where a token-by-token scan would rewrite the state 512
    times a layer."""
    rule = 12 * counts_olmo_hybrid.delta_rule_flops(config, 512)
    chunk = counts_olmo_hybrid.prefill_chunk_flops(config, 512, 1024)
    assert 30e9 < rule < 36e9
    assert 3.3e12 < chunk < 3.7e12


# -- the readers --------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_nothing(
        config, spec, name):
    """On the parent commit a traced run finds no snapshot counters, no
    ``gen.state.*`` phases and none of this runner's facts: the reader
    returns nothing and does not raise, with a trace and without."""
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "served_tokens_per_s"
    assert entry["workloads"] == [CELL]
    read = _reader(name).read
    assert read(_ctx(config)) is None
    trace = types.SimpleNamespace(program_ms=lambda pattern: None)
    assert read(_ctx(config, trace=trace, records=[])) is None


def test_the_new_entries_are_appended_and_the_cell_is_listed(spec):
    assert [m["name"] for m in spec["per_layer"][-5:]] == list(NEW_METRICS)
    assert spec["configs"][-1]["name"] == "olmo-hybrid"
    cell = spec["workloads"][-1]
    assert cell == {"name": CELL, "config": "olmo-hybrid",
                    "traffic": "session_turns", "chips": 1,
                    "why": cell["why"]}
    assert "prompt tokens" in cell["why"] and "49k" in cell["why"]
    served = next(m for m in spec["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert served["workloads"] == ["gpt2-xl.doc_backlog", CELL]
    assert served["bound"] == 0.03
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"programs.decode_step_ms.served",
            "programs.prefill_chunk_ms.served",
            "programs.paged_blocks_read_share.served",
            "kv_cache.pool_in_use_peak_share.served",
            "device.idle_share.served",
            "scheduler.host_ms_per_iter.served"} <= listed
    assert not any(name.endswith(".itl") for name in listed)


def test_the_state_cache_readers_read_counters_and_facts(config):
    before = {'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="local"}':
              1000.0,
              "hvd_tpu_gen_prefix_cache_miss_tokens_total": 500.0}
    after = {'hvd_tpu_gen_prefix_cache_hit_tokens_total{source="local"}':
             4000.0,
             "hvd_tpu_gen_prefix_cache_miss_tokens_total": 1500.0}
    ctx = _ctx(config, before, after, snapshot_slots=48,
               snapshot_slots_peak=36)
    assert _reader("state_cache.prefix_hit_token_share.served").read(ctx) \
        == 75.0
    assert _reader("state_cache.snapshot_slots_peak_share.served").read(
        ctx) == 75.0
    hist = lambda s, n: {"sum": s, "count": n}  # noqa: E731
    ctx = _ctx(config, {}, {
        'hvd_tpu_gen_step_seconds{component="host"}': hist(1.0, 200),
        'hvd_tpu_gen_phase_seconds{phase="iter"}': hist(0.5, 200),
        'hvd_tpu_gen_phase_seconds{phase="state.snapshot"}': hist(0.03, 30),
        'hvd_tpu_gen_phase_seconds{phase="state.restore"}': hist(0.01, 20)})
    assert _reader("state_cache.copy_ms_per_iter.served").read(ctx) \
        == pytest.approx(0.04 / 200 * 1e3)


def test_the_roofline_readers_divide_need_by_device_time(config):
    """Two lanes decoding over 1301 and 2002 tokens, and three chunks of
    which one starts at a prefix hit."""
    records = [types.SimpleNamespace(
        seq_id=i, req=types.SimpleNamespace(prompt=[0] * n),
        token_times=[4.0]) for i, n in ((7, 1300), (8, 2001))]
    trace = types.SimpleNamespace(program_ms=lambda pattern: 20.0)
    ctx = _ctx(config, trace=trace, records=records,
               prefill_chunks=[(0, 512), (1024, 512), (1536, 100)])
    ctx.spans["steps"] = [(3.0, "prefill", (7,)), (4.5, "decode", (7, 8)),
                          (5.5, "decode", (7, 8)), (12.0, "decode", (7,))]
    need = counts_olmo_hybrid.decode_bytes(config, 1301 + 2002, 2)
    got = _reader("programs.hybrid_decode_roofline.served").read(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.020)
    assert 0 < got < 100
    seconds = [max(counts_olmo_hybrid.prefill_chunk_flops(config, q, p)
                   / 197e12,
                   counts_olmo_hybrid.prefill_chunk_bytes(config, q, p)
                   / 819e9)
               for p, q in ((0, 512), (1024, 512), (1536, 100))]
    got = _reader("programs.hybrid_prefill_roofline.served").read(ctx)
    assert got == pytest.approx(100 * sum(seconds) / 3 / 0.020)
    assert 0 < got < 105


# -- the traffic --------------------------------------------------------------

def test_traffic_file_holds_what_the_issue_names(traffic_file):
    tr = traffic_file
    assert (tr["clients"], tr["turns"], tr["start_after"]) == (12, 8, 24)
    assert tr["first_prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 512,
        "max": 2048}
    assert tr["added_tokens"] == {"dist": "uniform", "min": 192, "max": 640}
    assert tr["output_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert tr["deadline_ms"] == 120000 and tr["sampling"] is None \
        and tr["eos"] is None
    assert tr["sessions"] % tr["clients"] == 0


def test_the_lengths_are_one_fixed_stratified_table(runner, traffic_file):
    from perfbench.harness import traffic

    tr = traffic_file
    table = runner.session_table(tr)
    assert table == runner.session_table(dict(tr))       # no seed in it
    assert table != runner.session_table(dict(tr, dealing_seed=32))
    # the multisets are the distributions' equal-probability quantiles
    for part, dist, n in ((0, "first_prompt_tokens", 24),
                          (1, "added_tokens", 24 * 7),
                          (2, "output_tokens", 24 * 8)):
        flat = sorted(np.ravel([np.atleast_1d(s[part]) for s in table])
                      .tolist())
        assert flat == sorted(traffic.quantile_lengths(tr[dist], n))
    first = [s[0] for s in table]
    assert min(first) >= 512 and max(first) <= 2048
    assert all(len(s[1]) == 7 and len(s[2]) == 8 for s in table)
    # every round of 12 first prompts spans the range of lengths, and
    # every session holds one added and one reply length a stratum
    for k in (0, 12):
        assert min(first[k:k + 12]) < 800 and max(first[k:k + 12]) > 1300
    for _, added, replies in table:
        assert 2700 < sum(added) < 3150          # 7 x 416, +- a stratum
        assert 950 < sum(replies) < 1100         # 8 x 128
        assert min(added) < 256 and max(added) > 576


def test_a_callers_turns_grow_and_its_sessions_are_fresh(runner,
                                                         traffic_file):
    tr, vocab = traffic_file, 100352
    for caller in (0, 3, 11):
        walk = runner.sessions(tr, vocab, 5, caller)
        reqs = [next(walk) for _ in range(1 + caller % 8 + 8 + 8)]
        # the first session is cut to 1 + (caller mod 8) turns
        assert [r.session for r in reqs] \
            == [0] * (1 + caller % 8) + [1] * 8 + [2] * 8
        assert [r.turn for r in reqs[1 + caller % 8:][:8]] == list(range(8))
        assert all(r.caller == caller and r.sampling is None
                   and r.deadline_ms == 120000 for r in reqs)
        for a, b in zip(reqs, reqs[1:]):
            if a.session == b.session:
                # a turn re-sends the conversation and adds to it
                assert b.prompt[:len(a.prompt)] == a.prompt
                assert 192 <= len(b.prompt) - len(a.prompt) <= 640
            else:
                # no session repeats an earlier one's first block
                assert b.prompt[:64] != a.prompt[:64]
        assert max(len(r.prompt) + r.max_tokens for r in reqs) <= 6720
        assert max(t for r in reqs for t in r.prompt) > 65536
    # the same seed gives the same requests, another seed other ids of
    # the same lengths
    again = next(runner.sessions(tr, vocab, 5, 3))
    other = next(runner.sessions(tr, vocab, 6, 3))
    assert again.prompt == next(runner.sessions(tr, vocab, 5, 3)).prompt
    assert again.prompt[:64] != other.prompt[:64]
    assert (len(again.prompt), again.max_tokens) \
        == (len(other.prompt), other.max_tokens)


# -- the comparison and the reference -----------------------------------------

def test_reference_is_float32_highest_and_free_of_the_program():
    with open(os.path.join(ROOT,
                           "perfbench/reference/olmo_hybrid.py")) as f:
        source = f.read()
    assert 'PRECISION = "highest"' in source
    assert not re.search(r"^\s*(from|import)\s+horovod_tpu", source, re.M)
    body = source.split('"""', 2)[2]
    assert "bfloat16" not in body and "lax.scan(token" in body
    assert "chunk" not in body.lower().replace("query_block", "")


@pytest.mark.parametrize("number,value", [
    ("worst", 10.0), ("rms", 10.0), ("session_rms", 10.0),
    ("hit_tokens", 960.0), ("restored", 0.0)])
def test_correct_is_held_by_each_of_the_checks_numbers(runner, config,
                                                       number, value):
    """``compare`` on served tokens that are the reference's own best,
    then with one number off."""
    vocab = 50
    settings = dict(config, check_session=config["check_session"])

    class Plain:
        @staticmethod
        def forward(params, tokens, settings, at=None):
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
    served = {"sample": [request(17, 6), request(30, 6),
                         request(36, 6, value if number == "session_rms"
                                 else 0.0)],
              "batch": [request(9, 7, value if number == "rms" else 0.0)
                        for _ in range(3)],
              "session": {"hit_tokens": 1024.0, "restored": 1.0}}
    if number in served["session"]:
        served["session"][number] = value
    ok, numbers = runner.compare(served, {}, Plain, settings)
    assert not ok
    assert numbers["served_positions"] == 21
    if number not in ("worst", "rms"):
        assert numbers["worst_logit_gap"] == 0.0
        assert numbers["logprob_rms"] < 1e-6
    if number == "session_rms":
        assert numbers["session_logprob_rms"] == pytest.approx(10.0)
    number = None
    served = {"sample": [request(17, 6), request(30, 6), request(36, 6)],
              "batch": [request(9, 7) for _ in range(3)],
              "session": {"hit_tokens": 1024.0, "restored": 1.0}}
    assert runner.compare(served, {}, Plain, settings)[0]


def test_the_batch_is_sampled_one_request_a_lane(runner, config):
    """``serve_check``: the sample and the session go greedy; the batch
    of ``check_logprobs`` is sampled at its temperature, a seed of its
    own a request, the same for the same ``--seed``."""
    b = config["check_logprobs"]
    assert b == {"requests": 16, "prompt_tokens": 32, "new_tokens": 256,
                 "temperature": 1.0}
    assert b["requests"] <= config["engine"]["max_seqs"]

    class Engine:
        def __init__(self):
            self.sent = []

        def submit(self, prompt, max_tokens, deadline_ms, temperature, seed):
            self.sent.append((list(prompt), max_tokens, temperature, seed))
            return types.SimpleNamespace(tokens=[1] * max_tokens,
                                         logprobs=[0.0] * max_tokens)

        @staticmethod
        def result(seq, timeout):
            return seq.tokens

    def sent(seed):
        engine = Engine()
        served = runner.serve_check(
            types.SimpleNamespace(config=config, seed=seed), engine)
        assert len(served["sample"]) == 5 and len(served["batch"]) == 16
        return engine.sent

    first = sent(60060893)
    assert all(t is None and seed is None for _, _, t, seed in first[:5])
    batch = first[5:]
    assert [(len(p), n, t) for p, n, t, _ in batch] == [(32, 256, 1.0)] * 16
    assert len({seed for *_, seed in batch}) == 16
    assert first == sent(60060893) and first != sent(60060894)


def test_the_limits_lie_between_their_readings(runner):
    """PERF.md, section 6, PR 31: clean readings under each limit, the
    faults' readings over one of them."""
    assert 0.16 < runner.LOGIT_TOL < 1.0          # clean 0.03-0.16
    assert 0.0268 < runner.LOGPROB_RMS_TOL < 0.0390   # clean | state bf16
    assert 0.1 < runner.SESSION_LOGPROB_RMS_TOL < 0.4


# -- the command --------------------------------------------------------------

def test_traced_rehearsal_reports_the_new_metrics():
    proc = run_cell(ROOT, "--workload", CELL, "--seed", "3000000019",
                    "--seconds", "3", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = last_line(proc)
    assert RESULT_KEYS | {"breakdown"} <= set(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    got = set(doc["metrics"])
    # counters, spans and facts are there without a device; the two
    # rooflines read a device trace and are left out on the CPU
    assert {"state_cache.prefix_hit_token_share.served",
            "state_cache.snapshot_slots_peak_share.served",
            "state_cache.copy_ms_per_iter.served",
            "kv_cache.pool_in_use_peak_share.served",
            "programs.paged_blocks_read_share.served"} <= got
    assert "programs.hybrid_decode_roofline.served" not in got
    info = [json.loads(x)["info"] for x in proc.stdout.splitlines()
            if x.startswith('{"info"')]
    check = next(i for i in info if "worst_logit_gap" in i)
    assert check["ok"] and check["session_hit_tokens"] == 32 \
        and check["session_restored"] == 1
    turns = next(i for i in info if "turns_sent_by_caller" in i)
    assert len(turns["turns_sent_by_caller"]) == 3
    assert turns["state_slots_held"] == 0
    assert turns["snapshot_slots_orphaned"] == 0
    window = next(i for i in info if "compiles_in_window" in i)
    assert window["compiles_in_window"] == 0
    assert window["kv_blocks_leaked"] == 0


def test_tolerance_tool_reads_clean_and_one_fault():
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, "perfbench/tools/olmo_tolerance.py", "--rehearse",
         "--faults", "clean,no_restore"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    readings = [json.loads(x)["reading"] for x in proc.stdout.splitlines()
                if x.startswith('{"reading"')]
    assert [r["fault"] for r in readings] == ["clean", "no_restore"]
    clean, fault = readings
    assert clean["ok"] and clean["worst_logit_gap"] < 1e-3
    # the fault is silent in the counters and loud in the logits
    assert fault["session_restored"] == 1 and fault["session_hit_tokens"] == 32
    assert not fault["ok"] and fault["worst_logit_gap"] > fault[
        "logit_tolerance"]
