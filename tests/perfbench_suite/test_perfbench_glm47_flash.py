"""The benchmark's own files of the ``glm47-flash`` configuration: the
configuration file against the published ``config.json`` values, the
counts against the parameter table (PERF.md, section 4) and the model's
tree, the new entries and readers (found by name, not by their place in
a list), the table of lengths against the traffic the cell's ``why``
names, the runner's comparison, and the traced rehearsal of the cell."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import core
from perfbench.harness import counts_glm47_flash as counts
from perfbench_testlib import RESULT_KEYS, ROOT, last_line, run_cell

CELL = "glm47-flash.reasoning_mtp"
REDUCED = ["num_hidden_layers", "max_position_embeddings"]
#: ``config.json`` of zai-org/GLM-4.7-Flash, the keys that say something
#: about its shape
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}
NEW_METRICS = ("scheduler.mtp_accept_share.served",
               "programs.mtp_verify_step_ms.served",
               "programs.mla_mtp_verify_roofline.served",
               "programs.mla_mtp_prefill_roofline.served")
#: existing ``.served`` readers the cell reports
LISTED = ("service.ttft_p50_ms.served",
          "service.completed_tokens_per_s.served",
          "scheduler.host_ms_per_iter.served",
          "kv_cache.pool_in_use_peak_share.served",
          "kv_cache.preemptions.served", "device.idle_share.served",
          "programs.prefill_chunk_ms.served",
          "scheduler.admit_ms_per_iter.served",
          "scheduler.launch_ms_per_iter.served",
          "scheduler.deliver_ms_per_iter.served",
          "scheduler.batch_occupancy_mean.served",
          "scheduler.queue_wait_mean_ms.served",
          "scheduler.prefill_span_mean_ms.served")
#: readers the cell must not be listed on: the seven that accepted tests
#: pin to the cells before it, and the plain decode program's step time
#: (this cell runs no ``jit__decode``)
UNLISTED = ("moe.held_picks_per_token.served",
            "moe.held_load_max_over_mean.served",
            "scheduler.chunk_iter_share.served",
            "scheduler.iter_ms_with_chunk.served",
            "device.idle_ms_per_iter.parked.served",
            "device.idle_ms_per_iter.wait_head.served",
            "device.idle_ms_per_iter.wait_tail.served",
            "programs.decode_step_ms.served")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "perfbench/configs/glm47-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic_file():
    with open(os.path.join(ROOT, "perfbench/traffic/reasoning_mtp.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return core.load_module(
        os.path.join(ROOT, "perfbench/runners/serve_glm47_flash.py"),
        "perfbench_runner_serve_glm47_flash_under_test")


def _reader(name):
    return core.load_module(core.reader_path(name),
                            "reader_under_test_" + re.sub(r"\W", "_", name))


def _ctx(config, before=None, after=None, trace=None, **facts):
    ctx = types.SimpleNamespace(
        config=config, trace=trace, facts=facts, spans={}, window=(0.0, 1.0),
        counters_before=before or {}, counters_after=after or {},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    ctx.counter_delta = lambda series: core.Context.counter_delta(ctx, series)
    return ctx


def test_configuration_holds_the_published_values(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == "glm47-flash")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["max_position_embeddings"]) \
        == (5, 8256)
    assert config["held_experts"] == [0, 64]
    assert "8-stage" in config["deployment"] and "EP1" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("sigmoid", "correction bias", "interleaved",
                 "after the main model's final norm", "position i",
                 "embedding and lm_head", "[enorm(Emb(t_{i+1})) ; hnorm(h_i)]",
                 "draft_gain"):
        assert said in assumed, said
    eng = config["engine"]
    assert (eng["max_seqs"], eng["block_size"], eng["prefill_chunk"],
            eng["spec_mode"], eng["spec_tokens"]) == (32, 64, 512, "mtp", 1)
    assert (config["runner"], config["reference"]) \
        == ("serve_glm47_flash", "glm47_flash")


def test_counts_hold_the_parameter_table(config):
    c = counts.param_counts(config)
    million = {k: round(v / 1e6, 2) for k, v in c.items()}
    assert million["attention"] == 21.76
    assert million["dense_layer"] == 84.68
    assert million["moe_layer"] == 635.31
    assert million["mtp"] == 643.71
    assert round((c["embedding"] + c["head"]) / 1e6, 2) == 634.39
    assert round(counts.weight_bytes(config) / 1e9, 2) == 7.81
    assert counts.weight_bytes(config) == config["engine"]["weight_bytes"]
    # a block of 64 tokens: 6 planes x 64 x 640 x 2 B
    assert config["engine"]["num_blocks"] * 6 * 64 * 640 * 2 \
        == config["engine"]["kv_pool_bytes"] == 2032926720
    # arguments over a quarter of the chip's 16 GB
    assert counts.weight_bytes(config) \
        + config["engine"]["kv_pool_bytes"] > 0.6 * 16e9


def test_counts_match_the_models_tree(config, runner):
    """The parameter count from the shapes alone is the tree's, at the
    rehearsal's sizes."""
    from horovod_tpu.models import Glm47Flash

    small = core.merged(config, config["rehearsal"])
    model = Glm47Flash(runner.model_config(small))
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             jnp.zeros((1, 8), jnp.int32)))
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(tree))
    assert total == counts.param_counts(small)["resident"]


def test_a_verify_step_reads_about_what_a_plain_step_does(config):
    """The sizing: with 63 of 64 experts touched either way, a
    verify step of 32 lanes moves about 7.8 GB of weights, the cache
    rows beside them (3000 a lane in each of 6 planes)."""
    rows = [3000] * 32
    got = counts.verify_bytes(config, rows, 63.0, 0.8)
    cache = 32 * 6 * 3000 * counts.latent_row_bytes(config)
    assert 7.6e9 < got - cache < 7.9e9
    assert counts.verify_flops(config, rows, 4.0, 0.8) \
        > 2 * counts.verify_flops(config, rows[:16], 4.0, 0.8) * 0.99


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_where_the_program_has_nothing(
        config, spec, name):
    """On the parent commit a traced run finds no ``mode`` label, no
    verify routing counts and no ``jit__verify_mtp``: the reader returns
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


def test_the_new_entries_are_listed_by_name(spec):
    configs = {c["name"] for c in spec["configs"]}
    assert "glm47-flash" in configs
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "glm47-flash",
                    "traffic": "reasoning_mtp", "chips": 1,
                    "why": cell["why"]}
    assert "32 callers" in cell["why"] and "MTP verify" in cell["why"]
    assert len(cell["why"]) <= 200
    served = next(m for m in spec["end_to_end"]
                  if m["name"] == "served_tokens_per_s")
    assert CELL in served["workloads"] and served["bound"] == 0.03
    names = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS + LISTED:
        assert CELL in names[name]["workloads"], name
    listed = {n for n, m in names.items() if CELL in m.get("workloads", ())}
    assert listed == set(NEW_METRICS + LISTED)
    assert not set(UNLISTED) & listed
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert len(spec["workloads"]) <= 24


def test_the_accept_share_reads_the_mtp_series(config):
    after = {'hvd_tpu_gen_spec_drafted_total{mode="mtp"}': 200.0,
             'hvd_tpu_gen_spec_accepted_total{mode="mtp"}': 150.0,
             'hvd_tpu_gen_spec_drafted_total{mode="ngram"}': 900.0}
    read = _reader("scheduler.mtp_accept_share.served").read
    assert read(_ctx(config, after=after)) == pytest.approx(75.0)


def test_the_roofline_readers_divide_need_by_device_time(config):
    trace = types.SimpleNamespace(program_ms=lambda pattern: 40.0)
    after = {"hvd_tpu_gen_moe_tokens_total": 4000.0,
             'hvd_tpu_gen_moe_picks_total{kind="held"}': 16000.0,
             'hvd_tpu_gen_moe_experts_touched_total{phase="verify"}': 3150.0,
             'hvd_tpu_gen_moe_calls_total{phase="verify"}': 10.0,
             'hvd_tpu_gen_spec_drafted_total{mode="mtp"}': 100.0,
             'hvd_tpu_gen_spec_accepted_total{mode="mtp"}': 75.0}
    chunks = [(0, 512), (512, 300)]
    ctx = _ctx(config, after=after, trace=trace, prefill_chunks=chunks)
    got = _reader("programs.mla_mtp_prefill_roofline.served").read(ctx)
    need = [max(counts.prefill_chunk_flops(config, q, p, 4.0) / 197e12,
                counts.prefill_chunk_bytes(config, q, p) / 819e9)
            for p, q in chunks]
    assert got == pytest.approx(100.0 * sum(need) / 2 / 0.04)
    assert 0 < got < 100
    rec = types.SimpleNamespace(seq_id=1, req=types.SimpleNamespace(
        prompt=[0] * 1000), token_times=[0.1, 0.2, 0.5])
    ctx = _ctx(config, after=after, trace=trace, records=[rec])
    ctx.spans["steps"] = [(0.5, "decode", (1,))]
    got = _reader("programs.mla_mtp_verify_roofline.served").read(ctx)
    need = max(counts.verify_bytes(config, [1001], 63.0, 0.75) / 819e9,
               counts.verify_flops(config, [1001], 4.0, 0.75) / 197e12)
    assert got == pytest.approx(100.0 * need / 0.04)
    assert 0 < got < 100


def test_traffic_file_holds_the_cells_parameters(traffic_file):
    tr = traffic_file
    assert (tr["kind"], tr["clients"], tr["requests"], tr["start_after"],
            tr["start_stagger_ms"]) == ("closed_loop", 32, 96, 32, 100)
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                   "sigma": 0.6, "min": 256, "max": 4096}
    assert tr["output_tokens"] == {"dist": "loguniform", "min": 1024,
                                   "max": 4096}
    assert tr["sampling"] is None and tr["eos"] is None
    assert "154 880" in tr["token_ids"]
    assert "no shared prefix" in tr["token_ids"]
    assert "rehearsal" in tr


def test_the_lengths_are_one_fixed_stratified_table(runner, traffic_file):
    table = runner.DSV.length_table(traffic_file)
    assert table == runner.DSV.length_table(dict(traffic_file))
    prompts = sorted(p for p, _ in table)
    replies = sorted(r for _, r in table)
    assert len(table) == 96
    assert 256 <= prompts[0] and prompts[-1] <= 4096
    assert 1024 <= replies[0] and replies[-1] <= 4096
    assert abs(np.median(prompts) - 1024) < 40
    # log-uniform: median 2048
    assert abs(np.median(replies) - 2048) < 80
    assert max(p + r for p, r in table) <= 8192 < 8256


def test_reference_is_float32_highest_and_free_of_the_program():
    with open(os.path.join(ROOT, "perfbench/reference/glm47_flash.py")) as f:
        text = f.read()
    assert "PRECISION = DS.PRECISION" in text
    assert "horovod_tpu" not in text.replace(
        "``horovod_tpu/models/glm47_flash.py``", "")
    plain = core.load_module(
        os.path.join(ROOT, "perfbench/reference/glm47_flash.py"),
        "reference_under_test_glm47_flash_precision")
    assert plain.PRECISION == "highest"


def test_correct_is_held_by_the_checks_numbers(runner, config):
    """At the rehearsal's sizes, served once: clean is correct with
    nothing off, and every fault of the reference moves the number that
    has to catch it (on the chip, the limits lie between the clean and
    the faulted readings: PERF.md, section 6)."""
    small = core.merged(config, config["rehearsal"])
    from horovod_tpu.models import Glm47Flash

    model = Glm47Flash(runner.model_config(small))
    params = runner.make_weights(model, 5, small["draft_gain"])
    plain = core.load_module(
        os.path.join(ROOT, "perfbench/reference/glm47_flash.py"),
        "reference_under_test_glm47_flash")
    ctx = types.SimpleNamespace(config=small, seed=5)
    with runner.build_engine(model, params, small["engine"]) as engine:
        served = runner.serve_check(ctx, engine, model, params)
    ok, numbers = runner.compare(served, params["params"], plain, small)
    assert ok and numbers["draft_agreement"] == 1.0
    # every greedy request's drafts were seen: the prefill's first and
    # one a verify step that left the lane decoding
    assert numbers["requests_without_drafts"] == 0
    assert numbers["drafts"] >= len(small["check_sample"]) * 6
    assert numbers["worst_logit_gap"] < 1e-4
    assert numbers["logprob_rms"] < 1e-4
    assert numbers["draft_gap_mean"] < 1e-5
    _, f8 = runner.compare(served, params["params"], plain, small,
                           faults=("activations_float8",))
    assert f8["logprob_rms"] > 100 * numbers["logprob_rms"] + 1e-4
    assert f8["draft_gap_mean"] > 100 * numbers["draft_gap_mean"] + 1e-4
    for fault in ("mtp_float8", "mtp_no_hidden"):
        _, off = runner.compare(served, params["params"], plain, small,
                                faults=(fault,))
        assert off["draft_gap_mean"] \
            > 100 * numbers["draft_gap_mean"] + 1e-4, fault
        assert off["logprob_rms"] == pytest.approx(numbers["logprob_rms"])


def test_the_cell_rehearses_with_its_new_metrics(spec):
    """The whole command on the CPU at the rehearsal's sizes, traced: the
    check passes, no block is left in use, drafts are accepted, and the
    line carries the acceptance (device metrics need a device trace and
    stay out)."""
    proc = run_cell(ROOT, "--workload", CELL, "--rehearse", "--seconds",
                    "3", "--seed", "3000000019", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert RESULT_KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for name in ("scheduler.mtp_accept_share.served",
                 "kv_cache.pool_in_use_peak_share.served"):
        assert name in line["metrics"], name
    infos = [json.loads(x)["info"] for x in proc.stdout.splitlines()
             if x.startswith('{"info"')]
    check = next(i for i in infos if "check" in i)
    assert check["ok"] is True and check["in_use_after_check"] == 0
    after = next(i for i in infos if "blocks_in_use_after" in i)
    assert after["blocks_in_use_after"] == 0
