"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric as new files and new entries, and edits no file that is there."""

import hashlib
import json
import os
import shutil

from perfbench_testlib import RESULT_KEYS, ROOT, last_line, run_cell


def _digests(root, paths):
    out = {}
    for path in paths:
        for base, _, files in os.walk(os.path.join(root, path)):
            if "__pycache__" in base:
                continue
            for name in files:
                full = os.path.join(base, name)
                with open(full, "rb") as f:
                    out[os.path.relpath(full, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "horovod_tpu"), tmp_path / "horovod_tpu")
    before = _digests(root, ["perfbench"])

    # a configuration: its file of sizes (the plain reference is gpt2's)
    with open(tmp_path / "perfbench/configs/gpt2-xl.json") as f:
        config = json.load(f)
    config.update(config.pop("rehearsal"))
    config["n_layer"] = 3
    with open(tmp_path / "perfbench/configs/tiny-gpt2.json", "w") as f:
        json.dump(config, f)
    # a traffic mix: a data file of parameters for the general generator
    with open(tmp_path / "perfbench/traffic/short_bursts.json", "w") as f:
        json.dump({"kind": "open_loop", "rate_per_s": 5.0,
                   "prompt_tokens": {"dist": "uniform", "min": 4, "max": 24},
                   "output_tokens": {"dist": "fixed", "value": 4, "min": 4,
                                     "max": 4},
                   "sampling": None}, f)
    # a per-layer metric: a small reader of its own
    with open(tmp_path / "perfbench/metrics/scheduler.decode_steps.py",
              "w") as f:
        f.write("def read(ctx):\n"
                "    t0, t1 = ctx.window\n"
                "    return float(sum(1 for t, phase, _ in "
                "ctx.spans['steps'] if phase == 'decode' and t0 <= t <= t1))"
                "\n")
    spec["configs"].append({
        "name": "tiny-gpt2", "source": "a test", "reduced": ["n_layer"],
        "file": "perfbench/configs/tiny-gpt2.json", "why": "a test"})
    spec["workloads"].append({
        "name": "tiny-gpt2.short_bursts", "config": "tiny-gpt2",
        "traffic": "short_bursts", "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": "scheduler.decode_steps.itl", "unit": "count",
        "better": "higher", "source": "program_span", "layer": "scheduler",
        "moves": "itl_p90_ms", "workloads": ["tiny-gpt2.short_bursts"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("itl_p90_ms", "kv_cache.preemptions.itl"):
            m["workloads"].append("tiny-gpt2.short_bursts")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)

    listed = run_cell(root, "--list")
    assert listed.returncode == 0, listed.stderr[-2000:]
    assert "tiny-gpt2.short_bursts" in listed.stdout
    plain = run_cell(root, "--workload", "tiny-gpt2.short_bursts", "--seed",
                     "5", "--seconds", "2", "--trace", "0", "--rehearse")
    assert plain.returncode == 0, plain.stderr[-3000:]
    doc = last_line(plain)
    assert RESULT_KEYS <= set(doc) and doc["correct"] is True
    assert set(doc["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert doc["attempted"] == 10                      # round(5.0 x 2 s)
    traced = run_cell(root, "--workload", "tiny-gpt2.short_bursts",
                      "--seed", "5", "--seconds", "2", "--trace", "1",
                      "--rehearse")
    assert traced.returncode == 0, traced.stderr[-3000:]
    got = set(last_line(traced)["metrics"])
    assert {"scheduler.decode_steps.itl", "kv_cache.preemptions.itl",
            "compile_cache.warmup_s"} <= got
    assert "scheduler.host_ms_per_iter.itl" not in got  # not this cell's

    after = _digests(root, ["perfbench"])
    assert {k: after[k] for k in before} == before      # nothing edited
    assert sorted(set(after) - set(before)) == [
        "perfbench/configs/tiny-gpt2.json",
        "perfbench/metrics/scheduler.decode_steps.py",
        "perfbench/traffic/short_bursts.json"]
