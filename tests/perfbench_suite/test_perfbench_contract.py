"""``BENCHMARK.json`` against the limits of the benchmark's contract, so
that a slip is caught here and not by the driver's refusal."""

import os
import re

from perfbench_testlib import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in spec["command"])
    assert 1 <= len(spec["paths"]) <= 16
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in spec["paths"])
    assert any(w.startswith(p + "/") for w in spec["command"]
               for p in spec["paths"])
    assert isinstance(spec["run_seconds"], int) \
        and 1 <= spec["run_seconds"] <= 51
    # the full check with 24 cells fits the driver's 43200 seconds
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_configs_and_cells(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    assert len(configs) == len(spec["configs"]) <= 24
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k
                       or "n_embd" in k or "n_inner" in k
                       for k in c["reduced"])
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
    assert {w["config"] for w in cells} == set(configs)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(spec["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        mine = set(m.get("workloads", cells))
        assert mine <= set(e2e[m["moves"]].get("workloads", cells))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in spec["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2                      # setup_s and another
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])


def test_files_under_paths_have_legal_names(spec):
    legal = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in spec["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert legal.match(rel) and len(rel) <= 200, rel
