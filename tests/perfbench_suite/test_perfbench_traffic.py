"""The traffic generator: the seed changes order and instants, never the
work offered."""

import collections
import os

import pytest

from perfbench.harness import core, traffic

SEEDS = (7, 3000000019)


def _file(name):
    return traffic.load(core.BENCH_DIR, name)


def test_open_loop_offers_the_same_work_in_every_seed():
    tr = _file("chat_steady")
    a, b = (traffic.open_loop(tr, 50257, s, 51.0) for s in SEEDS)
    assert len(a) == len(b) == round(tr["rate_per_s"] * 51.0)
    for key in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert collections.Counter(map(key, a)) == \
            collections.Counter(map(key, b))
        assert list(map(key, a)) != list(map(key, b))      # another order
    for reqs in (a, b):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] <= 51.0
        assert all(16 <= len(r.prompt) <= 512 for r in reqs)
        assert all(16 <= r.max_tokens <= 256 for r in reqs)
        assert all(0 <= t < 50257 for r in reqs for t in r.prompt)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    # the same seed gives the same inputs
    again = traffic.open_loop(tr, 50257, SEEDS[0], 51.0)
    assert [(r.prompt, r.max_tokens, r.due_s, r.seed) for r in again] == \
        [(r.prompt, r.max_tokens, r.due_s, r.seed) for r in a]


def test_quantile_lengths_follow_the_distribution():
    ln = traffic.quantile_lengths(
        {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 16,
         "max": 512}, 101)
    assert ln == sorted(ln) and ln[50] == 128 and ln[0] >= 16 \
        and ln[-1] <= 512
    un = traffic.quantile_lengths({"dist": "uniform", "min": 384,
                                   "max": 960}, 64)
    assert un[0] == round(384 + 0.5 / 64 * 576) and un[-1] <= 960
    assert abs(sum(un) / 64 - 672) < 1
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


def test_closed_loop_deals_one_multiset_to_the_clients():
    tr = _file("doc_backlog")
    a, b = (traffic.closed_loop(tr, 50257, s) for s in SEEDS)
    assert len(a) == len(b) == tr["clients"]
    flat = [[r for mine in x for r in mine] for x in (a, b)]
    assert len(flat[0]) == len(flat[1]) == tr["pool"]
    for key in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert collections.Counter(map(key, flat[0])) == \
            collections.Counter(map(key, flat[1]))
    assert [len(r.prompt) for r in flat[0]] != \
        [len(r.prompt) for r in flat[1]]
    for r in flat[0]:
        assert 384 <= len(r.prompt) <= 960 and 16 <= r.max_tokens <= 64
        assert len(r.prompt) + r.max_tokens <= 1024
        assert r.deadline_ms == 120000 and r.sampling is None


def test_closed_loop_rounds_each_span_the_range_of_lengths():
    """Request k of every caller is round k: one length from each
    eighth of the multiset, so a few rounds carry the same work in
    every seed."""
    tr = _file("doc_backlog")
    for seed in SEEDS:
        lists = traffic.closed_loop(tr, 50257, seed)
        sums = [sum(len(mine[k].prompt) for mine in lists)
                for k in range(tr["pool"] // tr["clients"])]
        assert max(sums) - min(sums) < 0.05 * min(sums)
        first = sorted(len(mine[0].prompt) for mine in lists)
        step = (960 - 384) / tr["clients"]
        assert all(384 + i * step <= n <= 384 + (i + 1) * step + 1
                   for i, n in enumerate(first))


def test_preload_retires_at_staggered_times():
    tr = _file("chat_steady")
    pre = traffic.preload(tr, 50257, SEEDS[0], 64)
    p = tr["preload"]
    assert len(pre) == p["count"]
    assert all(len(r.prompt) == p["prompt_tokens"] for r in pre)
    chunks = -(-p["prompt_tokens"] // 64)
    # what request i still has to decode once the last one is prefilled
    left = [r.max_tokens - chunks * (len(pre) - 1 - i)
            for i, r in enumerate(pre)]
    assert left[0] == p["retire_from"] and left[-1] == p["retire_to"]
    assert left == sorted(left) and len(set(left)) == len(left)
    assert traffic.preload(_file("doc_backlog"), 50257, 1, 64) == []


def test_every_cell_has_its_files(spec):
    for w in spec["workloads"]:
        assert traffic.load(core.BENCH_DIR, w["traffic"])["kind"] in (
            "open_loop", "closed_loop", "train_job")
    for m in spec["per_layer"]:
        assert os.path.exists(core.reader_path(m["name"]))
    with pytest.raises(FileNotFoundError):
        traffic.load(core.BENCH_DIR, "no_such_mix")
    with pytest.raises(FileNotFoundError):
        core.reader_path("no.such_metric.itl")
