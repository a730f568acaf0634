"""The generation scheduler's loop spans (tracing.py), the histograms
derived from them, the request-scope waits and the compile counters.

Counts and structure only: no test here asserts a duration. Everything
runs in-process on the CPU with the tiny fp32 transformer of the
generation suites.
"""

import collections
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import compile_cache
from horovod_tpu import metrics as M
from horovod_tpu import tracing
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving.generation import GenerationEngine

CFG = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=96,
                        dtype=jnp.float32)

ROOT = "gen.iter"
PARK = "gen.park"
CHILDREN = {"gen.admit", "gen.prefill.prepare", "gen.prefill.dispatch",
            "gen.decode.prepare", "gen.decode.dispatch", "gen.wait",
            "gen.deliver"}
WAITS = ("hvd_tpu_gen_queue_wait_seconds",
         "hvd_tpu_gen_prefill_span_seconds", "hvd_tpu_gen_ttft_seconds")
ITL = "hvd_tpu_gen_itl_seconds"
ITER = "hvd_tpu_gen_iter_seconds"
STEP = "hvd_tpu_gen_step_seconds"


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracing.reset()
    yield
    tracing.reset()


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 49)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("deadline_ms", 0)
    return GenerationEngine(model, params=params, **kw)


def _prompt(rng, n):
    return rng.randint(0, CFG.vocab_size, (n,)).tolist()


def _count(snap, series):
    v = snap.get(series)
    return 0 if v is None else v["count"]


def _sum(snap, series):
    v = snap.get(series)
    return 0.0 if v is None else v["sum"]


def _phase_series(snap):
    return [k for k in snap if k.startswith("hvd_tpu_gen_phase_seconds{")]


def _delta(before, after, family, field="count"):
    """What ``family``'s series took between two snapshots, by the value
    of its one label."""
    return {k.split('"')[1]: after[k][field] - (
        before[k][field] if k in before else 0)
        for k in after if k.startswith(family + "{")}


def _run(model, params, requests, **engine_kw):
    """Serve ``requests`` (submit kwargs) to their end; returns the
    outputs, the loop spans of the run, and the registry before/after."""
    before = M.snapshot()
    t0 = time.perf_counter()
    with _engine(model, params, **engine_kw) as eng:
        seqs = [eng.submit(**kw) for kw in requests]
        outs = [eng.result(s, timeout=240) for s in seqs]
        assert eng.allocator.in_use == 0
    return outs, tracing.loop_spans(t0), before, M.snapshot()


def _check_trees(spans):
    """Every iteration is one root whose descendants carry the seven
    child names, nest inside their parents without overlapping their
    siblings, and whose self times add up to the root exactly; a park is
    a root with nothing under it. Returns the iterations' roots."""
    by_trace = collections.defaultdict(list)
    for s in spans:
        by_trace[s["trace"]].append(s)
    roots = []
    for trace, group in by_trace.items():
        assert trace.startswith("gen-iter:")
        root = [s for s in group if s["parent"] is None]
        if root[0]["name"] == PARK:
            assert len(group) == 1 and not root[0]["args"], group
            continue
        assert len(root) == 1 and root[0]["name"] == ROOT, group
        roots.append(root[0])
        ids = {s["span"]: s for s in group}
        kids = collections.defaultdict(list)
        for s in group:
            if s["parent"] is None:
                continue
            assert s["name"] in CHILDREN, s
            parent = ids[s["parent"]]
            assert parent["start_ns"] <= s["start_ns"] \
                and s["end_ns"] <= parent["end_ns"], (s, parent)
            kids[s["parent"]].append(s)
        for sibs in kids.values():
            sibs.sort(key=lambda s: s["start_ns"])
            for a, b in zip(sibs, sibs[1:]):
                assert a["end_ns"] <= b["start_ns"], (a, b)
        self_ns = sum(
            s["end_ns"] - s["start_ns"]
            - sum(k["end_ns"] - k["start_ns"] for k in kids[s["span"]])
            for s in group)
        assert self_ns == root[0]["end_ns"] - root[0]["start_ns"]
    return roots


def _plain(rng):
    return [dict(prompt=_prompt(rng, n), max_tokens=10) for n in (11, 5, 20)]


def _spec(rng):
    return [dict(prompt=[3, 11, 42, 7, 19, 5, 11, 42, 7], max_tokens=16)]


def _beam(rng):
    return [dict(prompt=_prompt(rng, 20), max_tokens=6, num_beams=2)]


@pytest.mark.parametrize("requests,engine_kw,programs", [
    (_plain, {}, {"prefill", "decode"}),
    (_spec, {"spec_mode": "ngram", "spec_tokens": 4},
     {"prefill", "verify"}),
    (_beam, {"max_beams": 2}, {"beam"}),
], ids=["plain", "speculative", "beam"])
def test_every_iteration_is_one_tree_of_the_eight_names(
        model_params, requests, engine_kw, programs):
    model, params = model_params
    _, spans, before, after = _run(
        model, params, requests(np.random.RandomState(3)), **engine_kw)
    roots = _check_trees(spans)
    names = {s["name"] for s in spans}
    assert names <= CHILDREN | {ROOT, PARK}
    assert {"gen.admit", "gen.prefill.dispatch", "gen.decode.dispatch",
            "gen.wait", "gen.deliver"} <= names
    for r in roots:
        assert set(r["args"]) == {"busy", "running", "waiting", "inflight",
                                  "chunk", "lanes", "emitted"}
    # the other paths use the same names, with the program in the args
    waited = {s["args"]["program"] for s in spans if s["name"] == "gen.wait"}
    assert programs <= waited
    # one gen.iter a busy iteration: what hvd_tpu_gen_step_seconds counts
    busy = sum(1 for r in roots if r["args"]["busy"])
    host = 'hvd_tpu_gen_step_seconds{component="host"}'
    assert busy == _count(after, host) - _count(before, host) > 0
    # an engine coming out of idle is traced, not observed
    assert any(not r["args"]["busy"] for r in roots)


def test_step_seconds_and_phase_seconds_are_the_same_stamps(model_params):
    model, params = model_params
    _, spans, before, after = _run(model, params,
                                   _plain(np.random.RandomState(4)))
    step = sum(_sum(after, k) - _sum(before, k) for k in (
        'hvd_tpu_gen_step_seconds{component="host"}',
        'hvd_tpu_gen_step_seconds{component="device"}'))
    phases = sum(_sum(after, k) - _sum(before, k)
                 for k in _phase_series(after))
    assert step == pytest.approx(phases, rel=1e-9)
    assert {k.split('"')[1] for k in _phase_series(after)} <= {
        n.split(".", 1)[1] for n in CHILDREN | {ROOT}}
    # device is what the gen.wait spans of the busy iterations cover
    busy = {r["trace"] for r in _check_trees(spans) if r["args"]["busy"]}
    waited = sum(s["end_ns"] - s["start_ns"] for s in spans
                 if s["name"] == "gen.wait" and s["trace"] in busy)
    device = 'hvd_tpu_gen_step_seconds{component="device"}'
    assert _sum(after, device) - _sum(before, device) == \
        pytest.approx(waited * 1e-9, rel=1e-9)
    # one observation a phase an iteration in which it ran
    admit = 'hvd_tpu_gen_phase_seconds{phase="admit"}'
    assert _count(after, admit) - _count(before, admit) == len(busy)


def _mixed(rng):
    # the short prompt decodes while the long one prefills, chunk by chunk
    return [dict(prompt=_prompt(rng, 5), max_tokens=24),
            dict(prompt=_prompt(rng, 30), max_tokens=4)]


@pytest.mark.parametrize("requests,engine_kw", [
    (_plain, {}), (_mixed, {}),
    (_spec, {"spec_mode": "ngram", "spec_tokens": 4}),
    (_beam, {"max_beams": 2}),
], ids=["plain", "mixed", "speculative", "beam"])
def test_every_token_but_a_requests_first_is_one_itl_observation(
        model_params, requests, engine_kw):
    model, params = model_params
    outs, spans, before, after = _run(
        model, params, requests(np.random.RandomState(11)), **engine_kw)
    got = _delta(before, after, ITL)
    assert set(got) == {"decode", "prefill", "preempt"}
    assert sum(got.values()) == sum(len(o) - 1 for o in outs) > 0
    assert got["preempt"] == 0
    # what the passes put on streams is what the requests received
    assert sum(r["args"]["emitted"] for r in _check_trees(spans)) == \
        sum(len(o) for o in outs)


def test_a_gap_is_labelled_by_what_the_loop_dispatched_in_it(model_params):
    model, params = model_params
    rng = np.random.RandomState(12)
    # alone, within one chunk: every gap holds decode steps only
    _, _, before, after = _run(
        model, params, [dict(prompt=_prompt(rng, 6), max_tokens=12)])
    assert _delta(before, after, ITL) == {
        "decode": 11, "prefill": 0, "preempt": 0}
    # beside a prompt of four chunks: the gaps of the sequence already
    # decoding carry another sequence's chunks
    outs, spans, before, after = _run(model, params, _mixed(rng))
    got = _delta(before, after, ITL)
    chunks = [s for s in spans if s["name"] == "gen.prefill.dispatch"]
    assert len(chunks) == 5 and got["preempt"] == 0
    assert 1 <= got["prefill"] <= len(chunks)
    assert got["decode"] + got["prefill"] == sum(len(o) - 1 for o in outs)


def test_a_gap_across_a_preemption_is_labelled_preempt(model_params):
    model, params = model_params
    rng = np.random.RandomState(10)
    # the pool of test_a_readmission_after_preemption...: the younger of
    # two sequences is preempted while it decodes, and recomputed
    outs, _, before, after = _run(
        model, params,
        [dict(prompt=_prompt(rng, 6), max_tokens=20) for _ in range(2)],
        num_blocks=10)
    key = "hvd_tpu_gen_preemptions_total"
    preemptions = after[key] - before.get(key, 0)
    got = _delta(before, after, ITL)
    assert 1 <= got["preempt"] <= preemptions
    assert sum(got.values()) == sum(len(o) - 1 for o in outs)


def test_iter_seconds_are_step_seconds_by_what_the_pass_carried(
        model_params):
    model, params = model_params
    _, spans, before, after = _run(model, params,
                                   _mixed(np.random.RandomState(13)))
    counts = _delta(before, after, ITER)
    host = _delta(before, after, STEP)["host"]
    assert set(counts) <= {"decode", "prefill", "both"}
    assert sum(counts.values()) == host > 0
    assert sum(_delta(before, after, ITER, "sum").values()) == \
        pytest.approx(sum(v for k, v in _delta(
            before, after, STEP, "sum").items() if k != "verify"), rel=1e-9)
    # the root's record holds what the histogram's label says
    want = collections.Counter()
    for r in _check_trees(spans):
        a = r["args"]
        if a["busy"]:
            want["both" if a["chunk"] and a["lanes"] else
                 "prefill" if a["chunk"] else "decode"] += 1
    assert {k: v for k, v in counts.items() if v} == dict(want)
    assert want["both"] >= 1 and want["decode"] >= 1


def test_gen_iter_carries_chunk_lanes_and_emitted(model_params):
    model, params = model_params
    requests = _plain(np.random.RandomState(14))
    outs, spans, _, _ = _run(model, params, requests)
    roots = _check_trees(spans)
    by_trace = collections.defaultdict(list)
    for s in spans:
        by_trace[s["trace"]].append(s)
    for r in roots:
        kids = by_trace[r["trace"]]
        assert r["args"]["chunk"] == sum(
            s["args"]["chunk"] for s in kids
            if s["name"] == "gen.prefill.dispatch")
        assert r["args"]["lanes"] == sum(
            s["args"]["lanes"] for s in kids
            if s["name"] == "gen.decode.dispatch")
    assert sum(r["args"]["chunk"] for r in roots) == \
        sum(len(kw["prompt"]) for kw in requests)
    assert sum(r["args"]["emitted"] for r in roots) == \
        sum(len(o) for o in outs)


def test_iter_and_park_records_tile_the_loop_threads_time(model_params):
    model, params = model_params
    _, spans, before, after = _run(model, params,
                                   _plain(np.random.RandomState(15)))
    roots = sorted((s for s in spans if s["parent"] is None),
                   key=lambda s: s["start_ns"])
    assert {s["name"] for s in roots} == {ROOT, PARK}
    # the engine starts with nothing to do, and ends so
    assert roots[0]["name"] == PARK
    for a, b in zip(roots, roots[1:]):
        assert a["end_ns"] == b["start_ns"], (a, b)
        assert a["start_ns"] < a["end_ns"]
    parked = sum(s["end_ns"] - s["start_ns"] for s in roots
                 if s["name"] == PARK)
    key = "hvd_tpu_gen_parked_seconds_total"
    assert after[key] - before.get(key, 0.0) == \
        pytest.approx(parked * 1e-9, rel=1e-9)


@pytest.mark.parametrize("requests,engine_kw", [
    (_mixed, {}), (_spec, {"spec_mode": "ngram", "spec_tokens": 4}),
    (_beam, {"max_beams": 2}),
], ids=["plain", "speculative", "beam"])
def test_a_dispatch_and_its_wait_share_a_flight(model_params, requests,
                                                engine_kw):
    model, params = model_params
    _, spans, _, _ = _run(model, params,
                          requests(np.random.RandomState(16)), **engine_kw)
    dispatches = sorted((s for s in spans if s["name"].endswith(".dispatch")),
                        key=lambda s: s["start_ns"])
    flights = [s["args"]["flight"] for s in dispatches]
    # one counter a batcher, in the order of dispatch
    assert flights == list(range(flights[0], flights[0] + len(flights)))
    by_flight = dict(zip(flights, dispatches))
    waits = [s for s in spans if s["name"] == "gen.wait"]
    assert waits and len({s["args"]["flight"] for s in waits}) == len(waits)
    for w in waits:
        d = by_flight[w["args"]["flight"]]
        assert d["end_ns"] <= w["start_ns"]
        assert d["args"].get("program", "prefill") == w["args"]["program"]
    # a chunk that is not a prompt's last is dispatched and never awaited
    awaited = {w["args"]["flight"] for w in waits}
    for d in dispatches:
        if d["name"] == "gen.prefill.dispatch":
            last = d["args"]["prefilled"] + d["args"]["chunk"] \
                == d["args"]["total"]
            # (a beam request's last chunk leaves its token unread)
            assert (d["args"]["flight"] in awaited) == (
                last and "max_beams" not in engine_kw)


def _two_humps(rng, n, decode_ms, chunk_ms, chunk_share):
    """Gaps as an ITL cell shows them: most a decode iteration, a share
    a decode iteration and a prefill chunk, each hump a few per cent
    wide."""
    chunk = rng.random_sample(n) < chunk_share
    return np.where(chunk, rng.normal(chunk_ms, 0.12 * chunk_ms, n),
                    rng.normal(decode_ms, 0.04 * decode_ms, n)) * 1e-3


@pytest.mark.parametrize("decode_ms,chunk_ms,chunk_share", [
    (12.6, 24.0, 0.054), (12.6, 24.0, 0.14),
    (44.0, 80.0, 0.14), (44.0, 80.0, 0.08),
], ids=["chat", "chat-more-chunks", "longcat", "longcat-fewer-chunks"])
def test_itl_buckets_hold_the_90th_percentile_within_3_per_cent(
        decode_ms, chunk_ms, chunk_share):
    from perfbench.harness import gaps as reader

    gaps = _two_humps(np.random.RandomState(17), 20000, decode_ms,
                      chunk_ms, chunk_share)
    hist = M.histogram("hvd_tpu_test_itl_seconds", "a test",
                       buckets=M.REGISTRY._families[ITL]._buckets)
    before = M.snapshot()["hvd_tpu_test_itl_seconds"]["buckets"]
    for g in gaps:
        hist.observe(g)
    after = M.snapshot()["hvd_tpu_test_itl_seconds"]["buckets"]
    got = reader.quantile({le: n - before[le] for le, n in after.items()},
                          90)
    assert got == pytest.approx(np.percentile(gaps, 90), rel=0.03)


def test_prefill_dispatch_spans_name_the_chunk_and_the_request(model_params):
    model, params = model_params
    rng = np.random.RandomState(5)
    before = time.perf_counter()
    with _engine(model, params) as eng:
        seq = eng.submit(_prompt(rng, 19), max_tokens=2,
                         request_id="req-19")
        eng.result(seq, timeout=240)
    chunks = [s["args"] for s in tracing.loop_spans(before)
              if s["name"] == "gen.prefill.dispatch"]
    assert [(c["chunk"], c["prefilled"], c["total"]) for c in chunks] == \
        [(8, 0, 19), (8, 8, 19), (3, 16, 19)]
    assert all(c["request"] == "req-19" and c["seq"] == seq.id
               for c in chunks)


def test_request_waits_are_observed_once_a_request(model_params):
    model, params = model_params
    n = 5
    rng = np.random.RandomState(6)
    _, _, before, after = _run(
        model, params,
        [dict(prompt=_prompt(rng, 6 + i), max_tokens=4) for i in range(n)],
        max_seqs=2)
    for series in WAITS:
        assert _count(after, series) - _count(before, series) == n, series
    assert _sum(after, WAITS[2]) - _sum(before, WAITS[2]) == pytest.approx(
        sum(_sum(after, k) - _sum(before, k) for k in WAITS[:2]))


def test_a_readmission_after_preemption_is_no_second_observation(
        model_params):
    model, params = model_params
    rng = np.random.RandomState(10)
    # 2 sequences x (6 prompt + 20 generated) need 7 blocks each; a
    # 9-block pool cannot hold both, so the younger one is preempted
    _, _, before, after = _run(
        model, params,
        [dict(prompt=_prompt(rng, 6), max_tokens=20) for _ in range(2)],
        num_blocks=10)
    key = "hvd_tpu_gen_preemptions_total"
    assert after[key] - before.get(key, 0) >= 1
    for series in WAITS:
        assert _count(after, series) - _count(before, series) == 2, series


def test_wait_histograms_reach_a_minute():
    for series in WAITS:
        buckets = M.snapshot()[series]["buckets"]
        assert max(float(b) for b in buckets if b != "+Inf") >= 60.0


def test_a_sampled_request_gains_a_queue_span_and_an_unsampled_none(
        model_params, monkeypatch):
    model, params = model_params
    rng = np.random.RandomState(7)
    with _engine(model, params) as eng:
        with tracing.request_span("server.generate", "rid-off"):
            eng.result(eng.submit(_prompt(rng, 5), max_tokens=2,
                                  request_id="rid-off"), timeout=240)
        assert tracing.tracer() is None     # HVD_TPU_TRACE_SAMPLE=0
        monkeypatch.setenv("HVD_TPU_TRACE_SAMPLE", "1")
        tracing.reset()
        with tracing.request_span("server.generate", "rid-on"):
            eng.result(eng.submit(_prompt(rng, 5), max_tokens=2,
                                  request_id="rid-on"), timeout=240)
    names = [s["name"] for s in tracing.tracer().spans("rid-on")]
    assert names.count("gen.queue") == 1 and "gen.prefill" in names
    assert tracing.tracer().spans("rid-off") == []


def test_the_ring_is_bounded_and_read_since_an_instant():
    depth = tracing._LOOP_RING_DEPTH
    assert tracing._LOOP_RING.maxlen == depth
    loop = tracing.LoopTrace("t.iter")
    assert loop.span("t.outside").__enter__().dur_ns == 0   # no root open
    for _ in range(depth // 4 + 8):
        with loop.iteration(observe=False):
            with loop.span("t.a"):
                with loop.span("t.b", k=1):
                    pass
            with loop.span("t.c"):
                pass
    assert len(tracing._LOOP_RING) == depth
    mark = time.perf_counter()
    with loop.iteration(n=1):
        with loop.span("t.a") as a:
            a.annotate(late=True)
    assert sum(loop.self_ns.values()) > 0
    got = tracing.loop_spans(mark)
    assert [(s["name"], s["args"]) for s in got] == [
        ("t.a", {"late": True}), ("t.iter", {"n": 1})]
    assert got[0]["parent"] == got[1]["span"] \
        and got[0]["trace"] == got[1]["trace"]
    tracing.reset()
    assert len(tracing._LOOP_RING) == 0


def test_phase_histogram_takes_the_self_times_of_observed_iterations():
    hist = M.histogram("hvd_tpu_test_loop_phase_seconds", "a test",
                       labels=("phase",), buckets=(1.0,))
    loop = tracing.LoopTrace("t.iter", histogram=hist)
    for observe in (True, False, True):
        with loop.iteration(observe=observe):
            with loop.span("t.work"):
                with loop.span("t.inner"):
                    pass
            with loop.span("t.work"):
                pass
    snap = M.snapshot()
    for phase in ("iter", "work", "inner"):
        series = f'hvd_tpu_test_loop_phase_seconds{{phase="{phase}"}}'
        assert snap[series]["count"] == 2, series   # once an iteration


def test_an_iteration_that_raises_closes_its_spans():
    loop = tracing.LoopTrace("t.iter")
    mark = time.perf_counter()
    with pytest.raises(ValueError):
        with loop.iteration():
            with loop.span("t.a"):
                raise ValueError("boom")
    assert [s["name"] for s in tracing.loop_spans(mark)] == [
        "t.a", "t.iter"]
    assert loop.span("t.later") is loop.span("t.later")     # the null span


def test_loop_spans_are_profiler_annotations_under_hvd(monkeypatch):
    opened = []

    class Annotation:
        is_enabled = staticmethod(lambda: True)    # a session is on

        def __init__(self, name, **kw):
            opened.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    loop = tracing.LoopTrace("t.iter")
    with loop.iteration(running=2):
        with loop.span("t.a", program="decode"):
            pass
    assert opened == [("hvd.t.iter", {"running": 2}),
                      ("hvd.t.a", {"program": "decode"})]


def test_compile_counters_count_what_jax_builds():
    compile_cache.ensure_compile_cache()
    compile_cache.ensure_compile_cache()        # registers once
    before = M.snapshot()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7)).block_until_ready()
    after = M.snapshot()
    assert after["hvd_tpu_compile_total"] \
        - before["hvd_tpu_compile_total"] == 1
    assert after["hvd_tpu_compile_seconds_total"] \
        > before["hvd_tpu_compile_seconds_total"]
    assert after["hvd_tpu_compile_cache_misses_total"] \
        >= before["hvd_tpu_compile_cache_misses_total"]
    # the names this jax gives the events the counters listen for
    from jax._src import compilation_cache as cc
    from jax._src import dispatch
    import inspect
    assert dispatch.BACKEND_COMPILE_EVENT == compile_cache.COMPILE_EVENT
    assert compile_cache.CACHE_MISS_EVENT in inspect.getsource(cc)
