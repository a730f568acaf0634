"""Per-request distributed tracing suite (ISSUE 16).

Owned exclusively by the seeded ``observability`` CI suite
(ci/gen_pipeline.py): span lifecycle and context propagation units, the
zero-overhead-when-disabled contract, histogram exemplar linkage, the
bounded timeline writer, the ``tools.trace`` merger, and the seeded
2-process drill that pushes one request id through the real fleet
router -> replica -> generation path plus a cross-rank eager collective
and asserts a single merged cross-host timeline.
"""

import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time
from urllib.request import Request, urlopen

import numpy as np
import pytest

from horovod_tpu import config as _config
from horovod_tpu import metrics as M
from horovod_tpu import timeline
from horovod_tpu import tracing
from tools import trace as trace_tool

WORKER = os.path.join(os.path.dirname(__file__), "tracing_drill_worker.py")
SEED = 1234
RID = "feedc0dedeadbeef"


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracing.reset()
    yield
    tracing.reset()


def _on(monkeypatch, trace_dir=None, rate="1"):
    """Enable the tracer through the real knobs and re-resolve."""
    monkeypatch.setenv("HVD_TPU_TRACE_SAMPLE", rate)
    if trace_dir is not None:
        monkeypatch.setenv("HVD_TPU_TRACE_DIR", str(trace_dir))
    tracing.reset()
    tr = tracing.tracer()
    assert tr is not None
    return tr


# ---------------------------------------------------------------------------
# sampling + context plumbing
# ---------------------------------------------------------------------------

class TestSampling:
    def test_rate_bounds(self):
        assert not tracing.sampled("abc", 0.0)
        assert not tracing.sampled("", 1.0)
        assert tracing.sampled("abc", 1.0)

    def test_deterministic_and_hash_seed_independent(self):
        """The decision is a pure function of the id (sha1, not
        ``hash()``), so every process in a fleet agrees."""
        import hashlib
        rid = "a1b2c3d4e5f60718"
        expect = int(hashlib.sha1(rid.encode()).hexdigest()[:8], 16) \
            / float(0x100000000) < 0.5
        for _ in range(3):
            assert tracing.sampled(rid, 0.5) == expect

    def test_rate_is_roughly_the_traced_fraction(self):
        ids = [f"req{i:08x}" for i in range(2000)]
        hits = sum(tracing.sampled(i, 0.25) for i in ids)
        assert 0.18 < hits / len(ids) < 0.32

    def test_request_id_shapes_match(self):
        """Server-minted ids and router-minted ids are the same 16-hex
        shape, so either side can originate a trace."""
        rid = tracing.new_request_id()
        assert len(rid) == 16 and int(rid, 16) >= 0


class TestContext:
    def test_encode_decode_roundtrip(self):
        ctx = tracing.TraceContext("tid01", "span02")
        out = tracing.TraceContext.decode(ctx.encode())
        assert (out.trace_id, out.span_id) == ("tid01", "span02")

    def test_decode_rejects_garbage(self):
        for raw in (None, "", "no-separator", ":orphan", 42):
            assert tracing.TraceContext.decode(raw) is None

    def test_set_current_returns_previous(self):
        a = tracing.TraceContext("t", "a")
        b = tracing.TraceContext("t", "b")
        assert tracing.set_current(a) is None
        assert tracing.set_current(b) is a
        assert tracing.current() is b


# ---------------------------------------------------------------------------
# span lifecycle (tracer on)
# ---------------------------------------------------------------------------

class TestSpans:
    def test_root_and_child_span(self, monkeypatch):
        tr = _on(monkeypatch)
        with tracing.request_span("server.infer", RID,
                                  args={"rows": 2}) as root:
            assert tracing.current().span_id == root.span_id
            with tracing.span("batch.queue"):
                pass
        assert tracing.current() is None
        spans = {s["name"]: s for s in tr.spans(RID)}
        assert set(spans) == {"server.infer", "batch.queue"}
        child, parent = spans["batch.queue"], spans["server.infer"]
        assert child["trace"] == parent["trace"] == RID
        assert child["parent"] == parent["span"]
        assert parent["parent"] is None
        assert parent["args"] == {"rows": 2}
        assert parent["dur"] >= child["dur"] >= 0
        assert parent["ts"] <= child["ts"]
        assert parent["rank"] == 0

    def test_parent_header_nests_across_hops(self, monkeypatch):
        tr = _on(monkeypatch)
        upstream = tracing.TraceContext(RID, "routerspan000001")
        with tracing.request_span("server.generate", RID,
                                  parent=upstream.encode()):
            pass
        (span,) = tr.spans(RID)
        assert span["parent"] == "routerspan000001"

    def test_parent_header_for_other_trace_is_ignored(self, monkeypatch):
        tr = _on(monkeypatch)
        foreign = tracing.TraceContext("othertrace", "x").encode()
        with tracing.request_span("server.infer", RID, parent=foreign):
            pass
        (span,) = tr.spans(RID)
        assert span["parent"] is None

    def test_exception_annotates_and_restores(self, monkeypatch):
        tr = _on(monkeypatch)
        with pytest.raises(RuntimeError):
            with tracing.request_span("server.infer", RID):
                raise RuntimeError("boom")
        (span,) = tr.spans(RID)
        assert "boom" in span["args"]["error"]
        assert tracing.current() is None

    def test_emit_span_maps_monotonic_onto_epoch(self, monkeypatch):
        tr = _on(monkeypatch)
        ctx = tracing.TraceContext(RID, "parent0000000001")
        t0 = time.monotonic() - 0.2
        before = time.time() * 1e6
        tracing.emit_span(ctx, "batch.queue", t0, t0 + 0.15,
                          args={"rows": 1})
        (span,) = tr.spans(RID)
        assert span["parent"] == "parent0000000001"
        assert 0.10e6 < span["dur"] < 0.20e6
        # started ~200ms before "now" on the epoch clock
        assert before - 0.5e6 < span["ts"] < before - 0.1e6

    def test_collective_hook_binds_to_current_span(self, monkeypatch):
        tr = _on(monkeypatch)
        with tracing.request_span("server.infer", RID) as root:
            tracing.collective(("allreduce", "dense_1", (4,), "f32"))
        names = [s["name"] for s in tr.spans(RID)]
        assert "collective:allreduce:dense_1" in names
        coll = next(s for s in tr.spans(RID)
                    if s["name"].startswith("collective:"))
        assert coll["parent"] == root.span_id

    def test_collective_hook_without_context_is_silent(self, monkeypatch):
        tr = _on(monkeypatch)
        tracing.collective(("allreduce", "untraced", (4,), "f32"))
        assert tr.spans() == []

    def test_ring_is_bounded(self, monkeypatch):
        tr = _on(monkeypatch)
        ctx = tracing.TraceContext(RID, "p")
        for i in range(tracing._BUFFER_DEPTH + 50):
            t = time.monotonic()
            tracing.emit_span(ctx, f"s{i}", t, t)
        assert len(tr.spans()) == tracing._BUFFER_DEPTH

    def test_span_file_written_and_loadable(self, monkeypatch, tmp_path):
        tr = _on(monkeypatch, trace_dir=tmp_path)
        with tracing.request_span("server.infer", RID):
            with tracing.span("batch.forward"):
                pass
        path = tr.span_path
        tracing.reset()        # closes the writer -> file complete
        assert path == str(tmp_path / "spans-rank0.jsonl")
        spans = trace_tool.load_span_file(path)
        assert {s["name"] for s in spans} == {"server.infer",
                                              "batch.forward"}


# ---------------------------------------------------------------------------
# the zero-overhead contract (tracer off — the default)
# ---------------------------------------------------------------------------

class TestDisabled:
    def test_default_sample_rate_is_off(self):
        assert tracing.tracer() is None

    def test_all_helpers_return_the_null_singleton(self):
        assert tracing.request_span("server.infer", RID) \
            is tracing._NULL_SPAN
        assert tracing.span("x") is tracing._NULL_SPAN
        assert tracing.span_for(tracing.TraceContext(RID, "s"), "x") \
            is tracing._NULL_SPAN

    def test_null_span_never_installs_context(self):
        with tracing.request_span("server.infer", RID) as sp:
            assert tracing.current() is None
            assert not sp.sampled and sp.span_id is None
            sp.annotate(rows=1)
            assert sp.context() is None
        tracing.collective(("allreduce", "g", (2,), "f32"))
        tracing.emit_span(None, "x", time.monotonic())

    def test_request_noted_even_when_untraced(self):
        """Failure attribution (StallError, preemption logs) must not
        depend on the sampling knob."""
        with tracing.request_span("server.infer", "req42"):
            pass
        assert tracing.last_request_id() == "req42"

    def test_unsampled_request_is_null_even_with_tracer_on(self,
                                                           monkeypatch):
        monkeypatch.setenv("HVD_TPU_TRACE_SAMPLE", "0.5")
        tracing.reset()
        assert tracing.tracer() is not None
        rid = next(r for r in (f"probe{i:011x}" for i in range(200))
                   if not tracing.sampled(r, 0.5))
        assert tracing.request_span("server.infer", rid) \
            is tracing._NULL_SPAN


# ---------------------------------------------------------------------------
# the micro-batcher path: retroactive spans + latency exemplars
# ---------------------------------------------------------------------------

class TestBatcherIntegration:
    def test_batch_spans_and_exemplars(self, monkeypatch):
        from horovod_tpu.serving.batcher import _M_LATENCY, MicroBatcher
        tr = _on(monkeypatch)
        mb = MicroBatcher(lambda x, n: x, max_batch=4, timeout_ms=1.0,
                          queue_depth=8, default_deadline_ms=0,
                          row_shape=(2,))
        try:
            with tracing.request_span("server.infer", RID):
                out = mb.infer(np.ones((1, 2), np.float32), timeout=30)
            assert out.shape == (1, 2)
        finally:
            mb.stop()
        names = {s["name"] for s in tr.spans(RID)}
        assert {"server.infer", "batch.queue", "batch.forward"} <= names
        # both latency phases carry the request's trace id as exemplar
        for phase in ("queue", "forward"):
            ex = _M_LATENCY.labels(phase=phase).exemplar()
            assert ex is not None and ex[0] == RID, (phase, ex)

    def test_untraced_request_leaves_no_exemplar(self, monkeypatch):
        """exemplar=None must not clobber a previously stored one."""
        from horovod_tpu.serving.fleet.tenancy import _M_QUEUE_WAIT
        h = _M_QUEUE_WAIT.labels(tenant="ex-test")
        h.observe(1.0, exemplar=RID)
        h.observe(2.0)                 # untraced: no exemplar argument
        assert h.exemplar() == (RID, 1.0)


# ---------------------------------------------------------------------------
# request-id attribution in failure paths
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_stall_error_names_the_in_flight_request(self):
        from horovod_tpu import stall
        from horovod_tpu.exceptions import StallError

        class _World:
            config = _config.Config({_config.STALL_CHECK_DISABLE: True})

        insp = stall.StallInspector(_World())
        try:
            insp._shutdown_deadline_hit = True
            insp._divergence_hint = "ledger hint"
            tracing.note_request("req7777")
            with pytest.raises(StallError, match=r"request req7777 in "
                                                 r"flight"):
                insp.check_shutdown()
        finally:
            insp.stop()


# ---------------------------------------------------------------------------
# the bounded timeline/tracer record writer
# ---------------------------------------------------------------------------

class TestRecordWriter:
    def test_overflow_drops_and_counts(self, monkeypatch, tmp_path):
        release = threading.Event()
        orig = timeline.RecordWriter._drain

        def stalled_drain(self):
            release.wait(10)       # a "dead disk" until released
            orig(self)

        monkeypatch.setattr(timeline.RecordWriter, "_drain", stalled_drain)
        before = M.snapshot().get("hvd_tpu_timeline_dropped_total", 0)
        w = timeline.RecordWriter(str(tmp_path / "t.jsonl"), mode="jsonl",
                                  maxsize=2)
        accepted = sum(w.put({"i": i}) for i in range(5))
        assert accepted == 2
        assert M.snapshot()["hvd_tpu_timeline_dropped_total"] \
            == before + 3
        release.set()
        assert w.close()
        recs = trace_tool.load_span_file(str(tmp_path / "t.jsonl"))
        assert recs == []          # dropped records carried no 'trace'
        with open(tmp_path / "t.jsonl") as f:
            assert [json.loads(l) for l in f if l.strip()] \
                == [{"i": 0}, {"i": 1}]

    def test_bound_resolves_from_the_knob(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HVD_TPU_TIMELINE_QUEUE_EVENTS", "7")
        w = timeline.RecordWriter(str(tmp_path / "k.jsonl"), mode="jsonl")
        assert w._q.maxsize == 7
        assert w.close()

    def test_chrome_mode_streams_an_array(self, tmp_path):
        w = timeline.RecordWriter(str(tmp_path / "c.json"), mode="chrome")
        w.put({"name": "e1", "ph": "X"})
        w.put({"name": "e2", "ph": "X"})
        assert w.close()
        doc = json.loads((tmp_path / "c.json").read_text())
        assert [e.get("name") for e in doc if e] == ["e1", "e2"]


# ---------------------------------------------------------------------------
# the tools.trace merger
# ---------------------------------------------------------------------------

def _span(name, rank, ts, span_id, parent=None, trace=RID, dur=5.0):
    return {"trace": trace, "span": span_id, "parent": parent,
            "name": name, "rank": rank, "ts": ts, "dur": dur}


class TestMerger:
    SPANS = [
        _span("server.generate", 0, 200.0, "s2", parent="s1"),
        _span("router.route", 0, 100.0, "s1"),
        _span("collective:allreduce:g", 1, 300.0, "s3", parent="s2"),
        _span("other", 0, 50.0, "x1", trace="othertrace"),
        _span("router.route", 0, 100.0, "s1"),     # duplicate (KV + file)
    ]

    def test_merge_filters_dedupes_orders(self):
        doc = trace_tool.merge(RID, self.SPANS)
        assert trace_tool.span_names(doc) == [
            "router.route", "server.generate", "collective:allreduce:g"]
        assert doc["otherData"] == {"trace_id": RID, "spans": 3,
                                    "ranks": [0, 1]}
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["pid"] for e in events] == [0, 0, 1]
        assert events[1]["args"]["parent_id"] == "s1"
        lanes = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {e["args"]["name"] for e in lanes} == {"rank 0", "rank 1"}

    def test_merge_unknown_trace_is_empty(self):
        doc = trace_tool.merge("nope", self.SPANS)
        assert trace_tool.span_names(doc) == []

    def test_cli_round_trip(self, tmp_path, capsys):
        f0, f1 = tmp_path / "r0.jsonl", tmp_path / "r1.jsonl"
        f0.write_text("\n".join(json.dumps(s) for s in self.SPANS[:2])
                      + "\n{truncated")
        f1.write_text(json.dumps(self.SPANS[2]) + "\n")
        out = tmp_path / "merged.json"
        rc = trace_tool.main(["--trace-id", RID, str(f0), str(f1),
                              "-o", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert trace_tool.span_names(doc) == [
            "router.route", "server.generate", "collective:allreduce:g"]
        capsys.readouterr()
        assert trace_tool.main(["--trace-id", "nope", str(f0)]) == 1


# ---------------------------------------------------------------------------
# serving front-end: request-id echo on every response
# ---------------------------------------------------------------------------

def _post(url, body=b"{}", headers=None, timeout=30):
    req = Request(url, data=body, method="POST",
                  headers={"Content-Type": "application/json",
                           **(headers or {})})
    try:
        with urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except Exception as e:                         # noqa: BLE001
        if hasattr(e, "read") and hasattr(e, "code"):
            return e.code, json.loads(e.read() or b"{}"), dict(e.headers)
        raise


class TestRequestIdEcho:
    @pytest.fixture()
    def server(self):
        from horovod_tpu import serving
        eng = serving.InferenceEngine(
            lambda p, x: x, params={"w": np.ones(2, np.float32)},
            max_batch=4, batch_timeout_ms=1.0, deadline_ms=0,
            reload_poll_seconds=0, warmup=False)
        srv = serving.InferenceServer(eng, port=0, addr="127.0.0.1")
        srv.start()
        yield srv
        srv.close()

    def test_success_echoes_client_id(self, server):
        code, doc, headers = _post(
            f"http://127.0.0.1:{server.port}/v1/infer",
            json.dumps({"inputs": [[1.0, 2.0]]}).encode(),
            headers={"X-HVD-TPU-Request-Id": RID})
        assert code == 200
        assert headers["X-HVD-TPU-Request-Id"] == RID

    def test_error_body_carries_generated_id(self, server):
        """No client id, a 400: the server mints one and stamps BOTH
        the header and the error body."""
        code, doc, headers = _post(
            f"http://127.0.0.1:{server.port}/v1/infer", b'{"bad": 1}')
        assert code == 400
        rid = headers.get("X-HVD-TPU-Request-Id")
        assert rid and len(rid) == 16
        assert doc["request_id"] == rid

    def test_404_carries_the_id_too(self, server):
        code, doc, headers = _post(
            f"http://127.0.0.1:{server.port}/v1/nope", b"{}",
            headers={"X-HVD-TPU-Request-Id": RID})
        assert code == 404
        assert headers["X-HVD-TPU-Request-Id"] == RID
        assert doc["request_id"] == RID


# ---------------------------------------------------------------------------
# generation: deadline attribution through the scheduler
# ---------------------------------------------------------------------------

class TestGenerationAttribution:
    def test_deadline_error_names_the_request(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models.transformer import (Transformer,
                                                    TransformerConfig)
        from horovod_tpu.serving.batcher import DeadlineExceededError
        from horovod_tpu.serving.generation import GenerationEngine
        cfg = TransformerConfig(vocab_size=32, num_layers=1, d_model=16,
                                num_heads=2, head_dim=8, max_seq_len=32,
                                dtype=jnp.float32)
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        eng = GenerationEngine(model, params=params, block_size=4,
                               num_blocks=17, max_seqs=2, prefill_chunk=4,
                               deadline_ms=0, reload_poll_seconds=0)
        try:
            seq = eng.submit([1, 2, 3], max_tokens=2, deadline_ms=0.001,
                             request_id="reqdl01")
            with pytest.raises(DeadlineExceededError,
                               match=r"request reqdl01"):
                eng.result(seq, timeout=60)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# the seeded 2-process drill: one request id, one merged timeline
# ---------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_drill(n, per_proc_env, timeout=300):
    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(WORKER)))
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH",
                                                           ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": str(n),
            "HVD_TPU_RANK": str(pid),
        })
        env.update(per_proc_env(pid))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs, codes = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode(errors="replace"))
        codes.append(p.returncode)
    return codes, outs


@pytest.mark.integration
def test_tracing_drill_two_proc(tmp_path):
    """One request id through the real router -> replica -> generation
    path on rank 0, handed off to rank 1 for a shared eager collective:
    ``tools.trace`` must assemble ONE ordered cross-host timeline —
    routing, admission, server, every prefill chunk, decode steps, and
    the named collective on BOTH ranks — from the span files and again
    from the rendezvous KV scope."""
    from horovod_tpu.runner.rendezvous import KVStoreServer

    server = KVStoreServer(port=0)
    kv_port = server.start()
    trace_dir = tmp_path / "spans"
    try:
        def env_for(pid):
            return {
                "HVD_TPU_LOCAL_RANK": "0",
                "HVD_TPU_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVD_TPU_RENDEZVOUS_PORT": str(kv_port),
                "HVD_TPU_TRACE_SAMPLE": "1",
                "HVD_TPU_TRACE_DIR": str(trace_dir),
                "TRACING_DRILL_TRACE_ID": RID,
            }

        codes, outs = _launch_drill(2, env_for)
        assert codes == [0, 0], "\n===\n".join(outs)
        assert all("NSPANS" in o for o in outs), outs

        files = sorted(glob.glob(str(trace_dir / "spans-rank*.jsonl")))
        assert [os.path.basename(f) for f in files] == [
            "spans-rank0.jsonl", "spans-rank1.jsonl"]
        spans = [s for f in files for s in trace_tool.load_span_file(f)]
        doc = trace_tool.merge(RID, spans)
        names = trace_tool.span_names(doc)

        # every layer reported, in start-time order
        for earlier, later in zip(
                ["router.route", "router.admission", "server.generate",
                 "gen.prefill", "gen.decode"],
                ["router.admission", "server.generate", "gen.prefill",
                 "gen.decode", "collective:allreduce:drill_grad"]):
            assert names.index(earlier) < names.index(later), names
        # 6 prompt tokens / prefill_chunk=4 -> one span per chunk
        assert names.count("gen.prefill") == 2, names
        assert names.count("gen.decode") >= 1, names
        # the collective span landed on BOTH ranks under the same trace
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        coll_ranks = {e["pid"] for e in events
                      if e["name"] == "collective:allreduce:drill_grad"}
        assert coll_ranks == {0, 1}, events
        # the warm-up allreduce ran outside any trace context: no span
        assert not any("warm" in n for n in names), names

        # the live-fleet path: the same timeline assembles from what the
        # ranks published to the rendezvous 'trace' scope
        kv_spans = trace_tool.fetch_kv_spans("127.0.0.1", kv_port)
        kv_doc = trace_tool.merge(RID, kv_spans)
        kv_names = trace_tool.span_names(kv_doc)
        assert names.count("gen.prefill") == kv_names.count("gen.prefill")
        kv_coll = {e["pid"] for e in kv_doc["traceEvents"]
                   if e.get("name") == "collective:allreduce:drill_grad"}
        assert kv_coll == {0, 1}, kv_names
    finally:
        server.stop()
