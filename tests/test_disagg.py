"""Disaggregated prefill/decode serving suite (ISSUE 19): the
pool-split fleet with content-addressed KV-block shipping.

Runs as its own seeded CI suite (``serving-disagg`` in
ci/gen_pipeline.py, owns this file exclusively). The headline pins:

* disaggregated generation (prefill pool -> KV transfer -> decode
  pool) is **bit-identical** to colocated, for greedy AND seeded
  sampling, logprobs included;
* a warm shared-prefix request moves **zero** KV bytes (the
  content-addressed offer dedups against the decode replica's index);
* the seeded ``disagg.transfer`` drill — the prefill side dying
  mid-transfer — recovers via decode-side re-prefill with zero
  client-visible errors and bit-identical output.
"""

import json
import socket

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from urllib.error import HTTPError
from urllib.request import Request, urlopen

from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import serving
from horovod_tpu import tracing
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving import fleet
from horovod_tpu.serving.batcher import (DEADLINE_HEADER,
                                         DEADLINE_STAGE_HEADER)
from horovod_tpu.serving.disagg import (pack_blocks, prompt_manifest,
                                        pull_and_import, unpack_blocks)
from horovod_tpu.serving.generation import GenerationEngine

SEED = 1234

CFG = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                        num_heads=2, head_dim=16, max_seq_len=96,
                        dtype=jnp.float32)

#: 19 tokens over block_size 4: a 4-block (16-token) manifest plus a
#: 3-token tail the decode side prefills itself
PROMPT = [3, 11, 42, 7, 19, 5, 23, 8, 31, 4, 17, 29, 2, 40, 13, 22, 9,
          35, 6]
BLOCK_SIZE = 4
MANIFEST_BLOCKS = (len(PROMPT) - 1) // BLOCK_SIZE

#: restrictive non-greedy sampling — the hard case for transfer parity
SAMPLED = dict(temperature=0.9, top_k=12, top_p=0.85, seed=77)

TB = "hvd_tpu_disagg_transfer_bytes_total"
TS = "hvd_tpu_disagg_transfer_seconds"
HIT_TRANSFER = 'hvd_tpu_gen_prefix_cache_hit_tokens_total' \
    '{source="transfer"}'
SHED_TRANSFER = 'hvd_tpu_serving_deadline_stage_total{stage="transfer"}'


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    F.configure("", seed=0)


@pytest.fixture(scope="module")
def model_params():
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _gen_engine(model, params, **kw):
    kw.setdefault("block_size", BLOCK_SIZE)
    kw.setdefault("num_blocks", 49)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("deadline_ms", 0)
    return GenerationEngine(model, params=params, **kw)


def _replica(model, params, **kw):
    srv = serving.InferenceServer(
        None, port=0, addr="127.0.0.1",
        gen_engine=_gen_engine(model, params, **kw))
    srv.start()
    return srv


def _router(replicas, **kw):
    kw.setdefault("addr", "127.0.0.1")
    r = fleet.FleetRouter(replicas, port=0, **kw)
    r.start()
    return r


def _post(url, doc, headers=None, timeout=60):
    req = Request(url, data=json.dumps(doc).encode(), method="POST",
                  headers={"Content-Type": "application/json",
                           **(headers or {})})
    try:
        with urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(url, timeout=10):
    with urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _stream(url, doc, headers=None, timeout=120):
    req = Request(url, data=json.dumps(doc).encode(), method="POST",
                  headers={"Content-Type": "application/json",
                           **(headers or {})})
    with urlopen(req, timeout=timeout) as resp:
        return [json.loads(line) for line in resp if line.strip()]


def _delta(before, key):
    return M.snapshot().get(key, 0) - before.get(key, 0)


def _dead_port():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    return port


def _baseline(model, params, **sample):
    """Colocated ground truth: (tokens, rounded logprobs)."""
    eng = _gen_engine(model, params)
    try:
        seq = eng.submit(PROMPT, max_tokens=8, **sample)
        toks = eng.result(seq)
        return toks, [round(x, 6) for x in seq.logprobs]
    finally:
        eng.close()


class _Fleet:
    """One prefill replica + one decode replica behind a pooled router."""

    def __init__(self, model, params, prefill_url=None, **router_kw):
        self.pre = None if prefill_url else _replica(model, params,
                                                     role="prefill")
        self.dec = _replica(model, params, role="decode")
        self.router = _router(
            {"p0": prefill_url or f"http://127.0.0.1:{self.pre.port}",
             "d0": f"http://127.0.0.1:{self.dec.port}"},
            pools={"p0": "prefill", "d0": "decode"}, **router_kw)
        self.url = self.router.url

    def close(self):
        self.router.stop()
        if self.pre is not None:
            self.pre.close()
        self.dec.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

class TestWire:
    def test_prompt_manifest_matches_engine_hashes(self, model_params):
        model, params = model_params
        eng = _gen_engine(model, params)
        try:
            hashes = prompt_manifest(PROMPT, BLOCK_SIZE)
            assert len(hashes) == MANIFEST_BLOCKS
            assert eng.kv_manifest(PROMPT) == hashes
        finally:
            eng.close()

    def test_pack_unpack_native_is_bit_identical(self):
        rng = np.random.RandomState(SEED)
        k = rng.randn(2, 3, 4, 2, 16).astype(np.float32)
        v = rng.randn(2, 3, 4, 2, 16).astype(np.float32)
        hashes = ["h0", "h1", "h2"]
        doc = pack_blocks(hashes, (k, v), "native")
        json.dumps(doc)    # must be wire-serializable as-is
        out_h, (out_k, out_v), nbytes = unpack_blocks(doc)
        assert out_h == hashes
        assert out_k.dtype == np.float32
        assert np.array_equal(out_k, k) and np.array_equal(out_v, v)
        assert nbytes == k.nbytes + v.nbytes

    def test_pack_bf16_halves_the_wire(self):
        rng = np.random.RandomState(SEED)
        k = rng.randn(1, 2, 4, 2, 16).astype(np.float32)
        v = rng.randn(1, 2, 4, 2, 16).astype(np.float32)
        doc = pack_blocks(["h0", "h1"], (k, v), "bf16")
        out_h, (out_k, out_v), nbytes = unpack_blocks(doc)
        assert out_h == ["h0", "h1"]
        assert str(out_k.dtype) == "bfloat16"
        assert nbytes == (k.nbytes + v.nbytes) // 2
        # bf16 round-trip of bf16-representable values is lossless
        exact = np.asarray(k).astype(jnp.bfloat16)
        assert np.array_equal(np.asarray(out_k),
                              np.asarray(exact))

    def test_empty_and_bad_dtype(self):
        assert unpack_blocks(pack_blocks([], None)) \
            == ([], None, 0)
        with pytest.raises(ValueError):
            pack_blocks(["h"], (np.zeros((1, 1, 2, 1, 4)),
                                np.zeros((1, 1, 2, 1, 4))), "fp8")


# ---------------------------------------------------------------------------
# engine-level export/import round trip
# ---------------------------------------------------------------------------

class TestExportImport:
    def test_round_trip_is_bit_identical_and_counts_transfer_hits(
            self, model_params):
        model, params = model_params
        a = _gen_engine(model, params)
        b = _gen_engine(model, params)
        try:
            base = a.generate(PROMPT, max_tokens=8)
            hashes = a.kv_manifest(PROMPT)
            served, rows = a.kv_export(hashes)
            assert served == hashes and len(served) == MANIFEST_BLOCKS
            # exporting must not corrupt the exporter: its own stats
            # still sum to capacity and the blocks stay matchable
            assert sum(a.allocator.stats().values()) \
                == a.allocator.capacity
            assert a.kv_probe(hashes) == MANIFEST_BLOCKS

            held, imported = b.kv_import(hashes, served, rows)
            assert (held, imported) == (0, MANIFEST_BLOCKS)
            assert b.kv_probe(hashes) == MANIFEST_BLOCKS
            assert b.allocator.remote_blocks == MANIFEST_BLOCKS
            # imported blocks park cached (LRU) with refcount released
            assert b.allocator.in_use == 0
            assert b.allocator.cached_blocks >= MANIFEST_BLOCKS
            assert sum(b.allocator.stats().values()) \
                == b.allocator.capacity

            before = M.snapshot()
            assert b.generate(PROMPT, max_tokens=8) == base
            # zero prefill debt for the manifest span: the admission
            # hit is attributed to the transfer source
            assert _delta(before, HIT_TRANSFER) \
                == MANIFEST_BLOCKS * BLOCK_SIZE
        finally:
            a.close()
            b.close()

    def test_double_import_of_same_hashes_dedups(self, model_params):
        model, params = model_params
        a = _gen_engine(model, params)
        b = _gen_engine(model, params)
        try:
            a.generate(PROMPT, max_tokens=4)
            hashes = a.kv_manifest(PROMPT)
            served, rows = a.kv_export(hashes)
            assert b.kv_import(hashes, served, rows) \
                == (0, MANIFEST_BLOCKS)
            stats = b.allocator.stats()
            # the second import of the identical manifest matches
            # everything and writes nothing
            assert b.kv_import(hashes, served, rows) \
                == (MANIFEST_BLOCKS, 0)
            assert b.allocator.stats() == stats
            assert b.allocator.remote_blocks == MANIFEST_BLOCKS
        finally:
            a.close()
            b.close()

    def test_pull_and_import_degrades_on_dead_source(self, model_params):
        """The mid-transfer host-loss shape: the offer names a source
        that stopped existing — the decode side reports the degraded
        transfer and serves correctly via local re-prefill."""
        model, params = model_params
        b = _gen_engine(model, params)
        try:
            hashes = prompt_manifest(PROMPT, BLOCK_SIZE)
            before = M.snapshot()
            res = pull_and_import(
                b, hashes, source=f"http://127.0.0.1:{_dead_port()}",
                request_id="t-dead", timeout=0.5)
            assert res["held"] == 0 and res["imported"] == 0
            assert res["bytes"] == 0 and res["error"]
            assert _delta(before, TB) == 0
            base, _ = _baseline(model, params)
            assert b.generate(PROMPT, max_tokens=8) == base
        finally:
            b.close()


# ---------------------------------------------------------------------------
# pooled fleet: bit parity, zero-byte warm transfers, health docs
# ---------------------------------------------------------------------------

class TestDisaggFleetParity:
    def test_greedy_and_seeded_parity_and_warm_zero_bytes(
            self, model_params):
        model, params = model_params
        base_greedy, base_greedy_lp = _baseline(model, params)
        base_sampled, base_sampled_lp = _baseline(model, params,
                                                  **SAMPLED)
        with _Fleet(model, params) as fl:
            b0 = M.snapshot()
            code, doc, _ = _post(fl.url + "/v1/generate",
                                 {"prompt": PROMPT, "max_tokens": 8})
            assert code == 200
            assert doc["tokens"] == base_greedy
            assert doc["logprobs"] == base_greedy_lp
            cold_bytes = _delta(b0, TB)
            assert cold_bytes > 0
            assert _delta(b0, TS) > 0
            assert _delta(b0, HIT_TRANSFER) \
                == MANIFEST_BLOCKS * BLOCK_SIZE

            # warm shared prefix: the offer matches every hash on the
            # decode replica — ZERO bytes move
            b1 = M.snapshot()
            code, doc, _ = _post(fl.url + "/v1/generate",
                                 {"prompt": PROMPT, "max_tokens": 8})
            assert code == 200 and doc["tokens"] == base_greedy
            assert _delta(b1, TB) == 0

            # seeded sampling rides the same transferred blocks and
            # still matches colocated bit-for-bit, logprobs included
            code, doc, _ = _post(fl.url + "/v1/generate",
                                 dict({"prompt": PROMPT,
                                       "max_tokens": 8}, **SAMPLED))
            assert code == 200
            assert doc["tokens"] == base_sampled
            assert doc["logprobs"] == base_sampled_lp

    def test_streaming_path_is_bit_identical(self, model_params):
        model, params = model_params
        base, base_lp = _baseline(model, params, **SAMPLED)
        with _Fleet(model, params) as fl:
            recs = _stream(fl.url + "/v1/generate/stream",
                           dict({"prompt": PROMPT, "max_tokens": 8},
                                **SAMPLED))
            assert [r["t"] for r in recs if "t" in r] == base
            assert [r["lp"] for r in recs if "t" in r] == base_lp
            assert [r for r in recs if "error" in r] == []
            assert recs[-1].get("done") is True

    def test_health_docs_report_role_and_pools(self, model_params):
        model, params = model_params
        with _Fleet(model, params) as fl:
            pre_doc = _get(f"http://127.0.0.1:{fl.pre.port}/healthz")
            dec_doc = _get(f"http://127.0.0.1:{fl.dec.port}/healthz")
            assert pre_doc["disagg_role"] == "prefill"
            assert dec_doc["disagg_role"] == "decode"
            for path in ("/healthz", "/fleet/health"):
                doc = _get(fl.url + path)
                assert doc["disagg"] is True
                assert doc["pools"] == {"prefill": 1, "decode": 1}
                assert doc["replicas"]["p0"]["pool"] == "prefill"
                assert doc["replicas"]["d0"]["pool"] == "decode"
                # the narrowest pool bounds admission capacity
                assert doc["admission"]["pools"] == doc["pools"]
                assert doc["admission"]["total"] \
                    == min(doc["pools"].values()) \
                    * doc["admission"]["per_replica"]

    def test_colocated_role_is_default_and_fleet_reports_no_pools(
            self, model_params):
        model, params = model_params
        eng = _gen_engine(model, params)
        try:
            assert eng.role == "colocated"
        finally:
            eng.close()
        srv = _replica(model, params)
        router = _router({"r0": f"http://127.0.0.1:{srv.port}"})
        try:
            doc = _get(router.url + "/fleet/health")
            assert doc["disagg"] is False and "pools" not in doc
        finally:
            router.stop()
            srv.close()

    def test_spans_cover_offer_transfer_admit(self, model_params,
                                              monkeypatch):
        model, params = model_params
        monkeypatch.setenv("HVD_TPU_TRACE_SAMPLE", "1")
        tracing.reset()
        tr = tracing.tracer()
        rid = "d15a66a7e5f60718"
        try:
            with _Fleet(model, params) as fl:
                code, doc, _ = _post(
                    fl.url + "/v1/generate",
                    {"prompt": PROMPT, "max_tokens": 4},
                    headers={"X-HVD-TPU-Request-Id": rid})
                assert code == 200
                names = [s["name"] for s in tr.spans(rid)]
                for want in ("router.route", "disagg.offer",
                             "server.kv_offer", "disagg.transfer",
                             "disagg.admit", "server.kv_fetch"):
                    assert want in names, (want, names)
        finally:
            tracing.reset()


# ---------------------------------------------------------------------------
# deadline propagation: the transfer stage
# ---------------------------------------------------------------------------

class TestTransferStage:
    def test_offer_sheds_spent_budget_as_transfer_stage(
            self, model_params):
        model, params = model_params
        srv = _replica(model, params, role="decode")
        try:
            before = M.snapshot()
            code, doc, headers = _post(
                f"http://127.0.0.1:{srv.port}/v1/kv/offer",
                {"hashes": prompt_manifest(PROMPT, BLOCK_SIZE),
                 "source": "http://127.0.0.1:1"},
                headers={DEADLINE_HEADER: "0"})
            assert code == 429
            assert headers.get(DEADLINE_STAGE_HEADER) == "transfer"
            assert doc["stage"] == "transfer"
            assert _delta(before, SHED_TRANSFER) == 1
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# the seeded mid-transfer kill drill
# ---------------------------------------------------------------------------

class TestTransferDrill:
    def test_mid_transfer_fault_recovers_bit_identical(
            self, model_params):
        """THE drill: the prefill->decode pull dies mid-transfer
        (injected ``disagg.transfer`` fault — the prefill replica's
        death as seen from the decode side). The decode replica
        re-prefills locally; the client stream completes with zero
        error records and bit-identical tokens."""
        model, params = model_params
        base, base_lp = _baseline(model, params, **SAMPLED)
        with _Fleet(model, params) as fl:
            before = M.snapshot()
            F.configure("disagg.transfer:error:times=1", seed=SEED)
            recs = _stream(fl.url + "/v1/generate/stream",
                           dict({"prompt": PROMPT, "max_tokens": 8},
                                **SAMPLED))
            F.configure("", seed=0)
            assert [r for r in recs if "error" in r] == []
            assert recs[-1].get("done") is True
            assert [r["t"] for r in recs if "t" in r] == base
            assert [r["lp"] for r in recs if "t" in r] == base_lp
            # the aborted pull moved nothing and admitted nothing as
            # transferred — the decode pool paid local prefill instead
            assert _delta(before, TB) == 0
            assert _delta(before, HIT_TRANSFER) == 0

            # with the fault exhausted, the next cold prompt transfers
            # normally again
            b1 = M.snapshot()
            other = PROMPT[::-1]
            code, doc, _ = _post(fl.url + "/v1/generate",
                                 {"prompt": other, "max_tokens": 4})
            assert code == 200
            assert _delta(b1, TB) > 0

    def test_prefill_pool_death_degrades_to_cold_decode(
            self, model_params):
        """The whole prefill pool unreachable: the router's prestage
        degrades and forwards cold to the decode pool — still zero
        client-visible errors, still bit-identical."""
        model, params = model_params
        base, base_lp = _baseline(model, params)
        with _Fleet(model, params,
                    prefill_url=f"http://127.0.0.1:{_dead_port()}") as fl:
            before = M.snapshot()
            code, doc, _ = _post(fl.url + "/v1/generate",
                                 {"prompt": PROMPT, "max_tokens": 8})
            assert code == 200
            assert doc["tokens"] == base
            assert doc["logprobs"] == base_lp
            assert _delta(before, TB) == 0
