"""Metrics & telemetry suite (metrics.py — the third observability
pillar next to timeline.py and stall.py).

Covers registry semantics (counter/gauge/histogram, labels, thread
safety, snapshot determinism), Prometheus text-format exposition
(rendered AND parsed back), the HTTP endpoint, instrumented hot paths
actually moving metrics (allreduce bumps op count/bytes/latency; the
response cache bumps hits/misses/evictions), the cross-rank
``metrics_allgather_summary()`` (single-process here; the real
multi-process round trip runs in test_multiprocess_metrics below), and
lifecycle wiring through ``init()``/``shutdown()``.

The default registry is process-global (counters survive re-init by
design), so tests against it assert DELTAS, never absolute values;
registry-semantics tests use fresh private Registry instances.
"""

import re
import socket
import threading

import numpy as np
import pytest

from horovod_tpu import metrics as M


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = M.Registry()
        c = reg.counter("c_total", "a counter")
        c.inc()
        c.inc(2.5)
        assert c.get() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

        g = reg.gauge("g", "a gauge")
        g.set(7)
        g.inc(3)
        g.dec(1)
        assert g.get() == 9.0

        h = reg.histogram("h_seconds", "a histogram",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        counts, total_sum, total = h._children[()].read()
        assert counts == (1, 1, 1, 1)       # one per bucket incl. +Inf
        assert total == 4
        assert total_sum == pytest.approx(55.55)

    def test_observe_n_is_n_observations_of_one_value(self):
        reg = M.Registry()
        h = reg.histogram("hn_seconds", "", buckets=(0.1, 1.0))
        one = reg.histogram("h1_seconds", "", buckets=(0.1, 1.0))
        h.observe(0.05)
        h._children[()].observe_n(0.5, 3)
        h._children[()].observe_n(7.0, 0)       # nothing
        for v in (0.05, 0.5, 0.5, 0.5):
            one.observe(v)
        counts, total_sum, total = h._children[()].read()
        want = one._children[()].read()
        assert (counts, total) == (want[0], want[2]) == ((1, 3, 0), 4)
        assert total_sum == pytest.approx(want[1])

    def test_histogram_le_boundary_is_inclusive(self):
        """Prometheus le semantics: an observation equal to a bound lands
        in that bound's bucket."""
        reg = M.Registry()
        h = reg.histogram("hb", "", buckets=(1.0, 2.0))
        h.observe(1.0)
        h.observe(2.0)
        counts, _, total = h._children[()].read()
        assert counts == (1, 1, 0) and total == 2

    def test_labels(self):
        reg = M.Registry()
        fam = reg.counter("ops_total", "by op", labels=("op",))
        fam.labels(op="allreduce").inc(3)
        fam.labels(op="broadcast").inc()
        assert fam.labels(op="allreduce").get() == 3
        # same labelvalues -> same child object (cached)
        assert fam.labels(op="allreduce") is fam.labels(op="allreduce")
        with pytest.raises(ValueError):
            fam.labels(wrong="x")
        with pytest.raises(ValueError):
            fam.labels()

    def test_registration_idempotent_and_type_checked(self):
        reg = M.Registry()
        a = reg.counter("x_total", "x")
        b = reg.counter("x_total", "x")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total", "now a gauge?")
        with pytest.raises(ValueError):
            reg.counter("x_total", "different labels", labels=("op",))
        # histogram bucket layout is part of the identity: silently
        # reusing the old layout would misfile the caller's observations
        reg.histogram("h_seconds", "", buckets=(0.1, 1.0))
        assert reg.histogram("h_seconds", "", buckets=(1.0, 0.1)) \
            is not None   # same bounds, any order
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h_seconds", "", buckets=(0.5, 1.0))
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h_seconds", "")   # default buckets != explicit

    def test_native_resolution_is_lazy(self, monkeypatch):
        """Registering families (which happens at module import across
        the package) must not touch the native loader — `import
        horovod_tpu` would otherwise trigger a synchronous C++ build."""
        calls = []
        monkeypatch.setattr(
            M, "_native_get", lambda: (calls.append(1), None)[1])
        reg = M.Registry()
        c = reg.counter("lazy_total", "")
        g = reg.gauge("lazy_g", "")
        h = reg.histogram("lazy_h", "", buckets=(1.0,))
        assert calls == []            # construction resolves nothing
        c.inc()
        g.set(1)
        h.observe(0.5)
        assert calls                  # first use resolves
        assert c.get() == 1 and h._children[()].read()[2] == 1

    def test_disabled_registry_is_noop(self):
        reg = M.Registry()
        c = reg.counter("c_total", "")
        h = reg.histogram("h", "", buckets=(1.0,))
        reg.enabled = False
        c.inc(100)
        h.observe(5)
        reg.enabled = True
        assert c.get() == 0
        assert reg.snapshot()["h"]["count"] == 0

    def test_thread_safety_exact_counts(self):
        """Concurrent increments from 8 threads lose nothing — the
        registry's one job under a multi-threaded dispatcher."""
        reg = M.Registry()
        c = reg.counter("c_total", "")
        g = reg.gauge("g", "")
        h = reg.histogram("h", "", buckets=(0.5,))
        n_threads, per_thread = 8, 5000

        def work():
            for i in range(per_thread):
                c.inc()
                g.inc()
                h.observe(i % 2)   # alternates both buckets

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * per_thread
        assert c.get() == total
        assert g.get() == total
        counts, _, seen = h._children[()].read()
        assert seen == total and sum(counts) == total

    def test_snapshot_deterministic_and_plain(self):
        reg = M.Registry()
        reg.counter("b_total", "").inc()
        reg.gauge("a", "").set(1)
        reg.histogram("c_seconds", "", labels=("op",),
                      buckets=(1.0,)).labels(op="x").observe(0.5)
        s1, s2 = reg.snapshot(), reg.snapshot()
        assert s1 == s2
        assert list(s1) == sorted(s1)
        assert s1["a"] == 1.0 and s1["b_total"] == 1.0
        hist = s1['c_seconds{op="x"}']
        assert hist["count"] == 1 and hist["buckets"]["+Inf"] == 1
        # histograms snapshot cumulatively
        assert hist["buckets"]["1"] == 1


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.e+-]+|NaN|[+-]Inf)$")


def _parse_prometheus(text: str) -> dict:
    """Minimal text-format 0.0.4 parser: every non-comment line must be a
    valid sample; returns {series: float}."""
    out = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out, types


class TestPrometheusExposition:
    def test_render_parses_and_is_complete(self):
        reg = M.Registry()
        reg.counter("ops_total", "ops by verb", labels=("op",)) \
            .labels(op="allreduce").inc(3)
        reg.gauge("depth", "queue depth").set(2)
        h = reg.histogram("lat_seconds", "latency", labels=("op",),
                          buckets=(0.1, 1.0))
        h.labels(op="allreduce").observe(0.05)
        h.labels(op="allreduce").observe(0.5)
        h.labels(op="allreduce").observe(5.0)

        text = reg.render_prometheus()
        samples, types = _parse_prometheus(text)
        assert types == {"ops_total": "counter", "depth": "gauge",
                         "lat_seconds": "histogram"}
        assert samples['ops_total{op="allreduce"}'] == 3
        assert samples["depth"] == 2
        # cumulative buckets, monotone, closed by +Inf == _count
        assert samples['lat_seconds_bucket{op="allreduce",le="0.1"}'] == 1
        assert samples['lat_seconds_bucket{op="allreduce",le="1"}'] == 2
        assert samples['lat_seconds_bucket{op="allreduce",le="+Inf"}'] == 3
        assert samples['lat_seconds_count{op="allreduce"}'] == 3
        assert samples['lat_seconds_sum{op="allreduce"}'] == \
            pytest.approx(5.55)
        assert "# HELP ops_total ops by verb" in text

    def test_label_escaping(self):
        reg = M.Registry()
        reg.counter("e_total", "", labels=("name",)) \
            .labels(name='we"ird\\x\ny').inc()
        text = reg.render_prometheus()
        assert r'name="we\"ird\\x\ny"' in text

    def test_http_endpoint_roundtrip(self):
        import urllib.request
        reg = M.Registry()
        reg.counter("served_total", "").inc(7)
        port = _free_port()
        server = M.start_http_server(port, addr="127.0.0.1", registry=reg)
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10)
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            samples, _ = _parse_prometheus(resp.read().decode())
            assert samples["served_total"] == 7
            # unknown paths 404 rather than serving metrics
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10)
        finally:
            M.stop_http_server(server)
        # endpoint is really down
        with pytest.raises(OSError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2)


# ---------------------------------------------------------------------------
# instrumented paths move the default-registry series
# ---------------------------------------------------------------------------

def _series(name, snap=None):
    snap = snap if snap is not None else M.snapshot()
    return snap.get(name, 0.0)


class TestInstrumentation:
    def test_allreduce_bumps_ops_bytes_latency(self, hvd_world):
        before = M.snapshot()
        x = np.ones((32, 8), np.float32)
        hvd_world.allreduce(x, name="metrics.ar")
        hvd_world.allreduce(x, name="metrics.ar2")
        after = M.snapshot()
        key_ops = 'hvd_tpu_collective_ops_total{op="allreduce"}'
        key_bytes = 'hvd_tpu_collective_bytes_total{op="allreduce"}'
        key_lat = 'hvd_tpu_collective_dispatch_seconds{op="allreduce"}'
        assert after[key_ops] - _series(key_ops, before) == 2
        assert after[key_bytes] - _series(key_bytes, before) == 2 * x.nbytes
        assert after[key_lat]["count"] - before[key_lat]["count"] == 2
        assert after[key_lat]["sum"] > before[key_lat]["sum"]

    def test_every_verb_is_instrumented(self, hvd_world):
        before = M.snapshot()
        x = np.arange(8, dtype=np.float32)
        hvd_world.allgather(x, name="metrics.ag")
        hvd_world.broadcast(x, root_rank=0, name="metrics.bc")
        hvd_world.alltoall(x, name="metrics.a2a")
        hvd_world.grouped_allreduce([x, x], name="metrics.gar")
        hvd_world.grouped_broadcast([x, x], root_rank=0, name="metrics.gbc")
        after = M.snapshot()
        for verb, nbytes in [("allgather", x.nbytes), ("broadcast", x.nbytes),
                             ("alltoall", x.nbytes),
                             ("grouped_allreduce", 2 * x.nbytes),
                             ("grouped_broadcast", 2 * x.nbytes)]:
            ops = f'hvd_tpu_collective_ops_total{{op="{verb}"}}'
            byt = f'hvd_tpu_collective_bytes_total{{op="{verb}"}}'
            assert after[ops] - _series(ops, before) == 1, verb
            assert after[byt] - _series(byt, before) == nbytes, verb

    def test_optimizer_steps_counter(self, hvd_world):
        import optax
        key = "hvd_tpu_optimizer_steps_total"
        before = _series(key)
        opt = hvd_world.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": np.ones((4,), np.float32)}
        state = opt.init(params)
        for _ in range(3):
            _updates, state = opt.update(
                {"w": np.ones((4,), np.float32)}, state, params)
        assert _series(key) - before == 3

    def test_response_cache_hits_misses_evictions(self):
        from horovod_tpu.response_cache import ResponseCache
        h0 = _series("hvd_tpu_response_cache_hits_total")
        m0 = _series("hvd_tpu_response_cache_misses_total")
        e0 = _series("hvd_tpu_response_cache_evictions_total")
        cache = ResponseCache(capacity=2)
        assert not cache.lookup(1)          # miss
        cache.put(1)
        assert cache.lookup(1)              # hit
        cache.put(2)
        cache.put(3)                        # evicts 1 (capacity 2)
        assert not cache.lookup(1)          # miss (evicted)
        assert _series("hvd_tpu_response_cache_hits_total") - h0 == 1
        assert _series("hvd_tpu_response_cache_misses_total") - m0 == 2
        assert _series("hvd_tpu_response_cache_evictions_total") - e0 == 1

    def test_dispatcher_queue_depth_settles_to_zero(self, hvd_world):
        for i in range(5):
            hvd_world.allreduce(np.ones((4,), np.float32),
                                name=f"metrics.qd.{i}")
        # sync collectives: queue fully drained by each synchronize
        assert _series("hvd_tpu_dispatcher_queue_depth") == 0

    def test_lifecycle_counters_and_endpoint_via_init(self):
        import urllib.request

        import horovod_tpu as hvd
        if hvd.is_initialized():
            hvd.shutdown()
        port = _free_port()
        i0 = _series("hvd_tpu_init_total")
        s0 = _series("hvd_tpu_shutdown_total")
        hvd.init(config_overrides={"METRICS_PORT": port,
                                   "METRICS_ADDR": "127.0.0.1"})
        try:
            assert _series("hvd_tpu_init_total") - i0 == 1
            assert _series("hvd_tpu_world_size") == 1
            hvd.allreduce(np.ones((4,), np.float32), name="metrics.ep")
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
            samples, types = _parse_prometheus(text)
            assert types["hvd_tpu_collective_ops_total"] == "counter"
            assert samples['hvd_tpu_collective_ops_total{op="allreduce"}'] >= 1
            assert types["hvd_tpu_collective_dispatch_seconds"] == "histogram"
        finally:
            hvd.shutdown()
        assert _series("hvd_tpu_shutdown_total") - s0 == 1
        # shutdown stops the endpoint
        with pytest.raises(OSError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2)

    def test_out_of_range_port_warns_instead_of_killing_init(self, caplog):
        """Metrics are advisory: a bad HVD_TPU_METRICS_PORT (>65535
        raises OverflowError, not OSError) must log and continue, not
        crash hvd.init()."""
        import horovod_tpu as hvd
        if hvd.is_initialized():
            hvd.shutdown()
        hvd.init(config_overrides={"METRICS_PORT": 70000})
        try:
            assert hvd.is_initialized()
            from horovod_tpu import basics
            assert basics.world().metrics_server is None
        finally:
            hvd.shutdown()

    def test_metrics_disabled_via_knob(self):
        import horovod_tpu as hvd
        if hvd.is_initialized():
            hvd.shutdown()
        key = 'hvd_tpu_collective_ops_total{op="allreduce"}'
        hvd.init(config_overrides={"METRICS": False})
        try:
            before = _series(key)
            hvd.allreduce(np.ones((4,), np.float32), name="metrics.off")
            assert _series(key) == before
        finally:
            hvd.shutdown()
            # re-arm the process-global registry for later tests
            M.REGISTRY.enabled = True

    def test_timeline_observes_itself(self, tmp_path):
        import horovod_tpu as hvd
        if hvd.is_initialized():
            hvd.shutdown()
        key = "hvd_tpu_timeline_events_total"
        before = _series(key)
        hvd.init(config_overrides={"TIMELINE": str(tmp_path / "tl.json")})
        try:
            hvd.allreduce(np.ones((4,), np.float32), name="metrics.tl")
        finally:
            hvd.shutdown()
        assert _series(key) > before


# ---------------------------------------------------------------------------
# cross-rank summary
# ---------------------------------------------------------------------------

class TestSummary:
    def test_aggregate_merges_scalars_and_histograms(self):
        a = {"c_total": 3.0,
             "h": {"buckets": {"1": 1, "+Inf": 2}, "sum": 5.0, "count": 2}}
        b = {"c_total": 7.0,
             "h": {"buckets": {"1": 0, "+Inf": 1}, "sum": 9.0, "count": 1},
             "only_b": 1.0}
        agg = M.aggregate([a, b])
        assert agg["c_total"] == {"sum": 10.0, "min": 3.0, "max": 7.0}
        assert agg["h"] == {"buckets": {"1": 1, "+Inf": 3},
                            "sum": 14.0, "count": 3}
        assert agg["only_b"] == {"sum": 1.0, "min": 1.0, "max": 1.0}

    def test_single_process_roundtrip(self, hvd_world):
        hvd_world.allreduce(np.ones((4,), np.float32), name="metrics.sum1")
        summary = hvd_world.metrics_allgather_summary()
        assert len(summary["per_rank"]) == 1
        snap = summary["per_rank"][0]
        key = 'hvd_tpu_collective_ops_total{op="allreduce"}'
        assert snap[key] >= 1
        agg = summary["aggregate"][key]
        assert agg["min"] == agg["max"] == agg["sum"] == snap[key]


class TestRobustnessMetrics:
    """The fault-injection / retry / recovery series (ISSUE 2): chaos runs
    must be observable, and recovery activity must be visible launcher-side."""

    def test_fault_injection_counter(self):
        from horovod_tpu import faults as F
        key = 'hvd_tpu_faults_injected_total{site="mtest.site",kind="delay"}'
        before = _series(key)
        F.configure("mtest.site:delay=0.0", seed=1)
        try:
            F.FaultPoint("mtest.site").fire()
        finally:
            F.configure("", seed=0)
        assert _series(key) - before == 1

    def test_retry_attempt_and_exhausted_counters(self):
        from horovod_tpu import retry as R
        a_key = 'hvd_tpu_retry_attempts_total{site="mtest.retry"}'
        a0 = _series(a_key)
        x0 = _series("hvd_tpu_retry_exhausted_total")
        pol = R.RetryPolicy(max_attempts=3, initial_backoff=0.0,
                            sleep=lambda s: None)
        with pytest.raises(ConnectionError):
            pol.call(lambda: (_ for _ in ()).throw(ConnectionError("x")),
                     site="mtest.retry")
        assert _series(a_key) - a0 == 2          # retries, not first call
        assert _series("hvd_tpu_retry_exhausted_total") - x0 == 1

    def test_blacklisted_hosts_gauge_moves_on_failure(self):
        """Registry barrier action blacklists the failing host and updates
        the gauge (driver simulation, no processes — test_elastic.py
        pattern)."""
        import time as _t

        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.elastic.driver import ElasticDriver

        class _Rdv:
            def init(self, a):
                pass

            def stop(self):
                pass

        key = "hvd_tpu_elastic_blacklisted_hosts"
        driver = ElasticDriver(_Rdv(), FixedHosts({"h1": 1, "h2": 1}),
                               min_np=1, max_np=2, timeout=10)

        def create_worker(slot_info, events):
            if slot_info.hostname == "h2":
                return 1, _t.time()
            driver.record_ready("h1", 0)
            return 0, _t.time()

        driver.start(2, create_worker)
        driver.get_results()
        assert driver._host_manager.is_blacklisted("h2")
        # gauge reflects the CURRENT count for this driver's job
        assert _series(key) == 1
        driver.stop()

    def test_worker_restarts_counter(self, monkeypatch):
        """reset() outside an elastic launch (in-process shutdown+init)
        ticks hvd_tpu_worker_restarts_total."""
        import horovod_tpu as hvd
        from horovod_tpu.elastic.run import reset

        for var in ("HVD_TPU_ELASTIC", "HVD_TPU_RENDEZVOUS_ADDR"):
            monkeypatch.delenv(var, raising=False)
        key = "hvd_tpu_worker_restarts_total"
        before = _series(key)
        if hvd.is_initialized():
            hvd.shutdown()
        hvd.init()
        try:
            reset()
        finally:
            hvd.shutdown()
        assert _series(key) - before == 1


@pytest.mark.integration
@pytest.mark.parametrize("n", [2, 4])
def test_multiprocess_metrics(n):
    """The real cross-rank round trip: N processes rendezvous through the
    JAX coordinator (the test_multiprocess_integration pattern), run a
    collective mix plus a deliberately skewed local counter, and every
    rank verifies metrics_allgather_summary(); rank 0 also scrapes its
    own Prometheus endpoint."""
    import os
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__), "metrics_worker.py")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(worker)))
    port = _free_port()
    metrics_port = _free_port()
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": str(n),
            "HVD_TPU_RANK": str(pid),
            "HVD_TPU_METRICS_PORT": str(metrics_port),
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        text = out.decode(errors="replace")
        assert p.returncode == 0, \
            f"worker {i} failed (exit {p.returncode}):\n{text[-4000:]}"
        assert f"worker {i} OK" in text
