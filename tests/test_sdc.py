"""Silent-data-corruption defense tests (CI suite ``chaos-sdc``).

Covers the ``bitflip``/``nan`` fault kinds and the ``worker.grads``
corruption site, the eager and jit step guards (finite/magnitude +
loss-spike EWMA bound), cross-replica parameter fingerprints (fold,
majority diff, live KV publish/compare), the skip/rollback/quarantine
policy, the report codec and its rendezvous routing, the driver's
quarantine path (blacklist reason='sdc', gauge, journal re-seed), the
CheckpointManager last-good promotion, the guarded Estimator loop
(skip-retry bit-identity, auto-rollback, guard-off containment) and —
integration-marked — the seeded 2-process drill: rank 1's gradients are
bit-flipped mid-run, both ranks detect and retry, the offender's
quarantine report lands in the journaled ``sdc`` scope, and the final
parameters are bit-identical to an uninjected run's.
"""

import logging
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from horovod_tpu import _schedule
from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import sdc
from horovod_tpu.sdc import guard as guard_mod
from horovod_tpu.sdc.report import SDC_SCOPE, decode_report, encode_report

SEED = 1234
WORKER = os.path.join(os.path.dirname(__file__), "sdc_train_worker.py")


@pytest.fixture(autouse=True)
def _reset_faults():
    """Every test leaves the process-wide fault registry disabled."""
    yield
    F.configure("", seed=0)


def _counter(name):
    return float(M.snapshot().get(name, 0.0))


def _flatleaves(tree):
    import jax
    return np.concatenate([np.asarray(l).reshape(-1).astype(np.float64)
                           for l in jax.tree_util.tree_leaves(tree)])


class RecordingRendezvous:
    """Driver-facing KV double (mirrors tests/test_preemption.py)."""

    def __init__(self, data=None):
        self.published = []
        self.stopped = False
        self.data = {scope: dict(kv) for scope, kv in (data or {}).items()}
        self.puts = []
        self.deletes = []

    def init(self, assignment_list):
        self.published.append(list(assignment_list))

    def stop(self):
        self.stopped = True

    def put(self, scope, key, value):
        self.data.setdefault(scope, {})[key] = value
        self.puts.append((scope, key, value))

    def delete(self, scope, key):
        self.data.get(scope, {}).pop(key, None)
        self.deletes.append((scope, key))

    def items(self, scope):
        return dict(self.data.get(scope, {}))


# ---------------------------------------------------------------------------
# fault grammar: the bitflip / nan kinds
# ---------------------------------------------------------------------------

class TestFaultGrammar:
    def test_parse_bitflip_with_step_and_rank(self):
        rule = F.parse_spec("worker.grads:bitflip:step=3:rank=1")[0]
        assert rule.kind == "bitflip"
        assert rule.step == 3
        assert rule.rank == 1

    def test_parse_nan(self):
        rule = F.parse_spec("worker.grads:nan:step=7")[0]
        assert rule.kind == "nan"
        assert rule.step == 7

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            F.parse_spec("worker.grads:fliparoo")

    def test_fire_without_corrupt_handler_is_ignored_but_counted(self):
        """A data-corruption rule on a site that passes no ``corrupt``
        handler must not raise — and still counts as injected (the drill
        schedule fired; the site just carries no data)."""
        F.configure("worker.grads:bitflip:once", seed=SEED)
        key = ('hvd_tpu_faults_injected_total'
               '{site="worker.grads",kind="bitflip"}')
        before = _counter(key)
        guard_mod._FP_GRADS.fire()   # no corrupt= handler
        assert _counter(key) == before + 1


# ---------------------------------------------------------------------------
# the worker.grads corruption site
# ---------------------------------------------------------------------------

class TestCorruptGrads:
    def _grads(self):
        import jax.numpy as jnp
        return {"dense": {"kernel": jnp.linspace(0.01, 0.5, 12,
                                                 dtype=jnp.float32),
                          "bias": jnp.full((4,), 0.25, jnp.float32)}}

    def test_no_rule_is_identity(self):
        F.configure("", seed=0)
        grads = self._grads()
        assert sdc.corrupt_grads(grads) is grads

    def test_bitflip_changes_exactly_one_element_deterministically(self):
        grads = self._grads()
        clean = _flatleaves(grads)
        F.configure("worker.grads:bitflip:once", seed=SEED)
        out1 = _flatleaves(sdc.corrupt_grads(grads))
        F.configure("worker.grads:bitflip:once", seed=SEED)
        out2 = _flatleaves(sdc.corrupt_grads(grads))
        # same seed -> identical corruption, and exactly one element hit
        np.testing.assert_array_equal(out1, out2)
        diff = out1 != clean
        assert int(diff.sum()) == 1
        # the flipped exponent bit explodes the magnitude past the
        # guard's limit (that is WHY the drill flips that bit)
        bad = float(np.abs(out1[diff])[0])
        assert not np.isfinite(bad) or bad > guard_mod.GRAD_ABS_LIMIT

    def test_nan_overwrites_one_element(self):
        grads = self._grads()
        F.configure("worker.grads:nan:once", seed=SEED)
        out = _flatleaves(sdc.corrupt_grads(grads))
        assert int(np.isnan(out).sum()) == 1

    def test_bitflip_on_all_zero_leaves_falls_back_to_nan(self):
        """Flipping a zero's exponent yields 2.0 — indistinguishable from
        a real gradient — so degenerate leaves get the NaN overwrite."""
        import jax.numpy as jnp
        grads = {"w": jnp.zeros((8,), jnp.float32)}
        F.configure("worker.grads:bitflip:once", seed=SEED)
        out = _flatleaves(sdc.corrupt_grads(grads))
        assert int(np.isnan(out).sum()) == 1


# ---------------------------------------------------------------------------
# eager step guard
# ---------------------------------------------------------------------------

class TestStepGuard:
    def _guard(self, **kw):
        kw.setdefault("sync", lambda code: code)
        return sdc.StepGuard(**kw)

    def test_nonfinite_gradient_detected(self):
        g = self._guard()
        before = _counter(
            'hvd_tpu_sdc_detections_total{kind="nonfinite"}')
        det = g.check({"w": np.array([1.0, np.nan], np.float32)}, 0.5)
        assert det == sdc.Detection(kind="nonfinite", local=True)
        assert _counter(
            'hvd_tpu_sdc_detections_total{kind="nonfinite"}') == before + 1

    def test_nonfinite_loss_detected(self):
        det = self._guard().check({"w": np.ones(3, np.float32)},
                                  float("inf"))
        assert det is not None and det.kind == "nonfinite"

    def test_out_of_range_magnitude_detected(self):
        """The canonical SDC event — one flipped exponent bit — usually
        stays FINITE; the magnitude bound is the matching detector."""
        g = self._guard()
        det = g.check({"w": np.array([0.1, 1e13], np.float32)}, 0.5)
        assert det is not None and det.kind == "nonfinite"

    def test_integer_leaves_ignored(self):
        det = self._guard().check(
            {"count": np.array([10**15], np.int64),
             "w": np.ones(2, np.float32)}, 0.5)
        assert det is None

    def test_loss_spike_after_warmup(self):
        g = self._guard(loss_spike_factor=10.0)
        assert g.check({"w": np.ones(2, np.float32)}, 1.0) is None
        det = g.check({"w": np.ones(2, np.float32)}, 100.0)
        assert det == sdc.Detection(kind="loss_spike", local=True)

    def test_first_step_never_spikes(self):
        # no EWMA yet: any finite loss is in bound by definition
        g = self._guard(loss_spike_factor=10.0)
        assert g.check({"w": np.ones(2, np.float32)}, 1e6) is None

    def test_ewma_frozen_on_poisoned_steps(self):
        g = self._guard(loss_spike_factor=10.0)
        g.check({"w": np.ones(2, np.float32)}, 1.0)
        ewma = g._ewma
        assert g.check({"w": np.array([np.inf], np.float32)},
                       1.0) is not None
        assert g._ewma == ewma   # a poisoned loss must not widen its bound

    def test_spike_bound_disabled_by_nonpositive_factor(self):
        g = self._guard(loss_spike_factor=0.0)
        assert g.check({"w": np.ones(2, np.float32)}, 1.0) is None
        assert g.check({"w": np.ones(2, np.float32)}, 1e9) is None

    def test_peer_verdict_is_not_local(self):
        """A clean rank whose MAX-allreduced verdict comes back poisoned
        skips the step too — but the strike is NOT charged to it."""
        g = self._guard(sync=lambda code: 2)
        det = g.check({"w": np.ones(2, np.float32)}, 0.5)
        assert det == sdc.Detection(kind="nonfinite", local=False)


# ---------------------------------------------------------------------------
# jit step guard
# ---------------------------------------------------------------------------

class TestGuardUpdateJit:
    def _run(self, grads, loss, ewma):
        import jax
        fn = jax.jit(lambda g, l, e: sdc.guard_update(g, l, e,
                                                      factor=10.0))
        code, new_ewma = fn(grads, loss, ewma)
        return int(code), float(new_ewma)

    def test_clean_step_advances_ewma(self):
        import jax.numpy as jnp
        code, ewma = self._run({"w": jnp.ones(3)}, 2.0, 1.0)
        assert code == 0
        assert ewma == pytest.approx(0.9 * 1.0 + 0.1 * 2.0)

    def test_nonfinite_gradient_code(self):
        import jax.numpy as jnp
        code, ewma = self._run({"w": jnp.array([1.0, jnp.nan])}, 1.0, 1.0)
        assert code == 2
        assert ewma == 1.0   # frozen

    def test_out_of_range_gradient_code(self):
        import jax.numpy as jnp
        code, _ = self._run({"w": jnp.array([1e13])}, 1.0, 1.0)
        assert code == 2

    def test_loss_spike_code_and_frozen_ewma(self):
        import jax.numpy as jnp
        code, ewma = self._run({"w": jnp.ones(3)}, 100.0, 1.0)
        assert code == 1
        assert ewma == 1.0

    def test_warmup_without_ewma(self):
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda g, l: sdc.guard_update(g, l, None,
                                                   factor=10.0))
        code, ewma = fn({"w": jnp.ones(3)}, 7.0)
        assert int(code) == 0 and float(ewma) == 7.0


# ---------------------------------------------------------------------------
# cross-replica fingerprints
# ---------------------------------------------------------------------------

class TestFingerprint:
    def _tree(self):
        import jax.numpy as jnp
        return {"a": jnp.linspace(-1.0, 1.0, 32, dtype=jnp.float32),
                "b": jnp.full((4, 4), 0.5, jnp.float32),
                "steps": np.int64(7)}   # non-inexact: ignored

    def test_fold_is_deterministic_uint32(self):
        fp1 = sdc.fold_fingerprint(self._tree())
        fp2 = sdc.fold_fingerprint(self._tree())
        assert fp1 == fp2
        assert 0 <= fp1 < 2 ** 32

    def test_fold_is_bit_sensitive(self):
        tree = self._tree()
        base = sdc.fold_fingerprint(tree)
        a = np.asarray(tree["a"]).copy()
        bits = a.view(np.uint32)
        bits[5] ^= np.uint32(1)          # one mantissa LSB
        tree["a"] = a
        assert sdc.fold_fingerprint(tree) != base

    def test_diff_names_minority_by_majority_vote(self):
        peers = {0: {"step": 10, "fp": 1, "rank": 0},
                 1: {"step": 10, "fp": 1, "rank": 1},
                 2: {"step": 10, "fp": 2, "rank": 2}}
        ranks, msg = _schedule.diff_sdc_fingerprints(peers, 10)
        assert ranks == [2]
        assert "rank(s) 2" in msg and "at step 10" in msg

    def test_diff_two_rank_tie_charges_the_higher_rank(self):
        # 1-vs-1 tie: the group containing the lowest rank wins the
        # majority, so rank 1 is the one named
        peers = {0: {"step": 4, "fp": 7}, 1: {"step": 4, "fp": 9}}
        ranks, _ = _schedule.diff_sdc_fingerprints(peers, 4)
        assert ranks == [1]

    def test_diff_ignores_stale_steps(self):
        peers = {0: {"step": 10, "fp": 1},
                 1: {"step": 8, "fp": 2}}    # mid-publish at an older step
        assert _schedule.diff_sdc_fingerprints(peers, 10) is None

    def test_diff_agreement_and_singleton_are_none(self):
        agree = {0: {"step": 3, "fp": 5}, 1: {"step": 3, "fp": 5}}
        assert _schedule.diff_sdc_fingerprints(agree, 3) is None
        assert _schedule.diff_sdc_fingerprints(
            {0: {"step": 3, "fp": 5}}, 3) is None

    def test_publish_fetch_diff_through_live_kv(self, monkeypatch):
        from horovod_tpu.runner.rendezvous import KVStoreServer
        server = KVStoreServer(port=0)
        port = server.start()
        try:
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_PORT", str(port))
            _schedule.reset()
            assert _schedule.publish_sdc_fingerprint(5, 123, rank=0) == 0
            assert _schedule.publish_sdc_fingerprint(5, 999, rank=1) == 1
            peers = _schedule.fetch_sdc_fingerprints(2)
            assert set(peers) == {0, 1}
            ranks, msg = _schedule.diff_sdc_fingerprints(peers, 5)
            assert ranks == [1] and "0x0000007b" in msg
        finally:
            server.stop()
            _schedule.reset()

    def test_monitor_disabled_and_off_cadence(self):
        mon = sdc.FingerprintMonitor(every=0)
        assert mon.maybe_check(20, self._tree()) is None
        mon = sdc.FingerprintMonitor(every=4)
        assert mon.maybe_check(3, self._tree()) is None   # off-cadence

    def test_monitor_detects_peer_divergence(self, monkeypatch):
        """Rank 0 of a 2-rank world publishes at step 4 and finds rank
        1's pre-published fingerprint disagreeing: a ``fingerprint``
        detection, NOT charged locally (rank 0 holds the majority)."""
        import json

        from horovod_tpu.runner.rendezvous import KVStoreServer
        server = KVStoreServer(port=0)
        port = server.start()
        try:
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_PORT", str(port))
            monkeypatch.setenv("HVD_TPU_SIZE", "2")
            monkeypatch.setenv("HVD_TPU_RANK", "0")
            _schedule.reset()
            tree = self._tree()
            fp = sdc.fold_fingerprint(tree)
            server.put("schedule", "sdc.fp.rank1",
                       json.dumps({"step": 4, "fp": fp ^ 1,
                                   "rank": 1}).encode())
            before = _counter(
                'hvd_tpu_sdc_detections_total{kind="fingerprint"}')
            mon = sdc.FingerprintMonitor(every=4)
            det = mon.maybe_check(4, tree)
            assert det == sdc.Detection(kind="fingerprint", local=False)
            assert _counter(
                'hvd_tpu_sdc_detections_total{kind="fingerprint"}') \
                == before + 1
        finally:
            server.stop()
            _schedule.reset()

    def test_monitor_single_process_is_local_only(self, monkeypatch):
        """world size 1: the fingerprint is published (an external
        observer can read it) but never compared."""
        monkeypatch.delenv("HVD_TPU_SIZE", raising=False)
        _schedule.reset()
        try:
            mon = sdc.FingerprintMonitor(every=2)
            assert mon.maybe_check(2, self._tree()) is None
        finally:
            _schedule.reset()

    def test_fingerprint_diverged_jit(self):
        import jax
        import jax.numpy as jnp
        fps = jnp.array([7, 7, 9, 7], jnp.uint32)
        out = jax.pmap(
            lambda fp: sdc.fingerprint_diverged(fp, "world"),
            axis_name="world", devices=jax.devices()[:4])(fps) \
            if jax.device_count() >= 4 else None
        if out is None:
            pytest.skip("needs 4 devices")
        assert bool(np.all(np.asarray(out)))


# ---------------------------------------------------------------------------
# reaction policy
# ---------------------------------------------------------------------------

class TestPolicy:
    def _det(self, kind="nonfinite", local=True):
        return sdc.Detection(kind=kind, local=local)

    def test_first_trip_skips_second_rolls_back(self):
        p = sdc.SdcPolicy(confirm_steps=1, strikes=99,
                          report=lambda k, s: True)
        assert p.on_detection(self._det()) == sdc.SKIP
        assert p.on_detection(self._det()) == sdc.ROLLBACK

    def test_fingerprint_divergence_rolls_back_immediately(self):
        # parameters already poisoned: skipping forward cannot unpoison
        p = sdc.SdcPolicy(confirm_steps=1, strikes=99,
                          report=lambda k, s: True)
        assert p.on_detection(self._det("fingerprint")) == sdc.ROLLBACK

    def test_trips_outside_window_forgotten(self):
        p = sdc.SdcPolicy(confirm_steps=1, strikes=99,
                          report=lambda k, s: True)
        assert p.on_detection(self._det()) == sdc.SKIP
        for _ in range(sdc.policy.WINDOW_STEPS):
            p.on_clean_step()
        # the old trip aged out: this one is a fresh blip, not a pattern
        assert p.on_detection(self._det()) == sdc.SKIP

    def test_confirm_steps_gate_promotion(self):
        p = sdc.SdcPolicy(confirm_steps=2, strikes=99,
                          report=lambda k, s: True)
        p.on_saved(5)
        assert p.on_clean_step() is None      # 1 clean step: not yet
        assert p.on_clean_step() == 5         # 2 clean steps: promoted
        assert p.last_good == 5
        assert _counter("hvd_tpu_sdc_last_good_step") == 5

    def test_promotion_keeps_newest_confirmed(self):
        p = sdc.SdcPolicy(confirm_steps=2, strikes=99,
                          report=lambda k, s: True)
        p.on_saved(1)
        p.on_saved(2)
        assert p.on_clean_step() is None
        assert p.on_clean_step() == 2   # both confirmed: newest wins
        assert p.last_good == 2

    def test_quarantine_report_is_one_shot(self):
        reports = []
        p = sdc.SdcPolicy(confirm_steps=1, strikes=2,
                          report=lambda k, s: reports.append((k, s)))
        p.on_detection(self._det())
        assert reports == []
        p.on_detection(self._det())
        assert reports == [("nonfinite", 2)]
        p.on_detection(self._det())
        assert len(reports) == 1   # the driver quarantines on the first

    def test_peer_detections_never_charge_this_host(self):
        reports = []
        p = sdc.SdcPolicy(confirm_steps=1, strikes=1,
                          report=lambda k, s: reports.append((k, s)))
        p.on_detection(self._det(local=False))
        p.on_detection(self._det(local=False))
        assert reports == []

    def test_rollback_resets_windows_and_counts(self):
        p = sdc.SdcPolicy(confirm_steps=1, strikes=99,
                          report=lambda k, s: True)
        p.on_detection(self._det())
        assert p.on_detection(self._det()) == sdc.ROLLBACK
        before = _counter("hvd_tpu_sdc_rollbacks_total")
        p.on_rollback()
        assert _counter("hvd_tpu_sdc_rollbacks_total") == before + 1
        # the restored state is clean: the trip pattern restarts
        assert p.on_detection(self._det()) == sdc.SKIP


# ---------------------------------------------------------------------------
# report codec
# ---------------------------------------------------------------------------

class TestReportCodec:
    def test_round_trip(self):
        kind, strikes, ts = decode_report(
            encode_report("fingerprint", strikes=4, ts=123.5))
        assert (kind, strikes, ts) == ("fingerprint", 4, 123.5)

    def test_garbage_tolerated(self):
        for blob in (None, b"", b"\xff\xfe", b"[1, 2]", b"42"):
            kind, strikes, _ = decode_report(blob)
            assert kind == "nonfinite" and strikes == 1

    def test_bare_string_is_a_kind(self):
        kind, strikes, _ = decode_report(b'"loss_spike"')
        assert (kind, strikes) == ("loss_spike", 1)


# ---------------------------------------------------------------------------
# rendezvous routing
# ---------------------------------------------------------------------------

class TestRendezvousRouting:
    def test_sdc_scope_handler_routes_to_driver_journaled(self):
        """The ``sdc`` scope PUT handler decodes the report and hands it
        to the driver with persist=False (already journaled) — and the
        scope is NOT ephemeral (a caught corrupting host must stay
        caught across a coordinator restart)."""
        from horovod_tpu.elastic.rendezvous import attach_elastic_handlers

        class StubRendezvous:
            def __init__(self):
                self.handlers = {}
                self.put_handlers = {}
                self.ephemeral_scopes = set()

            def add_handler(self, scope, fn):
                self.handlers[scope] = fn

            def add_put_handler(self, scope, fn):
                self.put_handlers[scope] = fn

        class StubDriver:
            def __init__(self):
                self.reports = []

            def record_ready(self, host, slot):
                pass

            def get_slot_info(self, host, slot):
                raise AssertionError("unused")

            def register_worker_server(self, *a):
                pass

            def record_preemption_notice(self, host, grace, ts=None,
                                         persist=True):
                pass

            def record_sdc_report(self, host, kind, strikes=1, ts=None,
                                  persist=True):
                self.reports.append((host, kind, strikes, persist))

        rdv, drv = StubRendezvous(), StubDriver()
        attach_elastic_handlers(rdv, drv)
        assert SDC_SCOPE in rdv.put_handlers
        assert SDC_SCOPE not in rdv.ephemeral_scopes   # journaled!
        rdv.put_handlers[SDC_SCOPE](
            "host-q", encode_report("fingerprint", strikes=4))
        assert drv.reports == [("host-q", "fingerprint", 4, False)]


# ---------------------------------------------------------------------------
# driver quarantine
# ---------------------------------------------------------------------------

class TestDriverQuarantine:
    def test_report_blacklists_persists_and_counts(self):
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.elastic.driver import (BLACKLIST_SCOPE,
                                                ElasticDriver)
        rdv = RecordingRendezvous()
        driver = ElasticDriver(rdv, FixedHosts({"h1": 1, "h2": 1}),
                               min_np=1, timeout=5)
        try:
            driver.record_sdc_report("h2", "nonfinite", strikes=3)
            assert driver._host_manager.is_blacklisted("h2")
            assert rdv.data[BLACKLIST_SCOPE]["h2"] == b"sdc"
            kind, strikes, _ = decode_report(rdv.data[SDC_SCOPE]["h2"])
            assert (kind, strikes) == ("nonfinite", 3)
            assert _counter("hvd_tpu_sdc_quarantined_hosts") == 1

            # idempotent per host: a repeat report changes nothing
            puts = len(rdv.puts)
            driver.record_sdc_report("h2", "nonfinite", strikes=4)
            assert len(rdv.puts) == puts
            assert _counter("hvd_tpu_sdc_quarantined_hosts") == 1
        finally:
            driver.stop()

    def test_restore_from_rendezvous_reseeds_quarantine(self):
        """A journaled report survives a coordinator restart: restore
        re-blacklists the host and restores the gauge, without
        re-journaling (persist=False)."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.elastic.driver import ElasticDriver
        rdv = RecordingRendezvous(
            {SDC_SCOPE: {"h7": encode_report("fingerprint", strikes=5)}})
        driver = ElasticDriver(rdv, FixedHosts({"h1": 1}), min_np=1,
                               timeout=5)
        try:
            count = driver.restore_from_rendezvous()
            assert count >= 1
            assert driver._host_manager.is_blacklisted("h7")
            assert "h7" in driver._quarantined
            assert _counter("hvd_tpu_sdc_quarantined_hosts") == 1
            assert not any(scope == SDC_SCOPE
                           for scope, _, _ in rdv.puts)
        finally:
            driver.stop()


# ---------------------------------------------------------------------------
# checkpoint manager: last-good promotion
# ---------------------------------------------------------------------------

class TestManagerLastGood:
    def _tree(self, fill):
        import jax.numpy as jnp
        return {"w": jnp.full(16, float(fill), jnp.float32)}

    def test_promote_and_restore_roundtrip(self, tmp_path):
        from horovod_tpu import checkpointing as cp
        mgr = cp.CheckpointManager(str(tmp_path))
        mgr.save(1, self._tree(1), async_=False)
        mgr.save(2, self._tree(2), async_=False)
        mgr.promote_last_good(1)
        assert mgr.last_good_step == 1
        out = mgr.restore_last_good()
        np.testing.assert_array_equal(np.asarray(out["w"]), 1.0)

    def test_restore_without_promotion_refuses(self, tmp_path):
        from horovod_tpu import checkpointing as cp
        mgr = cp.CheckpointManager(str(tmp_path))
        mgr.save(1, self._tree(1), async_=False)
        with pytest.raises(RuntimeError, match="no last-good"):
            mgr.restore_last_good()


# ---------------------------------------------------------------------------
# guarded Estimator loop (single process)
# ---------------------------------------------------------------------------

class _Records(logging.Handler):
    """hvd.init() installs the repo's own handler with propagate=False
    on the ``horovod_tpu`` logger, so caplog never sees these records;
    capture them at the source instead."""

    def __init__(self, name="horovod_tpu.estimator"):
        super().__init__(logging.WARNING)
        self.records = []
        self._logger = logging.getLogger(name)

    def emit(self, record):
        self.records.append(record)

    def __enter__(self):
        self._logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self)

    def messages(self):
        return [r.getMessage() for r in self.records]


def _toy_net():
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(4)(x)

    return Net()


def _toy_data():
    rng = np.random.RandomState(7)
    x = rng.randn(64, 8).astype(np.float32)
    y = (np.arange(64) % 4).astype(np.int32)
    return x, y


def _fit(epochs=2, checkpoint_dir=None):
    import optax

    from horovod_tpu.estimator import Estimator
    x, y = _toy_data()
    est = Estimator(_toy_net(), optimizer=optax.sgd(1e-2), seed=3,
                    scale_lr_by_world=False,
                    checkpoint_dir=checkpoint_dir)
    est.fit(x, y, epochs=epochs, batch_size=16, shard=False)
    return est


class TestEstimatorIntegration:
    def test_skip_retry_keeps_run_bit_identical(self, hvd_world,
                                                monkeypatch):
        """A one-shot bitflip is detected, the poisoned update skipped
        and the batch retried (clean): the corrupted run's final params
        are bit-identical to an uninjected run's."""
        monkeypatch.setenv("HVD_TPU_SDC_GUARD", "1")
        clean = _fit()
        before = _counter(
            'hvd_tpu_sdc_detections_total{kind="nonfinite"}')
        F.configure("worker.grads:bitflip:step=3", seed=SEED)
        corrupt = _fit()
        assert _counter(
            'hvd_tpu_sdc_detections_total{kind="nonfinite"}') \
            == before + 1
        np.testing.assert_array_equal(_flatleaves(clean.params),
                                      _flatleaves(corrupt.params))

    def test_persistent_corruption_drops_the_batch(self, hvd_world,
                                                   monkeypatch):
        """Corruption on the retry too, with the rollback escalation out
        of reach: the batch is dropped (one skip must not become an
        infinite retry loop) and the run finishes."""
        monkeypatch.setenv("HVD_TPU_SDC_GUARD", "1")
        monkeypatch.setattr(sdc.policy, "ROLLBACK_TRIPS", 3)
        F.configure("worker.grads:nan:step=3;worker.grads:nan:step=4",
                    seed=SEED)
        with _Records() as rec:
            est = _fit(epochs=1)
        assert any("batch dropped" in m for m in rec.messages())
        assert np.all(np.isfinite(_flatleaves(est.params)))

    def test_repeat_trips_roll_back_to_last_good(self, hvd_world,
                                                 monkeypatch, tmp_path):
        """Two trips inside the window: the loop restores the promoted
        last-good checkpoint (epoch-0 save, confirmed by one clean step)
        and counts the rollback."""
        monkeypatch.setenv("HVD_TPU_SDC_GUARD", "1")
        monkeypatch.setenv("HVD_TPU_SDC_CONFIRM_STEPS", "1")
        # 4 steps/epoch: calls 9+10 are epoch 2's first attempt + retry
        F.configure("worker.grads:nan:step=9;worker.grads:nan:step=10",
                    seed=SEED)
        rb_before = _counter("hvd_tpu_sdc_rollbacks_total")
        with _Records() as rec:
            _fit(epochs=3, checkpoint_dir=str(tmp_path))
        assert _counter("hvd_tpu_sdc_rollbacks_total") == rb_before + 1
        assert _counter("hvd_tpu_sdc_last_good_step") == 0
        assert any("rolled back to last-good step 0" in m
                   for m in rec.messages())

    def test_rollback_without_last_good_skips_instead(self, hvd_world,
                                                      monkeypatch):
        """No checkpoint promoted yet: the rollback degrades to skipping
        the poisoned update — never a crash, never a poisoned apply."""
        monkeypatch.setenv("HVD_TPU_SDC_GUARD", "1")
        F.configure("worker.grads:nan:step=1;worker.grads:nan:step=2",
                    seed=SEED)
        with _Records() as rec:
            est = _fit(epochs=1)
        assert any("no last-good" in m for m in rec.messages())
        assert np.all(np.isfinite(_flatleaves(est.params)))

    def test_guard_off_means_site_never_fires(self, hvd_world,
                                              monkeypatch):
        """HVD_TPU_SDC_GUARD unset: zero overhead — the worker.grads
        site is never even reached, so a configured drill cannot fire."""
        monkeypatch.delenv("HVD_TPU_SDC_GUARD", raising=False)
        key = ('hvd_tpu_faults_injected_total'
               '{site="worker.grads",kind="bitflip"}')
        before = _counter(key)
        F.configure("worker.grads:bitflip:step=1", seed=SEED)
        _fit(epochs=1)
        assert _counter(key) == before


# ---------------------------------------------------------------------------
# the seeded 2-process drill (real collectives, real KV store)
# ---------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_drill(n, per_proc_env, timeout=240):
    """Like test_multiprocess_integration._launch, but with PER-PROCESS
    env (each drill worker needs its own HVD_TPU_HOSTNAME so quarantine
    attribution is observable)."""
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


def _drill_stats(out):
    params = detections = None
    for line in out.splitlines():
        if line.startswith("PARAMS "):
            params = line.split()[-1]
        elif line.startswith("DETECTIONS "):
            detections = int(line.split()[-1])
    return params, detections


@pytest.mark.integration
def test_sdc_drill_two_proc():
    """worker.grads:bitflip:step=3:rank=1 through real collectives:
    rank 1's local gradients are bit-flipped once; the MAX-allreduced
    verdict makes BOTH ranks skip and retry the step; rank 1 (strikes=1)
    reports itself into the journaled ``sdc`` scope; and the final
    parameters are bit-identical to an uninjected run's — the corruption
    left zero trace in the model."""
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.rendezvous import KVStoreServer

    server = KVStoreServer(port=0)
    kv_port = server.start()
    try:
        def env_for(pid):
            return {
                "HVD_TPU_HOSTNAME": f"sdc-host-{pid}",
                "HVD_TPU_LOCAL_RANK": "0",
                "HVD_TPU_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVD_TPU_RENDEZVOUS_PORT": str(kv_port),
                "HVD_TPU_SDC_STRIKES": "1",
            }

        def env_clean(pid):
            return {k: v for k, v in env_for(pid).items()
                    if not k.startswith("HVD_TPU_RENDEZVOUS")}

        codes, outs = _launch_drill(2, env_clean)
        assert codes == [0, 0], "\n===\n".join(outs)
        clean = [_drill_stats(o) for o in outs]
        assert all(d == 0 for _, d in clean), outs

        def env_corrupt(pid):
            env = env_for(pid)
            env.update({
                "HVD_TPU_FAULT_SPEC":
                    "worker.grads:bitflip:step=3:rank=1",
                "HVD_TPU_FAULT_SEED": str(SEED),
            })
            return env

        codes, outs = _launch_drill(2, env_corrupt)
        assert codes == [0, 0], "\n===\n".join(outs)
        corrupt = [_drill_stats(o) for o in outs]
        # both ranks saw the (allreduced) detection...
        assert all(d >= 1 for _, d in corrupt), outs
        # ...and the retried step erased the corruption: all four final
        # parameter digests are the same bits
        digests = {p for p, _ in clean} | {p for p, _ in corrupt}
        assert len(digests) == 1, (clean, corrupt)

        # only the offender reported itself for quarantine
        reports = server.items(SDC_SCOPE)
        assert set(reports) == {"sdc-host-1"}, reports
        kind, strikes, _ = decode_report(reports["sdc-host-1"])
        assert kind == "nonfinite" and strikes >= 1

        # a restarted coordinator replays the journaled report into a
        # real quarantine
        rdv = RecordingRendezvous({SDC_SCOPE: dict(reports)})
        driver = ElasticDriver(rdv, FixedHosts({"sdc-host-0": 1}),
                               min_np=1, timeout=5)
        try:
            driver.restore_from_rendezvous()
            assert driver._host_manager.is_blacklisted("sdc-host-1")
        finally:
            driver.stop()
    finally:
        server.stop()
