"""Mesh-aware elastic recovery (docs/elastic.md, mesh-aware recovery).

Covers the mesh plane end to end:

* reshape-policy units — every branch of
  :func:`horovod_tpu.parallel.mesh_utils.plan_reshape` (shrink dp first,
  then fsdp; ``degrade`` drops a remainder; ``strict`` refuses;
  :class:`MeshShapeError` names the policy and the counts) and the
  replica-group layout helpers;
* replica-group-scoped fingerprints — including the pre-fix companion
  proving the flat whole-world compare WOULD false-trip across fsdp/tp
  shard-holders, plus a true within-group divergence ticking
  ``hvd_tpu_sdc_fingerprint_divergence_total{replica_group=...}``;
* the driver's mesh plane — replan on membership change, journaled
  publish, ``strict`` refusals surfacing via ``mesh_error()``, and the
  reason-preserving blacklist restore (an SDC-quarantined host stays
  quarantined across a coordinator restart);
* shard handoff — save@one-mesh -> restore@another through the
  resharding reader, and the coverage-gap IntegrityError;
* the ``worker.mesh`` fault site and the seeded 2-process drill: kill
  rank 1 of a dp=2 x (local fsdp=2) run mid-step, the survivor re-forms
  a 1-host mesh, restores the sharded checkpoint, and finishes with
  parameters bit-identical to an uninterrupted 1-host run over the same
  data order — with zero false fingerprint divergences.

Owned exclusively by the seeded ``chaos-mesh`` CI suite
(ci/gen_pipeline.py); the generic unit/chaos suites ignore this file.
"""

import json
import os
import re
import stat
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from horovod_tpu import _schedule
from horovod_tpu import faults as F
from horovod_tpu import metrics as M
from horovod_tpu import sdc
from horovod_tpu.elastic.discovery import FixedHosts
from horovod_tpu.elastic.driver import (BLACKLIST_SCOPE, MESH_SCOPE,
                                        ElasticDriver)
from horovod_tpu.parallel import mesh_utils
from horovod_tpu.parallel.mesh_utils import (MeshConfig, MeshShapeError,
                                             plan_reshape, replica_group_of,
                                             replica_groups)

SEED = 1234
WORKER = os.path.join(os.path.dirname(__file__), "mesh_train_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(WORKER)))

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _reset_faults():
    """Every test leaves the process-wide fault registry disabled."""
    yield
    F.configure("", seed=0)


def _counter(name):
    return float(M.snapshot().get(name, 0.0))


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
# reshape policy units (plan_reshape)
# ---------------------------------------------------------------------------

class TestReshapePolicy:
    def test_spec_parses_and_defaults_unnamed_axes(self):
        cfg = mesh_utils.mesh_config_from_spec("dp=2, fsdp=4,tp=2")
        assert (cfg.dp, cfg.fsdp, cfg.tp) == (2, 4, 2)
        assert (cfg.pp, cfg.ep, cfg.sp) == (1, 1, 1)

    def test_spec_unknown_axis_names_valid_axes(self):
        with pytest.raises(MeshShapeError, match=r"dq.*dp.*fsdp"):
            mesh_utils.mesh_config_from_spec("dq=2")

    def test_spec_non_integer_and_empty_rejected(self):
        with pytest.raises(MeshShapeError, match="non-integer"):
            mesh_utils.mesh_config_from_spec("dp=two")
        with pytest.raises(MeshShapeError, match="empty"):
            mesh_utils.mesh_config_from_spec("  ")

    def test_shrink_drops_dp_first(self):
        # dp=4 x fsdp=2 x tp=2 = 16; 12 survive -> dp shrinks to 3,
        # fsdp/tp untouched
        plan = plan_reshape(MeshConfig(dp=4, fsdp=2, tp=2), 12,
                            policy="shrink")
        assert (plan.config.dp, plan.config.fsdp, plan.config.tp) == (3, 2, 2)
        assert plan.direction == "down"
        assert (plan.used, plan.dropped) == (12, 0)

    def test_shrink_falls_back_to_fsdp_when_dp_cannot_absorb(self):
        # dp=2 x fsdp=4 = 8; 6 survive: 6 inner groups don't divide by
        # fsdp=4, so fsdp shrinks to the largest divisor (3), dp holds
        plan = plan_reshape(MeshConfig(dp=2, fsdp=4), 6, policy="shrink")
        assert (plan.config.dp, plan.config.fsdp) == (2, 3)
        assert plan.used == 6 and plan.dropped == 0

    def test_shrink_refuses_to_break_inner_axes(self):
        # tp=4 protected: 6 survivors don't divide into tp groups; the
        # error names the policy, the counts, and the degrade escape hatch
        with pytest.raises(MeshShapeError,
                           match=r"shrink.*6\s+survivor.*4.*degrade"):
            plan_reshape(MeshConfig(dp=2, tp=4), 6, policy="shrink")

    def test_survivors_below_inner_group_always_refused(self):
        with pytest.raises(MeshShapeError, match=r"degrade.*2 survivor"):
            plan_reshape(MeshConfig(dp=2, tp=4), 2, policy="degrade")

    def test_degrade_drops_remainder_instead_of_aborting(self):
        # dp=2 x fsdp=2 = 4; 3 survive: keep fsdp=2, dp=1 -> 2 used,
        # 1 survivor idles instead of the job dying
        plan = plan_reshape(MeshConfig(dp=2, fsdp=2), 3, policy="degrade")
        assert (plan.config.dp, plan.config.fsdp) == (1, 2)
        assert (plan.used, plan.dropped) == (2, 1)
        assert plan.direction == "down"

    def test_degrade_respects_inner_axes(self):
        # tp=2 inner; 5 survivors -> 2 full replica groups (dp=2), 1 idles
        plan = plan_reshape(MeshConfig(dp=4, tp=2), 5, policy="degrade")
        assert (plan.config.dp, plan.config.tp) == (2, 2)
        assert (plan.used, plan.dropped) == (4, 1)

    def test_strict_refuses_any_change_naming_counts(self):
        with pytest.raises(MeshShapeError, match=r"strict.*8.*6"):
            plan_reshape(MeshConfig(dp=4, fsdp=2), 6, policy="strict")

    def test_strict_no_change_is_direction_none(self):
        plan = plan_reshape(MeshConfig(dp=4, fsdp=2), 8, policy="strict")
        assert plan.direction == "none"
        assert plan.config == MeshConfig(dp=4, fsdp=2)

    def test_initial_adoption_resolves_dp(self):
        plan = plan_reshape(MeshConfig(dp=-1, fsdp=2), 8, policy="shrink")
        assert (plan.config.dp, plan.config.fsdp) == (4, 2)
        assert plan.direction == "none"   # adopting a shape != reshaping

    def test_strict_initial_adoption_requires_exact_fit(self):
        with pytest.raises(MeshShapeError, match=r"strict.*fsdp=4"):
            plan_reshape(MeshConfig(dp=-1, fsdp=4), 6, policy="strict")

    def test_growth_is_direction_up(self):
        plan = plan_reshape(MeshConfig(dp=1, fsdp=2), 4, policy="shrink")
        assert plan.config.dp == 2
        assert plan.direction == "up"

    def test_unknown_policy_rejected(self):
        with pytest.raises(MeshShapeError, match="fliparoo"):
            plan_reshape(MeshConfig(dp=2), 1, policy="fliparoo")

    def test_policy_defaults_from_knob(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_MESH_RESHAPE_POLICY", "degrade")
        plan = plan_reshape(MeshConfig(dp=2, fsdp=2), 3)
        assert plan.policy == "degrade" and plan.dropped == 1

    def test_mesh_total_requires_resolved_dp(self):
        with pytest.raises(MeshShapeError, match="unresolved"):
            mesh_utils.mesh_total(MeshConfig(dp=-1))


class TestReplicaGroups:
    def test_groups_stride_by_inner_index(self):
        # dp outermost: rank = dp_index * stride + inner_index, so a
        # group collects the ranks holding the SAME shard across replicas
        assert replica_groups(8, 2) == [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert replica_groups(4, 4) == [[0, 1, 2, 3]]
        assert replica_groups(4, 1) == [[0], [1], [2], [3]]

    def test_group_of_matches_groups(self):
        for world, dp in ((8, 2), (6, 3), (4, 4), (4, 1)):
            groups = replica_groups(world, dp)
            for g, ranks in enumerate(groups):
                for r in ranks:
                    assert replica_group_of(r, world, dp) == g

    def test_non_dividing_world_refused(self):
        with pytest.raises(MeshShapeError, match=r"5.*dp=2"):
            replica_groups(5, 2)
        with pytest.raises(MeshShapeError):
            replica_group_of(1, 5, 2)


# ---------------------------------------------------------------------------
# replica-group-scoped fingerprints
# ---------------------------------------------------------------------------

class TestScopedFingerprints:
    def _shard(self, lo, hi):
        import jax.numpy as jnp
        return {"w": jnp.linspace(lo, hi, 16, dtype=jnp.float32)}

    def test_pre_fix_flat_compare_false_trips_across_shards(self):
        """The companion proving the fix is needed: two fsdp
        shard-holders legitimately hold DIFFERENT parameter bytes; the
        legacy flat whole-world compare reads that as a divergence. The
        replica-group layout puts them in different groups, so the
        scoped compare never sees them side by side."""
        fp0 = sdc.fold_fingerprint(self._shard(0.0, 1.0))   # shard 0
        fp1 = sdc.fold_fingerprint(self._shard(2.0, 3.0))   # shard 1
        assert fp0 != fp1
        # pre-fix behavior: flat keys, whole-world diff -> false trip
        peers = {0: {"step": 3, "fp": fp0}, 1: {"step": 3, "fp": fp1}}
        diverged = _schedule.diff_sdc_fingerprints(peers, 3)
        assert diverged is not None, \
            "flat compare should trip on healthy shards (the pre-fix bug)"
        # post-fix: world=2 hosting dp=1 x fsdp=2 puts each shard-holder
        # in its own replica group -> nothing to compare, no false trip
        assert replica_group_of(0, 2, 1) != replica_group_of(1, 2, 1)
        mon = sdc.FingerprintMonitor.for_mesh(2, 0, dp=1, every=1)
        assert mon.group_ranks == [0]
        assert mon.maybe_check(3, self._shard(0.0, 1.0)) is None

    def test_scoped_keys_isolate_groups_on_live_kv(self, monkeypatch):
        """(replica_group, rank)-scoped keys through a real KV store:
        group 1's fingerprints are invisible to group 0's fetch, and the
        flat legacy key stays untouched for pure-dp worlds."""
        from horovod_tpu.runner.rendezvous import KVStoreServer
        server = KVStoreServer(port=0)
        port = server.start()
        try:
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_PORT", str(port))
            _schedule.reset()
            _schedule.publish_sdc_fingerprint(5, 111, rank=0, group=0)
            _schedule.publish_sdc_fingerprint(5, 222, rank=1, group=1)
            _schedule.publish_sdc_fingerprint(5, 333, rank=2)   # legacy flat
            assert server.items("schedule").keys() >= {
                "sdc.fp.g0.rank0", "sdc.fp.g1.rank1", "sdc.fp.rank2"}
            g0 = _schedule.fetch_sdc_fingerprints(group=0, ranks=[0])
            assert set(g0) == {0} and g0[0]["fp"] == 111
            # a shard-holder in another group is NOT fetched as a peer
            assert _schedule.fetch_sdc_fingerprints(
                group=0, ranks=[0, 1]) == g0
            flat = _schedule.fetch_sdc_fingerprints(3)
            assert set(flat) == {2}
        finally:
            server.stop()
            _schedule.reset()

    def test_true_within_group_divergence_detected(self, monkeypatch):
        """A REAL divergence between two ranks of one replica group is
        still caught, scoped metric
        hvd_tpu_sdc_fingerprint_divergence_total{replica_group="0"}
        ticks, and the diagnostic names the group and the bad leaf."""
        from horovod_tpu.runner.rendezvous import KVStoreServer
        server = KVStoreServer(port=0)
        port = server.start()
        try:
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HVD_TPU_RENDEZVOUS_PORT", str(port))
            monkeypatch.setenv("HVD_TPU_RANK", "0")
            _schedule.reset()
            tree = self._shard(0.0, 1.0)
            fp = sdc.fold_fingerprint(tree)
            leaves = sdc.fold_leaf_fingerprints(tree)
            # rank 2 shares replica group 0 on a world=4, dp=2 mesh
            # (groups [[0,2],[1,3]]) but publishes corrupted checksums
            server.put("schedule", "sdc.fp.g0.rank2", json.dumps({
                "step": 6, "fp": fp ^ 1, "rank": 2, "group": 0,
                "leaves": {str(i): v ^ 1 for i, v in leaves.items()},
            }).encode())
            key = ('hvd_tpu_sdc_fingerprint_divergence_total'
                   '{replica_group="0"}')
            before = _counter(key)
            mon = sdc.FingerprintMonitor.for_mesh(4, 0, dp=2, every=1)
            assert mon.replica_group == 0 and mon.group_ranks == [0, 2]
            det = mon.maybe_check(6, tree)
            assert det == sdc.Detection(kind="fingerprint", local=False)
            assert _counter(key) == before + 1
        finally:
            server.stop()
            _schedule.reset()

    def test_diff_message_names_group_and_leaves(self):
        peers = {
            0: {"step": 2, "fp": 10, "leaves": {"0": 5, "1": 7}},
            4: {"step": 2, "fp": 11, "leaves": {"0": 5, "1": 8}},
        }
        ranks, msg = _schedule.diff_sdc_fingerprints(peers, 2, group=3)
        assert ranks == [4]
        assert "within replica group 3" in msg
        assert "diverging leaf index(es): 1" in msg

    def test_leaf_fold_matches_scalar_fold_skips(self):
        import jax.numpy as jnp
        tree = {"a": jnp.ones((3,), jnp.float32),
                "n": np.int64(4),            # non-inexact: skipped
                "e": jnp.zeros((0,), jnp.float32)}   # empty: skipped
        leaves = sdc.fold_leaf_fingerprints(tree)
        assert len(leaves) == 1
        flipped = {"a": jnp.asarray(np.array([1.0, 1.0, 1.5], np.float32)),
                   "n": np.int64(4), "e": jnp.zeros((0,), jnp.float32)}
        assert sdc.fold_leaf_fingerprints(flipped) != leaves


# ---------------------------------------------------------------------------
# driver mesh plane + reason-preserving blacklist restore
# ---------------------------------------------------------------------------

class TestDriverMeshPlane:
    def _driver(self, monkeypatch, shape="dp=2,fsdp=2", policy=None,
                data=None):
        monkeypatch.setenv("HVD_TPU_MESH_SHAPE", shape)
        if policy:
            monkeypatch.setenv("HVD_TPU_MESH_RESHAPE_POLICY", policy)
        rdv = RecordingRendezvous(data)
        driver = ElasticDriver(rdv, FixedHosts({"h1": 1}), min_np=1,
                               timeout=5)
        return driver, rdv

    def _published_axes(self, rdv):
        blob = rdv.data.get(MESH_SCOPE, {}).get("shape")
        assert blob, rdv.data
        return json.loads(bytes(blob).decode())["axes"]

    def test_replan_publishes_and_counts_reshapes(self, monkeypatch):
        driver, rdv = self._driver(monkeypatch)
        try:
            key = ('hvd_tpu_elastic_mesh_reshapes_total'
                   '{policy="shrink",direction="down"}')
            before = _counter(key)
            driver._replan_mesh(4)        # matches the configured shape
            assert self._published_axes(rdv)["dp"] == 2
            assert _counter(key) == before    # direction 'none': no tick
            driver._replan_mesh(2)        # host lost: dp shrinks first
            assert driver.mesh_shape() == {"dp": 1, "fsdp": 2, "pp": 1,
                                           "ep": 1, "sp": 1, "tp": 1}
            assert self._published_axes(rdv) == driver.mesh_shape()
            assert _counter(key) == before + 1
            assert driver.mesh_error() is None
        finally:
            driver.stop()

    def test_strict_refusal_keeps_old_plan_and_surfaces_error(
            self, monkeypatch):
        driver, rdv = self._driver(monkeypatch, policy="strict")
        try:
            driver._replan_mesh(4)
            assert driver.mesh_error() is None
            driver._replan_mesh(3)
            assert "strict" in driver.mesh_error()
            assert "3" in driver.mesh_error()
            # the old plan survives a refused replan
            assert driver.mesh_shape()["dp"] == 2
            assert self._published_axes(rdv)["dp"] == 2
        finally:
            driver.stop()

    def test_mesh_plane_off_without_knob(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_MESH_SHAPE", raising=False)
        rdv = RecordingRendezvous()
        driver = ElasticDriver(rdv, FixedHosts({"h1": 1}), min_np=1,
                               timeout=5)
        try:
            driver._replan_mesh(4)
            assert driver.mesh_shape() is None
            assert MESH_SCOPE not in rdv.data
        finally:
            driver.stop()

    def test_restore_preserves_blacklist_reasons_and_mesh(
            self, monkeypatch):
        """Satellite regression: across a coordinator restart the
        blacklist keeps its *reasons* — an SDC-quarantined host is
        re-quarantined (not downgraded to a generic failure) — and the
        journaled mesh plan is resumed, not replanned from the
        configured shape."""
        published = {"axes": {"dp": 1, "fsdp": 2, "pp": 1, "ep": 1,
                              "sp": 1, "tp": 1},
                     "policy": "shrink", "dropped": 0}
        driver, rdv = self._driver(monkeypatch, data={
            BLACKLIST_SCOPE: {"h-sdc": b"sdc", "h-fail": b"failure"},
            MESH_SCOPE: {"shape": json.dumps(published).encode()},
        })
        try:
            assert driver.restore_from_rendezvous() >= 3
            assert driver.blacklist_reason("h-sdc") == "sdc"
            assert driver.blacklist_reason("h-fail") == "failure"
            assert driver._host_manager.is_blacklisted("h-sdc")
            assert driver._host_manager.is_blacklisted("h-fail")
            assert "h-sdc" in driver._quarantined
            assert "h-fail" not in driver._quarantined
            # the restored coordinator resumes the RESHAPED mesh (dp=1),
            # not the configured dp=2
            assert driver.mesh_shape()["dp"] == 1
        finally:
            driver.stop()

    def test_blacklist_persists_reason_bytes(self, monkeypatch):
        driver, rdv = self._driver(monkeypatch)
        try:
            driver.blacklist_host("h-bad", reason="sdc")
            assert rdv.data[BLACKLIST_SCOPE]["h-bad"] == b"sdc"
            driver.blacklist_host("h-dead")
            assert rdv.data[BLACKLIST_SCOPE]["h-dead"] == b"failure"
        finally:
            driver.stop()


# ---------------------------------------------------------------------------
# shard handoff: save@one-mesh -> restore@another
# ---------------------------------------------------------------------------

class TestShardHandoff:
    def _mesh(self, spec, n):
        import jax
        return mesh_utils.make_training_mesh(
            mesh_utils.mesh_config_from_spec(spec), jax.devices()[:n])

    def _tree(self, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        w = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0,
            NamedSharding(mesh, P("fsdp", None)))
        m = jax.device_put(jnp.arange(8, dtype=jnp.float32),
                           NamedSharding(mesh, P()))
        return {"params": {"w": w}, "opt": {"m": m}}

    def test_save_fsdp2_restore_other_meshes_bit_exact(self, tmp_path):
        """The departed host's fsdp shards come from the checkpoint:
        a tree saved on a dp=1 x fsdp=2 mesh restores bit-exactly onto
        dp=2 x fsdp=1, onto fsdp=4, and onto the host — the save-mesh
        and restore-mesh are fully independent."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from horovod_tpu import checkpointing as cp

        tree = self._tree(self._mesh("dp=1,fsdp=2", 2))
        ref = jax.tree_util.tree_map(np.asarray, tree)
        mgr = cp.CheckpointManager(str(tmp_path))
        mgr.save(0, tree, async_=False)

        for spec, n in (("dp=2,fsdp=1", 2), ("dp=1,fsdp=4", 4)):
            mesh = self._mesh(spec, n)
            sh = {"params": {"w": NamedSharding(mesh, P("fsdp", None))},
                  "opt": {"m": NamedSharding(mesh, P())}}
            out = jax.tree_util.tree_map(
                np.asarray, mgr.restore(step=0, sharding=sh, fallback=True))
            assert np.array_equal(out["params"]["w"], ref["params"]["w"])
            assert np.array_equal(out["opt"]["m"], ref["opt"]["m"])
        host = mgr.restore(step=0)
        assert np.array_equal(np.asarray(host["params"]["w"]),
                              ref["params"]["w"])

    def test_coverage_gap_raises_integrity_error(self):
        """A restore plan that cannot cover a departed host's shards
        must fail loudly — never yield a half-initialized array."""
        from horovod_tpu.checkpointing import snapshot
        from horovod_tpu.checkpointing.layout import IntegrityError
        manifest = {
            "dtype": "float32", "shape": [4, 2], "path": "['w']",
            "shards": [{"shape": [2, 2], "starts": [0, 0], "file": "s0"}],
        }
        payload = np.arange(4, dtype=np.float32).tobytes()
        with pytest.raises(IntegrityError, match="cover"):
            snapshot.assemble_array(manifest, lambda s: payload)


# ---------------------------------------------------------------------------
# the worker.mesh fault site
# ---------------------------------------------------------------------------

class TestMeshFaultSite:
    def test_worker_mesh_site_fires_on_configured_step(self):
        from horovod_tpu.parallel import train as ptrain
        F.configure("worker.mesh:error:step=2", seed=SEED)
        key = ('hvd_tpu_faults_injected_total'
               '{site="worker.mesh",kind="error"}')
        before = _counter(key)
        ptrain._FP_MESH.fire()            # hit 1: clean
        with pytest.raises(F.InjectedFault):
            ptrain._FP_MESH.fire()        # hit 2: the configured step
        assert _counter(key) == before + 1

    def test_crash_rule_parses_with_rank_scope(self):
        rule = F.parse_spec("worker.mesh:crash:step=4:rank=1")[0]
        assert rule.kind == "crash" and rule.step == 4 and rule.rank == 1


# ---------------------------------------------------------------------------
# the seeded 2-process drill
# ---------------------------------------------------------------------------

def _write_discovery_script(path: str, hosts_file: str) -> None:
    with open(path, "w") as f:
        f.write(f"#!/bin/sh\ncat {hosts_file}\n")
    os.chmod(path, os.stat(path).st_mode | stat.S_IEXEC)


def _launch(test_dir: str, hosts: str, extra_env=None, np_=2, min_np=1,
            timeout=300):
    hosts_file = os.path.join(test_dir, "hosts.txt")
    with open(hosts_file, "w") as f:
        f.write(hosts + "\n")
    script = os.path.join(test_dir, "discover.sh")
    _write_discovery_script(script, hosts_file)

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "ELASTIC_TEST_DIR": test_dir,
    })
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "horovod_tpu.runner",
           "-np", str(np_), "--min-np", str(min_np),
           "--host-discovery-script", script,
           "--slots", "1",
           "--stall-check-warning-time-seconds", "5",
           "--stall-check-shutdown-time-seconds", "15",
           sys.executable, WORKER]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, cwd=test_dir)


def _finish(proc, timeout=300):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        raise AssertionError(
            "mesh drill timed out:\n" + out.decode(errors="replace")[-6000:])
    return proc.returncode, out.decode(errors="replace")


def _events(test_dir):
    path = os.path.join(test_dir, "events.log")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [line.strip() for line in f if line.strip()]


def _final_sha(events):
    done = [e for e in events if e.startswith("done rank=0 ")]
    assert done, events
    m = re.search(r" sha=([0-9a-f]{64})", done[-1])
    assert m, done
    return m.group(1)


@pytest.mark.integration
@pytest.mark.slow
def test_mesh_drill_two_proc():
    """The acceptance drill. Run 1 (reference): one host, dp=1, no
    faults. Run 2: dp=2 over two hosts, each a local fsdp=2 mesh;
    ``worker.mesh:crash:step=4:rank=1`` hard-kills rank 1 entering its
    4th sharded step. The driver replans dp=2 -> dp=1 and publishes it;
    the survivor re-execs, adopts the 1-host mesh, restores the last
    committed sharded checkpoint through the resharding reader, and
    finishes — with final parameters bit-identical to the reference and
    zero fingerprint divergences (group-scoped compares never read a
    different shard as a peer)."""
    with tempfile.TemporaryDirectory() as td_ref:
        proc = _launch(td_ref, "localhost:1", np_=1, min_np=1,
                       extra_env={"HVD_TPU_MESH_SHAPE": "dp=1"})
        code, out = _finish(proc)
        ref_events = _events(td_ref)
        assert code == 0, f"reference run exited {code}:\n{out[-6000:]}"
        sha_ref = _final_sha(ref_events)
        assert not any(e.startswith("sdc ") for e in ref_events), ref_events

    with tempfile.TemporaryDirectory() as td:
        proc = _launch(
            td, "localhost:1\n127.0.0.1:1", np_=2, min_np=1,
            extra_env={
                "HVD_TPU_MESH_SHAPE": "dp=2",
                "HVD_TPU_FAULT_SPEC": "worker.mesh:crash:step=4:rank=1",
                "HVD_TPU_FAULT_SEED": str(SEED),
            })
        code, out = _finish(proc)
        events = _events(td)
        assert code == 0, f"drill exited {code}:\n{out[-6000:]}\n" \
                          f"events: {events}"
        # generation 1 formed the dp=2 mesh on both ranks
        gen1 = [e for e in events if re.match(r"mesh rank=\d size=2 dp=2 ",
                                              e)]
        assert len(gen1) >= 2, events
        # the survivor re-formed a 1-host mesh from the driver's replan
        # and resumed from a restored (non-fresh) checkpoint step
        gen2 = [e for e in events
                if re.match(r"mesh rank=0 size=1 dp=1 ", e)]
        assert gen2, events
        m = re.search(r"restored=(\d+) start=(\d+)", gen2[-1])
        assert m, gen2
        assert int(m.group(2)) == int(m.group(1)) + 1
        # rank 1 died mid-step; steps after the kill ran at size 1
        assert any(re.match(r"step=5 rank=0 size=1 ", e) for e in events), \
            events
        # zero false fingerprint divergences across the whole drill
        assert not any(e.startswith("sdc ") for e in events), events
        # step-exact: bit-identical to the uninterrupted reference
        assert _final_sha(events) == sha_ref, (events, sha_ref)
