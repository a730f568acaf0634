"""Autotuner (ParameterManager) tests.

Mirrors the reference's autotune coverage style: drive the sampling protocol
directly and through the DistributedOptimizer eager path, assert the
schedule (warmup -> samples -> converged) and that the tuned knob lands in
range (reference: common/parameter_manager.h:33-105 schedule semantics).
"""

import math
import os

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import config as _config
from horovod_tpu import parameter_manager as pm_mod


@pytest.fixture
def autotune_world(tmp_path):
    if hvd.is_initialized():
        hvd.shutdown()
    log = str(tmp_path / "autotune.log")
    hvd.init(config_overrides={
        "AUTOTUNE": True,
        "AUTOTUNE_LOG": log,
        "AUTOTUNE_WARMUP_SAMPLES": 1,
        "AUTOTUNE_STEPS_PER_SAMPLE": 2,
        "AUTOTUNE_BAYES_OPT_MAX_SAMPLES": 4,
    })
    yield log
    hvd.shutdown()


def test_parameter_manager_schedule(autotune_world):
    from horovod_tpu import basics
    w = basics.world()
    pm = w.parameter_manager
    assert pm is not None and pm.active
    start_threshold = pm.fusion_threshold
    # warmup sample (2 steps): threshold unchanged, score discarded
    pm.record(1 << 20, 0.01)
    pm.record(1 << 20, 0.01)
    assert pm.fusion_threshold == start_threshold
    # 4 scored samples lock the fusion threshold; tuning then moves to
    # the pack cutoff (round-5 coordinate descent), so the manager stays
    # active
    for s in range(4):
        assert pm.active
        pm.record(1 << 20, 0.01 + 0.001 * s)
        pm.record(1 << 20, 0.01 + 0.001 * s)
    assert pm.active
    t = pm.fusion_threshold
    assert (1 << 20) <= t <= (1 << 28)
    assert t & (t - 1) == 0  # power of two
    # knob propagated to config for later consumers
    assert w.config.get(_config.FUSION_THRESHOLD) == t
    # phase 2: warmup + 4 samples tune PACK_CUTOFF, then tuning finishes
    pm.record(1 << 20, 0.01)
    pm.record(1 << 20, 0.01)  # phase-2 warmup sample
    for s in range(4):
        assert pm.active
        pm.record(1 << 20, 0.01 + 0.001 * s)
        pm.record(1 << 20, 0.01 + 0.001 * s)
    assert not pm.active
    assert pm.fusion_threshold == t  # locked knob untouched by phase 2
    c = w.config.get(_config.PACK_CUTOFF)
    assert (1 << 12) <= c <= (1 << 22)
    assert c & (c - 1) == 0
    # further records are no-ops
    pm.record(1, 1.0)
    assert pm.fusion_threshold == t
    with open(autotune_world) as f:
        log = f.read()
    assert "warmup" in log and "knob locked" in log
    assert "tuning complete" in log


def test_autotune_through_optimizer(autotune_world):
    """The eager DistributedOptimizer path must feed the tuner and converge
    without disturbing gradient correctness."""
    import optax
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": np.ones((4, 4), np.float32), "b": np.ones(4, np.float32)}
    state = opt.init(params)
    from horovod_tpu import basics
    pm = basics.world().parameter_manager
    grads = {"w": np.full((4, 4), 2.0, np.float32),
             "b": np.full(4, 2.0, np.float32)}
    # two phases x (1 warmup + 4 samples) x 2 steps/sample = 20 steps
    for _ in range(20):
        updates, state = opt.update(grads, state, params)
    assert not pm.active
    # size-1 world: averaged grad == grad; sgd update = -0.1*grad
    np.testing.assert_allclose(np.asarray(updates["w"]),
                               -0.2 * np.ones((4, 4)), rtol=1e-6)


def test_python_fallback_optimizer_deterministic():
    def run():
        opt = pm_mod._PythonFallbackOptimizer(20.0, 28.0)
        xs = []
        for i in range(8):
            x = opt.suggest()
            xs.append(x)
            opt.observe(x, -(x - 24.2) ** 2)
            assert 20.0 <= x <= 28.0
        return xs
    assert run() == run()


def test_python_fallback_optimizer_refines_near_best():
    opt = pm_mod._PythonFallbackOptimizer(20.0, 28.0)
    for _ in range(12):
        x = opt.suggest()
        opt.observe(x, -(x - 24.0) ** 2)
    # after the grid + refinement, suggestions cluster near the optimum
    assert abs(opt.suggest() - 24.0) <= 2.0


def test_no_parameter_manager_without_knob(hvd_world):
    from horovod_tpu import basics
    assert basics.world().parameter_manager is None


# ---------------------------------------------------------------------------
# round 3: compiled-plane autotune (reduce strategy x packing) + adoption
# ---------------------------------------------------------------------------
def _mesh_world():
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.init()


def test_compiled_reduction_variants_numerically_equal():
    """All four (strategy, packing) combos produce identical gradients on
    an 8-device outer x inner mesh."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import optax

    _mesh_world()
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("outer", "inner"))
    grads = {"w": np.arange(8 * 3, dtype=np.float32).reshape(8, 3),
             "b": np.arange(8, dtype=np.float32).reshape(8, 1)}

    results = {}
    for strategy in ("hierarchical", "flat"):
        for packing in ("per_leaf", "packed"):
            opt = hvd.DistributedOptimizer(
                optax.sgd(1.0), axis_name="outer", inner_axis="inner",
                reduce_strategy=strategy, packing=packing)

            def red(g):
                return opt.reduce_gradients(g)

            f = jax.jit(shard_map(
                red, mesh=mesh,
                in_specs=({"w": P(("outer", "inner")),
                           "b": P(("outer", "inner"))},),
                out_specs={"w": P(("outer", "inner")),
                           "b": P(("outer", "inner"))}))
            results[(strategy, packing)] = jax.tree_util.tree_map(
                np.asarray, f(grads))

    ref = results[("hierarchical", "per_leaf")]
    for k, r in results.items():
        np.testing.assert_allclose(r["w"], ref["w"], rtol=1e-6,
                                   err_msg=str(k))
        np.testing.assert_allclose(r["b"], ref["b"], rtol=1e-6,
                                   err_msg=str(k))
    hvd.shutdown()


def test_autotune_variants_picks_fastest():
    import time as _t
    from horovod_tpu.compiled_autotune import autotune_variants

    _mesh_world()

    def slow():
        _t.sleep(0.03)
        return np.zeros(2)

    def fast():
        return np.zeros(2)

    chosen, fn, times = autotune_variants(
        {"slow": slow, "fast": fast}, warmup=0, iters=2, key="t.pick")
    assert chosen == "fast"
    assert times["slow"] > times["fast"]
    assert fn is fast
    hvd.shutdown()


def test_tune_distributed_step_end_to_end():
    """tune_distributed_step compiles all combos of a real sharded step and
    returns a winner whose output matches every other variant."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import optax

    _mesh_world()
    from horovod_tpu.compiled_autotune import tune_distributed_step

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "ici"))
    g = np.arange(16, dtype=np.float32).reshape(8, 2)

    def make_step(reduce_strategy, packing):
        opt = hvd.DistributedOptimizer(
            optax.sgd(1.0), axis_name="dp", inner_axis="ici",
            reduce_strategy=reduce_strategy, packing=packing)
        return jax.jit(shard_map(
            lambda x: opt.reduce_gradients(x), mesh=mesh,
            in_specs=P(("dp", "ici")), out_specs=P(("dp", "ici"))))

    options, step = tune_distributed_step(make_step, (g,), warmup=1,
                                          iters=2, key="t.step")
    assert options["reduce_strategy"] in ("hierarchical", "flat")
    assert options["packing"] in ("per_leaf", "packed")
    out = np.asarray(step(g))
    expect = np.asarray(make_step("hierarchical", "per_leaf")(g))
    np.testing.assert_allclose(out, expect, rtol=1e-6)
    hvd.shutdown()


@pytest.mark.integration
def test_autotune_cross_process_adoption():
    """Two processes with rank-dependent measurements adopt ONE threshold
    and ONE compiled variant (rank 0's) — the SynchronizeParameters
    semantics the reference gets from controller.cc:33-47."""
    import re
    import socket
    import subprocess
    import sys as _sys

    worker = os.path.join(os.path.dirname(__file__),
                          "autotune_adoption_worker.py")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(worker)))
        env.update({
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": "2",
            "HVD_TPU_RANK": str(pid),
        })
        procs.append(subprocess.Popen(
            [_sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode(errors="replace"))
        assert p.returncode == 0, outs
    got = [dict(re.findall(r"(THRESHOLD|VARIANT)=(\S+)", o)) for o in outs]
    assert got[0]["THRESHOLD"] == got[1]["THRESHOLD"], got
    assert got[0]["VARIANT"] == got[1]["VARIANT"] == "b", got
