"""World lifecycle and query tests (reference: test/test_torch.py rank/size
smoke tests + basics.py API surface)."""

import os

import pytest

import horovod_tpu as hvd
from horovod_tpu.exceptions import NotInitializedError


def test_init_rank_size(hvd_world):
    assert hvd.is_initialized()
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.device_count() == 8
    assert hvd.local_device_count() == 8
    assert hvd.dp_size() == 8
    assert hvd.is_homogeneous()


def test_double_init_is_noop(hvd_world):
    hvd.init()
    assert hvd.size() == 1


def test_shutdown_then_reinit(hvd_world):
    hvd.shutdown()
    assert not hvd.is_initialized()
    hvd.init()
    assert hvd.is_initialized()


def test_not_initialized_raises():
    if hvd.is_initialized():
        hvd.shutdown()
    with pytest.raises(NotInitializedError):
        hvd.rank()
    with pytest.raises(NotInitializedError):
        hvd.size()


def test_capability_queries(hvd_world):
    assert hvd.xla_built()
    assert not hvd.mpi_built()
    assert not hvd.nccl_built()
    assert not hvd.gloo_built()
    assert not hvd.cuda_built()
    assert not hvd.mpi_enabled()
    assert not hvd.mpi_threads_supported()
    assert isinstance(hvd.tpu_available(), bool)


def test_process_sets(hvd_world):
    hvd.shutdown()
    hvd.init(process_sets=[[0]])
    wm = hvd.process_set_mesh(0)
    assert wm.num_procs == 1


def test_hostname(hvd_world):
    assert isinstance(hvd.hostname(), str) and hvd.hostname()


def test_mxnet_bridge_surface_is_gated():
    """The mxnet bridge exposes the full reference surface
    (mxnet/__init__.py:37-107) and every entry point raises the clear
    import-gate error in images without mxnet."""
    import horovod_tpu.mxnet as hvd_mx
    for fn_name, call in [
            ("allreduce", lambda: hvd_mx.allreduce(None)),
            ("grouped_allreduce", lambda: hvd_mx.grouped_allreduce([])),
            ("allgather", lambda: hvd_mx.allgather(None)),
            ("broadcast", lambda: hvd_mx.broadcast(None)),
            ("alltoall", lambda: hvd_mx.alltoall(None)),
            ("broadcast_parameters",
             lambda: hvd_mx.broadcast_parameters({})),
            ("DistributedOptimizer",
             lambda: hvd_mx.DistributedOptimizer(None)),
            ("DistributedTrainer",
             lambda: hvd_mx.DistributedTrainer(None, "sgd")),
    ]:
        assert hasattr(hvd_mx, fn_name)
        try:
            import mxnet  # noqa: F401
        except ImportError:
            with pytest.raises(ImportError, match="mxnet"):
                call()


def test_mpi_env_rank_detection():
    """Bare `mpirun/srun python train.py` resolves rank identity from the
    first COHERENT scheduler env family (reference: MPI env detection,
    docs/mpirun.rst). Partial families must not create identity: PMIX_RANK
    without a size var, or sbatch's batch-step SLURM_PROCID, previously
    turned fail-safe runs into wrong worlds."""
    from horovod_tpu import config as _config

    FAMILY_VARS = [v for fam in _config._MPI_FAMILIES for v in fam] + [
        "HVD_TPU_RANK", "HOROVOD_RANK", "HVD_TPU_SIZE", "HOROVOD_SIZE",
        "HVD_TPU_LOCAL_RANK", "HOROVOD_LOCAL_RANK",
        "HVD_TPU_LOCAL_SIZE", "HOROVOD_LOCAL_SIZE",
        "JSM_NAMESPACE_RANK", "SLURM_NTASKS"]

    def with_env(env):
        # hermetic: resolve against a controlled environ (CI itself may
        # run under SLURM/jsrun and export these vars)
        return _config.mpi_task_identity(env)

    # OMPI family: coherent rank+size
    ident = with_env({"OMPI_COMM_WORLD_RANK": "3",
                      "OMPI_COMM_WORLD_SIZE": "8",
                      "OMPI_COMM_WORLD_LOCAL_RANK": "1",
                      "OMPI_COMM_WORLD_LOCAL_SIZE": "4"})
    assert ident == {"RANK": 3, "SIZE": 8, "LOCAL_RANK": 1,
                     "LOCAL_SIZE": 4,
                     # derived for uniform hosts (round 5): host index
                     # and host count from rank//local_size
                     "CROSS_RANK": 0, "CROSS_SIZE": 2}

    # PMIx rank WITHOUT a size variable: no identity (silent
    # single-process degradation would mean wrong gradients)
    assert with_env({"PMIX_RANK": "2"}) == {}
    # ... but with JSM size it is coherent
    ident = with_env({"PMIX_RANK": "2", "JSM_NAMESPACE_SIZE": "4"})
    assert ident["RANK"] == 2 and ident["SIZE"] == 4

    # sbatch batch step (PROCID=0, step size 1): harmless single-process
    ident = with_env({"SLURM_PROCID": "0", "SLURM_STEP_NUM_TASKS": "1",
                      "SLURM_NTASKS": "4"})
    assert ident == {"RANK": 0, "SIZE": 1}
    # srun step: per-step vars give the real world; "4(x2)" parses
    ident = with_env({"SLURM_PROCID": "5", "SLURM_STEP_NUM_TASKS": "8",
                      "SLURM_LOCALID": "1",
                      "SLURM_STEP_TASKS_PER_NODE": "4(x2)"})
    assert ident == {"RANK": 5, "SIZE": 8, "LOCAL_RANK": 1,
                     "LOCAL_SIZE": 4, "CROSS_RANK": 1, "CROSS_SIZE": 2}

    # Config.get precedence: HVD_TPU_ > HOROVOD_ > family detection
    import unittest.mock as mock
    base = {"OMPI_COMM_WORLD_RANK": "3", "OMPI_COMM_WORLD_SIZE": "8"}
    with mock.patch.dict("os.environ", base, clear=False):
        for v in FAMILY_VARS:
            if v not in base:
                os.environ.pop(v, None)
        cfg = _config.Config()
        assert cfg.get(_config.RANK) == 3
        assert cfg.get(_config.SIZE) == 8
        with mock.patch.dict("os.environ", {"HOROVOD_RANK": "5"}):
            assert cfg.get(_config.RANK) == 5
            with mock.patch.dict("os.environ", {"HVD_TPU_RANK": "6"}):
                assert cfg.get(_config.RANK) == 6


def test_config_describe_provenance(monkeypatch):
    """describe() reports the live value AND its true source for every
    knob (docs/configuration.md points debugging at it)."""
    from horovod_tpu import config

    monkeypatch.setenv("HVD_TPU_FUSION_THRESHOLD", "1048576")
    monkeypatch.setenv("HOROVOD_CACHE_CAPACITY", "7")
    out = config.Config()
    text = config.describe(out)
    lines = {l.split()[0]: l for l in text.splitlines()}
    assert "[env HVD_TPU_FUSION_THRESHOLD]" in lines["HVD_TPU_FUSION_THRESHOLD"]
    assert "1048576" in lines["HVD_TPU_FUSION_THRESHOLD"]
    assert "[env HOROVOD_CACHE_CAPACITY]" in lines["HVD_TPU_CACHE_CAPACITY"]
    out.set("CYCLE_TIME", 9.5)
    lines2 = {l.split()[0]: l for l in config.describe(out).splitlines()}
    assert "[override]" in lines2["HVD_TPU_CYCLE_TIME"]
    assert len(text.splitlines()) == len(config.knobs())


def test_jax_profiler_helpers(tmp_path):
    import jax.numpy as jnp
    import jax
    import horovod_tpu as hvd

    hvd.start_jax_profiler(str(tmp_path))
    jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    hvd.stop_jax_profiler()
    files = list(tmp_path.rglob("*"))
    assert files, "profiler produced no trace files"


# -- one process per chip: the worker-side refusal ---------------------------
def _on_a_tpu_host(monkeypatch, chips=4):
    from types import SimpleNamespace

    from jax._src import hardware_utils

    from horovod_tpu import basics
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (chips, None))
    # the suite pins jax to the CPU; pose as a process that may use the TPU
    monkeypatch.setattr(basics, "_jax", lambda: SimpleNamespace(
        config=SimpleNamespace(jax_platforms=None)))
    return basics


def test_unbound_ranks_sharing_a_tpu_host_are_refused(monkeypatch):
    basics = _on_a_tpu_host(monkeypatch)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    with pytest.raises(RuntimeError, match="without a chip binding"):
        basics._check_chip_binding(3)


def test_chip_binding_check_lets_the_supported_layouts_through(monkeypatch):
    basics = _on_a_tpu_host(monkeypatch)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    basics._check_chip_binding(1)           # one rank drives every chip
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
    basics._check_chip_binding(4)           # bound by the launcher
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    _on_a_tpu_host(monkeypatch, chips=0)._check_chip_binding(3)   # no TPU


def test_chip_binding_check_ignores_a_cpu_pinned_process(monkeypatch):
    """Under JAX_PLATFORMS=cpu (this suite) the chips are never opened."""
    from jax._src import hardware_utils

    from horovod_tpu import basics
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (4, None))
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    basics._check_chip_binding(3)
