"""init(comm=...) interop (VERDICT r4 item 8; reference
/root/reference/horovod/common/basics.py:33-65 horovod_init_comm).

The communicator is duck-typed on the mpi4py surface, so the always-on
tests use fakes (single-process inline; two real processes through a
file-backed comm with NO env contract); the real-mpi4py test self-skips
when mpi4py is absent.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "comm_init_worker.py")


class _SoloComm:
    def Get_rank(self):
        return 0

    def Get_size(self):
        return 1

    def bcast(self, obj, root=0):  # pragma: no cover - size-1 never bcasts
        return obj


def test_init_comm_single():
    """A size-1 communicator initializes a size-1 world with no env."""
    import horovod_tpu as hvd
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.init(comm=_SoloComm())
    try:
        assert hvd.rank() == 0 and hvd.size() == 1
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="c1")
        np.testing.assert_allclose(np.asarray(out), np.ones(2))
    finally:
        hvd.shutdown()


def test_init_comm_ranks_list_requires_mpi4py():
    """The list-of-ranks form needs mpi4py to split COMM_WORLD; without
    it the error must say so (not crash in some unrelated way)."""
    try:
        import mpi4py  # noqa: F401
        pytest.skip("mpi4py installed; list form is exercised for real")
    except ImportError:
        pass
    import horovod_tpu as hvd
    if hvd.is_initialized():
        hvd.shutdown()
    with pytest.raises(ValueError, match="mpi4py"):
        hvd.init(comm=[0, 1])


@pytest.mark.integration
def test_init_comm_two_processes_no_env_contract(tmp_path):
    """Two real processes rendezvous purely through the communicator:
    rank 0 binds the coordinator, bcasts the address over the comm, both
    join and allreduce — no HVD_TPU_* env at all."""
    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("HVD_TPU_", "HOROVOD_"))}
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            os.path.abspath(WORKER)))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(rank), "2", str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs, codes = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode(errors="replace"))
        codes.append(p.returncode)
    for r, (c, o) in enumerate(zip(codes, outs)):
        assert c == 0, f"rank {r} failed (exit {c}):\n{o[-3000:]}"
        assert f"comm init worker {r} OK" in o


def test_init_comm_real_mpi4py():
    """With real mpi4py (self-skips otherwise): COMM_WORLD drives
    identity. Under a plain `python` run COMM_WORLD is size 1, so this
    validates the genuine mpi4py object against the duck-typed surface;
    under `mpirun -np N python -m pytest` it validates N-process init."""
    MPI = pytest.importorskip("mpi4py.MPI")
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init(comm=MPI.COMM_WORLD)
    try:
        assert hvd.rank() == MPI.COMM_WORLD.Get_rank()
        assert hvd.size() == MPI.COMM_WORLD.Get_size()
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="cm")
        np.testing.assert_allclose(np.asarray(out),
                                   np.full(2, float(hvd.size())))
    finally:
        hvd.shutdown()


def test_routable_host_never_loopback_when_route_exists():
    """The comm-rendezvous coordinator address must be dialable by
    remote peers: when the hostname resolves to 127.x (stock Debian
    /etc/hosts), the default-route interface IP is used instead."""
    from horovod_tpu.basics import _routable_host
    import socket
    host = _routable_host()
    assert host
    try:
        resolved = socket.gethostbyname(host)
    except OSError:
        resolved = host
    # either a non-loopback resolution, or the box genuinely has no
    # route (then the hostname fallback is the best available)
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 53))
            has_route = not s.getsockname()[0].startswith("127.")
    except OSError:
        has_route = False
    if has_route:
        assert not resolved.startswith("127."), (host, resolved)
