"""Process/world lifecycle for horovod_tpu.

TPU-native analogue of the reference's ``HorovodBasics``
(/root/reference/horovod/common/basics.py:25-215) and the C init path
(`InitializeHorovodOnce`, common/operations.cc:611-657). Instead of spawning a
background C++ coordination thread and rendezvousing MPI/Gloo communicators,
``init()``:

1. connects the JAX distributed runtime (coordinator address from the
   launcher's env contract — the analogue of the Gloo HTTP rendezvous,
   gloo/gloo_context.cc:70-171) when running multi-process;
2. builds the eager-plane :class:`~horovod_tpu.mesh.WorldMesh`;
3. starts host-side services (timeline, stall inspector, async coordinator).

Rank semantics (documented departure from the reference): the reference runs
one process per GPU, so ``rank`` is both a process and a device. On TPU the
single-controller model runs one process per *host* and addresses devices
through meshes, so:

* ``rank()/size()`` are **process**-granular (what eager collectives reduce
  over);
* ``device_count()/local_device_count()`` are chip-granular;
* inside compiled code, per-device identity comes from
  ``jax.lax.axis_index(axis)`` over the training mesh.

For learning-rate scaling in data-parallel training use
``horovod_tpu.dp_size()`` (= devices on the data axis), the moral equivalent
of the reference's ``hvd.size()`` in its GPU-per-process world.
"""

import atexit
import os
import socket
import threading
from typing import Optional, Sequence

from . import config as _config
from . import metrics as _metrics
from .compile_cache import ensure_compile_cache as _ensure_compile_cache
from .exceptions import NotInitializedError

_lock = threading.Lock()
_world: Optional["World"] = None

_M_INITS = _metrics.counter(
    "hvd_tpu_init_total",
    "hvd.init() completions (elastic resets re-init, so a climbing count "
    "on a long-lived process is a reset-rate signal).")
_M_SHUTDOWNS = _metrics.counter(
    "hvd_tpu_shutdown_total", "hvd.shutdown() completions.")
_M_WORLD_SIZE = _metrics.gauge(
    "hvd_tpu_world_size", "Process count of the current world.")


class World:
    """Singleton world state (reference: HorovodGlobalState,
    common/global_state.h:42-122)."""

    def __init__(self, cfg: _config.Config):
        self.config = cfg
        self.process_id = 0
        self.num_processes = 1
        self.coordinator_addr = ""
        self.world_mesh = None          # WorldMesh, built in init()
        self.controller = None          # set when multi-process
        self.coordinator = None         # async fusion coordinator (lazy)
        self.timeline = None
        self.stall_inspector = None
        self.parameter_manager = None
        self.metrics_server = None      # Prometheus endpoint (metrics.py)
        self.process_sets = {}
        self.joined = False
        self.shutdown_requested = False

    # -- queries -------------------------------------------------------------
    def rank(self) -> int:
        return self.process_id

    def size(self) -> int:
        return self.num_processes

    def local_rank(self) -> int:
        # One process per host in the TPU model; if a launcher packs several
        # processes per host it exports the reference env contract.
        v = self.config.get(_config.LOCAL_RANK)
        return v if v >= 0 else 0

    def local_size(self) -> int:
        v = self.config.get(_config.LOCAL_SIZE)
        return v if v >= 0 else 1

    def cross_rank(self) -> int:
        v = self.config.get(_config.CROSS_RANK)
        return v if v >= 0 else self.process_id

    def cross_size(self) -> int:
        v = self.config.get(_config.CROSS_SIZE)
        return v if v >= 0 else self.num_processes


def _jax():
    import jax
    return jax


def _identity_from_comm(comm, coordinator_address):
    """Derive (coordinator_address, size, rank) from an MPI communicator
    (reference: ``hvd.init(comm=...)`` / horovod_init_comm,
    common/basics.py:33-65 — rank identity and rendezvous both ride the
    caller's communicator instead of env vars).

    ``comm`` is duck-typed on the mpi4py surface (``Get_rank``,
    ``Get_size``, ``bcast``), so any communicator-shaped object works —
    including a subcommunicator, in which case THIS job's world is that
    subcomm (the reference's subset-communicator semantics). Rank 0 of
    ``comm`` binds the JAX coordinator and broadcasts its address over
    the communicator itself, so no launcher env contract is needed.
    """
    import socket

    rank, size = int(comm.Get_rank()), int(comm.Get_size())
    if size > 1 and coordinator_address is None:
        addr = None
        if rank == 0:
            with socket.socket() as s:
                s.bind(("0.0.0.0", 0))
                port = s.getsockname()[1]
            addr = f"{_routable_host()}:{port}"
        coordinator_address = comm.bcast(addr, root=0)
    return coordinator_address, size, rank


def _check_chip_binding(local_size: int) -> None:
    """Refuse to start as one of several unbound ranks on a TPU host.

    A chip belongs to one process. The launcher binds each local slot to
    its own chip where it knows the host's chip grid
    (``runner.exec_run.chip_binding_env``); without that binding every
    rank opens every chip and all but the first fail or hang inside the
    runtime. The chip count comes from the PCI bus, so nothing here
    touches the chips."""
    if local_size <= 1 or os.environ.get("TPU_VISIBLE_CHIPS"):
        return
    platforms = _jax().config.jax_platforms
    if platforms and "tpu" not in platforms.split(","):
        return
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    chips, _ = num_available_tpu_chips_and_device_id()
    if chips:
        raise RuntimeError(
            f"{local_size} ranks were started on this host, which has "
            f"{chips} TPU chip(s), without a chip binding: the launcher "
            f"gives each rank its own chip only for a single-host job with "
            f"as many slots as a known host shape (-H localhost:4 on a "
            f"four-chip host). Start one rank per host instead (-H "
            f"host:1): one process drives all of a host's chips.")


def _routable_host() -> str:
    """A host identity peers can actually dial. ``gethostname()`` alone is
    a trap on stock Debian/Ubuntu, where /etc/hosts maps the hostname to
    127.0.1.1 — remote ranks would connect to themselves and hang in
    jax.distributed init. Prefer the default-route interface IP (UDP
    connect performs no traffic); keep the hostname when it already
    resolves to a routable address (reference: the driver/task services
    resolve a usable NIC the same spirit, runner/driver_service.py)."""
    import socket

    host = socket.gethostname()
    try:
        resolved = socket.gethostbyname(host)
    except OSError:
        resolved = "127.0.0.1"
    if not resolved.startswith("127."):
        return host
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 53))
            ip = s.getsockname()[0]
        if ip and not ip.startswith("127."):
            return ip
    except OSError:
        pass
    return host


def init(process_sets: Optional[Sequence[Sequence[int]]] = None,
         comm=None,
         coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         config_overrides: Optional[dict] = None) -> None:
    """Initialize horovod_tpu.

    Single-process (the default on a TPU host, where all local chips are
    addressable without any rendezvous): no arguments needed. Multi-process
    (launched by ``horovodrun-tpu`` or manually): the coordinator address and
    process identity come from arguments or the env contract
    (HVD_TPU_COORDINATOR_ADDR / HVD_TPU_RANK / HVD_TPU_SIZE — same shape as
    the reference's HOROVOD_GLOO_RENDEZVOUS_ADDR / HOROVOD_RANK / HOROVOD_SIZE
    contract, gloo/gloo_context.cc:142-165).

    ``comm``: an mpi4py communicator (or any object with
    ``Get_rank/Get_size/bcast``) supplying identity AND rendezvous — the
    reference's ``hvd.init(comm=...)`` (common/basics.py:33-65). A
    subcommunicator makes this job's world exactly that subcomm. A LIST of
    world ranks is the reference's other accepted form: it is turned into
    an mpi4py subcommunicator of ``COMM_WORLD`` (requires mpi4py; only the
    listed ranks may call ``init``).

    ``process_sets``: optional list of process-index lists, the analogue of
    the reference's subset communicators. Retrieve with
    :func:`process_set_mesh`.
    """
    global _world
    with _lock:
        if _world is not None:
            return
        cfg = _config.Config(config_overrides)
        w = World(cfg)

        # Fail fast on a malformed HVD_TPU_FAULT_SPEC: parsed here (once
        # per process) so a typo is a startup FaultSpecError, not a
        # mid-training surprise the elastic loop would retry forever.
        from . import faults as _faults
        _faults.ensure_configured()

        if comm is not None and isinstance(comm, (list, tuple)):
            try:
                from mpi4py import MPI
            except ImportError as e:
                raise ValueError(
                    "init(comm=[ranks]) requires mpi4py to split "
                    "COMM_WORLD; pass an mpi4py (sub)communicator or use "
                    "process_sets instead") from e
            ranks = sorted(set(comm))
            # MPI_Comm_create_group is collective over the GROUP only and
            # is erroneous from a non-member (unlike MPI_Comm_create's
            # COMM_NULL contract), so membership must be checked first.
            if MPI.COMM_WORLD.Get_rank() not in ranks:
                raise ValueError(
                    f"this process (COMM_WORLD rank "
                    f"{MPI.COMM_WORLD.Get_rank()}) is not in "
                    f"init(comm={ranks}); only listed ranks may call init")
            comm = MPI.COMM_WORLD.Create_group(
                MPI.COMM_WORLD.group.Incl(ranks))

        if comm is not None:
            coordinator_address, num_processes, process_id = \
                _identity_from_comm(comm, coordinator_address)

        addr = coordinator_address or cfg.get(_config.COORDINATOR_ADDR) or None
        n = num_processes if num_processes is not None else cfg.get(_config.SIZE)
        pid = process_id if process_id is not None else cfg.get(_config.RANK)

        jax = _jax()
        _ensure_compile_cache()
        _check_chip_binding(w.local_size())
        if addr and n and n > 1:
            # Controlled failure-detection latency: under an elastic launch
            # a dead peer must surface quickly so the driver's recovery
            # path (respawn + state restore) wins over a stalled job; a
            # non-elastic job has no recovery path and keeps the tolerant
            # jax default instead.
            heartbeat = cfg.get(_config.HEARTBEAT_TIMEOUT_SECONDS)
            if heartbeat < 0:
                heartbeat = 10.0 if cfg.get(_config.ELASTIC) else 100.0
            # Multi-process eager collectives on the CPU backend need a
            # cross-process implementation; without one the FIRST
            # collective fails ("Multiprocess computations aren't
            # implemented on the CPU backend"), not init.
            if jax.config.jax_cpu_collectives_implementation == "none":
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=addr, num_processes=n, process_id=pid,
                initialization_timeout=int(
                    cfg.get(_config.INIT_TIMEOUT_SECONDS)),
                heartbeat_timeout_seconds=int(heartbeat),
                shutdown_timeout_seconds=int(
                    cfg.get(_config.SHUTDOWN_TIMEOUT_SECONDS)))
            w.coordinator_addr = addr
        w.process_id = jax.process_index()
        w.num_processes = jax.process_count()

        from .mesh import WorldMesh
        w.world_mesh = WorldMesh()

        if process_sets:
            for i, ranks in enumerate(process_sets):
                w.process_sets[i] = w.world_mesh.subset(list(ranks))

        from .logging_setup import configure as _configure_logging
        _configure_logging(cfg)
        # metrics gate + exposition endpoint come up before the other
        # host services so their own startup telemetry is captured
        w.metrics_server = _metrics.configure(w)
        _M_INITS.inc()
        _M_WORLD_SIZE.set(w.num_processes)
        from .timeline import maybe_start_timeline
        w.timeline = maybe_start_timeline(w)
        from .stall import StallInspector
        w.stall_inspector = StallInspector(w)
        from .parameter_manager import maybe_create as _maybe_autotune
        w.parameter_manager = _maybe_autotune(w)

        _world = w
        atexit.register(_shutdown_quietly)


def _shutdown_quietly():
    try:
        shutdown()
    except Exception:
        pass


def shutdown() -> None:
    """Tear down world state (reference: horovod_shutdown,
    operations.cc:690-700). Safe to call twice; after shutdown, init() may be
    called again (elastic reset does exactly this,
    reference torch/elastic.py:46-49)."""
    global _world
    with _lock:
        w = _world
        if w is None:
            return
        w.shutdown_requested = True
        d = getattr(w, "dispatcher", None)
        if d is not None:
            d.stop()
        if w.coordinator is not None:
            w.coordinator.stop()
        if w.timeline is not None:
            w.timeline.close()
        if w.stall_inspector is not None:
            w.stall_inspector.stop()
        # flush + drop the collective schedule ledger so an elastic
        # reset's next generation restarts at sequence 0 on every rank
        from . import _schedule
        _schedule.reset()
        _metrics.stop_http_server(w.metrics_server)
        w.metrics_server = None
        _M_SHUTDOWNS.inc()
        if w.coordinator_addr:
            try:
                _jax().distributed.shutdown()
            except Exception:
                pass
        _world = None


def world() -> World:
    if _world is None:
        raise NotInitializedError()
    return _world


def is_initialized() -> bool:
    return _world is not None


def rank() -> int:
    return world().rank()


def size() -> int:
    return world().size()


def local_rank() -> int:
    return world().local_rank()


def local_size() -> int:
    return world().local_size()


def cross_rank() -> int:
    return world().cross_rank()


def cross_size() -> int:
    return world().cross_size()


def device_count() -> int:
    world()
    return _jax().device_count()


def local_device_count() -> int:
    world()
    return _jax().local_device_count()


def dp_size() -> int:
    """Device-granular world size: the number the reference calls hvd.size()
    in its one-process-per-GPU model. Use for LR scaling of data-parallel
    compiled training."""
    world()
    return _jax().device_count()


def mapped_axis_sizes() -> dict:
    """``{axis_name: size}`` for every named mesh axis mapped over the
    *current trace* (shard_map/pmap scope). Empty when called eagerly or
    under plain jit with no mapped axis — the signal the in-jit
    collective fast path (collectives.py, docs/injit.md) keys on.

    Read from jax's axis environment (``jax._src.core.get_axis_env``; jax
    0.9 exposes no public accessor for the set of mapped axes).
    """
    from jax._src.core import get_axis_env
    return dict(get_axis_env().axis_sizes)


def mapped_axes() -> "tuple":
    """Names of the mapped mesh axes in scope for the current trace,
    outermost first (empty eagerly / under unmapped jit)."""
    return tuple(mapped_axis_sizes())


def is_homogeneous() -> bool:
    """True when every process has the same number of local devices
    (reference: mpi_controller.cc:25-81 homogeneity check)."""
    w = world()
    jax = _jax()
    counts = {}
    for d in jax.devices():
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


def process_set_mesh(i: int):
    """The WorldMesh for process set ``i`` registered at init()."""
    return world().process_sets[i]


def hostname() -> str:
    w = world()
    return w.config.get(_config.HOSTNAME) or socket.gethostname()


# -- capability queries (reference: mpi_built/gloo_built/nccl_built/...,
#    basics.py:140-215). On TPU the data plane is always XLA. -----------------
def xla_built() -> bool:
    return True


def tpu_available() -> bool:
    try:
        return any(d.platform == "tpu" for d in _jax().devices())
    except Exception:
        return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False
