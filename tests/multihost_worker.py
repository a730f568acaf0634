"""Worker for the simulated 2-host x 2-slot integration test.

Each of 4 real processes is told (via the launcher env contract) that it
lives on one of two simulated hosts with two slots each. Asserts the
GLOBAL/LOCAL/CROSS identity triple (reference: common.h:111,
mpi_context.cc:147-156 communicator split math) and then runs the
hierarchical allreduce decomposition (reference NCCLHierarchicalAllreduce,
nccl_operations.cc:178-372) over a real (node, slot) mesh spanning the 4
processes, checking it against plain psum and the numpy recompute.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()

    # --- identity triple from the env contract (what the launcher's
    # get_host_assignments computed for host list a:2,b:2)
    assert hvd.size() == 4, hvd.size()
    assert hvd.local_rank() == rank % 2, (rank, hvd.local_rank())
    assert hvd.local_size() == 2, hvd.local_size()
    assert hvd.cross_rank() == rank // 2, (rank, hvd.cross_rank())
    assert hvd.cross_size() == 2, hvd.cross_size()

    # --- hierarchical allreduce over a (node, slot) mesh of the 4
    # process-devices: reduce_scatter over the intra-host axis, psum over
    # the cross-host axis, all_gather back — must equal plain psum over
    # both axes and the numpy total.
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from horovod_tpu.parallel.hierarchical import hierarchical_allreduce

    devs = np.array(jax.devices()).reshape(2, 2)  # rows = simulated hosts
    mesh = Mesh(devs, ("node", "slot"))

    # per-process contribution: rank-dependent so ordering bugs show
    local = (np.arange(8, dtype=np.float32) + 1) * (rank + 1)
    expected = np.stack(
        [(np.arange(8, dtype=np.float32) + 1) * (r + 1) for r in range(4)]
    ).sum(axis=0)

    garr = jax.make_array_from_single_device_arrays(
        (4, 8),
        jax.sharding.NamedSharding(mesh, P(("node", "slot"), None)),
        [jax.device_put(local[None], jax.local_devices()[0])])

    @partial(shard_map, mesh=mesh, in_specs=P(("node", "slot"), None),
             out_specs=P(("node", "slot"), None))
    def hier(x):
        return hierarchical_allreduce(
            x[0], inner_axis="slot", outer_axis="node",
            scatter_dimension=0)[None]

    @partial(shard_map, mesh=mesh, in_specs=P(("node", "slot"), None),
             out_specs=P(("node", "slot"), None))
    def plain(x):
        return jax.lax.psum(x[0], ("node", "slot"))[None]

    out_h = np.asarray(jax.jit(hier)(garr).addressable_data(0))[0]
    out_p = np.asarray(jax.jit(plain)(garr).addressable_data(0))[0]
    np.testing.assert_allclose(out_h, expected, rtol=1e-6)
    np.testing.assert_allclose(out_p, expected, rtol=1e-6)

    # --- eager plane sanity on the same 4-process world
    out = np.asarray(hvd.allreduce(
        np.full(4, float(rank + 1), np.float32), op=hvd.Sum, name="mh"))
    np.testing.assert_allclose(out, np.full(4, 10.0), rtol=1e-6)

    print(f"multihost worker {rank} OK "
          f"(local {hvd.local_rank()}/{hvd.local_size()} "
          f"cross {hvd.cross_rank()}/{hvd.cross_size()})", flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
