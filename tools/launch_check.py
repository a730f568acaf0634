"""Worker that proves a ``horovodrun-tpu`` launch owns its chips correctly.

Run it under the launcher on a TPU host (through the chip tool — the
launcher parent never touches JAX, so each worker gets its chips)::

    python -m horovod_tpu.runner -np 4 -H localhost:4 python tools/launch_check.py
    python -m horovod_tpu.runner -np 1 -H localhost:1 python tools/launch_check.py

With one slot per chip every rank must see exactly one local chip and all
of the host's chips globally; with one slot the single rank drives them
all. Either way an eager ``hvd.allreduce`` over the ranks and a
``shard_map`` ``psum`` over a mesh of every chip must give the right sums.
Each rank prints one JSON line naming the device it ran on; a wrong count
or sum raises, the rank exits non-zero and the launcher reports it.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    n, n_local = jax.device_count(), jax.local_device_count()
    if n_local * size != n or (size > 1 and n_local != 1):
        raise RuntimeError(
            f"rank {rank}/{size}: {n_local} local of {n} chips — expected "
            f"one chip per rank, or one rank driving every chip")

    # eager plane: sum over ranks of (rank + 1)
    eager = np.asarray(hvd.allreduce(
        jnp.full((8,), rank + 1.0, jnp.float32), op=hvd.Sum,
        name="launch_check"))
    np.testing.assert_array_equal(eager, size * (size + 1) / 2)

    # compiled plane: psum over a mesh of every chip of (device index + 1)
    mesh = Mesh(np.array(jax.devices()), ("chips",))
    sharding = NamedSharding(mesh, P("chips"))
    order = {d: i for i, d in enumerate(jax.devices())}
    x = jax.make_array_from_single_device_arrays(
        (n, 128), sharding,
        [jax.device_put(np.full((1, 128), order[d] + 1.0, np.float32), d)
         for d in jax.local_devices()])
    summed = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "chips"), mesh=mesh, in_specs=P("chips"),
        out_specs=P("chips")))(x)
    for shard in summed.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), n * (n + 1) / 2)

    dev = jax.local_devices()[0]
    print(json.dumps({
        "rank": rank, "size": size, "local_devices": n_local, "devices": n,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "platform": dev.platform, "device_kind": dev.device_kind,
        "jax": jax.__version__, "eager_sum": float(eager[0]),
        "psum": float(np.asarray(summed.addressable_shards[0].data)[0, 0]),
        "ok": True}), flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
