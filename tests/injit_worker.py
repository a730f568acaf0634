"""Worker for the 2-process in-jit fast-path parity test (docs/injit.md).

Each process owns one CPU device. Validates that a collective verb
called under jit/shard_map over the 2-process world mesh lowers
in-trace (zero dispatcher submissions, metrics-verified) and produces
bit-identical fp32 results to the eager dispatcher path on the same
per-rank payloads — the cross-plane agreement the compiled SPMD program
is supposed to embody.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    from functools import partial

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    assert size == 2, size

    mesh = Mesh(np.array(jax.devices()), ("world",))
    # integer-valued payloads: fp32 sums are exact, so eager-vs-injit
    # parity below is assert_array_equal, not allclose
    local = (np.arange(12, dtype=np.float32) + 1.0) * (rank + 1)
    garr = jax.make_array_from_single_device_arrays(
        (2, 12), NamedSharding(mesh, P("world", None)),
        [jax.device_put(local[None], jax.local_devices()[0])])

    # --- eager plane: the dispatcher path (reference semantics)
    eager_out = np.asarray(hvd.allreduce(local, op=hvd.Sum, name="pw_eager"))

    ops_key = 'hvd_tpu_collective_ops_total{op="allreduce"}'
    injit_key = 'hvd_tpu_injit_lowerings_total{op="allreduce"}'
    before = hvd_metrics.snapshot()

    # --- compiled plane: the same verb, called under jit/shard_map —
    # must lower in-trace with zero dispatcher submissions
    @partial(shard_map, mesh=mesh, in_specs=P("world", None),
             out_specs=P("world", None), check_vma=False)
    def step(x):
        return hvd.allreduce(x[0], op=hvd.Sum, name="pw_injit")[None]

    injit_out = np.asarray(jax.jit(step)(garr).addressable_data(0))[0]

    after = hvd_metrics.snapshot()
    assert after.get(ops_key, 0) == before.get(ops_key, 0), \
        (before.get(ops_key), after.get(ops_key))
    assert after.get(injit_key, 0) > before.get(injit_key, 0)

    np.testing.assert_array_equal(injit_out, eager_out)
    expected = sum((np.arange(12, dtype=np.float32) + 1.0) * (r + 1)
                   for r in range(size))
    np.testing.assert_array_equal(injit_out, expected)

    # --- grouped verb: packed in-jit buckets vs eager grouped dispatch
    xs = [np.full((3,), float(rank + 1), np.float32),
          np.full((2, 2), float((rank + 1) * 2), np.float32)]
    eager_group = [np.asarray(o) for o in
                   hvd.grouped_allreduce(xs, op=hvd.Sum, name="pw_grp")]

    flat = np.concatenate([x.ravel() for x in xs])
    gflat = jax.make_array_from_single_device_arrays(
        (2, flat.size), NamedSharding(mesh, P("world", None)),
        [jax.device_put(flat[None], jax.local_devices()[0])])

    @partial(shard_map, mesh=mesh, in_specs=P("world", None),
             out_specs=P("world", None), check_vma=False)
    def grouped(x):
        a = x[0, :3].reshape(3)
        b = x[0, 3:].reshape(2, 2)
        outs = hvd.grouped_allreduce([a, b], op=hvd.Sum, name="pw_grp_injit")
        import jax.numpy as jnp
        return jnp.concatenate([jnp.ravel(o) for o in outs])[None]

    out = np.asarray(jax.jit(grouped)(gflat).addressable_data(0))[0]
    np.testing.assert_array_equal(out[:3], eager_group[0].ravel())
    np.testing.assert_array_equal(out[3:], eager_group[1].ravel())

    print(f"injit worker {rank} OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
