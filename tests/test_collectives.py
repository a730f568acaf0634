"""Eager and in-jit collective tests.

Modeled on the reference suites (/root/reference/test/test_torch.py:
test_horovod_allreduce*, test_horovod_allgather*, test_horovod_broadcast*,
error-path tests at :325-434): random tensors over dtypes x dims compared
against local math, plus deliberate misuse (duplicate names, bad ops).
Single-process eager semantics here (size-1 degradation, as the reference
tests do without a launcher); real multi-process runs live in
test_multiprocess_integration.py; device-granular reduction semantics are
covered by the in-jit tests over the 8-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.exceptions import DuplicateNameError

DTYPES = [np.float32, np.float64, np.int32, np.int64, np.uint8, np.bool_]
DIMS = [1, 2, 3]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("dim", DIMS)
def test_allreduce_size1(hvd_world, dtype, dim):
    rng = np.random.RandomState(42)
    shape = (17,) * dim
    x = (rng.uniform(-100, 100, size=shape)).astype(dtype)
    out = hvd.allreduce(x, op=hvd.Sum)
    np.testing.assert_array_equal(np.asarray(out), x)
    assert np.asarray(out).dtype == dtype


def test_allreduce_average_default(hvd_world):
    x = np.ones((4, 4), np.float32) * 3
    out = hvd.allreduce(x)  # default Average; size 1 -> identity
    np.testing.assert_allclose(np.asarray(out), x)


def test_allreduce_prescale_postscale(hvd_world):
    x = np.full((8,), 2.0, np.float32)
    out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=0.5,
                        postscale_factor=4.0)
    np.testing.assert_allclose(np.asarray(out), x * 2.0)


def test_allreduce_int_scale_error(hvd_world):
    with pytest.raises(ValueError):
        hvd.allreduce(np.ones((4,), np.int32), op=hvd.Sum,
                      prescale_factor=0.5)


def test_allreduce_average_and_op_both_set_error(hvd_world):
    with pytest.raises(ValueError):
        hvd.allreduce(np.ones(3, np.float32), average=True, op=hvd.Sum)


def test_allreduce_bad_op_type(hvd_world):
    with pytest.raises(TypeError):
        hvd.allreduce(np.ones(3, np.float32), op="sum")


def test_duplicate_name_error(hvd_world):
    h = hvd.allreduce_async(np.ones(3, np.float32), name="dup")
    with pytest.raises(DuplicateNameError):
        hvd.allreduce_async(np.ones(3, np.float32), name="dup")
    hvd.synchronize(h)
    # after synchronize the name is free again (reference: name released when
    # the op completes)
    h2 = hvd.allreduce_async(np.ones(3, np.float32), name="dup")
    hvd.synchronize(h2)


def test_async_poll_synchronize(hvd_world):
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    h = hvd.allreduce_async(x, op=hvd.Sum, name="apoll")
    assert isinstance(h, int)
    out = hvd.synchronize(h)
    np.testing.assert_array_equal(np.asarray(out), x)
    with pytest.raises(ValueError):
        hvd.synchronize(h)  # handle consumed


def test_grouped_allreduce(hvd_world):
    xs = [np.full((5,), float(i), np.float32) for i in range(4)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum)
    assert len(outs) == 4
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), xs[i])


def test_grouped_allreduce_hybrid_packing_paths(hvd_world):
    """The fused dispatch routes members three ways (host-packed per
    dtype, large-separate, device-resident-separate); results must come
    back in input order regardless of route. Covers the round-5 hybrid
    fusion buffer: members over HVD_TPU_PACK_CUTOFF bytes stage
    separately, the rest pack per dtype."""
    import jax.numpy as jnp
    from horovod_tpu import config as _config
    from horovod_tpu.basics import world
    assert world().config.get(_config.PACK_CUTOFF) == 256 * 1024
    big = np.full((80000,), 2.0, np.float32)      # 320KB > cutoff
    xs = [
        np.full((7,), 1.0, np.float32),           # packed (f32 group)
        big,                                      # separate: too large
        np.arange(4, dtype=np.int32),             # packed (i32 group)
        jnp.full((3,), 5.0, jnp.float32),         # separate: on device
        np.full((2, 2), 3.0, np.float32),         # packed (f32 group)
        np.float32(4.0).reshape(()),              # packed scalar
    ]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="hybrid")
    assert len(outs) == len(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(x))
        assert np.asarray(o).dtype == np.asarray(x).dtype
        assert np.asarray(o).shape == np.asarray(x).shape


def test_grouped_program_cache_does_not_pin_inputs(hvd_world):
    """The cached jit programs must capture only the plan, never the
    first call's tensors — a 97 MB gradient list pinned per cache entry
    for the process lifetime is a leak (round-5 review finding)."""
    import gc
    import weakref
    big = np.ones(80000, np.float32)      # separate route (> cutoff)
    small = np.ones(7, np.float32)        # packed route
    refs = [weakref.ref(big), weakref.ref(small)]
    hvd.grouped_allreduce([small, big], op=hvd.Sum, name="pin1")
    # second call through the now-cached program with fresh values
    hvd.grouped_allreduce([np.ones(7, np.float32),
                           np.ones(80000, np.float32)],
                          op=hvd.Sum, name="pin2")
    del big, small
    gc.collect()
    assert all(r() is None for r in refs), \
        "cached collective program retains first-call tensors"


def test_grouped_allreduce_pack_cutoff_zero_disables(hvd_world,
                                                     monkeypatch):
    monkeypatch.setenv("HVD_TPU_PACK_CUTOFF", "0")
    xs = [np.full((5,), float(i + 1), np.float32) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="nopack")
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(np.asarray(o), x)


def test_grouped_allreduce_average_and_scales_across_routes(hvd_world):
    """Scales apply per member on both the packed and separate routes."""
    big = np.full((80000,), 4.0, np.float32)
    xs = [np.full((3,), 4.0, np.float32), big]
    outs = hvd.grouped_allreduce(xs, op=hvd.Average, prescale_factor=0.5)
    np.testing.assert_allclose(np.asarray(outs[0]), np.full((3,), 2.0))
    np.testing.assert_allclose(np.asarray(outs[1]),
                               np.full((80000,), 2.0))


def test_allgather_size1(hvd_world):
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = hvd.allgather(x)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_broadcast_size1_and_validation(hvd_world):
    x = np.arange(4, dtype=np.int32)
    out = hvd.broadcast(x, root_rank=0)
    np.testing.assert_array_equal(np.asarray(out), x)
    with pytest.raises(ValueError):
        hvd.broadcast(x, root_rank=5)


def test_alltoall_size1(hvd_world):
    x = np.arange(8, dtype=np.float32)
    out = hvd.alltoall(x)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_join_and_barrier(hvd_world):
    hvd.barrier()
    assert not hvd.joined()
    last = hvd.join()
    assert last == 0
    assert hvd.joined()


# ---------------------------------------------------------------------------
# In-jit (compiled-plane) collectives over the 8-device mesh: this is where
# real reductions across "ranks" (devices) are validated, matching the
# reference's multi-process numeric tests.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from horovod_tpu import collectives as C  # noqa: E402


def _ranked(mesh, shape=(8, 4)):
    """Per-device distinct values: row d = d+1."""
    rows = np.stack([np.full(shape[1:], d + 1, np.float32)
                     for d in range(shape[0])])
    return rows


def test_injit_psum(hvd_world, mesh8):
    x = _ranked(mesh8)
    f = shard_map(lambda v: C.psum(v, "world"), mesh=mesh8,
                  in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x))
    expected = np.tile(np.full((1, 4), sum(range(1, 9)), np.float32), (8, 1))
    np.testing.assert_allclose(out, expected)


def test_injit_pmean(hvd_world, mesh8):
    x = _ranked(mesh8)
    f = shard_map(lambda v: C.pmean(v, "world"), mesh=mesh8,
                  in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x))
    np.testing.assert_allclose(out, np.full((8, 4), 4.5, np.float32))


def test_injit_all_gather(hvd_world, mesh8):
    x = _ranked(mesh8)
    f = shard_map(lambda v: C.all_gather_in_jit(v, "world"), mesh=mesh8,
                  in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x))
    # tiled all_gather leaves the full (8, 4) on every device; stacked over
    # the mesh that is 8 copies of x
    np.testing.assert_allclose(out, np.tile(x, (8, 1)))


def test_injit_reduce_scatter(hvd_world, mesh8):
    x = np.tile(np.arange(8, dtype=np.float32)[:, None], (1, 8))  # (dev, 8)
    f = shard_map(lambda v: C.reduce_scatter_in_jit(v[0], "world"),
                  mesh=mesh8, in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x))
    # each device ends with its 1-element chunk of the summed vector
    np.testing.assert_allclose(out, np.full((8,), 28.0, np.float32))


def test_injit_all_to_all(hvd_world, mesh8):
    # device d holds row of 8 values d*8..d*8+7; all_to_all transposes chunks
    x = np.arange(64, dtype=np.float32).reshape(8, 8)

    def fn(v):  # per-device shard (1, 8)
        return C.all_to_all_in_jit(v, "world", split_axis=1, concat_axis=1)
    f = shard_map(fn, mesh=mesh8, in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x))
    np.testing.assert_allclose(out, x.T)


def test_injit_ppermute_ring(hvd_world, mesh8):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    perm = [(i, (i + 1) % 8) for i in range(8)]
    f = shard_map(lambda v: C.ppermute(v, "world", perm), mesh=mesh8,
                  in_specs=P("world"), out_specs=P("world"))
    out = np.asarray(jax.jit(f)(x)).reshape(-1)
    np.testing.assert_allclose(out, np.roll(np.arange(8, dtype=np.float32), 1))


def test_jax_array_inputs_stay_on_device(hvd_world):
    """allreduce/allgather/broadcast accept jax arrays without a host
    round trip (_stage_input keeps fully-addressable jax arrays
    as-is)."""
    import jax.numpy as jnp
    from horovod_tpu import collectives as _c

    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(_c.allreduce(x, op=_c.Sum, name="jx.ar")),
        np.arange(8, dtype=np.float32))
    g = _c.allgather(jnp.ones((2, 3), jnp.float32), name="jx.ag")
    assert np.asarray(g).shape == (2, 3)
    b = _c.broadcast(jnp.full((4,), 7.0, jnp.float32), root_rank=0,
                     name="jx.bc")
    np.testing.assert_allclose(np.asarray(b), 7.0)
    # bf16 path (no numpy-native dtype) survives too
    hb = _c.allreduce(jnp.ones((3,), jnp.bfloat16), op=_c.Sum, name="jx.bf")
    assert str(np.asarray(hb).dtype) == "bfloat16"


def test_joined_zero_substitution_preserves_residency(hvd_world):
    """join()'s zero substitution must keep each member's host/device
    residency: the hybrid routing is part of the compiled SPMD program
    and must stay identical across ranks (round-5 review finding)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.collectives import _zeros_like_staged
    z = _zeros_like_staged(np.ones(4, np.float32))
    assert isinstance(z, np.ndarray) and not z.any()
    zd = _zeros_like_staged(jnp.ones((2, 3), jnp.float32))
    assert isinstance(zd, jax.Array) and not np.asarray(zd).any()
    assert zd.shape == (2, 3)


def test_alltoall_input_residency_numerics(hvd_world):
    """alltoall numerics are identical for device (jax array) and host
    (numpy) inputs, uniform or ragged. A size-1 world short-circuits
    before the pack/unpack programs, so the on-device-path PROOF (jit
    cache keys a2a_pack/a2a_unpack after a device-resident uniform call)
    lives in tests/integration_worker.py over real processes."""
    x = jnp.arange(12, dtype=jnp.float32).reshape(12, 1) * 2
    out = hvd.alltoall(x, name="a2a.dev")
    assert isinstance(out, jax.Array)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    y = jnp.arange(5, dtype=jnp.float32)
    out2 = hvd.alltoall(y, splits=[5], name="a2a.devragged")
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(y))
    z = np.arange(6, dtype=np.float32)
    out3 = hvd.alltoall(z, splits=[6], name="a2a.host")
    np.testing.assert_array_equal(np.asarray(out3), z)


def test_program_cache_lru_bound(hvd_world, monkeypatch):
    """The compiled-program cache honors HVD_TPU_PROGRAM_CACHE_CAPACITY
    as an LRU bound (floor 16): data-dependent key streams (ragged
    alltoallv maxs) must not grow it — and the XLA executables it pins —
    forever. Evicted programs rebuild correctly on reuse."""
    import horovod_tpu as hvd2
    hvd2.shutdown()
    monkeypatch.setenv("HVD_TPU_PROGRAM_CACHE_CAPACITY", "4")  # floor 16
    hvd2.init()
    try:
        from horovod_tpu.basics import world
        from horovod_tpu.collectives import _jit_cache
        cache = _jit_cache(world())
        for n in range(1, 41):  # 40 distinct shapes -> 40 distinct keys
            out = hvd2.allreduce(np.ones(n, np.float32), op=hvd2.Sum,
                                 name=f"lru.{n}")
            np.testing.assert_array_equal(np.asarray(out), np.ones(n))
        # exactly at the floor: proves insertions DID flow through the
        # bounded cache (a <= alone would pass vacuously on an empty one)
        assert len(cache) == 16, len(cache)
        # an evicted shape still computes correctly (rebuilds)
        out = hvd2.allreduce(np.ones(1, np.float32), op=hvd2.Sum,
                             name="lru.again")
        np.testing.assert_array_equal(np.asarray(out), np.ones(1))
    finally:
        hvd2.shutdown()
