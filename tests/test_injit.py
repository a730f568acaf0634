"""In-jit collective fast path tests (docs/injit.md, ROADMAP item 2).

Covers the three coupled pieces: trace-aware lowering (verbs under
jit/shard_map lower to XLA collectives with zero dispatcher
submissions, metrics-verified), packed fusion buffers (bit-exact fp32
parity per_leaf vs packed; memoized plans), and wire compression
(bf16 error bound; int8 shared-scale quantization with error-feedback
residual carried as optax state — convergence to within tolerance of
uncompressed training).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import fusion
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.compression import Compression
from horovod_tpu.optimizer import Int8ErrorFeedbackState


def _smap(f, mesh, in_specs, out_specs):
    # check_vma=False: all_gather-based lowerings (broadcast, int8) fail
    # shard_map's static replication inference
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _counter(snap, key):
    return snap.get(key, 0)


OPS = 'hvd_tpu_collective_ops_total{op="%s"}'
INJIT = 'hvd_tpu_injit_lowerings_total{op="%s"}'


# -- trace-aware lowering: routing + semantics -------------------------------

def test_injit_allreduce_sum_zero_dispatcher(hvd_world, mesh8):
    before = hvd_metrics.snapshot()
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    f = jax.jit(_smap(lambda v: hvd.allreduce(v, op=hvd.Sum),
                      mesh8, P("world"), P("world")))
    out = np.asarray(f(x))
    np.testing.assert_array_equal(out, np.tile(x.sum(axis=0), (8, 1)))
    after = hvd_metrics.snapshot()
    assert _counter(after, OPS % "allreduce") == \
        _counter(before, OPS % "allreduce")
    assert _counter(after, INJIT % "allreduce") > \
        _counter(before, INJIT % "allreduce")


def test_injit_allreduce_average(hvd_world, mesh8):
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    f = jax.jit(_smap(lambda v: hvd.allreduce(v, op=hvd.Average),
                      mesh8, P("world"), P("world")))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.tile(x.mean(axis=0), (8, 1)), rtol=1e-6)


def test_injit_allreduce_min_max(hvd_world, mesh8):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    fmin = jax.jit(_smap(lambda v: hvd.allreduce(v, op=hvd.Min),
                         mesh8, P("world"), P("world")))
    fmax = jax.jit(_smap(lambda v: hvd.allreduce(v, op=hvd.Max),
                         mesh8, P("world"), P("world")))
    np.testing.assert_array_equal(np.asarray(fmin(x)), np.zeros((8, 1)))
    np.testing.assert_array_equal(np.asarray(fmax(x)), np.full((8, 1), 7.0))


def test_injit_grouped_allreduce_matches_per_leaf_bitexact(hvd_world, mesh8):
    """Packed buckets (grouped verb) vs per-leaf in-jit: same elementwise
    sums in the same order -> bit-identical fp32."""
    a = np.arange(24, dtype=np.float32).reshape(8, 3)
    b = np.arange(40, dtype=np.float32).reshape(8, 5) * 3
    before = hvd_metrics.snapshot()

    def grouped(u, v):
        return tuple(hvd.grouped_allreduce([u, v], op=hvd.Sum))

    def per_leaf(u, v):
        return hvd.allreduce(u, op=hvd.Sum), hvd.allreduce(v, op=hvd.Sum)

    fg = jax.jit(_smap(grouped, mesh8, (P("world"), P("world")),
                       (P("world"), P("world"))))
    fp = jax.jit(_smap(per_leaf, mesh8, (P("world"), P("world")),
                       (P("world"), P("world"))))
    ga, gb = fg(a, b)
    pa, pb = fp(a, b)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(pa))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(pb))
    after = hvd_metrics.snapshot()
    assert _counter(after, OPS % "grouped_allreduce") == \
        _counter(before, OPS % "grouped_allreduce")
    assert _counter(after, INJIT % "grouped_allreduce") > \
        _counter(before, INJIT % "grouped_allreduce")


def test_injit_allgather_broadcast(hvd_world, mesh8):
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    fg = jax.jit(_smap(lambda v: hvd.allgather(v), mesh8,
                       P("world"), P("world")))
    out = np.asarray(fg(x))
    # every shard gathers all 8 rows -> out_specs restacks to (64, 2)
    assert out.shape == (64, 2)
    np.testing.assert_array_equal(out[:8], x)

    fb = jax.jit(_smap(lambda v: hvd.broadcast(v, root_rank=3), mesh8,
                       P("world"), P("world")))
    np.testing.assert_array_equal(np.asarray(fb(x)),
                                  np.tile(x[3], (8, 1)))


def test_injit_async_handle_completes(hvd_world, mesh8):
    def step(v):
        h = hvd.allreduce_async(v, op=hvd.Sum)
        assert hvd.poll(h)
        return hvd.synchronize(h)
    f = jax.jit(_smap(step, mesh8, P("world"), P("world")))
    x = np.ones((8, 2), np.float32)
    np.testing.assert_array_equal(np.asarray(f(x)), np.full((8, 2), 8.0))


def test_injit_unmapped_jit_is_size1(hvd_world):
    # plain pjit, no mapped axis: sharding propagation already supplies
    # globally-correct values — the verb is the identity (mode 2)
    x = jnp.arange(6, dtype=jnp.float32)
    out = jax.jit(lambda v: hvd.allreduce(v, op=hvd.Sum))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_injit_fastpath_disabled_raises(hvd_world, mesh8, monkeypatch):
    monkeypatch.setenv("HVD_TPU_INJIT_FASTPATH", "0")
    f = jax.jit(_smap(lambda v: hvd.allreduce(v, op=hvd.Sum),
                      mesh8, P("world"), P("world")))
    with pytest.raises(TypeError, match="INJIT_FASTPATH"):
        f(np.ones((8, 2), np.float32))


def test_injit_process_set_raises(hvd_world, mesh8):
    f = jax.jit(_smap(
        lambda v: hvd.allreduce(v, op=hvd.Sum, process_set=object()),
        mesh8, P("world"), P("world")))
    with pytest.raises(ValueError, match="process_set"):
        f(np.ones((8, 2), np.float32))


def test_eager_path_untouched_by_fastpath(hvd_world):
    """Concrete arrays never enter the fast path: the dispatcher counter
    moves, the injit counter does not."""
    before = hvd_metrics.snapshot()
    out = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                                   name="eager_still_eager"))
    np.testing.assert_array_equal(out, np.ones(4))
    after = hvd_metrics.snapshot()
    assert _counter(after, OPS % "allreduce") == \
        _counter(before, OPS % "allreduce") + 1
    assert _counter(after, INJIT % "allreduce") == \
        _counter(before, INJIT % "allreduce")


# -- packed fusion buffers ---------------------------------------------------

def _params():
    return {"w": jnp.zeros((100,), jnp.float32),
            "b": jnp.zeros((7,), jnp.float32),
            "k": jnp.zeros((33,), jnp.float32)}


def _grads(n=8, scale=1.0):
    params = _params()
    rng = np.random.RandomState(0)
    return {k: np.stack([
        rng.standard_normal(v.shape).astype(np.float32) * (d + 1) * scale
        for d in range(n)]) for k, v in params.items()}


def _mesh_dp():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), ("dp",))


def _run_update(opt, grads, mesh, params, state):
    def step(g):
        u, _ = opt.update(g, state, params)
        return u
    f = jax.jit(_smap(step, mesh, P("dp"), P("dp")))
    return f(grads)


def test_packed_vs_per_leaf_bit_exact(hvd_world):
    mesh = _mesh_dp()
    params, grads = _params(), _grads()
    o1 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                  packing="per_leaf")
    o2 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                  packing="packed")
    u1 = _run_update(o1, grads, mesh, params, o1.init(params))
    u2 = _run_update(o2, grads, mesh, params, o2.init(params))
    for k in params:
        np.testing.assert_array_equal(np.asarray(u1[k]), np.asarray(u2[k]))


def test_packed_threshold_splits_buckets(hvd_world, monkeypatch):
    # tiny threshold: every leaf gets its own bucket; numerics unchanged
    monkeypatch.setenv("HVD_TPU_INJIT_PACKED_THRESHOLD", "64")
    mesh = _mesh_dp()
    params, grads = _params(), _grads()
    o1 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                  packing="per_leaf")
    o2 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                  packing="packed")
    u1 = _run_update(o1, grads, mesh, params, o1.init(params))
    u2 = _run_update(o2, grads, mesh, params, o2.init(params))
    for k in params:
        np.testing.assert_array_equal(np.asarray(u1[k]), np.asarray(u2[k]))


def test_packed_plan_cached_and_shaped():
    shapes = ((4,), (2, 3), (8,), (5,))
    dtypes = ("float32", "float32", "int32", "float32")
    p1 = fusion.packed_plan(shapes, dtypes, 1 << 20)
    p2 = fusion.packed_plan(list(shapes), list(dtypes), 1 << 20)
    assert p1 is p2  # memoized on (shapes, dtypes, threshold)
    # one bucket per dtype at a roomy threshold, leaf order preserved
    assert p1 == (("float32", (0, 1, 3)), ("int32", (2,)))
    # threshold 0: unbounded per-dtype buffer (knob semantics)
    assert fusion.packed_plan(shapes, dtypes, 0) == p1
    # tiny threshold: splits within a dtype
    tiny = fusion.packed_plan(shapes, dtypes, 16)
    assert tiny == (("float32", (0,)), ("float32", (1,)),
                    ("float32", (3,)), ("int32", (2,)))


def test_bucketed_apply_plan_memoized(hvd_world):
    info0 = fusion._plan_buckets_cached.cache_info()
    vals = [np.ones((16,), np.float32) for _ in range(4)]
    fusion.bucketed_apply(vals, 1 << 20, lambda vs, ns: vs)
    fusion.bucketed_apply(vals, 1 << 20, lambda vs, ns: vs)
    info1 = fusion._plan_buckets_cached.cache_info()
    assert info1.hits > info0.hits


def test_optimizer_jit_update_zero_dispatcher(hvd_world):
    """Acceptance: a jit-compiled DistributedGradientTransform.update
    performs zero dispatcher submissions, metrics-verified."""
    mesh = _mesh_dp()
    params, grads = _params(), _grads()
    before = hvd_metrics.snapshot()
    total_before = sum(v for k, v in before.items()
                       if k.startswith("hvd_tpu_collective_ops_total"))
    for packing in ("per_leaf", "packed"):
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                       packing=packing)
        _run_update(opt, grads, mesh, params, opt.init(params))
    after = hvd_metrics.snapshot()
    total_after = sum(v for k, v in after.items()
                      if k.startswith("hvd_tpu_collective_ops_total"))
    assert total_after == total_before


# -- wire compression --------------------------------------------------------

def test_packed_bf16_error_bound(hvd_world):
    mesh = _mesh_dp()
    params, grads = _params(), _grads()
    o_fp32 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                      packing="packed")
    o_bf16 = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                      packing="packed",
                                      compression=Compression.bf16)
    u32 = _run_update(o_fp32, grads, mesh, params, o_fp32.init(params))
    u16 = _run_update(o_bf16, grads, mesh, params, o_bf16.init(params))
    for k in params:
        a, b = np.asarray(u32[k]), np.asarray(u16[k])
        # bf16 keeps 8 mantissa bits: relative error bound ~2^-8 per
        # element, loosened for the cross-replica sum
        np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
    # and compression actually happened (results differ somewhere)
    assert any(not np.array_equal(np.asarray(u32[k]), np.asarray(u16[k]))
               for k in params)


def test_int8_requires_packed_compiled_path(hvd_world):
    with pytest.raises(ValueError, match="packed"):
        hvd.DistributedOptimizer(optax.sgd(1.0),
                                 compression=Compression.int8)
    with pytest.raises(ValueError, match="packed"):
        hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                 packing="per_leaf",
                                 compression=Compression.int8)
    with pytest.raises(NotImplementedError, match="packed"):
        Compression.int8.compress(jnp.ones(4))


def test_int8_state_shape_and_update(hvd_world):
    mesh = _mesh_dp()
    params, grads = _params(), _grads()
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="dp",
                                   packing="packed",
                                   compression=Compression.int8)
    state = opt.init(params)
    assert isinstance(state, Int8ErrorFeedbackState)
    for k, v in params.items():
        assert state.residual[k].shape == v.shape
        assert state.residual[k].dtype == jnp.float32

    def step(g, st):
        return opt.update(g, st, params)
    f = jax.jit(_smap(step, mesh, (P("dp"), P()), (P("dp"), P())))
    u, st2 = f(grads, state)
    assert isinstance(st2, Int8ErrorFeedbackState)
    # quantization error was recorded for feedback
    assert max(float(jnp.max(jnp.abs(st2.residual[k]))) for k in params) > 0
    # wrong state type is a loud error, not silent divergence
    with pytest.raises(TypeError, match="init"):
        opt.update(grads, opt._base.init(params), params)


def test_int8_error_feedback_convergence(hvd_world):
    """EF-SGD acceptance: int8-compressed training converges to within
    tolerance of uncompressed on a quadratic, and the loss decreases."""
    mesh = _mesh_dp()
    n = len(jax.devices())
    dim = 32
    targets = np.stack([np.linspace(-1.0, 1.0, dim) * (d + 1)
                        for d in range(n)]).astype(np.float32)
    target_mean = targets.mean(axis=0)
    w0 = jnp.zeros((dim,), jnp.float32)

    def run(compression, steps=30):
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.4), axis_name="dp", packing="packed",
            compression=compression)
        state = opt.init(w0)

        def step(w, st, t):
            g = w - t[0]  # per-device grad; Average -> w - mean(targets)
            u, st = opt.update(g, st, w)
            return optax.apply_updates(w, u), st

        f = jax.jit(_smap(step, mesh, (P(), P(), P("dp")), (P(), P())))
        w, st = w0, state
        losses = []
        for _ in range(steps):
            w, st = f(w, st, targets)
            losses.append(float(np.mean((np.asarray(w) - target_mean) ** 2)))
        return np.asarray(w), losses

    w_fp32, loss_fp32 = run(Compression.none)
    w_int8, loss_int8 = run(Compression.int8)
    # loss decreases and lands within tolerance of the uncompressed run
    assert loss_int8[-1] < loss_int8[0] * 1e-3
    assert abs(loss_int8[-1] - loss_fp32[-1]) < 1e-3
    np.testing.assert_allclose(w_int8, w_fp32, atol=0.02)


# -- multiprocess parity (n=2) ----------------------------------------------

WORKER = os.path.join(os.path.dirname(__file__), "injit_worker.py")


@pytest.mark.integration
@pytest.mark.slow
def test_injit_multiprocess_parity_2proc():
    """Eager dispatcher vs in-jit lowering across 2 real processes:
    bit-identical results, zero dispatcher submissions under jit."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(WORKER)))
        env.update({
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVD_TPU_SIZE": "2",
            "HVD_TPU_RANK": str(pid),
        })
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        text = out.decode(errors="replace")
        assert p.returncode == 0, f"worker {i} failed:\n{text[-4000:]}"
        assert f"injit worker {i} OK" in text
