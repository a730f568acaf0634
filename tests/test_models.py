"""Model zoo + benchmark machinery + driver entry tests."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import (MLP, ResNet18, ResNet50, Transformer,
                                TransformerConfig)


def test_mlp_forward():
    m = MLP(features=(32,), num_classes=10)
    x = jnp.zeros((4, 28, 28, 1))
    v = m.init(jax.random.PRNGKey(0), x)
    out = m.apply(v, x)
    assert out.shape == (4, 10)
    assert out.dtype == jnp.float32


def test_resnet18_forward_small():
    m = ResNet18(num_classes=10, num_filters=8)
    x = jnp.zeros((2, 32, 32, 3), jnp.bfloat16)
    v = m.init(jax.random.PRNGKey(0), x, train=False)
    out = m.apply(v, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32  # fp32 head


def test_resnet_batchstats_update():
    m = ResNet18(num_classes=10, num_filters=8)
    x = jnp.ones((2, 32, 32, 3), jnp.bfloat16)
    v = m.init(jax.random.PRNGKey(0), x, train=True)
    _, updates = m.apply(v, x, train=True, mutable=["batch_stats"])
    # running stats must move away from init
    leaves = jax.tree_util.tree_leaves(updates["batch_stats"])
    assert any(bool(jnp.any(l != 0) & jnp.any(jnp.isfinite(l)))
               for l in leaves)


def test_resnet50_param_count():
    m = ResNet50(num_classes=1000)
    v = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
                       train=False))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(v["params"]))
    # torchvision resnet50: 25,557,032 params — v1.5-compatible definition
    assert abs(n - 25_557_032) / 25_557_032 < 0.01, n


def test_transformer_forward():
    cfg = TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                            num_heads=2, head_dim=16, max_seq_len=16,
                            dtype=jnp.float32)
    m = Transformer(cfg)
    toks = jnp.zeros((2, 8), jnp.int32)
    v = m.init(jax.random.PRNGKey(0), toks)
    out = m.apply(v, toks)
    assert out.shape == (2, 8, 64)


def test_transformer_causality():
    cfg = TransformerConfig(vocab_size=64, num_layers=1, d_model=32,
                            num_heads=2, head_dim=16, max_seq_len=16,
                            dtype=jnp.float32)
    m = Transformer(cfg)
    rng = np.random.RandomState(0)
    t1 = rng.randint(0, 64, (1, 8)).astype(np.int32)
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 1) % 64  # change only the last token
    v = m.init(jax.random.PRNGKey(0), jnp.asarray(t1))
    o1 = m.apply(v, jnp.asarray(t1))
    o2 = m.apply(v, jnp.asarray(t2))
    # earlier positions must be unaffected by a future-token change
    np.testing.assert_allclose(np.asarray(o1[:, :-1]), np.asarray(o2[:, :-1]),
                               rtol=1e-5)
    assert not np.allclose(np.asarray(o1[:, -1]), np.asarray(o2[:, -1]))


@pytest.mark.integration
def test_benchmark_machinery_smoke(hvd_world):
    from horovod_tpu.benchmark import synthetic_resnet50_benchmark
    r = synthetic_resnet50_benchmark(
        batch_per_chip=2, num_warmup_batches=1, num_batches_per_iter=1,
        num_iters=1, image_size=32, model_name="resnet18")
    assert r.images_per_sec_total > 0
    assert r.num_chips == 8


@pytest.mark.integration
def test_graft_entry_dryrun():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "/root/repo/__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)
    # entry() compile check on small shapes is covered by the driver; here
    # just validate it returns a jittable fn + args
    fn, args = mod.entry()
    assert callable(fn) and len(args) == 2


def test_peak_flops_table_rejects_an_unknown_tpu():
    from horovod_tpu.benchmark import peak_flops_per_chip
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    assert peak_flops_per_chip("cpu") is None       # no MFU off the chip
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        peak_flops_per_chip("TPU v9 mystery")


def test_space_to_depth_stem_matches_conv_stem():
    """The space_to_depth stem must be EXACTLY the 7x7/s2 conv stem's
    math (zero-padded kernel regrouping) — same params, same outputs.
    fp32 end to end so the comparison is tight."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import ResNet18

    rng = jax.random.PRNGKey(42)
    x = jax.random.normal(rng, (2, 32, 32, 3), jnp.float32)

    a = ResNet18(num_classes=10, dtype=jnp.float32, stem="conv")
    b = ResNet18(num_classes=10, dtype=jnp.float32, stem="space_to_depth")
    va = a.init(jax.random.PRNGKey(7), x, train=False)
    vb = b.init(jax.random.PRNGKey(7), x, train=False)
    # identical param trees (same names, shapes, init streams)
    ja = jax.tree_util.tree_structure(va)
    jb = jax.tree_util.tree_structure(vb)
    assert ja == jb
    for la, lb in zip(jax.tree_util.tree_leaves(va),
                      jax.tree_util.tree_leaves(vb)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    ya = a.apply(va, x, train=False)
    yb = b.apply(vb, x, train=False)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                               rtol=1e-5, atol=1e-5)

    # gradients agree too (the training path)
    def loss(m, v):
        return jnp.sum(m.apply(v, x, train=False) ** 2)
    ga = jax.grad(lambda v: loss(a, v))(va)
    gb = jax.grad(lambda v: loss(b, v))(vb)
    for la, lb in zip(jax.tree_util.tree_leaves(ga),
                      jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-4, atol=2e-4)


class TestVGG:
    """VGG-16 — the third network of the reference's headline scaling
    table (docs/benchmarks.rst:13-14; allreduce-bound: fc-dominated
    ~138M params)."""

    def test_vgg16_forward_shapes_and_dtype(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import VGG16
        model = VGG16(num_classes=10, classifier_width=64,
                      dropout_rate=0.0)
        x = jnp.zeros((2, 32, 32, 3), jnp.bfloat16)
        v = model.init(jax.random.PRNGKey(0), x, train=False)
        assert "batch_stats" not in v  # classic VGG: no BN
        out = model.apply(v, x, train=False)
        assert out.shape == (2, 10)
        assert out.dtype == jnp.float32  # fp32 head

    def test_vgg16_param_count_full_size(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import VGG16
        model = VGG16(num_classes=1000)
        v = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
                               train=False))
        n = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(v["params"]))
        assert abs(n - 138_357_544) < 1_000_000, n  # canonical ~138.36M

    def test_vgg16_trains_through_benchmark_rig(self):
        from horovod_tpu.benchmark import synthetic_resnet50_benchmark
        r = synthetic_resnet50_benchmark(
            batch_per_chip=2, image_size=32, model_name="vgg16",
            num_warmup_batches=1, num_batches_per_iter=1, num_iters=1)
        assert r.images_per_sec_total > 0

    def test_vgg16_dropout_active_in_train(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import VGG16
        model = VGG16(num_classes=10, classifier_width=64,
                      dropout_rate=0.5)
        x = jnp.ones((2, 32, 32, 3), jnp.bfloat16)
        v = model.init(jax.random.PRNGKey(0), x, train=False)
        a = model.apply(v, x, train=True,
                        rngs={"dropout": jax.random.PRNGKey(1)})
        b = model.apply(v, x, train=True,
                        rngs={"dropout": jax.random.PRNGKey(2)})
        assert not np.allclose(np.asarray(a), np.asarray(b))
        # eval is deterministic
        c = model.apply(v, x, train=False)
        d = model.apply(v, x, train=False)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d))


class TestInceptionV3:
    """Inception V3 — completes the reference's scaling-table trio
    (docs/benchmarks.rst:13-14: Inception V3 / ResNet-101 / VGG-16)."""

    def test_param_count_matches_canonical(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import InceptionV3
        m = InceptionV3(num_classes=1000, dropout_rate=0.0)
        v = jax.eval_shape(
            lambda: m.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 299, 299, 3), jnp.bfloat16),
                           train=False))
        n = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(v["params"]))
        assert n == 23_834_568, n  # torchvision inception_v3, no aux

    def test_forward_and_aux_head(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import InceptionV3
        m = InceptionV3(num_classes=7, dropout_rate=0.0, aux_logits=True)
        x = jnp.zeros((2, 128, 128, 3), jnp.bfloat16)
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out, aux = m.apply(v, x, train=False)
        assert out.shape == (2, 7) and aux.shape == (2, 7)
        assert out.dtype == jnp.float32

    def test_trains_through_benchmark_rig(self):
        from horovod_tpu.benchmark import synthetic_resnet50_benchmark
        r = synthetic_resnet50_benchmark(
            batch_per_chip=2, image_size=96, model_name="inception3",
            num_warmup_batches=1, num_batches_per_iter=1, num_iters=1)
        assert r.images_per_sec_total > 0
