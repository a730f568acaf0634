"""The plain references against the program's models, on seeded weights
at a small size on the CPU."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models import ResNet50, Transformer, TransformerConfig
from perfbench.reference import gpt2, resnet50

# Both sides compute in float32 here, so they differ only in the order of
# their sums: a few units in the last place of a logit near 1. 1e-4 is
# two orders above that and three below what a wrong epsilon, GELU form,
# mask or padding moves (each checked below to be caught).
TOL = 1e-4


def _transformer():
    cfg = TransformerConfig(vocab_size=211, num_layers=2, d_model=64,
                            num_heads=4, head_dim=16, max_seq_len=48,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))
    # norms away from their initial (1, 0), so that they count
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), x.shape, x.dtype)
        if x.ndim == 1 else x, params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 40), 0, 211)
    return model, params, tokens


def test_gpt2_reference_matches_the_transformer():
    model, params, tokens = _transformer()
    want = np.asarray(model.apply(params, tokens))
    got = np.asarray(gpt2.forward(params["params"], tokens))
    assert got.dtype == np.float32 and got.shape == (3, 40, 211)
    assert np.max(np.abs(got - want)) < TOL
    targets = jnp.roll(tokens, -1, axis=1)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        jnp.asarray(want), targets).mean()
    assert abs(gpt2.loss(params["params"], tokens, targets,
                         rows_at_once=2) - float(ce)) < TOL


def test_gpt2_reference_would_catch_a_departure(monkeypatch):
    model, params, tokens = _transformer()
    want = np.asarray(model.apply(params, tokens))
    monkeypatch.setattr(gpt2, "LN_EPS", 1e-2)
    gpt2.layer.clear_cache()
    gpt2.head.clear_cache()
    try:
        got = np.asarray(gpt2.forward(params["params"], tokens))
        assert np.max(np.abs(got - want)) > 10 * TOL
    finally:
        monkeypatch.undo()
        gpt2.layer.clear_cache()
        gpt2.head.clear_cache()


def test_resnet50_reference_matches_the_model():
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 64, 64, 3)), train=True))()
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), x.shape, x.dtype)
        if x.ndim == 1 else x, variables["params"])
    images = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 64, 3))
    labels = jnp.array([1, 2, 3, 4])
    logits, _ = model.apply(
        {"params": params, "batch_stats": variables["batch_stats"]}, images,
        train=True, mutable=["batch_stats"])
    want = float(optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean())
    # a loss near 2.3 summed over 53 normalised layers: float32 rounding
    # reaches the fifth decimal
    assert abs(float(resnet50.loss(params, images, labels)) - want) < TOL
    ref_logits = np.asarray(resnet50.logits(params, images))
    assert np.max(np.abs(ref_logits - np.asarray(logits))) < 1e-3
