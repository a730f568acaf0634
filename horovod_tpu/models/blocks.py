"""What the served blocks share: RMSNorm, the gated MLP, interleaved
rotary embedding, the untied head.

:mod:`.longcat_flash`, :mod:`.olmo_hybrid` and :mod:`.command_a_plus`
import them from here. Weights are created and held in ``param_dtype`` and nothing casts
a weight inside a call: a matmul takes them as they lie.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any


def normal_init(std=0.02):
    return nn.initializers.normal(std)


def rms_norm(x, weight, eps):
    """Normalise in float32, weigh in the activations' dtype."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32.astype(x.dtype) * weight.astype(x.dtype)


def rotary_interleaved(x, positions, theta):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``position * theta ** (-2i / d)``. ``x``: ``(B, S, ..., d)``;
    ``positions``: ``(B, S)``. Angles in float32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freq     # (B, S, d/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class GatedMlp(nn.Module):
    """``W_down(silu(W_gate x) * (W_up x))``, ``hidden -> inner ->
    hidden``, no biases."""

    hidden: int
    inner: int
    param_dtype: Dtype

    @nn.compact
    def __call__(self, x):
        D, F, pd = self.hidden, self.inner, self.param_dtype
        gate = self.param("gate_proj", normal_init(), (D, F), pd)
        up = self.param("up_proj", normal_init(), (D, F), pd)
        down = self.param("down_proj", normal_init(), (F, D), pd)
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def untied_head(h, head, logits_at=None):
    """Float32 logits of ``h`` ``(B, S, D)`` through the untied head
    ``(D, vocab)``, from the weights as they lie. With ``logits_at``
    ``(B,)`` (the paged path: the caller samples one position a row)
    only that position is projected and the result is ``(B, 1, vocab)``."""
    if logits_at is not None:
        h = jnp.take_along_axis(
            h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
    with jax.named_scope("head"):
        return jnp.einsum("bse,ev->bsv", h, head,
                          preferred_element_type=jnp.float32)
