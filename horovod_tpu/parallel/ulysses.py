"""Ulysses-style sequence parallelism: all_to_all over the head axis.

Absent from the reference (SURVEY.md §2.3). The other long-context strategy:
instead of rotating K/V blocks (ring attention), one all_to_all re-shards
the activations from sequence-sharded to head-sharded, each device computes
*full-sequence* attention for its subset of heads, and a second all_to_all
restores sequence sharding. Two collectives total (vs n-1 ppermutes), at the
cost of requiring num_heads % sp_size == 0 and full-sequence scores memory
per head — the right trade on ICI-rich TPU slices for moderate sequence
lengths; ring attention wins for extreme lengths.
"""

import jax
import jax.numpy as jnp


def _seq_to_heads(x, axis_name):
    # (B, S_local, H, D) -> (B, S_full, H_local, D)
    return jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True)


def _heads_to_seq(x, axis_name):
    # (B, S_full, H_local, D) -> (B, S_local, H, D)
    return jax.lax.all_to_all(
        x, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      attention_fn=None, out_dtype=None):
    """Exact attention with sequence sharded over ``axis_name``.

    Args:
      q, k, v: (B, S_local, H, D); H must be divisible by the axis size.
      attention_fn: inner full-sequence attention (defaults to the model's
        XLA softmax attention); receives (q, k, v, mask, dtype) with shapes
        (B, S_full, H_local, D). A Pallas flash-attention kernel slots in
        here unchanged.
    Returns (B, S_local, H, D).
    """
    out_dtype = out_dtype or q.dtype
    n = jax.lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n != 0:
        raise ValueError(f"num_heads {H} not divisible by '{axis_name}' "
                         f"axis size {n}; use ring_attention instead")
    if attention_fn is None:
        from horovod_tpu.ops.flash_attention import use_pallas_default
        if use_pallas_default():
            # after the all_to_all each device holds the full sequence for
            # its head subset — exactly the flash kernel's shape
            from horovod_tpu.ops.flash_attention import flash_attention

            def attention_fn(qh, kh, vh, mask, dtype):
                del mask  # causal handled inside the kernel
                return flash_attention(qh, kh, vh, causal=causal,
                                       out_dtype=dtype)
        else:
            from horovod_tpu.models.transformer import _default_attention
            attention_fn = _default_attention
    qh = _seq_to_heads(q, axis_name)
    kh = _seq_to_heads(k, axis_name)
    vh = _seq_to_heads(v, axis_name)
    S = qh.shape[1]
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None] if causal else None
    oh = attention_fn(qh, kh, vh, mask, jnp.float32)
    return _heads_to_seq(oh.astype(out_dtype), axis_name)


def make_ulysses_attention(axis_name: str, causal: bool = True,
                           attention_fn=None):
    """Adapter for models.transformer.TransformerConfig.attention_fn."""
    def fn(q, k, v, mask, dtype):
        del mask
        return ulysses_attention(q, k, v, axis_name, causal=causal,
                                 attention_fn=attention_fn, out_dtype=dtype)
    return fn
