"""Ring attention: context parallelism over the 'sp' mesh axis.

Absent from the reference (SURVEY.md §2.3: no sequence/context parallelism
anywhere in the tree) — a required first-class capability of the TPU build.

Design (blockwise/flash attention over a device ring): the sequence is
sharded over the 'sp' axis; each device keeps its Q block resident and the
K/V blocks rotate around the ring via ``ppermute`` (one ICI hop per step, n
steps total). Attention is accumulated with the online-softmax recurrence in
fp32, so the result is exact — identical math to flash attention, with the
"blocks" living on different chips. Communication per step overlaps with the
block matmuls (XLA schedules ppermute async start/done around compute).

Causal masking is done at block granularity with global positions:
block from source device s attends fully when s < my_index, causally when
s == my_index, and is skipped (masked) when s > my_index.

Use inside shard_map with q/k/v sharded over 'sp' on the sequence axis:
shapes (B, S_local, H, D).
"""

import functools

import jax
import jax.numpy as jnp

from ..ops.flash_attention import flash_attention_with_lse, use_pallas_default

NEG_INF = -1e30


def _vary_like(q, axis_name):
    """Mark a fresh accumulator device-varying over the ring axis and every
    other manual mesh axis ``q`` varies over, so it type-checks against the
    per-step results under shard_map's VMA tracking."""
    axes = tuple(sorted(jax.typeof(q).vma | {axis_name}))
    return lambda x: jax.lax.pcast(x, axes, to="varying")


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   out_dtype=None, impl: str = "auto",
                   interpret: bool = False):
    """Exact attention over sequence blocks distributed on ``axis_name``.

    Args:
      q, k, v: (B, S_local, H, D) per-device blocks (sequence axis sharded).
      axis_name: mesh axis carrying the sequence shards (the ring).
      causal: apply a causal mask using global positions.
      impl: "flash" = Pallas flash kernel per ring step, "xla" = blockwise
        einsum recurrence, "auto" = flash where the backend is a TPU (the
        kernel compiles or the call raises) and xla on backends that have
        no Mosaic compiler.
      interpret: run the flash kernel through the Pallas interpreter
        (CPU tests); selects the flash implementation.
    Returns (B, S_local, H, D) attention output for the local Q block.
    """
    if impl == "auto":
        impl = "flash" if interpret or use_pallas_default() else "xla"
    if impl == "flash":
        return ring_attention_flash(q, k, v, axis_name, causal=causal,
                                    out_dtype=out_dtype, interpret=interpret)
    out_dtype = out_dtype or q.dtype
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    qf = q.astype(jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def blockwise(carry, i):
        o, m, l, k_blk, v_blk = carry
        # source device whose block we hold at step i
        src = (my - i) % n
        # scores: (B, H, Sq, Sk) in fp32
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        s = s * scale
        if causal:
            qpos = my * S + jnp.arange(S)             # (Sq,) global
            kpos = src * S + jnp.arange(S)            # (Sk,) global
            mask = qpos[:, None] >= kpos[None, :]     # (Sq, Sk)
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)                   # (B, H, Sq)
        m_new = jnp.maximum(m, m_blk)
        # clamp so fully-masked rows (all NEG_INF) don't produce inf-inf
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)                     # (B, H, Sq)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        # rotate K/V to the next device (skip the final, unused rotation
        # is harmless and keeps the scan body uniform)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    vary = _vary_like(q, axis_name)
    o0 = vary(jnp.zeros((B, S, H, D), jnp.float32))
    m0 = vary(jnp.full((B, H, S), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((B, H, S), jnp.float32))
    (o, m, l, _, _), _ = jax.lax.scan(
        blockwise, (o0, m0, l0, k, v), jnp.arange(n))
    # fully-masked rows have l == 0 (can't happen with causal self-attn,
    # every query sees at least itself; guard anyway)
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(out_dtype)


def ring_attention_flash(q, k, v, axis_name: str, causal: bool = True,
                         out_dtype=None, interpret: bool = False,
                         block_q: int = 512, block_k: int = 128):
    """Ring attention with the Pallas flash kernel as the per-step block
    engine (ops/flash_attention.py).

    Each ring step computes this device's Q block against the currently-held
    K/V block with the flash kernel — which returns (out_i, lse_i), both
    differentiable — and merges the partials with the standard log-sum-exp
    combine::

        lse' = logaddexp(lse, lse_i)
        o'   = o * exp(lse - lse') + o_i * exp(lse_i - lse')

    Steps whose K block is entirely in the causal future yield lse_i ~ -1e30
    and contribute exp(-big) = 0, so the merge is uniform (no data-dependent
    control flow — one compiled SPMD program). ``jax.checkpoint`` wraps the
    step so the backward re-runs the kernel instead of storing every rotated
    K/V block — memory stays O(S_local) like the forward, the standard ring
    attention trade.
    """
    out_dtype = out_dtype or q.dtype
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, o, lse, k_blk, v_blk):
        src = (my - i) % n
        o_i, lse_i = flash_attention_with_lse(
            q, k_blk, v_blk, causal=causal,
            q_offset=my * S, k_offset=src * S,
            block_q=block_q, block_k=block_k, interpret=interpret,
            out_dtype=jnp.float32)
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)[..., None]        # (B, S, H, 1)
        w_new = jnp.exp(lse_i - lse_new)[..., None]
        o = o * w_old + o_i * w_new
        if i + 1 < n:  # final rotation unnecessary
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, lse_new, k_blk, v_blk

    # remat each step: the backward re-runs the kernel instead of storing
    # every rotated K/V block, keeping memory O(S_local)
    step = jax.checkpoint(step, static_argnums=(0,))

    vary = _vary_like(q, axis_name)
    o = vary(jnp.zeros((B, S, H, D), jnp.float32))
    lse = vary(jnp.full((B, S, H), NEG_INF, jnp.float32))
    k_blk, v_blk = k, v
    # unrolled ring (n is static = axis size): one pallas call per step,
    # ppermute overlapped with the next step's compute by XLA's scheduler
    for i in range(n):
        o, lse, k_blk, v_blk = step(i, o, lse, k_blk, v_blk)
    return o.astype(out_dtype)


def make_ring_attention(axis_name: str, causal: bool = True):
    """Adapter matching models.transformer.TransformerConfig.attention_fn's
    signature (q, k, v, mask, dtype). The local mask argument is ignored —
    global causal masking is computed from ring positions."""
    @functools.wraps(ring_attention)
    def fn(q, k, v, mask, dtype):
        del mask
        return ring_attention(q, k, v, axis_name, causal=causal,
                              out_dtype=dtype)
    return fn
