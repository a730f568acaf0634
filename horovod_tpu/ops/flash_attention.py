"""Flash (blockwise online-softmax) attention as a Pallas TPU kernel.

No counterpart exists in the reference (it is a communication framework;
SURVEY.md §2.3), but the TPU build's long-context strategies — ring
attention over 'sp' (parallel/ring_attention.py) and Ulysses head sharding
(parallel/ulysses.py) — need an attention inner loop that never
materializes the (S_q, S_k) score matrix in HBM. This kernel computes exact
attention with fp32 online-softmax accumulators, tiled (block_q x block_k)
so the MXU sees dense (block, D) matmuls and HBM traffic stays O(S*D).

Positions are global: ``q_offset``/``k_offset`` give the global index of
local row 0, so a shard_map caller can mask causally across device shards
(ring attention passes the rotating source block's offset each step). They
are *dynamic* values (traced under shard_map — e.g. derived from
``jax.lax.axis_index``) and ride into the kernel through SMEM, which keeps
one compiled kernel serving every ring step.

The public entry is differentiable via custom_vjp: the forward saves the
per-row log-sum-exp and the backward recomputes scores blockwise (the
standard flash-attention recipe) in plain XLA, so memory stays O(S*D) end
to end while the forward rides the Pallas kernel.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Mosaic's default scoped-VMEM budget, and the most this kernel asks for
# (a v5e core has 128 MiB; the rest is left to the compiler).
_VMEM_DEFAULT_BYTES = 16 << 20
_VMEM_MAX_BYTES = 100 << 20


def use_pallas_default() -> bool:
    """Whether the default backend compiles Pallas TPU kernels. The
    context-parallel wrappers (ring / Ulysses attention) pick the flash
    kernel from this; a backend that fails to initialize raises here
    rather than reading as "not a TPU"."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Reference implementation (test oracle)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  q_offset=0, k_offset=0, out_dtype=None):
    """Exact attention in plain XLA. Shapes (B, S, H, D); fp32 softmax."""
    out_dtype = out_dtype or q.dtype
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, block_k, sk_real, block_q):
    """One (batch*head, q-block) program: stream K/V blocks with the
    online-softmax recurrence.

    Refs: q (1, block_q, D); k, v (1, S_k_padded, D); o (1, block_q, D);
    lse (1, 1, S_q) — per-row log-sum-exp residual for the backward. The lse
    block spans the full row (TPU tiling forbids a (1, block_q) block) and
    stays resident across this batch-head's q-block programs; each program
    stores its slice.
    """
    iq = pl.program_id(1)
    D = q_ref.shape[-1]
    q = q_ref[0]                                         # (bq, D) native dtype
    sk_pad = k_ref.shape[1]
    nkb = sk_pad // block_k

    qpos = (qoff_ref[0, 0] + iq * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    def body(j, carry):
        o, m, l = carry
        # inputs stay in their storage dtype (bf16 feeds the MXU at full
        # rate); accumulation is fp32 via preferred_element_type
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        col = (j * block_k
               + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
        valid = col < sk_real                            # mask padded K rows
        if causal:
            kpos = koff_ref[0, 0] + col
            valid = jnp.logical_and(valid, qpos >= kpos)
        s = jnp.where(valid, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, D)
        o_new = o * corr + pv
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    if causal:
        # Skip key blocks entirely above the diagonal: key block j is needed
        # iff its first key position <= this program's last query position.
        q_last = qoff_ref[0, 0] + (iq + 1) * block_q - 1
        nkb_needed = jnp.clip(
            (q_last - koff_ref[0, 0]) // block_k + 1, 0, nkb)
    else:
        nkb_needed = nkb
    o, m, l = jax.lax.fori_loop(0, nkb_needed, body, (o0, m0, l0))

    l = jnp.maximum(l, 1e-30)                            # fully-masked rows
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse = m[:, 0] + jnp.log(l[:, 0])
    if lse_ref.shape[-1] == block_q:
        lse_ref[0, 0, :] = lse                           # single q block
    else:
        # Mosaic needs a lane offset it can prove is a multiple of 128;
        # _flash_fwd only lets block_q % 128 == 0 reach this branch
        start = pl.multiple_of(iq * block_q, 128) if block_q % 128 == 0 \
            else iq * block_q
        lse_ref[0, 0, pl.ds(start, block_q)] = lse


def _compiler_params(dtype, SQ, SK, D, block_q, block_k):
    """Mosaic parameters for the compiled kernel, or ValueError for a
    tiling it cannot take — there is no other implementation to route
    such a shape to."""
    sublanes = 32 // jnp.dtype(dtype).itemsize   # rows per packed vreg tile
    if SQ > block_q and block_q % 128:
        raise ValueError(
            f"flash_attention: block_q={block_q} splits S_q={SQ} into "
            f"several q blocks, and the per-row lse store then needs "
            f"block_q to be a multiple of 128; pass block_q >= S_q or a "
            f"multiple of 128")
    if block_k % sublanes:
        raise ValueError(
            f"flash_attention: block_k={block_k} must be a multiple of "
            f"{sublanes} rows for {jnp.dtype(dtype).name} (the kernel "
            f"slices K and V at block_k offsets)")
    # Resident per program, double-buffered by the pipeline: the q and o
    # blocks, the whole local K and V, the lse row; plus the fp32 o/s/p
    # working set. Lanes pad to 128.
    item = jnp.dtype(dtype).itemsize
    lanes = -(-D // 128) * 128
    need = 2 * ((2 * block_q + 2 * SK) * lanes * item + SQ * 4) \
        + 4 * block_q * (lanes + 2 * max(block_k, 128)) * 4
    if need > _VMEM_MAX_BYTES:
        raise ValueError(
            f"flash_attention keeps the local K and V resident in VMEM: "
            f"{SK} rows x D={D} {jnp.dtype(dtype).name} needs "
            f"~{need >> 20} MiB, over the {_VMEM_MAX_BYTES >> 20} MiB this "
            f"kernel asks for; shard the sequence further (ring attention "
            f"over a larger 'sp' axis)")
    return pltpu.CompilerParams(
        # bh programs are independent; q-block programs share the
        # resident lse row block, so that dimension stays sequential
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=need if need > _VMEM_DEFAULT_BYTES else None)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k",
                              "sk_real", "interpret", "vma"))
def _flash_fwd(q, k, v, q_offset, k_offset, *, causal, sm_scale,
               block_q, block_k, sk_real, interpret, vma=()):
    """(BH, S_q, D) x (BH, S_k_padded, D) -> out (BH, S_q, D),
    lse (BH, S_q). S_q % block_q == 0, S_k_padded % block_k == 0."""
    BH, SQ, D = q.shape
    SK = k.shape[1]
    grid = (BH, SQ // block_q)
    compiler_params = None
    if not interpret:
        compiler_params = _compiler_params(q.dtype, SQ, SK, D, block_q,
                                           block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=block_k,
        sk_real=sk_real, block_q=block_q)
    qoff = q_offset.reshape(1, 1)
    koff = k_offset.reshape(1, 1)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda b, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, SK, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, SK, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, SQ), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, SQ, D), q.dtype, vma=frozenset(vma)),
            jax.ShapeDtypeStruct((BH, 1, SQ), jnp.float32,
                                 vma=frozenset(vma)),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(qoff, koff, q, k, v)
    return out, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Differentiable entry point. Offsets are float32 scalars (differentiable
# dtype with zero cotangent) so traced values — axis_index-derived ring
# positions — flow through custom_vjp.
# ---------------------------------------------------------------------------

def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, qoff, koff, causal, sm_scale, block_q, block_k,
           interpret, vma):
    """Returns (out, lse). lse (the per-row log-sum-exp of scores) is a
    first-class differentiable output: ring attention merges per-step block
    results through it, so its cotangent feeds the score gradients."""
    return _flash_fwd_padded(q, k, v, qoff, koff, causal, sm_scale,
                             block_q, block_k, interpret, vma)


def _flash_fwd_padded(q, k, v, qoff, koff, causal, sm_scale, block_q,
                      block_k, interpret, vma=()):
    sq = q.shape[1]
    sk = k.shape[1]
    out, lse = _flash_fwd(
        _pad_to(q, 1, block_q), _pad_to(k, 1, block_k),
        _pad_to(v, 1, block_k), qoff, koff, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k, sk_real=sk,
        interpret=interpret, vma=vma)
    return out[:, :sq], lse[:, :sq]


def _flash_vjp_fwd(q, k, v, qoff, koff, causal, sm_scale, block_q, block_k,
                   interpret, vma):
    out, lse = _flash_fwd_padded(q, k, v, qoff, koff, causal, sm_scale,
                                 block_q, block_k, interpret, vma)
    return (out, lse), (q, k, v, qoff, koff, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, vma, res,
                   gs):
    """Blockwise recompute backward (standard flash-attention bwd) in XLA:
    memory stays O(S*D + S*block) via a scan over K blocks. The lse
    cotangent g_lse enters the score gradient as
    d lse / d s_k = softmax_k = exp(s_k - lse)."""
    g, g_lse = gs
    q, k, v, qoff, koff, out, lse = res
    BH, SQ, D = q.shape
    SK = k.shape[1]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    g_lse = g_lse.astype(jnp.float32)
    delta = jnp.sum(out.astype(jnp.float32) * g, axis=-1)  # (BH, SQ)
    qpos = qoff + jnp.arange(SQ)
    koff_i = koff

    nkb = -(-SK // block_k)
    kfp = _pad_to(kf, 1, block_k)
    vfp = _pad_to(vf, 1, block_k)

    def kblock(dq_acc, j):
        ks = jax.lax.dynamic_slice_in_dim(kfp, j * block_k, block_k, 1)
        vs = jax.lax.dynamic_slice_in_dim(vfp, j * block_k, block_k, 1)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks) * sm_scale
        col = j * block_k + jnp.arange(block_k)
        valid = col[None, :] < SK
        if causal:
            valid = valid & (qpos[:, None] >= (koff_i + col)[None, :])
        s = jnp.where(valid[None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # (BH, SQ, bk)
        dp = jnp.einsum("bqd,bkd->bqk", g, vs)
        ds = p * (dp - delta[..., None] + g_lse[..., None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, ks)
        dk_blk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        dv_blk = jnp.einsum("bqk,bqd->bkd", p, g)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((BH, SQ, D), jnp.float32)
    if vma:
        # under shard_map the carry must be marked varying over the same
        # mesh axes as the body's output
        dq0 = jax.lax.pcast(dq0, vma, to="varying")
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(kblock, dq0, jnp.arange(nkb))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(BH, nkb * block_k, D)[:, :SK]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(BH, nkb * block_k, D)[:, :SK]
    # integer offsets have float0 cotangents
    zero_off = np.zeros(res[3].shape, jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_off, zero_off)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             sm_scale: Optional[float] = None,
                             q_offset=0, k_offset=0,
                             block_q: int = 512, block_k: int = 128,
                             interpret: bool = False,
                             out_dtype=None):
    """Flash attention over (B, S, H, D) tensors; also returns the per-row
    log-sum-exp ``lse`` with shape (B, S, H) — differentiable — so callers
    can merge partial attention over distributed K/V blocks (ring
    attention's per-step combine).

    The Pallas kernel is compiled by Mosaic, which exists only for TPU:
    on any other backend, or for a tiling the kernel cannot take, the
    call raises. ``interpret=True`` runs the same kernel body through the
    Pallas interpreter instead — what the CPU tests pass.
    ``q_offset``/``k_offset`` are the global positions of local row 0 for
    causal masking across sharded sequences; they may be traced values
    (ring attention derives them from ``jax.lax.axis_index``).
    """
    out_dtype = out_dtype or q.dtype
    B, SQ, H, D = q.shape
    SK = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    block_q = min(block_q, SQ)
    # a short K is one block, padded (and masked) up to whole vreg tiles
    block_k = min(block_k, -(-SK // 16) * 16)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    k_offset = jnp.asarray(k_offset, jnp.int32)
    # under shard_map the kernel's outputs vary over every manual mesh axis
    # its inputs vary over (empty outside shard_map)
    vma = frozenset().union(*(jax.typeof(x).vma
                              for x in (q, k, v, q_offset, k_offset)))
    out, lse = _flash(to_bh(q), to_bh(k), to_bh(v), q_offset, k_offset,
                      causal, float(sm_scale), int(block_q), int(block_k),
                      bool(interpret), tuple(sorted(vma)))
    out = out.reshape(B, H, SQ, D).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, SQ).transpose(0, 2, 1)
    return out.astype(out_dtype), lse


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    q_offset=0, k_offset=0,
                    block_q: int = 512, block_k: int = 128,
                    interpret: bool = False,
                    out_dtype=None):
    """Flash attention over (B, S, H, D); see flash_attention_with_lse."""
    out, _ = flash_attention_with_lse(
        q, k, v, causal=causal, sm_scale=sm_scale, q_offset=q_offset,
        k_offset=k_offset, block_q=block_q, block_k=block_k,
        interpret=interpret, out_dtype=out_dtype)
    return out
