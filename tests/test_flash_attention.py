"""Flash-attention kernel tests (interpret mode — no TPU needed).

Oracle strategy: every configuration is checked against the plain-XLA
reference (mha_reference), including gradients through the custom VJP, the
lse output's own gradient path, and ring attention's flash implementation
against a single-device full-sequence computation (the same
compare-to-local-math style the reference uses for collectives,
test_torch.py dtype/dimension sweeps).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.flash_attention import (
    flash_attention, flash_attention_with_lse, mha_reference)


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 48, 3, 16), (1, 64, 2, 32)])
def test_flash_matches_reference(causal, shape):
    q, k, v = (_rand(shape, s) for s in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_flash_offsets_cross_shard_causality():
    """Offsets reproduce causal masking between different global blocks —
    the ring-attention contract."""
    B, S, H, D = 1, 32, 2, 16
    q, k, v = (_rand((B, S, H, D), s) for s in range(3))
    # q block at global rows 64.., k block at global rows 32..: fully visible
    out = flash_attention(q, k, v, causal=True, q_offset=64, k_offset=32,
                          block_q=8, block_k=8, interpret=True)
    ref = mha_reference(q, k, v, causal=True, q_offset=64, k_offset=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    # q block strictly before k block: everything masked -> zeros
    out = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=32,
                          block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-5)


def test_flash_ragged_kv_padding():
    q = _rand((2, 24, 2, 16), 0)
    k = _rand((2, 19, 2, 16), 1)
    v = _rand((2, 19, 2, 16), 2)
    out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_flash_gradients_match_reference():
    shape = (2, 32, 2, 16)
    q, k, v = (_rand(shape, s) for s in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=8,
                                       block_k=8, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_lse_value_and_gradient():
    """lse must equal logsumexp of scaled scores and carry a correct VJP
    (it feeds ring attention's merge weights)."""
    B, S, H, D = 1, 16, 1, 8
    q, k, v = (_rand((B, S, H, D), s) for s in range(3))
    scale = 1.0 / np.sqrt(D)

    def lse_ref(q, k):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        return jnp.moveaxis(jax.nn.logsumexp(s, axis=-1), 1, 2)  # (B, S, H)

    def lse_flash(q, k):
        _, lse = flash_attention_with_lse(q, k, v, causal=False, block_q=8,
                                          block_k=8, interpret=True)
        return lse

    np.testing.assert_allclose(np.asarray(lse_flash(q, k)),
                               np.asarray(lse_ref(q, k)), atol=1e-4)
    gf = jax.grad(lambda q, k: jnp.sum(jnp.sin(lse_flash(q, k))),
                  argnums=(0, 1))(q, k)
    gr = jax.grad(lambda q, k: jnp.sum(jnp.sin(lse_ref(q, k))),
                  argnums=(0, 1))(q, k)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# ---------------------------------------------------------------------------
# ring attention with the flash block engine
# ---------------------------------------------------------------------------

def _ring_flash_sharded(q, k, v, mesh, causal):
    # check_vma=False: the pallas HLO interpreter traces the kernel body's
    # dynamic_slice ops, which trip shard_map's varying-axes checker (jax
    # suggests this flag as the workaround); the compiled TPU path never
    # traces kernel internals, so production keeps the check on.
    from horovod_tpu.parallel.ring_attention import ring_attention_flash
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_attention_flash, axis_name="sp",
                          causal=causal, interpret=True, block_q=8,
                          block_k=8),
        mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))
    return fn(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_global_reference(causal):
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:4]), ("sp",))
    B, S, H, D = 1, 32, 2, 16  # S_local = 8 per device
    q, k, v = (_rand((B, S, H, D), s) for s in range(3))
    out = _ring_flash_sharded(q, k, v, mesh, causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_ring_flash_gradient_matches_global_reference():
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:4]), ("sp",))
    B, S, H, D = 1, 32, 2, 16
    q, k, v = (_rand((B, S, H, D), s) for s in range(3))

    def loss_ring(q, k, v):
        return jnp.sum(_ring_flash_sharded(q, k, v, mesh, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# ---------------------------------------------------------------------------
# the compiled path: no silent fallback, clear refusals
# ---------------------------------------------------------------------------

def test_default_arguments_never_fall_back_off_the_tpu():
    """Without interpret=True the kernel goes to Mosaic, which exists only
    for TPU: on this CPU backend the call raises instead of quietly running
    the interpreter or the reference."""
    q = _rand((1, 128, 2, 64))
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, q, q)


def test_compiled_kernel_refuses_tilings_mosaic_cannot_take():
    from horovod_tpu.ops.flash_attention import (_VMEM_DEFAULT_BYTES,
                                                 _compiler_params)
    bf16 = jnp.bfloat16
    with pytest.raises(ValueError, match="multiple of 128"):
        _compiler_params(bf16, 256, 256, 64, block_q=64, block_k=128)
    with pytest.raises(ValueError, match="multiple of 16"):
        _compiler_params(bf16, 256, 264, 64, block_q=256, block_k=24)
    with pytest.raises(ValueError, match="shard the sequence further"):
        _compiler_params(bf16, 131072, 131072, 128, 512, 128)
    # the full-width train shape fits Mosaic's default budget; a long local
    # context asks for more, explicitly
    assert _compiler_params(bf16, 1024, 1024, 64, 512,
                            128).vmem_limit_bytes is None
    assert _compiler_params(bf16, 32768, 32768, 128, 512,
                            128).vmem_limit_bytes > _VMEM_DEFAULT_BYTES
