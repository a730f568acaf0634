"""Olmo-Hybrid: gated-delta-rule linear attention with a per-sequence
recurrent state, full attention every fourth layer. Serving first.

The third block the generation plane serves. It enters through the same
contract, ``apply(params, tokens, cache=PagedCache, logits_at=...)``,
and is the first whose cache is not a function of token positions
alone: :meth:`OlmoHybridConfig.cache_spec` declares **per-token rows**
(K and V of the full-attention layers, one plane a full layer) *and*
**per-sequence state** (for each linear layer the recurrent matrix of
every head, float32, and the last ``kernel - 1`` inputs of the
convolution).

One layer, for both kinds (RMSNorm, no biases; the family's reordered
norm)::

    h = h + norm(mixer(h))
    h = h + norm(mlp(h))

and a final RMSNorm before the untied head. ``layer_types`` is
configuration: ``"linear_attention"`` or ``"full_attention"`` a layer.

**Full attention**: ``q, k, v = W x``; ``q`` and ``k`` RMS-normed over
all their ``heads * head_dim`` values before the split into heads (the
family's qk-norm); causal softmax at ``head_dim ** -0.5``; no rotary
(``rope_theta`` is null: the recurrent layers carry position). The paged
path is the GPT-2 block's: rows scattered through the block tables
(:func:`~.transformer.write_kv_rows`), then the Pallas paged-attention
kernel for a few columns on a TPU, the gathered table elsewhere, by
groups of :data:`HEAD_GROUP` heads so that a chunk's float32 scores stay
small.

**Linear attention** (gated delta rule; :mod:`horovod_tpu.ops.gated_delta`
has the recurrence). With ``x`` the layer's input::

    [q~ | k~ | v~] = W_qkv x              # one matrix, 2 H dk + H dv wide
    u_t[c] = silu(sum_j w[c, j] u~_{t-3+j}[c])     # causal, depthwise
    k_t = k / |k|,  q_t = q / |q| * dk ** -0.5     # per head
    beta_t = 2 sigmoid(W_b x),  g_t = -exp(A_log) softplus(W_a x + dt_bias)
    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    y_t = RMSNorm_dv(S_t q_t) * silu(W_g x),  out = W_o y

``beta`` and ``g`` are float32. The state a sequence keeps is ``S``
(held transposed, ``(H, dk, dv)``) and the last three ``u~``: on the
paged path a chunk starts from the sequence's slot of the state pools
and writes back what it ends with. **Pad columns and dead lanes leave
both bit-identical**: a column at or past ``live`` has ``g = 0`` and
``beta = 0`` (``1 * S + 0``), and the window's new rows are rows
``live .. live + 2`` of ``[window | chunk]``, the old window itself for
``live = 0``. A chunk of up to :data:`STEP_COLUMNS` columns (a decode
step) takes the one-token form column by column; a wider one the chunked
form.

Weights are created and held in ``param_dtype`` (bfloat16 when served;
``A_log`` and ``dt_bias`` float32) and nothing casts a weight inside a
call.

Named scopes, under flax's module scopes:
``layer_<i>/linear_attn/{proj,conv,delta_rule,gate_norm,out_proj}``,
``layer_<i>/attn/{qkv_proj,kv_write,kv_gather,attention,out_proj}``,
``layer_<i>/mlp``, ``head``.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import gated_delta, paged_attention
from .blocks import GatedMlp, normal_init as _init, rms_norm, untied_head
from .transformer import (CacheSpec, PagedCache, _default_attention,
                          _table_mask, write_kv_rows)

Dtype = Any

LINEAR, FULL = "linear_attention", "full_attention"
#: heads a prefill chunk's full attention scores at a time: float32
#: scores are heads x chunk x table x 4 bytes (0.15 GB at 10 x 512 x
#: 7168); a head count it does not divide is taken whole
HEAD_GROUP = 10
#: the widest chunk that takes the one-token form of the delta rule,
#: column by column (a decode step has two); wider takes the chunked form
STEP_COLUMNS = 8
#: added under the root of the per-head L2 norm of q and k
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """The source's own key names (``config.json`` of
    ``allenai/Olmo-Hybrid-7B``), published values as defaults.
    ``table_positions`` is the serving engine's: the longest sequence a
    block table holds (``max_position_embeddings`` where None; nothing
    in the model reads a position)."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    table_positions: Optional[int] = None
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.bfloat16
    state_dtype: Dtype = jnp.float32

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {LINEAR!r} or {FULL!r}")
        if self.num_key_value_heads != self.num_attention_heads or \
                self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("grouped heads are not implemented: the "
                             "published model has as many key as value "
                             "heads in both kinds of layer")

    @property
    def max_seq_len(self) -> int:
        return self.table_positions or self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_channels(self) -> int:
        """``[q~ | k~ | v~]``: what the convolution runs over."""
        return self.linear_num_key_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim)

    def planes_of(self, kind: str) -> Tuple[int, ...]:
        """Layer index -> index among the layers of ``kind`` (its plane
        of that kind's pools), -1 for a layer of the other kind."""
        out, n = [], 0
        for t in self.layer_types:
            out.append(n if t == kind else -1)
            n += t == kind
        return tuple(out)

    def cache_spec(self) -> CacheSpec:
        """Per token: K and V of each full-attention layer. Per
        sequence: each linear layer's recurrent matrices (``S[k, v]`` a
        head) and its convolution's last ``kernel - 1`` inputs."""
        width = self.num_attention_heads * self.head_dim
        linear = sum(t == LINEAR for t in self.layer_types)
        return CacheSpec(
            planes=sum(t == FULL for t in self.layer_types),
            rows=(("k", width), ("v", width)), dtype=self.dtype,
            state=(("delta_state", linear,
                    (self.linear_num_value_heads, self.linear_key_head_dim,
                     self.linear_value_head_dim), self.state_dtype),
                   ("conv_window", linear,
                    (self.linear_conv_kernel_dim - 1, self.conv_channels),
                    self.dtype)))

    def paged_query_rows(self, chunk: int) -> int:
        """Query vectors a ``chunk``-column step brings to
        :mod:`horovod_tpu.ops.paged_attention` (a full layer's)."""
        return chunk * self.num_attention_heads


def _l2_normalise(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _decay_rates(key, shape, dtype):
    """``A_log`` of the seeded weights: the logarithm of a per-head rate
    drawn log-uniformly from 2**-12 to 2**-3 a token (``dt_bias`` is 0
    and ``softplus`` of the seeded ``W_a x`` is near 0.9, so a head
    forgets in 10 to 4000 tokens by its decay alone)."""
    return (jax.random.uniform(key, shape, jnp.float32, -12.0, -3.0)
            * math.log(2.0)).astype(dtype)


class LinearAttention(nn.Module):
    """The gated-delta-rule layer. ``layer_cache`` is ``(state_pool,
    window_pool, plane, slots, live)`` on the paged path; returns
    ``(out, state_pool, window_pool)`` then, ``out`` otherwise (a whole
    sequence from a zero state)."""

    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, layer_cache=None):
        cfg = self.cfg
        D, H = cfg.hidden_size, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps, ch = cfg.linear_conv_kernel_dim, cfg.conv_channels
        pd, dt, f32 = cfg.param_dtype, cfg.dtype, jnp.float32
        w_qkv = self.param("qkv_proj", _init(), (D, ch), pd)
        w_conv = self.param("conv_weight", _init(0.5), (taps, ch), pd)
        w_ab = self.param("ab_proj", _init(), (D, 2 * H), pd)
        a_log = self.param("A_log", _decay_rates, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,), f32)
        w_g = self.param("g_proj", _init(), (D, H * dv), pd)
        o_norm = self.param("o_norm", nn.initializers.ones, (dv,), pd)
        w_o = self.param("o_proj", _init(), (H * dv, D), pd)
        B, C = x.shape[0], x.shape[1]

        if layer_cache is None:
            state = jnp.zeros((B, H, dk, dv), f32)
            window = jnp.zeros((B, taps - 1, ch), dt)
            live = jnp.full((B,), C, jnp.int32)
        else:
            state_pool, window_pool, plane, slots, live = layer_cache
            at = (plane,) if slots is None else (plane, slots)
            state, window = state_pool[at].astype(f32), window_pool[at]
        valid = jnp.arange(C)[None, :] < live[:, None]          # (B, C)

        with jax.named_scope("proj"):
            u = x @ w_qkv                                       # (B, C, ch)
            ab = jnp.einsum("bse,eh->bsh", x, w_ab,
                            preferred_element_type=f32)
            gate = x @ w_g
            beta = jax.nn.sigmoid(ab[..., H:])
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log) * jax.nn.softplus(ab[..., :H] + dt_bias)
            # a pad column changes no state: alpha = 1, beta = 0
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("conv"):
            ext = jnp.concatenate([window, u], axis=1)   # (B, taps-1+C, ch)
            mixed = sum(ext[:, j:j + C].astype(f32) * w_conv[j].astype(f32)
                        for j in range(taps))
            mixed = jax.nn.silu(mixed).astype(dt)
            # the inputs of the last taps - 1 live tokens: rows
            # live .. live + taps - 2 of ext (the old window at live = 0)
            window = jnp.take_along_axis(
                ext, (live[:, None] + jnp.arange(taps - 1))[..., None],
                axis=1)
            q = _l2_normalise(mixed[..., :H * dk].reshape(B, C, H, dk)) \
                * dk ** -0.5
            k = _l2_normalise(
                mixed[..., H * dk:2 * H * dk].reshape(B, C, H, dk))
            v = mixed[..., 2 * H * dk:].reshape(B, C, H, dv)
        with jax.named_scope("delta_rule"):
            rule = gated_delta.gated_delta_recurrent if C <= STEP_COLUMNS \
                else gated_delta.gated_delta_chunked
            o, state = rule(state, q, k, v, g, beta)
        with jax.named_scope("gate_norm"):
            y = rms_norm(o, o_norm.astype(f32), cfg.rms_norm_eps).astype(dt)
            y = (y * jax.nn.silu(gate.reshape(B, C, H, dv))
                 ).reshape(B, C, H * dv)
        with jax.named_scope("out_proj"):
            out = y @ w_o
        if layer_cache is None:
            return out
        return (out,
                state_pool.at[at].set(state.astype(state_pool.dtype)),
                window_pool.at[at].set(window))


def _grouped_attention(q, k, v, mask, dtype):
    """:func:`~.transformer._default_attention` a group of
    :data:`HEAD_GROUP` heads at a time over ``(B, S, H, D)`` inputs, so
    that a wide chunk's float32 scores over a long table stay small."""
    H = q.shape[2]
    if H % HEAD_GROUP or H == HEAD_GROUP:
        return _default_attention(q, k, v, mask, dtype)

    def by_group(a):            # (B, S, H, D) -> (H/g, B, S, g, D)
        a = a.reshape(a.shape[:2] + (H // HEAD_GROUP, HEAD_GROUP, a.shape[3]))
        return jnp.moveaxis(a, 2, 0)

    out = jax.lax.map(
        lambda qkv: _default_attention(*qkv, mask, dtype),
        (by_group(q), by_group(k), by_group(v)))
    out = jnp.moveaxis(out, 0, 2)             # (B, S, H/g, g, D)
    return out.reshape(out.shape[:2] + (H, out.shape[-1]))


class FullAttention(nn.Module):
    """Multi-head attention with qk-norm and no rotary. ``layer_cache``
    is ``(k_pool, v_pool, plane, block_tables, positions, live)`` on the
    paged path; returns ``(out, (k_pool, v_pool))`` then."""

    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, mask, layer_cache=None):
        cfg = self.cfg
        D, H, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        pd, dt = cfg.param_dtype, cfg.dtype
        w_q = self.param("q_proj", _init(), (D, H * hd), pd)
        w_k = self.param("k_proj", _init(), (D, H * hd), pd)
        w_v = self.param("v_proj", _init(), (D, H * hd), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (H * hd,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (H * hd,), pd)
        w_o = self.param("o_proj", _init(), (H * hd, D), pd)
        B, C = x.shape[0], x.shape[1]
        with jax.named_scope("qkv_proj"):
            q = rms_norm(x @ w_q, q_norm, cfg.rms_norm_eps)
            k = rms_norm(x @ w_k, k_norm, cfg.rms_norm_eps)
            v = x @ w_v
        heads = lambda a: a.reshape(B, -1, H, hd)  # noqa: E731
        pools = None
        if layer_cache is None:
            ctx = _grouped_attention(heads(q), heads(k), heads(v), mask, dt)
        else:
            k_pool, v_pool, plane, block_tables, positions, live = \
                layer_cache
            with jax.named_scope("kv_write"):
                k_pool, v_pool = write_kv_rows(
                    k_pool, v_pool, plane, block_tables, positions, live,
                    k, v)
            pools = (k_pool, v_pool)
            if paged_attention.kernel_applies(
                    cfg.paged_query_rows(C), k_pool.shape[2],
                    k_pool.shape[3], k_pool.dtype):
                with jax.named_scope("attention"):
                    ctx = paged_attention.paged_attention(
                        heads(q), k_pool, v_pool, plane, block_tables,
                        positions[:, 0], live)
            else:
                with jax.named_scope("kv_gather"):
                    kc = heads(k_pool[plane, block_tables][..., :H * hd])
                    vc = heads(v_pool[plane, block_tables][..., :H * hd])
                with jax.named_scope("attention"):
                    ctx = _grouped_attention(heads(q), kc, vc, mask, dt)
        with jax.named_scope("out_proj"):
            out = ctx.reshape(B, C, H * hd) @ w_o
        return out if layer_cache is None else (out, pools)


class HybridLayer(nn.Module):
    """``h + norm(mixer(h))`` then ``h + norm(mlp(h))``; the mixer is
    the kind ``cfg.layer_types[index]`` names. On the paged path
    ``pools`` is ``(k_pool, v_pool, state_pool, window_pool)`` and comes
    back updated."""

    cfg: OlmoHybridConfig
    index: int

    @nn.compact
    def __call__(self, h, mask, cache=None, pools=None, positions=None):
        cfg, i = self.cfg, self.index
        norm = lambda name, x: rms_norm(  # noqa: E731
            x, self.param(name, nn.initializers.ones, (cfg.hidden_size,),
                          cfg.param_dtype), cfg.rms_norm_eps)
        if cache is not None:
            k_pool, v_pool, state_pool, window_pool = pools
        if cfg.layer_types[i] == LINEAR:
            mixer = LinearAttention(cfg, name="linear_attn")
            if cache is None:
                mixed = mixer(h)
            else:
                mixed, state_pool, window_pool = mixer(
                    h, (state_pool, window_pool, cfg.planes_of(LINEAR)[i],
                        cache.slots, cache.live))
        else:
            mixer = FullAttention(cfg, name="attn")
            if cache is None:
                mixed = mixer(h, mask)
            else:
                mixed, (k_pool, v_pool) = mixer(
                    h, mask, (k_pool, v_pool, cfg.planes_of(FULL)[i],
                              cache.block_tables, positions, cache.live))
        if cache is not None:
            pools = (k_pool, v_pool, state_pool, window_pool)
        h = h + norm("post_attention_layernorm", mixed)
        mlp = GatedMlp(cfg.hidden_size, cfg.intermediate_size,
                       cfg.param_dtype, name="mlp")(h)
        h = h + norm("post_feedforward_layernorm", mlp)
        return h if cache is None else (h, pools)


class OlmoHybrid(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, tokens, cache: Optional[PagedCache] = None,
                 logits_at=None):
        cfg = self.cfg
        B, S = tokens.shape
        emb = self.param("embed_tokens", _init(),
                         (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        head = self.param("lm_head", _init(),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        h = emb[tokens].astype(cfg.dtype)
        if cache is None:
            positions, pools = None, None
            mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
        else:
            # incremental: the chunk starts at each sequence's cache
            # length; only the full layers' mask and writes read it
            positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
            pools = cache.pools
            mask = _table_mask(
                positions, cache.block_tables.shape[1] * pools[0].shape[2])
        for i in range(cfg.num_hidden_layers):
            layer = HybridLayer(cfg, i, name=f"layer_{i}")
            if cache is None:
                h = layer(h, mask)
            else:
                h, pools = layer(h, mask, cache, pools, positions)
        h = rms_norm(h, self.param("norm", nn.initializers.ones,
                                   (cfg.hidden_size,), cfg.param_dtype),
                     cfg.rms_norm_eps)
        logits = untied_head(h, head, None if cache is None else logits_at)
        if cache is None:
            return logits
        cache = dataclasses.replace(cache, pools=pools)
        if logits_at is not None:
            return logits[:, 0], cache
        return logits, cache
