"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention, sigmoid routing
over 64 experts with a shared one, and one multi-token prediction (MTP)
module. Serving first.

It enters the generation plane through the contract every served block
keeps, ``apply(params, tokens, cache=PagedCache, logits_at=...)``, and
declares its cache (:meth:`Glm47FlashConfig.cache_spec`): one latent row
a token in each of the ``num_hidden_layers`` planes of the main model,
and one more plane, the last, for the MTP module's own attention, all
under one block table.

One layer (RMSNorm, no biases)::

    h = h + A(in(h))          # MLA over the whole prefix
    h = h + F(post(h))        # a SwiGLU in the leading dense layer,
                              # else routed experts plus a shared one

the layer of :mod:`.deepseek_v32` without its indexer
(:class:`~.deepseek_v32.Layer`, ``indexer=None``; its expert layer
routes by :func:`~horovod_tpu.parallel.moe.route_group_limited_topk` at
one group), and :class:`~.longcat_flash.LatentAttention` at this
model's widths: plain rotary, no YaRN, softmax scale ``256 ** -0.5``.

**MTP** (DeepSeek-V3, arXiv:2412.19437, section 2.2): for position
``i`` the module reads the main model's last hidden state ``h_i`` (after
its final norm) and the embedding of the next token ``t_{i+1}``::

    u = W_eh [enorm(Emb(t_{i+1})) ; hnorm(h_i)]
    g = MoE layer(u)          # its own attention, over the MTP plane
    logits(t_{i+2}) = head(norm(g))

with the main model's embedding and head. :meth:`Glm47Flash.__call__`
gives the final hidden states beside the logits where a program asks
(``with_hidden``); :meth:`Glm47Flash.mtp_forward` is the module's pass,
``tokens`` at column ``i`` being ``t_{i+1}`` and ``hidden`` ``h_i``, at
the same positions as the main pass that made ``hidden``. The serving
programs that run it are ``kv_cache``'s MTP prefill and verify.

Named scopes, under flax's module scopes: ``layers_<i>/attn/...``,
``layers_<i>/mlp``, ``layers_<i>/moe/...``, ``mtp/layer/...``, ``head``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from .blocks import YaRN, normal_init as _init, rms_norm, untied_head
from .deepseek_v32 import Layer
from .longcat_flash import LatentAttentionConfig
from .transformer import CacheSpec, PagedCache

Dtype = Any


@dataclasses.dataclass(frozen=True)
class Glm47FlashConfig(LatentAttentionConfig):
    """The source's own key names (``config.json`` of
    ``zai-org/GLM-4.7-Flash``), published values as defaults.
    ``n_routed_experts`` is the whole model's count (the router's
    width); ``held_experts`` names the experts whose weights this chip
    holds, ``(first, end)``."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    rope_scaling: Optional[YaRN] = None
    held_experts: Tuple[int, int] = (0, 64)
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.bfloat16
    #: latent attention's LongCat switches, off in this MLA
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    def cache_spec(self) -> CacheSpec:
        """A latent row a token in each main layer's plane and in the
        MTP module's, the last."""
        return CacheSpec(
            planes=self.num_hidden_layers + self.num_nextn_predict_layers,
            rows=(("latent", self.kv_lora_rank + self.qk_rope_head_dim),),
            dtype=self.dtype)


class MtpModule(nn.Module):
    """The MTP module (module docstring) up to its norm: ``emb``
    ``(B, S, D)`` the next tokens' embeddings, ``hidden`` the main
    model's last hidden states at the same columns."""

    cfg: Glm47FlashConfig

    @nn.compact
    def __call__(self, emb, hidden, positions, mask, valid,
                 layer_cache=None):
        cfg = self.cfg
        D, pd = cfg.hidden_size, cfg.param_dtype
        norm = lambda name, x: rms_norm(  # noqa: E731
            x, self.param(name, nn.initializers.ones, (D,), pd),
            cfg.rms_norm_eps)
        eh = self.param("eh_proj", _init(), (2 * D, D), pd)
        u = jnp.concatenate([norm("enorm", emb), norm("hnorm", hidden)],
                            axis=-1) @ eh
        layer = Layer(cfg, dense=False, indexer=None, name="layer")
        if layer_cache is None:
            return norm("shared_head_norm",
                        layer(u, positions, mask, valid))
        g, pool = layer(u, positions, mask, valid, layer_cache)
        return norm("shared_head_norm", g), pool


class Glm47Flash(nn.Module):
    cfg: Glm47FlashConfig

    def setup(self):
        cfg = self.cfg
        D, pd = cfg.hidden_size, cfg.param_dtype
        self.embed_tokens = self.param("embed_tokens", _init(),
                                       (cfg.vocab_size, D), pd)
        self.lm_head = self.param("lm_head", _init(), (D, cfg.vocab_size),
                                  pd)
        self.norm = self.param("norm", nn.initializers.ones, (D,), pd)
        self.layers = [Layer(cfg, dense=i < cfg.first_k_dense_replace,
                             indexer=None)
                       for i in range(cfg.num_hidden_layers)]
        self.mtp = MtpModule(cfg)

    def _frame(self, tokens, cache):
        """Positions, mask and live columns of a chunk: from 0 with no
        cache; on the paged path the chunk starts at each sequence's
        cache length and slot ``t`` of a lane's table holds position
        ``t``."""
        B, S = tokens.shape
        if cache is None:
            return (jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)),
                    jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None],
                    jnp.ones((B, S), jnp.bool_))
        return (cache.lengths[:, None] + jnp.arange(S)[None, :], None,
                jnp.arange(S)[None, :] < cache.live[:, None])

    def _out(self, h, cache, pool, logits_at):
        # the caller samples one position a row: project only that
        logits = untied_head(h, self.lm_head,
                             None if cache is None else logits_at)
        if cache is None:
            return logits
        cache = dataclasses.replace(cache, pools=(pool,))
        return (logits if logits_at is None else logits[:, 0]), cache

    def __call__(self, tokens, cache: Optional[PagedCache] = None,
                 logits_at=None, with_hidden: bool = False):
        """The main model. ``with_hidden``: the final hidden states
        ``(B, S, D)`` (after the final norm, what the MTP module reads)
        come last in what it returns."""
        cfg = self.cfg
        positions, mask, valid = self._frame(tokens, cache)
        h = self.embed_tokens[tokens].astype(cfg.dtype)
        pool = None if cache is None else cache.pools[0]
        for i, layer in enumerate(self.layers):
            if cache is None:
                h = layer(h, positions, mask, valid)
            else:
                h, pool = layer(h, positions, mask, valid,
                                (pool, i, cache.block_tables, cache.live))
        h = rms_norm(h, self.norm, cfg.rms_norm_eps)
        if self.is_initializing():
            self.mtp_forward(tokens, hidden=h)   # the module's weights
        out = self._out(h, cache, pool, logits_at)
        if not with_hidden:
            return out
        return (*out, h) if cache is not None else (out, h)

    def mtp_forward(self, tokens, cache: Optional[PagedCache] = None,
                    logits_at=None, hidden=None):
        """The MTP module over a chunk: ``tokens`` ``(B, S)`` holds
        ``t_{i+1}`` at the column of position ``i``, ``hidden`` the main
        model's ``h_i`` there; returns the logits of ``t_{i+2}`` as
        :meth:`__call__` returns its own (and the cache, whose last
        plane the module's attention has written and read)."""
        cfg = self.cfg
        positions, mask, valid = self._frame(tokens, cache)
        emb = self.embed_tokens[tokens].astype(cfg.dtype)
        if cache is None:
            return self._out(self.mtp(emb, hidden, positions, mask, valid),
                             None, None, None)
        g, pool = self.mtp(emb, hidden, positions, mask, valid,
                           (cache.pools[0], cfg.num_hidden_layers,
                            cache.block_tables, cache.live))
        return self._out(g, cache, pool, logits_at)
