"""Command A+: window and full attention layers with grouped-query
heads, a parallel attention + expert block, a sigmoid top-k router with
averaged shared experts. Serving first.

The fourth block the generation plane serves. It enters through the
same contract as the others, ``apply(params, tokens, cache=PagedCache,
logits_at=...)``, and declares its cache
(:meth:`CommandAPlusConfig.cache_spec`) in **two plane groups**: the
full-attention layers' planes keep every token, the sliding-window
layers' planes keep a token only while a later query may still read it
(``sliding_window`` positions), so the two groups have pools of their
own and a sequence one block table for each
(:class:`~.transformer.PlaneGroup`).

One layer, for the hidden vector ``h`` of a token at position ``p``
(Cohere's LayerNorm: a weight and no bias, in float32; no projection has
a bias)::

    x = LN(h)                           # one norm: both branches read x
    q, k, v = W_q x, W_k x, W_v x       # H query heads, G key-value heads
    sliding layer: rotary on q and k (interleaved pairs), keys p - W < t <= p
    full layer:    no position signal at all, keys t <= p
    a = W_o concat(heads)               # query head j reads kv head j // (H/G)
    s = sigmoid(W_r x); I = top-k(s); w_i = s_i / sum_I s
    m = sum_I w_i E_i(x) + mean_j S_j(x)
    h = h + a + m

``E`` and ``S`` are gated MLPs of one width. The ``num_shared_experts``
shared experts are held as **one** gated MLP of ``num_shared_experts``
times that width whose output is divided by their number: the same sum,
formulated as one matmul. After the last layer ``LN`` again, and logits
``logit_scale * (LN(h) . E^T)`` against the embedding table.

Weights are created and held in ``param_dtype`` (bfloat16 when served;
the router and the norms' weights in float32) and nothing casts a
weight inside a call. The expert layer is
:func:`horovod_tpu.parallel.moe.held_experts_mlp` under
:func:`~horovod_tpu.parallel.moe.route_sigmoid_topk`: this chip holds
``held_experts``, routes over all ``num_experts`` outputs, and computes
its own experts' part; routing counts leave through the flax collection
``moe_stats``.

**Which attention the paged read runs** follows what the code can see,
as in :mod:`.transformer`: a few query columns on a TPU take the paged
kernel (:mod:`horovod_tpu.ops.paged_attention`, its grouped layout, and
for a sliding layer its window); everything else, a prefill chunk or any
other backend, :func:`blocked_attention`: plain XLA that walks the keys
a block of :data:`KEY_BLOCK` at a time under a running softmax, from the
first block a query of the chunk may read to the last, so that a window
plane reads its window and the chunk whatever the context, and a full
plane's float32 scores never span the table.

Named scopes, under flax's module scopes:
``layer_<i>/attn/{qkv_proj,rope,kv_write,attention,out_proj}``,
``layer_<i>/moe/{router,sort,experts,shared,combine}``, ``head``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import paged_attention
from ..ops.flash_attention import NEG_INF
from ..parallel.moe import (STATS_COLLECTION, held_experts_mlp,
                            route_sigmoid_topk)
from .blocks import GatedMlp, normal_init as _init, rotary_interleaved
from .transformer import CacheSpec, PagedCache, PlaneGroup, write_kv_rows

Dtype = Any

SLIDING, FULL = "sliding_attention", "full_attention"
#: keys :func:`blocked_attention` scores at a time: the float32 scores of
#: a 512-column chunk's 128 heads against them are 134 MB
KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class CommandAPlusConfig:
    """The source's own key names (``config.json`` of
    ``CohereLabs/command-a-plus-05-2026``), published values as defaults.
    ``num_experts`` is the whole model's count (the router's outputs);
    ``held_experts`` names the experts whose weights this chip holds,
    ``(first, end)``. ``table_positions`` is the engine's: the longest
    sequence a block table is built for (None: the model's
    ``max_position_embeddings``; rotary needs no table of positions)."""

    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 8
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    max_position_embeddings: int = 200000
    held_experts: Tuple[int, int] = (0, 128)
    table_positions: Optional[int] = None
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.bfloat16

    @property
    def max_seq_len(self) -> int:
        return self.table_positions or self.max_position_embeddings

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The layers this model runs: the first ``num_hidden_layers``
        of the published list."""
        kinds = tuple(self.layer_types[:self.num_hidden_layers])
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))} for num_hidden_layers="
                f"{self.num_hidden_layers}")
        return kinds

    def cache_spec(self) -> CacheSpec:
        """A layer keeps a token's K and its V, ``num_key_value_heads *
        head_dim`` values each; the full layers' planes are one group,
        the sliding layers' another with the window."""
        kinds = self.kinds
        width = self.num_key_value_heads * self.head_dim
        groups = tuple(g for g in (
            PlaneGroup("full", kinds.count(FULL)),
            PlaneGroup("window", kinds.count(SLIDING), self.sliding_window))
            if g.planes)
        return CacheSpec(
            planes=len(kinds), rows=(("k", width), ("v", width)),
            dtype=self.dtype, groups=groups if len(groups) > 1 else ())

    def plane_of(self, layer: int) -> Tuple[int, int]:
        """``(group, plane)`` of layer ``layer``'s rows: its place among
        the layers of its kind, in the group :meth:`cache_spec` gives
        that kind."""
        kinds = self.kinds
        plane = kinds[:layer].count(kinds[layer])
        two = FULL in kinds and SLIDING in kinds
        return (1 if two and kinds[layer] == SLIDING else 0), plane

    def paged_query_rows(self, chunk: int) -> int:
        """Query vectors one pass of the paged kernel scores together:
        a chunk's columns times the query heads that share a key-value
        head."""
        return chunk * (self.num_attention_heads
                        // self.num_key_value_heads)


def layer_norm(x, weight, eps):
    """Cohere's LayerNorm: mean and variance over the last axis, a
    weight and no bias, all in float32; the result in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    x32 = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * weight.astype(jnp.float32)).astype(x.dtype)


def _grouped(q, kv_heads):
    B, C, H, D = q.shape
    return q.reshape(B, C, kv_heads, H // kv_heads, D)


def dense_attention(q, k, v, window):
    """Causal grouped-query attention of a whole sequence, no cache:
    ``q`` ``(B, S, H, D)``, ``k`` and ``v`` ``(B, S, G, D)``; with
    ``window`` a query at ``p`` reads ``p - window < t <= p``."""
    B, S, H, D = q.shape
    scores = jnp.einsum("bsgrd,btgd->bgrst", _grouped(q, k.shape[2]), k,
                        preferred_element_type=jnp.float32) * D ** -0.5
    at, t = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = t <= at
    if window:
        mask &= t > at - window
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, D)


def blocked_attention(q, k_pool, v_pool, plane, table, positions, live,
                      window, kv_heads):
    """The paged read as plain XLA, :data:`KEY_BLOCK` keys at a time
    under a running softmax: ``q`` ``(B, C, H, D)`` at ``positions``
    ``(B, C)`` over plane ``plane`` of the pools through ``table``
    ``(B, max_blocks)``. The walk runs from the block of the first key
    any live lane's first column may read (0, or with ``window`` the
    position ``window - 1`` before it) to the block of the last live
    column, so its cost follows what is read and not the table; a slot
    is masked by its position, so a table entry before the window may
    be the null block. A dead lane (``live == 0``) gives zeros."""
    B, C, H, D = q.shape
    G = kv_heads
    block_size = k_pool.shape[2]
    per = max(1, KEY_BLOCK // block_size)
    keys = per * block_size
    qg = _grouped(q, G)
    alive = live > 0
    first = positions[:, 0]
    oldest = jnp.maximum(first - window + 1, 0) if window \
        else jnp.zeros_like(first)
    last = first + jnp.maximum(live, 1) - 1
    top = table.shape[1] * block_size
    start = jnp.min(jnp.where(alive, oldest, top)) // keys
    end = jnp.max(jnp.where(alive, last // keys + 1, 0))

    def step(i, carry):
        m, l, acc = carry
        blocks = jnp.take(table, i * per + jnp.arange(per), axis=1,
                          mode="fill", fill_value=0)            # (B, per)
        k = k_pool[plane, blocks][..., :G * D].reshape(B, keys, G, D)
        v = v_pool[plane, blocks][..., :G * D].reshape(B, keys, G, D)
        t = i * keys + jnp.arange(keys)
        seen = (t[None, None, :] <= positions[:, :, None]) \
            & alive[:, None, None]                              # (B, C, keys)
        if window:
            seen &= t[None, None, :] > positions[:, :, None] - window
        seen = seen[:, None, None]
        s = jnp.einsum("bcgrd,btgd->bgrct", qg, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrct,btgd->bgrcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l * corr + jnp.sum(p, axis=-1), acc

    rows = (B, G, H // G, C)
    _, l, acc = jax.lax.fori_loop(
        start, end, step,
        (jnp.full(rows, NEG_INF, jnp.float32), jnp.zeros(rows, jnp.float32),
         jnp.zeros(rows + (D,), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]                # (B,G,r,C,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D).astype(q.dtype)


class Attention(nn.Module):
    """Grouped-query attention of one layer; ``sliding`` says which
    kind. ``layer_cache`` is ``(k_pool, v_pool, plane, table, live)``
    on the paged path; returns ``(out, (k_pool, v_pool))`` then."""

    cfg: CommandAPlusConfig
    sliding: bool

    @nn.compact
    def __call__(self, x, positions, layer_cache=None):
        cfg = self.cfg
        E, H, G, D = (cfg.hidden_size, cfg.num_attention_heads,
                      cfg.num_key_value_heads, cfg.head_dim)
        pd = cfg.param_dtype
        wq = self.param("q_proj", _init(), (E, H * D), pd)
        wk = self.param("k_proj", _init(), (E, G * D), pd)
        wv = self.param("v_proj", _init(), (E, G * D), pd)
        wo = self.param("o_proj", _init(), (H * D, E), pd)
        B, C = x.shape[0], x.shape[1]
        window = cfg.sliding_window if self.sliding else None
        with jax.named_scope("qkv_proj"):
            q = (x @ wq).reshape(B, C, H, D)
            k = (x @ wk).reshape(B, C, G, D)
            v = (x @ wv).reshape(B, C, G, D)
        if self.sliding:
            with jax.named_scope("rope"):
                q = rotary_interleaved(q, positions, cfg.rope_theta)
                k = rotary_interleaved(k, positions, cfg.rope_theta)
        if layer_cache is None:
            with jax.named_scope("attention"):
                ctx = dense_attention(q, k, v, window)
            pools = None
        else:
            k_pool, v_pool, plane, table, live = layer_cache
            with jax.named_scope("kv_write"):
                k_pool, v_pool = write_kv_rows(
                    k_pool, v_pool, plane, table, positions, live, k, v)
            with jax.named_scope("attention"):
                if paged_attention.kernel_applies(
                        cfg.paged_query_rows(C), k_pool.shape[2],
                        k_pool.shape[3], k_pool.dtype):
                    ctx = paged_attention.paged_attention(
                        q, k_pool, v_pool, plane, table, positions[:, 0],
                        live, kv_heads=G, window=window)
                else:
                    ctx = blocked_attention(q, k_pool, v_pool, plane, table,
                                            positions, live, window, G)
            pools = (k_pool, v_pool)
        with jax.named_scope("out_proj"):
            out = ctx.reshape(B, C, H * D) @ wo
        return out if pools is None else (out, pools)


class ExpertLayer(nn.Module):
    """The sigmoid router over every expert of the model, this chip's
    held experts, and the shared experts' mean."""

    cfg: CommandAPlusConfig

    @nn.compact
    def __call__(self, x, valid):
        cfg = self.cfg
        E, F, pd = cfg.hidden_size, cfg.intermediate_size, cfg.param_dtype
        n_held = cfg.held_experts[1] - cfg.held_experts[0]
        router = self.param("router", _init(), (E, cfg.num_experts),
                            jnp.float32)
        w_gate = self.param("experts_gate", _init(), (n_held, E, F), pd)
        w_up = self.param("experts_up", _init(), (n_held, E, F), pd)
        w_down = self.param("experts_down", _init(), (n_held, F, E), pd)
        B, C = x.shape[0], x.shape[1]
        flat = x.reshape(B * C, E)
        with jax.named_scope("router"):
            # the router runs in float32, whatever the activations are
            logits = jnp.dot(flat.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            idx, weights = route_sigmoid_topk(logits,
                                              cfg.num_experts_per_tok)
        held, _, stats = held_experts_mlp(
            flat, idx, weights, w_gate, w_up, w_down, cfg.held_experts,
            cfg.num_experts, valid=valid.reshape(B * C))
        if not self.is_initializing():   # init would keep the counts
            self.sow(STATS_COLLECTION, "counts", stats)
        # the shared experts side by side as one gated MLP, then their mean
        shared = GatedMlp(E, cfg.num_shared_experts * F, pd,
                          name="shared")(x)
        with jax.named_scope("combine"):
            return (held.reshape(B, C, E)
                    + shared.astype(jnp.float32) / cfg.num_shared_experts
                    ).astype(x.dtype)


class ParallelLayer(nn.Module):
    """``h + A(LN(h)) + M(LN(h))``: one norm, both branches read it."""

    cfg: CommandAPlusConfig
    sliding: bool

    @nn.compact
    def __call__(self, h, positions, valid, layer_cache=None):
        cfg = self.cfg
        x = layer_norm(h, self.param(
            "input_layernorm", nn.initializers.ones, (cfg.hidden_size,),
            jnp.float32), cfg.layer_norm_eps)
        attn = Attention(cfg, self.sliding, name="attn")(
            x, positions, layer_cache)
        m = ExpertLayer(cfg, name="moe")(x, valid)
        if layer_cache is None:
            return h + attn + m
        return h + attn[0] + m, attn[1]


class CommandAPlus(nn.Module):
    cfg: CommandAPlusConfig

    @nn.compact
    def __call__(self, tokens, cache: Optional[PagedCache] = None,
                 logits_at=None):
        cfg = self.cfg
        B, S = tokens.shape
        emb = self.param("embed_tokens", _init(),
                         (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        h = emb[tokens].astype(cfg.dtype)
        if cache is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            valid = jnp.ones((B, S), jnp.bool_)
        else:
            # incremental: the chunk starts at each sequence's cache
            # length; one table a plane group (one group: one array)
            positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
            valid = jnp.arange(S)[None, :] < cache.live[:, None]
            pools = list(cache.pools)
            tables = cache.block_tables \
                if isinstance(cache.block_tables, (tuple, list)) \
                else (cache.block_tables,)
        for i, kind in enumerate(cfg.kinds):
            layer = ParallelLayer(cfg, kind == SLIDING, name=f"layer_{i}")
            if cache is None:
                h = layer(h, positions, valid)
            else:
                g, plane = cfg.plane_of(i)
                h, pools[2 * g:2 * g + 2] = layer(
                    h, positions, valid,
                    (pools[2 * g], pools[2 * g + 1], plane, tables[g],
                     cache.live))
        h = layer_norm(h, self.param("norm", nn.initializers.ones,
                                     (cfg.hidden_size,), jnp.float32),
                       cfg.layer_norm_eps)
        if cache is not None and logits_at is not None:
            # the caller samples one position a row: project only that
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        with jax.named_scope("head"):
            # tied: against the embedding table as it lies, float32 sums
            logits = jnp.einsum("bse,ve->bsv", h, emb,
                                preferred_element_type=jnp.float32)
            if cfg.logit_scale != 1.0:
                logits = logits * cfg.logit_scale
        if cache is None:
            return logits
        cache = dataclasses.replace(cache, pools=tuple(pools))
        if logits_at is not None:
            return logits[:, 0], cache
        return logits, cache
