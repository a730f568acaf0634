"""LongCat-Flash: latent attention, the shortcut-connected double layer,
routed experts with zero-compute experts. Serving first.

The second block the generation plane serves (the first is the GPT-2
block of :mod:`.transformer`). It enters through the same contract,
``apply(params, tokens, cache=PagedCache, logits_at=...)``, and declares
its cache (:meth:`LongcatFlashConfig.cache_spec`): one **latent row**
an attention, shared by all heads, and two attentions a layer.

One layer (RMSNorm, no biases; ``A`` latent attention, ``F`` a gated
MLP, ``M`` the expert layer)::

    h = h + A_0(in_0(h))
    u = post_0(h)
    m = M(u)                  # the shortcut: added at the layer's end
    h = h + F_0(u)
    h = h + A_1(in_1(h))
    h = h + F_1(post_1(h)) + m

Latent attention (MLA) keeps, for a token, the normalised and scaled
``kv_lora_rank`` latent ``c`` and one rotated ``qk_rope_head_dim`` key
``kr`` for all heads: a pool row is ``[c | kr | zeros]`` up to a
multiple of 128. Two forms read it. **Absorbed** (a decode step, any
narrow chunk): ``W_kvb``'s key half is folded into the query and its
value half applied after the weighted sum, so scores and context are
taken against the cached rows themselves and the cached context is never
expanded. **Expanded** (a wide prefill chunk): keys and values are
rebuilt from the rows, by groups of heads, and attention runs at head
width; past ``r (dn + dv) / (2 r - dn - dv)`` queries a chunk (171 at
the published widths) it is the cheaper of the two, by the count of
multiplications alone.

On the paged path neither form gathers a block table where the chip
can help it. The absorbed form is multi-query attention with one
key-value head: the ``num_attention_heads x columns`` folded queries
``[q_lat | q_rope | 0]`` all score the same cached row, and the row's
first ``kv_lora_rank`` values are the value. That is the grouped layout
of :mod:`horovod_tpu.ops.paged_attention` with ``kv_heads=1`` and the
latent pool as K pool and V pool at once, so where that kernel applies
(:func:`~horovod_tpu.ops.paged_attention.kernel_applies`: a TPU, and
shapes it takes: a decode or beam step's two columns over the served
pool of 64-token blocks of 640 bfloat16 values) each live lane's rows
are read from the pool where they lie, up to what the lane holds, under
a float32 running softmax; a dead lane reads nothing. Everywhere else (a
CPU, a float32 pool of 8-token blocks, a verify step or a narrow chunk
of more than two columns) the absorbed form takes the **gather path**,
the oracle the kernel is held to: the lane's whole table gathered from
the pool and a slot masked by its position. The expanded form
**walks** the lane's blocks, :data:`KEY_BLOCK` slots at a time, from
block 0 to the one that holds the chunk's last live position
(``lengths + live - 1``: a dynamic trip count), reads the rows where
they lie in the pool the chunk has just written, rebuilds that key
block's keys and values, masks a slot by its position and folds the
block into a float32 running maximum, sum and accumulator
(:meth:`LatentAttention._walked`). A chunk costs what the sequence
holds and not the table, and the float32 scores that exist at a time
are a head group's against one key block.

Weights are created and held in ``param_dtype`` (bfloat16 when served;
the router and its correction bias in float32) and nothing casts a
weight inside a call: a matmul takes them as they lie. The expert layer
is :func:`horovod_tpu.parallel.moe.held_experts_mlp`: this chip holds
``held_experts``, routes over all of the router's outputs, and computes
its own experts' part. Routing counts leave the model through the flax
collection ``moe_stats`` (one int32 vector a layer).

Named scopes, under flax's module scopes:
``layer_<i>/attn_<j>/{q_proj,kv_write,kv_gather,absorb,attention,out_proj}``
(``absorb`` in the absorbed form only, ``kv_gather`` on its gather path
only),
``layer_<i>/mlp_<j>``, ``layer_<i>/moe/{router,sort,experts,identity,combine}``,
``head``.
"""

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import paged_attention
from ..parallel.moe import STATS_COLLECTION, held_experts_mlp, route_topk
from .blocks import (GatedMlp, normal_init as _init, rms_norm,
                     rotary_interleaved, untied_head)
from .transformer import CacheSpec, PagedCache

Dtype = Any

#: heads the expanded form of latent attention rebuilds keys and values
#: for at a time: its float32 scores are heads x queries x keys x 4
#: bytes; a head count it does not divide is taken whole
HEAD_GROUP = 16
#: slots of a lane's table the paged expanded form scores at a time, in
#: whole pool blocks (8 of 64): a head group's float32 scores against
#: them are 16 x 512 x 512 x 4 B = 16.8 MB, which the TPU compiler keeps
#: out of HBM where all 64 heads' 67 MB pass through it three times
KEY_BLOCK = 512
_NEG_INF = jnp.finfo(jnp.float32).min


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    """The source's own key names (``config.json`` of
    ``meituan-longcat/LongCat-Flash-Chat``), published values as
    defaults. ``n_routed_experts`` is the whole model's count (the
    router has ``n_routed_experts + zero_expert_num`` outputs);
    ``held_experts`` names the FFN experts whose weights this chip
    holds, ``(first, end)``."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    held_experts: Tuple[int, int] = (0, 512)
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.bfloat16

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    def cache_spec(self) -> CacheSpec:
        """One latent row an attention, two attentions a layer."""
        return CacheSpec(
            planes=2 * self.num_layers,
            rows=(("latent", self.kv_lora_rank + self.qk_rope_head_dim),),
            dtype=self.dtype)

    def expands(self, chunk: int) -> bool:
        """Whether a chunk of ``chunk`` queries takes the expanded form."""
        r, dn, dv = self.kv_lora_rank, self.qk_nope_head_dim, self.v_head_dim
        return chunk * (2 * r - dn - dv) > r * (dn + dv)

    def paged_query_rows(self, chunk: int) -> int:
        """Query vectors one pass of the paged kernel scores together:
        the absorbed form has one key-value head, the cached row, which
        a chunk's columns times all the heads share."""
        return chunk * self.num_attention_heads

    def prefill_keys_walked(self, chunk: int, length: int, live: int,
                            block_size: int, max_blocks: int) -> int:
        """Slots of a lane's table that one attention of a paged chunk
        reads: ``chunk`` columns wide, ``live`` of them live, after
        ``length`` tokens. The expanded form walks to the chunk's last
        live position, rounded up to the key block (nothing for a dead
        lane); the absorbed form of a narrow chunk gathers the whole
        table. Host arithmetic for the scheduler's counter
        (``hvd_tpu_gen_prefill_attn_keys_total``), the same rule and the
        same walk as :meth:`LatentAttention._walked`."""
        table = max_blocks * block_size
        if not self.expands(chunk):
            return table
        keys = _key_block(block_size)
        return min(-(-(length + live) // keys) * keys, table) if live else 0


class LatentAttention(nn.Module):
    """MLA. ``layer_cache`` is ``(pool, plane, block_tables, live)`` on
    the paged path; returns ``(out, pool)`` then, ``out`` otherwise.

    With no cache a chunk attends to its own rows under ``mask``,
    expanded or absorbed by :meth:`LongcatFlashConfig.expands`. On the
    paged path a wide chunk walks the lane's blocks expanded
    (:meth:`_walked`); a narrow one is absorbed and reads the pool
    through the paged-attention kernel where
    :func:`~horovod_tpu.ops.paged_attention.kernel_applies` (a TPU and
    fitting shapes: the rule is the backend's and the shapes', there is
    no option), else through the gathered table."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, positions, mask, layer_cache=None):
        cfg = self.cfg
        D, H = cfg.hidden_size, cfg.num_attention_heads
        r, rq = cfg.kv_lora_rank, cfg.q_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        pd, dt = cfg.param_dtype, cfg.dtype
        q_a = self.param("q_a_proj", _init(), (D, rq), pd)
        q_a_norm = self.param("q_a_layernorm", nn.initializers.ones, (rq,),
                              pd)
        q_b = self.param("q_b_proj", _init(), (rq, H, dn + dr), pd)
        kv_a = self.param("kv_a_proj", _init(), (D, r + dr), pd)
        kv_a_norm = self.param("kv_a_layernorm", nn.initializers.ones,
                               (r,), pd)
        # kv_b_proj, held as its two halves so that neither form slices
        # a weight inside a call
        w_uk = self.param("kv_b_proj_nope", _init(), (r, H, dn), pd)
        w_uv = self.param("kv_b_proj_v", _init(), (r, H, dv), pd)
        w_o = self.param("o_proj", _init(), (H, dv, D), pd)
        B, C = x.shape[0], x.shape[1]

        with jax.named_scope("q_proj"):
            q = rms_norm(x @ q_a, q_a_norm, cfg.rms_norm_eps)
            q = jnp.einsum("bsq,qhd->bshd", q, q_b)
            if cfg.mla_scale_q_lora:
                q = q * jnp.asarray((D / rq) ** 0.5, dt)
            q_nope = q[..., :dn]
            q_rope = rotary_interleaved(q[..., dn:], positions,
                                        cfg.rope_theta)
        with jax.named_scope("kv_write"):
            ckr = x @ kv_a
            c = rms_norm(ckr[..., :r], kv_a_norm, cfg.rms_norm_eps)
            if cfg.mla_scale_kv_lora:
                c = c * jnp.asarray((D / r) ** 0.5, dt)
            kr = rotary_interleaved(ckr[..., r:], positions, cfg.rope_theta)
            # what a token leaves behind: [c | kr | zeros] up to the
            # pool's lane-aligned row
            width = r + dr if layer_cache is None \
                else layer_cache[0].shape[3]
            new_rows = jnp.pad(jnp.concatenate([c, kr], axis=-1),
                               ((0, 0), (0, 0), (0, width - r - dr)))
            if layer_cache is not None:
                pool, plane, block_tables, live = layer_cache
                block_size = pool.shape[2]
                blocks = jnp.take_along_axis(
                    block_tables,
                    (positions // block_size).astype(jnp.int32), axis=1)
                valid = jnp.arange(C)[None, :] < live[:, None]
                # pad tokens and dead lanes write to the null block 0
                blocks = jnp.where(valid, blocks, 0)
                pool = pool.at[plane, blocks, positions % block_size].set(
                    new_rows)
        scale = (dn + dr) ** -0.5
        if cfg.expands(C):
            ctx = self._expanded(q_nope, q_rope, new_rows, mask, w_uk, w_uv,
                                 scale) if layer_cache is None \
                else self._walked(q_nope, q_rope, pool, plane, block_tables,
                                  positions, live, w_uk, w_uv, scale)
        else:
            if layer_cache is None:
                attend = functools.partial(_row_attention, rows=new_rows,
                                           mask=mask, scale=scale)
            elif paged_attention.kernel_applies(
                    cfg.paged_query_rows(C), block_size, width, pool.dtype):
                # one key-value head, and the pool is its keys and its
                # values: what each live lane holds, read where it lies
                attend = lambda q_row: paged_attention.paged_attention(  # noqa: E731
                    q_row, pool, None, plane, block_tables, positions[:, 0],
                    live, kv_heads=1, scale=scale)
            else:
                with jax.named_scope("kv_gather"):
                    # every table slot, from the pool just written:
                    # position t of a sequence lives at index t, and a
                    # query at position p attends to every t <= p
                    rows = pool[plane, block_tables].reshape(B, -1, width)
                    seen = (jnp.arange(rows.shape[1])[None, None, None, :]
                            <= positions[:, None, :, None])
                attend = functools.partial(_row_attention, rows=rows,
                                           mask=seen, scale=scale)
            ctx = self._absorbed(q_nope, q_rope, width, w_uk, w_uv, attend)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bshd,hde->bse", ctx, w_o)
        return out if layer_cache is None else (out, pool)

    def _absorbed(self, q_nope, q_rope, width, w_uk, w_uv, attend):
        """Scores and context against the cached rows themselves:
        ``attend`` takes the folded queries ``(B, S, H, width)`` to
        their weighted sums of ``width``-wide rows."""
        cfg = self.cfg
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
            # one contraction over the whole row: [q_lat | q_rope | 0]
            # against [c | kr | 0]
            q_row = jnp.pad(
                jnp.concatenate([q_lat, q_rope], axis=-1),
                ((0, 0),) * 3 + ((0, width - r - dr),))
        with jax.named_scope("attention"):
            ctx = attend(q_row)[..., :r]
        with jax.named_scope("absorb"):
            return jnp.einsum("bshr,rhd->bshd", ctx, w_uv)

    def _expanded(self, q_nope, q_rope, rows, mask, w_uk, w_uv, scale):
        """Keys and values rebuilt from the rows, a group of heads at a
        time, attention at head width."""
        r, dr = self.cfg.kv_lora_rank, self.cfg.qk_rope_head_dim
        c, kr = rows[..., :r], rows[..., r:r + dr]

        def group(qn, qr, uk, uv):
            scores, v = _rebuilt_scores(qn, qr, c, kr, uk, uv, scale)
            probs = _masked_softmax(scores, mask).astype(v.dtype)
            return jnp.einsum("bhst,bthd->bshd", probs, v)

        with jax.named_scope("attention"):
            return _by_head_groups(group, q_nope, q_rope, w_uk, w_uv)

    def _walked(self, q_nope, q_rope, pool, plane, tables, positions, live,
                w_uk, w_uv, scale):
        """The expanded form over the paged pool: plane ``plane`` of
        ``pool`` through ``tables`` ``(B, max_blocks)``, walked by key
        blocks under a running softmax from block 0 to the block of the
        last live column of any live lane, so its cost follows what the
        sequences hold and a table entry past it is never read; with no
        live lane nothing is read. Every softmax is
        :func:`_masked_softmax`'s, a key block's at a time, so the model
        keeps one softmax for whoever holds its precision (the
        benchmark's tolerance tool replaces that function)."""
        r, dr = self.cfg.kv_lora_rank, self.cfg.qk_rope_head_dim
        B, C = positions.shape
        block_size, width = pool.shape[2:]
        keys = _key_block(block_size)
        per = keys // block_size
        last = positions[:, 0] + live - 1
        end = jnp.max(jnp.where(live > 0, last // keys + 1, 0))

        def group(qn, qr, uk, uv):
            def step(i, carry):
                m, l, acc = carry
                blocks = jnp.take(tables, i * per + jnp.arange(per), axis=1,
                                  mode="fill", fill_value=0)    # (B, per)
                rows = pool[plane, blocks].reshape(B, keys, width)
                scores, v = _rebuilt_scores(
                    qn, qr, rows[..., :r], rows[..., r:r + dr], uk, uv, scale)
                t = i * keys + jnp.arange(keys)
                seen = (t[None, None, :]
                        <= positions[:, :, None])[:, None]  # (B, 1, C, keys)
                # the key block's own softmax, then its place in the
                # running one: its row maximum, and its row sum read off
                # the largest probability (exp(0) over the sum). A row
                # that sees nothing of the block weighs exp(-huge) = 0
                probs = _masked_softmax(scores, seen)
                m_blk = jnp.max(jnp.where(seen, scores, _NEG_INF), axis=-1)
                l_blk = 1.0 / jnp.max(probs, axis=-1)
                m_new = jnp.maximum(m, m_blk)
                old = jnp.exp(m - m_new)
                new = l_blk * jnp.exp(m_blk - m_new)
                acc = acc * old[..., None] + new[..., None] * jnp.einsum(
                    "bhst,bthd->bhsd", probs.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                return m_new, l * old + new, acc

            shape = (B, qn.shape[2], C)
            _, l, acc = jax.lax.fori_loop(
                0, end, step,
                (jnp.full(shape, _NEG_INF, jnp.float32),
                 jnp.zeros(shape, jnp.float32),
                 jnp.zeros(shape + (uv.shape[-1],), jnp.float32)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return jnp.swapaxes(out, 1, 2).astype(pool.dtype)

        with jax.named_scope("attention"):
            return _by_head_groups(group, q_nope, q_rope, w_uk, w_uv)


def _key_block(block_size: int) -> int:
    """Slots of one key block of the walk: :data:`KEY_BLOCK` in whole
    pool blocks, one block at the least."""
    return max(1, KEY_BLOCK // block_size) * block_size


def _rebuilt_scores(q_nope, q_rope, c, kr, w_uk, w_uv, scale):
    """``(scores (B, h, S, T) float32, v (B, T, h, dv))`` of a group of
    heads against ``T`` latent rows ``[c | kr]``."""
    k_nope = jnp.einsum("btr,rhd->bthd", c, w_uk)
    v = jnp.einsum("btr,rhd->bthd", c, w_uv)
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,btd->bhst", q_rope, kr,
                           preferred_element_type=jnp.float32))
    return scores * scale, v


def _row_attention(q_row, rows, mask, scale):
    """The absorbed form in plain XLA: every head's folded queries
    ``(B, S, H, W)`` against the same ``rows`` ``(B, T, W)``, which are
    keys and values both; float32 scores and softmax, probabilities in
    the rows' dtype."""
    scores = jnp.einsum("bshw,btw->bhst", q_row, rows,
                        preferred_element_type=jnp.float32)
    probs = _masked_softmax(scores * scale, mask).astype(rows.dtype)
    return jnp.einsum("bhst,btw->bshw", probs, rows)


def _by_head_groups(attend, q_nope, q_rope, w_uk, w_uv):
    """``attend(q_nope, q_rope, w_uk, w_uv) -> (B, S, g, dv)`` over the
    heads, :data:`HEAD_GROUP` at a time: ``(B, S, H, dv)``."""
    H = q_nope.shape[2]
    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H

    def split(a, axis):             # split the head axis, groups first
        a = a.reshape(a.shape[:axis] + (H // g, g) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    ctx = jax.lax.map(lambda args: attend(*args),
                      (split(q_nope, 2), split(q_rope, 2),
                       split(w_uk, 1), split(w_uv, 1)))
    # (groups, B, S, g, dv) -> (B, S, H, dv)
    ctx = jnp.moveaxis(ctx, 0, 2)
    return ctx.reshape(ctx.shape[:2] + (H, ctx.shape[-1]))


def _masked_softmax(scores, mask):
    """Causal softmax in float32."""
    return jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)


class ExpertLayer(nn.Module):
    """The router over every output of the model and this chip's held
    experts. Returns ``M(u)`` as far as this chip computes it."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, u, valid):
        cfg = self.cfg
        D, Fe, pd = cfg.hidden_size, cfg.expert_ffn_hidden_size, \
            cfg.param_dtype
        n_out = cfg.n_routed_experts + cfg.zero_expert_num
        n_held = cfg.held_experts[1] - cfg.held_experts[0]
        router = self.param("router", _init(), (D, n_out), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (n_out,), jnp.float32)
        w_gate = self.param("experts_gate", _init(), (n_held, D, Fe), pd)
        w_up = self.param("experts_up", _init(), (n_held, D, Fe), pd)
        w_down = self.param("experts_down", _init(), (n_held, Fe, D), pd)
        B, C = u.shape[0], u.shape[1]
        flat = u.reshape(B * C, D)
        with jax.named_scope("router"):
            # the router runs in float32, whatever the activations are
            logits = jnp.dot(flat.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            idx, weights = route_topk(logits, bias, cfg.moe_topk,
                                      cfg.routed_scaling_factor)
        held, zero, stats = held_experts_mlp(
            flat, idx, weights, w_gate, w_up, w_down, cfg.held_experts,
            cfg.n_routed_experts, valid=valid.reshape(B * C))
        if not self.is_initializing():   # init would keep the counts
            self.sow(STATS_COLLECTION, "counts", stats)
        with jax.named_scope("combine"):
            return (held + zero).astype(u.dtype).reshape(B, C, D)


class DoubleLayer(nn.Module):
    """The shortcut-connected double layer (module docstring)."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, h, positions, mask, valid, layer_cache=None):
        cfg = self.cfg
        norm = lambda name, x: rms_norm(  # noqa: E731
            x, self.param(name, nn.initializers.ones, (cfg.hidden_size,),
                          cfg.param_dtype), cfg.rms_norm_eps)
        pool = None

        def attend(j, x):
            nonlocal pool
            attn = LatentAttention(cfg, name=f"attn_{j}")
            if layer_cache is None:
                return attn(x, positions, mask)
            first_pool, layer, block_tables, live = layer_cache
            out, pool = attn(
                x, positions, mask,
                (first_pool if pool is None else pool, 2 * layer + j,
                 block_tables, live))
            return out

        h = h + attend(0, norm("input_layernorm_0", h))
        u = norm("post_attention_layernorm_0", h)
        m = ExpertLayer(cfg, name="moe")(u, valid)
        mlp = lambda name: GatedMlp(  # noqa: E731
            cfg.hidden_size, cfg.ffn_hidden_size, cfg.param_dtype, name=name)
        h = h + mlp("mlp_0")(u)
        h = h + attend(1, norm("input_layernorm_1", h))
        h = h + mlp("mlp_1")(norm("post_attention_layernorm_1", h)) + m
        return h if layer_cache is None else (h, pool)


class LongcatFlash(nn.Module):
    cfg: LongcatFlashConfig

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
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
            valid = jnp.ones((B, S), jnp.bool_)
            pool = None
        else:
            # incremental: the chunk starts at each sequence's cache
            # length; slot t of a lane's table holds absolute position
            # t, and a query at position p attends to every t <= p:
            # each paged form masks what it reads by position
            positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
            (pool,) = cache.pools
            mask = None
            valid = jnp.arange(S)[None, :] < cache.live[:, None]
        for i in range(cfg.num_layers):
            layer = DoubleLayer(cfg, name=f"layer_{i}")
            if cache is None:
                h = layer(h, positions, mask, valid)
            else:
                h, pool = layer(h, positions, mask, valid,
                                (pool, i, cache.block_tables, cache.live))
        h = rms_norm(h, self.param("norm", nn.initializers.ones,
                                   (cfg.hidden_size,), cfg.param_dtype),
                     cfg.rms_norm_eps)
        # the caller samples one position a row: project only that
        logits = untied_head(h, head,
                             None if cache is None else logits_at)
        if cache is None:
            return logits
        cache = dataclasses.replace(cache, pools=(pool,))
        if logits_at is not None:
            return logits[:, 0], cache
        return logits, cache
