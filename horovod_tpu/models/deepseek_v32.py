"""DeepSeek-V3.2: latent attention with DeepSeek sparse attention (DSA),
group-limited sigmoid routing. Serving first.

It enters the generation plane through the contract every served block
keeps, ``apply(params, tokens, cache=PagedCache, logits_at=...)``, and
declares its cache (:meth:`DeepseekV32Config.cache_spec`): a token
leaves **two rows** in each layer's plane, the latent row of latent
attention (``[c_kv | k_rope]``, 576 values) and the lightning indexer's
key (``index_key``, 128 values), in one pool each under one block
table.

One layer (RMSNorm, no biases)::

    h = h + A(in(h))          # MLA; DSA picks the rows it reads
    h = h + F(post(h))        # a SwiGLU in the leading dense layers,
                              # else routed experts plus a shared one

**DSA.** The indexer reads the normalised query latent ``c_q`` and the
layer input ``x``: ``q_j = W_q,j c_q`` (``index_n_heads`` heads of
``index_head_dim``), ``k = LayerNorm(W_k x)``, rotary on the first
``qk_rope_head_dim`` dimensions of both (halves, not interleaved),
``w_j = (W_w x)_j / sqrt(index_n_heads index_head_dim)``, and the score
of key ``s`` for query ``t`` is ``sum_j w_j relu(q_j . k_s)``. Each
query reads the ``index_topk`` positions of its prefix with the largest
scores (an exact top-k, ties to the lower position), and latent
attention (:class:`~.longcat_flash.LatentAttention`, whose ``indexer``
option this module's :class:`Indexer` is) attends to those alone.

On the paged path the chunk first writes both rows; the indexer walks
the lane's index rows in key blocks of :data:`INDEX_KEY_BLOCK` up to the
chunk's last live position, without gathering the table, into a float32
row of scores a query; :func:`exact_top_k` sorts it by segments of
:data:`TOP_K_SEGMENT` positions into a running top-k, as far as the
lane holds, each position carrying the pool row its slot maps to
(:func:`slot_rows`); latent attention reads those rows from the pool
where they lie, a block of queries at a time. Where every live query's
prefix is at most ``index_topk`` the choice is the whole prefix and
:class:`~.longcat_flash.LatentAttention`'s own forms run.

The router (:func:`~horovod_tpu.parallel.moe.route_group_limited_topk`):
sigmoid scores in float32, the correction bias steers the choice only,
8 groups of 32 experts, the 4 best groups by the sum of their two best
biased scores, the top 8 inside them, weights renormalised and times
``routed_scaling_factor``. The held experts go through
:func:`~horovod_tpu.parallel.moe.held_experts_mlp`; the shared expert
is a SwiGLU every token passes.

Named scopes, under flax's module scopes:
``layer_<i>/attn/{q_proj,kv_write,indexer,select,sparse_rows,attention,
absorb,out_proj}``, ``layer_<i>/mlp``,
``layer_<i>/moe/{router,sort,experts,identity,combine,shared}``, ``head``.
"""

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.moe import (STATS_COLLECTION, held_experts_mlp,
                            route_group_limited_topk)
from .blocks import (GatedMlp, YaRN, layer_norm, normal_init as _init,
                     rms_norm, rotary_half, untied_head)
from .longcat_flash import (LatentAttention, LatentAttentionConfig,
                            write_rows)
from .transformer import CacheSpec, PagedCache

Dtype = Any

#: index rows the indexer scores at a time on the paged path: its float32
#: logits are queries x index heads x keys x 4 bytes (512 x 64 x 512 x 4
#: B = 67 MB for a prefill chunk)
INDEX_KEY_BLOCK = 512
#: positions :func:`exact_top_k` sorts at a time before their first k
#: join the running top-k: a TPU's sort costs more than linearly in the
#: row's length
TOP_K_SEGMENT = 8192
_NEG_INF = jnp.finfo(jnp.float32).min


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(LatentAttentionConfig):
    """The source's own key names (``config.json`` of
    ``deepseek-ai/DeepSeek-V3.2``), published values as defaults.
    ``n_routed_experts`` is the whole model's count (the router's
    width); ``held_experts`` names the experts whose weights this chip
    holds, ``(first, end)``."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRN] = YaRN()
    held_experts: Tuple[int, int] = (0, 256)
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.bfloat16
    #: latent attention's LongCat switches, off in DeepSeek's MLA
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    def cache_spec(self) -> CacheSpec:
        """A layer's plane keeps a token's latent row and its index key."""
        return CacheSpec(
            planes=self.num_hidden_layers,
            rows=(("latent", self.kv_lora_rank + self.qk_rope_head_dim),
                  ("index_key", self.index_head_dim)),
            dtype=self.dtype)

    def attention_reads(self, lengths: Sequence[int],
                        live: Sequence[int]) -> dict:
        """Rows one attention sublayer of a paged dispatch reads, summed
        over its live queries, for lanes holding ``lengths`` tokens with
        ``live`` new columns each: ``context`` the rows of each query's
        prefix (``p + 1`` at position ``p``), ``scored`` the index rows
        the indexer scores (the prefix, in a dispatch that selects;
        nothing where every prefix is at most ``index_topk`` and the
        choice is the whole prefix), ``selected`` the rows latent
        attention reads (``min(index_topk, p + 1)`` where it selects).
        Host arithmetic for the scheduler's
        ``hvd_tpu_gen_sparse_attn_rows_total``: the forward's own rule."""
        k = self.index_topk
        prefixes = [n + j + 1 for n, c in zip(lengths, live)
                    for j in range(int(c))]
        context = sum(prefixes)
        if all(p <= k for p in prefixes):
            return {"context": context, "scored": 0, "selected": context}
        return {"context": context, "scored": context,
                "selected": sum(min(p, k) for p in prefixes)}


def _descending_key(scores):
    """int32 keys whose ascending order is the descending order of the
    float32 ``scores`` as ``lax.top_k`` ranks them (a total order: 0.0
    above -0.0)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return ~jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _score_of_key(keys):
    """The float32 score of :func:`_descending_key`'s ``keys``."""
    bits = ~keys
    return jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits), jnp.float32)


def exact_top_k(scores, k: int, payload, end=None):
    """The ``k`` largest of each row of ``scores`` ``(..., T)`` (float32;
    ties to the lower position) in ``lax.top_k``'s order, and what each
    array of ``payload`` (broadcast to ``scores``' shape) holds there:
    ``(values, *payload)``, each ``(..., k)``. With ``end`` (a traced
    count) only the first ``end`` positions can win and the segments
    past it are never ranked.

    A row is ranked :data:`TOP_K_SEGMENT` positions at a time: a
    segment's scores sorted with its payload alongside, its first ``k``
    merged into the running top-``k`` by a second sort of ``2k``, the
    running ones first. Both sorts are stable, so a tie stays in
    position order: the set and the order are one ``top_k``'s over the
    row, and the payload (the pool rows) rides along, never looked up
    afterwards."""
    T = scores.shape[-1]
    seg = min(max(TOP_K_SEGMENT, k), T)
    n = -(-T // seg)
    pad = [(0, 0)] * (scores.ndim - 1) + [(0, n * seg - T)]
    scores = jnp.pad(scores, pad, constant_values=_NEG_INF)
    payload = [jnp.pad(a, pad[-a.ndim:]) for a in payload]
    shape = scores.shape[:-1] + (seg,)

    def first_k(ops):
        return tuple(a[..., :k] for a in jax.lax.sort(ops, num_keys=1,
                                                       is_stable=True))

    def ranked(i):                  # segment i's first k, sorted
        part = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * seg, seg, axis=-1)
        return first_k((_descending_key(part(scores)),) + tuple(
            jnp.broadcast_to(part(a), shape) for a in payload))

    def merge(i, top):
        return first_k(tuple(jnp.concatenate(ab, axis=-1)
                             for ab in zip(top, ranked(i))))

    key, *payload = jax.lax.fori_loop(
        1, n if end is None else (end + seg - 1) // seg, merge, ranked(0))
    return (_score_of_key(key), *payload)


def slot_rows(tables, plane: int, num_blocks: int, block_size: int,
              width: int):
    """The row of the pools' flat view ``(planes * num_blocks *
    block_size, row)`` that holds slot ``t`` of each lane's table in
    plane ``plane``, for the ``width`` slots (whole blocks, the table's
    at the least): ``(B, width)`` int32, the table broadcast over a
    block's offsets (no gather). Slots past the table map to the plane's
    null block."""
    B, nb = tables.shape
    tables = jnp.pad(tables, ((0, 0), (0, width // block_size - nb)))
    base = (plane * num_blocks + tables) * block_size
    return (base[:, :, None] + jnp.arange(block_size, dtype=jnp.int32)
            ).reshape(B, width)


class Indexer(nn.Module):
    """The lightning indexer of one layer (module docstring). With no
    cache: the ``(B, 1, S, S)`` mask of the positions each query may
    read. On the paged path (``index_cache`` ``(pool, plane,
    block_tables, live)``): the chunk's index keys written, and
    ``(pool, choose)``, where ``choose()`` gives each query's chosen
    rows ``(B, C, k)`` of the pools' flat view (:func:`slot_rows`; the
    latent pool holds the same blocks) and which of them hold a row of
    its prefix.
    Applied with the collection ``dsa_chosen`` mutable (the benchmark's
    check), it sows those positions as ``positions``, -1 where a prefix
    is shorter, whether or not the dispatch selects."""

    cfg: DeepseekV32Config

    @nn.compact
    def __call__(self, x, c_q, positions, index_cache=None):
        cfg = self.cfg
        D, rq = cfg.hidden_size, cfg.q_lora_rank
        Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, \
            cfg.qk_rope_head_dim
        pd = cfg.param_dtype
        wq = self.param("wq_b", _init(), (rq, Hi, Di), pd)
        wk = self.param("wk", _init(), (D, Di), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (Di,), pd)
        k_bias = self.param("k_norm_bias", nn.initializers.zeros, (Di,), pd)
        ww = self.param("weights_proj", _init(), (D, Hi), pd)

        def rope_first(a):              # rotary on the first dr dims
            return jnp.concatenate(
                [rotary_half(a[..., :dr], positions, cfg.rope_theta,
                             cfg.rope_scaling), a[..., dr:]], axis=-1)

        q = rope_first(jnp.einsum("bsq,qhd->bshd", c_q, wq))
        k = rope_first(layer_norm(x @ wk, k_norm, k_bias, 1e-6))
        w = jnp.einsum("bsd,dh->bsh", x, ww,
                       preferred_element_type=jnp.float32) \
            * (Hi * Di) ** -0.5
        if index_cache is None:
            scores = _index_scores(q, k[:, None], w)[:, :, 0]  # (B, S, S)
            S = scores.shape[-1]
            seen = jnp.tril(jnp.ones((S, S), jnp.bool_))[None]
            with jax.named_scope("select"):
                idx = jax.lax.top_k(jnp.where(seen, scores, _NEG_INF),
                                    min(cfg.index_topk, S))[1]
                B = scores.shape[0]
                keep = jnp.zeros(scores.shape, jnp.bool_).at[
                    jnp.arange(B)[:, None, None],
                    jnp.arange(S)[None, :, None], idx].set(True)
            return keep[:, None]
        pool, plane, tables, live = index_cache
        # the pool's row is lane-aligned: 128 at the published width
        pool = write_rows(pool, plane, tables, positions, live, jnp.pad(
            k, ((0, 0), (0, 0), (0, pool.shape[3] - Di))))

        def choose(sow=False):
            scores, end = _walked_scores(q, w, pool, plane, tables,
                                         positions, live)
            T = scores.shape[-1]
            with jax.named_scope("select"):
                rows = slot_rows(tables, plane, pool.shape[1], pool.shape[2],
                                 T)[:, None]
                # the positions ride along only for the check that reads
                # them: a payload moves no choice
                top, *chosen = exact_top_k(
                    scores, min(cfg.index_topk, T), (rows,) + (
                        (jnp.arange(T, dtype=jnp.int32),) if sow else ()),
                    end)
                # the walk leaves _NEG_INF past a query's position and a
                # finite score at and before it
                return (chosen[0], top > _NEG_INF, *chosen[1:])

        if self.is_mutable_collection("dsa_chosen"):
            rows, valid, idx = choose(sow=True)
            self.sow("dsa_chosen", "positions", jnp.where(valid, idx, -1))
            return pool, lambda: (rows, valid)
        return pool, choose


def _index_scores(q, keys, w):
    """``sum_j w_j relu(q_j . k)``: ``q`` ``(B, C, Hi, Di)``, ``keys``
    ``(B, G, T, Di)`` (``G`` 1 or ``C``), ``w`` ``(B, C, Hi)`` float32;
    ``(B, C, G, T)`` float32 scores."""
    logits = jnp.einsum("bchd,bgtd->bcght", q, keys,
                        preferred_element_type=jnp.float32)
    return jnp.einsum("bcght,bch->bcgt", jnp.maximum(logits, 0.0), w)


def _walked_scores(q, w, pool, plane, tables, positions, live):
    """Every query's index scores over its lane's table ``(B, C, T)``,
    ``_NEG_INF`` past its position (``T``: the table's slots rounded up
    to whole key blocks and, past :data:`TOP_K_SEGMENT`, to whole
    segments of :func:`exact_top_k`, so that nothing is sliced or
    padded between the two): the lane's index rows read where they lie,
    :data:`INDEX_KEY_BLOCK` slots at a time, from block 0 to the block
    of the last live column of any live lane (a dynamic trip count; with
    no live lane nothing is read), and that end."""
    B, C = positions.shape
    block_size = pool.shape[2]
    keys = max(1, INDEX_KEY_BLOCK // block_size) * block_size
    per = keys // block_size
    slots = tables.shape[1] * block_size
    n = -(-slots // keys)
    if n * keys > TOP_K_SEGMENT and TOP_K_SEGMENT % keys == 0:
        n = -(-slots // TOP_K_SEGMENT) * (TOP_K_SEGMENT // keys)
    last = positions[:, 0] + live - 1
    trips = jnp.max(jnp.where(live > 0, last // keys + 1, 0))

    def step(i, buf):
        blocks = jnp.take(tables, i * per + jnp.arange(per), axis=1,
                          mode="fill", fill_value=0)        # (B, per)
        rows = pool[plane, blocks].reshape(B, 1, keys, -1)
        scores = _index_scores(q, rows[..., :q.shape[-1]], w)[:, :, 0]
        t = i * keys + jnp.arange(keys)
        scores = jnp.where(t[None, None, :] <= positions[:, :, None],
                           scores, _NEG_INF)
        return jax.lax.dynamic_update_slice_in_dim(buf, scores, i * keys,
                                                   axis=2)

    with jax.named_scope("indexer"):
        buf = jax.lax.fori_loop(
            0, trips, step, jnp.full((B, C, n * keys), _NEG_INF,
                                     jnp.float32))
    return buf, trips * keys


class ExpertLayer(nn.Module):
    """The router over all ``n_routed_experts``, this chip's held experts
    and the shared expert. Returns ``F(u)`` as far as this chip
    computes it."""

    cfg: DeepseekV32Config

    @nn.compact
    def __call__(self, u, valid):
        cfg = self.cfg
        D, Fe, pd = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.param_dtype
        n_out = cfg.n_routed_experts
        n_held = cfg.held_experts[1] - cfg.held_experts[0]
        router = self.param("router", _init(), (D, n_out), jnp.float32)
        bias = self.param("e_score_correction_bias", _init(0.01), (n_out,),
                          jnp.float32)
        w_gate = self.param("experts_gate", _init(), (n_held, D, Fe), pd)
        w_up = self.param("experts_up", _init(), (n_held, D, Fe), pd)
        w_down = self.param("experts_down", _init(), (n_held, Fe, D), pd)
        shared = GatedMlp(D, Fe * cfg.n_shared_experts, pd,
                          name="shared_experts")
        B, C = u.shape[0], u.shape[1]
        flat = u.reshape(B * C, D)
        with jax.named_scope("router"):
            # the router runs in float32, whatever the activations are
            logits = jnp.dot(flat.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            idx, weights = route_group_limited_topk(
                logits, bias, cfg.num_experts_per_tok, cfg.n_group,
                cfg.topk_group, cfg.routed_scaling_factor)
        held, _, stats = held_experts_mlp(
            flat, idx, weights, w_gate, w_up, w_down, cfg.held_experts,
            cfg.n_routed_experts, valid=valid.reshape(B * C))
        if not self.is_initializing():   # init would keep the counts
            self.sow(STATS_COLLECTION, "counts", stats)
        with jax.named_scope("shared"):
            s = shared(u)
        with jax.named_scope("combine"):
            return (held.reshape(B, C, D) + s.astype(jnp.float32)).astype(
                u.dtype)


class Layer(nn.Module):
    """One layer (module docstring); ``dense``: a leading dense layer.
    ``indexer``: the selection of :class:`LatentAttention` (None: MLA
    over the whole prefix, as GLM-4.7-Flash's layers,
    :mod:`.glm47_flash`). On the paged path it returns ``h`` and the
    pools its attention returns."""

    cfg: Any
    dense: bool
    indexer: Any = Indexer

    @nn.compact
    def __call__(self, h, positions, mask, valid, layer_cache=None,
                 index_cache=None):
        cfg = self.cfg
        norm = lambda name, x: rms_norm(  # noqa: E731
            x, self.param(name, nn.initializers.ones, (cfg.hidden_size,),
                          cfg.param_dtype), cfg.rms_norm_eps)
        attn = LatentAttention(cfg, indexer=self.indexer, name="attn")
        x = norm("input_layernorm", h)
        if layer_cache is None:
            h = h + attn(x, positions, mask)
        else:
            a, *pools = attn(x, positions, mask, layer_cache, index_cache)
            h = h + a
        u = norm("post_attention_layernorm", h)
        if self.dense:
            h = h + GatedMlp(cfg.hidden_size, cfg.intermediate_size,
                             cfg.param_dtype, name="mlp")(u)
        else:
            h = h + ExpertLayer(cfg, name="moe")(u, valid)
        return h if layer_cache is None else (h, *pools)


class DeepseekV32(nn.Module):
    cfg: DeepseekV32Config

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
        else:
            # the chunk starts at each sequence's cache length; slot t of
            # a lane's table holds absolute position t
            positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
            pool, index_pool = cache.pools
            mask = None
            valid = jnp.arange(S)[None, :] < cache.live[:, None]
        for i in range(cfg.num_hidden_layers):
            layer = Layer(cfg, dense=i < cfg.first_k_dense_replace,
                          name=f"layer_{i}")
            if cache is None:
                h = layer(h, positions, mask, valid)
            else:
                h, pool, index_pool = layer(
                    h, positions, mask, valid,
                    (pool, i, cache.block_tables, cache.live),
                    (index_pool, i, cache.block_tables, cache.live))
        h = rms_norm(h, self.param("norm", nn.initializers.ones,
                                   (cfg.hidden_size,), cfg.param_dtype),
                     cfg.rms_norm_eps)
        # the caller samples one position a row: project only that
        logits = untied_head(h, head,
                             None if cache is None else logits_at)
        if cache is None:
            return logits
        cache = dataclasses.replace(cache, pools=(pool, index_pool))
        if logits_at is not None:
            return logits[:, 0], cache
        return logits, cache
