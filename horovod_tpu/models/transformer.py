"""Decoder-only transformer, TPU-first.

Which models this package serves: :class:`Transformer` here is the
GPT-2 block (multi-head attention, learned positions, LayerNorm, GELU,
tied head; ``gpt2-xl`` is the published architecture it matches),
:mod:`.longcat_flash` is the LongCat-Flash block (latent attention,
the shortcut-connected double layer, routed and zero-compute experts)
and :mod:`.olmo_hybrid` the Olmo-Hybrid block (gated-delta-rule linear
attention with a per-sequence recurrent state, full attention every
fourth layer), :mod:`.command_a_plus` the Command A+ block (window and
full attention layers in two plane groups, grouped-query heads, a
sigmoid router). All enter :class:`~horovod_tpu.serving.generation.GenerationEngine`
through the same ``apply(params, tokens, cache=PagedCache,
logits_at=...)`` contract, and each declares the cache it keeps with a
:class:`CacheSpec` (``cfg.cache_spec()``); :class:`PagedCache` and
:class:`CacheSpec` live in this file for all of them. ``models/`` also holds
ResNet, Inception, VGG and an MLP, which are trained, not served by
the generation plane.

The reference has no transformer (its benchmarks are CNNs), but the TPU
build's parallelism strategies (TP/SP/PP/EP/ring attention — SURVEY.md §2.3,
§7 stage 8) need a first-class transformer to exercise them. Design:

* bfloat16 activations, fp32 params (a server holds the matmul weights
  in ``cfg.dtype``: :meth:`Transformer.serving_params`, cast once where
  the engine takes the params); all projections are einsums with
  explicit head axes so tensor parallelism is a sharding annotation, not a
  rewrite (heads shard over 'tp', hidden shards over 'tp' in the MLP).
* flax ``nn.with_logical_partitioning`` names every parameter axis
  ('embed', 'heads', 'kv', 'mlp', 'vocab'); horovod_tpu.parallel maps those
  logical names onto mesh axes (dp/fsdp/tp/sp) — the pjit idiom.
* the training path names its activations' axes the same way
  (:func:`_constrain`: 'act_batch', 'act_seq', 'act_embed', 'act_heads',
  'act_kv', 'act_mlp', 'act_vocab' on the residual stream, q/k/v, the MLP
  hidden and the logits). Under a mesh and its rules
  (``parallel.train.make_transformer_train_step``) the names say where
  each activation lives, so parameters sharded over 'fsdp' are gathered
  for use and every chip computes its own rows of the batch; with no
  mesh or rules in scope (every serving program, every one-chip caller;
  the MLP block is the paged path's too) they are the identity and
  leave nothing in the lowered program.
* causal attention runs through :func:`attention_fn` injection so context
  parallelism (ring attention over 'sp' via ppermute) and Pallas
  flash-attention kernels plug in without touching the model.

Decode path (the serving generation plane,
:mod:`horovod_tpu.serving.generation`): the same compact module — the
same parameter tree, so any training checkpoint serves — also runs an
incremental forward against a **paged KV cache** when ``__call__`` is
given a :class:`PagedCache`. One code path covers both phases of
autoregressive generation: a *prefill chunk* (``tokens`` is ``(B, C)``
with ``C`` prompt tokens, of which ``live`` are real) and a *decode
step* (a chunk of a few columns). New K/V are scattered into
fixed-size cache blocks through each sequence's block table, then
attention reads them back through the table — so live KV memory scales
with live tokens, not ``max_len × batch``. The pools are written **in
place**: one live ``(layers, blocks, block_size, row)`` pool is
threaded through the layers, layer ``i`` scatters at ``[i, blocks,
offsets]``, attends over plane ``i`` of what it has just written, and
hands the pool on — no layer's slab is sliced out or put back, so a
program that donates the pools holds a single version of each.
Block 0 is the **null block**: padded slots and
dead batch lanes write there (and only there), which keeps every shape
static across steps — the jit cache sees exactly two programs, one per
phase.

**Which attention the paged read runs** is decided by what the code can
see (:func:`horovod_tpu.ops.paged_attention.kernel_applies`), not by an
option. On a TPU, for a chunk of a few query columns (the decode,
verify and beam programs) over a pool of whole tiles, it is the Pallas
paged-attention kernel: the pools stay in HBM, a lane's blocks are read
where they lie and only as far as ``lengths + C``, a dead lane costs no
copy, and a step's cost follows the live tokens. Everywhere else — any
other backend, the prefill program's chunk, a toy pool of 4-token
blocks — it is the gather path (:func:`_gathered_attention`): every
slot of every table gathered into one ``(B, T, H, D)`` view, then
:func:`_default_attention` over it; the kernel's oracle. The gather
path deliberately reuses :func:`_default_attention`, so on XLA-CPU
decode logits are bit-identical to the full-sequence forward (tests pin
it); a TPU promises no bit identity between differently shaped
programs, and there the contract is a tolerance (the kernel within
``chip_smoke.KERNEL_TOL`` of the gather path, a served token's
reference logit within ``LOGIT_TOL`` of the best).
(``attention_fn`` injection is a training-side
hook and is not consulted on the paged path.) The paged path also
takes an optional ``logits_at`` ``(B,)`` position index: the vocab
projection then runs only at that position per row and returns
``(B, vocab)`` logits — the serving sampling programs use it so the
full ``(B, C, vocab)`` logits tensor never materializes on the decode
hot path (the selected row stays bit-identical to the full projection).
"""

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import paged_attention

Dtype = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    head_dim: int = 64
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    # injected attention implementation; default = XLA softmax attention
    attention_fn: Optional[Callable] = None
    remat: bool = False

    def cache_spec(self) -> "CacheSpec":
        """A layer's attention keeps a token's K and its V: two rows of
        ``heads * head_dim`` values, one plane a layer."""
        width = self.num_heads * self.head_dim
        return CacheSpec(planes=self.num_layers,
                         rows=(("k", width), ("v", width)),
                         dtype=self.dtype)

    def paged_query_rows(self, chunk: int) -> int:
        """Query vectors a ``chunk``-column step of the paged forward
        brings to :mod:`horovod_tpu.ops.paged_attention`, one a head a
        column: what its path rule (``kernel_applies``) is asked about,
        by :class:`Attention` and by whoever counts what a program
        reads."""
        return chunk * self.num_heads


@dataclasses.dataclass(frozen=True)
class PlaneGroup:
    """Planes of a :class:`CacheSpec` that keep a token's rows equally
    long: ``window=None`` as long as the sequence runs, ``window=W``
    only while a later query may still read them (a sliding-window
    attention: a query at position ``p`` reads the keys at ``p - W < t
    <= p``). A group has its own pools and its own block allocator, and
    a sequence one block table a group."""

    name: str
    planes: int
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a served model keeps in the paged cache: its declaration.

    **Per-token rows.** ``planes``: how many attention sublayers keep
    rows (a pool's leading axis). ``rows``: ``(name, width)`` for each
    array a token leaves behind in one plane: one pool a row, ``width``
    values of ``dtype`` at the head of a pool row.

    **Plane groups.** ``groups``: empty for a model whose planes all
    keep every token (one group, one pool a row, one block table a
    sequence: every model but one that mixes window and full attention).
    Otherwise the :class:`PlaneGroup` s that ``planes`` divide into, the
    group that keeps every token first: each group has one pool a row,
    ``(group.planes, its blocks, block_size, row)``, the pools of the
    first group's rows first; ``PagedCache.block_tables`` is then a
    tuple, one table a group, and a window group's table holds 0 (the
    null block) where a block was given back
    (``docs/serving_models.md``).

    **Per-sequence state.** ``state``: ``(name, planes, shape, dtype)``
    for each array a *sequence* keeps whatever its length (a linear
    attention's recurrent matrix, a convolution's window): one pool
    each, ``(planes, slots, *shape)``, a running sequence's entry at its
    **state slot**. A model that declares state cannot be served from
    its per-token rows alone: a prefix of cached blocks is worth nothing
    without the state the sequence had after the prefix's last token, so
    the scheduler keeps **snapshots** of it (``docs/serving_models.md``).
    Empty for a model whose cache is a function of token positions.

    :func:`~horovod_tpu.serving.generation.kv_cache.make_pools`,
    ``block_bytes``, ``gather_blocks``/``scatter_blocks``, the disagg
    wire codec, the scheduler, the allocator and the five programs read
    nothing about a model's cache but this; the model's paged forward
    gets the pools in the order of ``rows``, then of ``state``, and
    hands them back in it.
    """

    planes: int
    rows: Tuple[Tuple[str, int], ...]
    dtype: Dtype
    state: Tuple[Tuple[str, int, Tuple[int, ...], Dtype], ...] = ()
    groups: Tuple[PlaneGroup, ...] = ()

    def plane_groups(self) -> Tuple[PlaneGroup, ...]:
        """``groups``, or the one group of a model that declares none."""
        return self.groups or (PlaneGroup("full", self.planes),)


@dataclasses.dataclass(frozen=True)
class PagedCache:
    """The paged-cache view threaded through one incremental forward.

    ``pools``: one ``(planes, num_blocks, block_size, row)`` array for
    each row of the model's :class:`CacheSpec`, in its order (for
    :class:`Transformer`: K and V, a token's ``heads * head_dim`` values
    at the head of each row; block 0 reserved as the null block), as
    :func:`~horovod_tpu.serving.generation.kv_cache.make_pools` lays
    them out; the forward returns them updated in place of the ones it
    was given, every other row untouched. ``block_tables``:
    ``(B, max_blocks)`` int32 — each row maps a sequence's logical block
    index to a pool block (0-padded past its allocation); for a model
    that declares plane groups a tuple of such tables, one a group.
    ``lengths``:
    ``(B,)`` tokens already in each sequence's cache (the chunk starts
    there). ``live``: ``(B,)`` how many of this chunk's ``C`` tokens are
    real; pad tokens (and dead lanes, ``live == 0``) write to the null
    block. For a model that declares per-sequence state the state pools
    follow the row pools in ``pools`` and ``slots`` ``(B,)`` int32 names
    each batch row's state slot; ``slots=None`` says row ``i`` is slot
    ``i`` (the decode program: its lanes are the slots, so a layer takes
    its whole plane and nothing is gathered). A state has no null block:
    the forward itself leaves the state of a dead lane, and of a live
    one past its ``live`` columns, bit-identical. All leaves are arrays
    (``None`` has none), so the dataclass flattens cleanly through
    ``jax.jit`` argument trees.
    """

    pools: Tuple[Any, ...]
    block_tables: Any
    lengths: Any
    live: Any
    slots: Any = None


jax.tree_util.register_dataclass(
    PagedCache,
    data_fields=["pools", "block_tables", "lengths", "live", "slots"],
    meta_fields=[])


def _constrain(x, *names):
    """Say where activation ``x`` lives, one logical name an axis. The
    identity unless a mesh and flax logical-axis rules are in scope."""
    return nn.with_logical_constraint(x, names)


def _default_attention(q, k, v, mask, dtype):
    """Plain softmax attention: (B, S, H, D) inputs, causal mask applied.
    Softmax in fp32 (TPU recipe: keep reductions out of bf16)."""
    depth = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(depth).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _table_mask(positions, slots: int):
    """The gather path's causal mask ``(B, 1, C, slots)``: gathered slot
    ``t`` holds absolute position ``t``, and a chunk query at absolute
    position ``p`` attends to every ``t <= p``."""
    return (jnp.arange(slots)[None, None, None, :]
            <= positions[:, None, :, None])


def _gathered_attention(q, k_pool, v_pool, layer, block_tables, mask, dtype):
    """The paged read as plain XLA: gather every slot of every lane's
    table from plane ``layer`` of the pools into one contiguous
    ``(B, T, H, D)`` view (``T = max_blocks * block_size``, position
    ``t`` at index ``t``) and run :func:`_default_attention` over all of
    it. What a prefill chunk and every run off a TPU take, and the
    oracle :mod:`~horovod_tpu.ops.paged_attention` is held to."""
    B, _, H, D = q.shape
    with jax.named_scope("kv_gather"):
        kc = k_pool[layer, block_tables][..., :H * D].reshape(B, -1, H, D)
        vc = v_pool[layer, block_tables][..., :H * D].reshape(B, -1, H, D)
    with jax.named_scope("attention"):
        return _default_attention(q, kc, vc, mask, dtype)


def write_kv_rows(k_pool, v_pool, layer, block_tables, positions, live,
                  k, v):
    """Scatter a chunk's K and V (``(B, C, ...)``, a token's values
    flattened to its row) into plane ``layer`` of the pools through the
    block tables; pad tokens (and dead lanes) route to the null block 0.
    A token's row is its values, zero-padded to the pool's lane-aligned
    width."""
    B, C = k.shape[0], k.shape[1]
    block_size = k_pool.shape[2]
    blk_idx = positions // block_size                       # (B, C)
    offsets = positions % block_size                        # (B, C)
    blocks = jnp.take_along_axis(
        block_tables, blk_idx.astype(jnp.int32), axis=1)    # (B, C)
    valid = jnp.arange(C)[None, :] < live[:, None]
    blocks = jnp.where(valid, blocks, 0)
    pad = ((0, 0), (0, 0), (0, k_pool.shape[3] - k[0, 0].size))
    k_pool = k_pool.at[layer, blocks, offsets].set(
        jnp.pad(k.reshape(B, C, -1), pad))
    v_pool = v_pool.at[layer, blocks, offsets].set(
        jnp.pad(v.reshape(B, C, -1), pad))
    return k_pool, v_pool


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, layer_cache=None):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        wq = self.param("wq", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", "heads", "kv")),
            (cfg.d_model, H, D), jnp.float32)
        wk = self.param("wk", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", "heads", "kv")),
            (cfg.d_model, H, D), jnp.float32)
        wv = self.param("wv", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", "heads", "kv")),
            (cfg.d_model, H, D), jnp.float32)
        wo = self.param("wo", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("heads", "kv", "embed")),
            (H, D, cfg.d_model), jnp.float32)
        dt = cfg.dtype
        q = jnp.einsum("bse,ehd->bshd", x, wq.astype(dt))
        k = jnp.einsum("bse,ehd->bshd", x, wk.astype(dt))
        v = jnp.einsum("bse,ehd->bshd", x, wv.astype(dt))
        if layer_cache is None:
            q, k, v = (_constrain(a, "act_batch", "act_seq", "act_heads",
                                  "act_kv") for a in (q, k, v))
            attn = cfg.attention_fn or _default_attention
            out = attn(q, k, v, mask, dt)
            return jnp.einsum("bshd,hde->bse", out, wo.astype(dt))
        # -- paged incremental path ---------------------------------------
        # layer_cache: the whole (L, num_blocks, block_size, row) pools,
        # this layer's static index into them, and the batch's
        # tables/positions; see PagedCache. The layer writes and reads
        # only its own [layer] plane of the one live pool and hands the
        # updated pool on, so XLA keeps a single version of the donated
        # buffer: no slab is sliced out and none is put back. Heads and
        # head_dim are one merged, lane-aligned minor axis in the pool
        # (make_pools says why: the layout a TPU gives the array). The named
        # scopes cost nothing at run time; under flax's own module
        # scopes they name a captured profile's operations
        # ``layer_<i>/attn/kv_write`` and so on (the MLP is ``layer_<i>/mlp``).
        k_pool, v_pool, layer, block_tables, positions, live = layer_cache
        B, C = x.shape[0], x.shape[1]
        block_size = k_pool.shape[2]
        with jax.named_scope("kv_write"):
            k_pool, v_pool = write_kv_rows(
                k_pool, v_pool, layer, block_tables, positions, live, k, v)
        if paged_attention.kernel_applies(cfg.paged_query_rows(C), block_size,
                                          k_pool.shape[3], k_pool.dtype):
            # a few query columns on a TPU: the kernel walks each live
            # lane's blocks where they lie, as far as the lane has rows
            with jax.named_scope("attention"):
                out = paged_attention.paged_attention(
                    q, k_pool, v_pool, layer, block_tables,
                    positions[:, 0], live)
        else:
            out = _gathered_attention(q, k_pool, v_pool, layer,
                                      block_tables, mask, dt)
        return (jnp.einsum("bshd,hde->bse", out, wo.astype(dt)),
                (k_pool, v_pool))


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hidden = cfg.d_model * cfg.mlp_ratio
        wi = self.param("wi", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", "mlp")),
            (cfg.d_model, hidden), jnp.float32)
        wo = self.param("wo", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("mlp", "embed")),
            (hidden, cfg.d_model), jnp.float32)
        dt = cfg.dtype
        h = jnp.einsum("bse,em->bsm", x, wi.astype(dt))
        h = _constrain(h, "act_batch", "act_seq", "act_mlp")
        h = nn.gelu(h)
        return jnp.einsum("bsm,me->bse", h, wo.astype(dt))


#: the residual stream (batch, sequence, width)
_RESIDUAL = ("act_batch", "act_seq", "act_embed")


class DecoderLayer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, layer_cache=None):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        if layer_cache is None:
            x = _constrain(x, *_RESIDUAL)
            x = x + Attention(cfg, name="attn")(ln("ln1")(x), mask)
            x = _constrain(x, *_RESIDUAL)
            x = x + MlpBlock(cfg, name="mlp")(ln("ln2")(x))
            return _constrain(x, *_RESIDUAL)
        attn_out, kv = Attention(cfg, name="attn")(
            ln("ln1")(x), mask, layer_cache=layer_cache)
        x = x + attn_out
        x = x + MlpBlock(cfg, name="mlp")(ln("ln2")(x))
        return x, kv


#: the weights the block multiplies by, by module: what its serving
#: form holds in ``cfg.dtype`` (LayerNorms keep ``scale`` and ``bias``)
_MATMUL_WEIGHTS = {"attn": ("wq", "wk", "wv", "wo"), "mlp": ("wi", "wo")}


def _is_matmul_weight(path) -> bool:
    names = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    return len(names) >= 2 and names[-1] in _MATMUL_WEIGHTS.get(names[-2], ())


@functools.partial(jax.jit, static_argnums=1)
def _cast_weights(weights, dtype):
    """One device program for every cast; no input is donated (the
    caller may still hold its float32 tree)."""
    return tuple(w.astype(dtype) for w in weights)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.nowrap
    def serving_params(self, params):
        """The form of ``params`` the paged programs take: every layer's
        attention and MLP weights in ``cfg.dtype``, everything else (the
        LayerNorms, ``embedding`` and ``pos_embedding``) the very leaves
        given. The model multiplies by ``w.astype(cfg.dtype)``, so each
        program computes what it computes on the float32 masters, bit for
        bit, without reading and converting them in every call. The
        masters stay float32 for training, which shares this module.
        With ``cfg.dtype`` float32 ``params`` comes back as it is."""
        dt = jnp.dtype(self.cfg.dtype)
        if dt == jnp.float32:
            return params
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = [leaf for _, leaf in leaves]
        at = [i for i, (path, leaf) in enumerate(leaves)
              if _is_matmul_weight(path) and jnp.dtype(leaf.dtype) != dt]
        if at:
            for i, w in zip(at, _cast_weights(tuple(out[i] for i in at), dt)):
                out[i] = w
        return jax.tree_util.tree_unflatten(treedef, out)

    @nn.compact
    def __call__(self, tokens, cache=None, logits_at=None):
        cfg = self.cfg
        B, S = tokens.shape
        emb = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        pos = self.param("pos_embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (None, "embed")),
            (cfg.max_seq_len, cfg.d_model), jnp.float32)
        if cache is None:
            x = emb.astype(cfg.dtype)[tokens] \
                + pos.astype(cfg.dtype)[None, :S]
            x = _constrain(x, *_RESIDUAL)
            mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
        else:
            # incremental: S == chunk length C; absolute positions come
            # from each sequence's cache length (clipped only to keep
            # the pad-token gather in bounds — live tokens are validated
            # host-side against max_seq_len before submission)
            positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
            safe_pos = jnp.clip(positions, 0, cfg.max_seq_len - 1)
            # gather the rows, then cast them: the same values as
            # casting the tables first, without a whole-table convert
            x = emb[tokens].astype(cfg.dtype) \
                + pos[safe_pos].astype(cfg.dtype)
            mask = _table_mask(
                positions,
                cache.block_tables.shape[1] * cache.pools[0].shape[2])
        k_pool, v_pool = (None, None) if cache is None else cache.pools
        layer_cls = DecoderLayer
        if cfg.remat and cache is None:
            layer_cls = nn.remat(DecoderLayer, static_argnums=())
        for i in range(cfg.num_layers):
            layer = layer_cls(cfg, name=f"layer_{i}")
            if cache is None:
                x = layer(x, mask, None)
            else:
                # one live pool: layer i scatters into and attends over
                # plane i of what layer i-1 returned
                x, (k_pool, v_pool) = layer(
                    x, mask, (k_pool, v_pool, i, cache.block_tables,
                              positions, cache.live))
        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="ln_f")(x)
        if cache is not None and logits_at is not None:
            # paged serving fast path: the caller only samples one
            # position per row, so project just that position into the
            # vocab — the projection shrinks by the chunk factor and the
            # (B, C, V) logits tensor never materializes. The einsum
            # below reduces over the same 'e' axis with the same
            # contraction order, so the selected row's logits stay
            # bit-identical to the full projection (tests pin it).
            x = jnp.take_along_axis(
                x, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        # logits in fp32, weight-tied to the embedding
        with jax.named_scope("head"):
            logits = jnp.einsum("bse,ve->bsv", x.astype(jnp.float32),
                                emb.astype(jnp.float32))
        if cache is None:
            return _constrain(logits, "act_batch", "act_seq", "act_vocab")
        if logits_at is not None:
            return logits[:, 0], dataclasses.replace(
                cache, pools=(k_pool, v_pool))
        return logits, dataclasses.replace(cache, pools=(k_pool, v_pool))
