"""Expert parallelism (Mixture-of-Experts) over the 'ep' mesh axis.

Absent from the reference (SURVEY.md §2.3). Switch-Transformer-style top-1
routing with capacity, dispatched between devices by a single pair of
all_to_alls — the canonical TPU MoE layout: experts shard over 'ep', each
device computes only its experts, and token movement is one all_to_all each
way (ICI-friendly; the dispatch/combine einsums land on the MXU).

Static shapes throughout (capacity fixed at trace time); overflowing tokens
are dropped and their outputs fall back to zero (residual connections carry
them), the standard capacity-factor semantics.

The serving half (:func:`route_topk` or :func:`route_sigmoid_topk`, then
:func:`held_experts_mlp`) is another
layer: top-k routing over every router output of the model, **dropless**,
for a chip that is *told* which FFN experts it holds (``held_experts``, a
range of expert ids: an argument, not a property of the weights' shape).
Picks on held experts are sorted by expert and go through a grouped matmul
whose work follows the load (an expert no live token picked is not read);
picks on zero-compute identity experts add ``w * u`` with no matmul; picks
on experts another chip holds add nothing: the partial result goes on as
it is, and nothing stands in for the absent chips or their exchange.
"""

from typing import Tuple

import jax
import jax.numpy as jnp


def route_top1(gate_logits, capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 router (per device group).

    Args:
      gate_logits: (T, E) router scores for T tokens over E experts.
      capacity: max tokens per expert held by this group.
    Returns:
      dispatch: (T, E, C) one-hot dispatch mask.
      combine:  (T, E, C) combine weights (gate prob on the dispatch slot).
    """
    T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # (T, E)
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = (jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
                * (onehot * keep)[..., None])              # (T, E, C)
    gate = jnp.sum(probs * onehot, axis=-1)                # (T,)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def moe_mlp(x, gate_w, w_in, w_out, axis_name: str,
            capacity_factor: float = 1.25, act=jax.nn.gelu):
    """MoE FFN to use INSIDE shard_map over ``axis_name``.

    Args:
      x: (T_local, D) this device's tokens (flatten batch x seq first).
      gate_w: (D, E_total) router weights (replicated).
      w_in: (E_local, D, Hd) this device's expert up-projections.
      w_out: (E_local, Hd, D) this device's expert down-projections.
    Returns (T_local, D).
    """
    n = jax.lax.axis_size(axis_name)
    T, D = x.shape
    E_local = w_in.shape[0]
    E = E_local * n
    capacity = max(1, int(capacity_factor * T / E))

    logits = x @ gate_w.astype(x.dtype)                     # (T, E)
    dispatch, combine = route_top1(logits, capacity)

    xf = x.astype(jnp.float32)
    # local expert buffers: (E, C, D)
    buf = jnp.einsum("td,tec->ecd", xf, dispatch)
    # exchange: each device keeps rows for ITS experts from every peer:
    # (E, C, D) -> (E_local, n*C, D)
    buf = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                             tiled=True)
    h = jnp.einsum("ecd,edh->ech", buf.astype(x.dtype),
                   w_in.astype(x.dtype))
    h = act(h)
    out = jnp.einsum("ech,ehd->ecd", h, w_out.astype(x.dtype))
    # route back: (E_local, n*C, D) -> (E, C, D)
    out = jax.lax.all_to_all(out.astype(jnp.float32), axis_name,
                             split_axis=1, concat_axis=0, tiled=True)
    y = jnp.einsum("ecd,tec->td", out, combine)
    return y.astype(x.dtype)


class MoEMlp:
    """Parameter container + init for :func:`moe_mlp` (kept framework-thin;
    flax integration wraps this in a Module when needed)."""

    def __init__(self, d_model: int, hidden: int, num_experts: int):
        self.d_model = d_model
        self.hidden = hidden
        self.num_experts = num_experts

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        s = 0.02
        return {
            "gate_w": jax.random.normal(
                k1, (self.d_model, self.num_experts), jnp.float32) * s,
            "w_in": jax.random.normal(
                k2, (self.num_experts, self.d_model, self.hidden),
                jnp.float32) * s,
            "w_out": jax.random.normal(
                k3, (self.num_experts, self.hidden, self.d_model),
                jnp.float32) * s,
        }


# -- serving: top-k, dropless, told which experts it holds ------------------

#: the flax collection a model's expert layers sow their routing counts
#: into, one int32 vector a layer; the sampling programs of
#: ``serving.generation.kv_cache`` read it
STATS_COLLECTION = "moe_stats"
#: the head of the routing counts vector :func:`held_experts_mlp`
#: returns, followed by one entry for each held expert (its picks)
STATS_FIELDS = ("tokens", "held", "zero", "absent", "touched")
#: rows a grouped-matmul step of :func:`held_experts_mlp` takes (a
#: multiple of the MXU's 128; fewer where there are fewer tokens)
TILE = 128


def route_topk(router_logits, bias, k: int, scale: float):
    """Top-``k`` routing over all of a model's router outputs.

    ``s = softmax(float32(router_logits))``; the choice is made on
    ``s + bias`` (the correction bias steers load and nothing else), the
    weights are ``scale * s`` at the chosen outputs, not renormalised.
    Returns ``(idx (T, k) int32, weights (T, k) float32)``."""
    s = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(s, idx, axis=-1) * scale
    return idx.astype(jnp.int32), weights


def route_sigmoid_topk(router_logits, k: int):
    """Top-``k`` routing by sigmoid scores, renormalised:
    ``s = sigmoid(float32(router_logits))``, the ``k`` largest ``s`` are
    chosen (no bias steers the choice), and the weights are ``s`` at the
    chosen outputs divided by their sum over the ``k``. Returns what
    :func:`route_topk` does, for :func:`held_experts_mlp`."""
    s = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    picked, idx = jax.lax.top_k(s, k)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weights


def held_experts_mlp(u, idx, weights, w_gate, w_up, w_down,
                     held_experts: Tuple[int, int], num_ffn_experts: int,
                     valid=None):
    """This chip's part of a routed-expert layer, dropless.

    Args:
      u: ``(T, D)`` tokens (flatten batch x positions first).
      idx, weights: ``(T, k)`` from :func:`route_topk`. Ids below
        ``num_ffn_experts`` are FFN experts, the rest identity experts.
      w_gate, w_up: ``(E_held, D, F)``; w_down: ``(E_held, F, D)``: the
        gated MLPs of the held experts, in id order.
      held_experts: ``(first, end)`` ids of the FFN experts held here.
      valid: ``(T,)`` bool, the live tokens (None: all). A pad token or
        a dead lane is routed nowhere, costs no matmul row and counts in
        no statistic.

    Returns ``(held_part, zero_part, stats)``: ``(T, D)`` float32 sums
    ``w_i E_i(u)`` over picks on held experts and ``(sum w_i) u`` over
    picks on identity experts, and the int32 routing counts
    (:data:`STATS_FIELDS`, then picks of each held expert).

    The picks on held experts are sorted by expert; expert ``e`` then
    owns rows ``[start_e, start_e + count_e)`` of the sorted list and
    walks them :data:`TILE` at a time in a loop whose trip count is
    ``ceil(count_e / TILE)``: no capacity, no dropped token, and an
    expert nobody picked runs no step and reads no weight."""
    T, D = u.shape
    k = idx.shape[1]
    first, end = held_experts
    n_held = end - first
    if len(w_gate) != n_held:
        raise ValueError(
            f"held_experts={held_experts} names {n_held} experts, the "
            f"weights hold {len(w_gate)}")
    if valid is None:
        valid = jnp.ones((T,), jnp.bool_)
    live = valid[:, None]
    on_zero = (idx >= num_ffn_experts) & live
    on_held = (idx >= first) & (idx < end) & live

    with jax.named_scope("sort"):
        # one key a pick: the held expert's local id, or n_held (sorts
        # last) for a pick that costs no row here
        key = jnp.where(on_held, idx - first, n_held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        tile = min(TILE, T)
        # a tile may start at the last pick: pad so that it never clamps
        row_token = jnp.pad(order // k, (0, tile)).astype(jnp.int32)
        row_weight = jnp.pad(weights.reshape(-1)[order], (0, tile))
        counts = jnp.sum(
            key[:, None] == jnp.arange(n_held)[None, :], axis=0,
            dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts

    with jax.named_scope("experts"):
        out = jnp.zeros((T, D), jnp.float32)
        for e in range(n_held):
            start, count = starts[e], counts[e]

            def step(i, out, e=e, start=start, count=count):
                pos = start + i * tile
                rows = jax.lax.dynamic_slice(row_token, (pos,), (tile,))
                w = jax.lax.dynamic_slice(row_weight, (pos,), (tile,))
                mine = pos + jnp.arange(tile) < start + count
                x = u[rows]
                h = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
                y = (h @ w_down[e]).astype(jnp.float32)
                # rows past the group's end belong to the next expert:
                # they add zero here and are computed there
                return out.at[rows].add(
                    y * jnp.where(mine, w, 0.0)[:, None])

            out = jax.lax.fori_loop(0, (count + tile - 1) // tile, step,
                                    out)

    with jax.named_scope("identity"):
        zero_w = jnp.sum(jnp.where(on_zero, weights, 0.0), axis=-1)
        zero_part = zero_w[:, None] * u.astype(jnp.float32)

    n_tokens = jnp.sum(valid, dtype=jnp.int32)
    n_held_picks = jnp.sum(counts)
    n_zero = jnp.sum(on_zero, dtype=jnp.int32)
    stats = jnp.concatenate([
        jnp.stack([n_tokens, n_held_picks, n_zero,
                   n_tokens * k - n_held_picks - n_zero,
                   jnp.sum(counts > 0, dtype=jnp.int32)]),
        counts]).astype(jnp.int32)
    return out, zero_part, stats
